"""The work counts behind the roofline shares, and the peak table, on
hand-computed shapes."""

import pytest

from bench import common
from bench.work import LANE_BYTES, scan_bytes, train_bytes


def test_scan_bytes_counts_every_row_of_each_column_once():
    rows = {"rankings": 18_000_000, "uservisits": 10_000_000}
    # pageRank and pageURL of rankings: 2 x 18M x 4 B
    assert scan_bytes(["rankings.pageRank", "rankings.pageURL"],
                      rows) == 144_000_000
    # a column named twice counts once; two tables add up
    assert scan_bytes(["uservisits.visitDate", "uservisits.visitDate",
                       "rankings.pageRank"], rows) == (40_000_000
                                                       + 72_000_000)
    assert LANE_BYTES == 4


def test_train_bytes():
    # 5M selected rows x (10 features + label) x 4 B
    assert train_bytes(5_000_000, 11) == 220_000_000


def test_peaks_known_and_unknown_kind():
    p = common.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        common.peaks("cpu")
