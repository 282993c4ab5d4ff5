"""`reduce_merges_per_query.mesh4` on synthetic `shark.reduce` records: a
window whose group-bys each merge 64 buckets, one whose group-bys finalise
each device's states in place (`disjoint`), and windows with nothing to
read.  The recorder and the window come from test_mesh_metrics."""

import pytest

from test_mesh_metrics import _read, _run, _span, records  # noqa: F401

METRIC = "reduce_merges_per_query.mesh4"


def _round(recs, t, group_by_routes):
    """One round of the cell's three templates, starting at t: the
    colscan's single-bucket merge, then two group-bys."""
    _span(recs, "reduce", t, t + 0.01, op="merge", route="numpy",
          rows_in=16, rows_out=1)
    for k in range(2):
        for route in group_by_routes:
            _span(recs, "reduce", t + k, t + k + 0.01, op="merge",
                  route=route, rows_in=100, rows_out=100)


def test_merges_of_the_bucketed_reduce(records):  # noqa: F811
    # 64 bucket merges per group-by; a merge before the window and a
    # join inside it count for nothing
    _round(records, 101.0, ["jit"] * 63 + ["numpy"])
    _span(records, "reduce", 99.0, 99.5, op="merge", route="jit")
    _span(records, "reduce", 101.5, 101.6, op="join", route="jit")
    got = _read(METRIC, _run(done_at=[101.5, 102.5, 103.5]))
    assert got == pytest.approx((1 + 64 + 64) / 3)


def test_disjoint_finalisations_are_not_merges(records):  # noqa: F811
    _round(records, 101.0, ["disjoint"] * 4)
    _round(records, 105.0, ["disjoint"] * 4)
    got = _read(METRIC, _run(done_at=[101.5, 102.5, 103.5, 105.5, 106.5,
                                      107.5]))
    assert got == pytest.approx(2 / 6)


def test_only_disjoint_reads_zero(records):  # noqa: F811
    _span(records, "reduce", 101.0, 101.1, op="merge", route="disjoint")
    assert _read(METRIC, _run(done_at=[102.0])) == 0.0


def test_reads_nothing_without_merge_spans_or_queries(records):  # noqa: F811
    assert _read(METRIC, _run(done_at=[102.0])) is None
    _span(records, "query", 101.0, 101.5)
    _span(records, "reduce", 101.0, 101.1, op="join", route="jit")
    assert _read(METRIC, _run(done_at=[102.0])) is None
    _span(records, "reduce", 101.0, 101.1, op="merge", route="jit")
    assert _read(METRIC, _run(done_at=[])) is None
    assert _read(METRIC, _run(done_at=[102.0])) == 1.0
