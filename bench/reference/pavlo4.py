"""Plain reference for the four-node Pavlo cell, from the generator's
indices and numeric columns, in float64 numpy.

`range_agg` and `group_country` are bench/reference/pavlo.py's.  The
Pavlo aggregation task, `agg_sourceip` (SUM(adRevenue) GROUP BY
sourceIP), has no literal, so its truth is one bincount of adRevenue over
the source-IP indices, computed once: the answer's key set must equal the
IPs the table holds, and each sum is compared by its relative gap under
the same limit as the scan cell's.
"""

from __future__ import annotations

import ml_dtypes
import numpy as np

from bench.reference import pavlo
from bench.reference.pavlo import FLOAT_REL_GAP_LIMIT, _rel_gap

# an IP holds at most 15 of "0-9." and packs into one uint64 at 4 bits a
# character (a 16th nibble of 0 pads it); NUL < "." < "0" < ... < "9", so
# packed order is string order
_IP_CHARS = np.zeros(256, np.uint8)
_IP_CHARS[ord(".")] = 1
_IP_CHARS[ord("0"):ord("9") + 1] = np.arange(2, 12, dtype=np.uint8)
_IP_WIDTH = 15


def pack_ips(ips) -> np.ndarray:
    """Dotted quads as uint64, order-preserving; raises on anything
    longer or made of other characters."""
    u = np.asarray(ips)
    if u.dtype.kind != "U":
        u = u.astype(str)
    if not len(u):
        return np.zeros(0, np.uint64)
    width = u.dtype.itemsize // 4
    chars = np.ascontiguousarray(u).view(np.uint32).reshape(len(u), width)
    if width > _IP_WIDTH:
        if np.any(chars[:, _IP_WIDTH:]):
            raise ValueError("not a dotted quad")
        chars, width = chars[:, :_IP_WIDTH], _IP_WIDTH
    codes = np.zeros((len(u), _IP_WIDTH + 1), np.uint8)
    codes[:, :width] = _IP_CHARS[np.minimum(chars, 255)]
    if np.any((codes[:, :width] == 0) & (chars != 0)):
        raise ValueError("not a dotted quad")
    nibbles = (codes[:, 0::2] << 4) | codes[:, 1::2]
    return np.ascontiguousarray(nibbles).view(">u8").ravel().astype(np.uint64)


def bf16_group_sums(groups: np.ndarray, values: np.ndarray,
                    num_groups: int) -> np.ndarray:
    """Per-group sums with every operand and every partial sum rounded to
    bfloat16, each group summed in row order, all groups at once."""
    bf16 = ml_dtypes.bfloat16
    order = np.argsort(groups, kind="stable")
    g = groups[order]
    v = values[order].astype(bf16)
    start = np.searchsorted(g, g, side="left")
    rank = np.arange(len(g)) - start
    acc = np.zeros(num_groups, bf16)
    for r in range(int(rank.max()) + 1 if len(g) else 0):
        at = rank == r
        acc[g[at]] = (acc[g[at]] + v[at]).astype(bf16)
    return acc.astype(np.float64)


class Reference(pavlo.Reference):
    """pavlo.Reference's tables for the templates this cell sends, and the
    aggregation task's."""

    def __init__(self, truth: dict):
        self.t = truth
        days = truth["visitDate"].astype(np.int64)
        self.day0 = int(days.min())
        d = days - self.day0
        self.day_cnt = np.bincount(d)
        self.day_rev = np.bincount(d, weights=truth["adRevenue"])
        nc = len(truth["country_vocab"])
        self.c_cnt = np.bincount(truth["country"], minlength=nc)
        self.c_rev = np.bincount(truth["country"], weights=truth["adRevenue"],
                                 minlength=nc)
        self.ip_keys = pack_ips(truth["ip_vocab"])
        self.ip_rev = np.bincount(truth["ip"], weights=truth["adRevenue"],
                                  minlength=len(self.ip_keys))
        self.ip_control = None
        self.ip_seen = None     # (keys of the last answer, their order)

    def control(self, check: str, p):
        if check == "agg_sourceip":
            t = self.t
            if self.ip_control is None:     # the same for every answer
                self.ip_control = {"sourceIP": t["ip_vocab"],
                                   "rev": bf16_group_sums(
                                       t["ip"], t["adRevenue"],
                                       len(t["ip_vocab"]))}
            return self.ip_control
        return super().control(check, p)

    def compare(self, check: str, p, got: dict):
        if check != "agg_sourceip":
            return super().compare(check, p, got)
        ips = np.asarray(got["sourceIP"])
        if self.ip_seen is not None and np.array_equal(ips, self.ip_seen[0]):
            o = self.ip_seen[1]     # the keys of an answer already checked
        else:
            try:
                keys = pack_ips(ips)
            except ValueError:
                return False, 0.0
            o = np.argsort(keys)
            if not np.array_equal(keys[o], self.ip_keys):
                return False, 0.0
            self.ip_seen = (ips, o)
        return True, _rel_gap(np.asarray(got["rev"])[o], self.ip_rev)


def check(truth: dict, answers, control: bool = False) -> dict:
    """The compared numbers of a run, each with its limit; as
    bench/reference/pavlo.py's `check`."""
    ref = Reference(truth)
    unanswered = wrong = 0
    gap = 0.0
    for kind, p, got in answers:
        if got is None:
            unanswered += 1
            continue
        if control:
            got = ref.control(kind, p) or got
        ok, g = ref.compare(kind, p, got)
        wrong += int(not ok)
        gap = max(gap, g)
    return {"unanswered": {"value": unanswered, "limit": 0},
            "wrong_exact": {"value": wrong, "limit": 0},
            "float_rel_gap": {"value": gap, "limit": FLOAT_REL_GAP_LIMIT}}
