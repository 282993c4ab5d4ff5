"""Join algorithms (paper §3.1.1, Figure 4).

Two communication patterns:
  * shuffle join — both inputs hash-partitioned by key; each reducer joins
    corresponding partitions with a *local* algorithm chosen from runtime
    statistics (build hash over the small side; symmetric if both large);
  * map (broadcast) join — the small input is broadcast to all nodes and
    joined against each partition of the large input, skipping the shuffle.

PDE selects between them at run time from observed input sizes (§3.1.1); the
co-partitioned case (§3.4) degenerates to a zip of corresponding partitions.

The local algorithm is sort/searchsorted-based (vectorized "hash join" —
numpy has no cheap per-row hash table; sorted probe is its vector analogue,
and on TPU the probe compiles to gathers).  `_match_pairs` is the
interpreted oracle; `CompiledProbe` lowers the same sort/searchsorted/expand
pipeline into two cached jitted XLA programs (DESIGN.md §11) with
power-of-two padding so re-traces stay bounded — the reduce-side router
(physical.ReduceRunner) picks between them per bucket group.

String join keys never materialize strings: both sides' dictionary codes are
remapped into the union of the two (small) dictionaries and the probe runs
on int codes — the join-side half of the dictionary-preserving exchange.
"""

from __future__ import annotations

import threading
from typing import Callable, Dict, Optional, Tuple

import numpy as np

from . import tracing
from .batch import PartitionBatch, merge_string_dicts
from .expr import ColumnVal, next_pow2 as _next_pow2

Matcher = Callable[[np.ndarray, np.ndarray], Tuple[np.ndarray, np.ndarray]]


def _match_pairs(lkeys: np.ndarray, rkeys: np.ndarray
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """Equi-join row index pairs (vectorized, duplicate-correct).

    Sorts the build side once, probes with searchsorted, expands duplicate
    ranges with repeat arithmetic.  The semantic oracle for CompiledProbe:
    both must emit the same pairs in the same order."""
    order = np.argsort(rkeys, kind="stable")
    rs = rkeys[order]
    lo = np.searchsorted(rs, lkeys, side="left")
    hi = np.searchsorted(rs, lkeys, side="right")
    counts = hi - lo
    lidx = np.repeat(np.arange(len(lkeys)), counts)
    if len(lidx) == 0:
        return lidx, lidx.copy()
    # offsets within each left row's match range
    starts = np.repeat(lo, counts)
    cum = np.concatenate([[0], np.cumsum(counts)[:-1]])
    within = np.arange(len(lidx)) - np.repeat(cum, counts)
    ridx = order[starts + within]
    return lidx, ridx


# ---------------------------------------------------------------------------
# Compiled probe: the sort/searchsorted join lowered through jax.jit.
#
# The match is data-dependent in its OUTPUT size only, so it splits into two
# statically-shaped programs: phase 1 (sort + bound search + per-row match
# counts) and phase 2 (pair expansion into a padded output).  Inputs and the
# pair count are padded to powers of two so each program re-traces O(log n)
# times per dtype, mirroring the _PLAN_CACHE discipline of expr.compile_expr.
# ---------------------------------------------------------------------------


class CompiledProbe:
    """`_match_pairs` compiled: same pairs, same order, via two cached
    jitted XLA programs.  Instances are cheap; the jitted functions are
    shared process-wide."""

    _fns: Dict[str, Tuple] = {}
    _lock = threading.Lock()

    @classmethod
    def _get_fns(cls) -> Tuple:
        with cls._lock:
            fns = cls._fns.get("fns")
            if fns is not None:
                return fns
            import functools

            import jax
            import jax.numpy as jnp

            @jax.jit
            def phase1(lk, rk, n_l, n_r):
                order = jnp.argsort(rk, stable=True)
                rs = rk[order]
                lo = jnp.searchsorted(rs, lk, side="left")
                # rk padding sorts after every real key (max-value sentinel,
                # appended, stable sort) — clamping `hi` to n_r excludes it
                # even when real keys equal the sentinel value
                hi = jnp.minimum(jnp.searchsorted(rs, lk, side="right"), n_r)
                valid = jnp.arange(lk.shape[0]) < n_l
                counts = jnp.where(valid, jnp.maximum(hi - lo, 0), 0)
                return order, lo, counts

            @functools.partial(jax.jit, static_argnames=("total_p",))
            def phase2(order, lo, counts, total_p):
                n = lo.shape[0]
                lidx = jnp.repeat(jnp.arange(n), counts,
                                  total_repeat_length=total_p)
                starts = jnp.repeat(lo, counts, total_repeat_length=total_p)
                cum = jnp.concatenate(
                    [jnp.zeros(1, counts.dtype), jnp.cumsum(counts)[:-1]])
                within = (jnp.arange(total_p)
                          - jnp.repeat(cum, counts,
                                       total_repeat_length=total_p))
                gather = jnp.clip(starts + within, 0, order.shape[0] - 1)
                return lidx, order[gather]

            fns = (phase1, phase2)
            cls._fns["fns"] = fns
            return fns

    def __call__(self, lkeys: np.ndarray, rkeys: np.ndarray
                 ) -> Tuple[np.ndarray, np.ndarray]:
        n_l, n_r = len(lkeys), len(rkeys)
        if n_l == 0 or n_r == 0:
            empty = np.zeros(0, np.int64)
            return empty, empty.copy()
        from .expr import _x64
        phase1, phase2 = self._get_fns()
        dt = np.result_type(lkeys.dtype, rkeys.dtype)
        if dt.kind in ("U", "S", "O", "b"):
            # bool has no iinfo sentinel either — callers fall back to the
            # numpy oracle on TypeError
            raise TypeError("CompiledProbe takes numeric/code keys")
        if dt.kind == "f" and (np.isnan(lkeys).any() or np.isnan(rkeys).any()):
            # NaN sorts AFTER the +inf pad sentinel, breaking the invariant
            # that padding occupies the sorted tail (the hi-clamp would
            # admit pad rows) — same hazard code_space() guards against for
            # NaN dictionaries.  Callers fall back to the numpy oracle.
            raise TypeError("CompiledProbe cannot pad NaN float keys")
        sentinel = (np.array(np.inf, dt) if dt.kind == "f"
                    else np.array(np.iinfo(dt).max, dt))
        lp, rp = _next_pow2(n_l), _next_pow2(n_r)
        lk = np.full(lp, sentinel, dt)
        lk[:n_l] = lkeys
        rk = np.full(rp, sentinel, dt)
        rk[:n_r] = rkeys
        with tracing.device("probe", lk, rk) as sp, _x64():
            order, lo, counts = phase1(lk, rk, n_l, n_r)
            counts = sp.fetch(counts)
            total = int(counts.sum())
            if total == 0:
                empty = np.zeros(0, np.int64)
                return empty, empty.copy()
            sp.put(counts)
            lidx, ridx = phase2(order, lo, counts, _next_pow2(total))
            return (sp.fetch(lidx[:total]).astype(np.int64, copy=False),
                    sp.fetch(ridx[:total]).astype(np.int64, copy=False))


_COMPILED_PROBE = CompiledProbe()


def compile_probe() -> CompiledProbe:
    """The process-wide compiled matcher (jitted programs are shared)."""
    return _COMPILED_PROBE


# ---------------------------------------------------------------------------
# Key extraction — decode-free for dictionary-coded strings
# ---------------------------------------------------------------------------


def _key_arrays(lbatch: PartitionBatch, rbatch: PartitionBatch,
                lkey: str, rkey: str) -> Tuple[np.ndarray, np.ndarray]:
    """Join keys comparable across the two sides.  String keys stay codes:
    both sides remap into the union of their (small) dictionaries, so no row
    ever materializes a string."""
    import time

    from .batch import EXCHANGE_TIMERS
    t0 = time.perf_counter()
    lv, rv = lbatch.col(lkey), rbatch.col(rkey)
    if lv.is_string and rv.is_string:
        _, (lmap, rmap) = merge_string_dicts([lv.sdict, rv.sdict])
        out = (lmap.astype(np.int64)[np.asarray(lv.arr)],
               rmap.astype(np.int64)[np.asarray(rv.arr)])
        EXCHANGE_TIMERS["hash"] += time.perf_counter() - t0
        return out
    lk = lv.decoded() if lv.is_string else np.asarray(lv.arr)
    rk = rv.decoded() if rv.is_string else np.asarray(rv.arr)
    EXCHANGE_TIMERS["hash"] += time.perf_counter() - t0
    return lk, rk


def _key_array(batch: PartitionBatch, key: str) -> np.ndarray:
    """Single-side key materialization (legacy helper, kept for callers
    outside the two-sided join path)."""
    v = batch.col(key)
    return v.decoded() if v.is_string else np.asarray(v.arr)


def _combine(lbatch: PartitionBatch, lidx: np.ndarray,
             rbatch: PartitionBatch, ridx: np.ndarray,
             rsuffix: str = "_r") -> PartitionBatch:
    out: Dict[str, ColumnVal] = {}
    for n, v in lbatch.cols.items():
        out[n] = ColumnVal(np.asarray(v.arr)[lidx], v.sdict, v.sorted_dict)
    for n, v in rbatch.cols.items():
        name = n if n not in out else n + rsuffix
        out[name] = ColumnVal(np.asarray(v.arr)[ridx], v.sdict, v.sorted_dict)
    return PartitionBatch(out)


def _null_pad_right(out: PartitionBatch, lbatch: PartitionBatch,
                    rbatch: PartitionBatch, n_match: int,
                    n_miss: int) -> PartitionBatch:
    """NULL emulation for the unmatched tail of a left join: right-side
    numeric columns zero, right-side STRING columns get the reserved null
    code — the empty string joins the (sorted) dictionary and miss rows
    remap to it, matching the zero-partition pad_right path.  Without this,
    string miss rows silently kept whatever row the pad gather hit."""
    if n_miss == 0:
        return out
    for n, v in rbatch.cols.items():
        name = n if n not in lbatch.cols else n + "_r"
        cv = out.cols[name]
        if cv.is_string:
            base = cv.sdict if cv.sdict.size else np.zeros(0, np.str_)
            nd = np.unique(np.concatenate(
                [base, np.array([""], dtype=base.dtype if base.size
                                else np.str_)]))
            remap = np.searchsorted(nd, base).astype(np.int32)
            null_code = np.int32(np.searchsorted(nd, ""))
            codes = np.empty(n_match + n_miss, np.int32)
            codes[:n_match] = remap[np.asarray(cv.arr)[:n_match]]
            codes[n_match:] = null_code
            out.cols[name] = ColumnVal(codes, nd, True)
            continue
        arr = np.asarray(cv.arr).copy()
        if np.issubdtype(arr.dtype, np.number):
            arr[n_match:] = 0
        elif arr.dtype.kind in ("U", "S"):
            arr[n_match:] = ""   # raw strings (legacy decoded exchange)
        out.cols[name] = ColumnVal(arr, cv.sdict, cv.sorted_dict)
    return out


def join_local(lbatch: PartitionBatch, rbatch: PartitionBatch,
               lkey: str, rkey: str, how: str = "inner",
               matcher: Optional[Matcher] = None) -> PartitionBatch:
    """Local join of two co-located partitions.

    Mirrors the paper's reducer policy: probe from the larger side into the
    sorted smaller side (building over the small input); the symmetric case
    falls out naturally since sorted probe is order-symmetric.  `matcher`
    selects the pair-matching implementation (`_match_pairs` oracle by
    default, `CompiledProbe` when the reduce router picks the jit route)."""
    match = matcher if matcher is not None else _match_pairs
    lk, rk = _key_arrays(lbatch, rbatch, lkey, rkey)
    if how == "inner":
        if len(rk) <= len(lk):
            lidx, ridx = match(lk, rk)
        else:
            ridx, lidx = match(rk, lk)
        return _combine(lbatch, lidx, rbatch, ridx)
    if how == "left":
        lidx, ridx = match(lk, rk)
        matched = np.zeros(len(lk), bool)
        matched[lidx] = True
        miss = np.flatnonzero(~matched)
        if len(rk) == 0:
            # no right rows at all: emit left rows + null-padded right cols
            out = _combine(lbatch, miss,
                           PartitionBatch.empty_like(rbatch),
                           np.zeros(0, np.int64))
            for n, v in rbatch.cols.items():
                name = n if n not in lbatch.cols else n + "_r"
                cv = out.cols[name]
                if cv.is_string:
                    out.cols[name] = ColumnVal(
                        np.zeros(len(miss), np.int32),
                        np.array([""], np.str_), True)
                else:
                    out.cols[name] = ColumnVal(
                        np.zeros(len(miss), np.asarray(v.arr).dtype))
            return out
        all_l = np.concatenate([lidx, miss])
        # right side for misses: gather row 0, then rewrite to NULL
        # emulation (zeros / reserved null code) below
        pad = np.zeros(len(miss), np.int64)
        all_r = np.concatenate([ridx, pad])
        out = _combine(lbatch, all_l, rbatch, all_r)
        return _null_pad_right(out, lbatch, rbatch, len(lidx), len(miss))
    raise NotImplementedError(how)


def broadcast_join(part: PartitionBatch, small: PartitionBatch,
                   part_key: str, small_key: str,
                   how: str = "inner",
                   matcher: Optional[Matcher] = None) -> PartitionBatch:
    """Map join: `small` is the broadcast table (already collected to the
    master and shipped to every task)."""
    return join_local(part, small, part_key, small_key, how, matcher=matcher)
