"""Hash aggregation (paper §6.2.2, §6.3.1).

Like Shark (and Hive), aggregations run in two phases: task-local partial
aggregation on each partition, then a shuffle of the partial states by group
key and a final merge on the reduce side.  Spark's hash-based distributed
aggregation (no sort before shuffle, §7.1) is reproduced: grouping is
hash/unique-based, never a global sort.

Integer aggregates stay integer end to end: SUM/MIN/MAX over integer
columns accumulate in int64 (value-exact above 2^53, where a float64
round-trip silently loses precision); float aggregates accumulate in
float64.  String group keys are dictionary codes throughout — with the
dictionary-preserving exchange (DESIGN.md §11) the reduce side groups on
codes into the unified dictionary and never materializes strings.

`partial_aggregate` / `merge_aggregate` are the interpreted (numpy) oracle.
`CompiledMerge` lowers the reduce-side merge into ONE jitted segmented-
reduce program over all aggregate states (cached per state signature,
power-of-two padded so re-traces stay bounded), mirroring what
expr.compile_expr does for scan-side expressions; the reduce router
(physical.ReduceRunner) picks between them per reduce task.
"""

from __future__ import annotations

import threading
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from . import tracing
from .batch import PartitionBatch
from .expr import (ColumnVal, Evaluator, ExprCompileError, evaluate,
                   next_pow2 as _next_pow2)
from .plan import AggFunc, AggSpec


def group_indices(keys: Sequence[np.ndarray]) -> Tuple[np.ndarray, np.ndarray]:
    """Group rows by composite key.  Returns (representative row indices of
    each group, inverse mapping row -> group id).  Hash-based (np.unique),
    not sort-order dependent."""
    n = len(keys[0]) if keys else 0
    if not keys:
        return np.zeros(1, np.int64), np.zeros(n, np.int64)
    if len(keys) == 1:
        _, first, inverse = np.unique(keys[0], return_index=True,
                                      return_inverse=True)
        return first, inverse
    # composite: unique over a void view of stacked columns
    cols = [np.asarray(k) for k in keys]
    rec = np.empty(n, dtype=[(f"k{i}", c.dtype) for i, c in enumerate(cols)])
    for i, c in enumerate(cols):
        rec[f"k{i}"] = c
    _, first, inverse = np.unique(rec, return_index=True, return_inverse=True)
    return first, inverse


# -- integer-exact segmented reductions (the numpy oracle) -------------------

INT64_MIN_IDENT = np.iinfo(np.int64).max   # MIN identity for empty groups
INT64_MAX_IDENT = np.iinfo(np.int64).min


def seg_sum(inverse: np.ndarray, val: np.ndarray,
            num_groups: int) -> np.ndarray:
    """Per-group sum; int64 accumulation for integer inputs (bincount's
    float64 weights would round above 2^53), float64 otherwise."""
    if np.issubdtype(val.dtype, np.integer) or val.dtype.kind == "b":
        acc = np.zeros(num_groups, np.int64)
        np.add.at(acc, inverse, val.astype(np.int64))
        return acc
    return np.bincount(inverse, weights=val.astype(np.float64),
                       minlength=num_groups)


def seg_minmax(inverse: np.ndarray, val: np.ndarray, num_groups: int,
               is_min: bool) -> np.ndarray:
    """Per-group min/max with dtype-preserving accumulators: int64 with
    iinfo sentinels for integer inputs, float64 with ±inf otherwise."""
    if np.issubdtype(val.dtype, np.integer):
        fill = INT64_MIN_IDENT if is_min else INT64_MAX_IDENT
        acc = np.full(num_groups, fill, np.int64)
        v = val.astype(np.int64)
    else:
        acc = np.full(num_groups, np.inf if is_min else -np.inf, np.float64)
        v = val.astype(np.float64)
    (np.minimum if is_min else np.maximum).at(acc, inverse, v)
    return acc


def combine_colscan_stats(stats: Sequence[Sequence[float]]
                          ) -> Tuple[float, float, float, float]:
    """Combine per-chunk colscan [count, sum, min, max] states into one.

    Count/min/max combine exactly; the sum stays in the same float64
    rounding class as a single-pass accumulation (DESIGN.md §14: the
    double-buffered chunked colscan must be a semantic no-op)."""
    cnt = 0.0
    s = np.float64(0.0)
    mn = np.inf
    mx = -np.inf
    for st in stats:
        cnt += float(st[0])
        s = s + np.float64(st[1])
        if float(st[0]) > 0:
            mn = min(mn, float(st[2]))
            mx = max(mx, float(st[3]))
    return cnt, float(s), mn, mx


# State columns per aggregate: AVG keeps (sum, count); COUNT_DISTINCT defers
# to the reduce side (map side emits distinct (group, value) pairs).

def _state_cols(spec: AggSpec) -> List[str]:
    if spec.func == AggFunc.AVG:
        return [f"__{spec.out_name}__sum", f"__{spec.out_name}__cnt"]
    if spec.func == AggFunc.COUNT:
        return [f"__{spec.out_name}__cnt"]
    if spec.func == AggFunc.COUNT_DISTINCT:
        return [f"__{spec.out_name}__val"]
    return [f"__{spec.out_name}__acc"]


def _group_key_cols(batch: PartitionBatch, group_cols: Sequence[str],
                    first: np.ndarray) -> Dict[str, ColumnVal]:
    """Representative group-key columns (codes stay codes) — shared by
    every merge/partial output assembly."""
    out: Dict[str, ColumnVal] = {}
    for g in group_cols:
        v = batch.col(g)
        out[g] = ColumnVal(np.asarray(v.arr)[first], v.sdict, v.sorted_dict)
    return out


def _partial_states(spec: AggSpec, inverse: np.ndarray,
                    val: Optional[np.ndarray], num_groups: int,
                    out: Dict[str, ColumnVal]) -> None:
    """One spec's partial state columns for a pre-grouped partition."""
    if spec.func == AggFunc.COUNT:
        acc = np.bincount(inverse, minlength=num_groups).astype(np.int64)
        out[_state_cols(spec)[0]] = ColumnVal(acc)
    elif spec.func == AggFunc.SUM:
        out[_state_cols(spec)[0]] = ColumnVal(seg_sum(inverse, val,
                                                      num_groups))
    elif spec.func == AggFunc.AVG:
        s = np.bincount(inverse, weights=val.astype(np.float64),
                        minlength=num_groups)
        c = np.bincount(inverse, minlength=num_groups).astype(np.int64)
        sc, cc = _state_cols(spec)
        out[sc] = ColumnVal(s)
        out[cc] = ColumnVal(c)
    elif spec.func in (AggFunc.MIN, AggFunc.MAX):
        out[_state_cols(spec)[0]] = ColumnVal(
            seg_minmax(inverse, val, num_groups,
                       spec.func == AggFunc.MIN))
    else:
        raise NotImplementedError(spec.func)


def partial_aggregate(batch: PartitionBatch, group_cols: Sequence[str],
                      aggs: Sequence[AggSpec]) -> PartitionBatch:
    """Task-local aggregation: one output row per group in this partition."""
    n = batch.num_rows
    keys = [np.asarray(batch.col(g).arr) for g in group_cols]
    # string group keys: group locally on codes (cheap), the reduce side
    # unifies dictionaries — representative rows stay codes end to end.
    first, inverse = group_indices(keys) if group_cols else \
        (np.zeros(1, np.int64), np.zeros(n, np.int64))
    num_groups = len(first)

    out: Dict[str, ColumnVal] = _group_key_cols(batch, group_cols, first)

    distinct_specs = [a for a in aggs if a.func == AggFunc.COUNT_DISTINCT]
    plain_specs = [a for a in aggs if a.func != AggFunc.COUNT_DISTINCT]

    for spec in plain_specs:
        if spec.arg is not None:
            ctx = {name: batch.col(name) for name in batch.names()}
            val = np.asarray(evaluate(spec.arg, ctx).arr)
        else:
            val = None
        _partial_states(spec, inverse, val, num_groups, out)

    if distinct_specs:
        # Exact distinct: partial rows become per-(group, value) instead of
        # per-group.  Plain aggregates stay correct because their states are
        # additive across the finer grouping; the reduce side re-merges by
        # group and counts unique (group, value) pairs.
        if len(distinct_specs) > 1:
            raise NotImplementedError("multiple COUNT(DISTINCT) columns")
        spec = distinct_specs[0]
        ctx = {name: batch.col(name) for name in batch.names()}
        val = evaluate(spec.arg, ctx)
        pair_keys = keys + [np.asarray(val.arr)]
        pfirst, pinverse = group_indices(pair_keys)
        num_pairs = len(pfirst)
        out = _group_key_cols(batch, group_cols, pfirst)
        out[_state_cols(spec)[0]] = ColumnVal(
            np.asarray(val.arr)[pfirst], val.sdict, val.sorted_dict)
        for pspec in plain_specs:
            if pspec.arg is not None:
                pval = np.asarray(evaluate(pspec.arg, ctx).arr)
            else:
                pval = None
            _partial_states(pspec, pinverse, pval, num_pairs, out)

    return PartitionBatch(out)


def sorted_partial_aggregate(key: ColumnVal, values: Optional[np.ndarray],
                             key_col: str, aggs: Sequence[AggSpec]
                             ) -> PartitionBatch:
    """`partial_aggregate` of rows sorted by their one group key, for
    COUNT, SUM and AVG of `values`: each key's rows are one run, so a
    group's states are reductions over its run and no sort or hash of the
    rows is needed.  Groups come out in key order, as from
    `partial_aggregate`, with the same dtypes; float sums are float64
    additions of the same values, in another order."""
    k = np.asarray(key.arr)
    n = k.shape[0]
    starts = np.flatnonzero(np.concatenate([[n > 0], k[1:] != k[:-1]]))
    cnt = np.diff(np.append(starts, n)).astype(np.int64)
    out: Dict[str, ColumnVal] = {
        key_col: ColumnVal(k[starts], key.sdict, key.sorted_dict)}

    def run_sums(dtype) -> np.ndarray:
        if not n:
            return np.zeros(0, dtype)
        return np.add.reduceat(values.astype(dtype), starts)

    for spec in aggs:
        cols = _state_cols(spec)
        if spec.func == AggFunc.COUNT:
            out[cols[0]] = ColumnVal(cnt)
        elif spec.func == AggFunc.SUM:
            exact = (np.issubdtype(values.dtype, np.integer)
                     or values.dtype.kind == "b")
            out[cols[0]] = ColumnVal(run_sums(np.int64 if exact
                                              else np.float64))
        elif spec.func == AggFunc.AVG:
            out[cols[0]] = ColumnVal(run_sums(np.float64))
            out[cols[1]] = ColumnVal(cnt)
        else:
            raise NotImplementedError(spec.func)
    return PartitionBatch(out)


def merge_aggregate(batch: PartitionBatch, group_cols: Sequence[str],
                    aggs: Sequence[AggSpec]) -> PartitionBatch:
    """Reduce-side final merge of partial states (one row per group) — the
    interpreted oracle for CompiledMerge."""
    keys = [np.asarray(batch.col(g).arr) for g in group_cols]
    n = batch.num_rows
    first, inverse = group_indices(keys) if group_cols else \
        (np.zeros(1, np.int64), np.zeros(n, np.int64))
    num_groups = len(first)

    out: Dict[str, ColumnVal] = _group_key_cols(batch, group_cols, first)

    for spec in aggs:
        if spec.func == AggFunc.COUNT_DISTINCT:
            vc = batch.col(_state_cols(spec)[0])
            pair_keys = keys + [np.asarray(vc.arr)]
            _, pair_inv = group_indices(pair_keys)
            # count unique (group, value) pairs per group
            uniq_pairs, pair_first = np.unique(pair_inv, return_index=True)
            grp_of_pair = inverse[pair_first]
            cnt = np.bincount(grp_of_pair, minlength=num_groups).astype(np.int64)
            out[spec.out_name] = ColumnVal(cnt)
            continue
        cols = _state_cols(spec)
        if spec.func == AggFunc.COUNT:
            v = np.asarray(batch.col(cols[0]).arr)
            out[spec.out_name] = ColumnVal(
                seg_sum(inverse, v, num_groups).astype(np.int64))
        elif spec.func == AggFunc.SUM:
            v = np.asarray(batch.col(cols[0]).arr)
            out[spec.out_name] = ColumnVal(seg_sum(inverse, v, num_groups))
        elif spec.func == AggFunc.AVG:
            s = np.bincount(inverse,
                            weights=np.asarray(batch.col(cols[0]).arr,
                                               dtype=np.float64),
                            minlength=num_groups)
            c = np.bincount(inverse,
                            weights=np.asarray(batch.col(cols[1]).arr,
                                               dtype=np.float64),
                            minlength=num_groups)
            out[spec.out_name] = ColumnVal(s / np.maximum(c, 1))
        elif spec.func in (AggFunc.MIN, AggFunc.MAX):
            v = np.asarray(batch.col(cols[0]).arr)
            out[spec.out_name] = ColumnVal(
                seg_minmax(inverse, v, num_groups,
                           spec.func == AggFunc.MIN))
        else:
            raise NotImplementedError(spec.func)
    return PartitionBatch(out)


# ---------------------------------------------------------------------------
# Compiled reduce-side merge (DESIGN.md §11).
#
# The grouping itself (np.unique over the, typically few, partial-state
# rows) stays host-side: its output shape is data-dependent.  Everything
# after it — every aggregate's segmented reduction — lowers into ONE jitted
# XLA program over (inverse, state columns), cached process-wide per state
# signature.  Rows and group counts pad to powers of two (padding rows map
# to a discarded extra group slot), so each signature re-traces O(log n)
# times, the same discipline as expr._PLAN_CACHE and joins.CompiledProbe.
# ---------------------------------------------------------------------------


_MERGE_FNS: Dict[Tuple, Callable] = {}
_MERGE_FNS_LOCK = threading.Lock()


def _merge_fn(sig: Tuple) -> Callable:
    with _MERGE_FNS_LOCK:
        fn = _MERGE_FNS.get(sig)
        if fn is not None:
            return fn
        import functools

        import jax
        import jax.numpy as jnp

        def traced(inv, cols, gp):
            outs = []
            i = 0
            for kind, is_int in sig:
                if kind in ("count", "sum"):
                    dt = jnp.int64 if is_int else jnp.float64
                    acc = jnp.zeros(gp + 1, dt).at[inv].add(
                        cols[i].astype(dt))
                    i += 1
                    outs.append(acc[:gp])
                elif kind == "avg":
                    s = jnp.zeros(gp + 1, jnp.float64).at[inv].add(
                        cols[i].astype(jnp.float64))
                    c = jnp.zeros(gp + 1, jnp.float64).at[inv].add(
                        cols[i + 1].astype(jnp.float64))
                    i += 2
                    outs.append((s / jnp.maximum(c, 1.0))[:gp])
                elif kind in ("min", "max"):
                    if is_int:
                        fill = (INT64_MIN_IDENT if kind == "min"
                                else INT64_MAX_IDENT)
                        acc = jnp.full(gp + 1, fill, jnp.int64)
                        v = cols[i].astype(jnp.int64)
                    else:
                        fill = jnp.inf if kind == "min" else -jnp.inf
                        acc = jnp.full(gp + 1, fill, jnp.float64)
                        v = cols[i].astype(jnp.float64)
                    acc = (acc.at[inv].min(v) if kind == "min"
                           else acc.at[inv].max(v))
                    i += 1
                    outs.append(acc[:gp])
                else:
                    raise ValueError(kind)
            return tuple(outs)

        fn = functools.partial(jax.jit, static_argnames=("gp",))(traced)
        _MERGE_FNS[sig] = fn
        return fn


_KIND_OF = {AggFunc.COUNT: "count", AggFunc.SUM: "sum", AggFunc.AVG: "avg",
            AggFunc.MIN: "min", AggFunc.MAX: "max"}


class CompiledMerge:
    """`merge_aggregate` lowered to one fused jitted program per reduce
    task.  Bit-exact with the oracle on integer states (int64 segment
    adds); float reductions agree to rounding (XLA may reorder)."""

    def __init__(self, group_cols: Sequence[str], aggs: Sequence[AggSpec]):
        if any(a.func == AggFunc.COUNT_DISTINCT for a in aggs):
            raise ExprCompileError(
                "COUNT(DISTINCT) merge is pair-regrouping, not a segmented "
                "reduce — interpreted path")
        self.group_cols = list(group_cols)
        self.aggs = list(aggs)

    def _signature(self, batch: PartitionBatch) -> Tuple:
        sig = []
        for spec in self.aggs:
            kind = _KIND_OF[spec.func]
            state = np.asarray(batch.col(_state_cols(spec)[0]).arr)
            is_int = bool(np.issubdtype(state.dtype, np.integer))
            sig.append((kind, is_int))
        return tuple(sig)

    def __call__(self, batch: PartitionBatch) -> PartitionBatch:
        from .expr import _x64
        keys = [np.asarray(batch.col(g).arr) for g in self.group_cols]
        n = batch.num_rows
        first, inverse = group_indices(keys) if self.group_cols else \
            (np.zeros(1, np.int64), np.zeros(n, np.int64))
        num_groups = len(first)
        gp = _next_pow2(num_groups)
        npad = _next_pow2(max(n, 1))
        inv = np.full(npad, gp, np.int64)   # padding -> discarded slot gp
        inv[:n] = inverse

        cols: List[np.ndarray] = []
        for spec in self.aggs:
            for sc in _state_cols(spec):
                state = np.asarray(batch.col(sc).arr)
                pad = np.zeros(npad, state.dtype)
                pad[:n] = state
                cols.append(pad)

        sig = self._signature(batch)
        fn = _merge_fn(sig)
        with tracing.device("merge", inv, cols) as sp:
            with _x64():
                outs = fn(inv, tuple(cols), gp=gp)
            outs = [sp.fetch(o) for o in outs]

        out = _group_key_cols(batch, self.group_cols, first)
        for spec, o in zip(self.aggs, outs):
            arr = o[:num_groups]
            if spec.func == AggFunc.COUNT:
                arr = arr.astype(np.int64)
            out[spec.out_name] = ColumnVal(arr)
        return PartitionBatch(out)


def _final_column(spec: AggSpec, states: Sequence[np.ndarray]) -> ColumnVal:
    """One aggregate's output column from its merged state columns (one
    entry per group; AVG's are (sum, count)): COUNT as int64, SUM as its
    state (int64 exact, float64 otherwise), AVG as sum / max(count, 1),
    MIN and MAX as their state."""
    if spec.func == AggFunc.COUNT:
        return ColumnVal(np.round(states[0]).astype(np.int64))
    if spec.func == AggFunc.AVG:
        return ColumnVal(states[0] / np.maximum(states[1], 1.0))
    if spec.func in (AggFunc.SUM, AggFunc.MIN, AggFunc.MAX):
        return ColumnVal(states[0])
    raise ExprCompileError(str(spec.func))


# the [sum, count, min, max] lane each aggregate's states are read from
_LANE_OF = {AggFunc.COUNT: 0, AggFunc.SUM: 0, AggFunc.AVG: 0,
            AggFunc.MIN: 2, AggFunc.MAX: 3}


def merge_from_lanes(batch: PartitionBatch, group_cols: Sequence[str],
                     aggs: Sequence[AggSpec], first: np.ndarray,
                     lanes: Dict[str, np.ndarray]) -> PartitionBatch:
    """Assemble the final merge output from per-state-column (G, 4)
    [sum, count, min, max] lanes — the shape the Pallas `segmented_merge`
    kernel produces.  Lives here (next to merge_aggregate and
    CompiledMerge) so the per-AggFunc output policy has one home."""
    out = _group_key_cols(batch, group_cols, first)
    for spec in aggs:
        lane = _LANE_OF.get(spec.func)
        if lane is None:
            raise ExprCompileError(str(spec.func))
        out[spec.out_name] = _final_column(
            spec, [lanes[c][:, lane] for c in _state_cols(spec)])
    return PartitionBatch(out)


def finalize_disjoint(batch: PartitionBatch, group_cols: Sequence[str],
                      aggs: Sequence[AggSpec]) -> PartitionBatch:
    """The final rows of partial states that already hold one row per
    group, for groups no other batch holds — the mesh exchange's
    per-device output, where each key lives on one device.  Each state is
    its group's merged state, so no grouping runs: the rows keep the
    batch's order, and a batch sorted by key (as the exchange's are) gives
    `merge_aggregate`'s rows and dtypes."""
    out: Dict[str, ColumnVal] = {g: batch.col(g) for g in group_cols}
    for spec in aggs:
        out[spec.out_name] = _final_column(
            spec, [np.asarray(batch.col(c).arr) for c in _state_cols(spec)])
    return PartitionBatch(out)
