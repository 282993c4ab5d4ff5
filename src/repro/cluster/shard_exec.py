"""Mesh-sharded compiled execution (DESIGN.md §13.1).

Two shard_map programs over the MeshContext's 1-D ``('data',)`` mesh, both
reading partition lanes laid out ``[n_dev * per, rows]``: partition p in
row `slot[p]` of its device's `per` rows, padded to `rows`, sharded
``P('data')``:

- `mesh_colscan` — the fused filter+aggregate colscan of DESIGN.md §10 run
  as ONE compiled program over ALL placed partitions at once: each device
  reduces its own partitions' rows to ``[count, sum, min, max]`` partial
  states.  No collective is needed — the partial states feed the engine's
  standard shuffle/merge reduce, so the final result is computed by exactly
  the code path the single-host oracle uses.
- `mesh_group_exchange` — the compiled exchange of DESIGN.md §11 shipped
  ACROSS devices: each device bucket-assigns its local rows with the same
  radix hash the Pallas partitioner uses (`radix_partition.mix_u32` on
  uint32 key lanes folded as `fold_keys_u32` folds them), sorts them by
  destination, slices each destination's run into a fixed-stride chunk,
  and an ``all_to_all`` collective moves every chunk to its owning device,
  which sorts what it received by key — the shuffle blocks never touch
  the BlockManager, and the host finds each key's rows as one run.  A
  host-side mirror computes the exact (src, dst) bucket counts with the
  *same* hash to size the stride; the program returns the counts it
  found, and the host checks them against the mirror's and takes each
  device's rows by them.

The lanes stay on the devices between dispatches (`MeshContext.resident`):
a column is laid out and shipped at its first dispatch in a placement
generation (a fill), and every later dispatch over the same host arrays
ships only the partitions' row counts and the bounds.

Both programs follow the chip's accumulation contract (`kernels/ops.py`
`default_acc_dtype`): float values travel and accumulate in float32 on a
TPU and float64 elsewhere, counts are int32, integer filters compare as
int32 lanes, and keys travel as int32 (dictionary codes, INT32 keys) or
int64 (INT64 keys).  x64 is on only for a program that holds a 64-bit
array, so a TPU program is free of emulated 64-bit arithmetic.

Padded dimensions round up to powers of two (`expr.next_pow2`), so each
program re-traces O(log n) times per mesh generation — the discipline the
compiled expression planner and reduce runners already follow.

Each dispatch is one `shark.mesh` span (DESIGN.md §17) holding the
`shark.device` span of every fill and every program call: `program`,
`devices`, `partitions`, `rows`, `h2d_bytes`, `d2h_bytes`, `retries`,
`fills` where lanes were placed, and for the exchange `a2a_bytes`,
`shipped_rows` and `pad_rows`.

Device loss: every public entry point re-reads the placement per attempt
and retries on `DeviceLost` (chaos hook) or a generation bump observed
mid-dispatch — recomputation from host-resident partitions, the same
lineage contract as worker loss in the runtime scheduler; the bump drops
every resident lane, so the retry refills over the survivors.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..core import tracing
from ..core.expr import next_pow2
from ..kernels import ops as kernel_ops
from ..kernels.colscan import int_bounds
from ..kernels.radix_partition import mix_u32
from .mesh import DeviceLost, MeshContext

# jitted program caches; keys include the Mesh object (cached per placement
# generation), the lane dtypes and pow2-padded static dims, so entries stay
# O(log n)
_COLSCAN_PROGS: Dict[Tuple, object] = {}
_EXCHANGE_PROGS: Dict[Tuple, object] = {}


def _dispatch(ctx: MeshContext, program: str, run):
    """Run `run(span)` (which must re-read mesh + placement itself) with
    the device-loss retry contract, inside one `shark.mesh` span."""
    last: Optional[BaseException] = None
    with tracing.span("mesh", program=program, h2d_bytes=0, d2h_bytes=0,
                      retries=0) as sp:
        for attempt in range(ctx.max_retries + 1):
            sp.set(retries=attempt)
            try:
                gen0 = ctx.fire_dispatch()
                out = run(sp)
            except DeviceLost as e:
                last = e
                with ctx.lock:
                    ctx.retries += 1
                continue
            if ctx.generation != gen0:
                # a device died while the program ran: the placement we used
                # is stale — recompute over the survivors
                with ctx.lock:
                    ctx.retries += 1
                continue
            return out
    raise RuntimeError(
        f"mesh dispatch failed after {ctx.max_retries + 1} attempts") from last


def _call(sp, name: str, fn, *args) -> List[np.ndarray]:
    """`fn(*args)` in a `shark.device` span, every output read back; the
    bytes both ways add to the enclosing `shark.mesh` span."""
    with tracing.device(name, *args) as dev:
        out = [dev.fetch(x) for x in fn(*args)]
    if dev and sp:
        sp.set(h2d_bytes=sp.attrs["h2d_bytes"] + dev.attrs["h2d_bytes"],
               d2h_bytes=sp.attrs["d2h_bytes"] + dev.attrs["d2h_bytes"])
    return out


def _x64_for(*dtypes):
    """x64 scope of one program: on only where it holds a 64-bit lane."""
    import jax
    return jax.enable_x64(any(np.dtype(d).itemsize == 8 for d in dtypes))


# -- placed lanes -------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Column:
    """One column of the partitions a mesh program reads: `sources`, the
    host arrays it is made from (its identity while they live); `rows`,
    each partition's row count; and `make(p)`, partition p's lane as the
    program reads it, or None where the lane cannot hold it exactly."""
    tag: str
    sources: Tuple[object, ...]
    rows: Tuple[int, ...]
    make: Callable[[int], Optional[np.ndarray]]


def column(tag: str, arrays, dtype=None) -> Column:
    """A Column of plain per-partition arrays, cast to `dtype` (default:
    each array's own) when placed."""
    if isinstance(arrays, Column):
        return arrays
    arrays = tuple(np.asarray(a) for a in arrays)
    return Column(tag, arrays, tuple(a.shape[0] for a in arrays),
                  lambda p: arrays[p].astype(
                      arrays[p].dtype if dtype is None else dtype,
                      copy=False))


@dataclasses.dataclass(frozen=True)
class _Placed:
    """A column's lanes on the mesh: `arr` [n_dev * per, rows], sharded
    over ('data',), partition p at slot `slot[p]`; with `counts`, the host
    mirror's (src, dst) bucket counts where the lanes are exchange keys."""
    arr: object
    dtype: np.dtype
    counts: Optional[np.ndarray]


def _layout(placement, rows: Sequence[int]):
    """(per, padded rows, slot of each partition): partition p fills row
    `slot[p]` of the [n_dev * per, rows] lanes, its device's next slot."""
    per = next_pow2(placement.parts_per_device)
    fill = [0] * placement.n_devices
    slot = []
    for p in range(len(rows)):
        d = placement.device_of[p]
        slot.append(d * per + fill[d])
        fill[d] += 1
    return per, next_pow2(max([1] + list(rows))), slot


def _valid_counts(placement, rows: Sequence[int], per: int, slot
                  ) -> np.ndarray:
    N = np.zeros(placement.n_devices * per, np.int32)
    N[slot] = rows
    return N


def _mirror(host: np.ndarray, N: np.ndarray, n_dev: int) -> np.ndarray:
    """Host mirror of the exchange's bucket assignment: the exact (src,
    dst) counts, by the same fold + mix as the device program."""
    valid = np.arange(host.shape[1])[None, :] < N[:, None]
    counts = np.zeros((n_dev, n_dev), np.int64)
    for d, block in enumerate(np.split(np.arange(host.shape[0]), n_dev)):
        keys = host[block][valid[block]]
        dest = mix_u32(_fold_u32(keys)) % np.uint32(n_dev)
        counts[d] = np.bincount(dest, minlength=n_dev)
    return counts


def _placed(ctx: MeshContext, sp, mesh, placement, col: Column, per: int,
            rows: int, slot, keys: bool = False) -> Optional[_Placed]:
    """`col`'s lanes on the mesh: kept from an earlier dispatch of this
    placement generation, or made on the host, shipped and kept (a fill,
    whose bytes count in the dispatch's `h2d_bytes`).  None where a
    partition's lane cannot be made or the lanes disagree on a dtype."""
    gen = placement.generation
    got = ctx.resident.get(col.tag, col.sources, gen)
    if got is not None and (got.counts is not None or not keys):
        return got
    lanes = [col.make(p) for p in range(len(col.rows))]
    if any(x is None for x in lanes) or len({x.dtype for x in lanes}) != 1:
        return None
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    dtype = lanes[0].dtype
    host = np.zeros((placement.n_devices * per, rows), dtype)
    for x, r in zip(lanes, slot):
        host[r, :x.shape[0]] = x
    counts = (_mirror(host, _valid_counts(placement, col.rows, per, slot),
                      placement.n_devices) if keys else None)
    with _x64_for(dtype), tracing.device("mesh_place", host) as dev:
        arr = jax.device_put(host, NamedSharding(mesh, P("data")))
    if dev and sp:
        sp.set(h2d_bytes=sp.attrs["h2d_bytes"] + dev.attrs["h2d_bytes"],
               fills=sp.attrs.get("fills", 0) + 1)
    placed = _Placed(arr, dtype, counts)
    ctx.resident.keep(col.tag, col.sources, gen, placed, host.nbytes)
    return placed


# -- colscan under shard_map --------------------------------------------------

def _colscan_program(mesh, per: int, rows: int, fdtype: str, acc: str):
    key = (mesh, per, rows, fdtype, acc)
    fn = _COLSCAN_PROGS.get(key)
    if fn is None:
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        def local(F, A, N, lo, hi):
            # F: [per, rows] filter lanes; A: [per, rows] acc-dtype values;
            # N: [per] valid-row counts
            pos = jnp.arange(rows, dtype=jnp.int32)[None, :]
            mask = (F >= lo) & (F <= hi) & (pos < N[:, None])
            cnt = jnp.sum(mask, axis=1, dtype=jnp.int32)
            s = jnp.sum(jnp.where(mask, A, 0), axis=1)
            mn = jnp.min(jnp.where(mask, A, jnp.inf), axis=1)
            mx = jnp.max(jnp.where(mask, A, -jnp.inf), axis=1)
            return cnt, jnp.stack([s, mn, mx], axis=1)

        fn = jax.jit(jax.shard_map(
            local, mesh=mesh,
            in_specs=(P("data"), P("data"), P("data"), P(), P()),
            out_specs=(P("data"), P("data"))))
        _COLSCAN_PROGS[key] = fn
    return fn


def filter_column(arrays: Sequence[np.ndarray], acc: str) -> Column:
    """The filter lanes of a mesh colscan: `kernel_ops.colscan_filter` of
    each partition's column."""
    arrays = tuple(arrays)
    return Column(f"filter:{acc}", arrays,
                  tuple(a.shape[0] for a in arrays),
                  lambda p: kernel_ops.colscan_filter(arrays[p], acc))


def mesh_colscan(ctx: MeshContext, fcols, acols, lo: float, hi: float,
                 acc: Optional[str] = None
                 ) -> Optional[Tuple[List[Tuple[float, float, float, float]],
                                     Dict]]:
    """Fused filter+aggregate over every placed partition in one program.
    `fcols` are the filter lanes (a Column such as `filter_column` makes,
    or arrays already in lane dtypes) compared with the closed bounds
    [lo, hi]; `acols` are the values, placed in the accumulation dtype.
    Returns per-partition ``(count, sum, min, max)`` partial states (same
    contract as `_fused_colscan_fns`) plus a dispatch report, or None
    where the lanes cannot be placed."""
    acc = acc or kernel_ops.default_acc_dtype()
    fcol = column("filter", fcols)
    acol = column(f"value:{np.dtype(acc)}", acols, acc)

    def run(sp):
        mesh, _ = ctx.mesh()
        placement = ctx.place(len(fcol.rows))
        per, rows, slot = _layout(placement, fcol.rows)
        F = _placed(ctx, sp, mesh, placement, fcol, per, rows, slot)
        A = _placed(ctx, sp, mesh, placement, acol, per, rows, slot)
        if F is None or A is None:
            return None
        flo, fhi = int_bounds(lo, hi) if F.dtype == np.int32 else (lo, hi)
        N = _valid_counts(placement, fcol.rows, per, slot)
        n_dev = placement.n_devices
        sp.set(devices=n_dev, partitions=len(fcol.rows), rows=int(N.sum()))
        with _x64_for(F.dtype, A.dtype):
            cnt, sums = _call(sp, "mesh_colscan",
                              _colscan_program(mesh, per, rows, str(F.dtype),
                                               str(A.dtype)),
                              F.arr, A.arr, N, np.asarray(flo, F.dtype),
                              np.asarray(fhi, F.dtype))
        report = {"devices": n_dev, "partitions": len(fcol.rows),
                  "generation": placement.generation}
        return [(float(cnt[r]),) + tuple(float(x) for x in sums[r])
                for r in slot], report

    return _dispatch(ctx, "colscan", run)


# -- cross-device radix exchange ----------------------------------------------

def _fold_u32(k: np.ndarray) -> np.ndarray:
    """Host mirror of the device fold: `fold_keys_u32` of the key as int64
    (xor of its halves), computed without widening an int32 key."""
    if k.dtype == np.int64:
        u = k.view(np.uint64)
        return ((u ^ (u >> np.uint64(32))) & np.uint64(0xFFFFFFFF)).astype(
            np.uint32)
    k = k.astype(np.int32, copy=False)
    return (k ^ (k >> np.int32(31))).view(np.uint32)


def _fold_u32_jnp(k):
    """Device twin of `_fold_u32`, bit-identical to the host mirror."""
    import jax
    import jax.numpy as jnp
    if k.dtype == jnp.int64:
        u = jax.lax.bitcast_convert_type(k, jnp.uint64)
        return ((u ^ (u >> jnp.uint64(32)))
                & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32)
    return jax.lax.bitcast_convert_type(k ^ (k >> 31), jnp.uint32)


def _exchange_program(mesh, n_dev: int, per: int, rows: int, stride: int,
                      kdtype: str, vdtype: Optional[str]):
    key = (mesh, per, rows, stride, kdtype, vdtype)
    fn = _EXCHANGE_PROGS.get(key)
    if fn is None:
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        def local(K, N, *V):
            # K, V: [per, rows] partition lanes; N: [per] valid rows
            valid = (jnp.arange(rows, dtype=jnp.int32)[None, :]
                     < N[:, None]).reshape(-1)
            k = K.reshape(-1)
            dest = (mix_u32(_fold_u32_jnp(k))
                    % jnp.uint32(n_dev)).astype(jnp.int32)
            dest = jnp.where(valid, dest, n_dev)    # padding sorts last
            sent = jnp.stack([jnp.sum(dest == d, dtype=jnp.int32)
                              for d in range(n_dev)])
            starts = jnp.cumsum(sent) - sent
            # one sort groups the rows by destination; each destination's
            # run is then one slice into its fixed-stride chunk (the tail
            # past its count is never read)
            ordered = jax.lax.sort((dest, k) + tuple(v.reshape(-1)
                                                     for v in V),
                                   num_keys=1, is_stable=False)
            ex = []
            for x in ordered[1:]:
                x = jnp.concatenate([x, jnp.zeros(stride, x.dtype)])
                packed = jnp.concatenate([
                    jax.lax.dynamic_slice(x, (starts[d],), (stride,))
                    for d in range(n_dev)])
                ex.append(jax.lax.all_to_all(packed, "data", split_axis=0,
                                             concat_axis=0, tiled=True))
            # chunk s of the received buffer holds what s sent here at its
            # front: sort the received rows by key, the padding last, so the
            # host finds each key's rows as one run
            sent_all = jax.lax.all_gather(sent, "data")     # [src, dst]
            here = jax.lax.dynamic_index_in_dim(
                sent_all, jax.lax.axis_index("data"), axis=1, keepdims=False)
            pad = (jnp.arange(stride, dtype=jnp.int32)[None, :]
                   >= here[:, None]).reshape(-1).astype(jnp.int32)
            ordered = jax.lax.sort((pad,) + tuple(ex), num_keys=2,
                                   is_stable=False)
            return tuple(x[None] for x in ordered[1:]) + (sent[None],)

        n_v = vdtype is not None
        fn = jax.jit(jax.shard_map(
            local, mesh=mesh,
            in_specs=(P("data"), P("data")) + (P("data"),) * n_v,
            out_specs=(P("data", None),) * (2 + n_v)))
        _EXCHANGE_PROGS[key] = fn
    return fn


def _key_lane(dtype) -> np.dtype:
    """Lane dtype a key travels in: int64 for 64-bit keys, else int32."""
    return np.dtype(np.int64 if np.dtype(dtype).itemsize == 8 else np.int32)


def _value_lane(dtype, acc: str) -> np.dtype:
    """Lane dtype a value travels in: the accumulation dtype for floats,
    int32 for integers that fit it, int64 for 64-bit integers."""
    dtype = np.dtype(dtype)
    if dtype.kind == "f":
        return np.dtype(acc)
    return np.dtype(np.int64 if dtype.itemsize == 8 else np.int32)


def mesh_group_exchange(ctx: MeshContext, keys, vals,
                        acc: Optional[str] = None
                        ) -> Optional[Tuple[List[Tuple[np.ndarray,
                                                       Optional[np.ndarray]]],
                                            Dict]]:
    """Radix-exchange the placed partitions' (key, value) rows across
    devices: afterwards each device owns every row whose key hashes to it.
    `keys` and `vals` are Columns, or per-partition arrays placed in
    `_key_lane` / `_value_lane` dtypes.  Returns one ``(keys, values)``
    pair per device in those dtypes, the rows sorted by key (values is
    None when no value column was shipped), and a report with the exact
    (src, dst) bucket counts, or None where the lanes cannot be placed."""
    acc = acc or kernel_ops.default_acc_dtype()
    if not isinstance(keys, Column):
        kdt = _key_lane(keys[0].dtype if len(keys) else np.int64)
        keys = column(f"key:{kdt}", keys, kdt)
    if vals is not None and not isinstance(vals, Column):
        vdt = _value_lane(vals[0].dtype, acc) if len(vals) else None
        vals = None if vdt is None else column(f"value:{vdt}", vals, vdt)

    def run(sp):
        mesh, _ = ctx.mesh()
        placement = ctx.place(len(keys.rows))
        n_dev = placement.n_devices
        per, rows, slot = _layout(placement, keys.rows)
        K = _placed(ctx, sp, mesh, placement, keys, per, rows, slot,
                    keys=True)
        V = (_placed(ctx, sp, mesh, placement, vals, per, rows, slot)
             if vals is not None else None)
        if K is None or (vals is not None and V is None):
            return None
        N = _valid_counts(placement, keys.rows, per, slot)
        counts = K.counts
        stride = next_pow2(max(1, int(counts.max())))
        slots = n_dev * n_dev * stride
        lane_bytes = K.dtype.itemsize + (V.dtype.itemsize if V else 0)
        shipped = int(counts.sum() - np.trace(counts))
        sp.set(devices=n_dev, partitions=len(keys.rows), rows=int(N.sum()),
               a2a_bytes=slots * lane_bytes, shipped_rows=shipped,
               pad_rows=slots - int(N.sum()))
        args = (K.arr, N) + ((V.arr,) if V else ())
        with _x64_for(K.dtype, *([V.dtype] if V else [])):
            got = _call(sp, "mesh_exchange",
                        _exchange_program(mesh, n_dev, per, rows, stride,
                                          str(K.dtype),
                                          str(V.dtype) if V else None),
                        *args)
        sent = got[-1]
        if not np.array_equal(sent, counts):
            raise RuntimeError("mesh exchange: the device's bucket counts "
                               "differ from the host mirror's")
        out = []
        for d in range(n_dev):
            # device d's rows, sorted by key, lead its buffer
            n = int(sent[:, d].sum())
            out.append((got[0][d, :n], got[1][d, :n] if V else None))
        report = {"devices": n_dev, "counts": counts,
                  "shipped_rows": shipped,
                  "generation": placement.generation}
        return out, report

    return _dispatch(ctx, "exchange", run)
