"""Mesh-sharded compiled execution (DESIGN.md §13.1).

Two shard_map programs over the MeshContext's 1-D ``('data',)`` mesh:

- `mesh_colscan` — the fused filter+aggregate colscan of DESIGN.md §10 run
  as ONE compiled program over ALL placed partitions at once: the leading
  axis (device × partition-slot) is sharded ``P('data')``, each device
  reduces its own partitions' rows to ``[count, sum, min, max]`` partial
  states.  No collective is needed — the partial states feed the engine's
  standard shuffle/merge reduce, so the final result is computed by exactly
  the code path the single-host oracle uses.
- `mesh_group_exchange` — the compiled exchange of DESIGN.md §11 shipped
  ACROSS devices: each device bucket-assigns its local rows with the same
  radix hash the Pallas partitioner uses (`radix_partition.mix_u32` on
  host-folded uint32 key lanes), packs them into fixed-stride per-
  destination chunks, and an ``all_to_all`` collective moves every bucket
  to its owning device — the shuffle blocks never touch the BlockManager.
  A host-side mirror computes the exact (src, dst) bucket counts with the
  *same* hash to size the stride, and validity flags travel through the
  collective so receivers drop padding without trusting the mirror.

Padded dimensions round up to powers of two (`expr.next_pow2`), so each
program re-traces O(log n) times per mesh generation — the discipline the
compiled expression planner and reduce runners already follow.

Device loss: every public entry point re-reads the placement per attempt
and retries on `DeviceLost` (chaos hook) or a generation bump observed
mid-dispatch — recomputation from host-resident partitions, the same
lineage contract as worker loss in the runtime scheduler.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..core.expr import _x64, next_pow2
from ..kernels.radix_partition import fold_keys_u32, mix_u32
from .mesh import DeviceLost, MeshContext

# jitted program caches; keys include the Mesh object (cached per placement
# generation) and pow2-padded static dims, so entries stay O(log n)
_COLSCAN_PROGS: Dict[Tuple, object] = {}
_EXCHANGE_PROGS: Dict[Tuple, object] = {}


def _dispatch(ctx: MeshContext, run):
    """Run `run()` (which must re-read mesh + placement itself) with the
    device-loss retry contract."""
    last: Optional[BaseException] = None
    for _ in range(ctx.max_retries + 1):
        try:
            gen0 = ctx.fire_dispatch()
            out = run()
        except DeviceLost as e:
            last = e
            with ctx.lock:
                ctx.retries += 1
            continue
        if ctx.generation != gen0:
            # a device died while the program ran: the placement we used is
            # stale — recompute over the survivors
            with ctx.lock:
                ctx.retries += 1
            continue
        return out
    raise RuntimeError(
        f"mesh dispatch failed after {ctx.max_retries + 1} attempts") from last


# -- colscan under shard_map --------------------------------------------------

def _colscan_program(mesh, per: int, rows: int):
    key = (mesh, per, rows)
    fn = _COLSCAN_PROGS.get(key)
    if fn is None:
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        def local(F, A, N, lo, hi):
            # F, A: [per, rows] float64; N: [per] valid-row counts
            pos = jnp.arange(rows, dtype=jnp.int64)[None, :]
            mask = (F >= lo) & (F <= hi) & (pos < N[:, None])
            cnt = jnp.sum(mask.astype(jnp.float64), axis=1)
            s = jnp.sum(jnp.where(mask, A, 0.0), axis=1)
            mn = jnp.min(jnp.where(mask, A, jnp.inf), axis=1)
            mx = jnp.max(jnp.where(mask, A, -jnp.inf), axis=1)
            return jnp.stack([cnt, s, mn, mx], axis=1)

        fn = jax.jit(jax.shard_map(
            local, mesh=mesh,
            in_specs=(P("data"), P("data"), P("data"), P(), P()),
            out_specs=P("data")))
        _COLSCAN_PROGS[key] = fn
    return fn


def mesh_colscan(ctx: MeshContext, fcols: Sequence[np.ndarray],
                 acols: Sequence[np.ndarray], lo: float, hi: float
                 ) -> Tuple[List[Tuple[float, float, float, float]], Dict]:
    """Fused filter+aggregate over every placed partition in one program.
    Returns per-partition ``(count, sum, min, max)`` partial states (same
    contract as `_fused_colscan_fns`) plus a dispatch report."""

    def run():
        mesh, _ = ctx.mesh()
        placement = ctx.place(len(fcols))
        n_dev, per = placement.n_devices, next_pow2(
            placement.parts_per_device)
        rows = next_pow2(max([1] + [f.shape[0] for f in fcols]))
        F = np.zeros((n_dev * per, rows), np.float64)
        A = np.zeros((n_dev * per, rows), np.float64)
        N = np.zeros(n_dev * per, np.int64)
        slot_fill = [0] * n_dev
        rowmap = []
        for p, (f, a) in enumerate(zip(fcols, acols)):
            d = placement.device_of[p]
            r = d * per + slot_fill[d]
            slot_fill[d] += 1
            F[r, :f.shape[0]] = f
            A[r, :a.shape[0]] = a
            N[r] = f.shape[0]
            rowmap.append(r)
        with _x64():
            res = np.asarray(_colscan_program(mesh, per, rows)(
                F, A, N, np.float64(lo), np.float64(hi)))
        report = {"devices": n_dev, "partitions": len(fcols),
                  "generation": placement.generation}
        return [tuple(res[r]) for r in rowmap], report

    return _dispatch(ctx, run)


# -- cross-device radix exchange ----------------------------------------------

def _fold_u32_jnp(k):
    """Device twin of `radix_partition.fold_keys_u32`: xor of the int64
    halves, bit-identical to the host mirror."""
    import jax
    import jax.numpy as jnp
    u = jax.lax.bitcast_convert_type(k.astype(jnp.int64), jnp.uint64)
    return ((u ^ (u >> jnp.uint64(32)))
            & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32)


def _exchange_program(mesh, n_dev: int, rows: int, stride: int,
                      vdtype: Optional[str]):
    key = (mesh, rows, stride, vdtype)
    fn = _EXCHANGE_PROGS.get(key)
    if fn is None:
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        def local(K, V, F):
            # [1, rows] per-device blocks: keys, values, validity (int32)
            k, v, f = K[0], V[0], F[0]
            dest = (mix_u32(_fold_u32_jnp(k))
                    % jnp.uint32(n_dev)).astype(jnp.int32)
            dest = jnp.where(f > 0, dest, n_dev)    # padding -> sentinel
            lanes = jnp.arange(n_dev, dtype=jnp.int32)
            counts = jnp.sum((dest[None, :] == lanes[:, None]).astype(
                jnp.int64), axis=1)
            starts = jnp.concatenate(
                [jnp.cumsum(counts) - counts,
                 jnp.sum(counts, keepdims=True)])   # sentinel start
            order = jnp.argsort(dest)               # stable
            sd = dest[order]
            rank = jnp.arange(rows, dtype=jnp.int64) - starts[sd]
            target = sd.astype(jnp.int64) * stride + rank
            # pack each destination's rows into its fixed-stride chunk;
            # sentinel rows index past the buffer and drop
            outk = jnp.zeros(n_dev * stride, k.dtype).at[target].set(
                k[order], mode="drop")
            outv = jnp.zeros(n_dev * stride, v.dtype).at[target].set(
                v[order], mode="drop")
            outf = jnp.zeros(n_dev * stride, jnp.int32).at[target].set(
                f[order], mode="drop")
            ex = [jax.lax.all_to_all(x, "data", split_axis=0, concat_axis=0,
                                     tiled=True)
                  for x in (outk, outv, outf)]
            return ex[0][None], ex[1][None], ex[2][None]

        fn = jax.jit(jax.shard_map(
            local, mesh=mesh,
            in_specs=(P("data", None),) * 3,
            out_specs=(P("data", None),) * 3))
        _EXCHANGE_PROGS[key] = fn
    return fn


def mesh_group_exchange(ctx: MeshContext, keys: Sequence[np.ndarray],
                        vals: Optional[Sequence[np.ndarray]]
                        ) -> Tuple[List[Tuple[np.ndarray,
                                              Optional[np.ndarray]]], Dict]:
    """Radix-exchange the placed partitions' (key, value) rows across
    devices: afterwards each device owns every row whose key hashes to it.
    Returns one ``(keys, values)`` pair per device (values is None when no
    value column was shipped) and a report with the exact (src, dst) bucket
    counts from the host mirror."""
    kdtype = keys[0].dtype if keys else np.dtype(np.int64)
    vdtype = (vals[0].dtype if vals is not None and len(vals)
              else np.dtype(np.float64))

    def run():
        mesh, _ = ctx.mesh()
        placement = ctx.place(len(keys))
        n_dev = placement.n_devices
        # per-device concat of the placed partitions' rows
        dev_keys: List[List[np.ndarray]] = [[] for _ in range(n_dev)]
        dev_vals: List[List[np.ndarray]] = [[] for _ in range(n_dev)]
        for p, k in enumerate(keys):
            d = placement.device_of[p]
            dev_keys[d].append(k)
            if vals is not None:
                dev_vals[d].append(vals[p])
        cat_k = [np.concatenate(ks).astype(np.int64) if ks
                 else np.zeros(0, np.int64) for ks in dev_keys]
        rows = next_pow2(max(1, max(k.shape[0] for k in cat_k)))
        K = np.zeros((n_dev, rows), np.int64)
        V = np.zeros((n_dev, rows), vdtype)
        Fv = np.zeros((n_dev, rows), np.int32)
        for d in range(n_dev):
            n = cat_k[d].shape[0]
            K[d, :n] = cat_k[d]
            if vals is not None and n:
                V[d, :n] = np.concatenate(dev_vals[d]).astype(
                    vdtype, copy=False)
            Fv[d, :n] = 1
        # host mirror: same fold + mix as the device program, to size the
        # per-(src,dst) chunk stride exactly
        counts = np.zeros((n_dev, n_dev), np.int64)
        for d in range(n_dev):
            dest = (mix_u32(fold_keys_u32(cat_k[d]))
                    % np.uint32(n_dev)).astype(np.int64)
            counts[d] = np.bincount(dest, minlength=n_dev)
        stride = next_pow2(max(1, int(counts.max())))
        with _x64():
            Kx, Vx, Fx = (
                np.asarray(x) for x in _exchange_program(
                    mesh, n_dev, rows, stride, str(vdtype))(K, V, Fv))
        out = []
        for d in range(n_dev):
            flags = Fx[d] > 0
            kd = Kx[d][flags].astype(kdtype, copy=False)
            vd = Vx[d][flags] if vals is not None else None
            out.append((kd, vd))
        shipped = int(counts.sum() - np.trace(counts))
        report = {"devices": n_dev, "counts": counts,
                  "shipped_rows": shipped,
                  "generation": placement.generation}
        return out, report

    return _dispatch(ctx, run)
