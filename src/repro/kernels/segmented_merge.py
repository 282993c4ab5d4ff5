"""Segmented reduce-side merge — Pallas TPU kernel (DESIGN.md §11).

The reduce half of an aggregation merges partial states by group: per group
[sum, count, min, max] of one state column.  Shark's reducers do this with
JVM hash tables; per-row scatter is serial poison on TPU vector units, so
the TPU-native form mirrors `groupby_mxu`: each grid step builds a one-hot
tile of the (pre-grouped, host-side `np.unique`) group ids in VMEM, reduces
sum/count on the MXU (one-hot matmul) and min/max on the VPU (masked
tile-wide reductions), emitting per-tile (4, G) partials the wrapper folds
with a tiny final sum/min/max.

Groups are padded to a multiple of 128 so the matmul is MXU-aligned; rows
pad with an out-of-range group id so padding contributes nothing.  Like the
other engine kernels, `acc_dtype` is float32 on TPU and float64 in CPU
interpret mode, where the engine requires parity with the numpy oracle to
rounding; integer states stay on the jitted int64 segmented reduce
(aggregate.CompiledMerge) — float accumulation would round them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_ROWS = 1024


def _segmerge_kernel(codes_ref, vals_ref, out_ref, *,
                     num_groups_padded: int):
    dt = out_ref.dtype
    codes = codes_ref[...]
    vals = vals_ref[...].astype(dt)
    groups = jax.lax.broadcasted_iota(jnp.int32, (1, num_groups_padded), 1)
    onehot = codes[:, None] == groups                       # (B, Gp) bool
    oh = onehot.astype(dt)
    stacked = jnp.stack([vals, jnp.ones_like(vals)], axis=0)  # (2, B)
    sc = jnp.dot(stacked, oh,                               # MXU: (2, Gp)
                 precision=jax.lax.Precision.HIGHEST)
    mn = jnp.min(jnp.where(onehot, vals[:, None], jnp.inf), axis=0)
    mx = jnp.max(jnp.where(onehot, vals[:, None], -jnp.inf), axis=0)
    out_ref[...] = jnp.concatenate(
        [sc, mn[None, :], mx[None, :]], axis=0)[None]       # (1, 4, Gp)


@functools.partial(jax.jit, static_argnames=("num_groups", "interpret",
                                             "block_rows", "acc_dtype"))
def segmented_merge(codes: jnp.ndarray, values: jnp.ndarray, *,
                    num_groups: int, interpret: bool = False,
                    block_rows: int = BLOCK_ROWS,
                    acc_dtype: str = "float32") -> jnp.ndarray:
    """Returns (num_groups, 4): per-group [sum, count, min, max] of
    `values` segmented by `codes` (0 <= code < num_groups).  Empty groups
    report count 0 and the ±inf min/max identities."""
    dt = jnp.dtype(acc_dtype)
    n = codes.shape[0]
    gp = max(128, -(-num_groups // 128) * 128)
    num_blocks = max(1, -(-n // block_rows))
    padded = num_blocks * block_rows
    # pad codes to an out-of-range group so padding contributes nothing
    c = jnp.full((padded,), gp, jnp.int32).at[:n].set(codes.astype(jnp.int32))
    v = jnp.zeros((padded,), dt).at[:n].set(values.astype(dt))
    partials = pl.pallas_call(
        functools.partial(_segmerge_kernel, num_groups_padded=gp),
        grid=(num_blocks,),
        in_specs=[pl.BlockSpec((block_rows,), lambda i: (i,)),
                  pl.BlockSpec((block_rows,), lambda i: (i,))],
        out_specs=pl.BlockSpec((1, 4, gp), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((num_blocks, 4, gp), dt),
        interpret=interpret,
    )(c, v)
    sums = jnp.sum(partials[:, 0, :num_groups], axis=0)
    cnts = jnp.sum(partials[:, 1, :num_groups], axis=0)
    mns = jnp.min(partials[:, 2, :num_groups], axis=0)
    mxs = jnp.max(partials[:, 3, :num_groups], axis=0)
    return jnp.stack([sums, cnts, mns, mxs], axis=1)       # (G, 4)
