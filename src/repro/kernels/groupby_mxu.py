"""Group-by aggregation as a one-hot matmul — Pallas TPU kernel.

Hardware adaptation (DESIGN.md §2): Shark's reducers aggregate with JVM hash
tables; per-row scatter is serial poison on a TPU's vector units.  For the
low-cardinality keys that dominate warehouse group-bys (SHIPMODE: 7 groups,
country: ~200 — see §6.3.1/§6.4), the TPU-native algorithm is:

    one_hot(codes) @ values  -> per-group sums      (MXU, 128x128 systolic)
    one_hot(codes) @ ones    -> per-group counts

Each grid step builds the one-hot tile for BLOCK_ROWS rows in VMEM and issues
two fused matmuls at full f32 contract precision; partial (G,2) results land
per-tile and the wrapper does the final (num_blocks, G, 2) -> (G, 2) sum,
counts in int32 so they stay exact past float32's 2^24.  G is padded to a
multiple of 128 so the matmul is MXU-aligned.  High-cardinality group-bys stay on the
sort/segment-sum engine path (aggregate.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_ROWS = 1024


def _groupby_kernel(codes_ref, vals_ref, out_ref, *, num_groups_padded: int):
    dt = out_ref.dtype
    codes = codes_ref[...]
    vals = vals_ref[...].astype(dt)
    groups = jax.lax.broadcasted_iota(jnp.int32, (1, num_groups_padded), 1)
    onehot = (codes[:, None] == groups).astype(dt)  # (B, Gp)
    stacked = jnp.stack([vals, jnp.ones_like(vals)], axis=0)  # (2, B)
    out_ref[...] = jnp.dot(stacked, onehot,               # MXU: (2, Gp)
                           precision=jax.lax.Precision.HIGHEST)[None]


@functools.partial(jax.jit, static_argnames=("num_groups", "interpret",
                                             "block_rows", "acc_dtype"))
def groupby_sum(codes: jnp.ndarray, values: jnp.ndarray, *, num_groups: int,
                interpret: bool = False,
                block_rows: int = BLOCK_ROWS,
                acc_dtype: str = "float32") -> jnp.ndarray:
    """Returns (sums[num_groups], counts[num_groups] int32).  `acc_dtype`
    is float32 on TPU (MXU-native); the engine passes float64 in interpret
    mode on CPU to match the numpy oracle to rounding."""
    dt = jnp.dtype(acc_dtype)
    n = codes.shape[0]
    gp = max(128, -(-num_groups // 128) * 128)
    num_blocks = max(1, -(-n // block_rows))
    padded = num_blocks * block_rows
    # pad codes to an out-of-range group so padding contributes nothing
    c = jnp.full((padded,), gp, jnp.int32).at[:n].set(codes.astype(jnp.int32))
    v = jnp.zeros((padded,), dt).at[:n].set(values.astype(dt))
    partials = pl.pallas_call(
        functools.partial(_groupby_kernel, num_groups_padded=gp),
        grid=(num_blocks,),
        in_specs=[pl.BlockSpec((block_rows,), lambda i: (i,)),
                  pl.BlockSpec((block_rows,), lambda i: (i,))],
        out_specs=pl.BlockSpec((1, 2, gp), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((num_blocks, 2, gp), dt),
        interpret=interpret,
    )(c, v)
    sums = jnp.sum(partials[:, 0, :num_groups], axis=0)
    # per-tile counts are exact small floats (<= block_rows): cast to int32
    # BEFORE the cross-tile sum so totals stay exact past 2^24
    counts = jnp.sum(partials[:, 1, :num_groups].astype(jnp.int32), axis=0)
    return sums, counts
