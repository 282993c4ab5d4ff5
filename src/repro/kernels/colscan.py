"""Fused filter+aggregate columnar scan — Pallas TPU kernel.

The paper's hot path (§6.2.1–6.2.2): scan a cached column, apply a range
predicate, aggregate a second column.  Hive burns CPU deserializing rows and
interpreting expression evaluators; Shark's columnar store + compiled
evaluators fix that on the JVM.  The TPU-native form goes further: the
filter, select and aggregate are ONE kernel — each grid step streams a
row-tile of both columns HBM->VMEM, evaluates the predicate on the VPU, and
folds the tile's [count, sum, min, max] into one resident (1, 128)
accumulator block (lanes 0..3), so filtered data never round-trips to HBM
and the output is a single lane row.

Integer filter columns (int32 values, dictionary codes) are compared in
int32 against integer bounds, so the row set is exact on the chip; float
filter columns compare in the accumulation dtype.  Rows past the column's
end (tile padding) are masked by position, not by a sentinel value.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_ROWS = 8 * 128  # one full VPU tile of f32 per grid step

LANES = 128  # accumulator row width (TPU lane count)

_I32 = np.iinfo(np.int32)


def int_bounds(lo: float, hi: float):
    """Closed float bounds -> int32 bounds selecting the same integers;
    an empty range maps to (1, 0)."""
    ilo = math.ceil(lo) if math.isfinite(lo) else (
        _I32.min if lo < 0 else _I32.max + 1)
    ihi = math.floor(hi) if math.isfinite(hi) else (
        _I32.max if hi > 0 else _I32.min - 1)
    ilo, ihi = max(ilo, _I32.min), min(ihi, _I32.max)
    return (ilo, ihi) if ilo <= ihi else (1, 0)


def _colscan_kernel(bounds_ref, filt_ref, agg_ref, out_ref, *, n: int,
                    block_rows: int):
    """One grid step: fold a row tile into the [count, sum, min, max]
    lanes of the resident accumulator."""
    i = pl.program_id(0)
    dt = out_ref.dtype
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.where(lane == 2, jnp.inf,
                                 jnp.where(lane == 3, -jnp.inf,
                                           0.0)).astype(dt)

    f = filt_ref[...]
    a = agg_ref[...].astype(dt)
    pos = i * block_rows + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_rows,), 0)
    mask = (f >= bounds_ref[0]) & (f <= bounds_ref[1]) & (pos < n)
    cnt = jnp.sum(mask.astype(dt))
    s = jnp.sum(jnp.where(mask, a, 0.0))
    mn = jnp.min(jnp.where(mask, a, jnp.inf))
    mx = jnp.max(jnp.where(mask, a, -jnp.inf))
    acc = out_ref[...]
    out_ref[...] = jnp.where(
        lane == 0, acc + cnt,
        jnp.where(lane == 1, acc + s,
                  jnp.where(lane == 2, jnp.minimum(acc, mn),
                            jnp.where(lane == 3, jnp.maximum(acc, mx),
                                      acc))))


@functools.partial(jax.jit, static_argnames=("interpret", "block_rows",
                                             "acc_dtype"))
def colscan(filter_col: jnp.ndarray, agg_col: jnp.ndarray,
            lo, hi, *, interpret: bool = False,
            block_rows: int = BLOCK_ROWS,
            acc_dtype: str = "float32") -> jnp.ndarray:
    """Returns [count, sum, min, max] over rows with lo <= filter_col <= hi.

    An int32 `filter_col` takes int32 bounds (see `int_bounds`); any other
    filter dtype is compared in `acc_dtype`.  `acc_dtype` is the
    accumulation dtype: float32 on TPU (VPU-native), float64 when the
    engine runs the kernel in interpret mode on CPU and must match the
    numpy oracle to rounding.
    """
    dt = jnp.dtype(acc_dtype)
    fdt = jnp.int32 if filter_col.dtype == jnp.int32 else dt
    n = filter_col.shape[0]
    num_blocks = max(1, -(-n // block_rows))
    padded = num_blocks * block_rows
    f = jnp.zeros((padded,), fdt).at[:n].set(filter_col.astype(fdt))
    a = jnp.zeros((padded,), dt).at[:n].set(agg_col.astype(dt))
    bounds = jnp.stack([jnp.asarray(lo, fdt), jnp.asarray(hi, fdt)])

    acc = pl.pallas_call(
        functools.partial(_colscan_kernel, n=n, block_rows=block_rows),
        grid=(num_blocks,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # [lo, hi] scalars
            pl.BlockSpec((block_rows,), lambda i: (i,)),
            pl.BlockSpec((block_rows,), lambda i: (i,)),
        ],
        out_specs=pl.BlockSpec((1, LANES), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, LANES), dt),
        interpret=interpret,
    )(bounds, f, a)
    return acc[0, :4]
