"""Batch-gradient training step — Pallas TPU kernel (DESIGN.md §15.2).

One full-batch gradient for the in-engine estimators: logistic regression
(`sigmoid(x @ w) - y` residuals) or linear regression (`x @ w - y`).  The
PDE routes large feature partitions here (`decide_train_backend` ->
"train_grad"); smaller ones take the fused-jit or numpy-oracle routes,
all three producing the same gradient to rounding.

Tiling is the colscan resident-accumulator idiom: a 1-D grid over row
tiles, each grid step adding its tile's contribution `residual.T @ x_tile`
(one MXU matmul at full f32 contract precision, (1, d_pad)) into the one
(1, d_pad) output block, which stays in VMEM for the whole sweep.  Zero-padding is self-masking: a padded row has x == 0, and the
gradient weighs each residual by that zero feature row, so padded rows
contribute exactly nothing — no validity mask needed (the nonzero
logistic residual sigmoid(0) - 0 at padded rows is multiplied away).

The call is split in two programs.  `pad_inputs` lays `x` and `y` out as
the kernel reads them: `xp (n_pad, d_pad)`, features zero-padded to whole
row tiles and 128 lanes, and `yp (n_pad, 128)`, the label in lane 0.  A
`(n_pad, 1)` column takes as many bytes under the chip's (8, 128) tiling,
but a program that returns one stores it in a compact layout, which the
kernel's program would then copy back into tiles on every call; 128
lanes are stored as the kernel reads them.  `train_grad_padded` runs
the kernel on inputs already laid out so, padding only `w`.  The trainer
pads a partition's features once per fit and keeps `xp`/`yp` on the
device (ml/trainer.py `ResidentFeatures`), so later iterations run the
second program alone; `train_grad` is the two in one program, for
callers with host arrays.

`acc_dtype` follows the repo convention: float32 on TPU MXU, float64 in
interpret mode so the differential tests against the numpy oracle are
bit-stable.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_ROWS = 1024
LANES = 128


def _grad_kernel(x_ref, y_ref, w_ref, out_ref, *, kind: str):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    hi = jax.lax.Precision.HIGHEST
    x = x_ref[...]                     # (B, d_pad)
    y = y_ref[:, :1]                   # (B, 1): the label lane
    w = w_ref[...]                     # (d_pad, 1)
    z = jnp.dot(x, w, precision=hi)    # (B, 1) MXU
    if kind == "logistic":
        r = jax.nn.sigmoid(z) - y
    else:                              # "linear"
        r = z - y
    out_ref[...] += jnp.dot(r.T, x, precision=hi)   # (1, d_pad) MXU


@functools.partial(jax.jit, static_argnames=("block_rows", "acc_dtype"))
def pad_inputs(x: jnp.ndarray, y: jnp.ndarray, *,
               block_rows: int = BLOCK_ROWS, acc_dtype: str = "float32"):
    """(xp, yp): `x (n, d)` zero-padded to `(n_pad, d_pad)` whole row tiles
    and lanes, `y (n,)` to lane 0 of `(n_pad, 128)`, both in the
    accumulation dtype."""
    dt = jnp.dtype(acc_dtype)
    n, d = x.shape
    d_pad = max(LANES, -(-d // LANES) * LANES)
    padded = max(1, -(-n // block_rows)) * block_rows
    xp = jnp.zeros((padded, d_pad), dt).at[:n, :d].set(x.astype(dt))
    yp = jnp.zeros((padded, LANES), dt).at[:n, 0].set(y.astype(dt))
    return xp, yp


@functools.partial(jax.jit, static_argnames=("kind", "interpret",
                                             "block_rows", "acc_dtype"))
def train_grad_padded(xp: jnp.ndarray, yp: jnp.ndarray, w: jnp.ndarray,
                      kind: str = "logistic", *, interpret: bool = False,
                      block_rows: int = BLOCK_ROWS,
                      acc_dtype: str = "float32"):
    """The gradient of `train_grad` over inputs `pad_inputs` laid out
    (padded with the same `block_rows` and `acc_dtype`): a (d,) vector for
    the (d,) weights `w`."""
    if kind not in ("logistic", "linear"):
        raise ValueError(f"train_grad: unknown kind {kind!r}")
    dt = jnp.dtype(acc_dtype)
    padded, d_pad = xp.shape
    d = w.shape[0]
    wp = jnp.zeros((d_pad, 1), dt).at[:d, 0].set(w.astype(dt))

    grad = pl.pallas_call(
        functools.partial(_grad_kernel, kind=kind),
        grid=(padded // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, d_pad), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((d_pad, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, d_pad), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, d_pad), dt),
        interpret=interpret,
    )(xp, yp, wp)
    return grad[0, :d]


@functools.partial(jax.jit, static_argnames=("kind", "interpret",
                                             "block_rows", "acc_dtype"))
def train_grad(x: jnp.ndarray, y: jnp.ndarray, w: jnp.ndarray,
               kind: str = "logistic", *, interpret: bool = False,
               block_rows: int = BLOCK_ROWS, acc_dtype: str = "float32"):
    """Sum-of-residuals gradient `x.T @ (pred(x @ w) - y)` as a (d,)
    vector, streamed over row tiles.  Callers divide by their row count
    (the kernel returns the unnormalized sum so per-partition partials
    from different splits can be added before normalizing)."""
    xp, yp = pad_inputs(x, y, block_rows=block_rows, acc_dtype=acc_dtype)
    return train_grad_padded(xp, yp, w, kind, interpret=interpret,
                             block_rows=block_rows, acc_dtype=acc_dtype)
