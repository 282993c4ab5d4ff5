"""Top-k similarity search — Pallas TPU kernel (DESIGN.md §15.3).

The vector-analytics hot path: score n candidate embeddings against one
query vector (dot product) and keep the k best.  The naive form
materializes all n scores to HBM and sorts; this kernel streams candidate
row-tiles HBM->VMEM, computes the (tile x query) dot product on the MXU,
and merges each tile's scores into a running top-k that lives in the
revisited output block for the whole sweep — HBM traffic is one read of
the candidate matrix and one (1, k_pad) result write.

The running merge is rank-selection, not a sort: for the concatenation of
the carried top-k and the tile's scores, element i's rank is the count of
elements that beat it — score strictly greater, or equal score with a
smaller candidate index.  (score, index) pairs are unique, so ranks are a
permutation and a one-hot rank->slot matmul scatters the k best into
slot order.  That tie-break (equal scores keep the smaller row index) is
exactly numpy's stable `argsort(-scores)[:k]`, asserted by the
tests/test_kernels_topk.py parity suite, including k > rows edges.

One caveat on ties: the kernel orders by ITS dot products, whose rounding
can differ from a host-computed score by reduction order (padded MXU
matmul vs BLAS).  Ties in the mathematical score are therefore only
guaranteed to resolve identically when the products are exact (e.g.
integer-valued lanes, the parity tests' tie cases); for continuous data
distinct scores never sit within a reduction-order ulp of each other in
practice, so orderings agree.

Tiling follows colscan/flash_attention: a 1-D grid over row tiles with the
minor dimension padded to 128 lanes; the merge state carries across grid
steps through the constant-index output block (sequential TPU grids
revisit it without flushing).  `acc_dtype` is float32 on TPU and float64
in interpret mode so CPU parity with the float64 numpy oracle holds to
rounding.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_ROWS = 1024       # candidate rows per grid step (8x128 VPU tiles)
LANES = 128

NEG_INF = -jnp.inf


def _topk_kernel(x_ref, q_ref, out_s_ref, out_i_ref, *, n: int,
                 block_rows: int, k_pad: int, num_blocks: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def init():
        # empty slots: -inf scores with unique indices beyond every real
        # row, so the total order (score desc, index asc) stays strict
        out_s_ref[...] = jnp.full((1, k_pad), NEG_INF, out_s_ref.dtype)
        out_i_ref[...] = (num_blocks * block_rows
                          + jax.lax.broadcasted_iota(jnp.int32, (1, k_pad),
                                                     1))

    x = x_ref[...]                                     # (B, d_pad)
    qv = q_ref[...]                                    # (d_pad, 1)
    s_tile = jnp.dot(x, qv,                            # (1, B) MXU dot
                     precision=jax.lax.Precision.HIGHEST).T
    gi = (i * block_rows
          + jax.lax.broadcasted_iota(jnp.int32, (1, block_rows),
                                     1)).astype(jnp.int32)
    s_tile = jnp.where(gi < n, s_tile, NEG_INF)        # mask padding rows

    cs = jnp.concatenate([out_s_ref[...], s_tile], axis=1)   # (1, M)
    ci = jnp.concatenate([out_i_ref[...], gi], axis=1)       # (1, M)
    # rank[i] = |{j : s_j > s_i or (s_j == s_i and idx_j < idx_i)}| —
    # carried entries precede the tile in ci order, so equal scores resolve
    # to the smaller global index exactly like the stable host argsort
    beats = (cs > cs.T) | ((cs == cs.T) & (ci < ci.T))       # (M, M)
    rank = jnp.sum(beats.astype(jnp.int32), axis=1, keepdims=True)  # (M, 1)
    slot = jax.lax.broadcasted_iota(jnp.int32, rank.shape[:1] + (k_pad,), 1)
    sel = rank == slot                                       # (M, k_pad)
    out_s_ref[...] = jnp.sum(jnp.where(sel, cs.T, 0.0), axis=0,
                             keepdims=True)
    out_i_ref[...] = jnp.sum(jnp.where(sel, ci.T, 0), axis=0, keepdims=True,
                             dtype=jnp.int32)


@functools.partial(jax.jit, static_argnames=("k", "interpret", "block_rows",
                                             "acc_dtype"))
def topk_similarity(x: jnp.ndarray, q: jnp.ndarray, k: int, *,
                    interpret: bool = False, block_rows: int = BLOCK_ROWS,
                    acc_dtype: str = "float32"):
    """(scores, row indices) of the min(k, n) candidates in `x` (n x d)
    most similar to `q` (d,) by dot product, scores descending, ties by
    ascending row index.  Rows and lanes are zero-padded to whole tiles;
    padding rows are masked to -inf inside the kernel so they never win a
    slot while real rows remain."""
    dt = jnp.dtype(acc_dtype)
    n, d = x.shape
    m = min(int(k), n)
    d_pad = max(LANES, -(-d // LANES) * LANES)
    num_blocks = max(1, -(-n // block_rows))
    padded = num_blocks * block_rows
    k_pad = max(LANES, -(-max(m, 1) // LANES) * LANES)
    xp = jnp.zeros((padded, d_pad), dt).at[:n, :d].set(x.astype(dt))
    qp = jnp.zeros((d_pad, 1), dt).at[:d, 0].set(q.astype(dt))

    out_s, out_i = pl.pallas_call(
        functools.partial(_topk_kernel, n=n, block_rows=block_rows,
                          k_pad=k_pad, num_blocks=num_blocks),
        grid=(num_blocks,),
        in_specs=[
            pl.BlockSpec((block_rows, d_pad), lambda i: (i, 0)),
            pl.BlockSpec((d_pad, 1), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, k_pad), lambda i: (0, 0)),
            pl.BlockSpec((1, k_pad), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, k_pad), dt),
            jax.ShapeDtypeStruct((1, k_pad), jnp.int32),
        ],
        interpret=interpret,
    )(xp, qp)
    return out_s[0, :m], out_i[0, :m]
