"""Dictionary / bit-pack / RLE decode kernels — Pallas TPU.

Shark's columnar compression (§3.2) is a *bandwidth* optimization on TPU:
HBM->VMEM traffic shrinks by the compression ratio, and decode happens in
VMEM right where the consuming scan needs it.  Each kernel streams the
encoded stream tile-by-tile and materializes decoded tiles only in VMEM.

  * dict_decode: codes gather into a (small, fully VMEM-resident) dictionary;
  * bitpack_decode: uint32 words -> per-lane shift/mask unpack (VPU);
  * rle_decode: run values + cumulative ends; each output tile computes its
    run index with a broadcasted compare-and-sum against the (VMEM-resident)
    ends vector — O(tile x runs) VPU ops, no serial scan;

The scan over a dict-coded filter column (`ops.fused_decode_scan`) needs no
decoder at all: the engine's dictionaries are sorted, so value bounds become
code bounds and the colscan kernel compares the int32 codes directly.

None of these three decoders compiles for the TPU (Mosaic refuses the
vector gather, the dynamic run-index gather and the (n, per_word) -> (n *
per_word) reshape), and the engine calls none of them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK = 8 * 128


def _dict_decode_kernel(codes_ref, dict_ref, out_ref):
    out_ref[...] = dict_ref[codes_ref[...]]


@functools.partial(jax.jit, static_argnames=("interpret", "block"))
def dict_decode(codes: jnp.ndarray, dictionary: jnp.ndarray, *,
                interpret: bool = False, block: int = BLOCK) -> jnp.ndarray:
    n = codes.shape[0]
    d = dictionary.shape[0]
    num_blocks = max(1, -(-n // block))
    padded = num_blocks * block
    c = jnp.zeros((padded,), jnp.int32).at[:n].set(codes.astype(jnp.int32))
    out = pl.pallas_call(
        _dict_decode_kernel,
        grid=(num_blocks,),
        in_specs=[pl.BlockSpec((block,), lambda i: (i,)),
                  pl.BlockSpec((d,), lambda i: (0,))],
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((padded,), dictionary.dtype),
        interpret=interpret,
    )(c, dictionary)
    return out[:n]


def _bitpack_kernel(words_ref, out_ref, *, bit_width: int, bias: int):
    per_word = 32 // bit_width
    w = words_ref[...]
    shifts = (jnp.arange(per_word, dtype=jnp.uint32) * bit_width)
    lanes = (w[:, None] >> shifts[None, :]) & jnp.uint32((1 << bit_width) - 1)
    out_ref[...] = lanes.reshape(-1).astype(jnp.int32) + bias


@functools.partial(jax.jit,
                   static_argnames=("bit_width", "bias", "n", "interpret",
                                    "block_words"))
def bitpack_decode(words: jnp.ndarray, *, bit_width: int, bias: int, n: int,
                   interpret: bool = False,
                   block_words: int = 1024) -> jnp.ndarray:
    per_word = 32 // bit_width
    nw = words.shape[0]
    num_blocks = max(1, -(-nw // block_words))
    padded = num_blocks * block_words
    w = jnp.zeros((padded,), jnp.uint32).at[:nw].set(words)
    out = pl.pallas_call(
        functools.partial(_bitpack_kernel, bit_width=bit_width, bias=bias),
        grid=(num_blocks,),
        in_specs=[pl.BlockSpec((block_words,), lambda i: (i,))],
        out_specs=pl.BlockSpec((block_words * per_word,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((padded * per_word,), jnp.int32),
        interpret=interpret,
    )(w)
    return out[:n]


def _rle_kernel(ends_ref, vals_ref, out_ref, *, block: int):
    i = pl.program_id(0)
    pos = jax.lax.broadcasted_iota(jnp.int32, (block,), 0) + i * block
    ends = ends_ref[...]
    # run index of each position: number of run-ends <= pos
    idx = jnp.sum((ends[None, :] <= pos[:, None]).astype(jnp.int32), axis=1)
    idx = jnp.minimum(idx, ends.shape[0] - 1)
    out_ref[...] = vals_ref[idx]


@functools.partial(jax.jit, static_argnames=("n", "interpret", "block"))
def rle_decode(run_values: jnp.ndarray, run_ends: jnp.ndarray, *, n: int,
               interpret: bool = False, block: int = BLOCK) -> jnp.ndarray:
    r = run_values.shape[0]
    num_blocks = max(1, -(-n // block))
    out = pl.pallas_call(
        functools.partial(_rle_kernel, block=block),
        grid=(num_blocks,),
        in_specs=[pl.BlockSpec((r,), lambda i: (0,)),
                  pl.BlockSpec((r,), lambda i: (0,))],
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((num_blocks * block,),
                                       run_values.dtype),
        interpret=interpret,
    )(run_ends.astype(jnp.int32), run_values)
    return out[:n]
