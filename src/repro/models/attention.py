"""Attention variants: GQA (RoPE, optional QKV bias), MLA (DeepSeek-V2
latent compression), and cross-attention (VLM / encoder-decoder).

Self-attention uses blockwise online-softmax over KV chunks (flash-attention
semantics in pure JAX): scores for one (queries x kv-chunk) tile exist at a
time, so 32k-token prefill never materializes an S x S matrix.  GQA never
materializes repeated K/V heads — queries reshape to (kv_groups, q_per_kv)
and contract against the raw KV tensors.

Decode attends one query against the full KV cache with a length mask; MLA
caches only the compressed (c_kv, k_rope) streams, decompressing per step.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .common import (Params, Specs, apply_rope, dense_init,
                     stacked_dense_init)

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# GQA parameters
# ---------------------------------------------------------------------------

def gqa_init(key, d_model: int, n_heads: int, n_kv: int, head_dim: int,
             n: Optional[int] = None, qkv_bias: bool = False,
             dtype=jnp.bfloat16) -> Tuple[Params, Specs]:
    ks = jax.random.split(key, 4)
    mk = (lambda k, i, o: dense_init(k, i, o, dtype)) if n is None else \
         (lambda k, i, o: stacked_dense_init(k, n, i, o, dtype))
    lead = () if n is None else (None,)
    p = {"wq": mk(ks[0], d_model, n_heads * head_dim),
         "wk": mk(ks[1], d_model, n_kv * head_dim),
         "wv": mk(ks[2], d_model, n_kv * head_dim),
         "wo": mk(ks[3], n_heads * head_dim, d_model)}
    s = {"wq": P(*lead, None, "model"), "wk": P(*lead, None, "model"),
         "wv": P(*lead, None, "model"), "wo": P(*lead, "model", None)}
    if qkv_bias:
        for nm, width in (("bq", n_heads * head_dim), ("bk", n_kv * head_dim),
                          ("bv", n_kv * head_dim)):
            p[nm] = jnp.zeros((width,) if n is None else (n, width), dtype)
            s[nm] = P(*lead, "model")
    return p, s


def _project_qkv(p: Params, x: jnp.ndarray, n_heads: int, n_kv: int,
                 head_dim: int):
    b, s, _ = x.shape
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if "bq" in p:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    return (q.reshape(b, s, n_heads, head_dim),
            k.reshape(b, s, n_kv, head_dim),
            v.reshape(b, s, n_kv, head_dim))


# ---------------------------------------------------------------------------
# Blockwise causal attention (training / prefill)
# ---------------------------------------------------------------------------

def _blockwise_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                         q_positions: jnp.ndarray, kv_chunk: int,
                         causal: bool, kv_offset: int = 0,
                         scores_dtype: str = "f32",
                         chunk_remat: bool = False) -> jnp.ndarray:
    """q: (B,S,H,hd); k,v: (B,T,KV,hd).  Online softmax over KV chunks.

    scores_dtype="bf16" (perf variant): score/probability tensors — the
    dominant HBM traffic of non-fused attention — are kept in bf16; the
    online-softmax statistics (m, l) and the output accumulator stay f32,
    so softmax normalization keeps full precision.

    chunk_remat=True (perf variant): checkpoints the per-KV-chunk body so
    the scan backward recomputes scores/probs per chunk instead of stashing
    a (n_chunks, B, S, H, C) residual buffer — the flash-attention backward
    strategy expressed in XLA."""
    b, s, h, hd = q.shape
    t = k.shape[1]
    n_kv = k.shape[2]
    g = h // n_kv
    sdt = jnp.bfloat16 if scores_dtype == "bf16" else jnp.float32
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    qg = (q.reshape(b, s, n_kv, g, hd).astype(jnp.float32) * scale) \
        .astype(sdt)

    kv_chunk = min(kv_chunk, t)
    t_orig = t
    if t % kv_chunk != 0:
        pad = kv_chunk - t % kv_chunk
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        t = t + pad
    n_chunks = t // kv_chunk
    kc = k.reshape(b, n_chunks, kv_chunk, n_kv, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, n_chunks, kv_chunk, n_kv, hd).transpose(1, 0, 2, 3, 4)

    def body(carry, xs):
        m, l, acc = carry
        kb, vb, idx = xs
        kpos = idx * kv_chunk + jnp.arange(kv_chunk) + kv_offset
        # kb: (b, chunk, kv_groups, hd); queries grouped per kv head.
        # score/prob tensors live in sdt (bf16 halves the dominant HBM
        # traffic of non-fused attention); softmax stats stay f32.
        scores = jnp.einsum("bsgxd,bcgd->bsgxc", qg, kb.astype(sdt),
                            preferred_element_type=sdt)
        if causal:
            mask = kpos[None, None, None, None, :] \
                <= q_positions[:, :, None, None, None]
            scores = jnp.where(mask, scores, jnp.asarray(NEG_INF, sdt))
        if t != t_orig:  # mask KV padding (non-multiple chunk lengths)
            valid = (kpos < t_orig)[None, None, None, None, :]
            scores = jnp.where(valid, scores, jnp.asarray(NEG_INF, sdt))
        m_new = jnp.maximum(m, jnp.max(scores, axis=-1).astype(jnp.float32))
        p = jnp.exp(scores - m_new[..., None].astype(sdt))
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1, dtype=jnp.float32)
        pv = jnp.einsum("bsgxc,bcgd->bsgxd", p, vb.astype(sdt),
                        preferred_element_type=jnp.float32)
        acc_new = acc * corr[..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, s, n_kv, g), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, s, n_kv, g), jnp.float32)
    acc0 = jnp.zeros((b, s, n_kv, g, hd), jnp.float32)
    body_fn = jax.checkpoint(body) if chunk_remat else body
    (m, l, acc), _ = jax.lax.scan(
        body_fn, (m0, l0, acc0), (kc, vc, jnp.arange(n_chunks)))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.reshape(b, s, h, hd).astype(q.dtype)


def self_attention(p: Params, x: jnp.ndarray, positions: jnp.ndarray,
                   n_heads: int, n_kv: int, head_dim: int, rope_theta: float,
                   kv_chunk: int = 1024, causal: bool = True,
                   return_kv: bool = False, scores_dtype: str = "f32",
                   chunk_remat: bool = False, impl: str = "blockwise",
                   seq_shard: bool = False):
    """Full-sequence causal self-attention (train / prefill)."""
    with jax.named_scope("attention"):
        return _self_attention(p, x, positions, n_heads, n_kv, head_dim,
                               rope_theta, kv_chunk, causal, return_kv,
                               scores_dtype, chunk_remat, impl, seq_shard)


def _self_attention(p, x, positions, n_heads, n_kv, head_dim, rope_theta,
                    kv_chunk, causal, return_kv, scores_dtype="f32",
                    chunk_remat=False, impl="blockwise", seq_shard=False):
    b, s, d = x.shape
    q, k, v = _project_qkv(p, x, n_heads, n_kv, head_dim)
    if rope_theta > 0:
        q = apply_rope(q, positions, rope_theta)
        k = apply_rope(k, positions, rope_theta)
    if seq_shard:
        # context parallelism: queries shard over `model` along the sequence
        # axis (K/V stay whole — they are GQA-small); score tensors then
        # shard 16-ways even when head counts don't divide the mesh.
        from ..parallel.sharding import BATCH_AXES, maybe_shard
        q = maybe_shard(q, P(BATCH_AXES, "model", None, None))
    if impl == "flash" and k.shape[1] % min(kv_chunk, k.shape[1]) == 0:
        from .flash import flash_attention
        out = flash_attention(q, k, v, positions, kv_chunk, causal)
    else:
        out = _blockwise_attention(q, k, v, positions, kv_chunk, causal,
                                   scores_dtype=scores_dtype,
                                   chunk_remat=chunk_remat)
    if seq_shard:
        from ..parallel.sharding import BATCH_AXES, maybe_shard
        out = maybe_shard(out, P(BATCH_AXES, "model", None, None))
    y = out.reshape(b, s, n_heads * head_dim) @ p["wo"]
    if return_kv:
        return y, (k, v)
    return y


def _decode_q_constraint(qg, n_kv: int, head_dim: int):
    """Match the KV cache layout rule (launch/specs.cache_pspecs): when kv
    heads don't divide the model axis, caches shard head_dim; constrain q the
    same way so the score contraction runs as local partial dots + a small
    all-reduce instead of GSPMD gathering the cache (perf iteration C3)."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or "model" not in mesh.axis_names:
        return qg
    msize = dict(zip(mesh.axis_names, mesh.axis_sizes))["model"]
    if n_kv % msize == 0 or head_dim % msize != 0:
        return qg
    from ..parallel.sharding import BATCH_AXES, maybe_shard
    return maybe_shard(qg, P(BATCH_AXES, None, None, "model"))


def decode_attention(p: Params, x: jnp.ndarray, cache_k: jnp.ndarray,
                     cache_v: jnp.ndarray, cur_len: jnp.ndarray,
                     n_heads: int, n_kv: int, head_dim: int,
                     rope_theta: float):
    """One-token decode: x (B,1,D); cache (B,Smax,KV,hd); cur_len scalar =
    number of valid cache entries (the new token is written at cur_len).

    The cache is consumed at its storage dtype (bf16) with f32 accumulation
    inside the dots — decode is KV-bandwidth-bound, so upcasting the cache
    to f32 would double the dominant traffic term (perf iteration C1)."""
    with jax.named_scope("attention"):
        b, s1, d = x.shape
        q, k, v = _project_qkv(p, x, n_heads, n_kv, head_dim)
        pos = jnp.full((b, 1), cur_len, jnp.int32)
        if rope_theta > 0:
            q = apply_rope(q, pos, rope_theta)
            k = apply_rope(k, pos, rope_theta)
        cache_k = jax.lax.dynamic_update_slice(
            cache_k, k.astype(cache_k.dtype), (0, cur_len, 0, 0))
        cache_v = jax.lax.dynamic_update_slice(
            cache_v, v.astype(cache_v.dtype), (0, cur_len, 0, 0))
        t = cache_k.shape[1]
        g = n_heads // n_kv
        scale = 1.0 / jnp.sqrt(jnp.asarray(head_dim, jnp.float32))
        qg = (q.reshape(b, n_kv, g, head_dim).astype(jnp.float32)
              * scale).astype(cache_k.dtype)
        qg = _decode_q_constraint(qg, n_kv, head_dim)
        scores = jnp.einsum("bgxd,btgd->bgxt", qg, cache_k,
                            preferred_element_type=jnp.float32)
        mask = jnp.arange(t)[None, None, None, :] <= cur_len
        scores = jnp.where(mask, scores, NEG_INF)
        w = jax.nn.softmax(scores, axis=-1).astype(cache_v.dtype)
        out = jnp.einsum("bgxt,btgd->bgxd", w, cache_v,
                         preferred_element_type=jnp.float32)
        y = out.reshape(b, 1, n_heads * head_dim).astype(x.dtype) @ p["wo"]
        return y, (cache_k, cache_v)


# -- int8-quantized KV cache (perf variant `kv_int8`) ------------------------
#
# Shark's S3.2 insight applied to the KV store: compression is a bandwidth
# optimization.  K/V quantize symmetrically per (token, head) to int8 at
# prefill/append; scores factor exactly as (q . k_q) * k_scale, so the dot
# streams int8 and the dequant rides the scale multiply — halving the
# decode-dominant cache read traffic and the cache HBM footprint.

def quantize_kv(x: jnp.ndarray):
    """x: (..., hd) -> (int8 values, bf16 per-(...)-scales)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    s = jnp.maximum(amax / 127.0, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / s), -127, 127
                 ).astype(jnp.int8)
    return q, s[..., 0].astype(jnp.bfloat16)


def decode_attention_q8(p: Params, x: jnp.ndarray, cache_k: jnp.ndarray,
                        k_scale: jnp.ndarray, cache_v: jnp.ndarray,
                        v_scale: jnp.ndarray, cur_len: jnp.ndarray,
                        n_heads: int, n_kv: int, head_dim: int,
                        rope_theta: float):
    """Decode against an int8 cache.  cache_k/v: (B,Smax,KV,hd) int8;
    k_scale/v_scale: (B,Smax,KV) bf16."""
    with jax.named_scope("attention"):
        b, s1, d = x.shape
        q, k, v = _project_qkv(p, x, n_heads, n_kv, head_dim)
        pos = jnp.full((b, 1), cur_len, jnp.int32)
        if rope_theta > 0:
            q = apply_rope(q, pos, rope_theta)
            k = apply_rope(k, pos, rope_theta)
        kq, ks = quantize_kv(k)
        vq, vs = quantize_kv(v)
        cache_k = jax.lax.dynamic_update_slice(cache_k, kq,
                                               (0, cur_len, 0, 0))
        k_scale = jax.lax.dynamic_update_slice(k_scale, ks, (0, cur_len, 0))
        cache_v = jax.lax.dynamic_update_slice(cache_v, vq,
                                               (0, cur_len, 0, 0))
        v_scale = jax.lax.dynamic_update_slice(v_scale, vs, (0, cur_len, 0))
        t = cache_k.shape[1]
        g = n_heads // n_kv
        scale = 1.0 / jnp.sqrt(jnp.asarray(head_dim, jnp.float32))
        qg = (q.reshape(b, n_kv, g, head_dim).astype(jnp.float32)
              * scale).astype(jnp.bfloat16)
        qg = _decode_q_constraint(qg, n_kv, head_dim)
        # (q . k_q) * s_k — the int8 stream converts in-register on TPU
        raw = jnp.einsum("bgxd,btgd->bgxt", qg,
                         cache_k.astype(jnp.bfloat16),
                         preferred_element_type=jnp.float32)
        scores = raw * k_scale.transpose(0, 2, 1)[:, :, None, :] \
            .astype(jnp.float32)
        mask = jnp.arange(t)[None, None, None, :] <= cur_len
        scores = jnp.where(mask, scores, NEG_INF)
        w = jax.nn.softmax(scores, axis=-1)
        wv = (w * v_scale.transpose(0, 2, 1)[:, :, None, :]
              .astype(jnp.float32)).astype(jnp.bfloat16)
        out = jnp.einsum("bgxt,btgd->bgxd", wv,
                         cache_v.astype(jnp.bfloat16),
                         preferred_element_type=jnp.float32)
        y = out.reshape(b, 1, n_heads * head_dim).astype(x.dtype) @ p["wo"]
        return y, (cache_k, k_scale, cache_v, v_scale)


# ---------------------------------------------------------------------------
# Cross-attention (VLM image layers, enc-dec decoders)
# ---------------------------------------------------------------------------

def cross_attention(p: Params, x: jnp.ndarray, kv_src: jnp.ndarray,
                    n_heads: int, n_kv: int, head_dim: int,
                    kv_chunk: int = 512):
    """x: (B,S,D) queries; kv_src: (B,T,D) encoder/image states."""
    b, s, d = x.shape
    t = kv_src.shape[1]
    q = (x @ p["wq"]).reshape(b, s, n_heads, head_dim)
    k = (kv_src @ p["wk"]).reshape(b, t, n_kv, head_dim)
    v = (kv_src @ p["wv"]).reshape(b, t, n_kv, head_dim)
    positions = jnp.zeros((b, s), jnp.int32)
    out = _blockwise_attention(q, k, v, positions, min(kv_chunk, t),
                               causal=False)
    return out.reshape(b, s, n_heads * head_dim) @ p["wo"]


def cross_attention_cached(p: Params, x: jnp.ndarray, k: jnp.ndarray,
                           v: jnp.ndarray, n_heads: int, n_kv: int,
                           head_dim: int):
    """Decode-time cross-attention against precomputed encoder K/V."""
    b, s, _ = x.shape
    q = (x @ p["wq"]).reshape(b, s, n_heads, head_dim)
    g = n_heads // n_kv
    scale = 1.0 / jnp.sqrt(jnp.asarray(head_dim, jnp.float32))
    qg = q.reshape(b, s, n_kv, g, head_dim).astype(jnp.float32) * scale
    scores = jnp.einsum("bsgxd,btgd->bsgxt", qg, k.astype(jnp.float32))
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bsgxt,btgd->bsgxd", w, v.astype(jnp.float32))
    return out.reshape(b, s, n_heads * head_dim).astype(x.dtype) @ p["wo"]


def cross_kv(p: Params, kv_src: jnp.ndarray, n_kv: int, head_dim: int):
    b, t, _ = kv_src.shape
    k = (kv_src @ p["wk"]).reshape(b, t, n_kv, head_dim)
    v = (kv_src @ p["wv"]).reshape(b, t, n_kv, head_dim)
    return k, v


# ---------------------------------------------------------------------------
# MLA — Multi-head Latent Attention (DeepSeek-V2), naive/faithful mode
# ---------------------------------------------------------------------------

def mla_init(key, d_model: int, n_heads: int, kv_lora: int, nope_dim: int,
             rope_dim: int, v_dim: int, n: Optional[int] = None,
             dtype=jnp.bfloat16) -> Tuple[Params, Specs]:
    ks = jax.random.split(key, 6)
    mk = (lambda k, i, o: dense_init(k, i, o, dtype)) if n is None else \
         (lambda k, i, o: stacked_dense_init(k, n, i, o, dtype))
    lead = () if n is None else (None,)
    p = {
        "wq": mk(ks[0], d_model, n_heads * (nope_dim + rope_dim)),
        "wdkv": mk(ks[1], d_model, kv_lora),
        "wkr": mk(ks[2], d_model, rope_dim),
        "wuk": mk(ks[3], kv_lora, n_heads * nope_dim),
        "wuv": mk(ks[4], kv_lora, n_heads * v_dim),
        "wo": mk(ks[5], n_heads * v_dim, d_model),
        "kv_norm": jnp.ones((kv_lora,) if n is None else (n, kv_lora),
                            jnp.float32),
    }
    s = {
        "wq": P(*lead, None, "model"), "wdkv": P(*lead, None, None),
        "wkr": P(*lead, None, None), "wuk": P(*lead, None, "model"),
        "wuv": P(*lead, None, "model"), "wo": P(*lead, "model", None),
        "kv_norm": P(*lead, None),
    }
    return p, s


def _mla_qkv(p: Params, x: jnp.ndarray, positions, n_heads, nope_dim,
             rope_dim, v_dim):
    from .common import rmsnorm
    b, s, _ = x.shape
    q = (x @ p["wq"]).reshape(b, s, n_heads, nope_dim + rope_dim)
    q_nope, q_rope = q[..., :nope_dim], q[..., nope_dim:]
    q_rope = apply_rope(q_rope, positions, 10000.0)
    c_kv = rmsnorm(x @ p["wdkv"], p["kv_norm"])          # (b,s,lora)
    k_rope = (x @ p["wkr"]).reshape(b, s, 1, rope_dim)
    k_rope = apply_rope(k_rope, positions, 10000.0)
    return q_nope, q_rope, c_kv, k_rope


def mla_attention(p: Params, x: jnp.ndarray, positions: jnp.ndarray,
                  n_heads: int, nope_dim: int, rope_dim: int, v_dim: int,
                  kv_chunk: int = 1024, return_kv: bool = False,
                  seq_shard: bool = False):
    """Training/prefill MLA.  Decompresses K/V per KV-chunk inside the
    blockwise loop, so full (S, H, nope+v) tensors never materialize.

    seq_shard: context-parallel queries (same rationale as GQA — MLA's 16
    heads don't divide a model=16 mesh once grouped, and the score tensors
    are the traffic hotspot)."""
    b, s, _ = x.shape
    q_nope, q_rope, c_kv, k_rope = _mla_qkv(p, x, positions, n_heads,
                                            nope_dim, rope_dim, v_dim)
    if seq_shard:
        from ..parallel.sharding import BATCH_AXES, maybe_shard
        q_nope = maybe_shard(q_nope, P(BATCH_AXES, "model", None, None))
        q_rope = maybe_shard(q_rope, P(BATCH_AXES, "model", None, None))
    scale = 1.0 / jnp.sqrt(jnp.asarray(nope_dim + rope_dim, jnp.float32))
    kv_chunk = min(kv_chunk, s)
    assert s % kv_chunk == 0
    n_chunks = s // kv_chunk
    wuk = p["wuk"].reshape(-1, n_heads, nope_dim)
    wuv = p["wuv"].reshape(-1, n_heads, v_dim)

    ckv_c = c_kv.reshape(b, n_chunks, kv_chunk, -1).transpose(1, 0, 2, 3)
    krope_c = k_rope.reshape(b, n_chunks, kv_chunk, rope_dim) \
        .transpose(1, 0, 2, 3)

    qn = q_nope.astype(jnp.float32) * scale
    qr = q_rope.astype(jnp.float32) * scale

    def body(carry, xs):
        m, l, acc = carry
        ckv, kr, idx = xs
        kpos = idx * kv_chunk + jnp.arange(kv_chunk)
        k_nope = jnp.einsum("bcl,lhd->bchd", ckv, wuk)     # decompress K
        v = jnp.einsum("bcl,lhv->bchv", ckv, wuv)          # decompress V
        sc = jnp.einsum("bshd,bchd->bshc", qn, k_nope.astype(jnp.float32))
        sc = sc + jnp.einsum("bshr,bcr->bshc", qr, kr.astype(jnp.float32))
        mask = kpos[None, None, None, :] <= positions[:, :, None, None]
        sc = jnp.where(mask, sc, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1))
        pr = jnp.exp(sc - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(pr, axis=-1)
        pv = jnp.einsum("bshc,bchv->bshv", pr, v.astype(jnp.float32))
        acc_new = acc * corr[..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, s, n_heads), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, s, n_heads), jnp.float32)
    acc0 = jnp.zeros((b, s, n_heads, v_dim), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, acc0),
                                  (ckv_c, krope_c, jnp.arange(n_chunks)))
    out = (acc / jnp.maximum(l[..., None], 1e-30)).astype(x.dtype)
    if seq_shard:
        from ..parallel.sharding import BATCH_AXES, maybe_shard
        out = maybe_shard(out, P(BATCH_AXES, "model", None, None))
    y = out.reshape(b, s, n_heads * v_dim) @ p["wo"]
    if return_kv:
        return y, (c_kv, k_rope[:, :, 0, :])
    return y


def mla_decode(p: Params, x: jnp.ndarray, cache_ckv: jnp.ndarray,
               cache_kr: jnp.ndarray, cur_len: jnp.ndarray, n_heads: int,
               nope_dim: int, rope_dim: int, v_dim: int):
    """One-token MLA decode against the compressed cache
    (cache_ckv: (B,Smax,lora); cache_kr: (B,Smax,rope))."""
    b = x.shape[0]
    pos = jnp.full((b, 1), cur_len, jnp.int32)
    q_nope, q_rope, c_kv, k_rope = _mla_qkv(p, x, pos, n_heads, nope_dim,
                                            rope_dim, v_dim)
    cache_ckv = jax.lax.dynamic_update_slice(
        cache_ckv, c_kv.astype(cache_ckv.dtype), (0, cur_len, 0))
    cache_kr = jax.lax.dynamic_update_slice(
        cache_kr, k_rope[:, :, 0, :].astype(cache_kr.dtype), (0, cur_len, 0))
    t = cache_ckv.shape[1]
    scale = 1.0 / jnp.sqrt(jnp.asarray(nope_dim + rope_dim, jnp.float32))
    wuk = p["wuk"].reshape(-1, n_heads, nope_dim)
    wuv = p["wuv"].reshape(-1, n_heads, v_dim)
    # absorbed-score trick for decode: q_nope^T (c_kv W_uk) = (q_nope W_uk^T) c_kv
    q_abs = jnp.einsum("bshd,lhd->bshl", q_nope.astype(jnp.float32), wuk)
    sc = jnp.einsum("bshl,btl->bsht", q_abs,
                    cache_ckv.astype(jnp.float32)) * scale
    sc = sc + jnp.einsum("bshr,btr->bsht",
                         q_rope.astype(jnp.float32) * scale,
                         cache_kr.astype(jnp.float32))
    mask = jnp.arange(t)[None, None, None, :] <= cur_len
    sc = jnp.where(mask, sc, NEG_INF)
    w = jax.nn.softmax(sc, axis=-1)
    # attention over compressed V, decompress after weighting (absorbed-V)
    ctx = jnp.einsum("bsht,btl->bshl", w, cache_ckv.astype(jnp.float32))
    out = jnp.einsum("bshl,lhv->bshv", ctx, wuv)
    y = out.reshape(b, 1, n_heads * v_dim).astype(x.dtype) @ p["wo"]
    return y, (cache_ckv, cache_kr)
