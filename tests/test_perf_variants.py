"""Perf-variant correctness: every §Perf optimization must preserve model
semantics (EXPERIMENTS.md iteration log)."""

import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import lm
from repro.models.attention import _blockwise_attention
from repro.models.flash import flash_attention

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RNG = np.random.default_rng(0)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("s,kv_chunk", [(64, 16), (128, 64), (32, 32)])
def test_flash_matches_blockwise(causal, s, kv_chunk):
    b, h, kv, hd = 2, 8, 2, 16
    q = jnp.asarray(RNG.normal(size=(b, s, h, hd)), jnp.bfloat16)
    k = jnp.asarray(RNG.normal(size=(b, s, kv, hd)), jnp.bfloat16)
    v = jnp.asarray(RNG.normal(size=(b, s, kv, hd)), jnp.bfloat16)
    pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    o1 = np.asarray(flash_attention(q, k, v, pos, kv_chunk, causal),
                    np.float32)
    o2 = np.asarray(_blockwise_attention(q, k, v, pos, kv_chunk, causal),
                    np.float32)
    np.testing.assert_allclose(o1, o2, rtol=0.05, atol=0.05)


def test_flash_gradients_match_autodiff():
    b, s, h, kv, hd = 2, 64, 4, 2, 16
    q = jnp.asarray(RNG.normal(size=(b, s, h, hd)), jnp.bfloat16)
    k = jnp.asarray(RNG.normal(size=(b, s, kv, hd)), jnp.bfloat16)
    v = jnp.asarray(RNG.normal(size=(b, s, kv, hd)), jnp.bfloat16)
    pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))

    def lf(q, k, v):
        return jnp.sum(flash_attention(q, k, v, pos, 16, True)
                       .astype(jnp.float32) ** 2)

    def lr(q, k, v):
        return jnp.sum(_blockwise_attention(q, k, v, pos, 16, True)
                       .astype(jnp.float32) ** 2)

    gf = jax.grad(lf, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lr, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gf, gr):
        a32, b32 = np.asarray(a, np.float32), np.asarray(b_, np.float32)
        assert np.abs(a32 - b32).max() / (np.abs(b32).max() + 1e-9) < 0.06


def test_scores_bf16_loss_close():
    cfg = get_config("yi-9b-smoke")
    cfg_bf = dataclasses.replace(cfg, attn_scores_dtype="bf16")
    params, _ = lm.init_params(cfg, jax.random.PRNGKey(0))
    batch = {"tokens": jnp.asarray(RNG.integers(0, cfg.vocab, (2, 64))
                                   .astype(np.int32)),
             "labels": jnp.asarray(RNG.integers(0, cfg.vocab, (2, 64))
                                   .astype(np.int32))}
    l1 = float(lm.loss_fn(cfg, params, batch))
    l2 = float(lm.loss_fn(cfg_bf, params, batch))
    assert abs(l1 - l2) < 0.02


def test_flash_variant_full_model():
    cfg = dataclasses.replace(get_config("phi3-medium-14b-smoke"),
                              attn_impl="flash")
    base = get_config("phi3-medium-14b-smoke")
    params, _ = lm.init_params(base, jax.random.PRNGKey(0))
    batch = {"tokens": jnp.asarray(RNG.integers(0, base.vocab, (2, 64))
                                   .astype(np.int32)),
             "labels": jnp.asarray(RNG.integers(0, base.vocab, (2, 64))
                                   .astype(np.int32))}
    l1 = float(lm.loss_fn(base, params, batch))
    l2 = float(lm.loss_fn(cfg, params, batch))
    assert abs(l1 - l2) < 0.02, (l1, l2)


def test_kv_int8_decode_close():
    cfg = get_config("phi3-medium-14b-smoke")
    cfgq = dataclasses.replace(cfg, kv_cache_quant=True)
    params, _ = lm.init_params(cfg, jax.random.PRNGKey(0))
    B, S, MAXS = 2, 48, 64
    batch = {"tokens": jnp.asarray(RNG.integers(0, cfg.vocab, (B, S))
                                   .astype(np.int32))}
    lg1, c1 = lm.prefill_fn(cfg, params, batch, MAXS)
    lg2, c2 = lm.prefill_fn(cfgq, params, batch, MAXS)
    assert c2["k"].dtype == jnp.int8
    assert "k_scale" in c2
    tok = jnp.argmax(lg1[:, 0], -1).astype(jnp.int32)[:, None]
    d1, _ = lm.decode_fn(cfg, params, tok, c1, jnp.int32(S))
    d2, _ = lm.decode_fn(cfgq, params, tok, c2, jnp.int32(S))
    p1 = jax.nn.softmax(d1[:, 0], -1)
    p2 = jax.nn.softmax(d2[:, 0], -1)
    tv = 0.5 * np.abs(np.asarray(p1) - np.asarray(p2)).sum(-1).max()
    assert tv < 0.05
    assert (np.asarray(jnp.argmax(d1[:, 0], -1))
            == np.asarray(jnp.argmax(d2[:, 0], -1))).all()


def test_moe_ep_matches_gspmd_subprocess():
    """EP shard_map MoE vs GSPMD MoE on a 8-device debug mesh."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_debug_mesh
from repro.models.moe import MoEConfig, moe_apply, moe_apply_ep, moe_init
mesh = make_debug_mesh(2, 4)
cfg = MoEConfig(num_experts=8, top_k=2, d_expert=32, capacity_factor=4.0)
p, _ = moe_init(jax.random.PRNGKey(0), 64, cfg)
rng = np.random.default_rng(0)
x = jnp.asarray(rng.normal(size=(4, 16, 64)).astype(np.float32)).astype(jnp.bfloat16)
with jax.sharding.set_mesh(mesh):
    y1 = jax.jit(lambda p, x: moe_apply(p, x, cfg))(p, x)
    y2 = jax.jit(lambda p, x: moe_apply_ep(p, x, cfg))(p, x)
rel = np.abs(np.asarray(y1, np.float32) - np.asarray(y2, np.float32)).max()
assert rel < 1e-2, rel
print("OK")
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=300)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "OK" in out.stdout
