"""End-to-end behaviour tests: the paper's headline workflows wired through
the full system (SQL warehouse -> ML -> LM training), plus a subprocess
dry-run on a small mesh proving the distributed lowering path."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import DType, Schema, SharkSession

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_paper_listing1_workflow():
    """Listing 1: sql2rdd -> feature extraction -> logistic regression,
    all in one lineage graph, surviving a worker failure."""
    from repro.ml import LogisticRegression, table_rdd_to_features
    rng = np.random.default_rng(0)
    n, d = 6000, 8
    w_true = rng.normal(size=d)
    X = rng.normal(size=(n, d))
    y = (X @ w_true > 0).astype(np.float32)
    sess = SharkSession(num_workers=4, max_threads=4)
    cols = {f"f{i}": X[:, i].astype(np.float32) for i in range(d)}
    cols["label"] = y
    sess.create_table("users", Schema.of(
        **{f"f{i}": DType.FLOAT32 for i in range(d)}, label=DType.FLOAT32),
        cols)
    rdd, names = sess.sql2rdd("SELECT * FROM users WHERE f0 > -10")
    feats = table_rdd_to_features(rdd, [f"f{i}" for i in range(d)], "label")
    clf = LogisticRegression(dims=d, lr=0.5, iterations=5).fit(feats)
    sess.ctx.scheduler.kill_worker(0)      # node failure mid-workflow
    clf.iterations = 5
    clf.fit(feats)                          # lineage recomputes lost parts
    assert (clf.predict(X) == y).mean() > 0.9
    sess.shutdown()


def test_sql_to_training_pipeline():
    """SQL-selected corpus feeds LM training; loss decreases."""
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.data import TokenPipeline, synthetic_corpus
    from repro.models import lm
    from repro.training import AdamWConfig, init_opt_state, make_train_step

    sess = SharkSession(num_workers=2, max_threads=2)
    cfg = get_config("mamba2-370m-smoke")
    synthetic_corpus(sess, "corpus", cfg.vocab, n_docs=40, mean_doc_len=128)
    pipe = TokenPipeline(sess, "corpus", 32, 8, sql_filter="quality > 0.2")
    params, _ = lm.init_params(cfg, jax.random.PRNGKey(0))
    opt_state = init_opt_state(params)
    step_fn = jax.jit(make_train_step(cfg, AdamWConfig(lr=5e-3)))
    losses = []
    for s in range(10):
        batch = {k: jnp.asarray(v) for k, v in pipe.batch_at(s).items()}
        params, opt_state, m = step_fn(params, opt_state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0]
    sess.shutdown()


@pytest.mark.parametrize("multi_pod", [False, True])
def test_dryrun_small_mesh_subprocess(multi_pod):
    """The dry-run path (mesh + specs + lower + compile + analysis) on an
    8-device debug mesh, in a subprocess so the device-count flag applies."""
    code = f"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, json
from repro.configs import get_config, SHAPES, ShapeConfig
from repro.launch.mesh import make_debug_mesh
from repro.launch.specs import build_cell
from repro.launch.hlo_analysis import analyze_compiled
import dataclasses
cfg = get_config("qwen2.5-3b-smoke")
mesh = make_debug_mesh(2, 2, pod={2 if multi_pod else None})
shape = ShapeConfig("t", "train", 64, 8)
fn, arg_shapes, in_sh, out_sh = build_cell(cfg, shape, mesh)
with jax.sharding.set_mesh(mesh):
    compiled = jax.jit(fn, in_shardings=in_sh,
                       out_shardings=out_sh).lower(*arg_shapes).compile()
a = analyze_compiled(compiled)
assert a["roofline"]["flops"] > 0
assert a["roofline"]["wire_bytes"] > 0, "expected collectives on a mesh"
sh2 = ShapeConfig("d", "decode", 128, 8)
fn, arg_shapes, in_sh, out_sh = build_cell(cfg, sh2, mesh)
with jax.sharding.set_mesh(mesh):
    compiled = jax.jit(fn, in_shardings=in_sh,
                       out_shardings=out_sh).lower(*arg_shapes).compile()
print("SUBPROCESS_OK")
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=300)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "SUBPROCESS_OK" in out.stdout


def test_serving_greedy_deterministic():
    import jax
    from repro.configs import get_config
    from repro.models import lm
    from repro.serving import ServeEngine
    cfg = get_config("yi-9b-smoke")
    params, _ = lm.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)
    prompts = rng.integers(0, cfg.vocab, (2, 16)).astype(np.int32)
    out1 = ServeEngine(cfg, params, max_seq=48).generate(prompts, 8)
    out2 = ServeEngine(cfg, params, max_seq=48).generate(prompts, 8)
    np.testing.assert_array_equal(out1, out2)
