"""Elastic scaling: a checkpoint taken on one mesh restores and continues
training on a DIFFERENT mesh (the 1000-node fault-tolerance story: a job
restarted after losing a pod re-shards onto whatever is left)."""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_checkpoint_elastic_mesh_resize():
    code = """
import os, tempfile
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.checkpoint import CheckpointManager
from repro.configs import get_config
from repro.launch.mesh import make_debug_mesh
from repro.models import lm
from repro.training import AdamWConfig, init_opt_state, make_train_step

cfg = get_config("qwen2.5-3b-smoke")
rng = np.random.default_rng(0)
batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (8, 32)).astype(np.int32)),
         "labels": jnp.asarray(rng.integers(0, cfg.vocab, (8, 32)).astype(np.int32))}
step = make_train_step(cfg, AdamWConfig(lr=1e-3))

# train 3 steps on mesh A = (data=4, model=2)
mesh_a = make_debug_mesh(4, 2)
params, _ = lm.init_params(cfg, jax.random.PRNGKey(0))
opt = init_opt_state(params)
with jax.sharding.set_mesh(mesh_a):
    fa = jax.jit(step)
    for _ in range(3):
        params, opt, m = fa(params, opt, batch)
loss_a = float(m["loss"])

with tempfile.TemporaryDirectory() as d:
    mgr = CheckpointManager(d, async_save=False)
    mgr.save(3, {"params": params, "opt": opt})
    # "pod failure": restart on mesh B = (data=2, model=2) — 4 devices
    restored, man = mgr.restore_latest({"params": params, "opt": opt})
    mesh_b = make_debug_mesh(2, 2)
    with jax.sharding.set_mesh(mesh_b):
        fb = jax.jit(step)
        p2, o2, m2 = fb(restored["params"], restored["opt"], batch)
    assert int(o2["step"]) == 4
    assert np.isfinite(float(m2["loss"]))
    # and scale UP to mesh C = (data=4, model=2) again
    mesh_c = make_debug_mesh(4, 2)
    with jax.sharding.set_mesh(mesh_c):
        fc = jax.jit(step)
        p3, o3, m3 = fc(restored["params"], restored["opt"], batch)
    # same step from the same checkpoint on different meshes: same loss
    assert abs(float(m2["loss"]) - float(m3["loss"])) < 1e-2
print("ELASTIC_OK", loss_a)
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=560)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "ELASTIC_OK" in out.stdout
