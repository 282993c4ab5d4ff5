"""PDE applied to MoE training: the paper's statistics-driven replanning
(§3.1) closing the loop on expert routing.

Trains a reduced phi3.5-MoE on a SQL-selected corpus; every step the router
emits per-expert load (the paper's "heavy hitters" accumulator), the
replanner keeps a lossy 1-byte-encoded history, and at stage boundaries it
re-selects the capacity factor from observed p99 load — snapping to buckets
so the jit cache stays small (the "pre-lowered stage-2 variants" pattern).

    PYTHONPATH=src python examples/pde_moe_training.py
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.core import SharkSession
from repro.data import TokenPipeline, synthetic_corpus
from repro.models import lm
from repro.models import moe as moe_mod
from repro.training import AdamWConfig, init_opt_state, make_train_step
from repro.training.pde_moe import MoEReplanner

enable_compile_cache()
cfg = get_config("phi3.5-moe-42b-a6.6b-smoke")
sess = SharkSession(num_workers=2, max_threads=2)
synthetic_corpus(sess, "corpus", cfg.vocab, n_docs=60, mean_doc_len=256)
pipe = TokenPipeline(sess, "corpus", seq_len=32, global_batch=8,
                     sql_filter="quality > 0.25")
print(f"SQL-selected corpus: {len(pipe.stream)} tokens")

params, _ = lm.init_params(cfg, jax.random.PRNGKey(0))
opt_state = init_opt_state(params)
replanner = MoEReplanner(cfg.moe.num_experts, cfg.moe.top_k)
tokens_per_step = 8 * 32

step_fns = {}  # capacity bucket -> compiled step (pre-lowered variants)
current_cf = cfg.moe.capacity_factor

for step in range(30):
    if step % 10 == 0 and step > 0:
        plan = replanner.plan(tokens_per_step)
        if plan.capacity_factor != current_cf:
            print(f"  [PDE] step {step}: re-planning — {plan.reason}")
            current_cf = plan.capacity_factor
        else:
            print(f"  [PDE] step {step}: plan unchanged ({plan.reason})")
    if current_cf not in step_fns:
        c = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe,
                                         capacity_factor=current_cf))
        step_fns[current_cf] = jax.jit(make_train_step(c, AdamWConfig(lr=3e-3)))
    batch = {k: jnp.asarray(v) for k, v in pipe.batch_at(step).items()}
    params, opt_state, metrics = step_fns[current_cf](params, opt_state,
                                                      batch)
    # observe expert load (stats already computed inside the step's MoE)
    lp = jax.tree_util.tree_map(lambda x: x, params)  # params current
    x = lm.embed_lookup(params["embed"], batch["tokens"])
    _, stats = moe_mod.moe_apply(
        jax.tree.map(lambda a: a[0], params["layers"]["moe"]), x, cfg.moe,
        return_stats=True)
    replanner.observe(np.asarray(stats["expert_load"]))
    if step % 5 == 0:
        print(f"step {step:3d} loss {float(metrics['loss']):.4f} "
              f"cf={current_cf} compiled_variants={len(step_fns)}")

print(f"done; executable cache held {len(step_fns)} capacity variants")
sess.shutdown()
