import os
os.environ["XLA_FLAGS"] = (os.environ.get("DRYRUN_EXTRA_XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run: lower + compile every (architecture x input-shape x
mesh) cell with ShapeDtypeStruct inputs (no allocation), print
memory_analysis / cost_analysis, and write the roofline record.

The lines ABOVE the docstring must run before any jax import: jax locks
the device count at first init, and the production meshes need 512 host
devices.  They also pin this process and the `--all` children (which
inherit the environment) to the CPU platform: the dry run compiles on host
devices, and on a machine with a TPU every child would otherwise try to
open the one chip.

Usage:
    python -m repro.launch.dryrun --arch qwen2.5-3b --shape train_4k
    python -m repro.launch.dryrun --arch qwen2.5-3b --shape train_4k --multi-pod
    python -m repro.launch.dryrun --all [--jobs 4]    # orchestrate subprocesses
"""

import argparse
import json
import subprocess
import sys
import time
import traceback
from typing import List, Optional, Tuple

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "experiments", "dryrun")

# cells skipped by design: long_500k needs sub-quadratic attention
# (DESIGN.md §4); only ssm/hybrid run it.


def cell_list() -> List[Tuple[str, str]]:
    from ..configs import ARCH_NAMES, SHAPES, get_config
    cells = []
    for arch in ARCH_NAMES:
        cfg = get_config(arch)
        for shape in SHAPES.values():
            if shape.name == "long_500k" and not cfg.sub_quadratic:
                continue
            cells.append((arch, shape.name))
    return cells


PERF_OVERRIDES = {
    # measured perf-variant knobs (see EXPERIMENTS.md §Perf)
    "scores_bf16": {"attn_scores_dtype": "bf16"},
    "moe_ep": {"moe_impl": "ep_shardmap"},
    "kv_int8": {"kv_cache_quant": True},
    "flash": {"attn_impl": "flash"},
    "attn_remat": {"attn_chunk_remat": True},
    "seq_shard": {"attn_seq_shard": True},
    "seq_res": {"seq_parallel_residual": True},
}


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             out_dir: str, microbatches: int = 1,
             variant: str = "baseline", perf: str = "") -> dict:
    import dataclasses

    import jax
    from ..configs import SHAPES, get_config
    from .hlo_analysis import analyze_compiled, parse_collectives
    from .mesh import make_production_mesh
    from .specs import build_cell

    cfg = get_config(arch)
    if perf:
        over = {}
        for k in perf.split(","):
            over.update(PERF_OVERRIDES[k.strip()])
        cfg = dataclasses.replace(cfg, **over)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "multipod_2x16x16" if multi_pod else "pod_16x16"
    t0 = time.time()
    fn, arg_shapes, in_sh, out_sh = build_cell(cfg, shape, mesh,
                                               microbatches=microbatches)
    jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh)
    with jax.sharding.set_mesh(mesh):
        lowered = jitted.lower(*arg_shapes)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    n_chips = mesh.devices.size
    analysis = analyze_compiled(compiled, default_group=2)
    mem = analysis["memory"]
    print(f"[{arch} x {shape_name} x {mesh_name}] lower={t_lower:.1f}s "
          f"compile={t_compile:.1f}s")
    print("  memory_analysis:", json.dumps(mem))
    print("  cost_analysis: flops/device=%.3e bytes/device=%.3e"
          % (analysis["roofline"]["flops"], analysis["roofline"]["hbm_bytes"]))
    print("  collectives:", json.dumps(analysis["roofline"]["counts"]))

    record = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "variant": variant, "chips": int(n_chips),
        "microbatches": microbatches,
        "lower_s": t_lower, "compile_s": t_compile,
        **analysis,
    }
    os.makedirs(out_dir, exist_ok=True)
    fname = f"{arch}__{shape_name}__{mesh_name}__{variant}.json".replace(
        "/", "_")
    with open(os.path.join(out_dir, fname), "w") as f:
        json.dump(record, f, indent=1)
    return record


def orchestrate(jobs: int, multi_pod_too: bool, out_dir: str,
                only_missing: bool = True) -> int:
    cells = cell_list()
    meshes = [False, True] if multi_pod_too else [False]
    work = []
    for arch, shape in cells:
        for mp in meshes:
            mesh_name = "multipod_2x16x16" if mp else "pod_16x16"
            fname = os.path.join(
                out_dir, f"{arch}__{shape}__{mesh_name}__baseline.json")
            if only_missing and os.path.exists(fname):
                continue
            work.append((arch, shape, mp))
    print(f"{len(work)} cells to run ({len(cells)} cells x "
          f"{len(meshes)} meshes, skipping existing)")
    procs: List[Tuple[subprocess.Popen, tuple]] = []
    failures = []
    idx = 0
    while idx < len(work) or procs:
        while idx < len(work) and len(procs) < jobs:
            arch, shape, mp = work[idx]
            idx += 1
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", shape, "--out", out_dir]
            if mp:
                cmd.append("--multi-pod")
            p = subprocess.Popen(cmd, stdout=subprocess.PIPE,
                                 stderr=subprocess.STDOUT, text=True)
            procs.append((p, (arch, shape, mp)))
        for i, (p, meta) in enumerate(list(procs)):
            if p.poll() is not None:
                out, _ = p.communicate()
                tail = "\n".join(out.splitlines()[-12:])
                status = "OK" if p.returncode == 0 else f"FAIL({p.returncode})"
                print(f"--- {meta} {status} ---\n{tail}\n")
                if p.returncode != 0:
                    failures.append(meta)
                procs.remove((p, meta))
        time.sleep(1.0)
    if failures:
        print("FAILED CELLS:", failures)
        return 1
    print("ALL CELLS PASSED")
    return 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--jobs", type=int, default=3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--perf", default="",
                    help="comma-separated perf knobs: scores_bf16, moe_ep, "
                         "kv_int8, flash")
    ap.add_argument("--out", default=RESULTS_DIR)
    ap.add_argument("--single-pod-only", action="store_true")
    args = ap.parse_args()

    if args.all:
        sys.exit(orchestrate(args.jobs, not args.single_pod_only, args.out))
    try:
        variant = args.variant
        if args.perf and variant == "baseline":
            variant = args.perf.replace(",", "+")
        run_cell(args.arch, args.shape, args.multi_pod, args.out,
                 args.microbatches, variant, args.perf)
    except Exception:
        traceback.print_exc()
        sys.exit(1)


if __name__ == "__main__":
    main()
