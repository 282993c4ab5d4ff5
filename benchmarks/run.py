"""Benchmark harness — one module per paper table/figure (see DESIGN.md §8).
Prints ``name,us_per_call,derived`` CSV lines.

    PYTHONPATH=src python -m benchmarks.run [--only pavlo,ml_bench]
"""

from __future__ import annotations

import argparse
import sys
import time
import traceback

SUITES = ["loading", "kernels_bench", "exec_engine", "shuffle_bench",
          "pavlo", "tpch_micro", "join_pde", "join_bench",
          "chaos_bench", "warehouse", "ml_bench", "task_overhead",
          "concurrent_bench", "frame_overhead", "spill_bench",
          "pipeline_bench"]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of suites")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    suites = args.only.split(",") if args.only else SUITES
    print("name,us_per_call,derived")
    failures = []
    for name in suites:
        t0 = time.time()
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=["main"])
            mod.main()
            print(f"# {name} done in {time.time() - t0:.1f}s",
                  file=sys.stderr)
        except Exception:
            failures.append(name)
            traceback.print_exc()
    if failures:
        print(f"# FAILED: {failures}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
