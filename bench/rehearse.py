#!/usr/bin/env python3
"""CPU rehearsal of every cell of BENCHMARK.json at a tiny size.

    python bench/rehearse.py [--seconds 3] [--seed 7] [cell ...]

Each cell runs in its own process with JAX_PLATFORMS=cpu, the tables at
the configuration's `rehearsal` size and the Pallas kernels forced through
interpret mode, and prints the last line a chip run would print, with
`correct` decided by the same reference.  It is not a chip run: the line
reports the CPU as its device and no metric.  Exits 1 when a cell fails
or is not correct.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import common  # noqa: E402

CHILD = """
import sys
sys.path[:0] = [{root!r}, {src!r}]
from bench import run
sys.exit(run.main({argv!r}, rehearsal=True))
"""


def rehearse_cell(name: str, seed: int, seconds: float, trace: int = 0,
                  env_extra=None) -> dict:
    """Run one cell's rehearsal in a child process; its parsed last line,
    with the child's exit code and standard error."""
    argv = ["--workload", name, "--seed", str(seed), "--seconds",
            str(seconds), "--trace", str(trace)]
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    code = CHILD.format(root=ROOT, src=os.path.join(ROOT, "src"), argv=argv)
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    lines = p.stdout.strip().splitlines()
    out = json.loads(lines[-1]) if p.returncode == 0 and lines else {}
    out["_rc"], out["_stderr"] = p.returncode, p.stderr
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cells", nargs="*")
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args()
    cells = args.cells or [w["name"] for w in common.benchmark()["workloads"]]
    ok = True
    for name in cells:
        out = rehearse_cell(name, args.seed, args.seconds)
        rc, err = out.pop("_rc"), out.pop("_stderr")
        if rc != 0 or not out.get("correct"):
            ok = False
            sys.stderr.write(err[-4000:])
        print(f"{name}: rc={rc} {json.dumps(out)}", flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
