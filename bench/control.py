#!/usr/bin/env python3
"""Readings that set the limits of `correct`: the program's compared
numbers and the bfloat16 control's, on several seeds in one process.

    python bench/control.py --workload <cell> --seconds <s> --seeds 1 2 3 \\
        [--per-kind 3] [--out control-<cell>.jsonl]

Each seed is a full run of the cell (its own data, warm-up and window).
After the window the reference compares the program's answers as a run
does; then, on up to `--per-kind` answers of each query kind (or
workflows), the same reference computed in bfloat16 stands in for the
program and is compared in its place.  The control has to come out as not
correct.  Runs on the chip; the benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import run  # noqa: E402


def picker(per_kind: int):
    def pick(answers):
        seen: dict = {}
        out = []
        for a in answers:
            key = a[0] if isinstance(a, tuple) else "workflow"
            answered = (a[2] if isinstance(a, tuple)
                        else a.get("model")) is not None
            if answered and seen.get(key, 0) < per_kind:
                seen[key] = seen.get(key, 0) + 1
                out.append(a)
        return out
    return pick


def main(argv=None, rehearsal: bool = False) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--per-kind", type=int, default=3)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    rc = 0
    for seed in args.seeds:
        result, checks = run.measure(args.workload, seed, args.seconds,
                                     False, rehearsal,
                                     control=picker(args.per_kind))
        if result is None:
            return 3
        line = {"workload": args.workload, "seed": seed,
                "correct": result["correct"], "program": checks,
                "control": result["control"]}
        print(json.dumps(line), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(line) + "\n")
        control_correct = all(c["value"] <= c["limit"]
                              for c in result["control"].values())
        rc |= int(control_correct)
    return rc


if __name__ == "__main__":
    sys.exit(main())
