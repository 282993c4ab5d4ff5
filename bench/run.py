#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json on the chip this process holds.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up makes the cell's data from the seed, loads it into the system under
test and runs every program the window will use (`setup_s`, from process
start to the window's opening).  The window then drives the cell's traffic
for `--seconds` seconds on the host clock.  After it closes, the device's
peak memory is read, the system is shut down, and every answer due in the
window is compared with the plain reference (bench/reference/).  With
`--trace 1` the window runs under the JAX profiler and the line carries
the cell's per-layer metrics instead of its end-to-end ones.

The run refuses to start without a TPU, or with fewer chips than the cell
asks for: it then exits 3 and prints no result.  The compared numbers,
each with its limit, are the last lines on standard error; the last line
on standard output is the result as one JSON object.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_ROOT, os.path.join(_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import common  # noqa: E402
from bench.common import log  # noqa: E402


class RunRecord:
    """What a per-layer metric reader sees of a run."""

    def __init__(self, window, trace, rows, device_kind):
        self.window = window
        self.trace = trace
        self.rows = rows
        self.device_kind = device_kind

    def peaks(self) -> dict:
        return common.peaks(self.device_kind)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def metrics_of(bench: dict, workload: str, trace: bool, e2e: dict,
               record: RunRecord) -> dict:
    """The cell's end-to-end metrics (trace 0) or the per-layer metrics
    whose reader finds something to read (trace 1), as BENCHMARK.json
    lists them for this workload."""
    ends = [m for m in bench["end_to_end"]
            if workload in m.get("workloads", [workload])]
    if not trace:
        return {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                for m in ends if e2e.get(m["name"]) is not None}
    reported = {m["name"] for m in ends}
    out = {}
    for m in bench["per_layer"]:
        if workload not in m.get("workloads", [workload]):
            continue
        if m["moves"] not in reported:
            continue
        value = common.load_module("metrics", m["name"]).read(record)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def measure(workload: str, seed: int, seconds: float, trace: bool,
            rehearsal: bool = False, control=None):
    """One run of a cell: (result line without `checks`, checks).  With
    `control` (a function of the answers due in the window that picks those
    to read it on), the checks of the bfloat16 control put in the
    program's place come back too, under the key `control`; the benchmark's
    own runs never take it."""
    spec = common.cell(workload)
    w, config, traffic = spec["workload"], spec["config"], spec["traffic"]
    cache = common.enable_compile_cache()
    dev = common.device_info(w["chips"])
    if not rehearsal and (dev["platform"] != "tpu"
                          or dev["count"] < w["chips"]):
        log(f"bench: cell {w['name']} needs {w['chips']} TPU chip(s); JAX "
            f"sees {dev['count']} {dev['platform']} device(s). Refusing "
            f"to run.")
        return None, None
    log(f"device: platform={dev['platform']} kind={dev['kind']} "
        f"count={dev['count']}")
    log(f"compile cache: {cache}")
    compiles = common.CompileCounter()

    from bench import drive
    warm, run, end_to_end = drive.DRIVERS[traffic["driver"]]
    data = common.load_module("data", config["dataset"])
    t_begin = time.perf_counter()
    built = data.build(config, seed, rehearsal=rehearsal)
    t_warm = time.perf_counter()
    warm(built, traffic, config, seed)
    c_setup = compiles.snapshot()
    t_open = time.perf_counter()
    setup_s = t_open - T_START
    log(f"setup: {setup_s:.3f} s = start {t_begin - T_START:.3f} s + "
        f"generate {built.timings['generate_s']:.3f} s + encode "
        f"{built.timings['encode_s']:.3f} s + warm-up {t_open - t_warm:.3f}"
        f" s; compiles {c_setup['compiles']} ({c_setup['compile_s']:.3f} "
        f"s), persistent-cache misses {c_setup['cache_misses']}")

    tracedir = None
    if trace:
        import jax
        tracedir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(tracedir, profiler_options=opts)
    try:
        window = run(built, traffic, config, seed, seconds)
    finally:
        if trace:
            import jax
            jax.profiler.stop_trace()
    c_win = compiles.snapshot()
    log(f"window: {seconds} s, attempted {window.attempted()}, failed "
        f"{window.failed()}, compiles in the window "
        f"{c_win['compiles'] - c_setup['compiles']} "
        f"({c_win['compile_s'] - c_setup['compile_s']:.3f} s), counters "
        f"{window.counters}")
    peak = common.memory_peak_bytes(dev["used"])
    reduced = None
    if tracedir is not None:
        from bench import trace as trace_mod
        reduced = trace_mod.reduce(trace_mod.load(tracedir))
        shutil.rmtree(tracedir, ignore_errors=True)
    e2e = end_to_end(window)
    e2e["setup_s"] = setup_s
    record = RunRecord(window, reduced, built.rows, dev["kind"])
    metrics = ({} if rehearsal else
               metrics_of(spec["benchmark"], w["name"], trace, e2e, record))
    truth = built.truth
    built.close()
    del built

    t_check = time.perf_counter()
    ref = common.load_module("reference", config["dataset"])
    answers = due_answers(window, traffic, seed)
    checks = ref.check(truth, answers)
    log(f"reference: {len(answers)} answers compared in "
        f"{time.perf_counter() - t_check:.3f} s")
    correct = (window.attempted() > 0
               and all(c["value"] <= c["limit"] for c in checks.values()))
    device = {"platform": dev["platform"], "kind": dev["kind"],
              "count": dev["count"], "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": window.attempted(),
              "failed": window.failed(), "metrics": metrics,
              "device": device}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    if control is not None:
        picked = control(answers)
        t_ctl = time.perf_counter()
        result["control"] = ref.check(truth, picked, control=True)
        log(f"control: {len(picked)} answers in "
            f"{time.perf_counter() - t_ctl:.3f} s")
    return result, checks


def main(argv=None, rehearsal: bool = False) -> int:
    args = parse(argv)
    result, checks = measure(args.workload, args.seed, args.seconds,
                             bool(args.trace), rehearsal)
    if result is None:
        return 3
    if rehearsal:
        log("rehearsal: not a chip run; no metric is reported")
    common.emit(result, checks)
    return 0


def due_answers(window, traffic: dict, seed: int):
    """What the reference compares: every request issued in the window,
    or, where the mix sets `check_sample`, that many of them drawn from
    the seed (requests that failed are always kept: they never answered)."""
    reqs = window.requests
    n = traffic.get("check_sample")
    if n is not None and len(reqs) > n:
        from bench.drive import _rng
        keep = set(_rng(seed, 3).choice(len(reqs), n, replace=False).tolist())
        reqs = [r for i, r in enumerate(reqs)
                if i in keep or r.get("error") is not None]
    if window.driver == "queries":
        return [(r["check"], r["params"], r["answer"]) for r in reqs]
    return reqs


if __name__ == "__main__":
    sys.exit(main())
