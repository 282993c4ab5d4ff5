"""Shared pieces of the harness: where its files are, loading a cell's
files by name, percentiles, compile counting, and the result line."""

from __future__ import annotations

import importlib.util
import json
import os
import sys
import threading
from typing import Dict, List, Optional

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
# the compile cache lives at a fixed path in the checkout: the path is part
# of the cache key, so a directory that moved would never hit
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
SPAN_PREFIX = "bench."


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def cell(name: str) -> dict:
    """The workload entry of BENCHMARK.json, its configuration and its
    traffic mix, each read from the file named after it."""
    bench = benchmark()
    for w in bench["workloads"]:
        if w["name"] == name:
            break
    else:
        raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == w["config"])
    return {"workload": w, "config": load_json(os.path.join(ROOT,
                                                            conf["file"])),
            "traffic": load_json(os.path.join(BENCH, "traffic",
                                              w["traffic"] + ".json")),
            "benchmark": bench}


def load_module(kind: str, name: str):
    """bench/<kind>/<name>.py, loaded by its path (names may hold `-` and
    `.`, which no import statement can spell)."""
    path = os.path.join(BENCH, kind, name + ".py")
    mod_name = "bench_" + kind + "_" + "".join(
        c if c.isalnum() else "_" for c in name)
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def percentile(values, q: float) -> Optional[float]:
    """q-th percentile (linear interpolation); None for no values."""
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at CACHE_DIR, or where
    JAX_COMPILATION_CACHE_DIR points.  Every program is written to it, so
    that each run after a cell's first loads and compiles nothing."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CompileCounter:
    """Counts XLA compilations (in-memory cache misses that reached the
    backend compiler or the persistent cache) and persistent-cache misses,
    through JAX's monitoring events."""

    BACKEND = "/jax/core/compile/backend_compile_duration"
    MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        import jax.monitoring as mon
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_misses = 0
        self._lock = threading.Lock()
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, name: str, secs: float, **_kw) -> None:
        if name == self.BACKEND:
            with self._lock:
                self.compiles += 1
                self.compile_s += secs

    def _event(self, name: str, **_kw) -> None:
        if name == self.MISS:
            with self._lock:
                self.cache_misses += 1

    def snapshot(self) -> Dict[str, float]:
        with self._lock:
            return {"compiles": self.compiles,
                    "compile_s": self.compile_s,
                    "cache_misses": self.cache_misses}


def device_info(chips: int) -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "used": devs[:chips]}


def memory_peak_bytes(devices) -> int:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def peaks(kind: str) -> dict:
    """The chip's published peaks; a device missing from the table is an
    error, never a default."""
    table = load_json(os.path.join(BENCH, "peaks.json"))
    if kind not in table["devices"]:
        raise KeyError(f"bench/peaks.json has no entry for device kind "
                       f"{kind!r}")
    return table["devices"][kind]


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def emit(result: dict, checks: Dict[str, dict]) -> None:
    """The compared numbers as the last lines of standard error, then the
    result as the last line of standard output, `checks` its last key."""
    for name, c in checks.items():
        log(f"check {name} = {c['value']!r} (limit {c['limit']!r})")
    out = dict(result)
    out["checks"] = checks
    sys.stdout.flush()
    print(json.dumps(out), flush=True)
