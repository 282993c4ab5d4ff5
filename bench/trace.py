"""Reduction of a `jax.profiler` trace to the numbers the per-layer
metrics read: device busy time and idle share over the measured window,
device time per operation, and the longest idle gaps, each named by the
harness's own host span (`bench.*` TraceAnnotations) that covers most of
it.

`load` reads the `.xplane.pb` file with `jax.profiler.ProfileData`;
`reduce` works on plain (start_ns, end_ns, name) tuples, so it can be
checked on a synthetic trace (bench/tests/test_trace.py).
"""

from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, List, Optional, Tuple

from bench.common import SPAN_PREFIX

Interval = Tuple[float, float, str]
WINDOW_SPAN = SPAN_PREFIX + "window"
OP_LINES = ("XLA Ops",)


@dataclasses.dataclass
class Events:
    device: Dict[str, List[Interval]]      # device plane -> op intervals
    spans: List[Interval]                  # the harness's host spans


def load(logdir: str) -> Events:
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    device: Dict[str, List[Interval]] = {}
    spans: List[Interval] = []
    for path in paths:
        data = ProfileData.from_file(path)
        for plane in data.planes:
            if plane.name.startswith("/device:") and "CPU" not in plane.name:
                lines = list(plane.lines)
                ops = [ln for ln in lines if ln.name in OP_LINES] or lines
                device[plane.name] = [
                    (e.start_ns, e.start_ns + e.duration_ns, e.name)
                    for ln in ops for e in ln.events if e.duration_ns > 0]
            elif plane.name.startswith("/host:"):
                for ln in plane.lines:
                    spans.extend(
                        (e.start_ns, e.start_ns + e.duration_ns, e.name)
                        for e in ln.events if e.name.startswith(SPAN_PREFIX))
    return Events(device, spans)


def merge(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Union of intervals, sorted and disjoint."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _name_gap(gap: Tuple[float, float], spans: List[Interval]) -> str:
    """The host span covering most of the gap (the shorter span on a
    tie); `idle` where no span of the harness overlaps it."""
    best, best_key = "idle", (0.0, 0.0)
    for s, e, name in spans:
        ov = min(e, gap[1]) - max(s, gap[0])
        if ov > 0 and (ov, -(e - s)) > best_key:
            best, best_key = name[len(SPAN_PREFIX):], (ov, -(e - s))
    return best


def reduce(ev: Events, top: int = 10) -> Optional[dict]:
    """Busy and idle time of the devices over the `bench.window` span,
    averaged over the devices traced; None when the trace holds no device
    operation (a CPU run)."""
    windows = [(s, e) for s, e, n in ev.spans if n == WINDOW_SPAN]
    if not windows or not any(ev.device.values()):
        return None
    w0, w1 = windows[0]
    spans = [sp for sp in ev.spans if sp[2] != WINDOW_SPAN]
    busy = 0.0
    per_op: Dict[str, float] = {}
    gaps: List[Tuple[float, str]] = []
    devices = [d for d, ops in ev.device.items() if ops]
    for dev in devices:
        clipped = [(max(s, w0), min(e, w1), n) for s, e, n in ev.device[dev]
                   if e > w0 and s < w1]
        for s, e, n in clipped:
            per_op[n] = per_op.get(n, 0.0) + (e - s)
        merged = merge([(s, e) for s, e, _ in clipped])
        busy += sum(e - s for s, e in merged)
        edges = [w0] + [t for iv in merged for t in iv] + [w1]
        for gs, ge in zip(edges[0::2], edges[1::2]):
            if ge > gs:
                gaps.append((ge - gs, _name_gap((gs, ge), spans)))
    n = len(devices)
    window_s = (w1 - w0) * 1e-9
    busy_s = busy * 1e-9 / n
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    gaps.sort(key=lambda g: -g[0])
    return {"busy_s": busy_s, "window_s": window_s,
            "idle_share": 1.0 - busy_s / window_s, "devices": n,
            "device_ops": [[name, t * 1e-9 / n] for name, t in ops],
            "idle_gaps": [[name, t * 1e-9] for t, name in gaps[:top]]}
