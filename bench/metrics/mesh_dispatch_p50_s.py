"""Cluster tier, mesh dispatch (cluster/shard_exec.py): median wall time
of the `shark.mesh` spans ending in the window, one per dispatch of a
mesh program (placement, host staging, the program, read-back)."""

from bench.common import percentile
from bench.spans import in_window


def read(run):
    w = in_window(run)
    spans = w.named("shark.mesh") if w is not None else []
    if not spans:
        return None
    return percentile([(s.t1_ns - s.t0_ns) * 1e-9 for s in spans], 50)
