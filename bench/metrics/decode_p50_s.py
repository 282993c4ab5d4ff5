"""Executor, result assembly (`ExecResult.to_numpy`, on the client's
thread): median time to concatenate a result's partitions and decode its
strings, the `shark.result` spans."""

from bench.common import percentile
from bench.spans import in_window


def read(run):
    w = in_window(run)
    if w is None:
        return None
    return percentile([(r.t1_ns - r.t0_ns) * 1e-9
                       for r in w.named("shark.result")], 50)
