"""Storage, host column store to device (the `shark.device` spans of
kernels/ops.py, core/expr.py, core/aggregate.py, core/joins.py,
core/physical.py): bytes of host arrays handed to jitted and Pallas
programs in the window, per query completed in it."""

from bench.spans import in_window, queries_done


def read(run):
    w = in_window(run)
    done = queries_done(run)
    if w is None or not done:
        return None
    return sum(d.attrs["h2d_bytes"] for d in w.named("shark.device")) / done
