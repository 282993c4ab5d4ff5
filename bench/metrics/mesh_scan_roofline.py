"""Kernels, on all the cell's chips: logical bytes the window's queries
must read (bench/work.py) over the busy time of every traced device times
one chip's HBM bandwidth, in percent.  `busy_s` is the mean over the
devices (bench/trace.py), so it is multiplied by their count: the share
of the chips' summed roofline, at most 100 % with every chip busy."""

from bench.drive import query_bytes


def read(run):
    if run.trace is None or run.trace["busy_s"] <= 0:
        return None
    nbytes = query_bytes(run.window, run.rows)
    return 100.0 * nbytes / (run.trace["busy_s"] * run.trace["devices"]
                             * run.peaks()["hbm_bytes_per_s"])
