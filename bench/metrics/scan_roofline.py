"""Kernels (kernels/colscan.py, groupby_mxu.py, segmented_merge.py and the
XLA routes): logical bytes the window's queries must read (bench/work.py)
over device busy time times the chip's HBM bandwidth, in percent.  Needs
the device trace."""

from bench.drive import query_bytes


def read(run):
    if run.trace is None or run.trace["busy_s"] <= 0:
        return None
    nbytes = query_bytes(run.window, run.rows)
    return 100.0 * nbytes / (run.trace["busy_s"]
                             * run.peaks()["hbm_bytes_per_s"])
