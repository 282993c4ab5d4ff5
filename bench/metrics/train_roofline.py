"""Kernels (kernels/train_grad.py and the fused assemble step of
ml/featurize.py): feature and label bytes every iteration in the window
must read (bench/work.py) over device busy time times the chip's HBM
bandwidth, in percent.  Needs the device trace."""

from bench.drive import workflow_bytes


def read(run):
    if run.trace is None or run.trace["busy_s"] <= 0:
        return None
    nbytes = workflow_bytes(run.window)
    return 100.0 * nbytes / (run.trace["busy_s"]
                             * run.peaks()["hbm_bytes_per_s"])
