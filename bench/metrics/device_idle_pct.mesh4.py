"""Device: mean share of the window in which no operation ran on a chip,
over the cell's four chips, 1 - (union of device-op intervals / window)
as bench/trace.py reduces it."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * run.trace["idle_share"]
