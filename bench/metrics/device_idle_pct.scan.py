"""Device: share of the scan window in which no operation ran on the chip,
1 - (union of device-op intervals / window), from the profiler trace."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * run.trace["idle_share"]
