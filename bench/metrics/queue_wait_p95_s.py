"""Server scheduler (server/scheduler.py): 95th percentile of the time a
query waited in the fair scheduler's queue, `QueryHandle.wait_s`."""

from bench.common import percentile


def read(run):
    waits = [r["wait_s"] for r in run.window.requests
             if r["error"] is None and "wait_s" in r]
    return percentile(waits, 95)
