"""Executor (core/physical.py via SharkServer._run_query): median time a
query ran on a scheduler worker, `QueryHandle.run_s`."""

from bench.common import percentile


def read(run):
    runs = [r["run_s"] for r in run.window.requests
            if r["error"] is None and "run_s" in r]
    return percentile(runs, 50)
