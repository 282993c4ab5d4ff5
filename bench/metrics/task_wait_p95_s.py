"""Runtime scheduler (`Scheduler._run_tasks`, core/runtime.py): 95th
percentile of the time a task waited for a thread of the shared pool,
from its submission to the start of its body (`shark.task` `queued_s`)."""

from bench.common import percentile
from bench.spans import in_window


def read(run):
    w = in_window(run)
    if w is None:
        return None
    return percentile([t.attrs["queued_s"] for t in w.named("shark.task")],
                      95)
