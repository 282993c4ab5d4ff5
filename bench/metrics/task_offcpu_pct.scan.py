"""Runtime scheduler (`Scheduler._run_tasks`): share of the tasks' wall
time in which their threads did not run on a CPU (waiting for the
interpreter lock, a lock of the block store, or the device), 100 x (1 -
summed thread CPU time / summed wall time) over the `shark.task` spans."""

from bench.spans import in_window


def read(run):
    w = in_window(run)
    if w is None:
        return None
    tasks = w.named("shark.task")
    wall = sum(t.t1_ns - t.t0_ns for t in tasks)
    if wall <= 0:
        return None
    return 100.0 * (1.0 - sum(t.cpu_ns for t in tasks) / wall)
