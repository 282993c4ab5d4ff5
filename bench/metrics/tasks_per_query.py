"""Runtime scheduler (core/runtime.py): tasks launched during the window
(`ctx.scheduler.tasks_launched`, as a delta) per query answered."""


def read(run):
    done = sum(r["error"] is None for r in run.window.requests)
    if not done:
        return None
    return run.window.counters["tasks_launched"] / done
