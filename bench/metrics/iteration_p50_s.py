"""Analytics trainer (ml/trainer.py): median wall time of one training
iteration, `ExecMetrics.train_iterations[*].seconds`, over the window's
workflows."""

from bench.common import percentile


def read(run):
    secs = [s for r in run.window.requests if r["error"] is None
            for s in r["iteration_s"]]
    return percentile(secs, 50)
