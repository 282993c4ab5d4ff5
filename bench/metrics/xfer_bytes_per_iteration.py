"""Analytics trainer (`IterativeTrainer.run_stage`, ml/trainer.py): bytes
copied between host and device per training iteration, the summed
`h2d_bytes` and `d2h_bytes` of the `shark.device` spans under a
`shark.train.iteration` span, over the iterations that ended in the
window."""

from bench.spans import in_window


def read(run):
    w = in_window(run)
    if w is None:
        return None
    iterations = len(w.named("shark.train.iteration"))
    if not iterations:
        return None
    moved = sum(d.attrs["h2d_bytes"] + d.attrs["d2h_bytes"]
                for d in w.named("shark.device")
                if w.under(d, "shark.train.iteration"))
    return moved / iterations
