"""Analytics trainer (`ResidentFeatures`, ml/trainer.py): the share of
feature-partition gradient steps that read their kernel inputs already on
the device, 100 × the `shark.train.partition` spans with `resident` "hit"
over all of them, among the spans that ended in the window.  A program
whose partition spans carry no `resident` reads nothing."""

from bench.spans import in_window


def read(run):
    w = in_window(run)
    if w is None:
        return None
    parts = [p for p in w.named("shark.train.partition")
             if "resident" in p.attrs]
    if not parts:
        return None
    hits = sum(p.attrs["resident"] == "hit" for p in parts)
    return 100.0 * hits / len(parts)
