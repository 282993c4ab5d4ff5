"""Cluster tier, exchange: share of the all_to_all's stride slots that
carried no row in the window, 100 x sum of `pad_rows` / sum of slots
(`pad_rows` + `rows`) over the exchange's `shark.mesh` spans."""

from bench.spans import in_window


def read(run):
    w = in_window(run)
    spans = ([s for s in w.named("shark.mesh") if "pad_rows" in s.attrs]
             if w is not None else [])
    slots = sum(s.attrs["pad_rows"] + s.attrs["rows"] for s in spans)
    if not slots:
        return None
    return 100.0 * sum(s.attrs["pad_rows"] for s in spans) / slots
