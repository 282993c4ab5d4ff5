"""Cluster tier, exchange: bytes the mesh exchange's all_to_all moved in
the window, stride padding included (`a2a_bytes` of the `shark.mesh`
spans), per query completed in it."""

from bench.spans import in_window, queries_done


def read(run):
    w = in_window(run)
    spans = ([s for s in w.named("shark.mesh") if "a2a_bytes" in s.attrs]
             if w is not None else [])
    done = queries_done(run)
    if not spans or not done:
        return None
    return sum(s.attrs["a2a_bytes"] for s in spans) / done
