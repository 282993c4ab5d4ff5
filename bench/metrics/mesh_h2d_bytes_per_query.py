"""Storage, host column store to four devices: bytes of host arrays the
mesh programs were handed in the window (`h2d_bytes` of the `shark.mesh`
spans), per query completed in it."""

from bench.spans import in_window, queries_done


def read(run):
    w = in_window(run)
    spans = w.named("shark.mesh") if w is not None else []
    done = queries_done(run)
    if not spans or not done:
        return None
    return sum(s.attrs["h2d_bytes"] for s in spans) / done
