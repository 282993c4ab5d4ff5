"""Compiler (jit caches): XLA compilations during the scan window, the
`shark.compile` events; each names the step that recompiled."""

from bench.spans import in_window


def read(run):
    w = in_window(run)
    if w is None:
        return None
    return len(w.named("shark.compile"))
