"""Spans and counters inside the engine, on the profiler's clock
(DESIGN.md §17).

    with tracing.span("stage", stage_id=3, tasks=16) as sp:
        ...
        sp.set(rows_out=n)
    tracing.event("stage.map-done", shuffle_id=1, split=0)

Recording is on while a JAX profiler session runs, or inside a
`recording()` block; otherwise it is off.  Off, `span` costs one check
(`TraceMe.is_enabled()`) and hands back a shared do-nothing span: no
record is made and no lock is taken.  On, every span goes to two sinks:

  * a `jax.profiler.TraceAnnotation("shark.<name>")` while a profiler
    session runs, so program spans land on the profiler's host plane and
    line up with the device ops of the same `.xplane.pb`;
  * a bounded in-memory record (`records()`), timed with
    `time.perf_counter_ns()` (wall) and `time.thread_time_ns()` (CPU of
    the span's thread).  A record that finds the buffer full is dropped and
    counted (`stats()["dropped"]`).  Records are kept until the next
    session starts: the next outermost `recording()` block, or the next
    profiler session started outside one.

Every record names its parent, the span open on its thread when it began,
or the span handed to it explicitly (`parent=`, `carry`) when the work
crossed threads.  A `query` span starts a new `query_id`; every span below
it inherits that id.  A compile that JAX's backend runs while recording
is an event `shark.compile`, parented to the innermost open span of the
compiling thread: the step that recompiled.
"""

from __future__ import annotations

import contextlib
import itertools
import threading
import time
from typing import Any, Callable, Dict, Iterator, List, Optional

import jax
import numpy as np
from jax._src import profiler as _jax_profiler
from jax._src.lib import _profiler

PREFIX = "shark."
CAPACITY = 1 << 18
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_profiling = _profiler.TraceMe.is_enabled
_TraceAnnotation = jax.profiler.TraceAnnotation
_HERE = object()            # parent sentinel: the span open on this thread


class Span:
    """One span, or one event (t0_ns == t1_ns); a record once it ended.
    `attrs` holds the attributes given at the start and those `set` later."""

    __slots__ = ("name", "id", "parent_id", "query_id", "thread", "t0_ns",
                 "t1_ns", "cpu_ns", "attrs", "_ann")

    def __init__(self, name: str, parent: Optional["Span"],
                 attrs: Dict[str, Any], new_query: bool = False):
        self.name = PREFIX + name
        self.id = next(_ids)
        self.parent_id = parent.id if parent is not None else None
        self.query_id = (next(_query_ids) if new_query else
                         parent.query_id if parent is not None else None)
        self.thread = threading.get_ident()
        self.attrs = attrs
        self.t0_ns = self.t1_ns = self.cpu_ns = 0
        self._ann = None

    def __enter__(self) -> "Span":
        _stack().append(self)
        if _profiling():
            self._ann = _TraceAnnotation(self.name, **self.attrs)
        self.cpu_ns = time.thread_time_ns()
        if self._ann is not None:       # the two sinks' clocks read together
            self._ann.__enter__()
        self.t0_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> bool:
        self.t1_ns = time.perf_counter_ns()
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        self.cpu_ns = time.thread_time_ns() - self.cpu_ns
        stack = _stack()
        if stack and stack[-1] is self:
            stack.pop()
        elif self in stack:
            stack.remove(self)
        _R.add(self)
        return False

    def set(self, **attrs) -> None:
        self.attrs.update(attrs)

    def put(self, *args) -> None:
        """Count more host arrays handed to the program as `h2d_bytes`."""
        self.attrs["h2d_bytes"] = (self.attrs.get("h2d_bytes", 0)
                                   + host_bytes(args))

    def fetch(self, x) -> np.ndarray:
        """`np.asarray(x)`; a device array's bytes count as `d2h_bytes`."""
        a = np.asarray(x)
        if isinstance(x, jax.Array):
            self.attrs["d2h_bytes"] = self.attrs.get("d2h_bytes", 0) + a.nbytes
        return a

    def __repr__(self) -> str:
        return (f"Span({self.name} id={self.id} parent={self.parent_id} "
                f"query={self.query_id} {(self.t1_ns - self.t0_ns) * 1e-9:.6f}"
                f"s {self.attrs})")


class _Off:
    """The span handed out while recording is off: does nothing."""

    __slots__ = ()

    def __enter__(self) -> "_Off":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def __bool__(self) -> bool:
        return False

    def set(self, **attrs) -> None:
        pass

    def put(self, *args) -> None:
        pass

    @staticmethod
    def fetch(x) -> np.ndarray:
        return np.asarray(x)


_OFF = _Off()


class _Recorder:
    """The bounded record buffer of the current session."""

    def __init__(self, capacity: int):
        self.capacity = capacity
        self.lock = threading.Lock()
        self.forced = 0             # open recording() blocks
        self.session: object = None
        self.records: List[Span] = []
        self.dropped = 0
        self.first_drop_ns: Optional[int] = None
        self.last_drop_ns: Optional[int] = None

    def begin(self, session: object) -> None:
        """A new session: forget the last one's records (lock held)."""
        self.session = session
        self.records = []
        self.dropped = 0
        self.first_drop_ns = self.last_drop_ns = None

    def profiled(self) -> None:
        """Recording under a profiler session: a new session starts anew."""
        session = _jax_profiler._profile_state.profile_session
        if session is not self.session:
            with self.lock:
                if not self.forced and session is not self.session:
                    self.begin(session)

    def add(self, rec: Span) -> None:
        with self.lock:
            if len(self.records) < self.capacity:
                self.records.append(rec)
                return
            self.dropped += 1
            if self.first_drop_ns is None:
                self.first_drop_ns = rec.t1_ns
            self.last_drop_ns = rec.t1_ns


_R = _Recorder(CAPACITY)
_ids = itertools.count(1)
_query_ids = itertools.count(1)
_local = threading.local()


def _stack() -> List[Span]:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


def _top() -> Optional[Span]:
    stack = _stack()
    return stack[-1] if stack else None


def _on() -> bool:
    """Whether to record; off, this is the one check a span costs."""
    if _R.forced:
        return True
    if not _profiling():
        return False
    _R.profiled()
    return True


# -- the API -----------------------------------------------------------------

def span(name: str, parent: Any = _HERE, **attrs):
    """A span named `shark.<name>`, to be entered with `with`.  Its parent
    is the span open on this thread, or `parent` where the work was handed
    over from another thread."""
    if not _on():
        return _OFF
    return Span(name, _top() if parent is _HERE else parent, attrs)


def query(**attrs):
    """The root span of one query (a new `query_id`), unless a query span
    is already open on this thread: then nothing new is opened."""
    if not _on():
        return _OFF
    top = _top()
    if top is not None and top.query_id is not None:
        return _OFF
    return Span("query", top, attrs, new_query=True)


def device(program: str, *args):
    """A `shark.device` span around handing `args` to a jitted or Pallas
    program: `h2d_bytes` counts the host numpy arrays among them (arrays
    already on the device count 0); `fetch` counts `d2h_bytes`."""
    if not _on():
        return _OFF
    return Span("device", _top(), {"program": program,
                                   "h2d_bytes": host_bytes(args),
                                   "d2h_bytes": 0})


def event(name: str, **attrs) -> None:
    """An instant `shark.<name>`, parented to the span open on this thread."""
    if not _on():
        return
    ev = Span(name, _top(), attrs)
    if _profiling():
        with _TraceAnnotation(ev.name, **attrs):
            pass
    ev.t0_ns = ev.t1_ns = time.perf_counter_ns()
    _R.add(ev)


def current() -> Optional[Span]:
    """The innermost span open on this thread (None while off)."""
    return _top() if _on() else None


def carry(fn: Callable) -> Callable:
    """`fn`, run under the span open here now on whatever thread calls it,
    so that spans it opens name their cause.  Off, `fn` itself."""
    cause = current()
    if cause is None:
        return fn

    def run(*args, **kw):
        stack = _stack()
        stack.append(cause)
        try:
            return fn(*args, **kw)
        finally:
            stack.remove(cause)
    return run


@contextlib.contextmanager
def recording() -> Iterator[None]:
    """Record spans inside the block with no profiler running (tests, and
    an operator who wants the records without a profile).  The outermost
    block starts a new session; its records stay readable after it."""
    with _R.lock:
        if not _R.forced:
            _R.begin(object())
        _R.forced += 1
    try:
        yield
    finally:
        with _R.lock:
            _R.forced -= 1


def records() -> List[Span]:
    """The current session's finished records, in the order they ended."""
    with _R.lock:
        return list(_R.records)


def stats() -> Dict[str, Any]:
    with _R.lock:
        return {"records": len(_R.records), "dropped": _R.dropped,
                "first_drop_ns": _R.first_drop_ns,
                "last_drop_ns": _R.last_drop_ns}


def host_bytes(tree) -> int:
    """nbytes of the host numpy arrays in a nest of tuples, lists and
    dicts; anything else (device arrays, scalars) counts 0."""
    if isinstance(tree, np.ndarray):
        return int(tree.nbytes)
    if isinstance(tree, (tuple, list)):
        return sum(host_bytes(t) for t in tree)
    if isinstance(tree, dict):
        return sum(host_bytes(t) for t in tree.values())
    return 0


def _on_duration(name: str, secs: float, **_kw) -> None:
    if name == COMPILE_EVENT:
        event("compile", seconds=float(secs))


jax.monitoring.register_event_duration_secs_listener(_on_duration)
