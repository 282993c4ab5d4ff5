"""The program's own spans and counters (`repro.core.tracing`, DESIGN.md
§17) as the per-layer metrics read them.  A traced run records them: the
program records while a profiler session runs, and keeps the records
until the next session starts, so they are still there when the readers
run after the window.

`in_window` hands a reader the records whose span ends inside the window
(both on the `time.perf_counter` clock).  It returns None, and the reader
then reads nothing, where the program has no such recorder, where no
record ends in the window, or where the recorder dropped records during
it.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional


@dataclasses.dataclass
class Window:
    records: List            # records ending inside the window
    by_id: Dict[int, object]  # every record of the session, by id

    def named(self, name: str) -> List:
        return [r for r in self.records if r.name == name]

    def under(self, rec, name: str) -> bool:
        """Whether a span named `name` is among `rec`'s ancestors."""
        parent = self.by_id.get(rec.parent_id)
        while parent is not None:
            if parent.name == name:
                return True
            parent = self.by_id.get(parent.parent_id)
        return False


def in_window(run) -> Optional[Window]:
    try:
        from repro.core import tracing
    except ImportError:
        return None
    t0, t1 = run.window.t0 * 1e9, run.window.t1 * 1e9
    st = tracing.stats()
    if st["dropped"] and st["last_drop_ns"] >= t0 \
            and st["first_drop_ns"] <= t1:
        return None
    recs = tracing.records()
    inside = [r for r in recs if t0 <= r.t1_ns <= t1]
    if not inside:
        return None
    return Window(inside, {r.id: r for r in recs})


def queries_done(run) -> int:
    """Queries completed inside the window, as `queries_per_s` counts."""
    return sum(r["error"] is None and r["t_done"] <= run.window.t1
               for r in run.window.requests)
