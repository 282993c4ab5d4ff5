"""Planner (`SharkServer._run_query`, `Executor.execute`): median over the
window's queries of one query's planning time, the summed `shark.plan`
spans of its `query_id` (parse, bind, optimize, physical compile), less
the map stages that partial DAG execution runs while it compiles (their
`shark.stage` spans nest directly in the plan span)."""

from bench.common import percentile
from bench.spans import in_window


def read(run):
    w = in_window(run)
    if w is None:
        return None
    plans = [p for p in w.named("shark.plan") if p.query_id is not None]
    staged = {p.id: 0 for p in plans}
    for r in w.by_id.values():
        if r.name == "shark.stage" and r.parent_id in staged:
            staged[r.parent_id] += r.t1_ns - r.t0_ns
    per_query = {}
    for p in plans:
        own = p.t1_ns - p.t0_ns - staged[p.id]
        per_query[p.query_id] = per_query.get(p.query_id, 0) + own
    return percentile([ns * 1e-9 for ns in per_query.values()], 50)
