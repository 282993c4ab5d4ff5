"""Cluster tier, exchange (core/physical.py `ReduceRunner`): reduce-side
merges in the window, per query completed in it.  A merge is a
`shark.reduce` span with `op` "merge" whose `route` is not "disjoint":
the `disjoint` route finalises states whose keys no other batch holds,
and merges nothing.  None where the window holds no `shark.reduce` merge
span at all."""

from bench.spans import in_window, queries_done


def read(run):
    w = in_window(run)
    spans = ([s for s in w.named("shark.reduce")
              if s.attrs.get("op") == "merge"] if w is not None else [])
    done = queries_done(run)
    if not spans or not done:
        return None
    return sum(s.attrs.get("route") != "disjoint" for s in spans) / done
