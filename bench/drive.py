"""The traffic generator.  It reads a traffic mix (bench/traffic/<mix>.json)
and drives the system under test with it; the mix's `driver` key picks one
of two shapes of load:

* `queries`: a closed loop of `clients` dashboard clients, each on its own
  session attached to the server, each sending its next query when the
  last one has returned and been decoded to numpy.  The mix lists query
  templates with their share, the columns they read, and a fixed pool of
  literals; every seed sends the same pool in another order.
* `workflows`: the SQL->ML workflow back to back on one session: a lazy
  `SELECT * FROM <table> WHERE f0 > t` with t from a fixed pool, the
  featurisation, then `fit` of a logistic regression; each workflow frees
  its cached features before the next.

Both warm every program their window will run before it opens (`warm`),
and time the window on the host clock (`run`).  The harness's host spans
(`bench.*` TraceAnnotations) mark each call into the program.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List

import numpy as np

from bench.common import SPAN_PREFIX, percentile
from bench.work import scan_bytes, train_bytes


def _span(name: str):
    from jax.profiler import TraceAnnotation
    return TraceAnnotation(SPAN_PREFIX + name)


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([seed % (1 << 64), *stream])


class Window:
    """What one measured window did: every request due in it, with its
    host-clock times and its answer."""

    def __init__(self, driver: str, t0: float, seconds: float):
        self.driver = driver
        self.t0 = t0
        self.t1 = t0 + seconds
        self.seconds = seconds
        self.requests: List[dict] = []
        self.counters: Dict[str, float] = {}

    def attempted(self) -> int:
        return len(self.requests)

    def failed(self) -> int:
        return sum(r.get("error") is not None for r in self.requests)


# -- queries ------------------------------------------------------------------

def _texts(mix: List[dict]):
    """Every (template, literal) of the mix: index pairs and SQL text."""
    for k, q in enumerate(mix):
        for j, lit in enumerate(q["literals"]):
            yield k, j, q["sql"].format(**lit)


def _client_plan(mix: List[dict], rng: np.random.Generator):
    """One client's endless sequence of (template, literal) indices: rounds
    holding each template `share` times in a shuffled order, and each
    template's literals in shuffled cycles."""
    cycles = {k: [] for k in range(len(mix))}
    rounds = [k for k, q in enumerate(mix) for _ in range(q["share"])]
    while True:
        for k in rng.permutation(rounds):
            if not cycles[k]:
                cycles[k] = list(rng.permutation(len(mix[k]["literals"])))
            yield int(k), int(cycles[k].pop())


def warm_queries(built, traffic: dict, config: dict, seed: int) -> None:
    """Run every text of the pool once, so each program is compiled (or
    read from the persistent cache) before the window opens.  It runs
    through the server itself: with a result cache on, the warm-up fills
    it."""
    sess = built.server.session("warm-up")
    handles = [(sess.submit(sql)) for _, _, sql in _texts(traffic["mix"])]
    for h in handles:
        h.result().to_numpy()
    sess.shutdown()


def run_queries(built, traffic: dict, config: dict, seed: int,
                seconds: float) -> Window:
    mix = traffic["mix"]
    srv = built.server
    clients = traffic["clients"]
    sessions = [srv.session(f"client-{i}") for i in range(clients)]
    plans = [_client_plan(mix, _rng(seed, 1, i)) for i in range(clients)]
    results: List[List[dict]] = [[] for _ in range(clients)]
    start = threading.Barrier(clients + 1)
    tasks0 = srv.ctx.scheduler.tasks_launched
    win: Dict[str, float] = {}

    def client(i: int) -> None:
        start.wait()
        out = results[i]
        while True:
            t_issue = time.perf_counter()
            if t_issue >= win["t1"]:
                return
            k, j = next(plans[i])
            q = mix[k]
            rec = {"template": q["name"], "check": q["check"],
                   "params": q["literals"][j], "reads": q["reads"],
                   "t_issue": t_issue, "answer": None, "error": None}
            try:
                with _span("query." + q["name"]):
                    h = sessions[i].submit(q["sql"].format(**q["literals"][j]))
                    rec["answer"] = h.result().to_numpy()
                rec["wait_s"], rec["run_s"] = h.wait_s, h.run_s
                rec["cached"] = h.cached
            except Exception as e:          # counted as failed, not fatal
                rec["error"] = repr(e)
            rec["t_done"] = time.perf_counter()
            out.append(rec)

    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(clients)]
    for t in threads:
        t.start()
    with _span("window"):
        t0 = time.perf_counter()
        win["t1"] = t0 + seconds
        start.wait()
        time.sleep(max(0.0, win["t1"] - time.perf_counter()))
    for t in threads:
        t.join(timeout=120.0)
    w = Window("queries", t0, seconds)
    w.requests = sorted((r for rs in results for r in rs),
                        key=lambda r: r["t_issue"])
    w.counters["tasks_launched"] = srv.ctx.scheduler.tasks_launched - tasks0
    w.counters["clients_alive"] = sum(t.is_alive() for t in threads)
    w.counters["result_cache_hits"] = sum(
        bool(r.get("cached")) for r in w.requests)
    for s in sessions:
        s.shutdown()
    return w


def query_end_to_end(w: Window) -> dict:
    """queries_per_s: queries completed inside the window over its length;
    query_p95_s: 95th percentile of the client-side latency (issue to
    decoded answer) of every query issued in the window."""
    done = [r for r in w.requests if r["error"] is None]
    in_window = [r for r in done if r["t_done"] <= w.t1]
    return {"queries_per_s": len(in_window) / w.seconds,
            "query_p95_s": percentile([r["t_done"] - r["t_issue"]
                                       for r in done], 95)}


def query_bytes(w: Window, rows: Dict[str, int]) -> int:
    """Logical bytes of the queries completed inside the window."""
    return sum(scan_bytes(r["reads"], rows) for r in w.requests
               if r["error"] is None and r["t_done"] <= w.t1)


# -- workflows ----------------------------------------------------------------

def _workflow(sess, traffic: dict, config: dict, t: float, est_seed: int,
              iterations: int) -> dict:
    from repro.ml import LogisticRegression
    d = config["dims"]
    with _span("sql"):
        frame = sess.sql(f"SELECT * FROM {traffic['table']} WHERE f0 > "
                         f"{float(t)!r}", lazy=True)
        feats = frame.to_features([f"f{i}" for i in range(d)],
                                  traffic["label"])
    model = LogisticRegression(dims=d, lr=traffic["lr"],
                               iterations=iterations, seed=est_seed)
    init = model.w.copy()
    try:
        with _span("fit"):
            model.fit(feats)
    finally:
        feats.unpersist()
        sess.release_shuffles()
    its = model.metrics.train_iterations
    return {"t": t, "init": init, "model": model.w, "lr": traffic["lr"],
            "iterations": iterations,
            "rows": [int(it["rows"]) for it in its],
            "iteration_s": [float(it["seconds"]) for it in its],
            "columns": d + 1}


def warm_workflows(built, traffic: dict, config: dict, seed: int) -> None:
    """One one-iteration workflow per threshold of the pool: every program
    shape the window runs."""
    for t in traffic["thresholds"]:
        _workflow(built.session, traffic, config, t, 0, 1)


def run_workflows(built, traffic: dict, config: dict, seed: int,
                  seconds: float) -> Window:
    rng = _rng(seed, 2)
    pool = traffic["thresholds"]
    tasks0 = built.session.ctx.scheduler.tasks_launched
    out = []
    with _span("window"):
        t0 = time.perf_counter()
        t1 = t0 + seconds
        order: List[int] = []
        while time.perf_counter() < t1:
            if not order:
                order = list(rng.permutation(len(pool)))
            t = pool[order.pop()]
            rec = {"t": t, "t_issue": time.perf_counter(), "error": None}
            try:
                with _span("workflow"):
                    rec.update(_workflow(built.session, traffic, config, t,
                                         int(rng.integers(1 << 31)),
                                         traffic["iterations"]))
            except Exception as e:          # counted as failed, not fatal
                rec["error"] = repr(e)
                rec["model"] = None
            rec["t_done"] = time.perf_counter()
            out.append(rec)
    w = Window("workflows", t0, seconds)
    w.requests = out
    w.counters["tasks_launched"] = (built.session.ctx.scheduler.tasks_launched
                                    - tasks0)
    return w


def _rows_in_window(w: Window):
    """(workflow, rows) of every workflow that ran in the window: the rows
    all its iterations read, times the share of the workflow's own
    host-clock span, issue to return, that lies inside the window (1 for
    all but the last, which the window's close cuts)."""
    for r in w.requests:
        if r["error"] is None:
            span = r["t_done"] - r["t_issue"]
            inside = min(r["t_done"], w.t1) - max(r["t_issue"], w.t0)
            if span > 0 and inside > 0:
                yield r, sum(r["rows"]) * inside / span


def workflow_end_to_end(w: Window) -> dict:
    """train_rows_per_s: rows read by the training iterations of the
    window (each workflow's SQL selection and featurisation included in
    its span; the workflow the close cuts counts pro rata) over the
    window's length."""
    return {"train_rows_per_s": sum(rows for _, rows in _rows_in_window(w))
            / w.seconds}


def workflow_bytes(w: Window) -> float:
    return sum(train_bytes(rows, r["columns"])
               for r, rows in _rows_in_window(w))


DRIVERS = {
    "queries": (warm_queries, run_queries, query_end_to_end),
    "workflows": (warm_workflows, run_workflows, workflow_end_to_end),
}
