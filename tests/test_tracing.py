"""Spans and counters inside the engine (DESIGN.md §17).

  * off (no profiler, no `recording()`), a query makes no record at all;
  * under `recording()`, server queries and a logistic regression give
    spans that nest layer by layer and share one `query_id` per query,
    across the threads a query runs on;
  * under `jax.profiler.trace` the same spans land on the profiler's host
    plane, with the durations of the in-memory records;
  * `h2d_bytes` is exact, and a recompile names the step that caused it.
"""

import glob
import sys
import threading
import time

import jax
import numpy as np
import pytest

from repro.core import DType, Schema, SharkSession, tracing
from repro.ml import LogisticRegression
from repro.server import SharkServer

pytestmark = pytest.mark.tier1

ROWS, PARTS = 4000, 4
# no speculative backup attempts: the tests count spans per partition
QUIET = dict(speculation=False)


def _data(rng, rows=ROWS):
    return {"k": rng.integers(0, 6, rows).astype(np.int64),
            "f": rng.uniform(0.0, 1.0, rows),
            "v": rng.uniform(0.0, 10.0, rows)}


SCHEMA = Schema.of(k=DType.INT64, f=DType.FLOAT64, v=DType.FLOAT64)


@pytest.fixture
def server():
    srv = SharkServer(num_workers=4, max_threads=4, default_partitions=PARTS,
                      max_concurrent_queries=2, enable_result_cache=False,
                      **QUIET)
    srv.create_table("t", SCHEMA, _data(np.random.default_rng(0)))
    yield srv
    srv.shutdown()


def _by_id(recs):
    return {r.id: r for r in recs}


def _chain(rec, by_id):
    """Names from `rec` up to its root, `rec` first."""
    out = []
    while rec is not None:
        out.append(rec.name)
        rec = by_id.get(rec.parent_id)
    return out


def _ancestor_ids(rec, by_id):
    out = set()
    while rec is not None and rec.parent_id is not None:
        out.add(rec.parent_id)
        rec = by_id.get(rec.parent_id)
    return out


def _nests(chain, names):
    """`names` (innermost first) appear in `chain` in that order."""
    it = iter(chain)
    return all(any(n == c for c in it) for n in names)


def _named(recs, name):
    return [r for r in recs if r.name == name]


def test_off_records_nothing(server, monkeypatch):
    class NoRecord:
        def __init__(self, *a, **kw):
            raise AssertionError("a record was made while tracing is off")

    monkeypatch.setattr(tracing, "Span", NoRecord)
    before = tracing.stats()
    got = server.sql_np("SELECT k, SUM(v) AS s FROM t WHERE f > 0.25 "
                        "GROUP BY k")
    assert len(got["k"]) == 6
    assert tracing.stats() == before
    assert tracing.span("a") is tracing.span("b", x=1)
    assert not tracing.device("p", np.zeros(3))
    assert tracing.current() is None


def test_server_queries_nest_and_share_query_ids(server):
    sql = "SELECT k, SUM(v) AS s FROM t WHERE f > 0.25 GROUP BY k"
    with tracing.recording():
        handles = [server.session(f"c{i}").submit(sql) for i in range(2)]
        for h in handles:
            h.result().to_numpy()
    recs = tracing.records()
    by_id = _by_id(recs)
    queries = _named(recs, "shark.query")
    assert len(queries) == 2
    assert len({q.query_id for q in queries}) == 2
    assert {q.attrs["client"] for q in queries} == {"c0", "c1"}
    for q in queries:
        mine = [r for r in recs if q.id in _ancestor_ids(r, by_id)]
        assert mine and all(r.query_id == q.query_id for r in mine)
        names = {r.name for r in mine}
        assert {"shark.plan", "shark.stage", "shark.task", "shark.segment",
                "shark.device", "shark.reduce"} <= names
    devices = _named(recs, "shark.device")
    assert any(_nests(_chain(d, by_id), ["shark.device", "shark.segment",
                                         "shark.task", "shark.stage",
                                         "shark.query"]) for d in devices)
    for p in _named(recs, "shark.plan"):
        assert by_id[p.parent_id].name == "shark.query"
    for t in _named(recs, "shark.task"):
        assert by_id[t.parent_id].name == "shark.stage"
        assert t.attrs["queued_s"] >= 0 and t.cpu_ns >= 0
    assert len(_named(recs, "shark.result")) == 2


def test_query_keeps_one_id_across_threads(server):
    box = {}

    def client():
        with tracing.recording():
            box["h"] = server.submit("SELECT COUNT(*) AS c FROM t "
                                     "WHERE v < 5.0")
            box["h"].result()

    th = threading.Thread(target=client)
    th.start()
    th.join(timeout=60)
    assert not th.is_alive()
    recs = tracing.records()
    (q,) = _named(recs, "shark.query")
    assert q.thread != th.ident
    tasks = _named(recs, "shark.task")
    assert tasks and {t.query_id for t in tasks} == {q.query_id}
    assert {t.thread for t in tasks} - {q.thread}
    inside = [r for r in recs if q.id in _ancestor_ids(r, _by_id(recs))]
    assert {r.query_id for r in inside} == {q.query_id}


def test_training_iterations_nest():
    rng = np.random.default_rng(1)
    sess = SharkSession(num_workers=2, max_threads=4, default_partitions=2,
                        **QUIET)
    x = rng.normal(size=(3000, 3))
    sess.create_table("pts", Schema.of(f0=DType.FLOAT32, f1=DType.FLOAT32,
                                       f2=DType.FLOAT32, y=DType.FLOAT32),
                      {"f0": x[:, 0].astype(np.float32),
                       "f1": x[:, 1].astype(np.float32),
                       "f2": x[:, 2].astype(np.float32),
                       "y": (x[:, 0] > 0).astype(np.float32)})
    frame = sess.sql("SELECT * FROM pts WHERE f1 > -1.0", lazy=True)
    with tracing.recording():
        LogisticRegression(dims=3, lr=0.5, iterations=2).fit(
            frame.to_features(["f0", "f1", "f2"], "y"))
    sess.shutdown()
    recs = tracing.records()
    by_id = _by_id(recs)
    its = _named(recs, "shark.train.iteration")
    assert [i.attrs["iteration"] for i in its] == [0, 1]
    parts = _named(recs, "shark.train.partition")
    assert len(parts) == 4
    for p in parts:
        assert p.attrs["route"] == "jit"
        assert _nests(_chain(p, by_id), ["shark.train.partition",
                                         "shark.task", "shark.stage",
                                         "shark.train.iteration"])
    steps = [d for d in _named(recs, "shark.device")
             if d.attrs["program"] == "train_step.logistic"]
    assert len(steps) == 4
    for d in steps:
        assert by_id[d.parent_id].name == "shark.train.partition"
        assert d.attrs["h2d_bytes"] > 0 and d.attrs["d2h_bytes"] == 3 * 4


def test_colscan_h2d_bytes_are_the_columns_read():
    rng = np.random.default_rng(2)
    sess = SharkSession(num_workers=2, max_threads=4,
                        default_partitions=PARTS, **QUIET)
    data = _data(rng)
    table = sess.create_table("t", SCHEMA, data)
    with tracing.recording():
        got = sess.sql_np("SELECT COUNT(*) AS c, SUM(v) AS s FROM t "
                          "WHERE f BETWEEN 0.2 AND 0.7")
    sess.shutdown()
    m = (data["f"] >= 0.2) & (data["f"] <= 0.7)
    assert int(got["c"][0]) == int(m.sum())
    # the scan ships the filter column as the dictionary codes it is
    # stored in, and the summed column as its float64 values
    filter_codes = [p.columns["f"].code_space() for p in table.partitions]
    assert all(cs is not None for cs in filter_codes)
    want = sum(codes.nbytes for codes, _ in filter_codes) + data["v"].nbytes
    recs = tracing.records()
    by_id = _by_id(recs)
    scans = [d for d in _named(recs, "shark.device")
             if "shark.segment" in _chain(d, by_id)]
    assert {d.attrs["program"] for d in scans} == {"jit_colscan"}
    assert len(scans) == PARTS
    assert sum(d.attrs["h2d_bytes"] for d in scans) == want
    assert sum(d.attrs["d2h_bytes"] for d in scans) == PARTS * 4 * 8


def test_fresh_literal_compile_names_its_step():
    rng = np.random.default_rng(3)
    sess = SharkSession(num_workers=2, max_threads=4, default_partitions=2,
                        **QUIET)
    sess.create_table("t", SCHEMA, _data(rng))
    literal = 0.25 + (time.time_ns() % 1_000_003) * 1e-9   # never compiled
    with tracing.recording():
        sess.sql_np(f"SELECT k, v FROM t WHERE f > {literal!r}")
    sess.shutdown()
    recs = tracing.records()
    by_id = _by_id(recs)
    compiles = [c for c in _named(recs, "shark.compile")
                if c.parent_id in by_id
                and by_id[c.parent_id].name == "shark.device"
                and by_id[c.parent_id].attrs["program"] == "exprset"]
    assert compiles, [c for c in _named(recs, "shark.compile")]
    assert all(c.attrs["seconds"] > 0 for c in compiles)
    for c in compiles:
        assert _nests(_chain(c, by_id), ["shark.compile", "shark.device",
                                         "shark.segment", "shark.task"])


def test_profiler_host_plane_carries_the_same_spans(tmp_path):
    from jax.profiler import ProfileData
    # one pool thread: no other thread of the query takes the interpreter
    # lock between the two sinks' clock reads
    sess = SharkSession(num_workers=1, max_threads=1,
                        default_partitions=PARTS, **QUIET)
    sess.create_table("t", SCHEMA, _data(np.random.default_rng(4)))
    sql = "SELECT k, SUM(v) AS s FROM t WHERE f > 0.5 GROUP BY k"
    sess.sql_np(sql)                        # warm: compile outside the trace
    # and no forced hand-over of the lock between those reads either
    switch = sys.getswitchinterval()
    sys.setswitchinterval(1.0)
    try:
        with jax.profiler.trace(str(tmp_path)):
            for _ in range(8):
                sess.sql_np(sql)
    finally:
        sys.setswitchinterval(switch)
    sess.shutdown()
    recs = [r for r in tracing.records() if r.t1_ns > r.t0_ns]
    assert recs
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    plane_spans = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(tracing.PREFIX):
                    plane_spans.setdefault(e.name, []).append(e.duration_ns)
    mem = {}
    for r in recs:
        mem.setdefault(r.name, []).append(r.t1_ns - r.t0_ns)
    assert {"shark.query", "shark.plan", "shark.stage", "shark.task",
            "shark.segment", "shark.device", "shark.result"} <= set(mem)
    # a record nests inside its annotation, so each order statistic of a
    # name's plane durations bounds the record's from above; the two sinks
    # read their clocks next to each other, but the OS may preempt a
    # thread between the reads on a loaded CPU: each name's median span
    # and nine in ten of all spans agree within 10 % or 50 µs
    close = []
    for name, durs in mem.items():
        plane = sorted(plane_spans.get(name, []))
        assert len(plane) == len(durs), name
        pairs = list(zip(sorted(durs), plane))
        assert all(b >= a - 50_000 for a, b in pairs), (name, pairs)
        a, b = pairs[len(pairs) // 2]
        assert b - a <= max(0.10 * a, 50_000), (name, pairs)
        close += [b - a <= max(0.10 * a, 50_000) for a, b in pairs]
    assert sum(close) >= 0.9 * len(close), mem


def test_records_are_bounded(monkeypatch):
    monkeypatch.setattr(tracing._R, "capacity", 3)
    with tracing.recording():
        for i in range(5):
            with tracing.span("x", i=i):
                pass
    st = tracing.stats()
    assert st["records"] == 3 and st["dropped"] == 2
    assert st["first_drop_ns"] <= st["last_drop_ns"]
    with tracing.recording():
        pass
    assert tracing.stats()["dropped"] == 0
