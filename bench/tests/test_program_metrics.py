"""The per-layer metrics that read the program's own spans
(`repro.core.tracing`, through bench/spans.py): each reader on a
synthetic recorder, window clipping, nothing read from an empty window,
from a window in which records were dropped, or from a program without
the recorder; and a recorded window of each cell at rehearsal size
reporting every per-layer metric of the cell."""

import sys
import types

import numpy as np
import pytest

from bench import common, drive, run

SPAN_METRICS = ("plan_p50_s", "task_wait_p95_s", "task_offcpu_pct.scan",
                "h2d_bytes_per_query", "decode_p50_s",
                "compiles_in_window.scan", "xfer_bytes_per_iteration",
                "compiles_in_window.train")
S = 1_000_000_000                       # ns per second
T0, T1 = 100.0, 110.0                   # the window, perf_counter seconds


class Recorder:
    def __init__(self):
        self.recs = []
        self.stats = {"records": 0, "dropped": 0, "first_drop_ns": None,
                      "last_drop_ns": None}

    def add(self, name, t0, t1, parent=None, query_id=None, cpu=0.0,
            **attrs):
        rec = types.SimpleNamespace(
            name="shark." + name, id=len(self.recs) + 1,
            parent_id=parent.id if parent is not None else None,
            query_id=query_id, thread=1, t0_ns=int(t0 * S),
            t1_ns=int(t1 * S), cpu_ns=int(cpu * S), attrs=attrs)
        self.recs.append(rec)
        return rec


@pytest.fixture
def recorder(monkeypatch):
    from repro.core import tracing
    rec = Recorder()
    monkeypatch.setattr(tracing, "records", lambda: list(rec.recs))
    monkeypatch.setattr(tracing, "stats", lambda: dict(rec.stats))
    return rec


def _run(requests=()):
    w = drive.Window("queries", T0, T1 - T0)
    w.requests = list(requests)
    return run.RunRecord(w, None, {}, "cpu")


def _read(name, record):
    return common.load_module("metrics", name).read(record)


def _done(t_done, error=None):
    return {"error": error, "t_issue": t_done - 1.0, "t_done": t_done}


def _session(r):
    # a query planned before the window opened: left out
    q0 = r.add("query", 99.0, 99.95, query_id=1)
    r.add("plan", 99.0, 99.9, q0, 1)
    # query 2 plans twice; the first plan holds a 0.3 s map stage
    q2 = r.add("query", 100.5, 102.0, query_id=2)
    p = r.add("plan", 100.5, 101.0, q2, 2)
    st = r.add("stage", 100.6, 100.9, p, 2, tasks=2)
    r.add("plan", 101.0, 101.1, q2, 2)
    q3 = r.add("query", 103.0, 104.0, query_id=3)
    r.add("plan", 103.0, 103.5, q3, 3)
    # query 4 ends after the window closes: left out
    q4 = r.add("query", 109.0, 110.5, query_id=4)
    r.add("plan", 109.0, 110.2, q4, 4)
    t1 = r.add("task", 100.6, 101.6, st, 2, cpu=0.25, queued_s=0.1)
    r.add("task", 100.6, 101.6, st, 2, cpu=0.75, queued_s=0.2)
    r.add("task", 109.5, 110.5, q4, 4, cpu=0.0, queued_s=9.0)
    r.add("device", 100.7, 100.8, t1, 2, program="p", h2d_bytes=1000,
          d2h_bytes=8)
    r.add("device", 103.1, 103.2, q3, 3, program="p", h2d_bytes=3000,
          d2h_bytes=8)
    r.add("device", 110.1, 110.2, q4, 4, program="p", h2d_bytes=5000,
          d2h_bytes=8)
    r.add("result", 102.0, 102.01, rows=3, string_rows=0)
    r.add("result", 104.0, 104.03, rows=3, string_rows=3)
    r.add("result", 110.4, 110.9, rows=3, string_rows=3)
    r.add("compile", 101.05, 101.05, p, 2, seconds=0.5)
    r.add("compile", 103.1, 103.1, q3, 3, seconds=0.5)
    r.add("compile", 110.1, 110.1, q4, 4, seconds=0.5)
    # two training iterations in the window, one after it
    for k, (a, b) in enumerate([(105.0, 106.0), (106.0, 107.0),
                                (109.8, 110.3)]):
        it = r.add("train.iteration", a, b, iteration=k)
        part = r.add("train.partition", a, b, it, route="train_grad")
        r.add("device", a, b, part, program="train_step.assemble",
              h2d_bytes=100, d2h_bytes=400)
        r.add("device", a, b, part, program="train_grad", h2d_bytes=400,
              d2h_bytes=40)
    return _run([_done(102.0), _done(104.0), _done(104.5, "Err()"),
                 _done(110.9)])


def test_readers_on_a_synthetic_session(recorder):
    record = _session(recorder)
    got = {m: _read(m, record) for m in SPAN_METRICS}
    assert got["plan_p50_s"] == pytest.approx((0.3 + 0.5) / 2)
    assert got["task_wait_p95_s"] == pytest.approx(
        float(np.percentile([0.1, 0.2], 95)))
    assert got["task_offcpu_pct.scan"] == pytest.approx(50.0)
    # the scan's device spans plus the training ones, over 2 queries done
    assert got["h2d_bytes_per_query"] == pytest.approx(
        (1000 + 3000 + 2 * (100 + 400)) / 2)
    assert got["decode_p50_s"] == pytest.approx(0.02)
    assert got["compiles_in_window.scan"] == 2
    assert got["compiles_in_window.train"] == 2
    assert got["xfer_bytes_per_iteration"] == pytest.approx(
        100 + 400 + 400 + 40)


def test_off_cpu_share_from_hand_made_cpu_times(recorder):
    recorder.add("task", 101.0, 102.0, cpu=1.0, queued_s=0.0)
    recorder.add("task", 101.0, 105.0, cpu=0.0, queued_s=0.0)
    assert _read("task_offcpu_pct.scan", _run()) == pytest.approx(80.0)


def test_nothing_in_the_window_reads_none(recorder):
    recorder.add("task", 90.0, 91.0, cpu=0.5, queued_s=0.1)
    recorder.add("compile", 111.0, 111.0, seconds=0.1)
    record = _run([_done(102.0)])
    assert {m: _read(m, record) for m in SPAN_METRICS} == \
        {m: None for m in SPAN_METRICS}


def test_records_dropped_in_the_window_read_none(recorder):
    record = _session(recorder)
    recorder.stats.update(dropped=3, first_drop_ns=int(104 * S),
                          last_drop_ns=int(104.5 * S))
    assert all(_read(m, record) is None for m in SPAN_METRICS)
    # drops before the window opened do not touch it
    recorder.stats.update(first_drop_ns=int(90 * S),
                          last_drop_ns=int(95 * S))
    assert all(_read(m, record) is not None for m in SPAN_METRICS)


def test_a_program_without_the_recorder_reads_none(monkeypatch):
    import repro.core
    monkeypatch.delattr(repro.core, "tracing")
    monkeypatch.setitem(sys.modules, "repro.core.tracing", None)
    record = _run([_done(102.0)])
    assert all(_read(m, record) is None for m in SPAN_METRICS)


@pytest.mark.parametrize("cell", ["pavlo-scan", "sqlml-logreg"])
def test_recorded_window_reports_every_metric_of_the_cell(cell):
    """A `--trace 1` run records the program's spans under the profiler;
    `recording()` stands in for it on the CPU."""
    from repro.core import tracing
    spec = common.cell(cell)
    config, traffic = spec["config"], spec["traffic"]
    built = common.load_module("data", config["dataset"]).build(
        config, 17, rehearsal=True)
    warm, drive_run, end_to_end = drive.DRIVERS[traffic["driver"]]
    warm(built, traffic, config, 17)
    with tracing.recording():
        window = drive_run(built, traffic, config, 17, 1.0)
    rows = built.rows
    built.close()
    e2e = end_to_end(window)
    e2e["setup_s"] = 1.0
    trace = {"busy_s": 0.5, "window_s": 1.0, "idle_share": 0.5}
    record = run.RunRecord(window, trace, rows, "TPU v5 lite")
    bench = spec["benchmark"]
    got = run.metrics_of(bench, cell, True, e2e, record)
    want = {m["name"] for m in bench["per_layer"]
            if cell in m["workloads"]}
    assert set(got) == want
    assert all(v["value"] >= 0 for v in got.values())
