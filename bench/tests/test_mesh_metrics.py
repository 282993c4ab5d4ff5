"""The four-node Pavlo cell (`pavlo-mesh4`): its per-layer readers on
synthetic `shark.mesh` records and a synthetic four-device trace, what they
read from a program without the mesh spans, its reference and control,
and whole rehearsals at a tiny size on one and on four CPU devices."""

import types

import numpy as np
import pytest

from bench import common, control, drive, rehearse, run
from bench.reference import pavlo4 as ref4
from bench.trace import Events, reduce

CELL = "pavlo-mesh4"
READERS = ("mesh_dispatch_p50_s", "mesh_h2d_bytes_per_query",
           "mesh_a2a_bytes_per_query", "mesh_pad_pct")
S = 1_000_000_000                       # ns per second
MS = 1_000_000
T0, T1 = 100.0, 110.0                   # the window, perf_counter seconds


def _read(name, record):
    return common.load_module("metrics", name).read(record)


@pytest.fixture
def records(monkeypatch):
    from repro.core import tracing
    recs = []
    monkeypatch.setattr(tracing, "records", lambda: list(recs))
    monkeypatch.setattr(tracing, "stats", lambda: {
        "records": len(recs), "dropped": 0, "first_drop_ns": None,
        "last_drop_ns": None})
    return recs


def _span(recs, name, t0, t1, **attrs):
    recs.append(types.SimpleNamespace(
        name="shark." + name, id=len(recs) + 1, parent_id=None, query_id=1,
        thread=1, t0_ns=int(t0 * S), t1_ns=int(t1 * S), cpu_ns=0,
        attrs=attrs))


def _run(done_at=(), trace=None, rows=None):
    w = drive.Window("queries", T0, T1 - T0)
    w.requests = [{"error": None, "t_issue": t - 1.0, "t_done": t,
                   "reads": ["uservisits.visitDate", "uservisits.adRevenue"]}
                  for t in done_at]
    return run.RunRecord(w, trace, rows or {}, "TPU v5 lite")


def test_span_readers_on_synthetic_dispatches(records):
    # an exchange and a colscan inside the window, one exchange after it
    _span(records, "mesh", 101.0, 101.5, program="exchange", rows=300,
          pad_rows=100, a2a_bytes=6400, h2d_bytes=4000, d2h_bytes=6400)
    _span(records, "mesh", 102.0, 102.1, program="colscan", rows=300,
          h2d_bytes=2000, d2h_bytes=64)
    _span(records, "mesh", 103.0, 103.2, program="exchange", rows=100,
          pad_rows=300, a2a_bytes=6400, h2d_bytes=1000, d2h_bytes=6400)
    _span(records, "mesh", 109.9, 110.5, program="exchange", rows=1,
          pad_rows=1, a2a_bytes=1, h2d_bytes=1, d2h_bytes=1)
    _span(records, "device", 101.1, 101.4, program="mesh_exchange",
          h2d_bytes=4000, d2h_bytes=6400)
    record = _run(done_at=[102.0, 103.5])
    got = {m: _read(m, record) for m in READERS}
    assert got["mesh_dispatch_p50_s"] == pytest.approx(0.2)
    assert got["mesh_h2d_bytes_per_query"] == pytest.approx(7000 / 2)
    assert got["mesh_a2a_bytes_per_query"] == pytest.approx(12800 / 2)
    # 400 empty slots of 800
    assert got["mesh_pad_pct"] == pytest.approx(50.0)


def test_span_readers_read_nothing_without_mesh_spans(records):
    # the parent of the string-key mesh route: the program's other spans
    # are there, no shark.mesh span is
    _span(records, "device", 101.0, 101.5, program="colscan",
          h2d_bytes=4000, d2h_bytes=8)
    _span(records, "query", 100.5, 102.0)
    record = _run(done_at=[102.0])
    assert {m: _read(m, record) for m in READERS} == dict.fromkeys(READERS)


def _four_device_trace(busy_ms):
    """A 100 ms window; device d busy for busy_ms[d] from its start."""
    ops = {f"/device:TPU:{d}": [(0, b * MS, "mesh_exchange")]
           for d, b in enumerate(busy_ms) if b}
    return reduce(Events(ops, [(0, 100 * MS, "bench.window")]))


def test_roofline_divides_by_every_chip_busy_time():
    # four chips, 40 / 40 / 20 / 0 ms busy (the idle chip has no ops and
    # is not a traced device); the window's query must read 50 GB, 61 %
    # of what the three chips could read in their 100 ms of busy time
    trace = _four_device_trace([40, 40, 20, 0])
    assert trace["devices"] == 3
    assert trace["busy_s"] == pytest.approx(0.1 / 3)
    hbm = common.peaks("TPU v5 lite")["hbm_bytes_per_s"]
    rows = {"uservisits": 6_250_000_000}  # 2 columns x 4 B a row
    record = _run(done_at=[105.0], trace=trace, rows=rows)
    nbytes = drive.query_bytes(record.window, rows)
    assert nbytes == 5e10
    got = _read("mesh_scan_roofline", record)
    assert got == pytest.approx(100.0 * nbytes / (0.1 * hbm))
    assert got <= 100.0
    # the per-device mean alone would read it three times too high
    assert 100.0 * nbytes / (trace["busy_s"] * hbm) > 100.0
    idle = _read("device_idle_pct.mesh4", record)
    assert idle == pytest.approx(100.0 * (1 - (0.1 / 3) / 0.1))


def test_device_readers_need_a_trace():
    record = _run(done_at=[105.0], rows={"uservisits": 10})
    assert _read("mesh_scan_roofline", record) is None
    assert _read("device_idle_pct.mesh4", record) is None


def _truth():
    from bench.data import pavlo4
    return pavlo4.generate({"uservisits_rows": 4000, "page_urls": 500}, 3)[1]


def test_reference_refuses_a_missing_key_and_an_inexact_sum():
    truth = _truth()
    ref = ref4.Reference(truth)
    want = {"sourceIP": truth["ip_vocab"], "rev": ref.ip_rev.copy()}
    perm = np.random.default_rng(0).permutation(len(want["rev"]))
    shuffled = {k: v[perm] for k, v in want.items()}
    assert ref.compare("agg_sourceip", {}, shuffled) == (True, 0.0)
    short = {k: v[1:] for k, v in want.items()}
    assert ref.compare("agg_sourceip", {}, short)[0] is False
    off = dict(want, rev=want["rev"] * (1 + 2e-4))
    ok, gap = ref.compare("agg_sourceip", {}, off)
    assert ok and gap > ref4.FLOAT_REL_GAP_LIMIT
    assert ref.compare("agg_sourceip", {}, dict(
        want, sourceIP=np.array(["x"] * len(want["rev"]))))[0] is False


def test_packed_ips_keep_string_order():
    ips = np.array(["1.2.3.4", "1.2.3.40", "1.2.30.4", "10.0.0.0",
                    "255.255.255.255", "9.9.9.9", "0.0.0.0"])
    packed = ref4.pack_ips(ips)
    assert np.array_equal(np.argsort(packed), np.argsort(ips))
    assert len(np.unique(packed)) == len(ips)


def test_bf16_group_sums_round_every_partial_sum():
    g = np.array([0, 1, 0, 1, 0, 2])
    v = np.array([1.0, 256.0, 1 / 512, 1.0, 1 / 512, 3.0])
    # 1 + 2^-9 rounds back to 1 in bfloat16 (8 bits of mantissa), twice
    assert ref4.bf16_group_sums(g, v, 3).tolist() == [1.0, 256.0, 3.0]


def test_control_fails_where_the_program_passes():
    result, checks = run.measure(CELL, 5, 1.0, False, rehearsal=True,
                                 control=control.picker(2))
    assert result["correct"], checks
    ctl = result["control"]
    assert ctl["float_rel_gap"]["value"] > ctl["float_rel_gap"]["limit"]


def test_recorded_window_reports_every_metric_of_the_cell():
    from repro.core import tracing
    spec = common.cell(CELL)
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
    trace = {"busy_s": 0.5, "window_s": 1.0, "idle_share": 0.5,
             "devices": 1}
    record = run.RunRecord(window, trace, rows, "TPU v5 lite")
    bench = spec["benchmark"]
    got = run.metrics_of(bench, CELL, True, e2e, record)
    want = {m["name"] for m in bench["per_layer"]
            if CELL in m.get("workloads", [CELL])}
    assert set(got) == want
    assert all(v["value"] >= 0 for v in got.values())
    ends = run.metrics_of(bench, CELL, False, e2e, record)
    assert set(ends) == {"setup_s", "query_p95_s", "queries_per_s"}


def test_rehearsal_on_four_devices_is_correct():
    out = rehearse.rehearse_cell(CELL, 7, 2.0, env_extra={
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    assert out.pop("_rc") == 0, out["_stderr"][-4000:]
    assert out["correct"] and out["failed"] == 0, out
    assert out["device"]["count"] == 4
