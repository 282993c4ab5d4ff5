"""String group keys on the mesh exchange (DESIGN.md §13.1).

A STRING group key crosses devices as table-wide int32 codes: the
partitions' dictionaries are unified once per table and column
(`Table.string_domain`) and every later query reuses the unification.  The
Pavlo aggregation task (`GROUP BY sourceIP`) and `GROUP BY countryCode`
over a small uservisits from the benchmark's own generator must return the
same rows with the mesh on and off, and equal the benchmark's plain numpy
reference for the four-node deployment.

The cases that need several devices run in a subprocess started with
``XLA_FLAGS=--xla_force_host_platform_device_count=4``, so that they run
(and count) on a host that exposes one device.
"""

import functools
import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from bench.data import pavlo4 as data4  # noqa: E402
from bench.reference import pavlo4 as ref4  # noqa: E402
from repro.cluster import MeshContext, shard_exec  # noqa: E402
from repro.core import DType, Schema, tracing  # noqa: E402
from repro.core.batch import PartitionBatch, merge_string_dicts  # noqa: E402
from repro.core.expr import ColumnVal  # noqa: E402
from repro.core.session import SharkSession  # noqa: E402

pytestmark = pytest.mark.tier1

SIZE = {"uservisits_rows": 40_000, "page_urls": 20_000}
PARTS = 8
QUERIES = {
    "agg_sourceip": "SELECT sourceIP, SUM(adRevenue) AS rev FROM uservisits "
                    "GROUP BY sourceIP",
    "group_country": "SELECT countryCode, SUM(adRevenue) AS rev, COUNT(*) "
                     "AS c FROM uservisits GROUP BY countryCode",
}
RANGE_AGG = ("SELECT COUNT(*) AS c, SUM(adRevenue) AS rev FROM uservisits "
             "WHERE visitDate BETWEEN 11000 AND 11030")
SCHEMA = Schema.of(
    sourceIP=DType.STRING, destURL=DType.STRING, visitDate=DType.DATE,
    adRevenue=DType.FLOAT64, userAgent=DType.STRING,
    countryCode=DType.STRING, languageCode=DType.STRING,
    searchWord=DType.STRING, duration=DType.INT32)


def _session(mesh, seed=11):
    uv, truth = data4.generate(SIZE, seed)
    sess = SharkSession(num_workers=4, default_partitions=PARTS, mesh=mesh)
    sess.create_table("uservisits", SCHEMA, uv)
    return sess, truth


def _assert_same_rows(got, want, rtol=1e-9):
    """Same columns, dtypes and rows: keys and counts exactly, sums to
    rounding.  Rows are compared in key order: the reduce side coalesces
    buckets by the map outputs' sizes (PDE), which differ between the
    mesh's partial states and the host's, so a group-by without ORDER BY
    may list the same rows in another order."""
    assert list(got) == list(want)
    key = list(want)[0]
    og = np.argsort(np.asarray(got[key]), kind="stable")
    ow = np.argsort(np.asarray(want[key]), kind="stable")
    for c in want:
        a, b = np.asarray(got[c]), np.asarray(want[c])
        assert a.dtype == b.dtype and a.shape == b.shape, c
        if b.dtype.kind in "iuU":
            assert np.array_equal(a[og], b[ow]), c
        else:
            assert np.allclose(a[og], b[ow], rtol=rtol, atol=0), c


@pytest.fixture(scope="module")
def sessions():
    on, truth = _session(MeshContext())
    off, _ = _session(None)
    yield on, off, truth
    on.shutdown()
    off.shutdown()


@pytest.mark.parametrize("check", sorted(QUERIES))
def test_string_group_by_matches_host_path_and_reference(sessions, check):
    on, off, truth = sessions
    got = on.sql_np(QUERIES[check])
    _assert_same_rows(got, off.sql_np(QUERIES[check]))
    ok, gap = ref4.Reference(truth).compare(check, {}, got)
    assert ok and gap <= 1e-9, gap


@pytest.mark.parametrize("check", sorted(QUERIES))
def test_string_group_by_takes_the_mesh_exchange(check):
    on, _ = _session(MeshContext())
    try:
        on.sql_np(QUERIES[check])
        routes = on.metrics().segment_routes()
        assert routes.get("mesh-exchange", 0) >= 1, routes
        # the map side ran on the mesh alone: no host map route
        assert not set(routes) & {"code-groupby", "whole-stage",
                                  "groupby_mxu", "jit"}, routes
        assert on.metrics().mesh_partitions == PARTS
        # one device: its states are final, finalised in place
        assert routes.get("disjoint") == 1 and "numpy" not in routes, routes
    finally:
        on.shutdown()


def test_domain_is_built_once_per_table_and_column():
    on, truth = _session(MeshContext())
    try:
        with tracing.recording():
            for _ in range(2):
                on.sql_np(QUERIES["agg_sourceip"])
            on.sql_np(QUERIES["group_country"])
            dicts = [r for r in tracing.records()
                     if r.name == "shark.mesh.dict"]
        assert [(d.attrs["column"], d.attrs["hit"]) for d in dicts] == [
            ("sourceIP", False), ("sourceIP", True), ("countryCode", False)]
        assert dicts[0].attrs["entries"] == len(truth["ip_vocab"])
        assert dicts[2].attrs["entries"] == len(
            np.unique(truth["country"]))
        table = on.catalog.get("uservisits")
        dom, hit = table.string_domain("sourceIP")
        assert hit and np.array_equal(dom.values, truth["ip_vocab"])
    finally:
        on.shutdown()


def test_concurrent_first_uses_build_one_domain():
    from repro.core import columnar
    uv, _ = data4.generate({"uservisits_rows": 4000, "page_urls": 500}, 9)
    table = columnar.from_arrays("t", SCHEMA, uv, 8)
    got = []
    start = threading.Barrier(16)

    def use():
        start.wait(timeout=60)
        got.append(table.string_domain("sourceIP"))

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=use) for _ in range(16)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(th.is_alive() for th in threads)
    assert len(got) == 16 and len({id(d) for d, _ in got}) == 1
    assert sorted(hit for _, hit in got) == [False] + [True] * 15


def test_mesh_spans_carry_their_attributes(sessions):
    on, _, _ = sessions
    on.executor.mesh.resident.clear()
    with tracing.recording():
        for _ in range(2):
            on.sql_np(QUERIES["agg_sourceip"])
            on.sql_np(RANGE_AGG)
        recs = tracing.records()
    mesh = [r for r in recs if r.name == "shark.mesh"]
    assert [m.attrs["program"] for m in mesh] == ["exchange", "colscan"] * 2
    n_dev = len(MeshContext().devices)
    for i, m in enumerate(mesh):
        assert m.attrs["devices"] == n_dev
        assert m.attrs["partitions"] == PARTS
        assert m.attrs["rows"] == SIZE["uservisits_rows"]
        assert m.attrs["retries"] == 0
        # the mesh span's bytes are its device spans' bytes: the exchange
        # places its key and value lanes on the devices, the colscan its
        # filter lane beside the value lane it shares; the second round
        # finds them there and ships only the row counts
        devs = [r for r in recs if r.name == "shark.device"
                and r.parent_id == m.id]
        places = [d for d in devs if d.attrs["program"] == "mesh_place"]
        assert len(devs) - len(places) == 1
        assert len(places) == m.attrs.get("fills", 0) == [2, 1, 0, 0][i]
        for k in ("h2d_bytes", "d2h_bytes"):
            assert m.attrs[k] == sum(d.attrs[k] for d in devs) > 0
        if i >= 2:
            assert m.attrs["h2d_bytes"] < 4 * PARTS + 64
    ex = mesh[0].attrs
    # int32 codes and float64 values (CPU) per stride slot
    slots = ex["a2a_bytes"] // (4 + 8)
    assert ex["pad_rows"] + ex["rows"] == slots
    assert 0 <= ex["shipped_rows"] <= ex["rows"]
    assert "a2a_bytes" not in mesh[1].attrs


def test_exchange_lanes_follow_the_accumulation_dtype():
    ctx = MeshContext()
    rng = np.random.default_rng(3)
    keys = [rng.integers(-50, 50, 300).astype(np.int32) for _ in range(5)]
    vals = [rng.uniform(0, 1, 300) for _ in range(5)]
    out, rep = shard_exec.mesh_group_exchange(ctx, keys, vals, "float32")
    assert all(k.dtype == np.int32 and v.dtype == np.float32
               for k, v in out)
    allk, allv = np.concatenate(keys), np.concatenate(vals)
    gotk = np.concatenate([k for k, _ in out])
    gotv = np.concatenate([v for _, v in out])
    o, go = np.lexsort((allv, allk)), np.lexsort((gotv, gotk))
    assert np.array_equal(gotk[go], allk[o])
    assert np.array_equal(gotv[go], allv[o].astype(np.float32))
    wide = [k.astype(np.int64) << 33 for k in keys]
    out, _ = shard_exec.mesh_group_exchange(ctx, wide, None, "float32")
    assert all(k.dtype == np.int64 and v is None for k, v in out)
    assert sorted(np.concatenate([k for k, _ in out]).tolist()) == sorted(
        np.concatenate(wide).tolist())


def test_colscan_counts_exactly_under_float32_accumulation():
    ctx = MeshContext()
    rng = np.random.default_rng(4)
    days = [rng.integers(10957, 11688, n).astype(np.int32)
            for n in (1000, 0, 3000, 777)]
    rev = [rng.uniform(0, 1000, d.shape[0]) for d in days]
    # int32 lanes compare the closed float bounds as [11001, 11300]
    stats, _ = shard_exec.mesh_colscan(
        ctx, shard_exec.filter_column(days, "float32"), rev, 11000.5, 11300,
        "float32")
    for (cnt, s, mn, mx), d, r in zip(stats, days, rev):
        m = (d >= 11001) & (d <= 11300)
        assert cnt == m.sum()
        assert s == pytest.approx(r[m].sum(), rel=1e-5, abs=1e-3)
        if m.any():
            assert mn == pytest.approx(r[m].min(), rel=1e-6)
            assert mx == pytest.approx(r[m].max(), rel=1e-6)
    # float64 filter values cannot be compared exactly in float32 lanes
    assert shard_exec.mesh_colscan(
        ctx, shard_exec.filter_column(rev, "float32"), rev, 0.0, 1.0,
        "float32") is None


@pytest.mark.parametrize("funcs", [("SUM",), ("COUNT", "SUM"),
                                   ("AVG", "COUNT"), ("COUNT",)])
def test_sorted_partial_aggregate_is_partial_aggregate(funcs):
    from repro.core.aggregate import (partial_aggregate,
                                      sorted_partial_aggregate)
    from repro.core.expr import Col
    from repro.core.plan import AggFunc, AggSpec
    rng = np.random.default_rng(len(funcs))
    sdict = np.array([f"{i:03d}.1.2.3" for i in range(900)])
    aggs = [AggSpec(f"a{i}", AggFunc[f],
                    None if f == "COUNT" else Col("rev"))
            for i, f in enumerate(funcs)]
    for n, vdt in ((5000, np.float32), (3000, np.int32), (0, np.float32)):
        codes = np.sort(rng.integers(0, 700, n).astype(np.int32))
        vals = rng.uniform(0, 1000, n).astype(vdt)
        key = ColumnVal(codes, sdict, True)
        want = partial_aggregate(PartitionBatch({
            "ip": key, "rev": ColumnVal(vals)}), ["ip"], aggs)
        got = sorted_partial_aggregate(key, vals, "ip", aggs)
        assert got.names() == want.names() and got.num_rows == want.num_rows
        if not n:
            continue    # np.bincount gives an empty sum int64, not float64
        for c in want.names():
            a, b = got.col(c), want.col(c)
            assert a.sdict is b.sdict and a.sorted_dict == b.sorted_dict
            a, b = np.asarray(a.arr), np.asarray(b.arr)
            assert a.dtype == b.dtype and a.shape == b.shape, c
            if b.dtype.kind == "f":
                assert np.allclose(a, b, rtol=1e-12, atol=0), c
            else:
                assert np.array_equal(a, b), c


MERGE_MIXES = [("COUNT",), ("SUM",), ("AVG",), ("SUM", "COUNT"),
               ("AVG", "COUNT", "SUM")]


@pytest.mark.parametrize("vdt", [np.float32, np.int32])
@pytest.mark.parametrize("funcs", MERGE_MIXES)
def test_finalize_disjoint_is_merge_aggregate(funcs, vdt):
    """Per-device states after the exchange (sorted, disjoint keys, one
    device with no rows) finalise to merge_aggregate's rows and dtypes,
    per device and over all devices together."""
    from repro.core.aggregate import (finalize_disjoint, merge_aggregate,
                                      sorted_partial_aggregate)
    from repro.core.expr import Col
    from repro.core.plan import AggFunc, AggSpec
    rng = np.random.default_rng(len(funcs))
    sdict = np.array([f"{i:03d}.1.2.3" for i in range(900)])
    aggs = [AggSpec(f"a{i}", AggFunc[f],
                    None if f == "COUNT" else Col("rev"))
            for i, f in enumerate(funcs)]
    codes = rng.integers(0, 700, 6000).astype(np.int32)
    vals = rng.uniform(0, 1000, 6000).astype(vdt)
    dev = codes % 4
    dev[dev == 3] = 1                   # device 3 receives no rows
    parts = []
    for d in range(4):
        o = np.argsort(codes[dev == d], kind="stable")
        parts.append(sorted_partial_aggregate(
            ColumnVal(codes[dev == d][o], sdict, True), vals[dev == d][o],
            "ip", aggs))
    assert parts[3].num_rows == 0
    dtypes = None
    for pb in parts + [PartitionBatch.concat(parts)]:
        got = finalize_disjoint(pb, ["ip"], aggs)
        want = merge_aggregate(pb, ["ip"], aggs)
        assert got.names() == want.names() and got.num_rows == want.num_rows
        if pb.num_rows == len(np.unique(codes)):
            o = np.argsort(np.asarray(got.col("ip").arr))
        else:
            o = np.arange(got.num_rows)     # one device: already key order
        for c in want.names():
            a, b = got.col(c), want.col(c)
            assert a.sdict is b.sdict and a.sorted_dict == b.sorted_dict
            a, b = np.asarray(a.arr)[o], np.asarray(b.arr)
            assert a.shape == b.shape and np.array_equal(a, b), c
        if not pb.num_rows:
            # merge_aggregate's np.bincount gives an empty float sum as
            # int64; the empty device keeps the dtypes of the others
            assert [got.col(c).arr.dtype for c in got.names()] == dtypes
            continue
        assert [got.col(c).arr.dtype for c in got.names()] == [
            want.col(c).arr.dtype for c in want.names()]
        dtypes = dtypes or [got.col(c).arr.dtype for c in got.names()]


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_merge_string_dicts_unifies_as_unique_and_searchsorted(seed):
    rng = np.random.default_rng(seed)
    words = np.array([f"w{i:03d}" for i in range(300)])
    dicts = [np.unique(rng.choice(words, rng.integers(1, 80)))
             for _ in range(6)]
    dicts.append(rng.choice(words, 40))             # unsorted, duplicates
    dicts.append(np.zeros(0, words.dtype))
    unified, remaps = merge_string_dicts(dicts)
    want = np.unique(np.concatenate(dicts))
    assert np.array_equal(unified, want)
    for d, r in zip(dicts, remaps):
        assert r.dtype == np.int32
        assert np.array_equal(unified[r], d)


def test_concat_keeps_one_shared_sorted_dictionary():
    sdict = np.array(["a", "b", "c", "d"])
    pieces = [PartitionBatch({"k": ColumnVal(np.array(c, np.int32), sdict,
                                             True)})
              for c in ([0, 3], [2], [1, 1, 0])]
    out = PartitionBatch.concat(pieces).col("k")
    assert out.sdict is sdict
    assert out.decoded().tolist() == ["a", "d", "c", "b", "b", "a"]


def test_parallel_load_tasks_build_the_serial_table(monkeypatch):
    from repro.core import columnar
    uv, _ = data4.generate({"uservisits_rows": 5000, "page_urls": 3000}, 5)
    par = columnar.from_arrays("t", SCHEMA, dict(uv), 6)
    monkeypatch.setattr(columnar, "LOAD_THREADS", 1)
    ser = columnar.from_arrays("t", SCHEMA, dict(uv), 6)
    for p, s in zip(par.partitions, ser.partitions):
        assert p.index == s.index and p.num_rows == s.num_rows
        for name in SCHEMA.names:
            assert np.array_equal(p.columns[name].decoded(),
                                  s.columns[name].decoded())
            assert p.columns[name].encoding == s.columns[name].encoding


# -- four devices, in a subprocess --------------------------------------------

CHILD = r"""
import json, sys
import numpy as np
sys.path[:0] = [{repo!r}, {src!r}]
import jax
from repro.cluster import DeviceLost, MeshContext
from repro.core import tracing
sys.path.insert(0, {tests!r})
import test_cluster_strings as t

assert len(jax.devices()) == 4
case = {case!r}
out = {{}}
mesh = MeshContext(max_devices=4)
on, truth = t._session(mesh)
off, _ = t._session(None)
if case == "same_rows":
    out["routes"], out["shipped"], out["devices"] = [], 0, []
    out["reduce"], out["rows"] = [], []
    with tracing.recording():
        for check, q in sorted(t.QUERIES.items()) + [("", t.RANGE_AGG)]:
            n0 = len(tracing.records())
            got = on.sql_np(q)
            out["reduce"].append([r.attrs for r in tracing.records()[n0:]
                                  if r.name == "shark.reduce"])
            out["rows"].append(len(next(iter(got.values()))))
            m = on.metrics()
            out["routes"].append(m.segment_routes())
            out["shipped"] += m.mesh_shipped_rows
            out["devices"].append(m.mesh_devices)
            t._assert_same_rows(got, off.sql_np(q))
            if check:
                ok, gap = t.ref4.Reference(truth).compare(check, {{}}, got)
                assert ok and gap <= 1e-9, (check, gap)
        spans = [r.attrs for r in tracing.records() if r.name == "shark.mesh"]
    out["spans"] = spans
elif case == "device_loss":
    q = t.QUERIES["agg_sourceip"]
    want = off.sql_np(q)
    fired = []

    def killer(ctx, ordinal):
        if not fired:
            fired.append(ordinal)
            victim = ctx.alive_slots()[-1]
            ctx.kill_device(victim)
            raise DeviceLost(victim)

    mesh.on_dispatch = killer
    with tracing.recording():
        got = on.sql_np(q)
        spans = [r.attrs for r in tracing.records() if r.name == "shark.mesh"]
    t._assert_same_rows(got, want)
    out["spans"] = spans
    out["retries"] = mesh.retries
    out["devices"] = on.metrics().mesh_devices
    out["routes"] = on.metrics().segment_routes()
on.shutdown()
off.shutdown()
print("RESULT " + json.dumps(out, default=str))
"""


@functools.lru_cache(maxsize=None)
def _four_devices(case: str) -> dict:
    code = CHILD.format(repo=REPO, src=os.path.join(REPO, "src"),
                        tests=os.path.join(REPO, "tests"), case=case)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    line = [ln for ln in p.stdout.splitlines() if ln.startswith("RESULT ")]
    return json.loads(line[-1][len("RESULT "):])


def test_four_devices_string_keys_cross_chips_with_the_same_rows():
    out = _four_devices("same_rows")
    assert out["devices"] == [4, 4, 4]
    assert [r.get("mesh-exchange", 0) for r in out["routes"]] == [4, 4, 0]
    assert out["routes"][2].get("mesh-colscan", 0) == PARTS
    ex = [s for s in out["spans"] if s["program"] == "exchange"]
    assert len(ex) == 2 and all(s["devices"] == 4 for s in ex)
    # about three rows in four land on another chip
    for s in ex:
        assert 0.6 < s["shipped_rows"] / s["rows"] < 0.9, s
    assert out["shipped"] == sum(s["shipped_rows"] for s in ex)


def test_four_devices_device_loss_mid_exchange_recomputes_identically():
    out = _four_devices("device_loss")
    assert out["retries"] >= 1
    assert out["devices"] == 3
    (span,) = out["spans"]
    assert span["retries"] == 1 and span["devices"] == 3


MERGE_ROUTES = {"numpy", "jit", "segmented_merge"}


@pytest.mark.parametrize("check", sorted(QUERIES))
def test_four_devices_group_by_finalises_each_device_in_place(check):
    """After the exchange each key lives on one device: each device's
    states are finalised where they are, one `disjoint` reduce span per
    device and no shuffle or merge; the rows are checked in the child
    against mesh-off and the reference."""
    out = _four_devices("same_rows")
    i = sorted(QUERIES).index(check)
    routes = out["routes"][i]
    assert routes.get("disjoint") == 4, routes
    assert not set(routes) & MERGE_ROUTES, routes
    spans = out["reduce"][i]
    assert [s["route"] for s in spans] == ["disjoint"] * 4, spans
    assert all(s["op"] == "merge" and s["rows_in"] == s["rows_out"]
               for s in spans), spans
    assert sum(s["rows_out"] for s in spans) == out["rows"][i]


def test_four_devices_colscan_keeps_its_merge():
    out = _four_devices("same_rows")
    routes = out["routes"][2]
    assert "disjoint" not in routes and set(routes) & MERGE_ROUTES, routes
    (span,) = out["reduce"][2]
    assert span["op"] == "merge" and span["route"] in MERGE_ROUTES
    assert span["rows_in"] == PARTS and span["rows_out"] == 1


def test_four_devices_device_loss_finalises_the_survivors():
    out = _four_devices("device_loss")
    routes = out["routes"]
    assert routes.get("disjoint") == out["devices"] == 3, routes
    assert not set(routes) & MERGE_ROUTES, routes
