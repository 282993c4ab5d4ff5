"""Out-of-core storage tier (DESIGN.md §12): adaptive recompression,
spill-segment round-trip + corruption handling, StorageManager tiering with
lineage fallback, server-level budget enforcement through the spill rungs,
and the compressed-domain execution routes (for-colscan / rle-scan)."""

import os
import glob

import numpy as np
import pytest

from repro.core.batch import PartitionBatch
from repro.core.catalog import ExternalSource
from repro.core.columnar import build_partition, from_arrays
from repro.core.compression import (Encoding, choose_recompression, decode_np,
                                    encode, recompress)
from repro.core.session import SharkSession
from repro.core.storage import (SpillCorrupt, StorageManager,
                                deserialize_batch, deserialize_partition,
                                serialize_batch, serialize_partition)
from repro.core.types import DType, Field, Schema
from repro.server.memory import MemoryManager
from repro.server.server import SharkServer

pytestmark = pytest.mark.tier1


SCHEMA = Schema([Field("k", DType.INT64), Field("v", DType.FLOAT64),
                 Field("g", DType.STRING)])


def _partition(n=4000, seed=0):
    rng = np.random.default_rng(seed)
    data = {"k": rng.integers(10**6, 10**6 + (1 << 20), n),
            "v": rng.normal(size=n),
            "g": rng.choice(np.array(["aa", "bb", "cc"]), n)}
    return build_partition(0, SCHEMA, data), data


# ---------------------------------------------------------------------------
# Frame-of-reference encoding + adaptive recompression
# ---------------------------------------------------------------------------


class TestRecompression:
    def test_for_round_trip_lanes(self):
        for lo, span, dtype in [(-500, 200, np.int64), (0, 60000, np.int32),
                                (7 * 10**9, 2**31, np.int64)]:
            rng = np.random.default_rng(span % 97)
            vals = (lo + rng.integers(0, span + 1, 3000)).astype(dtype)
            enc = encode(vals, Encoding.FOR)
            assert enc.encoding == Encoding.FOR
            np.testing.assert_array_equal(decode_np(enc), vals)
            assert enc.codes.dtype.itemsize < np.dtype(dtype).itemsize

    def test_choose_recompression_signals(self):
        rng = np.random.default_rng(1)
        runs = np.repeat(rng.integers(0, 5, 40), 500)
        assert choose_recompression(runs) == Encoding.RLE
        wide = rng.integers(10**9, 10**9 + (1 << 20), 5000).astype(np.int64)
        assert choose_recompression(wide) == Encoding.FOR
        noise = rng.normal(size=5000)
        assert choose_recompression(noise) == Encoding.PLAIN

    def test_recompress_never_grows_and_round_trips(self):
        rng = np.random.default_rng(2)
        for vals in [rng.integers(-1000, 4 * 10**9, 2000).astype(np.int64),
                     np.repeat(rng.integers(0, 3, 30), 100),
                     rng.normal(size=1000),
                     rng.integers(0, 100, 1000).astype(np.int32)]:
            enc = encode(np.asarray(vals), Encoding.PLAIN)
            out = recompress(enc)
            assert out.nbytes <= enc.nbytes
            np.testing.assert_array_equal(decode_np(out), decode_np(enc))

    def test_block_recompress_updates_stats_and_spaces(self):
        rng = np.random.default_rng(3)
        vals = rng.integers(10**8, 10**8 + (1 << 24), 4000).astype(np.int64)
        part = build_partition(0, Schema([Field("k", DType.INT64)]),
                               {"k": vals})
        blk = part.columns["k"]
        blk.values()                       # populate the decode memo
        assert blk.enc.decoded_nbytes > 0
        freed = blk.recompress()
        assert freed > 0
        assert blk.enc.encoding == Encoding.FOR
        assert blk.stats.nbytes == blk.enc.nbytes
        assert blk.enc.decoded_nbytes == 0     # WARM drops the memo
        codes, bias = blk.frame_space()
        np.testing.assert_array_equal(
            codes.astype(np.int64) + bias, vals)


# ---------------------------------------------------------------------------
# Spill segment format
# ---------------------------------------------------------------------------


class TestSegmentFormat:
    def test_round_trip(self):
        part, data = _partition()
        blob = serialize_partition(part.index, part.columns)
        idx, cols = deserialize_partition(blob)
        assert idx == part.index
        assert set(cols) == set(part.columns)
        for name, blk in cols.items():
            np.testing.assert_array_equal(blk.decoded(),
                                          part.columns[name].decoded())
            assert blk.enc.encoding == part.columns[name].enc.encoding
            assert blk.stats.min == part.columns[name].stats.min
            assert blk.stats.max == part.columns[name].stats.max

    def test_round_trip_after_recompress(self):
        part, _ = _partition(seed=5)
        for blk in part.columns.values():
            blk.recompress()
        blob = serialize_partition(0, part.columns)
        _, cols = deserialize_partition(blob)
        for name, blk in cols.items():
            np.testing.assert_array_equal(blk.decoded(),
                                          part.columns[name].decoded())

    def test_corruption_detected(self):
        part, _ = _partition(seed=6)
        blob = bytearray(serialize_partition(0, part.columns))
        with pytest.raises(SpillCorrupt):
            deserialize_partition(b"NOTSPILL" + bytes(blob[8:]))
        flipped = bytearray(blob)
        flipped[len(flipped) // 2] ^= 0xFF
        with pytest.raises(SpillCorrupt):
            deserialize_partition(bytes(flipped))
        with pytest.raises(SpillCorrupt):
            deserialize_partition(bytes(blob[: len(blob) // 2]))


# ---------------------------------------------------------------------------
# StorageManager tiering
# ---------------------------------------------------------------------------


class TestStorageManager:
    def test_spill_and_fault_in(self, tmp_path):
        sm = StorageManager(spill_dir=str(tmp_path), async_write=True)
        part, data = _partition(seed=7)
        expect = {n: part.columns[n].decoded() for n in part.columns}
        freed = sm.evict("t", part)
        assert freed > 0 and not part.resident
        assert part.nbytes > 0          # stats snapshot, no fault-in
        assert not part.resident
        # read-your-writes: fault-in may race the write-behind flush
        got = {n: part.columns[n].decoded() for n in part.columns}
        assert part.resident
        for n in expect:
            np.testing.assert_array_equal(got[n], expect[n])
        st = sm.stats()
        assert st["spills"] == 1 and st["spill_reads"] == 1
        assert st["spill_bytes"] == 0   # segment retired on fault-in
        sm.shutdown()

    def test_flush_then_fault_reads_file(self, tmp_path):
        sm = StorageManager(spill_dir=str(tmp_path), async_write=True)
        part, _ = _partition(seed=8)
        expect = part.columns["k"].decoded().copy()
        sm.evict("t", part)
        sm.flush()
        files = glob.glob(os.path.join(str(tmp_path), "spill-*.shk"))
        assert len(files) == 1
        np.testing.assert_array_equal(part.columns["k"].decoded(), expect)
        assert sm.stats()["spill_reads"] == 1
        assert glob.glob(os.path.join(str(tmp_path), "spill-*.shk")) == []
        sm.shutdown()

    def test_lost_file_falls_back_to_lineage(self, tmp_path):
        sm = StorageManager(spill_dir=str(tmp_path), async_write=False)
        part, data = _partition(seed=9)
        part.lineage = lambda: build_partition(0, SCHEMA, data).columns
        sm.evict("t", part)
        for f in glob.glob(os.path.join(str(tmp_path), "*.shk")):
            os.remove(f)
        np.testing.assert_array_equal(part.columns["k"].decoded(), data["k"])
        st = sm.stats()
        assert st["spill_lost"] == 1 and st["lineage_faults"] == 1
        sm.shutdown()

    def test_corrupt_file_falls_back_to_lineage(self, tmp_path):
        sm = StorageManager(spill_dir=str(tmp_path), async_write=False)
        part, data = _partition(seed=10)
        part.lineage = lambda: build_partition(0, SCHEMA, data).columns
        sm.evict("t", part)
        [f] = glob.glob(os.path.join(str(tmp_path), "*.shk"))
        raw = bytearray(open(f, "rb").read())
        raw[len(raw) // 3] ^= 0x55
        open(f, "wb").write(bytes(raw))
        np.testing.assert_array_equal(part.columns["v"].decoded(), data["v"])
        st = sm.stats()
        assert st["spill_corrupt"] == 1 and st["lineage_faults"] == 1
        sm.shutdown()

    def test_lost_file_without_lineage_raises(self, tmp_path):
        sm = StorageManager(spill_dir=str(tmp_path), async_write=False)
        part, _ = _partition(seed=11)
        sm.evict("t", part)
        for f in glob.glob(os.path.join(str(tmp_path), "*.shk")):
            os.remove(f)
        with pytest.raises(RuntimeError, match="lineage"):
            _ = part.columns

    def test_drop_mode_recomputes(self, tmp_path):
        sm = StorageManager(spill_dir=str(tmp_path), mode="drop")
        part, data = _partition(seed=12)
        part.lineage = lambda: build_partition(0, SCHEMA, data).columns
        sm.evict("t", part)
        assert glob.glob(os.path.join(str(tmp_path), "*.shk")) == []
        np.testing.assert_array_equal(part.columns["k"].decoded(), data["k"])
        st = sm.stats()
        assert st["drops"] == 1 and st["lineage_faults"] == 1
        assert st["spills"] == 0
        sm.shutdown()


# ---------------------------------------------------------------------------
# Server-level integration: budget pressure drives the storage hierarchy
# ---------------------------------------------------------------------------


N_ROWS = 120_000


def _loader(seed=21):
    def load():
        rng = np.random.default_rng(seed)
        return {"k": rng.integers(10**6, 10**6 + (1 << 20), N_ROWS),
                "v": rng.normal(size=N_ROWS),
                "g": rng.choice(np.array(["x", "y", "z", "w"]), N_ROWS)}
    return load


QUERIES = [
    "SELECT g, COUNT(*) AS c, SUM(v) AS s FROM t "
    "WHERE k >= 1200000 GROUP BY g ORDER BY g",
    "SELECT COUNT(*) AS c, MIN(v) AS mn, MAX(v) AS mx FROM t "
    "WHERE k BETWEEN 1100000 AND 1900000",
    "SELECT k, v FROM t WHERE k > 2000000 ORDER BY k LIMIT 50",
]


def _run_server(spill_mode, budget, spill_dir=None, n_rounds=3):
    srv = SharkServer(num_workers=2, max_threads=4,
                      cache_budget_bytes=budget, default_partitions=6,
                      spill_mode=spill_mode, spill_dir=spill_dir)
    srv.register_external(ExternalSource("t", SCHEMA, _loader(), 6))
    sess = srv.session()
    outs = []
    for _ in range(n_rounds):
        for q in QUERIES:
            outs.append(sess.sql_np(q))
    stats = srv.memory.stats()
    srv.shutdown()
    return outs, stats


def _assert_same(outs_a, outs_b):
    assert len(outs_a) == len(outs_b)
    for a, b in zip(outs_a, outs_b):
        assert set(a) == set(b)
        for k in a:
            if a[k].dtype.kind == "U":
                np.testing.assert_array_equal(a[k], b[k])
            else:
                np.testing.assert_allclose(a[k], b[k], rtol=1e-9)


class TestServerSpill:
    def test_spill_under_pressure_correct_and_counted(self, tmp_path):
        baseline, _ = _run_server(None, None)
        spilled, stats = _run_server("spill", 300_000,
                                     spill_dir=str(tmp_path / "sp"))
        _assert_same(baseline, spilled)
        assert stats["spills"] > 0
        assert stats["spill_reads"] > 0
        assert stats["spill_bytes"] >= 0
        # the four ISSUE counters are always present (zeros without storage)
        base_stats = _run_server(None, None, n_rounds=1)[1]
        for key in ("spills", "spill_bytes", "spill_reads",
                    "recompressions"):
            assert key in base_stats and base_stats[key] == 0

    def test_deleted_spill_files_recover_via_lineage(self, tmp_path):
        spill_dir = tmp_path / "sp"
        baseline, _ = _run_server(None, None)
        srv = SharkServer(num_workers=2, max_threads=4,
                          cache_budget_bytes=300_000, default_partitions=6,
                          spill_mode="spill", spill_dir=str(spill_dir))
        srv.register_external(ExternalSource("t", SCHEMA, _loader(), 6))
        sess = srv.session()
        outs = []
        for i in range(3):
            for q in QUERIES:
                outs.append(sess.sql_np(q))
            # hostile filesystem: every spilled segment vanishes mid-run
            srv.storage.flush()
            for f in glob.glob(str(spill_dir / "*.shk")):
                os.remove(f)
        stats = srv.memory.stats()
        srv.shutdown()
        _assert_same(baseline, outs)
        assert stats["lineage_faults"] > 0      # recovery path exercised

    def test_drop_mode_is_recompute_baseline(self, tmp_path):
        baseline, _ = _run_server(None, None)
        dropped, stats = _run_server("drop", 300_000,
                                     spill_dir=str(tmp_path / "sp"))
        _assert_same(baseline, dropped)
        assert stats["lineage_faults"] > 0
        assert stats["spills"] == 0
        assert glob.glob(str(tmp_path / "sp" / "*.shk")) == []


# ---------------------------------------------------------------------------
# Shuffle-block spill (working-set rung)
# ---------------------------------------------------------------------------


class TestShuffleSpill:
    def test_batch_segment_round_trip(self):
        from repro.core.expr import ColumnVal
        rng = np.random.default_rng(9)
        batch = PartitionBatch({
            "k": ColumnVal(rng.integers(0, 100, 500).astype(np.int64)),
            "v": ColumnVal(rng.normal(size=500)),
            "g": ColumnVal(rng.integers(0, 3, 500).astype(np.int32),
                           sdict=np.array(["aa", "bb", "cc"]),
                           sorted_dict=True)})
        out = deserialize_batch(serialize_batch(batch))
        assert out.names() == batch.names()
        for name in batch.names():
            np.testing.assert_array_equal(np.asarray(out.col(name).arr),
                                          np.asarray(batch.col(name).arr))
        np.testing.assert_array_equal(out.col("g").sdict, batch.col("g").sdict)
        assert out.col("g").sorted_dict

    def test_segment_kinds_do_not_cross(self):
        part, _ = _partition(seed=8)
        pblob = serialize_partition(0, part.columns)
        with pytest.raises(SpillCorrupt):
            deserialize_batch(pblob)
        from repro.core.expr import ColumnVal
        sblob = serialize_batch(PartitionBatch(
            {"v": ColumnVal(np.arange(10.0))}))
        with pytest.raises(SpillCorrupt):
            deserialize_partition(sblob)
        flipped = bytearray(sblob)
        flipped[len(flipped) // 2] ^= 0xFF
        with pytest.raises(SpillCorrupt):
            deserialize_batch(bytes(flipped))

    def test_budgeted_shuffle_spills_and_results_identical(self, tmp_path):
        rng = np.random.default_rng(5)
        n = 60_000
        data = {"k": rng.integers(0, 2000, n).astype(np.int64),
                "v": rng.normal(size=n)}
        schema = Schema([Field("k", DType.INT64), Field("v", DType.FLOAT64)])
        q = ("SELECT k, SUM(v) AS s, COUNT(*) AS c FROM t "
             "GROUP BY k ORDER BY k")

        def run(budget):
            sess = SharkSession(num_workers=2, max_threads=4,
                                default_partitions=4)
            sess.create_table("t", schema,
                              {k: v.copy() for k, v in data.items()})
            st = None
            if budget:
                mm = MemoryManager(sess.ctx.block_manager,
                                   budget_bytes=budget)
                mm.attach_catalog(sess.catalog)
                st = StorageManager(spill_dir=str(tmp_path),
                                    async_write=False)
                mm.attach_storage(st)
            r = sess.sql_np(q)
            return r, st, sess

        base, _, _ = run(None)
        out, st, sess = run(120_000)
        for k in base:
            np.testing.assert_allclose(base[k], out[k], rtol=1e-9)
        stats = st.stats()
        assert stats["shuffle_spills"] > 0
        assert stats["shuffle_faults"] > 0
        assert stats["shuffle_lost"] == 0
        # releasing the shuffles retires every spilled segment (the server
        # tier calls this per completed query)
        sess.release_shuffles()
        assert sess.ctx.block_manager.spilled_shuffle == {}
        assert glob.glob(str(tmp_path / "shuf-*.shk")) == []

    def test_lost_shuffle_segment_recomputes_from_lineage(self, tmp_path):
        rng = np.random.default_rng(6)
        n = 60_000
        data = {"k": rng.integers(0, 2000, n).astype(np.int64),
                "v": rng.normal(size=n)}
        schema = Schema([Field("k", DType.INT64), Field("v", DType.FLOAT64)])
        q = ("SELECT k, SUM(v) AS s, COUNT(*) AS c FROM t "
             "GROUP BY k ORDER BY k")
        base_sess = SharkSession(num_workers=2, max_threads=4,
                                 default_partitions=4)
        base_sess.create_table("t", schema,
                               {k: v.copy() for k, v in data.items()})
        base = base_sess.sql_np(q)

        sess = SharkSession(num_workers=2, max_threads=4,
                            default_partitions=4)
        sess.create_table("t", schema, {k: v.copy() for k, v in data.items()})
        mm = MemoryManager(sess.ctx.block_manager, budget_bytes=120_000)
        mm.attach_catalog(sess.catalog)
        st = StorageManager(spill_dir=str(tmp_path), async_write=False)
        mm.attach_storage(st)
        # hostile filesystem: the first faulted segment of each fetch is
        # gone — the fetch must degrade to FetchFailed -> lineage recompute
        real = st.fault_shuffle
        state = {"dropped": 0}

        def flaky(ref):
            if state["dropped"] < 3:
                state["dropped"] += 1
                st.forget_shuffle(ref)
                return None
            return real(ref)

        st.fault_shuffle = flaky
        out = sess.sql_np(q)
        for k in base:
            np.testing.assert_allclose(base[k], out[k], rtol=1e-9)
        assert state["dropped"] > 0
        assert sess.ctx.block_manager.shuffle_spill_lost > 0


# ---------------------------------------------------------------------------
# Compressed-domain execution routes
# ---------------------------------------------------------------------------


def _for_session(backend: str):
    """A table recompressed to FOR and RLE blocks: the compiled backend
    reads them in the compressed domain, the numpy-backend reference
    decodes every block before it evaluates."""
    rng = np.random.default_rng(33)
    n = 40_000
    data = {"k": rng.integers(5 * 10**6, 5 * 10**6 + (1 << 20),
                              n).astype(np.int64),
            "r": np.repeat(rng.integers(0, 40, 200),
                           n // 200).astype(np.int32),
            "v": rng.normal(size=n)}
    schema = Schema([Field("k", DType.INT64), Field("r", DType.INT32),
                     Field("v", DType.FLOAT64)])
    sess = SharkSession(num_workers=2, max_threads=4, default_partitions=4,
                        backend=backend)
    sess.create_table("t", schema, data)
    for part in sess.catalog.get("t").partitions:
        for blk in part._columns.values():
            blk.recompress()
    encs = {n_: b.enc.encoding
            for p in sess.catalog.get("t").partitions
            for n_, b in p._columns.items()}
    assert encs["k"] == Encoding.FOR and encs["r"] == Encoding.RLE
    return sess


class TestCompressedDomainRoutes:
    def test_for_colscan_route_and_parity(self):
        on, off = _for_session("compiled"), _for_session("numpy")
        q = ("SELECT COUNT(*) AS c, SUM(v) AS s, MIN(v) AS mn FROM t "
             "WHERE k BETWEEN 5200000 AND 5700000")
        r_on, r_off = on.sql_np(q), off.sql_np(q)
        assert "for-colscan" in on.metrics().segment_routes()
        assert "for-colscan" not in off.metrics().segment_routes()
        for k in r_on:
            np.testing.assert_allclose(r_on[k], r_off[k], rtol=1e-12)

    def test_rle_scan_route_and_parity(self):
        on, off = _for_session("compiled"), _for_session("numpy")
        for q in ("SELECT COUNT(*) AS c, SUM(r) AS s, MAX(r) AS mx FROM t "
                  "WHERE r BETWEEN 5 AND 25",
                  "SELECT COUNT(*) AS c, SUM(v) AS s FROM t "
                  "WHERE r BETWEEN 5 AND 25"):
            r_on, r_off = on.sql_np(q), off.sql_np(q)
            assert "rle-scan" in on.metrics().segment_routes()
            assert "rle-scan" not in off.metrics().segment_routes()
            for k in r_on:
                np.testing.assert_allclose(r_on[k], r_off[k], rtol=1e-12)

    def test_bitpack_colscan_route_and_parity(self):
        # small-range ints BITPACK-encode at load; the jit colscan must
        # compare biased codes on the packed lanes (host-translated bounds)
        # instead of widening the filter column
        def _bp_session(backend: str):
            rng = np.random.default_rng(7)
            n = 40_000
            data = {"b": rng.integers(-50, 50, n).astype(np.int64),
                    "v": rng.normal(size=n)}
            schema = Schema([Field("b", DType.INT64),
                             Field("v", DType.FLOAT64)])
            sess = SharkSession(num_workers=2, max_threads=4,
                                default_partitions=4, backend=backend)
            sess.create_table("t", schema, data)
            encs = {nm: blk.enc.encoding
                    for p in sess.catalog.get("t").partitions
                    for nm, blk in p._columns.items()}
            assert encs["b"] == Encoding.BITPACK
            return sess

        on, off = _bp_session("compiled"), _bp_session("numpy")
        for q in ("SELECT COUNT(*) AS c, SUM(v) AS s, MIN(v) AS mn, "
                  "MAX(v) AS mx FROM t WHERE b BETWEEN -30 AND 20",
                  "SELECT COUNT(*) AS c, SUM(v) AS s FROM t WHERE b >= 44"):
            r_on, r_off = on.sql_np(q), off.sql_np(q)
            assert "bitpack-colscan" in on.metrics().segment_routes()
            assert "bitpack-colscan" not in off.metrics().segment_routes()
            for k in r_on:
                np.testing.assert_allclose(r_on[k], r_off[k], rtol=1e-12)

    def test_for_filter_projection_parity(self):
        on, off = _for_session("compiled"), _for_session("numpy")
        q = "SELECT k, v FROM t WHERE k > 5900000 ORDER BY k"
        r_on, r_off = on.sql_np(q), off.sql_np(q)
        for k in r_on:
            np.testing.assert_array_equal(r_on[k], r_off[k])

    def test_explain_identical_on_off(self):
        on, off = _for_session("compiled"), _for_session("numpy")
        for q in ["SELECT COUNT(*) AS c FROM t WHERE k BETWEEN 5200000 "
                  "AND 5700000",
                  "SELECT k, v FROM t WHERE k > 5900000 ORDER BY k"]:
            assert on.explain(q) == off.explain(q)

    def test_exec_metrics_carry_spill_deltas(self, tmp_path):
        from repro.core.storage import StorageManager
        rng = np.random.default_rng(44)
        n = 60_000
        data = {"k": rng.integers(0, 10**9, n),
                "v": rng.normal(size=n)}
        schema = Schema([Field("k", DType.INT64), Field("v", DType.FLOAT64)])
        sess = SharkSession(num_workers=2, max_threads=4,
                            default_partitions=4)
        mm = MemoryManager(sess.ctx.block_manager, budget_bytes=150_000)
        mm.attach_catalog(sess.catalog)
        storage = StorageManager(spill_dir=str(tmp_path), async_write=False)
        mm.attach_storage(storage)
        src = ExternalSource("t", schema,
                             lambda: {k: v.copy() for k, v in data.items()},
                             4)
        sess.register_external(src)
        r1 = sess.sql_np("SELECT COUNT(*) AS c, SUM(v) AS s FROM t "
                         "WHERE k > 500000000")
        mm.enforce()
        r2 = sess.sql_np("SELECT COUNT(*) AS c, SUM(v) AS s FROM t "
                         "WHERE k > 500000000")
        m = sess.metrics()
        np.testing.assert_allclose(r1["c"], r2["c"])
        assert storage.stats()["spills"] > 0
        assert m.spill_reads > 0        # faulted segments back this query
        storage.shutdown()
