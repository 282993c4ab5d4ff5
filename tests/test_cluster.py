"""Cluster tier — mesh-sharded execution (DESIGN.md §13.1).

Tier-1 tests run on however many XLA devices the host exposes (a 1-device
mesh exercises the full shard_map + all_to_all machinery); the
multidevice-marked tests need >= 2 devices and are re-run by scripts/ci.sh
under ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.

The oracle grid is the tentpole invariant: with mesh sharding ON the
engine must return ROW-IDENTICAL results (same order, same dtypes, values
to float tolerance) to the single-host path, and explain()/plan
fingerprints must be byte-identical — placement is physical-layer state.
"""

import numpy as np
import pytest

import jax

from repro.core import DType, Schema
from repro.core.session import SharkSession
from repro.cluster import DeviceLost, MeshContext
from repro.cluster import shard_exec

pytestmark = pytest.mark.tier1

N_DEV = len(jax.devices())


def _data(n=50_000, seed=7):
    rng = np.random.default_rng(seed)
    return {
        "k": rng.integers(0, 40, n).astype(np.int64),
        "k32": rng.integers(0, 500, n).astype(np.int32),
        "x": rng.uniform(-100.0, 100.0, n),
        "v": rng.uniform(0.0, 10.0, n),
        "i32": rng.integers(0, 1000, n).astype(np.int32),
        "s": rng.choice(np.array(["ca", "ny", "tx", "wa"]), n),
    }


SCHEMA = Schema.of(k=DType.INT64, k32=DType.INT32, x=DType.FLOAT64,
                   v=DType.FLOAT64, i32=DType.INT32, s=DType.STRING)


def _session(mesh, parts=12):
    sess = SharkSession(num_workers=4, default_partitions=parts, mesh=mesh)
    sess.create_table("t", SCHEMA, _data())
    return sess


# the differential grid: every aggregate shape the mesh routes handle plus
# shapes that must silently fall back to the host path
GRID = [
    "SELECT COUNT(*) AS c FROM t WHERE x BETWEEN -20 AND 60",
    "SELECT COUNT(*) AS c, SUM(v) AS sv, MIN(v) AS mn, MAX(v) AS mx "
    "FROM t WHERE x BETWEEN -20 AND 60",
    "SELECT AVG(v) AS a FROM t WHERE x >= 10",
    "SELECT SUM(i32) AS si FROM t WHERE x < 0",
    "SELECT k, COUNT(*) AS c, SUM(v) AS sv FROM t GROUP BY k",
    "SELECT k, AVG(v) AS a FROM t GROUP BY k",
    "SELECT k32, SUM(i32) AS si FROM t GROUP BY k32",
    # a string group key crosses devices as table-wide dictionary codes
    "SELECT s, COUNT(*) AS c FROM t GROUP BY s",
    # host-path fallbacks: multi-col predicate, string aggregate input,
    # int64 SUM exactness, expression argument
    "SELECT COUNT(*) AS c FROM t WHERE v > 5 AND x < 0",
    "SELECT COUNT(DISTINCT s) AS d FROM t WHERE x > 0",
    "SELECT k, SUM(k) AS sk FROM t GROUP BY k",
    "SELECT SUM(v + 1.0) AS sv FROM t WHERE x > 0",
]


class TestMeshOracleGrid:
    def test_mesh_on_vs_off_row_identical(self):
        on, off = _session(MeshContext()), _session(None)
        try:
            mesh_routed = 0
            for q in GRID:
                r1, r0 = on.sql_np(q), off.sql_np(q)
                assert list(r1) == list(r0), q
                for c in r0:
                    a1, a0 = r1[c], r0[c]
                    assert a1.dtype == a0.dtype, (q, c, a1.dtype, a0.dtype)
                    assert a1.shape == a0.shape, (q, c)
                    if a0.dtype.kind in "iuU":
                        # integer and string columns exactly, IN ORDER
                        assert np.array_equal(a1, a0), (q, c)
                    else:
                        assert np.allclose(a1, a0, rtol=1e-9, atol=1e-9), \
                            (q, c)
                routes = on.metrics().segment_routes()
                mesh_routed += routes.get("mesh-colscan", 0)
                mesh_routed += routes.get("mesh-exchange", 0)
            # the grid must actually exercise the mesh, not fall back
            # everywhere (8 eligible queries x >= 1 routed partition)
            assert mesh_routed >= 8, mesh_routed
        finally:
            on.shutdown()
            off.shutdown()

    def test_fallback_queries_take_host_routes(self):
        on = _session(MeshContext())
        try:
            for q in GRID[8:]:
                on.sql_np(q)
                routes = on.metrics().segment_routes()
                assert "mesh-colscan" not in routes, q
                assert "mesh-exchange" not in routes, q
        finally:
            on.shutdown()

    def test_explain_and_fingerprint_identical_with_sharding(self):
        from repro.server.result_cache import plan_fingerprint
        from repro.core.plan import optimize
        on, off = _session(MeshContext()), _session(None)
        try:
            for q in GRID:
                assert on.explain(q) == off.explain(q), q
                n1 = optimize(on.plan(q), on.catalog)
                n0 = optimize(off.plan(q), off.catalog)
                fp1, _ = plan_fingerprint(n1, on.catalog)
                fp0, _ = plan_fingerprint(n0, off.catalog)
                assert fp1 == fp0, q
        finally:
            on.shutdown()
            off.shutdown()


class TestMeshPlacement:
    def test_round_robin_over_alive_slots(self):
        ctx = MeshContext()
        p = ctx.place(10)
        n = len(ctx.devices)
        assert p.device_of == tuple(i % n for i in range(10))
        assert p.n_devices == n

    def test_generation_bumps_and_mesh_shrinks_on_kill(self):
        if N_DEV < 2:
            pytest.skip("needs >= 2 devices")
        ctx = MeshContext()
        g0 = ctx.generation
        ctx.kill_device(1)
        assert ctx.generation == g0 + 1
        assert 1 not in ctx.alive_slots()
        mesh, gen = ctx.mesh()
        assert len(mesh.devices.ravel()) == N_DEV - 1
        p = ctx.place(6)
        assert all(s != 1 for s in (p.alive_slots[d] for d in p.device_of))

    def test_cannot_kill_last_device(self):
        ctx = MeshContext(max_devices=1)
        with pytest.raises(RuntimeError):
            ctx.kill_device(0)


class TestMeshExchange:
    def test_exchange_partitions_by_key_and_preserves_rows(self):
        rng = np.random.default_rng(5)
        ctx = MeshContext()
        keys = [rng.integers(0, 64, n).astype(np.int64)
                for n in rng.integers(10, 400, 13)]
        vals = [rng.uniform(0, 5, k.shape[0]) for k in keys]
        out, rep = shard_exec.mesh_group_exchange(ctx, keys, vals)
        assert rep["devices"] == N_DEV
        allk = np.concatenate(keys)
        gotk = np.concatenate([k for k, _ in out])
        assert sorted(allk.tolist()) == sorted(gotk.tolist())
        owner = {}
        for d, (k, _) in enumerate(out):
            for kk in set(k.tolist()):
                assert owner.setdefault(kk, d) == d, "key on two devices"
        # per-key value sums survive the collective
        want, got = {}, {}
        for k, v in zip(allk, np.concatenate(vals)):
            want[int(k)] = want.get(int(k), 0.0) + v
        for kd, vd in out:
            for k, v in zip(kd, vd):
                got[int(k)] = got.get(int(k), 0.0) + v
        for k in want:
            assert np.isclose(want[k], got[k])

    def test_host_mirror_counts_match_device_hash(self):
        rng = np.random.default_rng(6)
        ctx = MeshContext()
        keys = [rng.integers(0, 1000, 300).astype(np.int64)
                for _ in range(5)]
        out, rep = shard_exec.mesh_group_exchange(ctx, keys, None)
        counts = rep["counts"]
        assert counts.sum() == sum(k.shape[0] for k in keys)
        # received rows per device == the mirror's column sums (the device
        # program and the numpy mirror share fold_keys_u32 + mix_u32)
        for d, (kd, vd) in enumerate(out):
            assert vd is None
            assert kd.shape[0] == int(counts[:, d].sum())


class TestResidentLanes:
    """Lanes of placed partitions kept on the devices between dispatches
    (`MeshContext.resident`)."""

    def test_hits_only_for_the_same_arrays_and_generation(self):
        from repro.cluster.mesh import ResidentLanes
        store = ResidentLanes(budget=None)
        a, b = np.arange(4), np.arange(4)
        assert store.keep("t", [a], 0, "A", 10) == 10
        assert store.get("t", [a], 0) == "A"
        assert store.get("t", [b], 0) is None          # equal, not the same
        assert store.get("u", [a], 0) is None          # another lane
        assert store.get("t", [a], 1) is None          # stale generation
        assert len(store) == 0 and store.pinned == 0   # ... and dropped

    def test_entry_goes_with_its_arrays(self):
        from repro.cluster.mesh import ResidentLanes
        store = ResidentLanes(budget=None)
        a, keep = np.arange(4), np.arange(3)
        store.keep("t", [a, keep], 0, "A", 10)
        del a
        assert len(store) == 0 and store.pinned == 0

    def test_budget_evicts_least_recently_used(self):
        from repro.cluster.mesh import ResidentLanes
        store = ResidentLanes(budget=25)
        xs = [np.arange(i + 1) for i in range(3)]
        store.keep("t", [xs[0]], 0, 0, 10)
        store.keep("t", [xs[1]], 0, 1, 10)
        assert store.get("t", [xs[0]], 0) == 0         # xs[1] is now oldest
        store.keep("t", [xs[2]], 0, 2, 10)
        assert store.get("t", [xs[1]], 0) is None
        assert store.get("t", [xs[0]], 0) == 0 and store.pinned == 20
        assert store.keep("t", [xs[1]], 0, 1, 26) == 0  # never fits

    def test_repeat_dispatch_ships_no_lanes(self):
        from repro.core import tracing
        rng = np.random.default_rng(8)
        ctx = MeshContext()
        keys = [rng.integers(0, 99, 300).astype(np.int64) for _ in range(5)]
        vals = [rng.uniform(0, 1, 300) for _ in range(5)]
        with tracing.recording():
            first, _ = shard_exec.mesh_group_exchange(ctx, keys, vals)
            again, _ = shard_exec.mesh_group_exchange(ctx, keys, vals)
            spans = [r.attrs for r in tracing.records()
                     if r.name == "shark.mesh"]
        assert [s.get("fills", 0) for s in spans] == [2, 0]
        assert spans[1]["h2d_bytes"] < spans[0]["h2d_bytes"] // 100
        for (k1, v1), (k2, v2) in zip(first, again):
            assert np.array_equal(k1, k2) and np.array_equal(v1, v2)
        ctx.revive_all()                # nothing died: lanes stay
        assert len(ctx.resident) == 2

    def test_device_counts_are_checked_against_the_mirror(self, monkeypatch):
        ctx = MeshContext()
        keys = [np.arange(100, dtype=np.int64)]
        real = shard_exec._mirror
        monkeypatch.setattr(
            shard_exec, "_mirror", lambda host, N, n_dev: real(
                host, N, n_dev) + np.eye(n_dev, dtype=np.int64))
        with pytest.raises(RuntimeError, match="host mirror"):
            shard_exec.mesh_group_exchange(ctx, keys, None)


@pytest.mark.multidevice
@pytest.mark.skipif(N_DEV < 2, reason="needs >= 2 XLA devices")
class TestMultiDevice:
    def test_runs_on_many_devices(self):
        assert N_DEV >= 2

    def test_exchange_ships_rows_across_devices(self):
        on = _session(MeshContext())
        try:
            on.sql_np("SELECT k, SUM(v) AS sv FROM t GROUP BY k")
            m = on.metrics()
            assert m.mesh_devices == N_DEV
            assert m.mesh_shipped_rows > 0      # buckets crossed devices
            assert m.mesh_partitions == 12
        finally:
            on.shutdown()

    def test_device_loss_mid_query_recomputes_identically(self):
        mesh = MeshContext()
        on, off = _session(mesh), _session(None)
        try:
            q = "SELECT k, COUNT(*) AS c, SUM(v) AS sv FROM t GROUP BY k"
            expect = off.sql_np(q)

            fired = []

            def killer(ctx, ordinal):
                if not fired:
                    fired.append(ordinal)
                    victim = ctx.alive_slots()[-1]
                    ctx.kill_device(victim)
                    raise DeviceLost(victim)

            mesh.on_dispatch = killer
            got = on.sql_np(q)
            assert mesh.retries >= 1
            assert on.metrics().mesh_retries >= 1
            assert on.metrics().mesh_devices == N_DEV - 1
            assert np.array_equal(got["k"], expect["k"])
            assert np.array_equal(got["c"], expect["c"])
            assert np.allclose(got["sv"], expect["sv"], rtol=1e-9)
        finally:
            on.shutdown()
            off.shutdown()

    def test_colscan_shards_partitions_across_devices(self):
        mesh = MeshContext()
        on = _session(mesh)
        try:
            on.sql_np("SELECT COUNT(*) AS c, SUM(v) AS sv FROM t "
                      "WHERE x BETWEEN -50 AND 50")
            m = on.metrics()
            assert m.mesh_partitions == 12
            assert m.mesh_devices == N_DEV
            assert m.mesh_shipped_rows == 0     # colscan needs no collective
            p = mesh.place(12)
            assert len(set(p.device_of)) == min(N_DEV, 12)
        finally:
            on.shutdown()
