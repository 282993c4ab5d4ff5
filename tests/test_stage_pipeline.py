"""Whole-stage compilation + pipelined scheduling (DESIGN.md §14).

Deterministic probes of the fused-stage machinery, complementing the
seeded differential grid in test_oracle_differential.py:

  * the pipelined scheduler observably starts a reduce task BEFORE the map
    stage drains (event-order probe on the recorded `shark.stage.*`
    events of `repro.core.tracing`, with a straggler injected on the later
    map splits);
  * the reduce result computed by the pipeline is consumed through
    `PipelinedShuffledRDD` (hit counter) and matches the pull path;
  * double-buffered Pallas dispatch (colscan chunking, radix-partition
    chunking) is bit-identical to single-shot dispatch
    (kernels_interpret-marked, runs on CPU in interpret mode);
  * fusion is physical-layer only: `explain()` text and the optimizer
    `plan_fingerprint` are byte-identical with the default, fused and
    unfused configurations.
"""

import numpy as np
import pytest

from repro.core import DType, Schema, SharkSession, tracing
from repro.core.pde import (PDEConfig, decide_pipelined_reduce,
                            decide_stage_fusion)
from repro.core.physical import Executor
from repro.server import SharkServer

from conftest import fusion_pde

pytestmark = pytest.mark.tier1


def _stage_events():
    """(seq, kind, shuffle_id, split) of each recorded stage event, in the
    order they happened (record ids are issued in that order)."""
    return sorted((r.id, r.name[len("shark.stage."):], r.attrs["shuffle_id"],
                   r.attrs["split"]) for r in tracing.records()
                  if r.name.startswith("shark.stage."))


def _dispatches() -> int:
    """Kernel launches made through double_buffer_map while recording."""
    return sum(r.attrs["chunks"] for r in tracing.records()
               if r.name == "shark.device"
               and r.attrs["program"] == "double_buffer")

FORCE_KERNELS = PDEConfig(segment_force_kernels=True,
                          segment_kernel_min_rows=256,
                          segment_min_compiled_rows=1)


def _star_session(backend="compiled", pde_config=None, rows=3000,
                  partitions=3, **kw):
    rng = np.random.default_rng(0)
    sess = SharkSession(num_workers=2, max_threads=4,
                        default_partitions=partitions, backend=backend,
                        pde_config=pde_config, **kw)
    data = {
        "fn": rng.integers(0, 100, rows).astype(np.int64),
        "fv": rng.uniform(0, 10, rows),
        "fd": rng.choice(np.round(np.linspace(0.0, 9.0, 37), 3), rows),
        "fs": np.array([f"g{i}" for i in rng.integers(0, 8, rows)]),
    }
    sess.create_table("t", Schema.of(fn=DType.INT64, fv=DType.FLOAT64,
                                     fd=DType.FLOAT64, fs=DType.STRING),
                      data)
    return sess, data


# ---------------------------------------------------------------------------
# PDE gate
# ---------------------------------------------------------------------------


def test_stage_fusion_gate():
    cfg = PDEConfig()
    big = cfg.stage_fusion_min_rows
    assert decide_stage_fusion(big, "compiled", cfg).route == "whole-stage"
    assert decide_stage_fusion(big, "numpy", cfg).route == "segment"
    # the row threshold is the one gate on the compiled backend
    assert decide_stage_fusion(big - 1, "compiled",
                               cfg).route == "segment"
    assert decide_stage_fusion(0, "compiled",
                               fusion_pde(True)).route == "whole-stage"
    # the exchange and fusion are not options: one production path
    for opt in ("exchange", "stage_fusion", "enable_pde",
                "enable_map_pruning"):
        for make in (SharkSession, SharkServer,
                     lambda **kw: Executor(None, None, **kw)):
            with pytest.raises(TypeError):
                make(**{opt: None})


def test_pipelined_reduce_admission_gate():
    """The overlap thread is admitted only when the executor pool keeps a
    slot free of map tasks."""
    cfg = PDEConfig()
    assert decide_pipelined_reduce(3, 4, cfg).route == "pipelined"
    # map splits saturate (or exceed) the pool -> sequential pull fetch
    assert decide_pipelined_reduce(4, 4, cfg).route == "pull"
    assert decide_pipelined_reduce(8, 4, cfg).route == "pull"
    # the slack requirement is a PDE knob
    wide = PDEConfig(pipeline_reduce_slack_threads=3)
    assert decide_pipelined_reduce(3, 4, wide).route == "pull"
    assert decide_pipelined_reduce(1, 4, wide).route == "pipelined"


def test_pull_fallback_when_pool_is_saturated():
    """With map splits saturating the pool the boundary must skip the
    overlap thread (no reduce-fetch event) and still be row-identical."""
    sess, data = _star_session(partitions=4)   # 4 splits, 4 pool threads
    with tracing.recording():
        got = sess.sql_np("SELECT SUM(fv) AS s, COUNT(*) AS c FROM t")
    np.testing.assert_allclose(got["s"], [data["fv"].sum()], rtol=1e-9)
    assert int(got["c"][0]) == len(data["fv"])
    assert not any(e[1] == "reduce-fetch" for e in _stage_events())
    assert any("sequential fetch" in r
               for r in sess.metrics().pipeline_decisions)
    # the fused map side is unaffected by the reduce-side admission gate
    assert sess.metrics().fused_partitions() > 0
    sess.shutdown()


# ---------------------------------------------------------------------------
# Pipelined scheduling: reduce starts before the map stage drains
# ---------------------------------------------------------------------------


def test_reduce_starts_before_map_stage_drains(monkeypatch):
    """Straggle map splits 1..n; the pipelined reduce must fetch map 0's
    pieces (logging "reduce-fetch") while the stragglers are still
    running — i.e. at a lower event sequence than the last "map-done"."""
    sess, data = _star_session()
    sched = sess.ctx.scheduler
    orig = sched.run_map_stage

    def straggle_then_run(dep, *a, **kw):
        dep.parent.delay_fn = lambda split: 0.0 if split == 0 else 0.4
        return orig(dep, *a, **kw)

    monkeypatch.setattr(sched, "run_map_stage", straggle_then_run)
    with tracing.recording():
        got = sess.sql_np("SELECT SUM(fv) AS s, COUNT(*) AS c FROM t")
    np.testing.assert_allclose(got["s"], [data["fv"].sum()], rtol=1e-9)
    assert int(got["c"][0]) == len(data["fv"])

    ev = _stage_events()
    fetches = [e for e in ev if e[1] == "reduce-fetch"]
    assert fetches, f"no pipelined reduce-fetch event: {ev}"
    shuffle_id = fetches[0][2]
    dones = [e for e in ev if e[1] == "map-done" and e[2] == shuffle_id]
    assert len(dones) == 3
    assert fetches[0][0] < max(d[0] for d in dones), \
        f"reduce never overlapped the map stage: {ev}"
    assert any(e[1] == "reduce-done" and e[2] == shuffle_id for e in ev)
    sess.shutdown()


def test_pipelined_reduce_result_is_consumed(monkeypatch):
    """The result stage must consume the pipeline-precomputed reduce output
    (PipelinedShuffledRDD hit) rather than recomputing it via pull."""
    import repro.core.physical as phys
    captured = []
    base = phys.PipelinedShuffledRDD

    class Capture(base):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            captured.append(self)

    monkeypatch.setattr(phys, "PipelinedShuffledRDD", Capture)
    sess, data = _star_session()
    got = sess.sql_np("SELECT MIN(fn) AS mn, MAX(fn) AS mx FROM t")
    assert int(got["mn"][0]) == int(data["fn"].min())
    assert int(got["mx"][0]) == int(data["fn"].max())
    assert captured, "global aggregate did not build a PipelinedShuffledRDD"
    assert sum(r.pipelined_hits for r in captured) > 0
    sess.shutdown()


def test_pipelined_reduce_failure_falls_back_to_pull(monkeypatch):
    """A crashing pipelined reduce attempt is an overlap loss, never a
    correctness loss: the split recomputes on the standard pull path."""
    from repro.core.runtime import Scheduler
    orig = Scheduler._pipelined_reduce

    def crash(self, dep, split, buckets, reduce_fn, cancel, results, rlock):
        def boom(*a, **kw):
            raise RuntimeError("injected pipelined-reduce failure")
        return orig(self, dep, split, buckets, boom, cancel, results, rlock)

    monkeypatch.setattr(Scheduler, "_pipelined_reduce", crash)
    sess, data = _star_session()
    with tracing.recording():
        got = sess.sql_np("SELECT SUM(fv) AS s, COUNT(*) AS c FROM t")
    np.testing.assert_allclose(got["s"], [data["fv"].sum()], rtol=1e-9)
    assert int(got["c"][0]) == len(data["fv"])
    assert not any(e[1] == "reduce-done" for e in _stage_events())
    sess.shutdown()


def test_pipelined_reduce_persistent_failure_raises(monkeypatch):
    """A reduce that fails every time is not swallowed by the pipelined
    attempt: the pull path re-runs it and raises the same error."""
    from repro.core.physical import ReduceRunner

    def boom(self, *a, **kw):
        raise RuntimeError("injected compiled-reduce failure")

    monkeypatch.setattr(ReduceRunner, "merge", boom)
    sess, _ = _star_session()
    with pytest.raises(RuntimeError, match="injected compiled-reduce"):
        sess.sql_np("SELECT SUM(fv) AS s, COUNT(*) AS c FROM t")
    sess.shutdown()


# ---------------------------------------------------------------------------
# Double-buffered Pallas dispatch (interpret mode on CPU)
# ---------------------------------------------------------------------------


@pytest.mark.kernels_interpret
def test_double_buffered_colscan_matches_single_shot(monkeypatch):
    from repro.kernels import ops as kernel_ops
    sess_n, _ = _star_session(backend="numpy", rows=5000)
    want = sess_n.sql_np("SELECT COUNT(*) AS c, SUM(fv) AS s, MIN(fv) AS mn,"
                         " MAX(fv) AS mx FROM t WHERE fn BETWEEN 20 AND 80")
    sess_n.shutdown()

    monkeypatch.setitem(kernel_ops.DOUBLE_BUFFER, "chunk_rows", 512)
    sess_k, _ = _star_session(pde_config=FORCE_KERNELS, rows=5000)
    with tracing.recording():
        got = sess_k.sql_np("SELECT COUNT(*) AS c, SUM(fv) AS s, MIN(fv) AS "
                            "mn, MAX(fv) AS mx FROM t WHERE fn BETWEEN 20 "
                            "AND 80")
    assert sess_k.metrics().segment_routes().get("colscan", 0) > 0
    assert _dispatches() > 1, \
        "colscan never took the double-buffered chunk path"
    for k in want:
        np.testing.assert_allclose(got[k], want[k], rtol=1e-12)
    sess_k.shutdown()


@pytest.mark.kernels_interpret
def test_double_buffered_radix_partition_is_bit_identical(monkeypatch):
    from repro.core.shuffle import _kernel_buckets
    from repro.kernels import ops as kernel_ops
    rng = np.random.default_rng(3)
    k = rng.integers(0, 1 << 40, 5000).astype(np.uint64)
    full = _kernel_buckets(k, 8)
    monkeypatch.setitem(kernel_ops.DOUBLE_BUFFER, "chunk_rows", 512)
    with tracing.recording():
        chunked = _kernel_buckets(k, 8)
    assert _dispatches() == int(np.ceil(5000 / 512))
    np.testing.assert_array_equal(full, chunked)


# ---------------------------------------------------------------------------
# Fusion is invisible to the planner: explain + fingerprint parity
# ---------------------------------------------------------------------------

PLAN_SQLS = [
    "SELECT fn, fv FROM t WHERE fn > 50",
    "SELECT SUM(fv) AS s, COUNT(*) AS c FROM t WHERE fn < 30",
    "SELECT fs, SUM(fv) AS s FROM t GROUP BY fs",
    "SELECT fn, fv FROM t ORDER BY fv DESC LIMIT 7",
]


def test_explain_and_fingerprint_identical_across_fusion_modes():
    from repro.core.plan import optimize
    from repro.server.result_cache import plan_fingerprint
    sessions = {"default": _star_session()[0],
                "fused": _star_session(pde_config=fusion_pde(True))[0],
                "unfused": _star_session(pde_config=fusion_pde(False))[0]}
    try:
        for sql in PLAN_SQLS:
            plans = {m: s.explain(sql) for m, s in sessions.items()}
            assert plans["default"] == plans["unfused"] == plans["fused"], \
                sql
            fps = {m: plan_fingerprint(
                       optimize(s.plan(sql), s.catalog), s.catalog)[0]
                   for m, s in sessions.items()}
            assert fps["default"] == fps["unfused"] == fps["fused"], sql
            # and the plans actually execute identically
            got = {m: s.sql_np(sql) for m, s in sessions.items()}
            for k in got["unfused"]:
                np.testing.assert_array_equal(got["default"][k],
                                              got["unfused"][k])
                np.testing.assert_array_equal(got["fused"][k],
                                              got["unfused"][k])
        assert sessions["unfused"].metrics().fused_partitions() == 0
        assert sessions["fused"].metrics().fused_partitions() > 0
    finally:
        for s in sessions.values():
            s.shutdown()
