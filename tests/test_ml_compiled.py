"""Compiled analytics tier (DESIGN.md §15): encoded feature pipelines,
PDE-scheduled iterative training, and their fault-tolerance story.

The tentpole claims under test:

  * differential parity — the encoded FeatureRDD path (decode fused into
    the jitted assemble+train step) produces BIT-IDENTICAL per-iteration
    gradients and final weights vs the host-materialized dense path, under
    forced float64 (the decode recipes are exact integer ops, so the XLA
    matmuls see identical operands);
  * zero host decode — training over cached encoded partitions never
    moves `expr.DECODE_COUNTERS`;
  * scheduling — every iteration is a map stage with a `<train:...>`
    segment record and per-route counts in ExecMetrics;
  * chaos — a worker killed mid-iteration (its cached feature blocks AND
    its map outputs vanish) costs a lineage recompute, not correctness:
    final weights equal the failure-free run bitwise;
  * residency — on the Pallas train_grad route each partition's kernel
    inputs are laid out on the device once per fit and read there by
    every later iteration, with weights bitwise equal to host-assembled
    gradients; a block recomputed from lineage refills, the budget sends
    partitions beyond it to `resident="none"`, and nothing stays on the
    device after `fit` returns or raises.
"""

import numpy as np
import pytest

from repro.core import DType, Schema, SharkSession
from repro.core.expr import DECODE_COUNTERS
from repro.core.pde import PDEConfig, decide_train_backend
from repro.ml import (FeatureRDD, IterativeTrainer, LogisticRegression,
                      KMeans, table_rdd_to_features)

pytestmark = pytest.mark.tier1

D = 5
ROWS = 4000


def _int_points_session(rows=ROWS, parts=4):
    """Small-range int64 columns: the load task FOR/BITPACK-encodes them,
    so the encoded pipeline has real block recipes to fuse."""
    rng = np.random.default_rng(7)
    w = rng.normal(size=D)
    raw = rng.integers(0, 16, size=(rows, D)).astype(np.int64)
    cols = {f"f{i}": raw[:, i] + 500 for i in range(D)}
    cols["label"] = ((raw - 8) @ w > 0).astype(np.int64)
    sess = SharkSession(num_workers=2, max_threads=2)
    sess.create_table("pts", Schema.of(
        **{f"f{i}": DType.INT64 for i in range(D)}, label=DType.INT64),
        cols, num_partitions=parts)
    return sess, cols


def _feats(sess, map_rows=None, dtype=np.float32):
    frame = sess.sql("SELECT * FROM pts", lazy=True)
    return table_rdd_to_features(frame, [f"f{i}" for i in range(D)], "label",
                                 map_rows=map_rows, dtype=dtype)


def test_encoded_partitions_stay_encoded_and_labels_keep_dtype():
    sess, _ = _int_points_session()
    feats = _feats(sess)
    assert isinstance(feats, FeatureRDD)
    batches = feats.collect()
    for b in batches:
        assert np.asarray(b.col("label").arr).dtype == np.int64
        # block-backed pass-through: the feature column still has its block
        assert b.col("f0").block is not None
    # legacy dense layout (map_rows) also preserves the label dtype
    dense = _feats(sess, map_rows=lambda x: x).collect()
    for b in dense:
        assert np.asarray(b.col("label").arr).dtype == np.int64
        assert b.col("features").arr.dtype == np.float32
    sess.shutdown()


def test_differential_parity_encoded_vs_materialized_f64():
    """Per-iteration gradients and final weights bit-identical between the
    encoded (decode-in-trace) and materialized (decode_np + stack) paths
    under float64."""
    sess, _ = _int_points_session()
    enc = _feats(sess, dtype=np.float64)
    mat = _feats(sess, map_rows=lambda x: x, dtype=np.float64)
    enc.cache()
    mat.cache()
    t_enc = IterativeTrainer(enc, "parity-enc", dtype=np.float64)
    t_mat = IterativeTrainer(mat, "parity-mat", dtype=np.float64)
    w = np.zeros(D, np.float64)
    for i in range(4):
        g_enc, n_enc = t_enc.gradient_iteration(w, "logistic")
        g_mat, n_mat = t_mat.gradient_iteration(w, "logistic")
        assert n_enc == n_mat == ROWS
        assert np.array_equal(g_enc, g_mat), (i, g_enc - g_mat)
        w = w - 0.5 * g_enc / ROWS
    sess.shutdown()


def test_encoded_training_never_decodes_host_side():
    sess, _ = _int_points_session()
    feats = _feats(sess)
    feats.cache()
    clf = LogisticRegression(dims=D, lr=0.5, iterations=2)
    clf.fit(feats)                       # materializes the cache
    before = dict(DECODE_COUNTERS)
    clf.fit(feats)
    clf.fit(feats)
    delta = {k: DECODE_COUNTERS[k] - before[k] for k in before}
    assert delta["numeric_blocks"] == 0 and delta["numeric_rows"] == 0, delta
    sess.shutdown()


def test_train_iterations_recorded_with_routes():
    sess, _ = _int_points_session()
    feats = _feats(sess)
    feats.cache()
    clf = LogisticRegression(dims=D, lr=0.5, iterations=3).fit(feats)
    m = clf.metrics
    assert m is not None
    train_segs = [s for s in m.segments if s.consumer == "train"]
    assert len(train_segs) == 3                     # one record per iteration
    for seg in train_segs:
        assert seg.table == "<train:logreg>"
        assert sum(seg.routes.values()) == 4        # one route per partition
        assert seg.rows_in == ROWS
    assert len(m.train_iterations) == 3
    for it in m.train_iterations:
        assert it["rows"] == ROWS and it["routes"]
    # kmeans records its own segment + objective must improve
    km = KMeans(k=3, dims=D, iterations=4).fit(feats)
    assert km.objective_history[-1] < km.objective_history[0]
    assert len(km.metrics.train_iterations) == 4
    sess.shutdown()


def test_decide_train_backend_routing():
    cfg = PDEConfig()
    assert decide_train_backend(10, D, on_tpu=False, cfg=cfg).route == "numpy"
    assert decide_train_backend(
        10_000, D, on_tpu=False, cfg=cfg).route == "jit"
    assert decide_train_backend(
        10_000, D, kernel_eligible="train_grad", on_tpu=True,
        cfg=cfg).route == "train_grad"
    forced = PDEConfig(segment_force_kernels=True)
    assert decide_train_backend(
        10_000, D, kernel_eligible="train_grad", on_tpu=False,
        cfg=forced).route == "train_grad"
    # below the kernel threshold the fused jit step still wins
    assert decide_train_backend(
        1000, D, kernel_eligible="train_grad", on_tpu=True,
        cfg=cfg).route == "jit"


@pytest.mark.kernels_interpret
def test_train_grad_kernel_route_parity():
    """Forced kernels: the gradient runs through the Pallas train_grad
    kernel (interpret mode on CPU) and matches the numpy-oracle route."""
    sess, _ = _int_points_session()
    cfg = PDEConfig(segment_force_kernels=True, segment_kernel_min_rows=256)
    feats = _feats(sess)
    feats.cache()
    tr_k = IterativeTrainer(feats, "kernel", cfg=cfg)
    tr_n = IterativeTrainer(feats, "oracle",
                            cfg=PDEConfig(segment_min_compiled_rows=10**9))
    w = np.zeros(D, np.float32)
    g_k, n_k = tr_k.gradient_iteration(w, "logistic")
    g_n, n_n = tr_n.gradient_iteration(w, "logistic")
    assert n_k == n_n == ROWS
    assert tr_k.metrics.segments[0].routes.get("train_grad", 0) > 0, \
        tr_k.metrics.segments[0].routes
    assert tr_n.metrics.segments[0].routes.get("numpy", 0) > 0
    np.testing.assert_allclose(g_k, g_n, rtol=5e-4, atol=5e-4)
    sess.shutdown()


def _chaos_fit(chaos: bool) -> np.ndarray:
    """Final weights of a 5-iteration fit; with `chaos`, a worker is killed
    between iteration 2's map stage and its fetch."""
    sess, _ = _int_points_session()
    sched = sess.ctx.scheduler
    if chaos:
        orig = sched.run_map_stage
        state = {"i": 0}

        def chaotic(dep):
            stats = orig(dep)
            state["i"] += 1
            if state["i"] == 2:      # mid-training: after iteration 2's
                w = sorted(sched.alive)[0]   # map stage, before fetch
                sched.kill_worker(w)
                sched.add_worker()
            return stats

        sched.run_map_stage = chaotic
    feats = _feats(sess)
    feats.cache()
    clf = LogisticRegression(dims=D, lr=0.5, iterations=5).fit(feats)
    sess.shutdown()
    return clf.w


def test_chaos_worker_killed_mid_iteration_model_identical():
    """Kill a worker between an iteration's map stage and its fetch: the
    shuffle outputs AND that worker's cached feature blocks vanish, the
    trainer recovers from lineage, and the final model is bitwise equal to
    the failure-free run."""
    w_chaos = _chaos_fit(chaos=True)
    w_clean = _chaos_fit(chaos=False)
    assert np.array_equal(w_chaos, w_clean)


# -- the train_grad route's features, resident on the device for a fit -------

PARTS = 4


@pytest.fixture
def forced_kernels(monkeypatch):
    """Estimators build their trainer's PDEConfig themselves: force the
    Pallas train_grad route (interpret mode here) on 1000-row partitions."""
    from repro.ml import trainer
    monkeypatch.setattr(trainer, "PDEConfig", lambda: PDEConfig(
        segment_force_kernels=True, segment_kernel_min_rows=256))
    return trainer


def _partition_spans(recs):
    """(iteration, partition span, device spans under it) of a recorded
    fit, from the records' parent links."""
    by_id = {r.id: r for r in recs}

    def up(rec, name):
        p = by_id.get(rec.parent_id)
        while p is not None and p.name != name:
            p = by_id.get(p.parent_id)
        return p

    parts = {r.id: (up(r, "shark.train.iteration").attrs["iteration"], r, [])
             for r in recs if r.name == "shark.train.partition"}
    for r in recs:
        if r.name == "shark.device":
            parts[up(r, "shark.train.partition").id][2].append(r)
    return list(parts.values())


def _host_assembled_fit(sess, iterations: int) -> np.ndarray:
    """The fit `LogisticRegression` makes, with each iteration's gradients
    from `ops.train_grad` on x, y assembled on the host."""
    from repro.kernels import ops
    from repro.ml.featurize import partition_xy_host
    batches = _feats(sess).collect()
    clf = LogisticRegression(dims=D, lr=0.5, iterations=iterations)
    w = clf.w
    for _ in range(iterations):
        gs = []
        for b in batches:
            x, y = partition_xy_host(b, [f"f{i}" for i in range(D)], "label",
                                     np.float32)
            gs.append(ops.train_grad(x, y, w, "logistic").astype(np.float32))
        g = np.sum(gs, axis=0)
        w = w - clf.lr * (g / ROWS).astype(w.dtype)
    return w


@pytest.mark.kernels_interpret
@pytest.mark.parametrize("kept", [PARTS, 2])
def test_resident_features_same_fit(forced_kernels, monkeypatch, kept):
    """Each partition lays out its kernel inputs once (a fill) and every
    later iteration reads them on the device (hits that upload only w); the
    weights equal, bitwise, a fit on host-assembled features.  With the
    budget holding `kept` partitions, the others take `resident="none"`
    every iteration, with the same gradient."""
    from repro.core import tracing
    from repro.kernels import ops
    from repro.kernels.train_grad import BLOCK_ROWS, LANES
    acc = np.dtype(ops.default_acc_dtype()).itemsize
    entry = 2 * BLOCK_ROWS * LANES * acc     # xp, yp of a 1000-row split
    monkeypatch.setattr(forced_kernels, "resident_budget",
                        lambda: kept * entry)
    sess, _ = _int_points_session(parts=PARTS)
    feats = _feats(sess)
    feats.cache()
    with tracing.recording():
        clf = LogisticRegression(dims=D, lr=0.5, iterations=5).fit(feats)
    spans = _partition_spans(tracing.records())
    want = _host_assembled_fit(sess, 5)
    sess.shutdown()
    assert np.array_equal(clf.w, want), clf.w - want
    assert len(spans) == 5 * PARTS
    assert {p.attrs["route"] for _, p, _ in spans} == {"train_grad"}
    first = sorted(p.attrs["resident"] for i, p, _ in spans if i == 0)
    assert first == ["fill"] * kept + ["none"] * (PARTS - kept)
    for it in range(1, 5):
        later = sorted(p.attrs["resident"] for i, p, _ in spans if i == it)
        assert later == ["hit"] * kept + ["none"] * (PARTS - kept)
    for _, p, devs in spans:
        programs = [d.attrs["program"] for d in devs]
        if p.attrs["resident"] == "hit":
            assert programs == ["train_grad"]
            assert devs[0].attrs["h2d_bytes"] == D * acc     # w alone
        else:
            assert programs == ["train_step.assemble", "train_grad"]
            pinned = devs[0].attrs["resident_bytes"]
            assert pinned == (entry if p.attrs["resident"] == "fill" else 0)


@pytest.mark.kernels_interpret
def test_resident_features_refill_after_worker_killed(forced_kernels):
    """The chaos run on the train_grad route: the killed worker's feature
    blocks recompute from lineage as new batches, which miss and refill,
    and the model is bitwise equal to the clean run."""
    from repro.core import tracing
    with tracing.recording():
        w_chaos = _chaos_fit(chaos=True)
    fills = sum(p.attrs["resident"] == "fill"
                for _, p, _ in _partition_spans(tracing.records()))
    w_clean = _chaos_fit(chaos=False)
    assert fills > PARTS
    assert np.array_equal(w_chaos, w_clean)


@pytest.mark.kernels_interpret
@pytest.mark.parametrize("ends", ["returns", "raises"])
def test_resident_features_released_when_fit_ends(forced_kernels,
                                                  monkeypatch, ends):
    stores, kept = [], []
    store_cls = forced_kernels.ResidentFeatures
    init, keep = store_cls.__init__, store_cls.keep

    def tracked_init(self):
        init(self)
        stores.append(self)

    def tracked_keep(self, split, batch, xp, yp):
        kept.extend([xp, yp])
        return keep(self, split, batch, xp, yp)

    monkeypatch.setattr(store_cls, "__init__", tracked_init)
    monkeypatch.setattr(store_cls, "keep", tracked_keep)
    if ends == "raises":
        step = forced_kernels.IterativeTrainer.gradient_iteration
        calls = {"n": 0}

        def failing(self, w, kind):
            calls["n"] += 1
            if calls["n"] == 3:
                raise RuntimeError("fit failed")
            return step(self, w, kind)

        monkeypatch.setattr(forced_kernels.IterativeTrainer,
                            "gradient_iteration", failing)
    sess, _ = _int_points_session(parts=PARTS)
    feats = _feats(sess)
    feats.cache()
    clf = LogisticRegression(dims=D, lr=0.5, iterations=5)
    if ends == "raises":
        with pytest.raises(RuntimeError, match="fit failed"):
            clf.fit(feats)
    else:
        clf.fit(feats)
    sess.shutdown()
    assert len(stores) == 1 and stores[0].entries == {}
    assert len(kept) == 2 * PARTS
    assert all(a.is_deleted() for a in kept)


def test_resident_store_accounting_under_thread_contention():
    """Many pool threads filling the same splits from fresh batches: the
    pinned count always equals the entries' bytes and stays within the
    budget, and release leaves nothing behind."""
    import sys
    import threading
    from repro.core.batch import PartitionBatch
    from repro.ml import trainer

    class Arr:
        def __init__(self, nbytes):
            self.nbytes, self.deleted = nbytes, False

        def on_device_size_in_bytes(self):
            return self.nbytes

        def delete(self):
            self.deleted = True

    store = trainer.ResidentFeatures()
    store.budget = 5 * 300
    batches = [PartitionBatch({}) for _ in range(64)]
    errors = []

    def fill(k):
        try:
            for i in range(200):
                split = (k + i) % 8
                batch = batches[(k * 7 + i) % len(batches)]
                store.keep(split, batch, Arr(200), Arr(100))
                store.get(split, batch)
                with store._lock:
                    pinned = sum(e[3] for e in store.entries.values())
                    assert store.pinned == pinned <= store.budget
        except Exception as e:      # read below, on the test's thread
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=fill, args=(k,))
                   for k in range(32)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert not errors, errors[0]
    kept = [a for e in store.entries.values() for a in e[1:3]]
    store.release()
    assert store.entries == {} and store.pinned == 0
    assert all(a.deleted for a in kept)
    assert store.keep(0, batches[0], Arr(1), Arr(1)) == 0   # closed


def test_string_feature_column_rejected():
    sess = SharkSession(num_workers=2)
    sess.create_table("t", Schema.of(s=DType.STRING, y=DType.INT64),
                      {"s": np.array(["a", "b"] * 50),
                       "y": np.arange(100, dtype=np.int64)})
    feats = table_rdd_to_features(sess.sql("SELECT * FROM t", lazy=True),
                                  ["s"], "y")
    with pytest.raises(Exception, match="string column"):
        feats.collect()
    sess.shutdown()
