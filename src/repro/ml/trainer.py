"""PDE-scheduled iterative training (DESIGN.md §15.2).

Each training iteration is a real map stage under the Scheduler — the
same `run_map_stage` machinery SQL shuffles use — not a private loop:

  * the per-partition step maps over the CACHED FeatureRDD, so iteration
    i > 0 reads worker-resident (encoded, byte-accounted) blocks;
  * the step's gradient/stats payload materializes as single-bucket
    shuffle output; the master fetches the per-map pieces and reduces
    them host-side (an O(dims) sum — the paper's map(gradient).reduce(+));
  * chaos mid-iteration is survivable for free: a dead worker's map task
    retries elsewhere (WorkerLost), its lost cache blocks recompute from
    lineage, and lost shuffle pieces recover via `_recover_lineage` — the
    steps are deterministic, so the final model is identical to a
    failure-free run (asserted by tests/test_ml_compiled.py);
  * each partition routes through `pde.decide_train_backend`: the numpy
    oracle for tiny partitions, the fused jitted assemble+train step
    (decode traced in — the encoded-pipeline fast path), or the Pallas
    `train_grad` gradient kernel on large partitions when kernels are
    forced/on-TPU.

Where the features live during a fit: on the numpy and jit routes, in
the cached FeatureRDD's host blocks, shipped encoded every iteration.  On
the `train_grad` route, the first iteration to reach a partition
assembles its features on the device, lays them out for the kernel and
keeps them there (`ResidentFeatures`); every later iteration uploads
only the (d,) weights and reads the (d,) gradient back, so the device
reads the features from HBM and nothing else crosses.  The entries are
held under a share of the device's memory and deleted when the fit
returns or raises (the estimators use the trainer as a context manager).

Observability mirrors the SQL executor: one `SegmentRecord` per iteration
(table `<train:name>`, consumer "train") tallies partitions/rows/routes,
and `ExecMetrics.train_iterations` records per-iteration wall-clock —
the estimators expose the ExecMetrics as `.metrics` after fit().
"""

from __future__ import annotations

import threading
import time
import weakref
from typing import Callable, Dict, List, Optional

import jax
import numpy as np

from ..core import tracing
from ..core.batch import PartitionBatch
from ..core.expr import ColumnVal, _x64
from ..core.pde import PDEConfig, decide_train_backend
from ..core.physical import ExecMetrics, SegmentRecord
from ..core.rdd import RDD, ShuffleDependency, ShuffledRDD
from ..core.runtime import FetchFailed
from ..core.shuffle import single_bucket
from .featurize import (FeatureRDD, fused_train_step, partition_recipes,
                        partition_xy_host)


def _np_sigmoid(z: np.ndarray) -> np.ndarray:
    out = np.empty_like(z)
    pos = z >= 0
    out[pos] = 1.0 / (1.0 + np.exp(-z[pos]))
    ez = np.exp(z[~pos])
    out[~pos] = ez / (1.0 + ez)
    return out


def partition_grad(split: int, batch: PartitionBatch, w: np.ndarray,
                   kind: str, cfg: PDEConfig, dtype, feature_cols, label_col,
                   on_tpu: bool, store: "ResidentFeatures"):
    """(route, unnormalized gradient) for one feature partition, routed by
    the PDE.  All three routes compute the same sum-of-residual-weighted
    features; they differ only in where the decode and the matmul run.
    The span's `resident` says whether the `train_grad` route found the
    partition's inputs on the device ("hit"), laid them out and kept them
    ("fill"), or neither ("none")."""
    n = batch.num_rows
    d = decide_train_backend(n, len(w), "train_grad", on_tpu, cfg)
    with tracing.span("train.partition", route=d.route, rows=n) as sp:
        if d.route == "train_grad":
            resident, g = _kernel_grad(split, batch, w, kind, dtype,
                                       feature_cols, label_col, store)
        else:
            resident, g = "none", _partition_grad(
                batch, w, kind, d.route, dtype, feature_cols, label_col)
        sp.set(resident=resident)
        return d.route, g


def _kernel_grad(split: int, batch: PartitionBatch, w: np.ndarray, kind: str,
                 dtype, feature_cols, label_col, store: "ResidentFeatures"):
    """(resident, gradient) on the Pallas `train_grad` route: the kernel
    reads the partition's inputs from `store`, or from a fill that
    assembles and lays them out on the device, where they stay."""
    from ..kernels import ops
    xy = store.get(split, batch)
    resident = "hit"
    if xy is None:
        sigs, col_args, lsig, largs = partition_recipes(batch, feature_cols,
                                                        label_col)
        with tracing.device("train_step.assemble", w, col_args,
                            largs) as sp:
            with _x64():
                x, y = fused_train_step("assemble", sigs, lsig, dtype)(
                    w, col_args, largs)
            xy = ops.train_grad_inputs(x, y)
            pinned = store.keep(split, batch, *xy)
            sp.set(resident_bytes=pinned)
        resident = "fill" if pinned else "none"
    return resident, ops.train_grad_padded(*xy, w, kind).astype(dtype)


def _partition_grad(batch: PartitionBatch, w: np.ndarray, kind: str,
                    route: str, dtype, feature_cols, label_col) -> np.ndarray:
    if route == "numpy":
        x, y = partition_xy_host(batch, feature_cols, label_col, dtype)
        z = x @ w.astype(dtype)
        p = _np_sigmoid(z) if kind == "logistic" else z
        return (x.T @ (p - y.astype(dtype))).astype(dtype)
    sigs, col_args, lsig, largs = partition_recipes(batch, feature_cols,
                                                    label_col)
    with tracing.device("train_step." + kind, w, col_args, largs) as sp, \
            _x64():
        return sp.fetch(fused_train_step(kind, sigs, lsig, dtype)(
            w, col_args, largs))


def partition_kmeans_stats(batch: PartitionBatch, centroids: np.ndarray,
                           cfg: PDEConfig, dtype, feature_cols,
                           on_tpu: bool):
    """(route, sums, counts, objective) for one partition's assignment
    step.  No dedicated Pallas kernel (the one-hot matmul is already
    MXU-shaped inside the fused step), so kernel_eligible is None."""
    n = batch.num_rows
    d = decide_train_backend(n, centroids.shape[1], None, on_tpu, cfg)
    with tracing.span("train.partition", route=d.route, rows=n,
                      resident="none"):
        return _partition_kmeans_stats(batch, centroids, d.route, dtype,
                                       feature_cols)


def _partition_kmeans_stats(batch: PartitionBatch, centroids: np.ndarray,
                            route: str, dtype, feature_cols):
    if route == "numpy":
        x, _ = partition_xy_host(batch, feature_cols, None, dtype)
        c = centroids.astype(dtype)
        d2 = ((x * x).sum(1, keepdims=True) - 2.0 * (x @ c.T)
              + (c * c).sum(1)[None, :])
        assign = np.argmin(d2, axis=1)
        obj = float(np.min(d2, axis=1).sum())
        sums = np.zeros_like(c)
        np.add.at(sums, assign, x)
        counts = np.bincount(assign, minlength=c.shape[0]).astype(dtype)
        return "numpy", sums, counts, obj
    sigs, col_args, lsig, largs = partition_recipes(batch, feature_cols,
                                                    None)
    with tracing.device("train_step.kmeans", centroids, col_args) as sp, \
            _x64():
        sums, counts, obj = fused_train_step("kmeans", sigs, None, dtype)(
            centroids, col_args, ())
        return ("jit", sp.fetch(sums), sp.fetch(counts),
                float(sp.fetch(obj)))


# Share of the device's memory that one fit's kernel-ready features may pin
# (ResidentFeatures).
RESIDENT_SHARE = 0.5


def resident_budget() -> Optional[int]:
    """Bytes the resident features may pin: RESIDENT_SHARE of the device's
    `bytes_limit`, or None (no cap) where the device reports no limit."""
    limit = (jax.devices()[0].memory_stats() or {}).get("bytes_limit")
    return int(limit * RESIDENT_SHARE) if limit else None


class ResidentFeatures:
    """The `train_grad` route's kernel-ready inputs (xp, yp) of each feature
    partition, kept on the device for one fit (DESIGN.md §15.2).

    The first iteration to reach a partition assembles its features on the
    device, lays them out for the kernel and keeps them there (a fill);
    every later iteration uploads only `w` (a hit).  An entry is keyed by
    its split and by the cached batch it was built from, so a batch
    recomputed from lineage misses and refills.  The entries stay under
    `resident_budget()`; a partition beyond it lays out its inputs anew
    each iteration and keeps nothing.  `release` deletes every entry and
    keeps none after it."""

    def __init__(self):
        self.budget = resident_budget()
        self._lock = threading.Lock()
        # split -> (weak reference to the batch, xp, yp, device bytes)
        self.entries: Dict[int, tuple] = {}
        self.pinned = 0
        self.closed = False

    def get(self, split: int, batch: PartitionBatch):
        """(xp, yp) built from this very batch, or None."""
        with self._lock:
            entry = self.entries.get(split)
        if entry is None or entry[0]() is not batch:
            return None
        return entry[1], entry[2]

    def keep(self, split: int, batch: PartitionBatch, xp, yp) -> int:
        """Keep (xp, yp) as `split`'s entry if the budget holds them: the
        bytes pinned, or 0."""
        nbytes = xp.on_device_size_in_bytes() + yp.on_device_size_in_bytes()
        with self._lock:
            stale = self.entries.pop(split, None)
            if stale is not None:
                self.pinned -= stale[3]
            if self.closed or (self.budget is not None
                               and self.pinned + nbytes > self.budget):
                return 0
            self.entries[split] = (weakref.ref(batch), xp, yp, nbytes)
            self.pinned += nbytes
        return nbytes

    def release(self) -> None:
        with self._lock:
            self.closed = True
            entries, self.entries = self.entries, {}
            self.pinned = 0
        for _, xp, yp, _ in entries.values():
            xp.delete()
            yp.delete()


class IterativeTrainer:
    """Drives an estimator's iterations as scheduled map stages over a
    cached features RDD (module docstring).  Used as a context manager,
    it releases the partitions' resident features when the fit leaves
    the block, by return or by raise."""

    def __init__(self, features_rdd: RDD, name: str,
                 cfg: Optional[PDEConfig] = None,
                 metrics: Optional[ExecMetrics] = None,
                 dtype=np.float32):
        self.rdd = features_rdd
        self.name = name
        self.cfg = cfg or PDEConfig()
        self.metrics = metrics or ExecMetrics()
        self.sched = features_rdd.ctx.scheduler
        self.bm = features_rdd.ctx.block_manager
        self.iteration = 0
        self.resident = ResidentFeatures()
        if isinstance(features_rdd, FeatureRDD):
            self.feature_cols = features_rdd.feature_cols
            self.label_col = features_rdd.label_col
            if features_rdd.map_rows is None:
                self.dtype = features_rdd.dtype
            else:
                self.dtype = np.dtype(dtype)
        else:
            # legacy featurized RDD: dense 'features'/'label' layout
            self.feature_cols = None
            self.label_col = None
            self.dtype = np.dtype(dtype)

    def __enter__(self) -> "IterativeTrainer":
        return self

    def __exit__(self, *exc) -> bool:
        self.resident.release()
        return False

    def run_stage(self, make_payload: Callable[[int, PartitionBatch],
                                               Dict[str, ColumnVal]]
                  ) -> List[PartitionBatch]:
        """One iteration: map `make_payload` over every feature partition
        as a scheduled single-bucket map stage, return the per-map payload
        pieces (master reduces them).  `make_payload` must be
        deterministic — lineage recovery re-runs it."""
        record = SegmentRecord(table=f"<train:{self.name}>", depth=0,
                               consumer="train", outputs=[], pred=None)
        self.metrics.segments.append(record)
        lock = threading.Lock()

        def note(route: str, rows: int) -> None:
            with lock:
                record.partitions += 1
                record.rows_in += rows
                record.routes[route] = record.routes.get(route, 0) + 1

        def step(split: int, batch: PartitionBatch) -> PartitionBatch:
            route, payload = make_payload(split, batch)
            note(route, batch.num_rows)
            return PartitionBatch(payload)

        payload_rdd = self.rdd.map_partitions(step)
        dep = ShuffleDependency(payload_rdd, 1, single_bucket())
        # recovery anchor: _recover_lineage locates lost shuffles by walking
        # an RDD's dependency DAG, and `dep` only appears BELOW a reduce-side
        # RDD — the payload rdd is dep's parent, not its consumer
        fetch_root = ShuffledRDD(dep)
        t0 = time.perf_counter()
        with tracing.span("train.iteration", iteration=self.iteration) as sp:
            self.sched.run_map_stage(dep)
            pieces: List[PartitionBatch] = []
            for _ in range(self.sched.max_stage_retries):
                try:
                    pieces = self.bm.fetch_shuffle(
                        dep.shuffle_id, payload_rdd.num_partitions, [0])
                    break
                except FetchFailed as ff:  # worker died after the map stage
                    self.sched._recover_lineage(fetch_root, ff)
            else:
                raise RuntimeError("exceeded max stage retries (train fetch)")
            sp.set(rows=record.rows_in)
        elapsed = time.perf_counter() - t0
        # per-iteration shuffle output is consumed exactly once: drop it so
        # a 100-iteration fit doesn't pin 100 generations of (tiny) blocks
        self.bm.drop_shuffle(dep.shuffle_id)
        self.metrics.train_iterations.append({
            "iteration": self.iteration, "seconds": elapsed,
            "rows": record.rows_in, "routes": dict(record.routes)})
        self.iteration += 1
        return pieces

    def gradient_iteration(self, w: np.ndarray, kind: str):
        """(summed gradient, total rows) across all partitions."""
        from ..kernels.ops import on_tpu
        tpu = on_tpu()

        def payload(split, batch):
            route, g = partition_grad(split, batch, w, kind, self.cfg,
                                      self.dtype, self.feature_cols,
                                      self.label_col, tpu, self.resident)
            return route, {"grad": ColumnVal(g[None, :]),
                           "count": ColumnVal(
                               np.array([batch.num_rows], np.int64))}

        pieces = self.run_stage(payload)
        g = np.sum([np.asarray(p.col("grad").arr)[0] for p in pieces],
                   axis=0)
        n = int(sum(np.asarray(p.col("count").arr)[0] for p in pieces))
        return g, n

    def kmeans_iteration(self, centroids: np.ndarray):
        """(per-centroid sums, counts, total objective)."""
        from ..kernels.ops import on_tpu
        tpu = on_tpu()

        def payload(split, batch):
            route, sums, counts, obj = partition_kmeans_stats(
                batch, centroids, self.cfg, self.dtype, self.feature_cols,
                tpu)
            return route, {"sums": ColumnVal(sums[None]),
                           "counts": ColumnVal(counts[None]),
                           "obj": ColumnVal(np.array([obj]))}

        pieces = self.run_stage(payload)
        sums = np.sum([np.asarray(p.col("sums").arr)[0] for p in pieces],
                      axis=0)
        counts = np.sum([np.asarray(p.col("counts").arr)[0]
                         for p in pieces], axis=0)
        obj = float(sum(np.asarray(p.col("obj").arr)[0] for p in pieces))
        return sums, counts, obj
