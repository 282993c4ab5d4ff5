"""Distributed logistic regression over RDD partitions (paper §4.1 Listing 1,
§6.5 Figure 11; DESIGN.md §15.2).

Each iteration is a PDE-scheduled map stage over the cached feature RDD:
every partition routes through `decide_train_backend` — numpy oracle,
fused jitted assemble+train (decode of encoded blocks traced into the XLA
program), or the Pallas `train_grad` kernel — and the master reduces the
per-partition gradients, exactly the paper's `data.map(gradient).reduce(+)`
loop.  Per-iteration cost on cached encoded partitions is one pass over
the features plus an O(dims) aggregation.  On the `train_grad` route the
features are laid out on the device by the first iteration and stay in
HBM until `fit` returns or raises, so later iterations move only the
weights and the gradient between host and device (ml/trainer.py); the
other routes ship the cached encoded blocks each iteration.  A lost
worker only recomputes its partitions (lineage), even mid-iteration,
and their device copies are laid out anew.

After `fit()`, `self.metrics` (an ExecMetrics) carries one SegmentRecord
per iteration with the routes taken, plus `train_iterations` timings.
"""

from __future__ import annotations

from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np


@jax.jit
def _loss_kernel(w: jnp.ndarray, x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    logits = x @ w
    return jnp.sum(jnp.logaddexp(0.0, logits) - y * logits)


class LogisticRegression:
    def __init__(self, dims: int, lr: float = 0.1, iterations: int = 10,
                 seed: int = 0):
        self.dims = dims
        self.lr = lr
        self.iterations = iterations
        rng = np.random.default_rng(seed)
        self.w = rng.normal(scale=0.01, size=dims).astype(np.float32)
        self.loss_history: List[float] = []
        self.metrics = None

    def fit(self, data, feature_cols=None, label_col=None,
            map_rows=None, dtype=np.float32) -> "LogisticRegression":
        """Train over feature partitions.  `data` is a FeatureRDD (or a
        legacy featurized RDD), or a SharkFrame / TableRDD with
        `feature_cols`/`label_col` naming the columns to featurize — the
        paper's Listing-1 pipeline as one fluent chain on one lineage
        graph.  `dtype` sets the feature compute dtype when featurizing
        here (float32 default; see featurize module docstring)."""
        from .featurize import as_features_rdd
        from .trainer import IterativeTrainer
        features_rdd = as_features_rdd(data, feature_cols, label_col,
                                       map_rows, dtype)
        features_rdd.cache()
        with IterativeTrainer(features_rdd, "logreg", dtype=dtype) as trainer:
            self.metrics = trainer.metrics
            for _ in range(self.iterations):
                g, n = trainer.gradient_iteration(self.w, "logistic")
                self.w = self.w - self.lr * (g / max(n, 1)).astype(
                    self.w.dtype)
        return self

    def loss(self, data, feature_cols=None, label_col=None) -> float:
        from ..core.batch import PartitionBatch
        from ..core.expr import ColumnVal
        from .featurize import (FeatureRDD, as_features_rdd,
                                partition_xy_host)
        features_rdd = as_features_rdd(data, feature_cols, label_col)
        fcols = getattr(features_rdd, "feature_cols", None)
        lcol = getattr(features_rdd, "label_col", None)
        sched = features_rdd.ctx.scheduler
        w = jnp.asarray(self.w)

        def map_loss(split: int, batch: PartitionBatch) -> PartitionBatch:
            x, y = partition_xy_host(batch, fcols, lcol, np.float32)
            val = float(_loss_kernel(w, jnp.asarray(x),
                                     jnp.asarray(y.astype(np.float32))))
            return PartitionBatch({
                "loss": ColumnVal(np.array([val])),
                "count": ColumnVal(np.array([x.shape[0]], np.int64))})

        parts = sched.run_result_stage(features_rdd.map_partitions(map_loss))
        total = sum(float(np.asarray(b.col("loss").arr)[0]) for b in parts)
        n = sum(int(np.asarray(b.col("count").arr)[0]) for b in parts)
        return total / max(n, 1)

    def predict_proba(self, x: np.ndarray) -> np.ndarray:
        return np.asarray(jax.nn.sigmoid(jnp.asarray(x) @ jnp.asarray(self.w)))

    def predict(self, x: np.ndarray) -> np.ndarray:
        return (self.predict_proba(x) >= 0.5).astype(np.int32)
