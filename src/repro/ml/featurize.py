"""Encoded feature pipelines (paper §4.1 Listing 1; DESIGN.md §15.1).

`table_rdd_to_features` turns a SQL result RDD — or a lazy `SharkFrame`
directly — into a `FeatureRDD`: a narrow map on the same lineage graph
whose partitions are NOT dense matrices but pass-through references to the
source's encoded column blocks.  Training consumes them by handing each
block's raw streams (DICT codes + dictionary, FOR/BITPACK codes + bias,
RLE runs) straight into ONE jitted assemble+train step per partition —
the decode is traced into the XLA program, so the host never materializes
a feature column on the encoded path.  That claim is assertable:
`expr.DECODE_COUNTERS["numeric_blocks"]` stays untouched (decode_np is
never reached), and the CI benchmark asserts a zero delta.

Why it matters: a cached FeatureRDD partition is byte-accounted at its
ENCODED size under the MemoryManager (spillable, recompute-from-lineage
on loss), so the working set that fits in cache is the compressed one —
the same in-memory-columnar economics the SQL engine gets, now for the
ML tier.

Dtype policy (ISSUE 9 satellite): feature matrices default to float32 —
the MXU-native lane width, matching the SQL engine's accumulators on TPU
— with a `dtype=` escape hatch (e.g. `np.float64` for the differential
parity tests).  Labels are NEVER silently pushed through float32: the
label column keeps its source dtype end to end (an int64 label stays
int64, exact), and the train step casts it to the compute dtype in-trace.

`as_features_rdd` is the dispatch helper the estimators use to accept a
SharkFrame, a TableRDD + column names, or an already-featurized RDD.
"""

from __future__ import annotations

from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.batch import PartitionBatch
from ..core.compression import Encoding
from ..core.expr import ColumnVal
from ..core.frame import SharkFrame
from ..core.rdd import OneToOneDependency, RDD, TaskContext


class FeatureRDD(RDD):
    """Feature partitions that stay encoded.

    compute() selects the feature/label ColumnVals from the parent batch
    WITHOUT touching `.arr`: block-backed columns ride through still
    encoded, so caching this RDD stores (and byte-accounts) compressed
    blocks, and the jitted assemble+train step fuses their decode.

    A user `map_rows` callable is a host-side black box, so that variant
    falls back to the legacy dense layout ('features' matrix + 'label'),
    materialized once at featurization time.
    """

    def __init__(self, parent: RDD, feature_cols: Sequence[str],
                 label_col: Optional[str] = None,
                 map_rows: Optional[Callable[[np.ndarray], np.ndarray]] = None,
                 dtype=np.float32):
        self.feature_cols = list(feature_cols)
        self.label_col = label_col
        self.map_rows = map_rows
        self.dtype = np.dtype(dtype)
        super().__init__(parent.ctx, parent.num_partitions,
                         [OneToOneDependency(parent)])

    def compute(self, split: int, tc: TaskContext) -> PartitionBatch:
        batch = self.deps[0].parent.iterator(split, tc)
        for c in self.feature_cols:
            if batch.col(c).is_string:
                raise ValueError(
                    f"feature column {c!r} is a string column; encode it "
                    f"numerically (e.g. dictionary codes via SQL) first")
        if self.map_rows is not None:
            x = np.stack(
                [np.asarray(batch.col(c).arr).astype(self.dtype)
                 for c in self.feature_cols], axis=1) \
                if self.feature_cols else \
                np.zeros((batch.num_rows, 0), self.dtype)
            x = np.asarray(self.map_rows(x), dtype=self.dtype)
            out = {"features": ColumnVal(x)}
            if self.label_col is not None:
                # source dtype preserved: int64 labels stay int64 exactly
                out["label"] = ColumnVal(
                    np.asarray(batch.col(self.label_col).arr))
            return PartitionBatch(out)
        needed = list(self.feature_cols)
        if self.label_col is not None and self.label_col not in needed:
            needed.append(self.label_col)
        return PartitionBatch({c: batch.col(c) for c in needed})


def table_rdd_to_features(rdd, feature_cols: Sequence[str],
                          label_col: Optional[str] = None,
                          map_rows: Optional[Callable[[np.ndarray], np.ndarray]] = None,
                          dtype=np.float32) -> RDD:
    """FeatureRDD over a TableRDD or lazy SharkFrame (compiled via
    `.to_rdd()`, same lineage graph) — the paper's ML pipeline step (2),
    as a narrow map whose partitions stay encoded (module docstring)."""
    if isinstance(rdd, SharkFrame):
        # the frame validates eagerly (FrameBindError naming the column)
        # instead of a raw KeyError inside a partition task
        return rdd.to_features(feature_cols, label_col, map_rows,
                               dtype=dtype)
    return FeatureRDD(rdd, feature_cols, label_col, map_rows, dtype)


def as_features_rdd(data, feature_cols: Optional[Sequence[str]] = None,
                    label_col: Optional[str] = None,
                    map_rows: Optional[Callable[[np.ndarray], np.ndarray]] = None,
                    dtype=np.float32) -> RDD:
    """Normalize an estimator's input to a features RDD.

    * SharkFrame -> featurized via `table_rdd_to_features` (feature_cols
      defaults to every column except `label_col`);
    * RDD with `feature_cols` given -> featurized likewise;
    * RDD without `feature_cols` -> assumed already featurized (a
      FeatureRDD, or legacy partitions carrying 'features' / 'label'),
      returned as-is.
    """
    if isinstance(data, SharkFrame):
        cols = (list(feature_cols) if feature_cols is not None
                else [c for c in data.columns if c != label_col])
        return table_rdd_to_features(data, cols, label_col, map_rows, dtype)
    if feature_cols is not None:
        return table_rdd_to_features(data, feature_cols, label_col,
                                     map_rows, dtype)
    return data


# -- encoded block -> in-trace decode recipes (DESIGN.md §15.1) ----------
#
# A recipe is (static signature, runtime args): the signature keys the
# jitted step cache (encoding scheme + the ints XLA needs at trace time),
# the args are the block's raw streams passed as device arrays — never
# trace constants, so one compiled program serves every partition with the
# same signature and shapes.

def column_recipe(v: ColumnVal) -> Tuple[tuple, tuple]:
    """Recipe handing one column to the jitted step with decode fused
    in-trace.  Materialized columns (and encodings without a fused decode)
    degrade to a dense hand-off of whatever array already exists."""
    if (not v.materialized) and v.block is not None and v.sdict is None:
        enc = v.block.enc
        e = enc.encoding
        if e == Encoding.PLAIN:
            return ("plain",), (enc.data,)
        if e == Encoding.DICT:
            return ("dict",), (enc.codes, enc.dictionary)
        if e == Encoding.FOR:
            return (("for", str(np.dtype(enc.orig_dtype))),
                    (enc.codes, np.int64(enc.bias)))
        if e == Encoding.RLE:
            return ("rle", int(enc.n)), (enc.run_values, enc.run_lengths)
        if e == Encoding.BITPACK:
            return (("bitpack", int(enc.bit_width), int(enc.n),
                     str(np.dtype(enc.orig_dtype))),
                    (enc.words, np.int64(enc.bias)))
    a = np.asarray(v.arr)
    return ("dense",), (a,)


def _decode_in_trace(sig: tuple, args) -> jnp.ndarray:
    """The jnp decode recipes (compression.decode_jnp, inlined so they
    trace INTO the assemble+train program instead of running standalone)."""
    tag = sig[0]
    if tag in ("dense", "plain", "mat"):
        return args[0]
    if tag == "dict":
        codes, dictionary = args
        return dictionary[codes]
    if tag == "for":
        codes, bias = args
        return (codes.astype(jnp.int64) + bias).astype(jnp.dtype(sig[1]))
    if tag == "rle":
        run_values, run_lengths = args
        ends = jnp.cumsum(run_lengths)
        idx = jnp.searchsorted(ends, jnp.arange(sig[1]), side="right")
        return run_values[idx]
    if tag == "bitpack":
        words, bias = args
        width, n, odt = sig[1], sig[2], sig[3]
        per_word = 32 // width
        shifts = jnp.arange(per_word, dtype=jnp.uint32) * jnp.uint32(width)
        lanes = ((words[:, None] >> shifts[None, :])
                 & jnp.uint32((1 << width) - 1))
        flat = lanes.reshape(-1)[:n].astype(jnp.int64) + bias
        return flat.astype(jnp.dtype(odt))
    raise ValueError(sig)


def partition_recipes(batch: PartitionBatch,
                      feature_cols: Optional[Sequence[str]],
                      label_col: Optional[str]):
    """(sigs, col_args, label_sig, label_args) for one feature partition.

    Legacy dense partitions ('features' matrix) get the single ("mat",)
    recipe — already-materialized, handed through as one 2-D array."""
    if "features" in batch.cols:
        x = np.asarray(batch.col("features").arr)
        sigs, col_args = (("mat",),), ((x,),)
        if "label" in batch.cols:
            lsig, largs = column_recipe(batch.col("label"))
        else:
            lsig, largs = None, ()
        return sigs, col_args, lsig, largs
    sigs, col_args = [], []
    for c in feature_cols or []:
        s, a = column_recipe(batch.col(c))
        sigs.append(s)
        col_args.append(a)
    if label_col is not None:
        lsig, largs = column_recipe(batch.col(label_col))
    else:
        lsig, largs = None, ()
    return tuple(sigs), tuple(col_args), lsig, largs


# -- fused assemble+train step cache -------------------------------------

_FUSED_CACHE: dict = {}


def fused_train_step(kind: str, sigs: tuple, label_sig, dtype) -> Callable:
    """One jitted program per (estimator kind, partition signature): decode
    every encoded column, stack the feature matrix, and run the train step
    — all in a single trace, so XLA fuses decode into the matmuls and the
    host never sees a decoded column.

    kinds: "logistic" / "linear" -> summed gradient (d,);
           "kmeans"              -> (per-centroid sums, counts, objective);
           "assemble"            -> (x, y) as device arrays, for the
                                    Pallas train_grad route, which lays
                                    them out for its kernel and keeps them
                                    on the device for the fit
                                    (ml/trainer.py), without paying
                                    decode_np.
    """
    key = (kind, sigs, label_sig, str(np.dtype(dtype)))
    fn = _FUSED_CACHE.get(key)
    if fn is not None:
        return fn
    dt = jnp.dtype(str(np.dtype(dtype)))
    dense_mat = bool(sigs) and sigs[0][0] == "mat"

    def step(params, col_args, label_args):
        if dense_mat:
            x = _decode_in_trace(sigs[0], col_args[0]).astype(dt)
        elif sigs:
            x = jnp.stack([_decode_in_trace(s, a).astype(dt)
                           for s, a in zip(sigs, col_args)], axis=1)
        else:
            x = jnp.zeros((0, 0), dt)
        y = (_decode_in_trace(label_sig, label_args).astype(dt)
             if label_sig is not None else None)
        if kind == "assemble":
            return x, y
        if kind == "logistic":
            p = jax.nn.sigmoid(x @ params.astype(dt))
            return x.T @ (p - y)
        if kind == "linear":
            return x.T @ (x @ params.astype(dt) - y)
        if kind == "kmeans":
            c = params.astype(dt)
            x2 = jnp.sum(x * x, axis=1, keepdims=True)
            c2 = jnp.sum(c * c, axis=1)
            d2 = x2 - 2.0 * (x @ c.T) + c2[None, :]
            assign = jnp.argmin(d2, axis=1)
            obj = jnp.sum(jnp.min(d2, axis=1))
            onehot = jax.nn.one_hot(assign, c.shape[0], dtype=dt)
            return onehot.T @ x, jnp.sum(onehot, axis=0), obj
        raise ValueError(kind)

    fn = jax.jit(step)
    _FUSED_CACHE[key] = fn
    return fn


def partition_xy_host(batch: PartitionBatch,
                      feature_cols: Optional[Sequence[str]],
                      label_col: Optional[str], dtype=np.float32):
    """Host-materialized (x, y) — the numpy-oracle route and the loss
    helpers.  Decodes through decode_np (counters bump: this is exactly
    the path the encoded pipeline avoids)."""
    if "features" in batch.cols:
        x = np.asarray(batch.col("features").arr).astype(dtype)
        y = (np.asarray(batch.col("label").arr)
             if "label" in batch.cols else None)
        return x, y
    cols = [np.asarray(batch.col(c).arr).astype(dtype)
            for c in feature_cols or []]
    x = (np.stack(cols, axis=1) if cols
         else np.zeros((batch.num_rows, 0), dtype))
    y = (np.asarray(batch.col(label_col).arr)
         if label_col is not None else None)
    return x, y
