"""Property tests for the storage tier (hypothesis, gated like
test_join_property.py):

  * RLE / BITPACK / frame-of-reference / DICT encode->decode round-trip on
    arbitrary integer columns (including negative bias and degenerate
    constant/empty inputs), and `recompress` never changing decoded content;
  * spill-segment serialize->deserialize round-trip for whole partitions;
  * compressed-domain predicate parity: `compile_expr` over FOR- and
    RLE-encoded layouts must agree with the interpreted `evaluate()` oracle
    for every generated range/comparison predicate — the §12 claim that
    executing on codes never changes answers.
"""

import numpy as np
import pytest

pytest.importorskip("hypothesis")

pytestmark = pytest.mark.tier1

from hypothesis import given, settings, strategies as st

from repro.core.columnar import build_partition, make_block
from repro.core.compression import (Encoding, decode_np, encode, recompress)
from repro.core.expr import (Between, Cmp, Col, ColumnVal, InList, compile_expr,
                             evaluate)
from repro.core.storage import deserialize_partition, serialize_partition
from repro.core.types import DType, Field, Schema

SETTINGS = settings(max_examples=60, deadline=None)


int_arrays = st.builds(
    lambda base, span, n, seed: (
        base + np.random.default_rng(seed).integers(0, span + 1, n)
    ).astype(np.int64),
    base=st.integers(-10**9, 10**9),
    span=st.integers(0, (1 << 31) - 1),
    n=st.integers(0, 400),
    seed=st.integers(0, 2**16),
)

runny_arrays = st.builds(
    lambda vals, reps, seed: np.repeat(
        np.asarray(vals, np.int64),
        np.random.default_rng(seed).integers(1, 1 + max(reps, 1),
                                             len(vals))).astype(np.int64),
    vals=st.lists(st.integers(-50, 50), min_size=1, max_size=30),
    reps=st.integers(1, 40),
    seed=st.integers(0, 2**16),
)


class TestRoundTrip:
    @SETTINGS
    @given(vals=int_arrays)
    def test_for_round_trip(self, vals):
        enc = encode(vals, Encoding.FOR)
        np.testing.assert_array_equal(decode_np(enc), vals)

    @SETTINGS
    @given(vals=int_arrays)
    def test_bitpack_round_trip(self, vals):
        span = int(vals.max() - vals.min()) if len(vals) else 0
        if span >= (1 << 16):
            vals = vals - vals.min()
            vals = (vals % (1 << 16)) + int(vals.min())
        enc = encode(vals.astype(np.int64), Encoding.BITPACK)
        np.testing.assert_array_equal(decode_np(enc), vals)

    @SETTINGS
    @given(vals=runny_arrays)
    def test_rle_round_trip(self, vals):
        enc = encode(vals, Encoding.RLE)
        np.testing.assert_array_equal(decode_np(enc), vals)

    @SETTINGS
    @given(vals=st.one_of(int_arrays, runny_arrays))
    def test_recompress_preserves_content_and_size(self, vals):
        for initial in (Encoding.PLAIN, Encoding.RLE):
            enc = encode(vals, initial)
            out = recompress(enc)
            assert out.nbytes <= enc.nbytes
            np.testing.assert_array_equal(decode_np(out), decode_np(enc))

    @SETTINGS
    @given(vals=int_arrays, runs=runny_arrays, seed=st.integers(0, 2**16))
    def test_segment_round_trip(self, vals, runs, seed):
        n = min(len(vals), len(runs))
        if n == 0:
            return
        rng = np.random.default_rng(seed)
        schema = Schema([Field("a", DType.INT64), Field("r", DType.INT64),
                         Field("s", DType.STRING)])
        data = {"a": vals[:n], "r": runs[:n],
                "s": rng.choice(np.array(["aa", "bb", "cc"]), n)}
        part = build_partition(3, schema, data)
        for blk in part.columns.values():
            blk.recompress()
        idx, cols = deserialize_partition(
            serialize_partition(3, part.columns))
        assert idx == 3
        for name in data:
            np.testing.assert_array_equal(cols[name].decoded(),
                                          part.columns[name].decoded())


# ---------------------------------------------------------------------------
# Compressed-domain predicate parity vs evaluate()
# ---------------------------------------------------------------------------


def _pred_strategy():
    lit = st.one_of(st.integers(-60, 60),
                    st.floats(-60, 60, allow_nan=False).map(
                        lambda f: round(f, 2)))
    cmps = st.builds(lambda op, v: Cmp(op, Col("x"), Lit_(v)),
                     st.sampled_from(["=", "!=", "<", "<=", ">", ">="]), lit)
    between = st.builds(lambda a, b: Between(Col("x"), min(a, b), max(a, b)),
                        lit, lit)
    inlist = st.builds(lambda vs: InList(Col("x"), tuple(vs)),
                       st.lists(st.integers(-60, 60), min_size=1,
                                max_size=4))
    return st.one_of(cmps, between, inlist)


def Lit_(v):
    from repro.core.expr import Lit
    return Lit(v)


class TestCompressedDomainParity:
    @SETTINGS
    @given(vals=st.builds(
        lambda base, n, seed: (base + np.random.default_rng(seed).integers(
            0, 120, n)).astype(np.int64),
        base=st.integers(-10**8, 10**8), n=st.integers(1, 300),
        seed=st.integers(0, 2**16)),
        pred=_pred_strategy())
    def test_for_codes_match_oracle(self, vals, pred):
        # predicate literals live near zero; shift them into the frame so
        # matches are possible but out-of-frame bounds are also exercised
        base = int(vals.min())
        pred = _shift_pred(pred, base)
        blk = make_block(Field("x", DType.INT64), vals,
                         encoding=Encoding.FOR)
        assert blk.enc.encoding == Encoding.FOR
        ctx = {"x": ColumnVal(block=blk)}
        expect = np.asarray(evaluate(pred, {"x": ColumnVal(vals)}).arr)
        got = np.asarray(compile_expr(pred)(ctx).arr)
        np.testing.assert_array_equal(got.astype(bool), expect.astype(bool))

    @SETTINGS
    @given(vals=runny_arrays, lo=st.integers(-60, 60), hi=st.integers(-60, 60))
    def test_rle_runs_match_oracle(self, vals, lo, hi):
        lo, hi = min(lo, hi), max(lo, hi)
        from repro.core.batch import PartitionBatch
        from repro.core.pde import PDEConfig
        from repro.core.physical import SegmentRecord, SegmentRunner
        from repro.core.plan import PipelineSegment, ScanNode
        blk = make_block(Field("x", DType.INT64), vals, encoding=Encoding.RLE)
        assert blk.enc.encoding == Encoding.RLE
        mask = (vals >= lo) & (vals <= hi)
        batch = PartitionBatch({"x": ColumnVal(block=blk)})
        runner = _colscan_runner()
        out, route = runner._run_rle_scan(batch, "x", lo, hi, "x",
                                          _count_sum_specs())
        assert route == "rle-scan"
        # partial-agg state columns, as _state_cols names them
        assert int(np.asarray(out.col("__c__cnt").arr)[0]) == int(mask.sum())
        assert np.asarray(out.col("__s__acc").arr)[0] == vals[mask].sum()


def _shift_pred(pred, base):
    from repro.core.expr import Lit, rewrite_expr
    def shift(node):
        if isinstance(node, Lit):
            return Lit(node.value + base)
        if isinstance(node, Between):
            return Between(node.child, node.lo + base, node.hi + base)
        if isinstance(node, InList):
            return InList(node.child, tuple(v + base for v in node.values))
        return None
    return rewrite_expr(pred, shift)


def _count_sum_specs():
    from repro.core.plan import AggFunc, AggSpec
    return [AggSpec("c", AggFunc.COUNT, None), AggSpec("s", AggFunc.SUM,
                                                       Col("x"))]


def _colscan_runner():
    from repro.core.pde import PDEConfig
    from repro.core.physical import SegmentRecord, SegmentRunner
    from repro.core.plan import PipelineSegment
    from repro.core.types import DType, Field, Schema
    seg = PipelineSegment.__new__(PipelineSegment)
    seg.pred = None
    seg.exprs = None
    record = SegmentRecord(table="t", depth=1, consumer="aggregate",
                           outputs=["x"], pred=None)
    schema = Schema([Field("x", DType.INT64)])
    runner = SegmentRunner.__new__(SegmentRunner)
    runner.seg = seg
    runner.schema = schema
    runner.backend = "compiled"
    runner.cfg = PDEConfig()
    runner.record = record
    return runner
