"""Property test (hypothesis): whole-stage fusion (DESIGN.md §14) is a
physical-layer rewrite — for ANY generated scan→filter→project→aggregate
chain it never changes the optimizer `plan_fingerprint` or the `explain()`
text, and the fused output is row-identical to the segment-at-a-time path.

The hypothesis grid is importorskip-gated; `test_fusion_invariants_sweep`
runs the same invariant check over a fixed grid so the property is still
exercised when hypothesis is absent.
"""

import numpy as np
import pytest

from repro.core import DType, Schema, SharkSession
from repro.core.plan import optimize
from repro.server.result_cache import plan_fingerprint

from conftest import fusion_pde

pytestmark = pytest.mark.tier1

AGGS = ("SUM", "AVG", "MIN", "MAX", "COUNT")
CMPS = (">", "<", ">=", "<=", "=", "!=")


@pytest.fixture(scope="module")
def sessions():
    rng = np.random.default_rng(0)
    data = {
        "a": rng.integers(0, 20, 900).astype(np.int64),
        "b": rng.integers(-40, 40, 900).astype(np.int64),
        "v": rng.uniform(0, 10, 900),
        "s": np.array([f"g{i}" for i in rng.integers(0, 6, 900)]),
    }
    schema = Schema.of(a=DType.INT64, b=DType.INT64, v=DType.FLOAT64,
                       s=DType.STRING)
    out = {}
    for mode in ("unfused", "fused"):
        sess = SharkSession(num_workers=2, max_threads=4,
                            default_partitions=3, default_shuffle_buckets=4,
                            pde_config=fusion_pde(mode == "fused"))
        sess.create_table("t", schema, data)
        out[mode] = sess
    yield out
    for sess in out.values():
        sess.shutdown()


def _gen_sql(pred_col, op, threshold, shape, group_col, agg_name, agg_col,
             limit):
    where = f"WHERE {pred_col} {op} {threshold}"
    if shape == "groupby":
        agg = (f"{agg_name}({agg_col})" if agg_name != "COUNT"
               else "COUNT(*)")
        return (f"SELECT {group_col}, {agg} AS x, COUNT(*) AS c "
                f"FROM t {where} GROUP BY {group_col}")
    if shape == "agg":
        agg = (f"{agg_name}({agg_col})" if agg_name != "COUNT"
               else "COUNT(*)")
        return f"SELECT {agg} AS x, COUNT(*) AS c FROM t {where}"
    if shape == "sort":
        return (f"SELECT a, b, v FROM t {where} "
                f"ORDER BY v DESC, a LIMIT {limit}")
    return f"SELECT a, b + a AS ba, v FROM t {where} LIMIT {limit}"


def _rows(got):
    cols = [np.asarray(got[k]).tolist() for k in sorted(got)]
    return sorted(zip(*cols)) if cols else []


def _check_one(sessions, sql):
    fps, plans, results = {}, {}, {}
    for mode, sess in sessions.items():
        plans[mode] = sess.explain(sql)
        node = optimize(sess.plan(sql), sess.catalog)
        fps[mode] = plan_fingerprint(node, sess.catalog)[0]
        results[mode] = sess.sql_np(sql)
    assert plans["fused"] == plans["unfused"], \
        f"fusion changed explain()\n  {sql}"
    assert fps["fused"] == fps["unfused"], \
        f"fusion changed plan_fingerprint\n  {sql}"
    rows_f, rows_o = _rows(results["fused"]), _rows(results["unfused"])
    assert len(rows_f) == len(rows_o), sql
    for rf, ro in zip(rows_f, rows_o):
        for vf, vo in zip(rf, ro):
            if isinstance(vo, float):
                assert vf == vo or abs(vf - vo) <= 1e-9 + 1e-9 * abs(vo), \
                    f"{vf!r} != {vo!r}\n  {sql}"
            else:
                assert vf == vo, f"{vf!r} != {vo!r}\n  {sql}"
    assert sessions["unfused"].metrics().fused_partitions() == 0


def test_fusion_invariants_sweep(sessions):
    """Deterministic grid over every query shape (runs even without
    hypothesis installed)."""
    cases = [
        ("a", ">", 5, "groupby", "s", "SUM", "v", None),
        ("b", "<=", 0, "groupby", "a", "MIN", "b", None),
        ("v", ">=", 3, "agg", None, "AVG", "v", None),
        ("s", "=", "'g2'", "agg", None, "COUNT", None, None),
        ("a", "!=", 7, "sort", None, None, None, 9),
        ("b", "<", 10, "limit", None, None, None, 5),
    ]
    for pred_col, op, thr, shape, gcol, agg, acol, limit in cases:
        _check_one(sessions, _gen_sql(pred_col, op, thr, shape, gcol,
                                      agg, acol, limit or 7))
    assert sessions["fused"].metrics().fused_partitions() > 0


try:
    from hypothesis import HealthCheck, given, settings, strategies as st
    _HAS_HYPOTHESIS = True
except ImportError:        # pragma: no cover - exercised on minimal images
    _HAS_HYPOTHESIS = False


if _HAS_HYPOTHESIS:
    @settings(max_examples=40, deadline=None,
              suppress_health_check=[HealthCheck.function_scoped_fixture])
    @given(
        pred_col=st.sampled_from(["a", "b", "v"]),
        op=st.sampled_from(CMPS),
        threshold=st.integers(min_value=-40, max_value=40),
        shape=st.sampled_from(["groupby", "agg", "sort", "limit"]),
        group_col=st.sampled_from(["a", "s"]),
        agg_name=st.sampled_from(AGGS),
        agg_col=st.sampled_from(["v", "b"]),
        limit=st.integers(min_value=1, max_value=20),
    )
    def test_property_fusion_never_changes_plan_or_rows(
            sessions, pred_col, op, threshold, shape, group_col, agg_name,
            agg_col, limit):
        _check_one(sessions, _gen_sql(pred_col, op, threshold, shape,
                                      group_col, agg_name, agg_col, limit))
else:
    @pytest.mark.skip(reason="hypothesis not installed")
    def test_property_fusion_never_changes_plan_or_rows():
        pass
