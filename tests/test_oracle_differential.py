"""Differential testing: ~200 seeded random queries (multi-way star joins,
filters, group-by/having, order/limit — see tests/oracle.py) execute on the
engine and on a pure-pandas reference; results must agree.

This is the correctness oracle for the compiled-vectorized-execution
surface: every query runs under BOTH execution backends —

  * ``backend="compiled"``: pipeline segments execute as jit-compiled
    columnar functions (with per-partition kernel/jit/numpy routing), and
  * ``backend="numpy"``: the same segments run the evaluate() oracle —

and both must match pandas AND each other row-identically.  ExecMetrics is
asserted on every query: zero standalone interpreted filter/project
operators on the scan path (the tentpole invariant), and per query
archetype at least one query must actually have taken a compiled route.
"""

import numpy as np
import pytest

pd = pytest.importorskip("pandas")

from repro.core import SharkSession

from conftest import fusion_pde
from oracle import QueryGen, compare, make_star_data, register_star_tables

pytestmark = pytest.mark.tier1

N_QUERIES = 200

SESSION_KW = dict(num_workers=2, max_threads=4, default_partitions=3,
                  default_shuffle_buckets=4)


def _archetypes(query):
    out = []
    if len(query.tables) > 1:
        out.append("join")
    if query.aggs and query.group_by:
        out.append("groupby")
    elif query.aggs:
        out.append("agg")
    else:
        out.append("scan")
    if query.limit is not None:
        out.append("limit")
    return out


@pytest.fixture(scope="module")
def env():
    data = make_star_data(seed=0)
    sess_c = SharkSession(backend="compiled", **SESSION_KW)
    sess_n = SharkSession(backend="numpy", **SESSION_KW)
    register_star_tables(sess_c, data)
    register_star_tables(sess_n, data)
    dfs = {name: pd.DataFrame({k: v for k, v in cols.items()})
           for name, cols in data.items()}
    coverage = {}   # archetype -> compiled partitions observed
    yield sess_c, sess_n, data, dfs, coverage
    sess_c.shutdown()
    sess_n.shutdown()


def _rows(got, names):
    arrays = []
    for n in names:
        a = np.asarray(got[n])
        arrays.append(a.tolist())
    return sorted(zip(*arrays)) if arrays else []


def assert_backend_parity(query, got_c, got_n, sql):
    """Compiled and numpy backends must produce row-identical results:
    exact on ints/bools/strings, to rounding on floats (XLA may reorder
    float reductions)."""
    names = (query.group_by + [a.alias for a in query.aggs]
             if query.aggs else list(query.select_cols))
    assert bool(got_c) == bool(got_n), f"one backend returned nothing\n  {sql}"
    if not got_c:
        return
    rows_c = _rows(got_c, names)
    rows_n = _rows(got_n, names)
    assert len(rows_c) == len(rows_n), \
        f"row counts differ: {len(rows_c)} vs {len(rows_n)}\n  {sql}"
    for rc, rn in zip(rows_c, rows_n):
        for vc, vn, name in zip(rc, rn, names):
            if isinstance(vn, float):
                # vc == vn first: covers the ±inf identity sentinels of
                # MIN/MAX over empty inputs (inf - inf is nan)
                assert vc == vn or abs(vc - vn) <= 1e-9 + 1e-9 * abs(vn), \
                    f"{name}: {vc!r} != {vn!r}\n  {sql}"
            else:
                assert vc == vn, f"{name}: {vc!r} != {vn!r}\n  {sql}"


def _run_one(env, seed):
    sess_c, sess_n, data, dfs, coverage = env
    query = QueryGen(data, seed).gen()
    sql = query.sql()
    got_c = sess_c.sql_np(sql)
    mc = sess_c.metrics()
    # the tentpole invariant: the scan path never runs interpreted
    # operator-at-a-time filter/project
    assert mc.interpreted_scan_ops == 0, sql
    if len(query.tables) == 1:
        assert len(mc.segments) >= 1, \
            f"single-table SELECT did not go through a PipelineSegment\n  {sql}"
    got_n = sess_n.sql_np(sql)
    assert sess_n.metrics().interpreted_scan_ops == 0, sql
    assert sess_n.metrics().compiled_partitions() == 0, \
        f"numpy backend took a compiled route\n  {sql}"
    for arch in _archetypes(query):
        coverage[arch] = coverage.get(arch, 0) + mc.compiled_partitions()
    return query, sql, got_c, got_n


@pytest.mark.parametrize("seed", range(N_QUERIES))
def test_random_query_matches_pandas(env, seed):
    _, _, _, dfs, _ = env
    query, sql, got_c, got_n = _run_one(env, seed)
    ref = query.pandas(dfs)
    compare(query, got_c, ref)
    compare(query, got_n, ref)
    assert_backend_parity(query, got_c, got_n, sql)


def test_compiled_path_taken_per_archetype(env):
    """≥1 query per archetype must actually have executed on a compiled
    route (jit or kernel), observed via ExecMetrics."""
    _, _, _, _, coverage = env
    required = ("scan", "join", "agg", "groupby", "limit")
    if any(coverage.get(a, 0) == 0 for a in required):
        # standalone / partial-selection run: generate coverage ourselves
        for seed in range(60):
            _run_one(env, seed)
    for arch in required:
        assert coverage.get(arch, 0) > 0, \
            f"archetype {arch!r} never took the compiled path: {coverage}"


N_EXCHANGE_SEEDS = 60


@pytest.fixture(scope="module")
def exchange_env(env):
    """One more executor over the SAME data: the compiled reduce path
    FORCED ON over the dictionary-preserving exchange (DESIGN.md §11),
    beside the numpy-backend reference session from `env`."""
    from repro.core.pde import PDEConfig
    _, sess_n, data, dfs, _ = env
    sess_f = SharkSession(backend="compiled",
                          pde_config=PDEConfig(reduce_force_compiled=True),
                          **SESSION_KW)
    register_star_tables(sess_f, data)
    yield sess_f, sess_n, data, dfs
    sess_f.shutdown()


@pytest.mark.parametrize("seed", range(N_EXCHANGE_SEEDS))
def test_compiled_reduce_forced_on_off_parity(exchange_env, seed):
    """Row-identical parity between the forced compiled reduce path and
    the fully interpreted numpy backend, both checked against pandas."""
    sess_f, sess_n, data, dfs = exchange_env
    query = QueryGen(data, seed).gen()
    sql = query.sql()
    got_f = sess_f.sql_np(sql)
    got_n = sess_n.sql_np(sql)
    assert sess_n.metrics().compiled_partitions() == 0, sql
    ref = query.pandas(dfs)
    compare(query, got_f, ref)
    compare(query, got_n, ref)
    assert_backend_parity(query, got_f, got_n, sql)
    # the forced session must never take a numpy reduce route
    for s in sess_f.metrics().segments:
        if s.consumer in ("merge_aggregate", "join_probe"):
            assert s.routes.get("numpy", 0) == s.fallbacks, s.describe()


N_STORAGE_SEEDS = 40


@pytest.fixture(scope="module")
def storage_env(env):
    """Two more executors over the SAME data for the storage tier
    (DESIGN.md §12), both over the same recompressed blocks (FOR/RLE
    layouts produced by the WARM-tier pass): the compiled backend, whose
    compressed-domain routes read those layouts, and the numpy-backend
    reference, which decodes every block before it evaluates.  Wrong
    code-bound translation or run-level aggregation shows up here as a
    parity break against pandas or against the reference."""
    _, _, data, dfs, _ = env
    sess_on = SharkSession(backend="compiled", **SESSION_KW)
    sess_off = SharkSession(backend="numpy", **SESSION_KW)
    register_star_tables(sess_on, data)
    register_star_tables(sess_off, data)
    # Force FOR / RLE layouts onto numeric columns (the star columns are
    # narrow-range, so adaptive recompression would pick BITPACK and the
    # grid would never touch the compressed-domain routes).  Predicates the
    # grid generates against these columns now hit the code-bound and
    # run-level paths in the compiled session.
    from repro.core.compression import Encoding, encode
    force = {"fact": {"fn": Encoding.FOR, "fk2": Encoding.FOR,
                      "fk3": Encoding.RLE},
             "dim1": {"a1": Encoding.RLE},
             "dim2": {"a2": Encoding.RLE}}
    for sess in (sess_on, sess_off):
        for tname, cols in force.items():
            for part in sess.catalog.get(tname).partitions:
                for cname, target in cols.items():
                    blk = part._columns[cname]
                    blk.enc = encode(blk.values(), target)
                    blk.drop_decoded()
    yield sess_on, sess_off, data, dfs
    sess_on.shutdown()
    sess_off.shutdown()


@pytest.mark.parametrize("seed", range(N_STORAGE_SEEDS))
def test_compressed_domain_forced_on_off_parity(storage_env, seed):
    """Row-identical parity between compressed-domain execution (range
    predicates on FOR codes, run-level RLE scans) and the numpy-backend
    reference on the same blocks, both checked against pandas."""
    sess_on, sess_off, data, dfs = storage_env
    query = QueryGen(data, seed).gen()
    sql = query.sql()
    got_on = sess_on.sql_np(sql)
    got_off = sess_off.sql_np(sql)
    ref = query.pandas(dfs)
    compare(query, got_on, ref)
    compare(query, got_off, ref)
    assert_backend_parity(query, got_on, got_off, sql)
    # the reference must never take a compiled or compressed-domain route
    assert sess_off.metrics().compiled_partitions() == 0, sql
    for s in sess_off.metrics().segments:
        assert s.routes.get("for-colscan", 0) == 0, s.describe()
        assert s.routes.get("rle-scan", 0) == 0, s.describe()


def test_compressed_domain_routes_fire_on_forced_layouts(storage_env):
    """The random grid rarely draws the exact colscan shape, so pin it:
    a range predicate over a FOR column and an RLE column must take the
    code-bound / run-level routes on the compiled backend, and agree with
    the numpy-backend reference, which takes neither."""
    sess_on, sess_off, _, _ = storage_env
    cases = [
        ("SELECT COUNT(*) AS c, SUM(fv) AS s FROM fact "
         "WHERE fn BETWEEN 20 AND 70", "for-colscan"),
        # fact, not a dim: partitions must clear the 64-row compiled
        # threshold; AVG not SUM: int64 SUM keeps integer accumulators and
        # is excluded from kernel colscan shapes
        ("SELECT COUNT(*) AS c, AVG(fk3) AS m FROM fact "
         "WHERE fk3 BETWEEN 2 AND 9", "rle-scan"),
    ]
    for sql, route in cases:
        got_on = sess_on.sql_np(sql)
        assert route in sess_on.metrics().segment_routes(), \
            f"{route} never fired for {sql}: " \
            f"{sess_on.metrics().segment_routes()}"
        got_off = sess_off.sql_np(sql)
        assert route not in sess_off.metrics().segment_routes()
        for k in got_on:
            np.testing.assert_allclose(got_on[k], got_off[k], rtol=1e-12)


def test_oracle_grid_covers_multiway_joins(env):
    """The seeded grid must actually exercise the tentpole surface: 3-way
    and 4-way joins, both join styles, grouping, having, and limits."""
    sess_c, _, data, dfs, _ = env
    queries = [QueryGen(data, s).gen() for s in range(N_QUERIES)]
    n_tables = {len(q.tables) for q in queries}
    assert {3, 4} <= n_tables, f"join-depth coverage hole: {n_tables}"
    styles = {q.join_style for q in queries if len(q.tables) > 2}
    assert styles == {"explicit", "comma"}
    assert any(q.having is not None for q in queries)
    assert any(q.limit is not None and q.aggs for q in queries)
    assert any(q.limit is not None and not q.aggs for q in queries)


# -- whole-stage fusion differential (DESIGN.md §14) --------------------------

N_FUSION_SEEDS = 60


@pytest.fixture(scope="module")
def fusion_env(env):
    """Three-way fusion differential over the SAME data: whole-stage
    compilation FUSED (every eligible partition runs the fused stage
    program), UNFUSED (the compiled segment-at-a-time path with its host
    seams), and the fully interpreted numpy backend from `env`.  All three
    must agree row-identically."""
    _, sess_n, data, dfs, _ = env
    sess_ws = SharkSession(backend="compiled", pde_config=fusion_pde(True),
                           **SESSION_KW)
    sess_seam = SharkSession(backend="compiled",
                             pde_config=fusion_pde(False), **SESSION_KW)
    register_star_tables(sess_ws, data)
    register_star_tables(sess_seam, data)
    fusion_coverage = {}   # archetype -> fused (whole-stage) partitions
    yield sess_ws, sess_seam, sess_n, data, dfs, fusion_coverage
    sess_ws.shutdown()
    sess_seam.shutdown()


def _run_one_fused(fusion_env, seed):
    sess_ws, sess_seam, sess_n, data, dfs, fusion_coverage = fusion_env
    query = QueryGen(data, seed).gen()
    sql = query.sql()
    got_ws = sess_ws.sql_np(sql)
    mws = sess_ws.metrics()
    # fused partitions surface as the synthetic "whole-stage" route key and
    # never as interpreted scan work
    assert mws.interpreted_scan_ops == 0, sql
    routes = mws.segment_routes()
    assert routes.get("whole-stage", 0) == mws.fused_partitions(), sql
    got_seam = sess_seam.sql_np(sql)
    mseam = sess_seam.metrics()
    assert mseam.interpreted_scan_ops == 0, sql
    assert mseam.fused_partitions() == 0, \
        f"the unfused session still fused a stage\n  {sql}"
    assert "whole-stage" not in mseam.segment_routes(), sql
    got_n = sess_n.sql_np(sql)
    assert sess_n.metrics().fused_partitions() == 0, sql
    for arch in _archetypes(query):
        fusion_coverage[arch] = (fusion_coverage.get(arch, 0)
                                 + mws.fused_partitions())
    return query, sql, got_ws, got_seam, got_n


@pytest.mark.parametrize("seed", range(N_FUSION_SEEDS))
def test_stage_fusion_forced_on_off_parity(fusion_env, seed):
    """Whole-stage FUSED vs segment-at-a-time vs fully interpreted: all
    three row-identical to each other and to pandas."""
    _, _, _, _, dfs, _ = fusion_env
    query, sql, got_ws, got_seam, got_n = _run_one_fused(fusion_env, seed)
    ref = query.pandas(dfs)
    compare(query, got_ws, ref)
    compare(query, got_seam, ref)
    compare(query, got_n, ref)
    assert_backend_parity(query, got_ws, got_seam, sql)
    assert_backend_parity(query, got_ws, got_n, sql)


def test_whole_stage_route_fired_per_archetype(fusion_env):
    """The whole-stage route must actually fire for every archetype with a
    shuffle boundary (join exchanges, global aggregates, group-bys, limits;
    plain scans have no map stage to fuse).  Aggregated across seeds —
    individual seeds may legitimately fall back (tiny partitions, numpy
    oracle rungs)."""
    _, _, _, _, _, fusion_coverage = fusion_env
    required = ("join", "agg", "groupby", "limit")
    if any(fusion_coverage.get(a, 0) == 0 for a in required):
        # standalone / partial-selection run: generate coverage ourselves
        for seed in range(N_FUSION_SEEDS):
            _run_one_fused(fusion_env, seed)
    for arch in required:
        assert fusion_coverage.get(arch, 0) > 0, \
            f"archetype {arch!r} never fused a whole stage: {fusion_coverage}"
