"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps + hypothesis."""

import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref

RNG = np.random.default_rng(0)


@pytest.mark.parametrize("n", [1, 100, 1024, 8 * 128, 8 * 128 * 3 + 17])
@pytest.mark.parametrize("dtype", [np.float32, np.int32])
def test_colscan_sweep(n, dtype):
    f = RNG.normal(size=n).astype(np.float32)
    a = (RNG.normal(size=n) * 10).astype(dtype)
    got = np.asarray(ops.colscan(f, a, -0.5, 0.5))
    want = np.asarray(ref.colscan_ref(jnp.asarray(f),
                                      jnp.asarray(a.astype(np.float32)),
                                      -0.5, 0.5))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("n,d", [(1, 3), (1000, 50), (8 * 128 * 2 + 5, 4096)])
@pytest.mark.parametrize("dtype", [np.float32, np.int32])
def test_dict_decode_sweep(n, d, dtype):
    dic = (RNG.normal(size=d) * 100).astype(dtype)
    codes = RNG.integers(0, d, n).astype(np.int32)
    got = np.asarray(ops.dict_decode(codes, dic))
    want = np.asarray(ref.dict_decode_ref(jnp.asarray(codes),
                                          jnp.asarray(dic)))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("width", [1, 4, 7, 8, 16])
def test_bitpack_sweep(width):
    n = 3000
    per = 32 // width
    vals = RNG.integers(0, 1 << width, n).astype(np.uint32)
    nw = -(-n // per)
    padded = np.zeros(nw * per, np.uint32)
    padded[:n] = vals
    words = np.zeros(nw, np.uint32)
    for j in range(per):
        words |= padded[j::per] << np.uint32(j * width)
    got = np.asarray(ops.bitpack_decode(words, width, -3, n))
    want = np.asarray(ref.bitpack_decode_ref(jnp.asarray(words), width, -3, n))
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(got, vals.astype(np.int32) - 3)


@pytest.mark.parametrize("runs,n", [(1, 64), (5, 1000), (100, 8 * 128 * 2)])
def test_rle_sweep(runs, n):
    lens = np.maximum(1, RNG.multinomial(n - runs, np.ones(runs) / runs) + 1)
    ends = np.cumsum(lens).astype(np.int32)
    vals = RNG.normal(size=runs).astype(np.float32)
    total = int(ends[-1])
    got = np.asarray(ops.rle_decode(vals, ends, total))
    want = np.asarray(ref.rle_decode_ref(jnp.asarray(vals),
                                         jnp.asarray(ends), total))
    np.testing.assert_allclose(got, want)


@pytest.mark.parametrize("n,g", [(100, 7), (5000, 150), (2048, 200),
                                 (1024, 1)])
def test_groupby_sweep(n, g):
    codes = RNG.integers(0, g, n).astype(np.int32)
    vals = RNG.normal(size=n).astype(np.float32)
    got = np.asarray(ops.groupby_sum(codes, vals, g))
    want = np.asarray(ref.groupby_sum_ref(jnp.asarray(codes),
                                          jnp.asarray(vals), g))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_fused_decode_scan_matches_unfused():
    # dictionaries are sorted, as ColumnBlock.code_space hands them out
    n, d = 8 * 128 + 9, 300
    dic = np.sort(RNG.normal(size=d).astype(np.float32))
    codes = RNG.integers(0, d, n).astype(np.int32)
    agg = RNG.normal(size=n).astype(np.float32)
    got = np.asarray(ops.fused_decode_scan(codes, dic, agg, -0.4, 0.9))
    want = np.asarray(ref.fused_decode_scan_ref(
        jnp.asarray(codes), jnp.asarray(dic), jnp.asarray(agg), -0.4, 0.9))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@settings(max_examples=25, deadline=None)
@given(st.integers(min_value=1, max_value=3000),
       st.integers(min_value=1, max_value=64))
def test_property_groupby_counts_total(n, g):
    codes = RNG.integers(0, g, n).astype(np.int32)
    vals = np.ones(n, np.float32)
    out = np.asarray(ops.groupby_sum(codes, vals, g))
    assert out[:, 1].sum() == n           # counts partition the rows
    np.testing.assert_allclose(out[:, 0], out[:, 1])  # sum of ones == count


@settings(max_examples=20, deadline=None)
@given(st.floats(-5, 5), st.floats(-5, 5))
def test_property_colscan_bounds(lo, hi):
    n = 500
    f = RNG.normal(size=n).astype(np.float32)
    a = RNG.normal(size=n).astype(np.float32)
    got = np.asarray(ops.colscan(f, a, min(lo, hi), max(lo, hi)))
    mask = (f >= min(lo, hi)) & (f <= max(lo, hi))
    assert got[0] == mask.sum()
