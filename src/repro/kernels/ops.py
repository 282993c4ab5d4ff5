"""Public wrappers for the Pallas kernels.

Each wrapper prepares its inputs on the host (int32 lanes for codes and
integer filters, the accumulation dtype for values) and calls its kernel;
`train_grad_inputs` and `train_grad_padded` take device arrays as they
are, so inputs kept on the device stay there.  On a TPU the kernels compile to Mosaic and accumulate in float32; on any
other platform they run in interpret mode and accumulate in float64, so
the engine matches the numpy oracle to rounding (semantics validated
against ref.py).

Every kernel call traces with x64 on only when it accumulates in float64
(`kernel_x64`), whatever scope the caller holds: under x64 Mosaic refuses
the 64-bit index maps and constants of a float32 kernel, so the engine's
`expr._x64()` scopes never reach a kernel on the chip.

Each kernel the engine routes to runs in a `shark.device` span (DESIGN.md
§17) counting the host arrays it hands the kernel and the results it reads
back here; a wrapper that returns a device array leaves the read-back to
its caller's span.  (The decode kernels have no engine caller.)
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import numpy as np

from . import colscan as _colscan
from . import dictdecode as _dd
from . import groupby_mxu as _gb
from . import radix_partition as _rp
from . import segmented_merge as _sm
from . import topk_similarity as _tk
from . import train_grad as _tg
from ..core import tracing


@functools.lru_cache(maxsize=1)
def on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


def _interp() -> bool:
    return not on_tpu()


def default_acc_dtype() -> str:
    """The engine's kernel accumulation dtype on this platform."""
    return "float32" if on_tpu() else "float64"


def kernel_x64(acc: str):
    """x64 scope of one kernel call: on only for float64 accumulation."""
    return jax.enable_x64(acc == "float64")


_I32 = np.iinfo(np.int32)


def colscan_filter(filter_col, acc: str) -> Optional[np.ndarray]:
    """The filter column as the colscan kernel compares it: int32 lanes
    for integers that fit, the accumulation dtype otherwise — or None when
    a float32 kernel could not compare it exactly (float64 values, integers
    past int32)."""
    f = np.asarray(filter_col)
    if f.dtype.kind in "iub":
        if f.dtype.itemsize < 4 or f.dtype == np.int32 or f.size == 0 or (
                f.min() >= _I32.min and f.max() <= _I32.max):
            return f.astype(np.int32, copy=False)
    elif f.dtype.itemsize <= np.dtype(acc).itemsize:
        return f.astype(acc, copy=False)
    return f.astype(acc, copy=False) if acc == "float64" else None


def colscan(filter_col, agg_col, lo, hi, acc_dtype: str = "float32"):
    """[count, sum, min, max] of agg_col where lo <= filter_col <= hi."""
    f = colscan_filter(filter_col, acc_dtype)
    if f is None:
        raise ValueError(f"colscan: a {acc_dtype} kernel cannot compare a "
                         f"{np.asarray(filter_col).dtype} filter exactly")
    if f.dtype == np.int32:
        lo, hi = _colscan.int_bounds(lo, hi)
    a = np.asarray(agg_col, acc_dtype)
    with tracing.device("colscan", f, a), kernel_x64(acc_dtype):
        return _colscan.colscan(f, a, lo, hi, interpret=_interp(),
                                acc_dtype=acc_dtype)


def fused_decode_scan(codes, dictionary, agg_col, lo, hi,
                      acc_dtype: str = "float32"):
    """colscan over a dict-coded filter column, decode fused away: the
    dictionary is sorted (`ColumnBlock.code_space`), so the value bounds
    become code bounds here and the kernel compares int32 codes."""
    d = np.asarray(dictionary)
    clo = int(np.searchsorted(d, lo, side="left"))
    chi = int(np.searchsorted(d, hi, side="right")) - 1
    return colscan(np.asarray(codes, np.int32), agg_col, clo, chi,
                   acc_dtype=acc_dtype)


def dict_decode(codes, dictionary):
    return _dd.dict_decode(np.asarray(codes), np.asarray(dictionary),
                           interpret=_interp())


def bitpack_decode(words, bit_width: int, bias: int, n: int):
    return _dd.bitpack_decode(np.asarray(words), bit_width=bit_width,
                              bias=bias, n=n, interpret=_interp())


def rle_decode(run_values, run_ends, n: int):
    return _dd.rle_decode(np.asarray(run_values), np.asarray(run_ends),
                          n=n, interpret=_interp())


def groupby_sum(codes, values, num_groups: int, acc_dtype: str = "float32"):
    """(num_groups, 2) float64 per-group [sum, count] via MXU one-hot
    matmul; counts are exact."""
    codes, values = np.asarray(codes, np.int32), np.asarray(values, acc_dtype)
    with tracing.device("groupby_mxu", codes, values) as sp:
        with kernel_x64(acc_dtype):
            s, c = _gb.groupby_sum(codes, values, num_groups=num_groups,
                                   interpret=_interp(), acc_dtype=acc_dtype)
        s, c = sp.fetch(s), sp.fetch(c)
    return np.stack([s.astype(np.float64), c.astype(np.float64)], axis=1)


def segmented_merge(codes, values, num_groups: int,
                    acc_dtype: str = "float32"):
    """(num_groups, 4) per-group [sum, count, min, max] — the reduce-side
    merge of one aggregate state column (DESIGN.md §11)."""
    codes, values = np.asarray(codes, np.int32), np.asarray(values, acc_dtype)
    with tracing.device("segmented_merge", codes, values) as sp, \
            kernel_x64(acc_dtype):
        return sp.fetch(_sm.segmented_merge(
            codes, values, num_groups=num_groups, interpret=_interp(),
            acc_dtype=acc_dtype))


# -- double-buffered kernel dispatch (DESIGN.md §14) --------------------
#
# JAX dispatch is asynchronous: a jit/Pallas call returns a tracer-backed
# array before the device work completes, and only np.asarray() blocks.
# double_buffer_map exploits that to overlap chunk i+1's dispatch (which
# includes host-side decode/staging of its inputs) with chunk i's compute:
# exactly one launch is kept in flight while the previous result drains.
# Each map is one `shark.device` span whose `chunks` attribute counts its
# launches, so tests can assert the chunked path actually ran.

DOUBLE_BUFFER = {"chunk_rows": 131072}


def double_buffer_map(fn, chunks):
    """Map `fn` over `chunks`, keeping one dispatch in flight.

    `fn(chunk)` must return a JAX array (or tuple of them); results are
    materialized to numpy in order.  With one chunk this degenerates to a
    plain call — same arithmetic, same rounding class."""
    out = []
    inflight = None
    with tracing.device("double_buffer") as sp:
        for chunk in chunks:
            nxt = fn(chunk)              # async dispatch: returns immediately
            if inflight is not None:
                out.append(jax.tree_util.tree_map(sp.fetch, inflight))
            inflight = nxt
        if inflight is not None:
            out.append(jax.tree_util.tree_map(sp.fetch, inflight))
        sp.set(chunks=len(out))
    return out


def topk_similarity(x, q, k: int, acc_dtype: str = None):
    """(scores, row indices) of the top-k dot-product matches of query `q`
    in candidate matrix `x` — scores descending, ties by ascending row
    index, matching `np.argsort(-scores, kind="stable")[:k]` exactly
    (DESIGN.md §15.3).  Returns numpy arrays of length min(k, rows)."""
    acc = acc_dtype or default_acc_dtype()
    x, q = np.asarray(x, acc), np.asarray(q, acc)
    with tracing.device("topk_similarity", x, q) as sp, kernel_x64(acc):
        s, i = _tk.topk_similarity(x, q, int(k), interpret=_interp(),
                                   acc_dtype=acc)
        return sp.fetch(s), sp.fetch(i)


def train_grad(x, y, w, kind: str = "logistic", acc_dtype: str = None):
    """Unnormalized batch gradient `x.T @ (pred(x @ w) - y)` as a numpy
    (d,) vector, from host arrays.  The trainer's `train_grad` route lays
    its inputs out once (`train_grad_inputs`) and calls
    `train_grad_padded` every iteration."""
    acc = acc_dtype or default_acc_dtype()
    x, y, w = np.asarray(x, acc), np.asarray(y, acc), np.asarray(w, acc)
    with tracing.device("train_grad", x, y, w) as sp, kernel_x64(acc):
        return sp.fetch(_tg.train_grad(x, y, w, kind, interpret=_interp(),
                                       acc_dtype=acc))


def train_grad_inputs(x, y, acc_dtype: str = None):
    """Device arrays (xp, yp): `x`, `y` laid out as the `train_grad` kernel
    reads them.  Takes device arrays as they are, so features assembled on
    the device stay there; the caller's span counts what it hands in."""
    acc = acc_dtype or default_acc_dtype()
    with kernel_x64(acc):
        return _tg.pad_inputs(x, y, acc_dtype=acc)


def train_grad_padded(xp, yp, w, kind: str = "logistic",
                      acc_dtype: str = None):
    """`train_grad` over inputs `train_grad_inputs` laid out on the device:
    only `w` goes up, and the numpy (d,) gradient comes back."""
    acc = acc_dtype or default_acc_dtype()
    w = np.asarray(w, acc)
    with tracing.device("train_grad", w) as sp, kernel_x64(acc):
        return sp.fetch(_tg.train_grad_padded(
            xp, yp, w, kind, interpret=_interp(), acc_dtype=acc))


def radix_partition(keys_u32, num_buckets: int, with_counts: bool = True):
    """(bucket_ids, per-bucket counts) for folded uint32 key hashes — the
    map side of the memory-based shuffle as one fused pass.
    `with_counts=False` skips the histogram matmul (ids-only callers).
    All lanes are 32-bit, so the call traces with x64 off everywhere."""
    keys = np.asarray(keys_u32, np.uint32)
    with tracing.device("radix_partition", keys), kernel_x64("float32"):
        return _rp.radix_partition(keys, num_buckets=num_buckets,
                                   interpret=_interp(),
                                   with_counts=with_counts)
