"""Every Pallas kernel the engine routes to compiles with Mosaic for a TPU
v5e chip, at real sizes, in the x64 scope the engine calls it from.

The chip is described, not attached (`jax.experimental.topologies`), so
nothing runs: these tests catch what interpret mode cannot — block shapes
off the (8, 128) tiling, 64-bit index maps under `expr._x64()`, gathers
and reshapes Mosaic refuses.  The topology is described inside a fixture,
so importing this file touches no TPU library.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.expr import _x64
from repro.kernels import colscan as _cs
from repro.kernels import groupby_mxu as _gb
from repro.kernels import ops
from repro.kernels import radix_partition as _rp
from repro.kernels import segmented_merge as _sm
from repro.kernels import topk_similarity as _tk
from repro.kernels import train_grad as _tg

M = 1 << 20


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(one_chip, fn, shapes, *args, **kw):
    """Lower and compile `fn` for one v5e chip the way the engine calls it:
    inside `expr._x64()`, with the kernel's own x64 scope on top."""
    sds = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    with _x64(), ops.kernel_x64("float32"):
        return fn.lower(*sds, *args, interpret=False, **kw).compile()


F32, I32, U32 = jnp.float32, jnp.int32, jnp.uint32

CASES = {
    # float filter column
    "colscan-131072": (_cs.colscan, [((131072,), F32), ((131072,), F32)],
                       (0.0, 1.0), {"acc_dtype": "float32"}),
    "colscan-1M": (_cs.colscan, [((M,), F32), ((M,), F32)], (0.0, 1.0),
                   {"acc_dtype": "float32"}),
    # fused_decode_scan: int32 dictionary codes against code bounds
    "fused_decode_scan-131072": (_cs.colscan,
                                 [((131072,), I32), ((131072,), F32)],
                                 (3, 40), {"acc_dtype": "float32"}),
    "fused_decode_scan-1M": (_cs.colscan, [((M,), I32), ((M,), F32)],
                             (3, 40), {"acc_dtype": "float32"}),
    "groupby_sum-ndv512": (_gb.groupby_sum, [((M,), I32), ((M,), F32)], (),
                           {"num_groups": 512, "acc_dtype": "float32"}),
    "segmented_merge-ndv512": (_sm.segmented_merge,
                               [((M,), I32), ((M,), F32)], (),
                               {"num_groups": 512, "acc_dtype": "float32"}),
    "radix_partition-64x1M": (_rp.radix_partition, [((M,), U32)], (),
                              {"num_buckets": 64, "with_counts": False}),
    "train_grad-1Mx8": (_tg.train_grad,
                        [((M, 8), F32), ((M,), F32), ((8,), F32)],
                        ("logistic",), {"acc_dtype": "float32"}),
    # the resident route: inputs laid out once, the kernel alone per call
    "train_grad_padded-374784x10": (_tg.train_grad_padded,
                                    [((374784, 128), F32),
                                     ((374784, 128), F32), ((10,), F32)],
                                    ("logistic",), {"acc_dtype": "float32"}),
    "topk_similarity-65536x64": (_tk.topk_similarity,
                                 [((65536, 64), F32), ((64,), F32)], (10,),
                                 {"acc_dtype": "float32"}),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_routed_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes, args, kw = CASES[name]
    compiled = _compile(one_chip, fn, shapes, *args, **kw)
    assert "tpu_custom_call" in compiled.as_text()


# the cluster tier's shard_map programs at the four-node Pavlo cell's
# shapes (cluster/shard_exec.py): 16 partitions of 1.25M rows, 4 per chip,
# padded to 2^21 rows; the exchange over 4 x 5M rows (4 x 2^21 lanes a
# chip) with a 2^21-row stride; int32 lanes and float32 values, as the
# chip runs them
MESH_CASES = {
    "mesh_colscan-4x4x2^21": ("colscan", (4, 1 << 21, "int32", "float32"),
                              [((16, 1 << 21), I32), ((16, 1 << 21), F32),
                               ((16,), I32), ((), I32), ((), I32)]),
    "mesh_exchange-4x2^23": ("exchange", (4, 4, 1 << 21, 1 << 21, "int32",
                                          "float32"),
                             [((16, 1 << 21), I32), ((16,), I32),
                              ((16, 1 << 21), F32)]),
}


@pytest.mark.parametrize("name", sorted(MESH_CASES))
def test_mesh_program_compiles_for_v5e_2x2(topo, name):
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.cluster import shard_exec
    kind, static, shapes = MESH_CASES[name]
    mesh = Mesh(np.array(topo.devices), ("data",))
    if kind == "colscan":
        fn = shard_exec._colscan_program(mesh, *static)
    else:
        fn = shard_exec._exchange_program(mesh, *static)
    sds = [jax.ShapeDtypeStruct(s, d, sharding=NamedSharding(
        mesh, P("data") if s else P())) for s, d in shapes]
    text = fn.lower(*sds).compile().as_text()
    assert "f64" not in text and "s64" not in text
    if kind == "exchange":
        assert "all-to-all" in text
