"""A four-node Pavlo deployment (SIGMOD 2009, sec. 4.3): `uservisits`,
drawn by bench/data/pavlo.py's generator, served by one SharkServer whose
MeshContext holds the host's four chips, one chip per node.

The engine places the table's partitions round-robin over the chips
(`MeshContext.place`) at every mesh dispatch.  Before it makes the data,
`build` checks on a small table that the program runs this deployment:
that the Pavlo aggregation task's GROUP BY on a string key takes the mesh
exchange between the chips.  A program that answers it on the host serves
another deployment, and the run stops there with exit code 1.  `destURL`
draws from one node's page-URL vocabulary; `rankings` is not loaded,
since no query of the cell reads it.  The reference keeps the generator's indices of the
country and source-IP columns, the IP strings in the order of their
indices, and the numeric columns it sums.
"""

from __future__ import annotations

import time

import numpy as np

from bench.data.pavlo import Built, _page_urls, gen_uservisits


def generate(size: dict, seed: int):
    """(uservisits columns, what the reference keeps) at `size`."""
    rng = np.random.default_rng(seed)
    uv, idx, vocab = gen_uservisits(rng, size["uservisits_rows"],
                                    _page_urls(rng, size["page_urls"]))
    # the IP strings in the order of their vocabulary indices (the
    # generator's vocabulary is sorted), and each row's rank among them
    _, first, ip = np.unique(idx["ip"], return_index=True,
                             return_inverse=True)
    truth = {
        "visitDate": uv["visitDate"], "adRevenue": uv["adRevenue"],
        "country": idx["country"], "country_vocab": vocab["country"],
        "ip": ip.astype(np.int32), "ip_vocab": uv["sourceIP"][first],
    }
    return uv, truth


PROBE_SQL = ("SELECT sourceIP, SUM(adRevenue) AS rev FROM uservisits "
             "GROUP BY sourceIP")
PROBE_SIZE = {"uservisits_rows": 4096, "page_urls": 4096}


def require_mesh_exchange(config: dict, seed: int) -> None:
    """Stop the run unless a string-key GROUP BY over a small uservisits,
    placed over the configuration's chips, takes the mesh exchange."""
    from repro.cluster import MeshContext
    from repro.core import DType, Schema
    from repro.core.session import SharkSession

    devices = config["serve"]["mesh_devices"]
    uv, _ = generate(PROBE_SIZE, seed)
    sess = SharkSession(num_workers=2, default_partitions=devices,
                        mesh=MeshContext(max_devices=devices))
    try:
        schema = config["schema"]["uservisits"]
        sess.create_table("uservisits", Schema.of(
            **{c: DType[t] for c, t in schema.items()}), uv)
        sess.sql_np(PROBE_SQL)
        routes = sess.metrics().segment_routes()
    finally:
        sess.shutdown()
    if not routes.get("mesh-exchange"):
        raise SystemExit(
            f"bench: {config['name']} shuffles its GROUP BY keys between "
            f"the chips, and this program answered the Pavlo aggregation "
            f"task on the host (routes {routes}); it cannot run this "
            f"deployment")


def build(config: dict, seed: int, rehearsal: bool = False) -> Built:
    from repro.cluster import MeshContext
    from repro.core import DType, Schema
    from repro.core.pde import PDEConfig
    from repro.server import SharkServer

    size = config["rehearsal"] if rehearsal else config
    serve = config["serve"]
    t0 = time.perf_counter()        # generate_s holds the probe's time
    require_mesh_exchange(config, seed)
    uv, truth = generate(size, seed)
    t1 = time.perf_counter()
    pde = (PDEConfig(segment_force_kernels=True, reduce_force_compiled=True)
           if rehearsal else None)
    workers = size.get("workers", serve["workers"])
    server = SharkServer(
        num_workers=workers, max_threads=workers,
        max_concurrent_queries=serve["max_concurrent_queries"],
        enable_result_cache=serve["result_cache"],
        default_partitions=size.get("partitions", serve["partitions"]),
        pde_config=pde, mesh=MeshContext(max_devices=serve["mesh_devices"]))
    rows = {"uservisits": len(uv["visitDate"])}
    server.create_table("uservisits", Schema.of(
        **{c: DType[t] for c, t in config["schema"]["uservisits"].items()}),
        uv)
    t2 = time.perf_counter()
    return Built(server, truth, rows,
                 {"generate_s": t1 - t0, "encode_s": t2 - t1})
