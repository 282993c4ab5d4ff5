"""The `users` feature table of the Shark paper's SQL->ML workflow
(sec. 6.5), generated from a seed and loaded into a plain SharkSession.

`dims` float32 features and a 0/1 float32 label.  Features f1.. are a
mixture of `clusters` unit Gaussians around centres drawn from the seed;
the label is a noisy linear function of all features.  f0 is the column
the workflow's SQL selects on: in every partition it holds the same
multiset of standard-normal values (drawn once, not from the seed), in an
order drawn from the seed.  So `f0 > t` keeps the same number of rows of
every partition for every seed, and the programs the window runs have
the same shapes on every seed, which the warm-up compiles once.
"""

from __future__ import annotations

import time

import numpy as np


class Built:
    def __init__(self, session, truth: dict, rows: dict, timings: dict):
        self.session = session
        self.truth = truth
        self.rows = rows
        self.timings = timings

    def close(self) -> None:
        if self.session is not None:
            self.session.shutdown()
            self.session = None


def gen_users(rng, n: int, dims: int, clusters: int, partitions: int):
    edges = np.linspace(0, n, partitions + 1, dtype=np.int64)
    x = rng.standard_normal((n, dims), dtype=np.float32)
    centres = rng.standard_normal((clusters, dims)).astype(np.float32)
    x += centres[rng.integers(0, clusters, n)]
    for lo, hi in zip(edges[:-1], edges[1:]):
        base = np.sort(np.random.default_rng(0).standard_normal(
            hi - lo)).astype(np.float32)
        x[lo:hi, 0] = base[rng.permutation(hi - lo)]
    w_true = rng.standard_normal(dims)
    z = x @ w_true + rng.normal(scale=0.5, size=n)
    y = (z > 0).astype(np.float32)
    return x, y


def build(config: dict, seed: int, rehearsal: bool = False) -> Built:
    from repro.core import DType, Schema, SharkSession

    size = config["rehearsal"] if rehearsal else config
    serve = config["serve"]
    d = config["dims"]
    parts = size.get("partitions", serve["partitions"])
    workers = size.get("workers", serve["workers"])
    t0 = time.perf_counter()
    rng = np.random.default_rng(seed)
    x, y = gen_users(rng, size["users_rows"], d, config["clusters"], parts)
    t1 = time.perf_counter()
    sess = SharkSession(num_workers=workers, max_threads=workers,
                        default_partitions=parts)
    cols = {f"f{i}": x[:, i] for i in range(d)}
    cols["label"] = y
    sess.create_table("users", Schema.of(
        **{f"f{i}": DType.FLOAT32 for i in range(d)},
        label=DType.FLOAT32), cols)
    t2 = time.perf_counter()
    return Built(sess, {"x": x, "y": y}, {"users": len(y)},
                 {"generate_s": t1 - t0, "encode_s": t2 - t1})
