#!/usr/bin/env python3
"""Bring-up smoke test: Shark's SQL+ML path on one TPU chip.

    python chip_smoke.py                 # one chip: load, queries, analytics
    python chip_smoke.py --chips 4       # only the mesh-sharded path, 4 chips

In one process it loads the Pavlo et al. benchmark tables (`rankings`,
`uservisits`, generated from `--seed`) and a feature table through
`SharkSession.create_table`, runs the Pavlo selection, aggregation and join
queries, a range-filter aggregate and two low-NDV group-bys through the
default compiled session, and trains a few logistic-regression iterations
over a SQL-selected frame.  Every answer is compared with a plain numpy
computation made here from the generator's own indices, independent of the
engine: counts, integer aggregates, keys and row sets exactly, float
aggregates within F_RTOL (the chip accumulates them in float32).  The
engine's own counters must show that each routed Pallas kernel ran and that
no query fell back to the interpreter.

`--chips 4` runs only the mesh path (`SharkSession(mesh=MeshContext())`):
a range-filter aggregate and an integer-key group-by on four devices, the
same queries on one device, and the numpy reference.

The script refuses to run without a TPU.  `--rehearse` runs the same phases
at a tiny size on any platform with the kernels forced through interpret
mode; it checks everything and then exits 1, since it is not a chip run
(add XLA_FLAGS=--xla_force_host_platform_device_count=4 for `--chips 4`).

The last line of a chip run is one JSON object:
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}
"""

from __future__ import annotations

import argparse
import contextlib
import itertools
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# float aggregates: float32 accumulation on the chip vs a float64 reference
F_RTOL = 1e-4
# logistic-regression weights after a few float32 iterations, relative to
# the largest weight of the float64 reference
W_TOL = 1e-3

# rows: Pavlo's rankings per node; uservisits cut from 155M rows per node
# to what the host and a few-minute run allow
SIZES = {"rankings": 18_000_000, "uservisits": 10_000_000,
         "users": 2_000_000, "partitions": 16}
SIZES_REHEARSAL = {"rankings": 60_000, "uservisits": 40_000,
                   "users": 20_000, "partitions": 4}
WORKERS = 8
ITERATIONS = 3

VISIT_DAYS = (10957, 11688)         # 2000-01-01 .. 2001-12-31, days
JOIN_DAYS = (11000, 11050)          # Pavlo join: a 51-day visitDate window
SCAN_DAYS = (11000, 11200)


def log(msg: str) -> None:
    print(msg, flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


@contextlib.contextmanager
def phase(name: str):
    t0 = time.perf_counter()
    yield
    log(f"phase {name}: {time.perf_counter() - t0:.3f} s")


# -- data: the Pavlo et al. tables, generated from a seed ---------------------

def _words(rng, n: int, lo: int, hi: int, alphabet: str) -> np.ndarray:
    """n distinct random words of lo..hi letters, sorted."""
    letters = np.array(list(alphabet))
    out: set = set()
    while len(out) < n:
        k = int(rng.integers(lo, hi + 1))
        out.add("".join(letters[rng.integers(0, len(letters), k)]))
    return np.array(sorted(out))


def gen_rankings(rng, n: int) -> dict:
    """rankings(pageURL, pageRank, avgDuration): one row per distinct URL."""
    return {
        "pageURL": np.strings.add("url", np.arange(n).astype("U9")),
        "pageRank": rng.zipf(1.5, n).clip(0, 10000).astype(np.int32),
        "avgDuration": rng.integers(1, 300, n).astype(np.int32),
    }


def gen_uservisits(rng, n: int, page_urls: np.ndarray):
    """uservisits with its 9 published columns.  String columns are drawn
    from vocabularies by index; the indices stay with the caller so the
    reference never has to parse strings back."""
    octets = rng.integers(0, 256, (max(1, n // 4), 4)).astype("U3")
    ips = octets[:, 0]
    for j in range(1, 4):
        ips = np.strings.add(np.strings.add(ips, "."), octets[:, j])
    ip_vocab = np.unique(ips)
    agents = np.array([f"Mozilla/5.0 (compatible; agent{i:03d})"
                       for i in range(400)])
    letters = "ABCDEFGHIJKLMNOPQRSTUVWXYZ"
    codes3 = np.array(["".join(t) for t in itertools.product(letters,
                                                            repeat=3)])
    country_vocab = np.sort(rng.choice(codes3, 250, replace=False))
    lang_vocab = np.unique(np.strings.add(
        np.strings.add(_words(rng, 60, 2, 2, letters.lower()), "-"),
        _words(rng, 60, 2, 2, letters))[:100])
    word_vocab = _words(rng, 10000, 4, 12, letters.lower())
    idx = {
        "ip": rng.integers(0, len(ip_vocab), n),
        "dest": rng.integers(0, len(page_urls), n),
        "agent": rng.integers(0, len(agents), n),
        "country": rng.integers(0, len(country_vocab), n),
        "lang": rng.integers(0, len(lang_vocab), n),
        "word": rng.integers(0, len(word_vocab), n),
    }
    data = {
        "sourceIP": ip_vocab[idx["ip"]],
        "destURL": page_urls[idx["dest"]],
        "visitDate": rng.integers(*VISIT_DAYS, n).astype(np.int32),
        "adRevenue": rng.uniform(0, 1000, n),
        "userAgent": agents[idx["agent"]],
        "countryCode": country_vocab[idx["country"]],
        "languageCode": lang_vocab[idx["lang"]],
        "searchWord": word_vocab[idx["word"]],
        "duration": rng.integers(1, 1000, n).astype(np.int32),
    }
    vocab = {"ip": ip_vocab, "country": country_vocab, "lang": lang_vocab}
    return data, idx, vocab


def gen_users(rng, n: int, d: int) -> dict:
    w_true = rng.normal(size=d)
    x = rng.normal(size=(n, d)).astype(np.float32)
    y = (x @ w_true + rng.normal(scale=0.5, size=n) > 0).astype(np.float32)
    cols = {f"f{i}": x[:, i] for i in range(d)}
    cols["label"] = y
    return cols


# -- checks -------------------------------------------------------------------

def check_groups(name: str, got: dict, key: str, exp_keys: np.ndarray,
                 exact: dict, approx: dict) -> None:
    """Group keys and integer columns equal, float columns within F_RTOL."""
    gk = np.asarray(got[key])
    check(len(gk) == len(exp_keys),
          f"{name}: {len(gk)} groups, reference has {len(exp_keys)}")
    go, eo = np.argsort(gk, kind="stable"), np.argsort(exp_keys,
                                                       kind="stable")
    check(np.array_equal(gk[go], exp_keys[eo]), f"{name}: group keys differ")
    for col, want in exact.items():
        g = np.asarray(got[col])[go]
        check(np.array_equal(g.astype(np.int64),
                             np.asarray(want)[eo].astype(np.int64)),
              f"{name}: {col} differs from the reference")
    for col, want in approx.items():
        g = np.asarray(got[col], np.float64)[go]
        w = np.asarray(want, np.float64)[eo]
        err = float(np.max(np.abs(g - w) / np.abs(w))) if len(w) else 0.0
        check(err <= F_RTOL, f"{name}: {col} max rel err {err:.3e} > "
              f"{F_RTOL:.0e}")


class Counters:
    """Kernel routes and interpreter fallbacks summed over every query."""

    def __init__(self):
        self.routes: dict = {}
        self.fallbacks = 0

    def add(self, name: str, metrics, seconds: float, rows: int) -> None:
        routes = metrics.segment_routes()
        for r, k in routes.items():
            self.routes[r] = self.routes.get(r, 0) + k
        fb = sum(s.fallbacks for s in metrics.segments)
        self.fallbacks += fb
        log(f"query {name}: {seconds:.3f} s, {rows} rows, routes "
            f"{json.dumps(routes, sort_keys=True)}, fallbacks {fb}")


def kernel_launches() -> tuple:
    """(radix_partition calls, double-buffered dispatches) recorded by the
    last `tracing.recording()` block."""
    from repro.core import tracing
    dev = [r.attrs for r in tracing.records() if r.name == "shark.device"]
    return (sum(a["program"] == "radix_partition" for a in dev),
            sum(a.get("chunks", 0) for a in dev
                if a["program"] == "double_buffer"))


def run_query(sess, name: str, sql: str, counters: Counters):
    t0 = time.perf_counter()
    got = sess.sql_np(sql)
    dt = time.perf_counter() - t0
    counters.add(name, sess.metrics(), dt,
                 len(next(iter(got.values()))) if got else 0)
    return got


# -- one chip -----------------------------------------------------------------

def one_chip(seed: int, size: dict, rehearse: bool, pde_config) -> None:
    from repro.core import DType, Schema, SharkSession, tracing
    from repro.ml import LogisticRegression

    rng = np.random.default_rng(seed)
    sess = SharkSession(num_workers=WORKERS, max_threads=WORKERS,
                        default_partitions=size["partitions"],
                        pde_config=pde_config)
    d = 10
    with phase("load"):
        rk = gen_rankings(rng, size["rankings"])
        uv, idx, vocab = gen_uservisits(rng, size["uservisits"],
                                        rk["pageURL"])
        users = gen_users(rng, size["users"], d)
        t_r = sess.create_table("rankings", Schema.of(
            pageURL=DType.STRING, pageRank=DType.INT32,
            avgDuration=DType.INT32), rk)
        t_v = sess.create_table("uservisits", Schema.of(
            sourceIP=DType.STRING, destURL=DType.STRING,
            visitDate=DType.DATE, adRevenue=DType.FLOAT64,
            userAgent=DType.STRING, countryCode=DType.STRING,
            languageCode=DType.STRING, searchWord=DType.STRING,
            duration=DType.INT32), uv)
        t_u = sess.create_table("users", Schema.of(
            **{f"f{i}": DType.FLOAT32 for i in range(d)},
            label=DType.FLOAT32), users)
    for t in (t_r, t_v, t_u):
        log(f"table {t.name}: {t.num_rows} rows, {t.num_partitions} "
            f"partitions, {t.nbytes} encoded bytes")
    page_rank = rk["pageRank"]
    page_url = rk["pageURL"]
    rev = uv["adRevenue"]
    vdate = uv["visitDate"]
    duration = uv["duration"]
    del uv

    counters = Counters()
    with tracing.recording(), phase("queries"):
        got = run_query(sess, "pavlo-selection", "SELECT pageURL, pageRank "
                        "FROM rankings WHERE pageRank > 1000", counters)
        m = page_rank > 1000        # pageURL is unique: rows are keys
        check_groups("pavlo-selection", got, "pageURL", page_url[m],
                     {"pageRank": page_rank[m]}, {})

        got = run_query(sess, "pavlo-aggregation", "SELECT sourceIP, "
                        "SUM(adRevenue) AS rev FROM uservisits "
                        "GROUP BY sourceIP", counters)
        nip = len(vocab["ip"])
        cnt = np.bincount(idx["ip"], minlength=nip)
        s = np.bincount(idx["ip"], weights=rev, minlength=nip)
        check_groups("pavlo-aggregation", got, "sourceIP",
                     vocab["ip"][cnt > 0], {}, {"rev": s[cnt > 0]})

        got = run_query(sess, "pavlo-join",
                        "SELECT sourceIP, AVG(pageRank) AS ar, "
                        "SUM(adRevenue) AS rev FROM rankings R, uservisits "
                        "UV WHERE R.pageURL = UV.destURL AND UV.visitDate "
                        f"BETWEEN {JOIN_DAYS[0]} AND {JOIN_DAYS[1]} "
                        "GROUP BY sourceIP", counters)
        m = (vdate >= JOIN_DAYS[0]) & (vdate <= JOIN_DAYS[1])
        g = idx["ip"][m]
        cnt = np.bincount(g, minlength=nip)
        sr = np.bincount(g, weights=page_rank[idx["dest"][m]], minlength=nip)
        sv = np.bincount(g, weights=rev[m], minlength=nip)
        k = cnt > 0
        check_groups("pavlo-join", got, "sourceIP", vocab["ip"][k], {},
                     {"ar": sr[k] / cnt[k], "rev": sv[k]})

        got = run_query(sess, "range-scan",
                        "SELECT COUNT(*) AS c, SUM(adRevenue) AS rev FROM "
                        f"uservisits WHERE visitDate BETWEEN {SCAN_DAYS[0]} "
                        f"AND {SCAN_DAYS[1]}", counters)
        m = (vdate >= SCAN_DAYS[0]) & (vdate <= SCAN_DAYS[1])
        check(int(got["c"][0]) == int(m.sum()), "range-scan: COUNT differs")
        check_groups("range-scan", {"k": np.zeros(1), "rev": got["rev"]},
                     "k", np.zeros(1), {}, {"rev": [rev[m].sum()]})

        got = run_query(sess, "int-sum", "SELECT SUM(pageRank) AS s, "
                        "MAX(pageRank) AS mx, COUNT(*) AS c FROM rankings "
                        "WHERE avgDuration BETWEEN 10 AND 200", counters)
        m = (rk["avgDuration"] >= 10) & (rk["avgDuration"] <= 200)
        want = int(page_rank[m].sum(dtype=np.int64))
        log(f"int-sum: SUM(pageRank) = {int(got['s'][0])} "
            f"(float32 is exact only to {1 << 24})")
        check(int(got["s"][0]) == want, "int-sum: SUM differs")
        check(int(got["mx"][0]) == int(page_rank[m].max()),
              "int-sum: MAX differs")
        check(int(got["c"][0]) == int(m.sum()), "int-sum: COUNT differs")

        got = run_query(sess, "group-country", "SELECT countryCode, "
                        "SUM(adRevenue) AS rev FROM uservisits "
                        "GROUP BY countryCode", counters)
        nc = len(vocab["country"])
        cnt = np.bincount(idx["country"], minlength=nc)
        s = np.bincount(idx["country"], weights=rev, minlength=nc)
        check_groups("group-country", got, "countryCode",
                     vocab["country"][cnt > 0], {}, {"rev": s[cnt > 0]})

        got = run_query(sess, "group-language", "SELECT languageCode, "
                        "COUNT(*) AS c, AVG(duration) AS d FROM uservisits "
                        "GROUP BY languageCode", counters)
        nl = len(vocab["lang"])
        cnt = np.bincount(idx["lang"], minlength=nl)
        s = np.bincount(idx["lang"], weights=duration, minlength=nl)
        k = cnt > 0
        check_groups("group-language", got, "languageCode",
                     vocab["lang"][k], {"c": cnt[k]}, {"d": s[k] / cnt[k]})
    radix, chunks = kernel_launches()

    with tracing.recording(), phase("analytics"):
        fcols = [f"f{i}" for i in range(d)]
        frame = sess.sql("SELECT * FROM users WHERE f0 > -1", lazy=True)
        clf = LogisticRegression(dims=d, lr=0.5, iterations=ITERATIONS)
        w = clf.w.astype(np.float64)
        clf.fit(frame.to_features(fcols, "label"))
        train_routes: dict = {}
        for s in clf.metrics.segments:
            for r, k in s.routes.items():
                train_routes[r] = train_routes.get(r, 0) + k
            counters.fallbacks += s.fallbacks
        for r, k in train_routes.items():
            counters.routes[r] = counters.routes.get(r, 0) + k
        log(f"logistic regression: {ITERATIONS} iterations, routes "
            f"{json.dumps(train_routes, sort_keys=True)}")
        m = users["f0"] > -1
        x = np.stack([users[c][m] for c in fcols], 1).astype(np.float64)
        y = users["label"][m].astype(np.float64)
        for _ in range(ITERATIONS):
            p = 1.0 / (1.0 + np.exp(-(x @ w)))
            w = w - 0.5 * (x.T @ (p - y)) / len(y)
        err = float(np.max(np.abs(clf.w - w)) / np.max(np.abs(w)))
        log(f"logistic regression: max |w - w_ref| / max |w_ref| = "
            f"{err:.3e}")
        check(err <= W_TOL, f"logistic regression: weights off by {err:.3e}")
    sess.shutdown()

    radix_fit, chunks_fit = kernel_launches()
    radix, chunks = radix + radix_fit, chunks + chunks_fit
    log(f"routes fired: {json.dumps(counters.routes, sort_keys=True)}, "
        f"radix_partition calls {radix}, double-buffered dispatches "
        f"{chunks}, fallbacks {counters.fallbacks}")
    r = counters.routes
    check(r.get("colscan", 0) + r.get("fused_decode_scan", 0) > 0,
          "neither colscan nor fused_decode_scan ran")
    # the trainer keeps its own PDEConfig, so forced kernels do not reach it
    # in a rehearsal, and rehearsal partitions are below the double-buffer
    # threshold: both are checked on the chip only
    for route in ("groupby_mxu", "segmented_merge") + (
            () if rehearse else ("train_grad",)):
        check(r.get(route, 0) > 0, f"{route} never ran")
    check(radix > 0, "radix_partition never ran")
    check(rehearse or chunks > 0, "the double-buffered path never ran")
    check(counters.fallbacks == 0, f"{counters.fallbacks} fallbacks")


# -- four chips: the mesh-sharded path ----------------------------------------

def mesh_chips(chips: int, seed: int, size: dict, pde_config) -> None:
    import jax
    from repro.cluster import MeshContext
    from repro.core import DType, Schema, SharkSession

    check(len(jax.devices()) >= chips,
          f"--chips {chips} but JAX sees {len(jax.devices())} devices")
    rng = np.random.default_rng(seed)
    kw = dict(num_workers=WORKERS, max_threads=WORKERS,
              default_partitions=size["partitions"], pde_config=pde_config)
    mesh = SharkSession(mesh=MeshContext(max_devices=chips), **kw)
    one = SharkSession(**kw)
    with phase("load"):
        n = size["uservisits"]
        data = {"visitDate": rng.integers(*VISIT_DAYS, n).astype(np.int32),
                "adRevenue": rng.uniform(0, 1000, n),
                "duration": rng.integers(1, 1000, n).astype(np.int32)}
        t = mesh.create_table("uservisits", Schema.of(
            visitDate=DType.DATE, adRevenue=DType.FLOAT64,
            duration=DType.INT32), data)
        one.catalog.register_table(t)
    log(f"table uservisits (mesh columns): {t.num_rows} rows, "
        f"{t.num_partitions} partitions, {t.nbytes} encoded bytes")
    rev, vdate, dur = data["adRevenue"], data["visitDate"], data["duration"]
    m = (vdate >= SCAN_DAYS[0]) & (vdate <= SCAN_DAYS[1])
    cnt = np.bincount(dur, minlength=1000)
    s = np.bincount(dur, weights=rev, minlength=1000)
    k = cnt > 0
    scan = ("SELECT COUNT(*) AS c, SUM(adRevenue) AS rev FROM uservisits "
            f"WHERE visitDate BETWEEN {SCAN_DAYS[0]} AND {SCAN_DAYS[1]}")
    group = ("SELECT duration, SUM(adRevenue) AS rev, COUNT(*) AS c "
             "FROM uservisits GROUP BY duration")
    counters = Counters()
    answers = {}
    with phase("queries"):
        for label, sess in (("mesh", mesh), ("one-device", one)):
            got = run_query(sess, f"{label} range-scan", scan, counters)
            answers[label] = [got]
            if label == "mesh":
                check(sess.metrics().mesh_devices == chips,
                      f"mesh ran on {sess.metrics().mesh_devices} devices")
            check(int(got["c"][0]) == int(m.sum()),
                  f"{label} range-scan: COUNT differs")
            check_groups(f"{label} range-scan",
                         {"k": np.zeros(1), "rev": got["rev"]}, "k",
                         np.zeros(1), {}, {"rev": [rev[m].sum()]})
            got = run_query(sess, f"{label} group-duration", group,
                            counters)
            if label == "mesh":
                check(sess.metrics().mesh_devices == chips,
                      f"mesh ran on {sess.metrics().mesh_devices} devices")
            check_groups(f"{label} group-duration", got, "duration",
                         np.flatnonzero(k), {"c": cnt[k]}, {"rev": s[k]})
            answers[label].append(got)
    (ms, mg), (os_, og) = answers["mesh"], answers["one-device"]
    check(int(ms["c"][0]) == int(os_["c"][0]),
          "range-scan: mesh and one-device COUNT differ")
    check_groups("range-scan mesh vs one-device",
                 {"k": np.zeros(1), "rev": ms["rev"]}, "k", np.zeros(1), {},
                 {"rev": os_["rev"]})
    check_groups("group-duration mesh vs one-device", mg, "duration",
                 np.asarray(og["duration"]), {"c": og["c"]},
                 {"rev": og["rev"]})
    mesh.shutdown()
    one.shutdown()
    log(f"routes fired: {json.dumps(counters.routes, sort_keys=True)}, "
        f"fallbacks {counters.fallbacks}")
    for route in ("mesh-colscan", "mesh-exchange"):
        check(counters.routes.get(route, 0) > 0, f"{route} never ran")
    check(counters.fallbacks == 0, f"{counters.fallbacks} fallbacks")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny run on any platform, kernels in interpret "
                         "mode; always exits 1")
    args = ap.parse_args()

    from repro.compile_cache import enable_compile_cache
    from repro.core.pde import PDEConfig
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        print(f"chip_smoke: no TPU (JAX's default device is "
              f"{dev.platform}); refusing to run", file=sys.stderr)
        return 2
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(jax.devices())}")
    log(f"compile cache: {enable_compile_cache()}")
    size = SIZES_REHEARSAL if args.rehearse else SIZES
    pde_config = None
    if args.rehearse:
        pde_config = PDEConfig(segment_force_kernels=True,
                               reduce_force_compiled=True)
    t0 = time.perf_counter()
    if args.chips == 4:
        mesh_chips(args.chips, args.seed, size, pde_config)
    else:
        one_chip(args.seed, size, args.rehearse, pde_config)
    log(f"total: {time.perf_counter() - t0:.3f} s")
    if args.rehearse:
        print("chip_smoke: rehearsal passed; not a chip run",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
