"""The Pavlo et al. benchmark tables (SIGMOD 2009, sec. 4.3), generated
from a seed and served through a SharkServer.

Distributions as the bring-up smoke test drew them, widths as the source
gives them: `rankings` (pageURL of 17..80 characters, 48.5 on average,
pageRank zipf(1.5) clipped at 10000, avgDuration) with one row per
distinct URL, and `uservisits` with its 9 published columns (2.5M
distinct source IPs, 250 countries, 100 languages, 400 user agents,
10000 search words).  String columns are drawn from vocabularies by index;
the indices stay with the reference, which parses back from a string only
the row number that leads a page URL.
Everything is vectorised: no Python loop runs per row.
"""

from __future__ import annotations

import time

import numpy as np

VISIT_DAYS = (10957, 11688)         # 2000-01-01 .. 2001-12-31, days
LETTERS = "ABCDEFGHIJKLMNOPQRSTUVWXYZ"
URL_FIXED = 17                      # "http://" + nine digits + "/"
URL_TAIL_MAX = 63


def _words(rng, n: int, lo: int, hi: int, alphabet: str) -> np.ndarray:
    """n distinct random words of lo..hi letters, sorted."""
    letters = np.array(list(alphabet))
    while True:
        m = 2 * n + 64
        lens = rng.integers(lo, hi + 1, m)
        chars = letters[rng.integers(0, len(letters), (m, hi))]
        chars[np.arange(hi)[None, :] >= lens[:, None]] = ""
        words = np.ascontiguousarray(chars).view(f"<U{hi}")[:, 0]
        uniq = np.unique(words)
        if len(uniq) >= n:
            return np.sort(rng.choice(uniq, n, replace=False))


def _zipf_clipped(rng, a: float, top: int, n: int) -> np.ndarray:
    """zipf(a) draws clipped at `top`, by inverse CDF: the law of
    `rng.zipf(a, n).clip(0, top)` without its rejection sampling."""
    k = np.arange(1, 1_000_000, dtype=np.float64)
    # zeta(a): the partial sum plus its Euler-Maclaurin tail
    zeta = np.sum(k[::-1] ** -a) + 1e6 ** (1 - a) / (a - 1) + 1e6 ** -a / 2
    cdf = np.cumsum(k[: top - 1] ** -a) / zeta
    return (np.searchsorted(cdf, rng.random(n), side="right") + 1).astype(
        np.int32)


def _page_urls(rng, n: int) -> np.ndarray:
    """n distinct page URLs at the source's width, as ASCII bytes: "http://",
    the row number in nine digits, "/", then 0..63 random lowercase
    letters, so 17..80 characters and 48.5 on average (Rankings holds
    about 1 GB in 18M rows per node, 55.6 B a row as text, of which the
    two integers and the delimiters take about 7).  The row number makes
    each URL distinct and lets the reference find a row from its URL."""
    width = URL_FIXED + URL_TAIL_MAX
    m = np.zeros((n, width), np.uint8)
    m[:, :7] = np.frombuffer(b"http://", np.uint8)
    v = np.arange(n, dtype=np.int64)
    for k in range(15, 6, -1):
        m[:, k] = 48 + v % 10
        v //= 10
    m[:, 16] = ord("/")
    tail = rng.integers(97, 123, (n, URL_TAIL_MAX), dtype=np.uint8)
    lens = rng.integers(0, URL_TAIL_MAX + 1, n)
    tail[np.arange(URL_TAIL_MAX)[None, :] >= lens[:, None]] = 0
    m[:, URL_FIXED:] = tail
    return m.view(f"S{width}")[:, 0]


def gen_rankings(rng, n: int) -> dict:
    """rankings(pageURL, pageRank, avgDuration): one row per distinct URL;
    row i's URL holds i as nine digits after "http://"."""
    return {
        "pageURL": _page_urls(rng, n),
        "pageRank": _zipf_clipped(rng, 1.5, 10000, n),
        "avgDuration": rng.integers(1, 300, n).astype(np.int32),
    }


def gen_uservisits(rng, n: int, page_urls: np.ndarray):
    """uservisits with its 9 published columns, and the vocabulary indices
    its string columns were drawn with."""
    octet = np.arange(256).astype("U3")
    octets = octet[rng.integers(0, 256, (max(1, n // 4), 4))]
    ips = octets[:, 0]
    for j in range(1, 4):
        ips = np.strings.add(np.strings.add(ips, "."), octets[:, j])
    ip_vocab = np.unique(ips)
    agents = np.strings.add("Mozilla/5.0 (compatible; agent",
                            np.strings.add(np.char.zfill(
                                np.arange(400).astype("U3"), 3), ")"))
    l3 = np.array(list(LETTERS))
    codes3 = np.strings.add(np.strings.add(l3[:, None, None],
                                           l3[None, :, None]),
                            l3[None, None, :]).ravel()
    country_vocab = np.sort(rng.choice(codes3, 250, replace=False))
    pairs = np.strings.add(
        np.strings.add(_words(rng, 60, 2, 2, LETTERS.lower())[:, None], "-"),
        _words(rng, 60, 2, 2, LETTERS)[None, :]).ravel()
    lang_vocab = np.sort(rng.choice(pairs, 100, replace=False))
    word_vocab = _words(rng, 10000, 4, 12, LETTERS.lower())
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
    vocab = {"country": country_vocab, "lang": lang_vocab}
    return data, idx, vocab


class Built:
    """The loaded system and what the reference keeps of the data."""

    def __init__(self, server, truth: dict, rows: dict, timings: dict):
        self.server = server
        self.truth = truth
        self.rows = rows            # table -> row count
        self.timings = timings

    def close(self) -> None:
        if self.server is not None:
            self.server.shutdown()
            self.server = None


def build(config: dict, seed: int, rehearsal: bool = False) -> Built:
    from repro.core import DType, Schema
    from repro.core.pde import PDEConfig
    from repro.server import SharkServer

    size = config["rehearsal"] if rehearsal else config
    serve = config["serve"]
    t0 = time.perf_counter()
    rng = np.random.default_rng(seed)
    rk = gen_rankings(rng, size["rankings_rows"])
    uv, idx, vocab = gen_uservisits(rng, size["uservisits_rows"],
                                    rk["pageURL"])
    t1 = time.perf_counter()
    pde = (PDEConfig(segment_force_kernels=True, reduce_force_compiled=True)
           if rehearsal else None)
    workers = size.get("workers", serve["workers"])
    server = SharkServer(
        num_workers=workers, max_threads=workers,
        max_concurrent_queries=serve["max_concurrent_queries"],
        enable_result_cache=serve["result_cache"],
        default_partitions=size.get("partitions", serve["partitions"]),
        pde_config=pde)
    server.create_table("rankings", Schema.of(
        pageURL=DType.STRING, pageRank=DType.INT32,
        avgDuration=DType.INT32), rk)
    server.create_table("uservisits", Schema.of(
        sourceIP=DType.STRING, destURL=DType.STRING,
        visitDate=DType.DATE, adRevenue=DType.FLOAT64,
        userAgent=DType.STRING, countryCode=DType.STRING,
        languageCode=DType.STRING, searchWord=DType.STRING,
        duration=DType.INT32), uv)
    t2 = time.perf_counter()
    truth = {
        "pageURL": rk["pageURL"],
        "pageRank": rk["pageRank"], "avgDuration": rk["avgDuration"],
        "visitDate": uv["visitDate"], "adRevenue": uv["adRevenue"],
        "duration": uv["duration"], "country": idx["country"],
        "lang": idx["lang"], "country_vocab": vocab["country"],
        "lang_vocab": vocab["lang"],
    }
    rows = {"rankings": len(rk["pageRank"]),
            "uservisits": len(uv["visitDate"])}
    return Built(server, truth, rows,
                 {"generate_s": t1 - t0, "encode_s": t2 - t1})
