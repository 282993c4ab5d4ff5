"""Plain reference for the Pavlo scan queries, from the generator's
indices and numeric columns, in float64 numpy.

Counts, integer aggregates, group keys and row sets must equal the
reference exactly.  Float aggregates (SUM(adRevenue), AVG(duration)) are
compared by their relative gap, since the configuration states float32
accumulation on the chip.  Each query kind (`check` in a traffic mix)
names a function of this module.
"""

from __future__ import annotations

import numpy as np

from bench.reference.lowp import bf16_group_sums, bf16_sum

# worst relative gap of a float aggregate from the float64 reference.
# Readings it was set from: see PERF.md, section 2.
FLOAT_REL_GAP_LIMIT = 1e-4


class Reference:
    """Precomputed float64 tables over the generator's arrays; each query
    is then answered in microseconds to milliseconds."""

    def __init__(self, truth: dict):
        self.t = truth
        days = truth["visitDate"].astype(np.int64)
        self.day0 = int(days.min())
        d = days - self.day0
        nd = int(d.max()) + 1
        self.day_cnt = np.bincount(d, minlength=nd)
        self.day_rev = np.bincount(d, weights=truth["adRevenue"],
                                   minlength=nd)
        ad = truth["avgDuration"].astype(np.int64)
        pr = truth["pageRank"].astype(np.int64)
        self.ad_cnt = np.bincount(ad, minlength=1001)
        self.ad_sum = np.bincount(ad, weights=pr, minlength=1001)
        self.ad_max = np.zeros(len(self.ad_cnt), np.int64)
        np.maximum.at(self.ad_max, ad, pr)
        nc = len(truth["country_vocab"])
        self.c_cnt = np.bincount(truth["country"], minlength=nc)
        self.c_rev = np.bincount(truth["country"], weights=truth["adRevenue"],
                                 minlength=nc)
        nl = len(truth["lang_vocab"])
        key = truth["lang"].astype(np.int64) * 1000 + truth["duration"]
        self.ld_cnt = np.bincount(key, minlength=nl * 1000).reshape(nl, 1000)
        self.ld_sum = np.bincount(key, weights=truth["duration"],
                                  minlength=nl * 1000).reshape(nl, 1000)

    # -- expected answers: (exact parts, float parts) ------------------------

    def selection(self, p):
        ids = np.flatnonzero(self.t["pageRank"] > p["x"])
        return {"ids": ids, "pageRank": self.t["pageRank"][ids]}, {}

    def range_agg(self, p):
        lo, hi = p["d0"] - self.day0, p["d1"] - self.day0 + 1
        return ({"c": int(self.day_cnt[lo:hi].sum())},
                {"rev": np.array([self.day_rev[lo:hi].sum()])})

    def int_agg(self, p):
        sl = slice(p["a"], p["b"] + 1)
        return ({"s": int(round(self.ad_sum[sl].sum())),
                 "mx": int(self.ad_max[sl].max()),
                 "c": int(self.ad_cnt[sl].sum())}, {})

    def group_country(self, p):
        k = self.c_cnt > 0
        return ({"keys": self.t["country_vocab"][k], "c": self.c_cnt[k]},
                {"rev": self.c_rev[k]})

    def group_language(self, p):
        sl = slice(p["a"], p["b"] + 1)
        cnt = self.ld_cnt[:, sl].sum(1)
        s = self.ld_sum[:, sl].sum(1)
        k = cnt > 0
        return ({"keys": self.t["lang_vocab"][k], "c": cnt[k]},
                {"d": s[k] / cnt[k]})

    # -- the control: the same answers computed in bfloat16 -----------------

    def control(self, check: str, p):
        """The answer with every float aggregate summed in bfloat16, in the
        layout the program returns."""
        t = self.t
        if check == "range_agg":
            m = (t["visitDate"] >= p["d0"]) & (t["visitDate"] <= p["d1"])
            return {"c": np.array([m.sum()]),
                    "rev": np.array([bf16_sum(t["adRevenue"][m])])}
        if check == "group_country":
            nc = len(t["country_vocab"])
            rev = bf16_group_sums(t["country"], t["adRevenue"], nc)
            k = self.c_cnt > 0
            return {"countryCode": t["country_vocab"][k], "c": self.c_cnt[k],
                    "rev": rev[k]}
        if check == "group_language":
            m = (t["duration"] >= p["a"]) & (t["duration"] <= p["b"])
            nl = len(t["lang_vocab"])
            cnt = np.bincount(t["lang"][m], minlength=nl)
            s = bf16_group_sums(t["lang"][m], t["duration"][m], nl)
            k = cnt > 0
            return {"languageCode": t["lang_vocab"][k], "c": cnt[k],
                    "d": s[k] / cnt[k]}
        return None         # no float aggregate: the control changes nothing

    # -- comparison ----------------------------------------------------------

    def compare(self, check: str, p, got: dict):
        """(exact parts equal, worst relative gap of the float parts)."""
        exact, approx = getattr(self, check)(p)
        if check == "selection":
            urls = np.asarray(got["pageURL"]).astype(str)
            # "http://" and the row number in nine digits lead every URL
            ids = np.strings.replace(urls.astype("U16"), "http://",
                                     "").astype(np.int64)
            o = np.argsort(ids, kind="stable")
            ok = (np.array_equal(ids[o], exact["ids"]) and np.array_equal(
                np.asarray(got["pageRank"], np.int64)[o], exact["pageRank"])
                and np.array_equal(urls[o], self.t["pageURL"][
                    exact["ids"]].astype(str)))
            return ok, 0.0
        if "keys" in exact:
            key = next(k for k in got if k not in ("c", "rev", "d"))
            keys = np.asarray(got[key]).astype(str)
            o = np.argsort(keys, kind="stable")
            eo = np.argsort(exact["keys"], kind="stable")
            ok = (len(keys) == len(exact["keys"])
                  and np.array_equal(keys[o], exact["keys"][eo])
                  and np.array_equal(np.asarray(got["c"], np.int64)[o],
                                     np.asarray(exact["c"], np.int64)[eo]))
            gap = 0.0
            if ok:
                for col, want in approx.items():
                    gap = max(gap, _rel_gap(np.asarray(got[col])[o],
                                            want[eo]))
            return ok, gap
        ok = all(len(np.asarray(got[k])) == 1
                 and int(np.asarray(got[k])[0]) == v
                 for k, v in exact.items())
        gap = 0.0
        for col, want in approx.items():
            gap = max(gap, _rel_gap(np.asarray(got[col]), want))
        return ok, gap


def _rel_gap(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape:
        return float("inf")
    if len(want) == 0:
        return 0.0
    return float(np.max(np.abs(got - want) / np.maximum(np.abs(want),
                                                        1e-300)))


def check(truth: dict, answers, control: bool = False) -> dict:
    """The compared numbers of a run, each with its limit.  `answers` are
    (check, params, answer or None) for every query due in the window;
    with `control` the bfloat16 control's answer stands in for the
    program's wherever the query has a float aggregate."""
    ref = Reference(truth)
    unanswered = wrong = 0
    gap = 0.0
    for kind, p, got in answers:
        if got is None:
            unanswered += 1
            continue
        if control:
            got = ref.control(kind, p) or got
        ok, g = ref.compare(kind, p, got)
        wrong += int(not ok)
        gap = max(gap, g)
    return {"unanswered": {"value": unanswered, "limit": 0},
            "wrong_exact": {"value": wrong, "limit": 0},
            "float_rel_gap": {"value": gap, "limit": FLOAT_REL_GAP_LIMIT}}
