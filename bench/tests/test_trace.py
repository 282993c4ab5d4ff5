"""bench/trace.py on a synthetic trace whose numbers are worked out by
hand."""

import pytest

from bench.trace import Events, merge, reduce

MS = 1_000_000      # nanoseconds


def test_merge_joins_overlaps_and_sorts():
    assert merge([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]


def events():
    # window 0..100 ms; ops at 10..20 and 15..30 (overlap), 50..60, and one
    # op straddling the window's end, 95..110; one op before the window
    ops = [(-20 * MS, -10 * MS, "early"), (10 * MS, 20 * MS, "fusion"),
           (15 * MS, 30 * MS, "colscan"), (50 * MS, 60 * MS, "fusion"),
           (95 * MS, 110 * MS, "copy")]
    spans = [(0, 100 * MS, "bench.window"),
             (0, 40 * MS, "bench.query.selection"),
             (35 * MS, 100 * MS, "bench.query.range_agg"),
             (60 * MS, 90 * MS, "bench.query.int_agg")]
    return Events({"/device:TPU:0": ops}, spans)


def test_reduce_busy_idle_ops_and_gaps():
    r = reduce(events())
    # busy: 10..30 + 50..60 + 95..100 = 35 ms of a 100 ms window
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busy_s"] == pytest.approx(0.035)
    assert r["idle_share"] == pytest.approx(0.65)
    ops = dict(r["device_ops"])
    assert ops == pytest.approx({"fusion": 0.02, "colscan": 0.015,
                                 "copy": 0.005})
    # gaps: 0..10 (selection), 30..50 (range_agg covers 15 ms of it,
    # selection 10 ms), 60..95 (range_agg 35 ms, int_agg only 30 ms)
    assert r["idle_gaps"] == [["query.range_agg", pytest.approx(0.035)],
                              ["query.range_agg", pytest.approx(0.02)],
                              ["query.selection", pytest.approx(0.01)]]


def test_reduce_averages_over_devices_and_names_uncovered_gaps_idle():
    ev = events()
    ev.device["/device:TPU:1"] = [(0, 100 * MS, "all")]
    ev.spans = [s for s in ev.spans if s[2] == "bench.window"]
    r = reduce(ev)
    assert r["devices"] == 2
    assert r["busy_s"] == pytest.approx((0.035 + 0.1) / 2)
    assert {name for name, _ in r["idle_gaps"]} == {"idle"}


def test_reduce_without_device_ops_reads_nothing():
    ev = events()
    ev.device = {}
    assert reduce(ev) is None
