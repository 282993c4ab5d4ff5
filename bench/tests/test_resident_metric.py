"""`resident_pct.train` on a synthetic recorder: the share of the window's
`shark.train.partition` spans whose kernel inputs were already on the
device, window clipping, and no reading from an empty window, from a
window in which records were dropped, from partition spans that do not
say where their inputs came from, or from a program without the
recorder.  The recorder and the window come from test_program_metrics."""

import sys

import pytest

from test_program_metrics import S, _read, _run, recorder  # noqa: F401

METRIC = "resident_pct.train"


def _fit(r):
    # one fill and one hit in the window; the hit after it is left out
    for k, (a, b) in enumerate([(105.0, 106.0), (106.0, 107.0),
                                (109.8, 110.3)]):
        it = r.add("train.iteration", a, b, iteration=k)
        r.add("train.partition", a, b, it, route="train_grad",
              resident="fill" if k == 0 else "hit")
    return _run()


def test_resident_share_of_a_synthetic_fit(recorder):  # noqa: F811
    assert _read(METRIC, _fit(recorder)) == pytest.approx(50.0)


def test_resident_share_of_partition_spans(recorder):  # noqa: F811
    for resident in ("fill", "hit", "hit", "none"):
        recorder.add("train.partition", 101.0, 102.0, route="train_grad",
                     resident=resident)
    assert _read(METRIC, _run()) == pytest.approx(50.0)


def test_partition_spans_without_resident_read_none(recorder):  # noqa: F811
    """A program whose partition spans do not say where their inputs came
    from reports no share, rather than 0."""
    recorder.add("train.partition", 101.0, 102.0, route="train_grad")
    assert _read(METRIC, _run()) is None


def test_resident_share_reads_none_without_a_whole_window(
        recorder, monkeypatch):  # noqa: F811
    recorder.add("train.partition", 90.0, 91.0, resident="hit")
    assert _read(METRIC, _run()) is None
    record = _fit(recorder)
    recorder.stats.update(dropped=3, first_drop_ns=int(104 * S),
                          last_drop_ns=int(104.5 * S))
    assert _read(METRIC, record) is None
    import repro.core
    monkeypatch.delattr(repro.core, "tracing")
    monkeypatch.setitem(sys.modules, "repro.core.tracing", None)
    assert _read(METRIC, _run()) is None
