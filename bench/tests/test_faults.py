"""A run with the timed path broken underneath must come out not correct.

Each test drives a whole rehearsal run (the harness's look for a chip is
skipped, everything else is as on the chip) with one fault planted in the
program, and reads `correct` from the result."""

import numpy as np
import pytest

from bench import run

SECONDS = 1.0


def _measure(cell):
    result, checks = run.measure(cell, 11, SECONDS, False, rehearsal=True)
    return result, checks


def test_sound_runs_are_correct():
    for cell in ("pavlo-scan", "sqlml-logreg"):
        result, checks = _measure(cell)
        assert result["correct"], (cell, checks)


def answer_altered_query(mp):
    from repro.core.physical import ExecResult
    orig = ExecResult.to_numpy

    def to_numpy(self):
        out = orig(self)
        for k, v in out.items():
            v = np.asarray(v)
            if v.dtype.kind in "iuf" and len(v):
                v = v.copy()
                v[0] += 1
                out[k] = v
                break
        return out
    mp.setattr(ExecResult, "to_numpy", to_numpy)


def half_batch_query(mp):
    from repro.core.rdd import TableScanRDD
    orig = TableScanRDD.__init__

    def init(self, ctx, table, columns=None, selected=None):
        sel = (selected if selected is not None
               else list(range(table.num_partitions)))
        orig(self, ctx, table, columns, sel[::2])
    mp.setattr(TableScanRDD, "__init__", init)


def state_unchanged_train(mp):
    from repro.ml.trainer import IterativeTrainer
    grad = IterativeTrainer.gradient_iteration

    def gradient_iteration(self, w, kind):
        g, n = grad(self, w, kind)
        return np.zeros_like(g), n
    mp.setattr(IterativeTrainer, "gradient_iteration", gradient_iteration)


def half_batch_train(mp):
    from repro.ml.trainer import IterativeTrainer
    orig = IterativeTrainer.run_stage

    def run_stage(self, make_payload):
        return orig(self, make_payload)[::2]
    mp.setattr(IterativeTrainer, "run_stage", run_stage)


def answer_altered_train(mp):
    from repro.ml import LogisticRegression
    lr_fit = LogisticRegression.fit

    def fit_lr(self, *a, **k):
        lr_fit(self, *a, **k)
        self.w = self.w.copy()
        self.w[0] += 1e-2 * np.abs(self.w).max()
        return self
    mp.setattr(LogisticRegression, "fit", fit_lr)


@pytest.mark.parametrize("cell,fault", [
    ("pavlo-scan", answer_altered_query),
    ("pavlo-scan", half_batch_query),
    ("sqlml-logreg", state_unchanged_train),
    ("sqlml-logreg", half_batch_train),
    ("sqlml-logreg", answer_altered_train),
], ids=lambda v: v if isinstance(v, str) else v.__name__)
def test_fault_makes_the_run_not_correct(cell, fault, monkeypatch):
    fault(monkeypatch)
    result, checks = _measure(cell)
    assert not result["correct"], checks
