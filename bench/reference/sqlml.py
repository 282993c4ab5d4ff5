"""Plain reference for the SQL->ML workflow: the selection `f0 > t` and
the same iterations of logistic regression (full-batch gradient descent)
from the same initial weights, in float64 numpy over the generator's
arrays.

The selected row count must equal the reference's in every iteration.
The model after the last iteration is compared by its relative gap,
||model - reference|| / ||reference||, since the configuration states
float32 on the chip.
"""

from __future__ import annotations

import numpy as np

from bench.common import log

# worst relative gap of a fitted model from the float64 reference.
# Readings it was set from: see PERF.md, section 2.
MODEL_GAP_LIMIT = 1e-4


def _sigmoid(z):
    return 0.5 * (1.0 + np.tanh(0.5 * z))


def logreg(x, y, w0, lr: float, iterations: int):
    w = np.asarray(w0, np.float64)
    n = len(y)
    for _ in range(iterations):
        p = _sigmoid(x @ w)
        w = w - lr * (x.T @ (p - y)) / n
    return w


def bf16_logreg(x, y, w0, lr: float, iterations: int):
    """The control: the same iterations with features, weights and every
    product rounded to bfloat16 (jax.numpy on the default device)."""
    import jax.numpy as jnp
    bf = jnp.bfloat16
    xb, yb = jnp.asarray(x, bf), jnp.asarray(y, bf)
    w = jnp.asarray(w0, bf)
    n = len(y)
    for _ in range(iterations):
        p = 1.0 / (1.0 + jnp.exp(-(xb @ w)))
        w = (w - bf(lr) * (xb.T @ (p - yb)) / bf(n)).astype(bf)
    return np.asarray(w, np.float64)


def reference_model(truth: dict, wf: dict, control: bool = False):
    x, y = truth["x"], truth["y"]
    m = x[:, 0] > np.float32(wf["t"])
    xs, ys = x[m].astype(np.float64), y[m].astype(np.float64)
    fit = bf16_logreg if control else logreg
    return int(m.sum()), fit(xs, ys, wf["init"], wf["lr"], wf["iterations"])


def model_gap(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def check(truth: dict, answers, control: bool = False) -> dict:
    """`answers`: one dict per workflow due in the window, with its
    threshold, initial and final weights and the rows each iteration read
    (model None for a workflow that raised).  With `control` the bfloat16
    control's weights stand in for the program's."""
    unanswered = rows_off = 0
    gap = 0.0
    for wf in answers:
        if wf.get("model") is None:
            unanswered += 1
            continue
        rows, want = reference_model(truth, wf)
        got = wf["model"]
        if control:
            got = reference_model(truth, wf, control=True)[1]
        rows_off += sum(abs(r - rows) for r in wf["rows"])
        rows_off += abs(len(wf["rows"]) - wf["iterations"]) * rows
        g = model_gap(got, want)
        log(f"workflow t={wf['t']}: {rows} rows, model gap {g!r}")
        gap = max(gap, g)
    return {"unanswered": {"value": unanswered, "limit": 0},
            "rows_off": {"value": rows_off, "limit": 0},
            "model_gap": {"value": gap, "limit": MODEL_GAP_LIMIT}}
