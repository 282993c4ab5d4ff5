"""Arithmetic in bfloat16, for the controls: the nearest precision below
the float32 that the configurations state."""

from __future__ import annotations

import ml_dtypes
import numpy as np

BF16 = ml_dtypes.bfloat16


def bf16_sum(x) -> float:
    """Pairwise (tree) sum with every operand and every partial sum
    rounded to bfloat16."""
    v = np.asarray(x, np.float64).astype(BF16)
    if len(v) == 0:
        return 0.0
    while len(v) > 1:
        if len(v) % 2:
            v = np.concatenate([v, np.zeros(1, BF16)])
        v = v[0::2] + v[1::2]
    return float(v[0])


def bf16_group_sums(groups: np.ndarray, values: np.ndarray,
                    num_groups: int) -> np.ndarray:
    """Per-group bf16_sum of `values`, grouped by integer `groups`."""
    order = np.argsort(groups, kind="stable")
    g, v = groups[order], values[order]
    bounds = np.searchsorted(g, np.arange(num_groups + 1))
    return np.array([bf16_sum(v[bounds[i]:bounds[i + 1]])
                     for i in range(num_groups)])
