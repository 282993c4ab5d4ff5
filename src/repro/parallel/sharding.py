"""Sharding helpers: mesh-aware activation constraints.

Parameters carry explicit PartitionSpecs built at init time (see
models/*.py); activations get constraints through `act_shard`, which filters
the requested axes down to those that exist in the *current* mesh — the same
model code runs unsharded on 1 CPU device, on a (data, model) pod, or on a
(pod, data, model) multi-pod mesh.

Axis convention (DESIGN.md §5):
  pod    — across pods (pure data parallel, gradient all-reduce hierarchy)
  data   — within-pod data parallel + ZeRO-1 optimizer sharding
  model  — tensor/expert parallel (heads, d_ff, vocab, experts)
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple, Union

import jax
from jax.sharding import PartitionSpec as P


BATCH_AXES: Tuple[str, ...] = ("pod", "data")
MODEL_AXIS = "model"


def current_mesh_axes() -> Tuple[str, ...]:
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return ()
    return tuple(mesh.axis_names)


def filter_spec(spec: P, axes: Sequence[str]) -> P:
    """Drop mesh axes not present in `axes` from a PartitionSpec."""
    parts = []
    for entry in spec:
        if entry is None:
            parts.append(None)
        elif isinstance(entry, (tuple, list)):
            kept = tuple(a for a in entry if a in axes)
            parts.append(kept if kept else None)
        else:
            parts.append(entry if entry in axes else None)
    return P(*parts)


def maybe_shard(x: jax.Array, spec: P) -> jax.Array:
    axes = current_mesh_axes()
    if not axes:
        return x
    return jax.lax.with_sharding_constraint(x, filter_spec(spec, axes))


def batch_spec(*rest) -> P:
    """PartitionSpec with the batch dim over all data-parallel axes."""
    return P(BATCH_AXES, *rest)


def act_shard(x: jax.Array, kind: str) -> jax.Array:
    """Named activation-sharding policies (referenced in EXPERIMENTS.md)."""
    if kind == "hidden":          # (B, S, D)
        return maybe_shard(x, P(BATCH_AXES, None, None))
    if kind == "hidden_seq":      # (B, S, D), sequence-parallel residual
        return maybe_shard(x, P(BATCH_AXES, MODEL_AXIS, None))
    if kind == "hidden_tp":       # (B, S, D) with D sharded (seq-parallel
        return maybe_shard(x, P(BATCH_AXES, None, MODEL_AXIS))
    if kind == "heads":           # (B, S, H, hd)
        return maybe_shard(x, P(BATCH_AXES, None, MODEL_AXIS, None))
    if kind == "ffn":             # (B, S, F)
        return maybe_shard(x, P(BATCH_AXES, None, MODEL_AXIS))
    if kind == "logits":          # (B, S, V)
        return maybe_shard(x, P(BATCH_AXES, None, MODEL_AXIS))
    if kind == "experts":         # (E, C, D)
        return maybe_shard(x, P(MODEL_AXIS, None, None))
    if kind == "seq":             # sequence sharding (long-context decode)
        return maybe_shard(x, P(BATCH_AXES, MODEL_AXIS, None))
    raise ValueError(kind)
