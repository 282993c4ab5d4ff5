"""Production mesh definitions.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — the dry-run must set
XLA_FLAGS=--xla_force_host_platform_device_count before first jax init.

Axes (DESIGN.md §5):
  single pod:  (data=16, model=16)            = 256 chips (one v5e pod)
  multi pod:   (pod=2, data=16, model=16)     = 512 chips
"""

from __future__ import annotations

from typing import Optional

import jax
import numpy as np



def _make_mesh(shape, axes, devices):
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices but only {len(devices)} present;"
            " run under XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{n} (launch/dryrun.py does this)")
    return _make_mesh(shape, axes, devices[:n])


def make_debug_mesh(data: int = 2, model: int = 2, pod: Optional[int] = None):
    """Small mesh for CPU tests (device count permitting)."""
    if pod:
        shape, axes = (pod, data, model), ("pod", "data", "model")
    else:
        shape, axes = (data, model), ("data", "model")
    n = int(np.prod(shape))
    return _make_mesh(shape, axes, jax.devices()[:n])
