"""Production mesh construction.

IMPORTANT: functions only — importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before any jax initialization).
"""
from __future__ import annotations

import numpy as np

import jax
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16x16 = 256 chips per pod (TPU v5e pod slice); 2 pods = 512 chips.

    Axes: ("data", "model") single-pod; ("pod", "data", "model") multi-pod.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes) -> Mesh:
    """Mesh over the first prod(shape) devices, every axis `Auto`: the
    models place arrays with sharding constraints and leave propagation
    to the compiler (jax.make_mesh would default to `Explicit` axes)."""
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices, have {len(devices)} — the dry-run must set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=512 before "
            "importing jax")
    axis_types = (AxisType.Auto,) * len(axes)
    if len(devices) == n:
        # all devices: let jax order them along the physical topology
        return jax.make_mesh(shape, axes, axis_types=axis_types)
    return Mesh(np.asarray(devices[:n]).reshape(shape), axes,
                axis_types=axis_types)
