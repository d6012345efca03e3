"""Production meshes. A FUNCTION (not module-level state) so importing never
touches jax device initialization."""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = one v5e pod (256 chips); 2x16x16 = two pods (512 chips).

    Axes: ("data", "model") single-pod, ("pod", "data", "model") multi-pod —
    DP spans pod x data, TP stays within a pod (ICI), the pod axis crosses
    DCI. The dry-run (launch/dryrun.py) must set
    XLA_FLAGS=--xla_force_host_platform_device_count=512 BEFORE importing jax.
    """
    if multi_pod:
        shape, axes = (2, 16, 16), ("pod", "data", "model")
    else:
        shape, axes = (16, 16), ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_debug_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for fake-device tests (device count must already allow it)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))
