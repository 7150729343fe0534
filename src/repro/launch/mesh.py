"""Mesh construction for single-pod and multi-pod deployments.

All constructors are FUNCTIONS (no module-level device access) so importing
this module never locks the jax device count — required for the dry-run's
``xla_force_host_platform_device_count`` dance.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mk(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """v5e production mesh: 16x16 per pod (256 chips), 2 pods multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_test_mesh(data: int = 2, model: int = 4, pod: int = 0):
    """Small mesh for host-device unit tests (requires the XLA flag)."""
    if pod:
        return _mk((pod, data, model), ("pod", "data", "model"))
    return _mk((data, model), ("data", "model"))


def make_single_device_mesh():
    return _mk((1, 1), ("data", "model"))
