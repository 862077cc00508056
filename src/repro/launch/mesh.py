"""Production mesh construction (brief: 16×16 single-pod, 2×16×16 multi-pod).

``make_production_mesh`` is a FUNCTION (never a module constant) so importing
this module touches no jax device state. The dry-run launcher sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import; tests use ``make_test_mesh`` with whatever devices exist.
"""

from __future__ import annotations

from typing import Tuple

import jax
from jax.sharding import AxisType


def _mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """TPU v5e production mesh: (data=16, model=16); multi-pod adds pod=2."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_test_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """Small CPU mesh for tests (requires forced host device count)."""
    return _mesh(shape, axes)


def mesh_chip_count(mesh) -> int:
    n = 1
    for s in mesh.devices.shape:
        n *= s
    return n
