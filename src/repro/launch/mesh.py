"""Production mesh builders.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so
importing this module never touches jax device state; the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before any jax
import and then calls it.
"""
from __future__ import annotations

from typing import Tuple

import jax


def _mk(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips.
    Multi-pod: (pod=2, data=16, model=16) = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_test_mesh(data: int = 2, model: int = 2, pod: int = 0):
    """Small fake-device mesh for unit tests (subprocess-scoped)."""
    if pod:
        return _mk((pod, data, model), ("pod", "data", "model"))
    return _mk((data, model), ("data", "model"))


def dp_axes(mesh) -> Tuple[str, ...]:
    """Data-parallel axes: everything that is not 'model'."""
    return tuple(a for a in mesh.axis_names if a != "model")


def dp_size(mesh) -> int:
    s = 1
    for a in dp_axes(mesh):
        s *= mesh.shape[a]
    return s


def tp_size(mesh) -> int:
    return mesh.shape["model"]
