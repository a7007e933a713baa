"""Production mesh construction.

Axes: ``pod`` (DCN-connected pod/data-center — Sector's wide-area dimension),
``data`` (batch parallel), ``model`` (tensor/expert parallel). Functions, not
module constants, so importing never touches jax device state.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over the locally available (possibly virtual) devices."""
    n = len(jax.devices())
    if data * model > n:
        raise ValueError(f"need {data * model} devices, have {n}")
    return _auto_mesh((data, model), ("data", "model"))


def dp_axes_of(mesh) -> tuple:
    return tuple(a for a in ("pod", "data") if a in mesh.shape)
