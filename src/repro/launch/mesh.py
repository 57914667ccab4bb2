"""Production mesh construction.

Functions, not module-level constants, so importing this module never
touches jax device state (jax locks the device count on first init).

Every axis is ``Auto``: the model code places arrays with sharding hints
and lets GSPMD propagate the rest.  ``jax.make_mesh`` defaults to
``Explicit`` axes, under which an unannotated gather such as
``params["embed"][tokens]`` is a trace-time ``ShardingTypeError``.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh():
    """Whatever devices exist locally (tests/smoke): (N, 1) data x model."""
    n = len(jax.devices())
    return _auto_mesh((n, 1), ("data", "model"))


def data_axes(mesh) -> tuple:
    """Axes that shard the batch dimension."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)
