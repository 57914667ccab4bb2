"""Activation-sharding hints.

GSPMD's propagation into lax.scan bodies is weak: without explicit
constraints the per-layer activations (and especially attention scores)
get replicated.  ``hint(x, *axes)`` applies with_sharding_constraint with
logical axis names, resolved against the mesh set with ``jax.set_mesh``
— and is a no-op when there is no mesh (single-device smoke tests)
or when a dim is not divisible by its axis size.

Logical names:  "batch" -> ("pod","data") subset present in the mesh;
"model" -> "model"; None -> unsharded.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import PartitionSpec as P


def _current_mesh():
    m = jax.sharding.get_abstract_mesh()
    return None if m.empty else m


def _resolve(axis: Optional[str], mesh) -> Optional[Tuple[str, ...]]:
    if axis is None:
        return None
    if axis == "batch":
        names = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        return names or None
    if axis in mesh.axis_names:
        return (axis,)
    return None


def hint_any(x: jax.Array, specs) -> jax.Array:
    """Apply the first spec whose named dims all divide (priority list).

    e.g. attention scores prefer head-sharding but fall back to
    sequence-sharding when the arch's kv-head count doesn't divide the
    model axis (GQA with 2 kv heads on a 16-way axis).
    """
    mesh = _current_mesh()
    if mesh is None:
        return x
    for spec in specs:
        if len(spec) != x.ndim:
            continue
        ok = True
        for dim, ax in zip(x.shape, spec):
            names = _resolve(ax, mesh)
            if ax is not None and names is not None:
                size = int(np.prod([mesh.shape[n] for n in names]))
                if size > 1 and dim % size != 0:
                    ok = False
                    break
            if ax is not None and names is None:
                ok = False
                break
        if ok:
            return hint(x, *spec)
    return x


def hint(x: jax.Array, *axes: Optional[str]) -> jax.Array:
    """Constrain x's sharding; silently no-op when impossible."""
    mesh = _current_mesh()
    if mesh is None or len(axes) != x.ndim:
        return x
    spec = []
    for dim, ax in zip(x.shape, axes):
        names = _resolve(ax, mesh)
        if names is None:
            spec.append(None)
            continue
        size = int(np.prod([mesh.shape[n] for n in names]))
        if size > 1 and dim % size == 0:
            spec.append(names if len(names) > 1 else names[0])
        else:
            spec.append(None)
    return jax.lax.with_sharding_constraint(x, P(*spec))
