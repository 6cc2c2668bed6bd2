"""Mesh-aware sharding helpers.

``constrain`` applies ``with_sharding_constraint`` only when a mesh context
carrying the referenced axes is active, so the same model code runs on a
single CPU device (smoke tests), under ``jax.set_mesh`` (dry-run/production),
and inside ``jax.eval_shape``.
"""

from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as P


def _flatten_axes(spec: P):
    for part in spec:
        if part is None:
            continue
        if isinstance(part, (tuple, list)):
            yield from part
        else:
            yield part


def active_axes() -> tuple:
    mesh = jax.sharding.get_abstract_mesh()
    return tuple(mesh.axis_names) if not mesh.empty else ()


def constrain(x, spec: P):
    axes = set(active_axes())
    if not axes:
        return x
    if not set(_flatten_axes(spec)) <= axes:
        # drop the axes the current mesh does not have
        spec = P(
            *(
                tuple(a for a in part if a in axes) or None
                if isinstance(part, (tuple, list))
                else (part if part in axes else None)
                for part in spec
            )
        )
    return jax.lax.with_sharding_constraint(x, spec)
