"""Production mesh definitions.

A FUNCTION, not a module-level constant: importing this module never touches
jax device state, so tests and benches keep their single CPU device.
"""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; multi_pod adds the 2-pod axis (512)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_local_mesh(n_devices: int | None = None, model: int = 1):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = n_devices or len(jax.devices())
    data = n // model
    return jax.make_mesh(
        (data, model), ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2,
    )
