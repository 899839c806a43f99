"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state. Single pod: 16x16 = 256 chips (v5e pod);
multi-pod: 2 pods = 512 chips with a leading "pod" axis (DCN-connected).
"""
from __future__ import annotations

import jax


def _make_mesh(shape, axes) -> jax.sharding.Mesh:
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(model: int = 1) -> jax.sharding.Mesh:
    """Tiny mesh over the actually-present local devices (CPU tests)."""
    n = len(jax.devices())
    model = min(model, n)
    return _make_mesh((n // model, model), ("data", "model"))
