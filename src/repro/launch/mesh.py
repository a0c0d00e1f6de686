"""Production mesh definition.

A function (not a module-level constant) so importing this module never
touches jax device state — required because the dry-run forces 512 host
devices while tests/benches must see 1.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; multi_pod adds the 2-pod axis (512)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh_from_shape(shape, axes)


def make_mesh_from_shape(shape: tuple, axes: tuple):
    """Arbitrary mesh (elastic restarts: e.g. (1, 16, 16) after pod loss)."""
    return jax.make_mesh(
        tuple(shape), tuple(axes),
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
    )
