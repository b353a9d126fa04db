"""Production mesh builders.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state — the dry-run must set
``XLA_FLAGS`` before the first jax initialization.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2x16x16 = 512 chips across two pods."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(model_axis: int = 1):
    """Tiny mesh over whatever devices the current process actually has
    (CPU smoke tests / single-host debugging)."""
    n = len(jax.devices())
    assert n % model_axis == 0
    return jax.make_mesh(
        (n // model_axis, model_axis),
        ("data", "model"),
        axis_types=(AxisType.Auto,) * 2,
    )


def mesh_chips(mesh) -> int:
    n = 1
    for s in mesh.shape.values():
        n *= s
    return n
