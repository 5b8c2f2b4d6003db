"""Production mesh builders.

NOTE: importing this module never touches jax device state — meshes are built
by FUNCTIONS so the dry-run can set XLA_FLAGS (512 host devices) before any
jax initialisation.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_debug_mesh(n_devices: int | None = None):
    """Small mesh over whatever devices exist (CPU smoke / examples)."""
    n = n_devices or len(jax.devices())
    return jax.make_mesh((n, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
