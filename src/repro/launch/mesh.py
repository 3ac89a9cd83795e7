"""Production mesh construction.

Importing this module never touches jax device state; the mesh is built on
demand (the dry-run sets XLA_FLAGS before any jax import).
"""
from __future__ import annotations

from repro.parallel import compat


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips when multi_pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return compat.make_mesh(shape, axes, auto_axis_types=True)


def make_debug_mesh(shape=(1, 1), axes=("data", "model")):
    """Small mesh over however many (possibly fake) local devices exist."""
    return compat.make_mesh(shape, axes, auto_axis_types=True)
