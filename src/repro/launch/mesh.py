"""Mesh construction for the launchers.

Importing this module never touches jax device state; the mesh is built on
demand.
"""
from __future__ import annotations

from repro.parallel import compat


def make_debug_mesh(shape=(1, 1), axes=("data", "model")):
    """Small mesh over however many (possibly fake) local devices exist."""
    return compat.make_mesh(shape, axes, auto_axis_types=True)
