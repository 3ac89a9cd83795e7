"""Jit'd public wrappers around the fast-tier classify+reduce kernel.

Handles row padding to tile multiples (pad rows are all-zero blocks whose
stats are cropped before the coder sees them) and the host-array boundary
for core/fastmode.py's device path.  ``interpret=None`` resolves by backend
in :mod:`repro.kernels.routing`.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import routing
from . import kernel as _k
from . import ref as _ref


@functools.partial(jax.jit, static_argnames=("bm", "interpret"))
def _stats_padded(x: jnp.ndarray, *, bm: int, interpret: bool):
    means, devs = _k.block_stats(x, bm=bm, interpret=interpret)
    return means[:, 0], devs[:, 0]


def block_stats(
    x: np.ndarray, *, interpret: Optional[bool] = None
) -> Tuple[np.ndarray, np.ndarray]:
    """Per-block (mean, max |x - mean|) for a host (nb, bs) float32 array."""
    interpret = routing.interpret_mode(interpret)
    x = np.asarray(x, np.float32)
    nb = x.shape[0]
    bm = 256 if nb >= 256 else 8
    pad = (-nb) % bm
    xj = jnp.asarray(np.pad(x, ((0, pad), (0, 0))) if pad else x)
    means, devs = _stats_padded(xj, bm=bm, interpret=interpret)
    return np.asarray(means)[:nb], np.asarray(devs)[:nb]


def ref_block_stats(x) -> Tuple[np.ndarray, np.ndarray]:
    means, devs = _ref.block_stats(jnp.asarray(x, jnp.float32))
    return np.asarray(means), np.asarray(devs)
