"""Jit'd wrappers for the bitplane transpose kernel (padding + flat API;
``interpret=None`` resolves by backend in :mod:`repro.kernels.routing`)."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import routing
from . import kernel as _k
from . import ref as _ref


@functools.partial(jax.jit, static_argnames=("interpret",))
def bitplane_encode(vals: jnp.ndarray, *, interpret: Optional[bool] = None) -> jnp.ndarray:
    """Flat uint32 values -> (32, ceil(n/32)) plane words (plane p = row p)."""
    n = vals.shape[0]
    # empty input still pads to one tile: the kernel grid needs >= 1 step
    # (decode crops back to n values, so the zero words are never observed)
    pad = (-n) % (32 * 512) or (32 * 512 if n == 0 else 0)
    v = jnp.pad(vals.astype(jnp.uint32), (0, pad)).reshape(-1, 32)
    return _k.encode(v, interpret=routing.interpret_mode(interpret))


@functools.partial(jax.jit, static_argnames=("n", "interpret"))
def bitplane_decode(
    words: jnp.ndarray, n: int, *, interpret: Optional[bool] = None
) -> jnp.ndarray:
    v = _k.decode(words, interpret=routing.interpret_mode(interpret)).reshape(-1)
    return v[:n]


def ref_encode(vals):
    n = vals.shape[0]
    pad = (-n) % 32
    v = jnp.pad(jnp.asarray(vals, jnp.uint32), (0, pad)).reshape(-1, 32)
    return _ref.encode(v)


def ref_decode(words, n):
    return _ref.decode(jnp.asarray(words, jnp.uint32)).reshape(-1)[:n]
