"""Jit'd public wrappers around the blockwise-transform kernels.

Handles padding to tile multiples (zero padding is crop-safe: tiles and
4-blocks nest, so padding only appends whole independent blocks) and the host
array boundary for the transform coder (core/transform.py device path).
``interpret=None`` resolves by backend in :mod:`repro.kernels.routing`.
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

AMP_1AXIS = _ref.AMP_1AXIS


def _pad2d(x: jnp.ndarray, bm: int, bn: int) -> Tuple[jnp.ndarray, Tuple[int, int]]:
    R, C = x.shape
    pr, pc = (-R) % bm, (-C) % bn
    if pr or pc:
        x = jnp.pad(x, ((0, pr), (0, pc)))
    return x, (R, C)


def _tiles(shape: Tuple[int, int]) -> Tuple[int, int]:
    bm = 256 if shape[0] >= 256 else max(8, 8 * (shape[0] // 8) or 8)
    bn = 512 if shape[1] >= 512 else 128
    return bm, bn


@functools.partial(jax.jit, static_argnames=("mode", "interpret"))
def transform_fwd(
    x: jnp.ndarray, *, mode: str = "2d", interpret: Optional[bool] = None
) -> jnp.ndarray:
    """(R, C) float32, transformed axes multiples of 4 -> coefficient grid."""
    assert x.ndim == 2
    bm, bn = _tiles(x.shape)
    xp, (R, C) = _pad2d(x, bm, bn)
    interpret = routing.interpret_mode(interpret)
    out = _k.fwd(xp, mode=mode, bm=bm, bn=bn, interpret=interpret)
    return out[:R, :C]


@functools.partial(jax.jit, static_argnames=("mode", "interpret"))
def transform_inv(
    c: jnp.ndarray, *, mode: str = "2d", interpret: Optional[bool] = None
) -> jnp.ndarray:
    assert c.ndim == 2
    bm, bn = _tiles(c.shape)
    cp, (R, C) = _pad2d(c, bm, bn)
    interpret = routing.interpret_mode(interpret)
    out = _k.inv(cp, mode=mode, bm=bm, bn=bn, interpret=interpret)
    return out[:R, :C]


def fwd_pipeline(x: np.ndarray, *, interpret: Optional[bool] = None) -> np.ndarray:
    """Forward transform for the REAL coder (host arrays, 1-D or 2-D).

    Shapes must already be padded to multiples of 4 along the transformed
    axes (core/transform.py owns the edge padding policy — zero padding here
    would leak into real blocks' coefficients, tile padding cannot)."""
    x2d = jnp.asarray(x if x.ndim == 2 else x.reshape(1, -1), jnp.float32)
    mode = "2d" if x.ndim == 2 else "1d"
    out = transform_fwd(x2d, mode=mode, interpret=interpret)
    return np.asarray(out).reshape(x.shape)


def inv_pipeline(c: np.ndarray, *, interpret: Optional[bool] = None) -> np.ndarray:
    """Inverse transform for the REAL coder (host arrays, 1-D or 2-D)."""
    c2d = jnp.asarray(c if c.ndim == 2 else c.reshape(1, -1), jnp.float32)
    mode = "2d" if c.ndim == 2 else "1d"
    out = transform_inv(c2d, mode=mode, interpret=interpret)
    return np.asarray(out).reshape(c.shape)


def ref_fwd(x, mode="2d"):
    return _ref.fwd(jnp.asarray(x, jnp.float32), mode=mode)


def ref_inv(c, mode="2d"):
    return _ref.inv(jnp.asarray(c, jnp.float32), mode=mode)
