"""Jit'd public wrappers around the fused Lorenzo kernels.

Handles padding to tile multiples, the fast-path guard (``PIPELINE_SAFE``;
beyond it callers use the core numpy int64 path), and the host array
boundary for the device compression path.
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

#: guard for the BOUND-EXACT pipeline fast path (predictors.LorenzoPredictor):
#: beyond int32 range safety, prequantized magnitudes must stay small enough
#: that float32 kernel arithmetic cannot round reconstructions past the error
#: bound before the host-side verification patches the stragglers.
PIPELINE_SAFE = float(1 << 22)


def encode_pipeline(
    x: np.ndarray, *, eb: float, radius: int = 32768,
    interpret: Optional[bool] = None,
) -> Tuple[np.ndarray, np.ndarray]:
    """Fused prequant+Lorenzo encode for the REAL pipeline (host arrays).

    Accepts 1-D (row-independent "1d" stencil) or 2-D ("2d" stencil) float32
    and returns host (codes, raw_diffs) int32 in the input's shape.  Callers
    are responsible for the PIPELINE_SAFE guard and for verifying/patching
    reconstruction against the error bound (predictors.LorenzoPredictor).
    """
    x2d = jnp.asarray(x if x.ndim == 2 else x.reshape(1, -1), jnp.float32)
    mode = "2d" if x.ndim == 2 else "1d"
    codes, draw = lorenzo_encode(
        x2d, eb=float(eb), radius=int(radius), mode=mode, interpret=interpret
    )
    shape = x.shape
    return (
        np.asarray(codes).reshape(shape),
        np.asarray(draw).reshape(shape),
    )


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


@functools.partial(jax.jit, static_argnames=("eb", "radius", "mode", "interpret"))
def lorenzo_encode(
    x: jnp.ndarray,
    *,
    eb: float,
    radius: int = 32768,
    mode: str = "2d",
    interpret: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused prequant+Lorenzo encode. Returns (codes, raw_diffs), both int32,
    cropped to the input shape.  mode: "1d" (row-independent) | "2d"."""
    assert x.ndim == 2, "reshape to 2-D before calling (rows, fastest-axis)"
    bm, bn = _tiles(x.shape)
    xp, (R, C) = _pad2d(x, bm, bn)
    interpret = routing.interpret_mode(interpret)
    codes, draw = _k.encode(xp, eb, radius, mode=mode, bm=bm, bn=bn, interpret=interpret)
    return codes[:R, :C], draw[:R, :C]


@functools.partial(jax.jit, static_argnames=("eb", "mode", "interpret"))
def lorenzo_decode(
    d: jnp.ndarray, *, eb: float, mode: str = "2d", interpret: Optional[bool] = None
) -> jnp.ndarray:
    """Inverse (prefix sums) + dequant.  ``d`` must contain the raw diffs with
    unpredictable positions already substituted."""
    assert d.ndim == 2
    bm, bn = _tiles(d.shape)
    dp, (R, C) = _pad2d(d, bm, bn)
    interpret = routing.interpret_mode(interpret)
    out = _k.decode(dp, eb, mode=mode, bm=bm, bn=bn, interpret=interpret)
    return out[:R, :C]


def decode_pipeline(
    d: np.ndarray, *, eb: float, interpret: Optional[bool] = None
) -> np.ndarray:
    """Fused prefix-sum+dequant decode for the REAL pipeline (host arrays).

    Inverse of :func:`encode_pipeline`: 1-D or 2-D int32 raw diffs (with
    unpredictable positions already substituted) -> float32 reconstruction.
    """
    d2 = jnp.asarray(d if d.ndim == 2 else d.reshape(1, -1), jnp.int32)
    mode = "2d" if d.ndim == 2 else "1d"
    out = lorenzo_decode(d2, eb=float(eb), mode=mode, interpret=interpret)
    return np.asarray(out).reshape(d.shape)


def ref_encode(x, eb, radius=32768, mode="2d"):
    fn = _ref.encode_1d if mode == "1d" else _ref.encode_2d
    return fn(jnp.asarray(x, jnp.float32), eb, radius)


def ref_decode(d, eb, mode="2d"):
    fn = _ref.decode_1d if mode == "1d" else _ref.decode_2d
    return fn(jnp.asarray(d, jnp.int32), eb)
