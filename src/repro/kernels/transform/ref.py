"""Pure-jnp oracle for the blockwise decorrelating-transform kernel.

Layout contract: the array is tiled into 4-point blocks along the transformed
axes (last axis for "1d", last two for "2d"; shapes must be pre-padded to
multiples of 4).  Each block is rotated by the orthonormal 4-point DCT-II
basis ``MAT`` — forward ``c = M b`` per axis, inverse ``b = M^T c`` — so the
coefficient grid has the same shape as the input and every 4-block is
independent (crop-safe: tile padding only ever adds whole blocks).

The basis master copy lives in ``core/transform.py`` (pure numpy, so the
host path imports without jax); this module re-exports it so the kernel,
the oracle, and the host coder provably share one basis — the error-bound
analysis (the L_inf amplification of ``M^T``) transfers only then.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...core.transform import AMP_1AXIS, MAT  # noqa: F401  (shared basis)


def _blocked(x: jnp.ndarray, axis: int) -> jnp.ndarray:
    """(..., 4n, ...) -> (..., n, 4, ...) with the 4-axis appended last."""
    return jnp.moveaxis(
        x.reshape(x.shape[:axis] + (x.shape[axis] // 4, 4) + x.shape[axis + 1 :]),
        axis + 1,
        -1,
    )


def _unblocked(b: jnp.ndarray, axis: int, shape) -> jnp.ndarray:
    return jnp.moveaxis(b, -1, axis + 1).reshape(shape)


def _apply(x: jnp.ndarray, m: jnp.ndarray, axes) -> jnp.ndarray:
    out = x
    for ax in axes:
        b = _blocked(out, ax)
        # full float32 passes: a TPU's default matmul precision rounds the
        # operands to bfloat16, which no oracle may do
        prod = jnp.matmul(b, m.T.astype(out.dtype), precision=jax.lax.Precision.HIGHEST)
        out = _unblocked(prod, ax, out.shape)
    return out


def fwd(x: jnp.ndarray, mode: str = "2d") -> jnp.ndarray:
    """x: (R, C) with transformed axes multiples of 4 -> coefficients.

    Last axis first, matching the kernel's rotation order bit-for-bit in
    float32 (separable rotations commute exactly only in exact arithmetic).
    """
    assert x.ndim == 2
    axes = (1,) if mode == "1d" else (1, 0)
    return _apply(x, jnp.asarray(MAT, x.dtype), axes)


def inv(c: jnp.ndarray, mode: str = "2d") -> jnp.ndarray:
    """Inverse rotation (transpose of the orthonormal basis)."""
    assert c.ndim == 2
    axes = (1,) if mode == "1d" else (1, 0)
    return _apply(c, jnp.asarray(MAT.T, c.dtype), axes)
