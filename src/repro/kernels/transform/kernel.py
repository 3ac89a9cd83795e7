"""Pallas TPU kernel: blockwise 4-point decorrelating transform (fwd/inv).

The transform stage of the ZFP-style coder (core/transform.py §device path):
each (4, 4) block of a 2-D field is rotated by the orthonormal DCT-II basis,
``c = M b M^T`` (or along the last axis only in "1d" mode).  On TPU this is a
pure VPU problem: a (bm, bn) VMEM tile holds bm/4 x bn/4 independent blocks.
The per-axis rotation never splits the lane or sublane axis into (n, 4) —
Mosaic cannot lower that reshape.  Instead each output element k of a block
sums its block's inputs through ``pltpu.roll`` by the offset j - k, weighted
by a coefficient vector chosen per lane (or sublane) from ``index % 4``; a
weight is zero where j falls outside the block, so a roll's wrap-around never
reaches the result.  The terms are added in order of j, as the matrix
product's rows would be.  No MXU, no gathers, no cross-tile dependency:
blocks never straddle tiles because bm, bn are multiples of 4.

Grid conventions: grid (R/bm, C/bn), both dimensions parallel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import MAT

_PAR = pltpu.CompilerParams(dimension_semantics=("parallel", "parallel"))


def _rotate(t: jnp.ndarray, m, axis: int) -> jnp.ndarray:
    """Apply the 4-point basis ``m`` along ``axis`` of a (bm, bn) tile:
    out[4b + k] = sum_j m[k][j] * t[4b + j]."""
    n = t.shape[axis]
    shape = [1, 1]
    shape[axis] = n
    k = jax.lax.broadcasted_iota(jnp.int32, tuple(shape), axis) % 4
    out = None
    for off in range(-3, 4):  # off = j - k; runs j upward for every k
        w = jnp.zeros(tuple(shape), jnp.float32)
        for kk in range(4):
            if 0 <= kk + off < 4:
                w = jnp.where(k == kk, float(m[kk][kk + off]), w)
        # roll by -off brings t[c + off] to position c
        term = w * (t if off == 0 else pltpu.roll(t, (-off) % n, axis))
        out = term if out is None else out + term
    return out


def _kernel(x_ref, o_ref, *, m, mode):
    t = _rotate(x_ref[...].astype(jnp.float32), m, 1)
    if mode == "2d":
        t = _rotate(t, m, 0)
    o_ref[...] = t


def _call(x, *, m, mode, bm, bn, interpret):
    R, C = x.shape
    kern = functools.partial(_kernel, m=m, mode=mode)
    return pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((R, C), jnp.float32),
        grid=(R // bm, C // bn),
        in_specs=[pl.BlockSpec((bm, bn), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        compiler_params=_PAR,
        interpret=interpret,
    )(x)


_M_FWD = tuple(tuple(row) for row in MAT.tolist())
_M_INV = tuple(tuple(row) for row in MAT.T.tolist())


def fwd(x, *, mode, bm, bn, interpret):
    """(R, C) float32, R % bm == 0 and C % bn == 0 -> coefficient grid."""
    return _call(x, m=_M_FWD, mode=mode, bm=bm, bn=bn, interpret=interpret)


def inv(c, *, mode, bm, bn, interpret):
    return _call(c, m=_M_INV, mode=mode, bm=bm, bn=bn, interpret=interpret)
