"""Pallas TPU kernels: fused prequantize + Lorenzo filter (encode/decode).

TPU adaptation of SZ's predict+quantize hot loop (DESIGN.md §3):
  * dual-quantization (cuSZ) removes the sequential decompressed-value
    feedback, so the filter is a pure integer stencil on VPU lanes;
  * tiles are (bm, bn) VMEM blocks in both modes, so no block spans an
    unbounded row; the cross-tile dependency is carried in VMEM scratch
    across the sequential grid — no halo re-reads, no extra HBM traffic;
  * encode fuses prequant -> stencil -> code clipping in one pass; decode
    fuses prefix-sum reconstruction -> dequant.  Mosaic has no ``cumsum``,
    so the prefix sums are log-step shift-and-add scans built from
    ``pltpu.roll`` and an iota mask; int32 wrap-around makes them exact,
    bit for bit equal to ``jnp.cumsum(..., dtype=int32)``.

Grid conventions: grid (R/bm, C/bn); TPU runs the grid in row-major order,
the column axis fastest.
  1d : rows independent; the carry is the (bm, 1) last column of the
       previous column tile (reset at j == 0).
  2d : the same column carry, plus a row carry per column tile — the last
       row of the previous row tile — held in an (nj, 1, bn) scratch ring.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_SEQ = pltpu.CompilerParams(dimension_semantics=("arbitrary", "arbitrary"))


def prefix_sum(x: jnp.ndarray, axis: int) -> jnp.ndarray:
    """Inclusive int32 prefix sum along ``axis`` (log-step scan)."""
    n = x.shape[axis]
    idx = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis)
    s = 1
    while s < n:
        x = x + jnp.where(idx >= s, pltpu.roll(x, s, axis), 0)
        s *= 2
    return x


def _clip_codes(d, radius):
    return jnp.where(jnp.abs(d) < radius, d + radius, 0).astype(jnp.int32)


# ---------------------------------------------------------------------------
# encode
# ---------------------------------------------------------------------------

def _encode1d_kernel(x_ref, codes_ref, draw_ref, col_ref, *, inv_two_eb, radius):
    @pl.when(pl.program_id(1) == 0)
    def _():
        col_ref[...] = jnp.zeros_like(col_ref)

    q = jnp.rint(x_ref[...].astype(jnp.float32) * inv_two_eb).astype(jnp.int32)
    left = jnp.concatenate([col_ref[...], q[:, :-1]], axis=1)
    col_ref[...] = q[:, -1:]
    d = q - left
    codes_ref[...] = _clip_codes(d, radius)
    draw_ref[...] = d


def _encode2d_kernel(x_ref, codes_ref, draw_ref, row_ref, col_ref, *, inv_two_eb, radius):
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(i == 0)
    def _():
        row_ref[j] = jnp.zeros(row_ref.shape[1:], jnp.int32)

    @pl.when(j == 0)
    def _():
        col_ref[...] = jnp.zeros_like(col_ref)

    q = jnp.rint(x_ref[...].astype(jnp.float32) * inv_two_eb).astype(jnp.int32)
    up = jnp.concatenate([row_ref[j], q[:-1, :]], axis=0)
    row_ref[j] = q[-1:, :]
    dr = q - up  # row difference; its last column is the next tile's left
    left = jnp.concatenate([col_ref[...], dr[:, :-1]], axis=1)
    col_ref[...] = dr[:, -1:]
    d = dr - left
    codes_ref[...] = _clip_codes(d, radius)
    draw_ref[...] = d


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------

def _decode1d_kernel(d_ref, out_ref, col_ref, *, two_eb):
    @pl.when(pl.program_id(1) == 0)
    def _():
        col_ref[...] = jnp.zeros_like(col_ref)

    q = prefix_sum(d_ref[...], 1) + col_ref[...]
    col_ref[...] = q[:, -1:]
    out_ref[...] = q.astype(jnp.float32) * two_eb


def _decode2d_kernel(d_ref, out_ref, row_ref, col_ref, *, two_eb):
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(i == 0)
    def _():
        row_ref[j] = jnp.zeros(row_ref.shape[1:], jnp.int32)

    @pl.when(j == 0)
    def _():
        col_ref[...] = jnp.zeros_like(col_ref)

    r = prefix_sum(d_ref[...], 1) + col_ref[...]  # running row sums
    col_ref[...] = r[:, -1:]
    q = prefix_sum(r, 0) + row_ref[j]
    row_ref[j] = q[-1:, :]
    out_ref[...] = q.astype(jnp.float32) * two_eb


# ---------------------------------------------------------------------------
# pallas_call wrappers (shapes must be pre-padded by ops.py)
# ---------------------------------------------------------------------------

def _scratch(mode, R, C, bm, bn):
    col = pltpu.VMEM((bm, 1), jnp.int32)
    if mode == "1d":
        return [col]
    return [pltpu.VMEM((C // bn, 1, bn), jnp.int32), col]


def _tiled(x, *, mode, bm, bn):
    return dict(
        grid=(x.shape[0] // bm, x.shape[1] // bn),
        in_specs=[pl.BlockSpec((bm, bn), lambda i, j: (i, j))],
        scratch_shapes=_scratch(mode, *x.shape, bm, bn),
        compiler_params=_SEQ,
    )


def encode(x, eb, radius, *, mode, bm, bn, interpret):
    """(R, C) float, R % bm == C % bn == 0 -> (codes, raw diffs) int32."""
    body = _encode1d_kernel if mode == "1d" else _encode2d_kernel
    kern = functools.partial(
        body, inv_two_eb=1.0 / (2.0 * float(eb)), radius=int(radius)
    )
    out = jax.ShapeDtypeStruct(x.shape, jnp.int32)
    blk = pl.BlockSpec((bm, bn), lambda i, j: (i, j))
    return pl.pallas_call(
        kern,
        out_shape=(out, out),
        out_specs=(blk, blk),
        interpret=interpret,
        **_tiled(x, mode=mode, bm=bm, bn=bn),
    )(x)


def decode(d, eb, *, mode, bm, bn, interpret):
    """(R, C) int32 raw diffs -> float32 reconstruction (inverse of encode)."""
    body = _decode1d_kernel if mode == "1d" else _decode2d_kernel
    kern = functools.partial(body, two_eb=2.0 * float(eb))
    return pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct(d.shape, jnp.float32),
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        interpret=interpret,
        **_tiled(d, mode=mode, bm=bm, bn=bn),
    )(d)
