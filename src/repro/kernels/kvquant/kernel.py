"""Pallas TPU kernels for quantized-KV serving (beyond-paper integration).

Two kernels:
  * quantize  — two-phase per-channel absmax + int8 cast, fused in one pass
                over row tiles (the absmax recurrence rides the sequential
                grid axis in VMEM scratch; codes are emitted on a second
                sweep).  Used when appending prefill KV blocks to the cache.
  * dequant_matmul — MXU-tiled matmul with the int8->f32 dequant fused into
                the VMEM load and the per-column scale folded into the
                epilogue: C[i,j] = sum_k A[i,k] * Q[k,j] * s[j].  Saves HBM
                bandwidth 2-4x vs bf16 KV — the memory-roofline lever for
                decode shapes (EXPERIMENTS.md §Perf).

All matmul block dims are 128-multiples so the MXU tiles are fully populated.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


SCALE_FLOOR = 1e-8


# ---------------------------------------------------------------------------
# per-channel absmax (phase 1 of quantize)
# ---------------------------------------------------------------------------

def _absmax_kernel(x_ref, amax_ref, acc_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = jnp.abs(x_ref[...].astype(jnp.float32))
    acc_ref[...] = jnp.maximum(acc_ref[...], jnp.max(x, axis=0, keepdims=True))

    @pl.when(i == pl.num_programs(0) - 1)
    def _():
        amax_ref[...] = acc_ref[...]


def _quant_kernel(x_ref, scale_ref, q_ref):
    x = x_ref[...].astype(jnp.float32)
    s = scale_ref[...]  # (1, bn)
    q = jnp.clip(jnp.rint(x / s), -127, 127)
    q_ref[...] = q.astype(jnp.int8)


def absmax(x, *, bm=256, interpret):
    T, C = x.shape
    return pl.pallas_call(
        _absmax_kernel,
        out_shape=jax.ShapeDtypeStruct((1, C), jnp.float32),
        grid=(T // bm,),
        in_specs=[pl.BlockSpec((bm, C), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((1, C), lambda i: (0, 0)),
        scratch_shapes=[pltpu.VMEM((1, C), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(x)


def quantize_with_scale(x, scale, *, bm=256, bn=128, interpret):
    T, C = x.shape
    return pl.pallas_call(
        _quant_kernel,
        out_shape=jax.ShapeDtypeStruct((T, C), jnp.int8),
        grid=(T // bm, C // bn),
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")
        ),
        interpret=interpret,
    )(x, scale)


# ---------------------------------------------------------------------------
# fused dequant matmul
# ---------------------------------------------------------------------------

def _dequant_matmul_kernel(a_ref, q_ref, scale_ref, o_ref, acc_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...].astype(jnp.float32)
    b = q_ref[...].astype(jnp.float32)  # int8 -> f32 in VMEM
    acc_ref[...] += jnp.dot(a, b, preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(2) - 1)
    def _():
        o_ref[...] = acc_ref[...] * scale_ref[...]  # per-column epilogue


def dequant_matmul(a, q, scale, *, bm=128, bn=128, bk=128, interpret):
    M, K = a.shape
    K2, N = q.shape
    assert K == K2 and scale.shape == (1, N)
    grid = (M // bm, N // bn, K // bk)
    return pl.pallas_call(
        _dequant_matmul_kernel,
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(a, q, scale)
