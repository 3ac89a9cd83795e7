"""Pallas TPU kernel: 32x32 bitplane transpose (unpred-aware quantizer core).

The paper's §4.2 embedded encoding stores unpredictable integers plane-by-
plane so significant planes become zero-runs for the lossless stage.  On TPU
this is a pure lane-shuffle-free integer op: each (128, 32) VMEM tile of
values produces a (32, 128) tile of plane words via shift/mask/reduce on the
VPU — no gather, no scalar loop (contrast with the byte-oriented CPU
implementation in SZ3).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _encode_kernel(v_ref, w_ref):
    v = v_ref[...]  # (bt, 32) uint32
    p = jnp.arange(32, dtype=jnp.uint32)[:, None, None]
    k = jnp.arange(32, dtype=jnp.uint32)[None, None, :]
    bits = (v[None, :, :] >> p) & jnp.uint32(1)
    w_ref[...] = (bits << k).sum(axis=2, dtype=jnp.uint32)  # (32, bt)


def _decode_kernel(w_ref, v_ref):
    w = w_ref[...]  # (32, bt) uint32
    k = jnp.arange(32, dtype=jnp.uint32)[None, None, :]
    p = jnp.arange(32, dtype=jnp.uint32)[:, None, None]
    bits = (w[:, :, None] >> k) & jnp.uint32(1)
    v_ref[...] = (bits << p).sum(axis=0, dtype=jnp.uint32)  # (bt, 32)


def encode(v, *, bt=512, interpret):
    R = v.shape[0]
    return pl.pallas_call(
        _encode_kernel,
        out_shape=jax.ShapeDtypeStruct((32, R), jnp.uint32),
        grid=(R // bt,),
        in_specs=[pl.BlockSpec((bt, 32), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((32, bt), lambda i: (0, i)),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
    )(v)


def decode(w, *, bt=512, interpret):
    R = w.shape[1]
    return pl.pallas_call(
        _decode_kernel,
        out_shape=jax.ShapeDtypeStruct((R, 32), jnp.uint32),
        grid=(R // bt,),
        in_specs=[pl.BlockSpec((32, bt), lambda i: (0, i))],
        out_specs=pl.BlockSpec((bt, 32), lambda i: (i, 0)),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
    )(w)
