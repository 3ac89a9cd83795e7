"""Pallas TPU kernel: fused per-block mean + max-deviation (classify+reduce).

The fast tier's only device-worthy stage: one VMEM pass per (bm, bs) tile
computes, for each of the tile's bm blocks, the block mean AND the maximum
absolute deviation from that mean — the constant-block classification signal
— without re-reading the block (the host path reads the array twice).  bs is
the coder's fixed block length (128/256), already a whole lane multiple, so
a tile holds bm independent blocks and both reductions run along the lane
axis; no cross-tile dependency, the grid is embarrassingly parallel.

Outputs are (nb, 128) lane-broadcast columns (TPU tiles want 128-lane last
dims); the ops.py wrapper takes column 0.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_PAR = pltpu.CompilerParams(dimension_semantics=("parallel",))


def _kernel(x_ref, mean_ref, dev_ref, *, bs):
    t = x_ref[...].astype(jnp.float32)  # (bm, bs)
    mean = jnp.sum(t, axis=1, keepdims=True) / float(bs)  # (bm, 1)
    dev = jnp.max(jnp.abs(t - mean), axis=1, keepdims=True)
    mean_ref[...] = jnp.broadcast_to(mean, mean_ref.shape)
    dev_ref[...] = jnp.broadcast_to(dev, dev_ref.shape)


def block_stats(x, *, bm=8, interpret):
    """(nb, bs) float32, nb % bm == 0 -> (means, devs), each (nb, 128)."""
    nb, bs = x.shape
    kern = functools.partial(_kernel, bs=bs)
    out = jax.ShapeDtypeStruct((nb, 128), jnp.float32)
    return pl.pallas_call(
        kern,
        out_shape=(out, out),
        grid=(nb // bm,),
        in_specs=[pl.BlockSpec((bm, bs), lambda i: (i, 0))],
        out_specs=(
            pl.BlockSpec((bm, 128), lambda i: (i, 0)),
            pl.BlockSpec((bm, 128), lambda i: (i, 0)),
        ),
        compiler_params=_PAR,
        interpret=interpret,
    )(x)
