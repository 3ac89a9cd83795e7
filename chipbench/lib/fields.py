"""Gaussian random fields with a power-law spectrum, made on the device.

A copy of ``benchmarks/datasets.gaussian_random_field`` (numpy, host), moved
onto the device and made from a JAX key, so that a cell's pool of fields is
one jitted call from the seed.  Two departures.  The k = 0 mode is zeroed:
the numpy original floors k at 1e-9 there, which only adds a constant that
the normalisation removes; in float32 that constant would swamp the low bits
of the field.  And each field is scaled to the range [0, 1] exactly, not to
zero mean and unit deviation: a REL bound then resolves to the same
absolute bound on every field of every seed, and the codec's kernels, which
take that bound as a static argument, compile once and not once per field.
"""
from __future__ import annotations

from functools import partial
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int) -> jax.Array:
    """A key from any non-negative seed, also one wider than 32 bits."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


@partial(jax.jit, static_argnames=("shape",))
def _pool(key, slopes, shape: Tuple[int, int]):
    h, w = shape
    ky = jnp.fft.fftfreq(h) * h
    kx = jnp.fft.fftfreq(w) * w
    k2 = ky[:, None] ** 2 + kx[None, :] ** 2

    def one(args):
        k, slope = args
        white = jax.random.normal(k, shape, jnp.float32)
        # |k|^(-slope/2) is (k^2)^(-slope/4)
        mult = jnp.where(k2 > 0, jnp.where(k2 > 0, k2, 1.0) ** (-slope / 4.0), 0.0)
        out = jnp.real(jnp.fft.ifft2(jnp.fft.fft2(white) * mult))
        lo, hi = out.min(), out.max()
        y = jnp.clip((out - lo) / (hi - lo), 0.0, 1.0).reshape(-1)
        # the extremes set exactly, however the division rounds: range 1.0
        y = y.at[jnp.argmin(out)].set(0.0).at[jnp.argmax(out)].set(1.0)
        return y.reshape(shape)

    keys = jax.random.split(key, slopes.shape[0])
    return jax.lax.map(one, (keys, slopes))


def make_pool(seed: int, shape: Sequence[int], slopes: Sequence[float], device=None):
    """One float32 device array per slope, all from ``seed``."""
    slopes = jnp.asarray(np.asarray(slopes, np.float32))
    if device is not None:
        slopes = jax.device_put(slopes, device)
    pool = _pool(seed_key(seed), slopes, tuple(int(n) for n in shape))
    fields = [pool[i] for i in range(pool.shape[0])]
    jax.block_until_ready(fields)
    return fields


def pool_order(seed: int, n: int) -> np.ndarray:
    """The order in which a run visits the pool: every field once per pass."""
    return np.random.default_rng(int(seed)).permutation(n)
