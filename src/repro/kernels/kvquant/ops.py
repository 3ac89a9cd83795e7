"""Jit'd wrappers for KV quantization kernels (padding; ``interpret=None``
resolves by backend in :mod:`repro.kernels.routing`)."""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .. import routing
from . import kernel as _k
from . import ref as _ref

SCALE_FLOOR = _k.SCALE_FLOOR


def _pad_to(x, m0, m1):
    p0, p1 = (-x.shape[0]) % m0, (-x.shape[1]) % m1
    if p0 or p1:
        x = jnp.pad(x, ((0, p0), (0, p1)))
    return x


@functools.partial(jax.jit, static_argnames=("interpret",))
def kv_quantize(
    x: jnp.ndarray, *, interpret: Optional[bool] = None
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(T, C) -> (int8 codes (T, C), per-channel scale (C,))."""
    interpret = routing.interpret_mode(interpret)
    T, C = x.shape
    xp = _pad_to(x.astype(jnp.float32), 256, 128)
    amax = _k.absmax(xp, interpret=interpret)  # (1, Cp)
    scale = jnp.maximum(amax / 127.0, SCALE_FLOOR)
    q = _k.quantize_with_scale(xp, scale, interpret=interpret)
    return q[:T, :C], scale[0, :C]


@functools.partial(jax.jit, static_argnames=("interpret",))
def kv_dequant_matmul(
    a: jnp.ndarray, q: jnp.ndarray, scale: jnp.ndarray, *,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """a (M, K) @ dequant(q (K, N), scale (N,)) -> (M, N) f32."""
    M, K = a.shape
    _, N = q.shape
    ap = _pad_to(a.astype(jnp.float32), 128, 128)
    qp = _pad_to(q, 128, 128)
    sp = jnp.pad(scale, (0, (-N) % 128)).reshape(1, -1)
    interpret = routing.interpret_mode(interpret)
    out = _k.dequant_matmul(ap, qp, sp, interpret=interpret)
    return out[:M, :N]


def ref_quantize(x):
    return _ref.quantize(jnp.asarray(x))


def ref_dequant_matmul(a, q, scale):
    return _ref.dequant_matmul(jnp.asarray(a), jnp.asarray(q), jnp.asarray(scale))
