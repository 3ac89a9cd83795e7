"""Jit'd public wrappers around the fused Lorenzo kernels.

Handles padding to tile multiples, the fast-path guard (``PIPELINE_SAFE``;
beyond it callers use the core numpy int64 path), and the host array
boundary for the device compression path.
``interpret=None`` resolves by backend in :mod:`repro.kernels.routing`.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import routing
from . import kernel as _k
from . import ref as _ref

#: guard for the BOUND-EXACT pipeline fast path (predictors.LorenzoPredictor):
#: beyond int32 range safety, prequantized magnitudes must stay small enough
#: that float32 kernel arithmetic cannot round reconstructions past the error
#: bound before the bound check patches the stragglers.
PIPELINE_SAFE = float(1 << 22)

#: flat positions the on-chip bound check hands back per call (index, q, the
#: float32 reconstruction and the raw difference, 16 B each); a field that
#: flags more takes the full host check
CHECK_CAPACITY = 1 << 16

#: the binary search's cost grows with the slots it fills, so a count that
#: fits here (a field at REL 1e-3 flags a few thousand) searches this many
_CHECK_FIRST = 1 << 12


class CheckedEncode(NamedTuple):
    """Host side of :func:`encode_checked`.

    ``codes`` are in the quantizer's code dtype, in the input's shape.  When
    ``n <= CHECK_CAPACITY`` the flagged flat positions ``idx`` (ascending)
    come with their prequantized integer ``q``, the kernel decode's float32
    reconstruction ``recon`` and the raw Lorenzo difference ``diffs``;
    otherwise those are empty and ``draw`` (the raw differences, still on
    the device) is what the caller checks in full."""

    codes: np.ndarray
    n: int
    idx: np.ndarray
    q: np.ndarray
    recon: np.ndarray
    diffs: np.ndarray
    draw: jnp.ndarray

    @property
    def overflow(self) -> bool:
        return self.n > self.idx.size


def encode_checked(
    x: np.ndarray, *, eb: float, radius: int, code_dtype,
    interpret: Optional[bool] = None,
) -> CheckedEncode:
    """Fused prequant+Lorenzo encode for the REAL pipeline (host arrays), with
    the error-bound check certified on the chip.

    Accepts 1-D (row-independent "1d" stencil) or 2-D ("2d" stencil) float32;
    callers are responsible for the PIPELINE_SAFE guard.  The encode kernel's
    program and :func:`lorenzo_check` run back to back on the device; only
    the codes and the flagged positions come back.
    """
    x2d = jnp.asarray(x if x.ndim == 2 else x.reshape(1, -1), jnp.float32)
    mode = "2d" if x.ndim == 2 else "1d"
    codes, draw = lorenzo_encode(
        x2d, eb=float(eb), radius=int(radius), mode=mode, interpret=interpret
    )
    codes, n, cand = lorenzo_check(
        x2d, codes, draw, eb=float(eb), mode=mode, code_dtype=np.dtype(code_dtype)
    )
    codes, n, cand = jax.device_get((codes, n, cand))
    n = int(n)
    k = n if n <= cand.shape[1] else 0
    idx, q, recon_bits, diffs = cand[:, :k]
    return CheckedEncode(
        codes.reshape(x.shape), n, idx, q, recon_bits.view(np.float32), diffs, draw
    )


def _check_floor(eb: float) -> np.float32:
    """A float32 at or below ``eb (1 - 2^-20) - 2^-100``: the flag threshold of
    :func:`lorenzo_check` (zero or less flags everything)."""
    t = float(eb) * (1.0 - 2.0 ** -20) - 2.0 ** -100
    f = np.float32(t)
    if float(f) > t:
        f = np.nextafter(f, np.float32(-np.inf))
    return f


def _cumsum_last(v: jnp.ndarray, block: int = 1024) -> jnp.ndarray:
    """Inclusive int32 prefix sum along the last axis; beyond four blocks it
    runs in blocks of ``block``: the TPU compiler takes tens of seconds over
    one ``cumsum`` a million long, and is quick with a few thousand."""
    lead, n = v.shape[:-1], v.shape[-1]
    if n <= 4 * block:
        return jnp.cumsum(v, axis=-1, dtype=jnp.int32)
    m = -(-n // block)
    v = jnp.pad(v, [(0, 0)] * len(lead) + [(0, m * block - n)])
    w = jnp.cumsum(v.reshape(*lead, m, block), axis=-1, dtype=jnp.int32)
    t = w[..., -1]
    w = w + (jnp.cumsum(t, axis=-1, dtype=jnp.int32) - t)[..., None]
    return w.reshape(*lead, m * block)[..., :n]


@functools.partial(jax.jit, static_argnames=("eb", "mode", "code_dtype"))
def lorenzo_check(
    x: jnp.ndarray,
    codes: jnp.ndarray,
    draw: jnp.ndarray,
    *,
    eb: float,
    mode: str,
    code_dtype=np.dtype(np.uint16),
):
    """On-chip error-bound certificate of :func:`lorenzo_encode`'s output.

    Returns ``(codes, n, cand)``: the codes in ``code_dtype``, the number
    ``n`` of flagged positions, and ``cand``, int32 ``(4, CHECK_CAPACITY)``:
    the first ``CHECK_CAPACITY`` flagged flat indices in ascending order, their ``q``,
    their reconstruction's float32 bits and their raw difference.

    The two decoders reconstruct ``recon_dev = f32(f32(q) * f32(2 eb))`` (the
    decode kernel) and ``recon_np = f32(f64(q) * f64(2 eb))``
    (``quantizer.dequantize_int``); the host check fails a point when either
    lies more than ``eb`` from ``float64(x)``.  ``q`` here is the kernel's,
    recovered from ``draw`` by exact int32 prefix sums as the decode kernel
    does (|q| <= 2^22, no wrap), and ``recon_dev`` is recomputed with the
    decode kernel's arithmetic.  A point is flagged unless

        fl32(|recon_dev - x| + 2^-21 |recon_dev|) < F,
        F <= eb (1 - 2^-20) - 2^-100          (:func:`_check_floor`),

    or its code is 0 (``|d| >= radius``: its raw difference must go to the
    unpredictable channel).  Superset argument, u = 2^-24, P = |q| 2eb, and
    eb >= 2^-100 (below it F <= 0 and everything is flagged):

      * |recon_dev - q 2eb| <= (2u + u^2) P and |recon_np - q 2eb| <=
        (u + 2^-52) P, so |recon_np - recon_dev| <= 3.01 u P <= 3.02 u
        |recon_dev| (q = 0 gives 0 both ways).
      * the host's float64 |r - x| > eb implies the exact |r - x| >
        eb (1 - 2^-53); for either reconstruction that gives the exact
        |recon_dev - x| > eb (1 - 2^-53) - 3.02 u |recon_dev|.
      * the float32 subtraction (rounded, or fused with the product, or
        with x flushed to zero) reads at least |recon_dev - x| (1 - u) -
        1.01 u |recon_dev| - 2^-126 >= eb (1 - 1.01 u) - 4.03 u |recon_dev|
        - 2^-126.
      * adding 2^-21 |recon_dev| = 8 u |recon_dev| (flushed below 2^-126)
        and rounding the sum keeps it at or above eb (1 - 2.02 u) - 2^-124,
        which is more than eb (1 - 2^-20) - 2^-100 >= F.

    So every point the full host check fails is flagged, and the host's exact
    re-check of the flagged points gives the same fail mask.  The margin is
    about 8 u |x| + 2^-20 eb, a share of about 2^-21 |x| / eb of the points
    at random (well under 1e-3 at REL 1e-3 on a field that spans its range);
    near ``PIPELINE_SAFE`` it reaches 4 eb and flags everything, and the
    caller's full check takes over.

    The flagged positions are found by a binary search of the running count
    for each slot; it searches ``_CHECK_FIRST`` slots when the count fits
    there, else ``CHECK_CAPACITY``.
    """
    q = _cumsum_last(draw)
    if mode == "2d":
        q = jnp.cumsum(q, axis=0, dtype=jnp.int32)
    recon = q.astype(jnp.float32) * (2.0 * eb)
    err = jnp.abs(recon - x) + jnp.abs(recon) * (2.0 ** -21)
    flag = ~(err < _check_floor(eb)) | (codes == 0)
    # running count in row-major order: within rows, then the rows' totals
    csum = _cumsum_last(flag.astype(jnp.int32))
    total = csum[:, -1]
    csum = (csum + (jnp.cumsum(total, dtype=jnp.int32) - total)[:, None]).reshape(-1)
    n = csum[-1]
    channels = (q, jax.lax.bitcast_convert_type(recon, jnp.int32), draw)

    def gather(slots):
        ranks = jnp.arange(1, slots + 1, dtype=jnp.int32)
        idx = jnp.searchsorted(csum, ranks, side="left", method="scan").astype(jnp.int32)
        cand = jnp.stack([idx] + [jnp.take(a.reshape(-1), idx, mode="clip") for a in channels])
        return jnp.pad(cand, ((0, 0), (0, CHECK_CAPACITY - slots)))

    cand = jax.lax.cond(
        n <= _CHECK_FIRST, lambda: gather(_CHECK_FIRST), lambda: gather(CHECK_CAPACITY)
    )
    return codes.astype(code_dtype), n, cand


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

    Inverse of :func:`encode_checked`: 1-D or 2-D int32 raw diffs (with
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
