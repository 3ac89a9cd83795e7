"""Transform-based coding subsystem: blockwise decorrelation + bitplane coding.

The paper's pipelines are all prediction-based; this module adds the OTHER
coder family of the lossy-compression literature (ZFP-style transform coding,
cf. Tao et al., arXiv:1806.08901 — automatic online selection between SZ and
ZFP), so the per-chunk contest in ``chunking.select_pipeline`` can choose
between prediction and transform per data region:

  1. the array is padded (edge replication) to 4-point blocks per axis and
     each 4^d block is rotated by the orthonormal 4-point DCT-II basis
     (``kernels/transform/ref.MAT``) — smooth or oscillatory content
     concentrates into few low/high-frequency bands;
  2. coefficients are quantized on an EXPONENT-ALIGNED grid: the step is the
     largest power of two such that the worst-case L_inf amplification of the
     inverse basis (``AMP_1AXIS ** ndim``) keeps every reconstructed value
     within the absolute error bound — so integer bitplanes line up with
     absolute error thresholds;
  3. integer coefficients are regrouped band-major (all DC together, etc.;
     the DC band is additionally delta-coded across blocks) and stored as
     MSB-first embedded bitplane streams via ``quantizers.bitplane_encode``
     — per-band truncation: planes above the band's max magnitude are never
     emitted, planes below the error bound never exist;
  4. the rare points where float rounding still breaks the bound (or
     non-finite inputs) are patched through a raw fail channel, exactly like
     the device Lorenzo path — the bound holds unconditionally.

Host path: numpy float64.  Device path (1-D/2-D float32, ``device="auto"`` on
real TPUs / ``"force"`` in tests): the forward/inverse Pallas kernels in
``kernels/transform``; compression verifies reconstruction against the host
inverse AND the kernel inverse and patches stragglers, and decode only takes
the kernel route on the backend whose arithmetic was verified (any other
backend gets the always-verified host inverse) — so the bound is
route-independent.

Containers carry the v3 header tag (``kind: "transform"``); ``pipeline.
decompress`` auto-detects it, and v1/v2 blobs decode unchanged.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import numpy as np

from . import lossless as ll_mod
from . import pipeline as pl_mod
from . import telemetry as tel
from .config import CompressionConfig, ErrorBoundMode
from .integrity import ContainerError, guard_alloc, guard_count, guard_shape
from .pipeline import CompressionResult, container_body, pack_container
from .predictors import _int_code_bits, _pack_mask, _unpack_mask
from .quantizers import bitplane_decode, bitplane_encode

_VERSION3 = 3
_BLOCK = 4

#: orthonormal 4-point DCT-II basis (rows = frequencies) — the MASTER copy,
#: defined here (pure numpy) so the host path imports without jax; the device
#: kernels (kernels/transform/ref.py) import it from here, keeping all three
#: implementations on one basis so the error-bound analysis transfers.
MAT = np.array(
    [
        [
            (np.sqrt(1.0 / 4.0) if k == 0 else np.sqrt(2.0 / 4.0))
            * np.cos(np.pi * (2 * j + 1) * k / 8.0)
            for j in range(4)
        ]
        for k in range(4)
    ],
    np.float64,
)

#: L_inf error amplification of the 1-axis inverse: max_i sum_k |MAT[k, i]|
AMP_1AXIS = float(np.abs(MAT).sum(axis=0).max())

_INT_SAFE = float(1 << 62)

#: cost-model calibration: the bitplane+generic-lossless stage lands farther
#: from the empirical entropy than the Huffman+zstd stage the prediction
#: pipelines are scored with (sign planes and plane framing are only partly
#: recovered by the lossless pass), so raw band entropies flatter the
#: transform coder in the cross-family contest.  Measured on the bench
#: fixtures the gap is 10-40% depending on plane density; scores carry the
#: low end and ambiguity is resolved by select_pipeline's trial runoff.
_BITPLANE_OVERHEAD = 1.15


# ---------------------------------------------------------------------------
# blockwise separable transform (host path, float64)
# ---------------------------------------------------------------------------

def _apply_axis(x: np.ndarray, m: np.ndarray, ax: int) -> np.ndarray:
    xm = np.moveaxis(x, ax, -1)
    shp = xm.shape
    b = xm.reshape(shp[:-1] + (shp[-1] // _BLOCK, _BLOCK))
    return np.moveaxis((b @ m.T).reshape(shp), -1, ax)


def _fwd_host(x64: np.ndarray) -> np.ndarray:
    out = x64
    for ax in range(out.ndim - 1, -1, -1):  # last axis first (kernel order)
        out = _apply_axis(out, MAT, ax)
    return out


def _inv_host(c64: np.ndarray) -> np.ndarray:
    out = c64
    for ax in range(out.ndim - 1, -1, -1):
        out = _apply_axis(out, MAT.T, ax)
    return out


def _pad_blocks(x: np.ndarray) -> np.ndarray:
    """Edge-replicate to multiples of the block size (keeps edge-block
    coefficients small; zero padding would inject an artificial step)."""
    pads = [(0, (-s) % _BLOCK) for s in x.shape]
    if any(p for _, p in pads):
        x = np.pad(x, pads, mode="edge")
    return x


def _blockify(kp: np.ndarray) -> np.ndarray:
    """Padded grid -> (4^d, nblocks) band-major (all DC together, ...)."""
    d = kp.ndim
    inter = []
    for s in kp.shape:
        inter += [s // _BLOCK, _BLOCK]
    t = kp.reshape(inter)
    order = list(range(1, 2 * d, 2)) + list(range(0, 2 * d, 2))
    return t.transpose(order).reshape(_BLOCK**d, -1)


def _unblockify(bands: np.ndarray, pshape: Tuple[int, ...]) -> np.ndarray:
    d = len(pshape)
    t = bands.reshape((_BLOCK,) * d + tuple(s // _BLOCK for s in pshape))
    order = []
    for i in range(d):
        order += [d + i, i]
    return t.transpose(order).reshape(pshape)


def _step_exponent(abs_eb: float, ndim: int) -> int:
    """Largest power-of-two step with amp^ndim * step/2 <= abs_eb (the
    exponent alignment of the quantization grid)."""
    target = 2.0 * abs_eb / (AMP_1AXIS ** max(1, ndim))
    e = int(np.floor(np.log2(target)))
    return max(-1022, min(1023, e))


def _quantize_coeffs(c: np.ndarray, step: float) -> np.ndarray:
    """Coefficients -> int64 on the aligned grid; overflow positions -> 0
    (they surface as fail-channel points after verification)."""
    with np.errstate(over="ignore", invalid="ignore"):
        scaled = c / step
    bad = ~np.isfinite(scaled) | (np.abs(scaled) >= _INT_SAFE)
    return np.rint(np.where(bad, 0.0, scaled)).astype(np.int64)


def _encode_bands(bands: np.ndarray) -> bytes:
    """Band-major int64 -> concatenated embedded bitplane streams (DC band
    delta-coded across blocks first: neighbouring blocks share their local
    mean, so the DC stream's significant planes become zero-runs too)."""
    parts = []
    for i in range(bands.shape[0]):
        vals = np.diff(bands[i], prepend=0) if i == 0 else bands[i]
        parts.append(bitplane_encode(vals))
    return b"".join(parts)


def _decode_bands(payload: bytes, nbands: int, nblocks: int) -> np.ndarray:
    bands = np.empty((nbands, nblocks), np.int64)
    pos = 0
    for i in range(nbands):
        vals, consumed = bitplane_decode(payload, pos)
        pos += consumed
        if vals.size != nblocks:
            raise ValueError("corrupt transform payload: band size mismatch")
        bands[i] = np.cumsum(vals) if i == 0 else vals
    return bands


# ---------------------------------------------------------------------------
# the compressor
# ---------------------------------------------------------------------------

class TransformCompressor:
    """Blockwise transform coder (the fourth coder family; see module doc)."""

    kind = "transform"

    #: below this many elements the kernel dispatch overhead dominates
    _DEVICE_MIN_SIZE = 4096

    def __init__(
        self,
        lossless: str = "zstd",
        device: str = "auto",
        conf: Optional[CompressionConfig] = None,
    ):
        self.lossless = ll_mod.make(lossless)
        self.device = device
        self.conf = conf or CompressionConfig()

    def spec(self) -> Dict[str, Any]:
        return {"kind": self.kind, "block": _BLOCK, "lossless": self.lossless.name}

    # -- cost model (the select_pipeline criterion) --------------------------
    def estimate_error(
        self, sample: np.ndarray, abs_eb: float, conf: CompressionConfig
    ) -> float:
        """Estimated coded bits/element on a sample — same currency as the
        predictors' ``estimate_error`` (empirical entropy), so the chunked
        engine can contest transform vs prediction pipelines directly."""
        x64 = np.asarray(sample, np.float64)
        if x64.size == 0:
            return 0.0
        if x64.ndim == 0:
            x64 = x64.reshape(1)
        x64 = np.where(np.isfinite(x64), x64, 0.0)
        step = 2.0 ** _step_exponent(abs_eb, x64.ndim)
        bands = _blockify(_quantize_coeffs(_fwd_host(_pad_blocks(x64)), step))
        bits = 0.0
        for i in range(bands.shape[0]):
            vals = np.diff(bands[i], prepend=0) if i == 0 else bands[i]
            bits += _int_code_bits(vals, int(_INT_SAFE))
        return bits / bands.shape[0] * _BITPLANE_OVERHEAD

    # -- device routing ------------------------------------------------------
    def _device_ok(self, x: np.ndarray) -> bool:
        if self.device == "off" or x.ndim not in (1, 2):
            return False
        if x.dtype != np.float32 or x.size < self._DEVICE_MIN_SIZE:
            return False
        from ..kernels import routing

        return self.device == "force" or routing.on_tpu()

    # -- compress ------------------------------------------------------------
    def compress(
        self,
        data: np.ndarray,
        conf: Optional[CompressionConfig] = None,
        with_stats: bool = False,
    ) -> CompressionResult:
        conf = conf or self.conf
        data = pl_mod.to_host(data)
        if data.dtype not in (np.float32, np.float64):
            data = data.astype(np.float32)
        shape = data.shape
        x = data.reshape(1) if data.ndim == 0 else data
        with tel.span("stats", bytes=x.nbytes):
            x64 = np.asarray(x, np.float64)
            finite = np.isfinite(x64)
            rng = float(x64[finite].max() - x64[finite].min()) if finite.any() else 0.0
            absmax = float(np.abs(x64[finite]).max()) if finite.any() else 0.0
            abs_eb = conf.resolve_abs_eb(rng, absmax)
        if abs_eb <= 0:
            abs_eb = float(np.finfo(np.float64).tiny)
        meta: Dict[str, Any] = {}
        if x.size == 0:
            header = self._header(shape, x.shape, data.dtype, conf, abs_eb, 0, 0, 0, meta)
            blob = pack_container(header, b"")
            return CompressionResult(blob=blob, ratio=data.nbytes / max(1, len(blob)))
        xc = np.where(finite, x64, 0.0)
        xp = _pad_blocks(xc)
        e = _step_exponent(abs_eb, xp.ndim)
        step = 2.0**e

        device = self._device_ok(np.asarray(x))
        if device:
            from ..kernels.transform import ops as tops

            with tel.span("device_transfer", bytes=xp.nbytes):
                c = np.asarray(tops.fwd_pipeline(xp.astype(np.float32)), np.float64)
        else:
            with tel.span("predict", bytes=xp.nbytes):  # decorrelating stage
                c = _fwd_host(xp)
        with tel.span("quantize", bytes=c.nbytes):
            k = _quantize_coeffs(c, step)

        # verify against every decode route — POST output-dtype cast, since
        # decode rounds the float64 reconstruction onto the storage grid and
        # that rounding alone can push a value past the bound (fail-channel
        # patches survive the cast exactly: they carry the original values);
        # stragglers ride the fail channel
        crop = tuple(slice(0, s) for s in x.shape)
        with tel.span("verify", bytes=x.nbytes):
            recon = _inv_host(k.astype(np.float64) * step)[crop]
            recon_cast = recon.astype(data.dtype).astype(np.float64)
            fail = ~finite | (np.abs(recon_cast - x64) > abs_eb)
            if device:
                from ..kernels.transform import ops as tops

                recon_dev = np.asarray(
                    tops.inv_pipeline((k.astype(np.float64) * step).astype(np.float32)),
                    np.float64,
                )[crop].astype(data.dtype).astype(np.float64)
                fail |= np.abs(recon_dev - x64) > abs_eb
        if device:
            meta["device"] = 1
            # the kernel-inverse verification above only covers THIS
            # backend's arithmetic; decode takes the device route only when
            # it runs on the same backend, else the (always-verified) host
            # float64 inverse
            meta["device_backend"] = _jax_backend()
        meta["nfail"] = int(fail.sum())
        if meta["nfail"]:
            meta["fail_mask"] = _pack_mask(fail)
            meta["fail_vals"] = x64[fail].tobytes()

        bands = _blockify(k)
        with tel.span("huffman", bytes=bands.nbytes):  # bitplane coding stage
            payload = _encode_bands(bands)
        with tel.span("lossless", bytes=len(payload)):
            body = self.lossless.compress(payload)
        header = self._header(
            shape, xp.shape, data.dtype, conf, abs_eb, e, bands.shape[0],
            bands.shape[1], meta,
        )
        # declared plaintext size: lets decode bound the lossless inflation
        # (decompression-bomb guard); absent on pre-integrity v3 blobs
        header["payload_len"] = len(payload)
        blob = pack_container(header, body)
        return CompressionResult(
            blob=blob,
            ratio=data.nbytes / max(1, len(blob)),
            codes=bands if with_stats else None,
            meta=meta if with_stats else None,
        )

    def _header(
        self, shape, pshape, dtype, conf, abs_eb, step_exp, nbands, nblocks, meta
    ) -> Dict[str, Any]:
        return {
            "v": _VERSION3,
            "kind": self.kind,
            "spec": self.spec(),
            "shape": list(shape),
            "pshape": list(pshape),
            "dtype": np.dtype(dtype).str,
            "mode": conf.mode.value,
            "eb": float(conf.eb),
            "abs_eb": float(abs_eb),
            "step_exp": int(step_exp),
            "nbands": int(nbands),
            "nblocks": int(nblocks),
            "meta": pl_mod._clean_meta(meta),
        }

    # -- decompress ----------------------------------------------------------
    @staticmethod
    def _decompress_body(blob: bytes, header: Dict[str, Any], body_off: int) -> np.ndarray:
        spec = header["spec"]
        dtype = np.dtype(header["dtype"])
        shape = guard_shape(header["shape"], dtype.itemsize, "shape")
        pshape = guard_shape(header["pshape"], 8, "pshape")
        meta = header.get("meta") or {}
        nbands = guard_count(header["nbands"], 1 << 20, "nbands")
        nblocks = guard_count(header["nblocks"], 1 << 40, "nblocks")
        guard_alloc(nbands * nblocks * 8, "band grid")
        if nblocks == 0:
            return np.zeros(shape, dtype)
        backend = ll_mod.make(spec["lossless"])
        raw = container_body(blob, body_off)
        payload_len = header.get("payload_len")
        if payload_len is not None:
            payload_len = guard_alloc(payload_len, "payload_len")
            payload = backend.decompress_bounded(raw, payload_len)
            if len(payload) != payload_len:
                raise ContainerError(
                    f"transform body decompressed to {len(payload)} bytes; "
                    f"header declares {payload_len}"
                )
        else:  # pre-integrity v3 blob: no declared plaintext size
            payload = backend.decompress(raw)
        bands = _decode_bands(payload, nbands, nblocks)
        k = _unblockify(bands, pshape)
        step = 2.0 ** int(header["step_exp"])
        crop = tuple(slice(0, s) for s in (shape if shape else (1,)))
        if (
            meta.get("device")
            and meta.get("device_backend") == _jax_backend()
            and _decode_device_ok(pshape)
        ):
            from ..kernels.transform import ops as tops

            with tel.span("device_transfer", bytes=k.size * 4):
                out = np.asarray(
                    tops.inv_pipeline((k.astype(np.float64) * step).astype(np.float32)),
                    np.float64,
                )[crop]
        else:
            with tel.span("predict", bytes=k.size * 8):  # the inverse transform
                out = _inv_host(k.astype(np.float64) * step)[crop]
        if meta.get("nfail"):
            n = int(np.prod(shape)) if shape else 1
            mask = _unpack_mask(meta["fail_mask"], n).reshape(out.shape)
            out = out.copy()
            out[mask] = np.frombuffer(meta["fail_vals"], np.float64)
        return out.astype(dtype).reshape(shape)


def _jax_backend() -> str:
    """The active jax backend name."""
    import jax

    return str(jax.default_backend())


def _decode_device_ok(pshape: Tuple[int, ...]) -> bool:
    """Fused inverse on decode: real-TPU backends only, and only for blobs
    whose compress-time verification ran the same backend's kernel
    arithmetic (the caller checks ``device_backend``); every other blob
    takes the host float64 inverse, which compress always verifies."""
    from ..kernels import routing

    return len(pshape) in (1, 2) and routing.on_tpu()


# ---------------------------------------------------------------------------
# named pipeline
# ---------------------------------------------------------------------------

def sz3_transform(lossless: str = "zstd", device: str = "auto") -> TransformCompressor:
    """Pure transform coder (ZFP-family analogue)."""
    return TransformCompressor(lossless=lossless, device=device)
