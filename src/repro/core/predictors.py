"""Predictor module (paper §3.2 "Predictor", Appendix A.2).

Instances (paper Fig 1 right column):

  * LorenzoPredictor          — N-D Lorenzo [34] in *dual-quantization* form
                                (cuSZ, arXiv:2007.09625): data are prequantized
                                onto the 2*eb grid once, then the Lorenzo
                                stencil runs on exact integers.  Fully parallel
                                on TPU lanes (DESIGN.md §3.1); inverse is a
                                cumulative sum.  Error bound identical to SZ.
  * LorenzoSequentialPredictor— the paper-faithful SZ1.4 semantics (predict
                                from *decompressed* neighbours, lock-step);
                                realized as nested ``jax.lax.scan`` wavefronts.
                                Used as the fidelity oracle in tests.
  * RegressionPredictor       — SZ2 [8] block-wise hyperplane fit; coefficient
                                streams are themselves quantized (as in SZ2) so
                                they ride the same entropy stage.
  * InterpolationPredictor    — SZ3-Interp [17]: multi-level linear/cubic
                                spline interpolation with per-level feedback.
  * PatternPredictor          — SZ-Pastri [19]: periodic pattern + per-block
                                scaling for GAMESS ERI data.
  * CompositePredictor        — SZ2's multi-algorithm block selection (Lorenzo
                                vs regression via sampled error estimation,
                                generalized per paper §3.2 "composite
                                predictor").
  * ZeroPredictor             — predicts 0 (baseline / bypass).

All predictors drive the quantizer through its array-at-a-time interface; the
traversal strategy (global stencil / level order / block order) is the
predictor's own, which is exactly the paper's Algorithm-1-stays-generic claim.
"""
from __future__ import annotations

import abc
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from . import telemetry as tel
from .config import CompressionConfig
from .quantizers import QuantizerBase


# ---------------------------------------------------------------------------
# shared helpers
# ---------------------------------------------------------------------------

def lorenzo_filter(q: np.ndarray, order: int = 1) -> np.ndarray:
    """N-D Lorenzo difference filter on integers (zero-padded boundaries).

    Successive first differences along each axis == inclusion-exclusion
    Lorenzo stencil; applying it ``order`` times gives the higher-order
    variant [7].  Exact on int64.
    """
    d = q
    for _ in range(order):
        for ax in range(d.ndim):
            d = np.diff(d, axis=ax, prepend=0)
    return d


def lorenzo_inverse(d: np.ndarray, order: int = 1) -> np.ndarray:
    """Inverse filter: cumulative sums (the parallel-decode win of dual-quant)."""
    q = d
    for _ in range(order):
        for ax in range(q.ndim - 1, -1, -1):
            q = np.cumsum(q, axis=ax)
    return q


# -- per-block entry points (blockwise hybrid engine; paper §3.2 per-block
#    best-fit selection).  Axis 0 indexes blocks: the caller tiles ONCE via
#    pad_to_blocks/blockify and every candidate below runs batched over the
#    whole block set — no per-block re-padding or per-block python calls. ----

def pad_to_blocks(data: np.ndarray, b: int) -> Tuple[np.ndarray, Tuple[int, ...]]:
    """Replicate-pad every axis up to a multiple of ``b``; returns
    (padded, original_shape)."""
    pads = [(0, (-s) % b) for s in data.shape]
    return np.pad(data, pads, mode="edge"), data.shape


def blockify(x: np.ndarray, b: int) -> np.ndarray:
    """(n1, n2, ...) -> (nblocks, b, b, ...); all axes must divide by ``b``."""
    nd = x.ndim
    shape = []
    for s in x.shape:
        shape += [s // b, b]
    y = x.reshape(shape)
    perm = list(range(0, 2 * nd, 2)) + list(range(1, 2 * nd, 2))
    return y.transpose(perm).reshape((-1,) + (b,) * nd)


def unblockify(blocks: np.ndarray, padded_shape: Sequence[int], b: int) -> np.ndarray:
    """Inverse of :func:`blockify`."""
    nd = len(padded_shape)
    grid = [s // b for s in padded_shape]
    y = blocks.reshape(grid + [b] * nd)
    perm = []
    for i in range(nd):
        perm += [i, nd + i]
    return y.transpose(perm).reshape(tuple(padded_shape))


def block_coords(b: int, nd: int) -> List[np.ndarray]:
    """Centred per-axis coordinates, broadcast-ready against (nb, b, ..., b)."""
    cs = []
    for ax in range(nd):
        c = np.arange(b, dtype=np.float64) - (b - 1) / 2.0
        shape = [1] * nd
        shape[ax] = b
        cs.append(c.reshape(shape))
    return cs


def block_lorenzo_filter(qblocks: np.ndarray, order: int = 1) -> np.ndarray:
    """Block-local Lorenzo filter, batched: axis 0 indexes blocks, the stencil
    runs over axes 1..nd only (zero-padded block boundaries, as in SZ2's
    block-wise candidate)."""
    d = qblocks
    for _ in range(order):
        for ax in range(1, qblocks.ndim):
            d = np.diff(d, axis=ax, prepend=0)
    return d


def block_lorenzo_inverse(dblocks: np.ndarray, order: int = 1) -> np.ndarray:
    """Inverse of :func:`block_lorenzo_filter` (per-block cumulative sums)."""
    q = dblocks
    for _ in range(order):
        for ax in range(q.ndim - 1, 0, -1):
            q = np.cumsum(q, axis=ax)
    return q


def block_plane_fit(
    blocks: np.ndarray, b: int, eb: float
) -> Tuple[List[np.ndarray], np.ndarray, np.ndarray]:
    """Batched SZ2 hyperplane fit on pre-blockified data.

    Returns ``(coef_q, pred, bad)``: per-block quantized coefficient integers
    (nd+1 streams, SZ2 bounds — eb/2 intercept, eb/(2b) slopes), the
    prediction every decoder will rebuild from those quantized coefficients,
    and a per-block mask of non-finite fits (nan/inf inputs) whose
    coefficients were zeroed — callers must not let such blocks win a
    selection contest (their points belong on the unpredictable fail path).
    """
    nd = blocks.ndim - 1
    nb = blocks.shape[0]
    axes = tuple(range(1, nd + 1))
    cs = block_coords(b, nd)
    denom = (b**nd) * ((b * b - 1) / 12.0)
    with np.errstate(invalid="ignore", over="ignore"):
        raw = [blocks.mean(axis=axes)]
        raw += [(blocks * cs[k]).sum(axis=axes) / denom for k in range(nd)]
    bad = np.zeros(nb, bool)
    coef_q: List[np.ndarray] = []
    qhat: List[np.ndarray] = []
    for k, vals in enumerate(raw):
        ceb = eb / 2.0 if k == 0 else eb / (2.0 * b)
        scaled = vals / (2.0 * ceb)
        finite = np.isfinite(scaled) & (np.abs(scaled) < float(2**62))
        bad |= ~finite
        q = np.rint(np.where(finite, scaled, 0.0)).astype(np.int64)
        coef_q.append(q)
        qhat.append(q.astype(np.float64) * (2.0 * ceb))
    pred = qhat[0].reshape((nb,) + (1,) * nd)
    for k in range(nd):
        pred = pred + qhat[1 + k].reshape((nb,) + (1,) * nd) * cs[k]
    return coef_q, pred, bad


def code_bits(
    abs_errors: np.ndarray, abs_eb: float, radius: int = 32768
) -> float:
    """Mean estimated coded bits/element for given |prediction errors|.

    Errors become quantization-bin indices (e/(2*eb)); the entropy stage pays
    the empirical entropy of that bin population, and out-of-range points are
    stored raw (~64 bits).  This is the common currency the chunked engine
    contests whole pipelines in — mean |error| (the composite predictor's
    intra-pipeline criterion) cannot see that e.g. an all-zeros bin population
    costs almost nothing, and over-weights a few unpredictable outliers.
    """
    e = np.asarray(abs_errors, np.float64).reshape(-1)
    if e.size == 0:
        return 0.0
    return _int_code_bits(np.rint(e / (2.0 * abs_eb)), radius)


def _int_code_bits(q: np.ndarray, radius: int) -> float:
    """Entropy of integer bin indices + raw-storage cost of out-of-range ones."""
    q = np.abs(np.asarray(q).reshape(-1))
    if q.size == 0:
        return 0.0
    out = q >= radius
    inr = q[~out]
    bits = 64.0 * float(out.mean())
    if inr.size:
        _, counts = np.unique(inr, return_counts=True)
        p = counts / inr.size
        bits += float(-(p * np.log2(p)).sum()) * float((~out).mean())
    return bits


def lorenzo_residuals(
    sample: np.ndarray, abs_eb: float, order: int = 1, radius: int = 32768
) -> np.ndarray:
    """|Lorenzo prediction error| per sample point (paper: estimate_error).

    Same statistic the composite predictor scores Lorenzo blocks with: the
    magnitude of the prequantized stencil output, clipped at the code range.
    """
    x64 = np.asarray(sample, np.float64)
    if x64.size == 0:
        return np.zeros(0)
    q = np.rint(x64 / (2.0 * abs_eb))
    d = lorenzo_filter(q, order)
    est = np.abs(d) * (2.0 * abs_eb)
    return np.minimum(est, 2.0 * abs_eb * radius)


def regression_residuals(
    sample: np.ndarray, abs_eb: float, block_size: int
) -> np.ndarray:
    """|hyperplane-fit residual| per sample point, block-wise as in SZ2."""
    res, _ = _regression_fit(sample, block_size)
    return res


def _regression_fit(
    sample: np.ndarray, block_size: int
) -> Tuple[np.ndarray, List[np.ndarray]]:
    """(per-point |residual|, per-stream coefficient values) of the SZ2 fit."""
    b = max(2, int(block_size))
    x = np.asarray(sample, np.float64)
    if x.size == 0:
        return np.zeros(0), []
    if x.ndim == 0:
        x = x.reshape(1)
    nd = x.ndim
    reg = RegressionPredictor()
    xp, _ = reg._pad(x, b)
    blocks = reg._blockify(xp, b)
    axes = tuple(range(1, nd + 1))
    cs = reg._coords(b, nd)
    denom = (b**nd) * ((b * b - 1) / 12.0)
    # nan/inf blocks produce nan residuals/coefficients by design (estimation
    # only — such points ride the unpredictable fail path when coding)
    with np.errstate(invalid="ignore", over="ignore"):
        coeffs = [blocks.mean(axis=axes)]
        pred = coeffs[0].reshape((-1,) + (1,) * nd)
        for k in range(nd):
            beta = (blocks * cs[k]).sum(axis=axes) / denom
            coeffs.append(beta)
            pred = pred + beta.reshape((-1,) + (1,) * nd) * cs[k]
        return np.abs(blocks - pred).reshape(-1), coeffs


def regression_bits(
    sample: np.ndarray, abs_eb: float, block_size: int, radius: int = 32768
) -> float:
    """Estimated bits/element for the SZ2 regression stage INCLUDING the
    quantized, delta-coded coefficient streams — on small blocks the
    coefficients are a material share of the coded payload, so ranking
    regression by residuals alone flatters it."""
    b = max(2, int(block_size))
    res, coeffs = _regression_fit(sample, block_size)
    if res.size == 0:
        return 0.0
    bits = code_bits(res, abs_eb, radius)
    n = res.size
    for k, vals in enumerate(coeffs):
        ceb = abs_eb / 2.0 if k == 0 else abs_eb / (2.0 * b)
        q = np.rint(vals / (2.0 * ceb))
        bits += _int_code_bits(np.diff(q, prepend=0), radius) * vals.size / n
    return bits


def interp_residuals(sample: np.ndarray) -> np.ndarray:
    """|linear-interpolation residual| pooled over ALL levels, per axis.

    Mirrors the interpolation predictor's code population: each point is
    predicted once, at the level that fills it — fine levels are cheap on
    smooth data but coarse levels pay near-full amplitude, which a
    finest-level-only estimate would hide (and then mis-rank the pipeline on
    e.g. periodic data whose period exceeds the fine strides).
    """
    x = np.asarray(sample, np.float64)
    if x.size == 0:
        return np.zeros(0)
    errs = []
    for ax in range(x.ndim):
        dim = x.shape[ax]
        if dim < 3:
            continue
        s = 1
        while s < dim:
            mid = [slice(None)] * x.ndim
            left = [slice(None)] * x.ndim
            right = [slice(None)] * x.ndim
            mid[ax] = slice(s, None, 2 * s)
            n_mid = len(range(s, dim, 2 * s))
            left[ax] = slice(0, 2 * s * n_mid, 2 * s)
            right_idx = np.minimum(np.arange(n_mid) * 2 * s + 2 * s, dim - 1)
            xl = x[tuple(left)]
            xr = np.take(x, right_idx, axis=ax)
            pred = 0.5 * (xl + xr)
            errs.append(np.abs(x[tuple(mid)] - pred).reshape(-1))
            s *= 2
    if not errs:
        flat = x.reshape(-1)
        return np.abs(np.diff(flat, prepend=0.0))
    return np.concatenate(errs)


def _pack_mask(mask: np.ndarray) -> bytes:
    return np.packbits(mask.reshape(-1)).tobytes()


def _unpack_mask(buf: bytes, n: int) -> np.ndarray:
    return np.unpackbits(np.frombuffer(buf, np.uint8), count=n).astype(bool)


class Predictor(abc.ABC):
    name: str = "abstract"

    def estimate_error(
        self, sample: np.ndarray, abs_eb: float, conf: CompressionConfig
    ) -> Optional[float]:
        """Estimated entropy-coded bits/element this predictor would incur.

        The paper's ``estimate_error`` (§3.2), lifted from the composite
        predictor's block-wise Lorenzo-vs-regression contest to a first-class
        predictor capability so *whole pipelines* can be contested per data
        region (chunking.py).  Scores are comparable across predictors (see
        :func:`code_bits`).  ``None`` means "no cheap estimator" — callers
        fall back to trial compression of the sample.
        """
        return None

    @abc.abstractmethod
    def compress(
        self, data: np.ndarray, quantizer: QuantizerBase, conf: CompressionConfig
    ) -> Tuple[np.ndarray, Dict[str, Any]]:
        """Return (flat quantization codes, serializable meta)."""

    @abc.abstractmethod
    def decompress(
        self,
        codes: np.ndarray,
        shape: Tuple[int, ...],
        dtype: np.dtype,
        quantizer: QuantizerBase,
        conf: CompressionConfig,
        meta: Dict[str, Any],
    ) -> np.ndarray: ...


# ---------------------------------------------------------------------------
# Zero predictor
# ---------------------------------------------------------------------------

class ZeroPredictor(Predictor):
    name = "zero"

    def estimate_error(self, sample, abs_eb, conf):
        return code_bits(
            np.abs(np.asarray(sample, np.float64)), abs_eb, conf.quant_radius
        )

    def compress(self, data, quantizer, conf):
        codes, _ = quantizer.quantize(data.reshape(-1), np.zeros(data.size))
        return codes, {}

    def decompress(self, codes, shape, dtype, quantizer, conf, meta):
        recon = quantizer.recover(np.zeros(codes.size), codes)
        return recon.reshape(shape).astype(dtype)


# ---------------------------------------------------------------------------
# Dual-quantization Lorenzo (parallel; the TPU-native default)
# ---------------------------------------------------------------------------

def _breaks_bound(x64, q, recon_dev, quantizer: QuantizerBase) -> np.ndarray:
    """Where either decode route's reconstruction of the prequantized ``q``
    lies more than ``eb`` from ``x64``: the host's (``dequantize_int``,
    float64) or the decode kernel's (``recon_dev``, float32)."""
    eb = quantizer.eb
    fail = np.abs(quantizer.dequantize_int(q).astype(np.float64) - x64) > eb
    fail |= np.abs(recon_dev.astype(np.float64) - x64) > eb
    return fail


def lorenzo_device_fail(
    data: np.ndarray, draw: np.ndarray, quantizer: QuantizerBase
) -> np.ndarray:
    """The full host bound check of the device Lorenzo route.

    ``draw`` is the encode kernel's raw differences; both decode routes are
    run in full (``lorenzo_inverse``, and the decode kernel's exact int32
    prefix sums and float32 dequantization).  The device route certifies
    the check on the chip and re-checks only the points it flags; this is
    its fallback when too many are flagged, and the oracle its tests
    compare with.
    """
    from ..kernels.lorenzo import ops as lops

    q = lorenzo_inverse(draw.astype(np.int64), 1)
    recon_dev = lops.decode_pipeline(draw, eb=quantizer.eb)
    return _breaks_bound(np.asarray(data, np.float64), q, recon_dev, quantizer)


class LorenzoPredictor(Predictor):
    """Parallel N-D Lorenzo via dual-quantization (DESIGN.md §3.1).

    Two execution routes behind the same codes/meta contract:

      * numpy (default on CPU) — ``prequantize`` + ``lorenzo_filter`` on
        int64, any ndim/order.
      * device — the fused Pallas prequant+Lorenzo kernels
        (``kernels/lorenzo``), for order-1 float32 1-D/2-D data whose
        prequantized magnitudes pass the ``PIPELINE_SAFE`` int32 guard.
        The kernel computes q in float32, so the chip then flags every
        point whose reconstruction under either decode route can come near
        the bound; the host re-derives those EXACTLY as both decode routes
        will compute them and patches any bound-breaking point into the
        fail channel (:func:`lorenzo_device_fail` when too many are
        flagged) — the error bound is therefore identical to the numpy
        route's.  ``device="auto"`` engages on real TPUs only (interpret-mode Pallas on CPU is far
        slower than numpy); ``"force"`` engages everywhere (tests);
        ``"off"`` never.
    """

    name = "lorenzo"

    def __init__(self, order: Optional[int] = None, device: str = "auto"):
        self.order = order
        self.device = device

    #: below this many elements the kernel dispatch overhead dominates
    _DEVICE_MIN_SIZE = 4096

    def estimate_error(self, sample, abs_eb, conf):
        return code_bits(
            lorenzo_residuals(
                sample, abs_eb, self.order or conf.lorenzo_order, conf.quant_radius
            ),
            abs_eb,
            conf.quant_radius,
        )

    # -- device routing -----------------------------------------------------
    def _device_ok(self, data: np.ndarray, eb: float, order: int) -> bool:
        if self.device == "off" or order != 1:
            return False
        if (
            data.ndim not in (1, 2)
            or data.dtype != np.float32
            or data.size < self._DEVICE_MIN_SIZE
        ):
            return False
        from ..kernels import routing
        from ..kernels.lorenzo import ops as lops

        absmax = float(np.abs(data).max())
        if not np.isfinite(absmax) or absmax / (2.0 * eb) >= lops.PIPELINE_SAFE:
            return False
        return self.device == "force" or routing.on_tpu()

    def _compress_device(self, data, quantizer):
        from ..kernels.lorenzo import ops as lops

        eb = quantizer.eb
        with tel.span("device_transfer", bytes=data.nbytes):
            enc = lops.encode_checked(
                data, eb=eb, radius=quantizer.radius, code_dtype=quantizer.code_dtype
            )
        tel.count("verify_device")
        # The kernel prequantizes in float32 (vs float64 on the numpy route);
        # the chip flagged every point that can break the bound under either
        # decode route's arithmetic, and the host re-checks those exactly.
        # Stragglers ride the fail channel (raw values).
        with tel.span("verify", bytes=data.nbytes):
            if enc.overflow:
                tel.count("verify_host_fallback")
                draw = np.asarray(enc.draw).reshape(data.shape)
                fail = lorenzo_device_fail(data, draw, quantizer).reshape(-1)
                flat = draw.reshape(-1)
                unpred = flat[np.abs(flat) >= quantizer.radius]
            else:
                tel.count("verify_rechecked", enc.n)
                x64 = data.reshape(-1)[enc.idx].astype(np.float64)
                bad = _breaks_bound(x64, enc.q, enc.recon, quantizer)
                fail = np.zeros(data.size, bool)
                fail[enc.idx[bad]] = True
                unpred = enc.diffs[enc.codes.reshape(-1)[enc.idx] == 0]
        if unpred.size:
            quantizer._store_unpred_int(unpred)
        meta: Dict[str, Any] = {"order": 1, "nfail": int(fail.sum()), "device": 1}
        if meta["nfail"]:
            meta["fail_mask"] = _pack_mask(fail)
            meta["fail_vals"] = data.reshape(-1)[fail].astype(np.float64).tobytes()
        return enc.codes.reshape(-1), meta

    def _decode_device_ok(self, shape, dtype, eb: float) -> bool:
        if self.device == "off" or len(shape) not in (1, 2):
            return False
        if np.dtype(dtype) != np.float32:
            return False
        from ..kernels import routing

        return self.device == "force" or routing.on_tpu()

    # -- the two directions --------------------------------------------------
    def compress(self, data, quantizer, conf):
        order = self.order or conf.lorenzo_order
        if self._device_ok(np.asarray(data), quantizer.eb, order):
            return self._compress_device(np.asarray(data), quantizer)
        q, recon, fail = quantizer.prequantize(data)
        d = lorenzo_filter(q, order)
        codes = quantizer.quantize_int_diff(d.reshape(-1))
        meta: Dict[str, Any] = {"order": order, "nfail": int(fail.sum())}
        if meta["nfail"]:
            meta["fail_mask"] = _pack_mask(fail)
            meta["fail_vals"] = np.asarray(data, np.float64)[fail].tobytes()
        return codes, meta

    def decompress(self, codes, shape, dtype, quantizer, conf, meta):
        order = int(meta["order"])
        d = quantizer.recover_int_diff(codes).reshape(shape)
        if meta.get("device") and self._decode_device_ok(shape, dtype, quantizer.eb):
            # compress verified this blob against the kernel decode's float32
            # arithmetic, so the fused route is bound-exact here
            from ..kernels.lorenzo import ops as lops

            with tel.span("device_transfer", bytes=d.size * 4):
                out = lops.decode_pipeline(d.astype(np.int32), eb=quantizer.eb).astype(dtype)
        else:
            q = lorenzo_inverse(d, order)
            out = quantizer.dequantize_int(q).astype(dtype)
        if meta.get("nfail"):
            mask = _unpack_mask(meta["fail_mask"], int(np.prod(shape))).reshape(shape)
            out[mask] = np.frombuffer(meta["fail_vals"], np.float64).astype(dtype)
        return out


# ---------------------------------------------------------------------------
# Sequential Lorenzo (paper-faithful SZ1.4 semantics; jax.lax.scan wavefront)
# ---------------------------------------------------------------------------

class LorenzoSequentialPredictor(Predictor):
    """Predict each point from *decompressed* neighbours, in raster scan order.

    This is the paper-faithful SZ1.4/SZ2 Lorenzo semantics: the value used for
    prediction is the reconstruction the decompressor will have, so the
    quantization-error feedback travels through the scan.  The data dependence
    is a wavefront; we express it as ONE ``jax.lax.scan`` over the flattened
    array with a ring buffer carrying the trailing reconstruction window
    (size = sum of strides + 1), gathering the 2^ndim - 1 inclusion-exclusion
    neighbours by modular index.  Out-of-range neighbours read as 0 (SZ
    convention), enforced with precomputed validity masks.

    Used as the fidelity oracle for the parallel dual-quant variant and as the
    ``fidelity="paper"`` path for host-side compression.  Any ndim >= 1.
    """

    name = "lorenzo_seq"

    def estimate_error(self, sample, abs_eb, conf):
        # same stencil statistics as the parallel dual-quant variant
        return code_bits(
            lorenzo_residuals(sample, abs_eb, 1, conf.quant_radius),
            abs_eb,
            conf.quant_radius,
        )

    @staticmethod
    def _stencil(shape: Tuple[int, ...]):
        """Inclusion-exclusion neighbour set: (flat_offset, sign, valid_mask)."""
        nd = len(shape)
        strides = np.ones(nd, np.int64)
        for k in range(nd - 2, -1, -1):
            strides[k] = strides[k + 1] * shape[k + 1]
        idx = np.indices(shape).reshape(nd, -1)
        subsets = []
        for bits in range(1, 1 << nd):
            axes = [k for k in range(nd) if bits & (1 << k)]
            off = int(sum(strides[k] for k in axes))
            sign = 1.0 if (len(axes) % 2 == 1) else -1.0
            valid = np.ones(idx.shape[1], bool)
            for k in axes:
                valid &= idx[k] >= 1
            subsets.append((off, sign, valid))
        return subsets

    def _run_scan(self, shape, eb, radius, dtype, mode, xs_arrays):
        """mode: 'compress_linear' | 'compress_aligned' | 'decompress'."""
        import jax
        import jax.numpy as jnp
        from jax import enable_x64

        subsets = self._stencil(shape)
        L = max(off for off, _, _ in subsets) + 1
        two_eb = 2.0 * eb
        out_dtype = np.dtype(dtype)

        def cast(v):
            if out_dtype == np.float64:
                return v
            return v.astype(jnp.dtype(out_dtype)).astype(jnp.float64)

        with enable_x64():

            def predict(buf, i, masks):
                pred = 0.0
                for s, (off, sign, _) in enumerate(subsets):
                    v = buf[(i - off) % L] * masks[s]
                    pred = pred + sign * v
                return pred

            if mode.startswith("compress"):
                aligned = mode.endswith("aligned")

                def step(carry, xin):
                    buf, i = carry
                    x = xin[0]
                    masks = xin[1:]
                    pred = predict(buf, i, masks)
                    d = x - pred
                    q = jnp.rint(d / two_eb)
                    in_range = jnp.abs(q) < radius
                    recon_try = cast(pred + q * two_eb)
                    ok = in_range & (jnp.abs(recon_try - x) <= eb)
                    if aligned:
                        cand = cast(pred + q * two_eb)
                        bad = jnp.abs(cand - x) > eb
                        recon_un = jnp.where(bad, x, cand)
                    else:
                        recon_un = x
                    recon = jnp.where(ok, recon_try, recon_un)
                    code = jnp.where(ok, q.astype(jnp.int64) + radius, 0)
                    buf = buf.at[i % L].set(recon)
                    return (buf, i + 1), (code, recon, pred)

                carry = (jnp.zeros(L), jnp.asarray(0))
                _, (codes, recon, pred) = jax.lax.scan(step, carry, xs_arrays)
                return np.asarray(codes), np.asarray(recon), np.asarray(pred)

            def dstep(carry, xin):
                buf, i = carry
                code, un_q, un_esc, un_raw = xin[0], xin[1], xin[2], xin[3]
                masks = xin[4:]
                pred = predict(buf, i, masks)
                q = code.astype(jnp.float64) - radius
                recon_pred = cast(pred + q * two_eb)
                recon_un = jnp.where(un_esc, un_raw, cast(pred + un_q * two_eb))
                recon = jnp.where(code == 0, recon_un, recon_pred)
                buf = buf.at[i % L].set(recon)
                return (buf, i + 1), recon

            carry = (jnp.zeros(L), jnp.asarray(0))
            _, recon = jax.lax.scan(dstep, carry, xs_arrays)
            return np.asarray(recon)

    def compress(self, data, quantizer, conf):
        x64 = np.ascontiguousarray(data, np.float64)
        shape = x64.shape
        subsets = self._stencil(shape)
        masks = tuple(m.astype(np.float64) for _, _, m in subsets)
        mode = (
            "compress_aligned"
            if quantizer.name == "unpred_aware"
            else "compress_linear"
        )
        codes, recon, pred = self._run_scan(
            shape,
            quantizer.eb,
            quantizer.radius,
            np.dtype(data.dtype),
            mode,
            (x64.reshape(-1),) + masks,
        )
        un = codes == 0
        if un.any():
            quantizer.absorb_unpred(x64.reshape(-1)[un], pred[un])
        return codes.astype(quantizer.code_dtype), {}

    def decompress(self, codes, shape, dtype, quantizer, conf, meta):
        n = int(np.prod(shape))
        subsets = self._stencil(tuple(shape))
        masks = tuple(m.astype(np.float64) for _, _, m in subsets)
        un = codes == 0
        un_q = np.zeros(n, np.float64)
        un_esc = np.zeros(n, bool)
        un_raw = np.zeros(n, np.float64)
        cnt = int(un.sum())
        if cnt:
            q, esc, raw = quantizer.emit_unpred_channels(cnt)
            pos = np.flatnonzero(un)
            un_q[pos] = q
            un_esc[pos] = esc
            un_raw[pos] = raw
        recon = self._run_scan(
            tuple(shape),
            quantizer.eb,
            quantizer.radius,
            np.dtype(dtype),
            "decompress",
            (codes.astype(np.int64), un_q, un_esc, un_raw) + masks,
        )
        return recon.reshape(shape).astype(dtype)


# ---------------------------------------------------------------------------
# Regression predictor (SZ2)
# ---------------------------------------------------------------------------

class RegressionPredictor(Predictor):
    """Block-wise hyperplane fit (SZ2 [8]).

    For each b^d block the least-squares plane  f(i) = beta0 + sum_k beta_k*i_k
    is fitted (closed form — centred coordinates make the normal equations
    diagonal, i.e. a batched reduction instead of a solve: MXU/VPU friendly).
    Coefficients are quantized (eb/2b per slope, eb/2 for the intercept, as in
    SZ2) and their codes ride the shared entropy stage.  Edge blocks are
    handled by replicate-padding; the original extent is restored on decode.
    """

    name = "regression"

    def estimate_error(self, sample, abs_eb, conf):
        return regression_bits(sample, abs_eb, conf.block_size, conf.quant_radius)

    # thin wrappers over the module-level block helpers (kept as methods for
    # API stability; the hybrid engine calls the module functions directly)
    def _pad(self, data: np.ndarray, b: int) -> Tuple[np.ndarray, Tuple[int, ...]]:
        return pad_to_blocks(data, b)

    def _blockify(self, x: np.ndarray, b: int) -> np.ndarray:
        return blockify(x, b)

    def _unblockify(self, blocks: np.ndarray, padded_shape, b: int) -> np.ndarray:
        return unblockify(blocks, tuple(padded_shape), b)

    def _coords(self, b: int, nd: int) -> List[np.ndarray]:
        return block_coords(b, nd)

    def compress(self, data, quantizer, conf):
        b = int(conf.block_size)
        nd = data.ndim
        x, orig_shape = self._pad(np.asarray(data, np.float64), b)
        blocks = self._blockify(x, b)  # (nb, b, ..., b)
        nb = blocks.shape[0]
        axes = tuple(range(1, nd + 1))
        cs = self._coords(b, nd)
        denom = (b**nd) * ((b * b - 1) / 12.0)  # sum of centred c^2 per axis
        beta0 = blocks.mean(axis=axes)
        betas = [
            (blocks * cs[k]).sum(axis=axes) / denom for k in range(nd)
        ]
        # Quantize coefficients (SZ2: slopes at eb/2b, intercept at eb/2) so
        # the decompressor sees identical planes.
        coef_codes: List[np.ndarray] = []
        eb = quantizer.eb
        qhat = []
        for vals, ceb in [(beta0, eb / 2.0)] + [(bt, eb / (2.0 * b)) for bt in betas]:
            q = np.rint(vals / (2.0 * ceb)).astype(np.int64)
            qhat.append(q.astype(np.float64) * (2.0 * ceb))
            coef_codes.append(q)
        # delta-encode coefficient streams (adjacent blocks correlate)
        cc = []
        for q in coef_codes:
            cc.append(quantizer.quantize_int_diff(np.diff(q, prepend=0)))
        pred = qhat[0].reshape((nb,) + (1,) * nd)
        for k in range(nd):
            pred = pred + qhat[1 + k].reshape((nb,) + (1,) * nd) * cs[k]
        dcodes, _ = quantizer.quantize(blocks.reshape(-1), pred.reshape(-1))
        codes = np.concatenate([c.astype(dcodes.dtype) for c in cc] + [dcodes])
        meta = {
            "orig_shape": list(orig_shape),
            "padded_shape": list(x.shape),
            "nb": int(nb),
            "b": b,
        }
        return codes, meta

    def decompress(self, codes, shape, dtype, quantizer, conf, meta):
        b = int(meta["b"])
        nb = int(meta["nb"])
        padded_shape = tuple(meta["padded_shape"])
        nd = len(padded_shape)
        eb = quantizer.eb
        pos = 0
        qhat = []
        for k in range(nd + 1):
            dq = quantizer.recover_int_diff(codes[pos : pos + nb])
            pos += nb
            q = np.cumsum(dq)
            ceb = eb / 2.0 if k == 0 else eb / (2.0 * b)
            qhat.append(q.astype(np.float64) * (2.0 * ceb))
        cs = self._coords(b, nd)
        pred = qhat[0].reshape((nb,) + (1,) * nd)
        for k in range(nd):
            pred = pred + qhat[1 + k].reshape((nb,) + (1,) * nd) * cs[k]
        recon = quantizer.recover(pred.reshape(-1), codes[pos:])
        blocks = recon.reshape((nb,) + (b,) * nd)
        out = self._unblockify(blocks, padded_shape, b)
        sl = tuple(slice(0, s) for s in meta["orig_shape"])
        return out[sl].astype(dtype)


# ---------------------------------------------------------------------------
# Interpolation predictor (SZ3-Interp)
# ---------------------------------------------------------------------------

class InterpolationPredictor(Predictor):
    """Multi-level spline interpolation [17] with per-level feedback.

    Levels run coarse->fine; within a level each axis pass predicts the
    odd-stride points from already-reconstructed neighbours via linear or
    cubic interpolation.  Every point within a pass is independent →
    log2(max_dim) * ndim fully-parallel passes (DESIGN.md §3 item 5).
    """

    name = "interp"

    def __init__(self, kind: Optional[str] = None):
        self.kind = kind

    def estimate_error(self, sample, abs_eb, conf):
        return code_bits(interp_residuals(sample), abs_eb, conf.quant_radius)

    # -- pass geometry -------------------------------------------------------
    def _passes(self, shape: Tuple[int, ...]):
        """Yield (axis, stride, coords_per_axis) for every pass, coarse->fine."""
        max_dim = max(shape)
        level = max(1, int(np.ceil(np.log2(max(2, max_dim)))))
        for lev in range(level, 0, -1):
            s = 1 << (lev - 1)
            if s >= max_dim:
                continue
            for ax in range(len(shape)):
                if s >= shape[ax] and not any(
                    2 * s < shape[j] for j in range(len(shape))
                ):
                    pass
                targets = np.arange(s, shape[ax], 2 * s)
                if targets.size == 0:
                    continue
                other: List[np.ndarray] = []
                for j in range(len(shape)):
                    if j == ax:
                        other.append(targets)
                    elif j < ax:
                        other.append(np.arange(0, shape[j], s))
                    else:
                        other.append(np.arange(0, shape[j], 2 * s))
                yield ax, s, other

    def _predict_pass(
        self, xhat: np.ndarray, ax: int, s: int, coords: Sequence[np.ndarray], kind: str
    ) -> Tuple[np.ndarray, Tuple[np.ndarray, ...]]:
        """Compute predictions for one pass; returns (pred, index tuple)."""
        shape = xhat.shape
        ts = coords[ax]
        dim = shape[ax]

        def grab(offsets: np.ndarray) -> np.ndarray:
            cs = list(coords)
            cs[ax] = offsets
            return xhat[np.ix_(*cs)]

        left = grab(ts - s)
        has_r = ts + s < dim
        right_idx = np.where(has_r, ts + s, ts - s)
        right = grab(right_idx)
        lin = 0.5 * (left + right)
        copy = left
        shape_bc = [1] * xhat.ndim
        shape_bc[ax] = ts.size
        has_r_bc = has_r.reshape(shape_bc)
        pred = np.where(has_r_bc, lin, copy)
        if kind == "cubic":
            has_ll = ts - 3 * s >= 0
            has_rr = ts + 3 * s < dim
            full = has_ll & has_rr & has_r
            if full.any():
                ll = grab(np.where(has_ll, ts - 3 * s, ts - s))
                rr = grab(np.where(has_rr, ts + 3 * s, ts - s))
                cubic = (-ll + 9.0 * left + 9.0 * right - rr) / 16.0
                pred = np.where(full.reshape(shape_bc), cubic, pred)
        return pred, np.ix_(*coords)

    def compress(self, data, quantizer, conf):
        kind = self.kind or conf.interp_kind
        x64 = np.asarray(data, np.float64)
        shape = x64.shape
        xhat = np.zeros_like(x64)
        all_codes: List[np.ndarray] = []
        # anchor point: origin, predicted as 0
        origin = (0,) * x64.ndim
        c0, r0 = quantizer.quantize(x64[origin].reshape(1), np.zeros(1))
        xhat[origin] = r0[0]
        all_codes.append(c0)
        for ax, s, coords in self._passes(shape):
            pred, idx = self._predict_pass(xhat, ax, s, coords, kind)
            codes, recon = quantizer.quantize(x64[idx].reshape(-1), pred.reshape(-1))
            xhat[idx] = recon.reshape(pred.shape)
            all_codes.append(codes)
        return np.concatenate(all_codes), {"kind": kind}

    def decompress(self, codes, shape, dtype, quantizer, conf, meta):
        kind = meta["kind"]
        xhat = np.zeros(shape, np.float64)
        pos = 0
        origin = (0,) * len(shape)
        r0 = quantizer.recover(np.zeros(1), codes[pos : pos + 1])
        xhat[origin] = r0[0]
        pos += 1
        for ax, s, coords in self._passes(tuple(shape)):
            pred, idx = self._predict_pass(xhat, ax, s, coords, kind)
            n = pred.size
            recon = quantizer.recover(pred.reshape(-1), codes[pos : pos + n])
            xhat[idx] = recon.reshape(pred.shape)
            pos += n
        return xhat.astype(dtype)


# ---------------------------------------------------------------------------
# Pattern predictor (SZ-Pastri)
# ---------------------------------------------------------------------------

class PatternPredictor(Predictor):
    """Periodic pattern + per-block scaling (SZ-Pastri [19]).

    GAMESS ERI blocks repeat a template scaled per block; the template is
    chosen as the max-energy window, itself quantized and sent first, then a
    per-block least-squares scale (delta-quantized), then the residual codes.
    The three code populations are exactly paper Fig 3's data/pattern/scale
    split (the benchmark slices them by the offsets in meta).
    """

    name = "pattern"

    def __init__(self, pattern_size: Optional[int] = None):
        self.pattern_size = pattern_size

    @staticmethod
    def detect_period(x: np.ndarray, lo: int = 4, hi: int = 4096) -> int:
        """Autocorrelation peak via FFT (preprocessing step of SZ-Pastri)."""
        n = min(x.size, 1 << 16)
        v = np.asarray(x[:n], np.float64)
        v = v - v.mean()
        f = np.fft.rfft(v, n=2 * n)
        ac = np.fft.irfft(f * np.conj(f))[: n // 2]
        hi = min(hi, ac.size - 1)
        if hi <= lo:
            return max(2, min(64, x.size))
        seg = ac[lo : hi + 1]
        return int(lo + np.argmax(seg))

    def compress(self, data, quantizer, conf):
        flat = np.asarray(data, np.float64).reshape(-1)
        n = flat.size
        P = self.pattern_size or conf.pattern_size or self.detect_period(flat)
        P = max(2, min(P, n))
        nb = n // P
        tail = n - nb * P
        body = flat[: nb * P].reshape(nb, P)
        # template: max-energy block, quantized through the shared quantizer
        t_idx = int(np.argmax((body * body).sum(axis=1))) if nb else 0
        template = body[t_idx] if nb else flat[:P]
        tcodes, that = quantizer.quantize(template, np.zeros(P))
        that = that.astype(np.float64)
        tt = float((that * that).sum())
        if tt <= 0:
            scales = np.zeros(nb)
        else:
            scales = body @ that / tt
        # quantize scales (delta, integer stream)
        s_eb = quantizer.eb / (max(1.0, float(np.max(np.abs(that))) ) )
        sq = np.rint(scales / (2.0 * s_eb)).astype(np.int64)
        scodes = quantizer.quantize_int_diff(np.diff(sq, prepend=0))
        shat = sq.astype(np.float64) * (2.0 * s_eb)
        pred = shat[:, None] * that[None, :]
        dcodes, _ = quantizer.quantize(body.reshape(-1), pred.reshape(-1))
        parts = [tcodes, scodes.astype(tcodes.dtype), dcodes]
        if tail:
            # tail: predict with the template prefix scaled by the last scale
            tp = (shat[-1] if nb else 0.0) * that[:tail]
            tl_codes, _ = quantizer.quantize(flat[nb * P :], tp)
            parts.append(tl_codes)
        codes = np.concatenate(parts)
        meta = {
            "P": int(P),
            "nb": int(nb),
            "tail": int(tail),
            "s_eb": float(s_eb),
            "sections": [int(tcodes.size), int(scodes.size), int(dcodes.size)],
        }
        return codes, meta

    def decompress(self, codes, shape, dtype, quantizer, conf, meta):
        P, nb, tail = int(meta["P"]), int(meta["nb"]), int(meta["tail"])
        s_eb = float(meta["s_eb"])
        pos = 0
        that = quantizer.recover(np.zeros(P), codes[pos : pos + P]).astype(np.float64)
        pos += P
        dsq = quantizer.recover_int_diff(codes[pos : pos + nb])
        pos += nb
        shat = np.cumsum(dsq).astype(np.float64) * (2.0 * s_eb)
        pred = shat[:, None] * that[None, :]
        body = quantizer.recover(pred.reshape(-1), codes[pos : pos + nb * P])
        pos += nb * P
        out = np.empty(int(np.prod(shape)), np.float64)
        out[: nb * P] = body
        if tail:
            tp = (shat[-1] if nb else 0.0) * that[:tail]
            out[nb * P :] = quantizer.recover(tp, codes[pos : pos + tail])
        return out.reshape(shape).astype(dtype)


# ---------------------------------------------------------------------------
# Composite predictor (SZ2 multi-algorithm selection)
# ---------------------------------------------------------------------------

class CompositePredictor(Predictor):
    """Block-wise best-of selection between Lorenzo and regression (SZ2 [8]).

    Per block the expected absolute error of each candidate is estimated on a
    strided sample (paper: ``estimate_error``); the winner's codes are kept.
    Lorenzo runs block-locally on prequantized integers (dual-quant) so the
    decoder never needs cross-candidate reconstructions; see DESIGN.md §3.
    Selection flags are packed into meta (1 bit per block).
    """

    name = "composite"

    def estimate_error(self, sample, abs_eb, conf):
        # best-of its two candidates, mirroring the block-wise contest below,
        # plus the 1-bit-per-block selection flag it must also code
        flag_bits = 1.0 / float(max(2, conf.block_size)) ** max(1, sample.ndim)
        return flag_bits + min(
            code_bits(
                lorenzo_residuals(sample, abs_eb, 1, conf.quant_radius),
                abs_eb,
                conf.quant_radius,
            ),
            regression_bits(sample, abs_eb, conf.block_size, conf.quant_radius),
        )

    def compress(self, data, quantizer, conf):
        b = int(conf.block_size)
        nd = data.ndim
        reg = RegressionPredictor()
        x64 = np.asarray(data, np.float64)
        x, orig_shape = reg._pad(x64, b)
        blocks = reg._blockify(x, b)  # (nb, b^d)
        nb = blocks.shape[0]
        axes = tuple(range(1, nd + 1))
        eb = quantizer.eb

        # --- candidate 1: block-local dual-quant Lorenzo ---
        qfull, recon_pre, fail = quantizer.prequantize(blocks)
        d_lor = qfull
        for ax in axes:
            d_lor = np.diff(d_lor, axis=ax, prepend=0)

        # --- candidate 2: regression plane from quantized coefficients ---
        cs = reg._coords(b, nd)
        denom = (b**nd) * ((b * b - 1) / 12.0)
        beta0 = blocks.mean(axis=axes)
        betas = [(blocks * cs[k]).sum(axis=axes) / denom for k in range(nd)]
        qhat, coef_q = [], []
        # non-finite block means (nan/inf inputs) quantize to garbage here by
        # design — those blocks lose the contest or their points ride the
        # unpredictable fail path, so the cast is safe and warning-worthless
        with np.errstate(invalid="ignore", over="ignore"):
            for vals, ceb in [(beta0, eb / 2.0)] + [(bt, eb / (2.0 * b)) for bt in betas]:
                qc = np.rint(vals / (2.0 * ceb)).astype(np.int64)
                coef_q.append(qc)
                qhat.append(qc.astype(np.float64) * (2.0 * ceb))
        pred_reg = qhat[0].reshape((nb,) + (1,) * nd)
        for k in range(nd):
            pred_reg = pred_reg + qhat[1 + k].reshape((nb,) + (1,) * nd) * cs[k]

        # --- estimation on strided samples (paper: estimate_error) ---
        stride = max(1, int(conf.sample_stride))
        sample = (slice(None),) + (slice(0, b, stride),) * nd
        est_lor = (np.abs(d_lor[sample]) * (2.0 * eb)).clip(max=2.0 * eb * quantizer.radius)
        est_lor = est_lor.reshape(nb, -1).sum(axis=1)
        est_reg = np.abs(blocks[sample] - pred_reg[sample]).reshape(nb, -1).sum(axis=1)
        use_reg = est_reg < est_lor

        # --- emit codes: per-block winner, streams interleaved block-major ---
        # regression coefficient streams are only kept for winning blocks
        coef_codes = []
        for qc in coef_q:
            kept = qc[use_reg]
            coef_codes.append(quantizer.quantize_int_diff(np.diff(kept, prepend=0)))
        lor_codes = quantizer.quantize_int_diff(d_lor[~use_reg].reshape(-1))
        dcodes, _ = quantizer.quantize(
            blocks[use_reg].reshape(-1), pred_reg[use_reg].reshape(-1)
        )
        codes = np.concatenate(
            [c.astype(lor_codes.dtype) for c in coef_codes] + [lor_codes, dcodes]
        )
        meta = {
            "orig_shape": list(orig_shape),
            "padded_shape": list(x.shape),
            "b": b,
            "nb": int(nb),
            "flags": _pack_mask(use_reg),
            "n_reg": int(use_reg.sum()),
            "nfail": int(fail.sum()),
        }
        if meta["nfail"]:
            fail_full = np.zeros_like(fail, bool)
            fail_full = fail
            meta["fail_mask"] = _pack_mask(fail_full[~use_reg])
            meta["fail_vals"] = blocks[~use_reg][fail_full[~use_reg]].tobytes()
        return codes, meta

    def decompress(self, codes, shape, dtype, quantizer, conf, meta):
        b = int(meta["b"])
        nb = int(meta["nb"])
        padded_shape = tuple(meta["padded_shape"])
        nd = len(padded_shape)
        eb = quantizer.eb
        use_reg = _unpack_mask(meta["flags"], nb)
        n_reg = int(meta["n_reg"])
        n_lor = nb - n_reg
        reg = RegressionPredictor()
        cs = reg._coords(b, nd)
        pos = 0
        qhat = []
        for k in range(nd + 1):
            dq = quantizer.recover_int_diff(codes[pos : pos + n_reg])
            pos += n_reg
            ceb = eb / 2.0 if k == 0 else eb / (2.0 * b)
            qhat.append(np.cumsum(dq).astype(np.float64) * (2.0 * ceb))
        blk_elems = b**nd
        d_lor = quantizer.recover_int_diff(codes[pos : pos + n_lor * blk_elems])
        pos += n_lor * blk_elems
        d_lor = d_lor.reshape((n_lor,) + (b,) * nd)
        qfull = d_lor
        for ax in range(nd, 0, -1):
            qfull = np.cumsum(qfull, axis=ax)
        lor_blocks = quantizer.dequantize_int(qfull).astype(np.float64)
        if meta.get("nfail"):
            fl = _unpack_mask(meta["fail_mask"], n_lor * blk_elems).reshape(
                (n_lor,) + (b,) * nd
            )
            lor_blocks[fl] = np.frombuffer(meta["fail_vals"], np.float64)
        pred_reg = qhat[0].reshape((n_reg,) + (1,) * nd)
        for k in range(nd):
            pred_reg = pred_reg + qhat[1 + k].reshape((n_reg,) + (1,) * nd) * cs[k]
        reg_recon = quantizer.recover(pred_reg.reshape(-1), codes[pos:])
        blocks = np.empty((nb,) + (b,) * nd, np.float64)
        blocks[~use_reg] = lor_blocks
        blocks[use_reg] = reg_recon.reshape((n_reg,) + (b,) * nd)
        out = reg._unblockify(blocks, padded_shape, b)
        sl = tuple(slice(0, s) for s in meta["orig_shape"])
        return out[sl].astype(dtype)


_REGISTRY = {
    "zero": ZeroPredictor,
    "lorenzo": LorenzoPredictor,
    "lorenzo_seq": LorenzoSequentialPredictor,
    "regression": RegressionPredictor,
    "interp": InterpolationPredictor,
    "pattern": PatternPredictor,
    "composite": CompositePredictor,
}


def register(name: str, cls) -> None:
    _REGISTRY[name] = cls


def make(name: str, **kw) -> Predictor:
    return _REGISTRY[name](**kw)
