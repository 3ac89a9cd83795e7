"""Block-level multi-predictor hybrid engine (paper §3.2, v5 container).

The chunked engine (chunking.py) contests whole pipelines per CHUNK; the
paper's second headline contribution is finer: *per-block* best-fit predictor
selection via an error-estimation criterion (SZ3 §3.2 — the same idea behind
SZ2's block-granular Lorenzo/regression contest and the online SZ-vs-ZFP
selector of Tao et al. 2018).  A chunk mixing regimes (smooth region next to
an oscillatory one) pays for whichever single predictor wins on the sampled
sub-block; this module closes that gap.

:class:`BlockHybridCompressor` (factory ``sz3_hybrid``) tiles the array into
fixed-size blocks (256 for 1-D, 16x16 for 2-D, 8x8x8 for 3-D), scores FOUR
candidates per block with the code-bits criterion, and keeps the per-block
winner:

  tag 0  zero        — predict 0 on the prequantized grid (the constant /
                       zero-block fast path; also the least-bad fallback on
                       oscillatory data, where differencing doubles noise)
  tag 1  lorenzo1    — block-local order-1 dual-quant Lorenzo
  tag 2  lorenzo2    — order-2 Lorenzo (wins on polynomial trends whose first
                       differences still carry a ramp)
  tag 3  regression  — SZ2 hyperplane fit, quantized coefficients

Every block's quantization indices feed ONE shared stream — a single Huffman
table and a single lossless pass, exactly the paper's amortization — while a
2-bit/block tag array and the delta-coded regression-coefficient streams for
regression-winning blocks ride as compact side channels inside the same
lossless body.  Prediction stays locally optimal; entropy coding stays
global.

Container: v5, kind "hybrid", auto-detected by ``pipeline.decompress``
(v1–v4 decode unchanged).  Error modes: ABS natively; REL resolves against
global finite stats; PW_REL composes :class:`preprocess.LogTransform`
automatically (sign/zero/non-finite side channels in ``pre_meta``), so the
engine is PW_REL-native and usable as a per-chunk candidate under every mode.
The bound is exact and unconditional: integer-grid candidates inherit the
``prequantize`` fail channel, regression rides ``quantize``'s raw-storage
path.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from . import encoders as enc_mod
from . import lossless as ll_mod
from . import pipeline as pl_mod
from . import predictors as pred_mod
from . import preprocess as pre_mod
from . import quantizers as quant_mod
from . import telemetry as tel
from .config import CompressionConfig, ErrorBoundMode
from .integrity import ContainerError, guard_alloc, guard_count, guard_shape
from .pipeline import CompressionResult, container_body, pack_container
from .predictors import (
    _int_code_bits,
    _pack_mask,
    _unpack_mask,
    block_coords,
    block_lorenzo_filter,
    block_lorenzo_inverse,
    block_plane_fit,
    blockify,
    pad_to_blocks,
    unblockify,
)

_VERSION5 = 5

#: block side length by dimensionality: ~256-4096 elements per block, so the
#: 2-bit tag costs <0.01 bits/value and the shared Huffman table amortizes,
#: while blocks stay small enough to isolate a regime change
BLOCK_SIDES = {1: 256, 2: 16, 3: 8}

#: side length for ndim >= 4 (4^d elements keep the coefficient overhead sane)
DEFAULT_SIDE = 4

#: tag values — also the tie-break priority (argmin keeps the lowest tag)
TAG_ZERO, TAG_LOR1, TAG_LOR2, TAG_REG = 0, 1, 2, 3
TAG_NAMES = ("zero", "lorenzo1", "lorenzo2", "regression")


def block_side_for(ndim: int, override: Optional[int] = None) -> int:
    if override:
        return max(2, int(override))
    return BLOCK_SIDES.get(int(ndim), DEFAULT_SIDE)


def _gamma_bits(q: np.ndarray) -> np.ndarray:
    """Per-code length proxy: Elias-gamma-style ``2*log2(1+|q|) + 1``.

    Monotone in |q|, zero-centred, and fully vectorizable across blocks —
    the per-block specialization of the ``code_bits`` entropy model (a true
    per-block empirical entropy would need one histogram per block per
    candidate; the gamma length ranks candidates identically on the
    populations that matter: near-zero vs wide).
    """
    return 2.0 * np.log2(1.0 + np.abs(np.asarray(q, np.float64))) + 1.0


def _pack_tags(tags: np.ndarray) -> bytes:
    """2 bits per block, 4 blocks per byte (little-endian within the byte)."""
    n = tags.size
    padded = np.zeros(((n + 3) // 4) * 4, np.uint8)
    padded[:n] = tags
    packed = (
        padded[0::4]
        | (padded[1::4] << 2)
        | (padded[2::4] << 4)
        | (padded[3::4] << 6)
    )
    return packed.tobytes()


def _unpack_tags(buf: bytes, n: int) -> np.ndarray:
    raw = np.frombuffer(buf, np.uint8)
    out = np.empty(raw.size * 4, np.uint8)
    out[0::4] = raw & 3
    out[1::4] = (raw >> 2) & 3
    out[2::4] = (raw >> 4) & 3
    out[3::4] = (raw >> 6) & 3
    return out[:n]


def _select_tags(
    qfull: np.ndarray,
    d1: np.ndarray,
    d2: np.ndarray,
    qres: np.ndarray,
    coef_q: List[np.ndarray],
    reg_bad: np.ndarray,
) -> np.ndarray:
    """Per-block winner by estimated coded bits (paper: estimate_error).

    All four candidates are scored in the same currency (gamma-length bits of
    their integer codes); regression additionally pays its delta-coded
    coefficient streams.  Blocks whose fit is non-finite never win regression
    (their points belong on the int-grid fail path).
    """
    nb = qfull.shape[0]
    if nb == 0:
        return np.zeros(0, np.uint8)
    axes = tuple(range(1, qfull.ndim))
    cost = np.empty((4, nb))
    cost[TAG_ZERO] = _gamma_bits(qfull).sum(axis=axes)
    cost[TAG_LOR1] = _gamma_bits(d1).sum(axis=axes)
    cost[TAG_LOR2] = _gamma_bits(d2).sum(axis=axes)
    reg_cost = _gamma_bits(qres).sum(axis=axes)
    for qc in coef_q:
        # the real stream delta-codes coefficients against the PREVIOUS
        # REGRESSION WINNER (unknown until selection completes), so price
        # the cheaper of delta-vs-neighbour and coding the value fresh —
        # charging the raw neighbour delta would overbill blocks whose
        # global-order predecessor sits in a different regime
        reg_cost = reg_cost + np.minimum(
            _gamma_bits(np.diff(qc, prepend=0)), _gamma_bits(qc)
        )
    cost[TAG_REG] = np.where(reg_bad, np.inf, reg_cost)
    return np.argmin(cost, axis=0).astype(np.uint8)


def _candidate_codes(
    blocks: np.ndarray, qfull: np.ndarray, eb: float
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, List[np.ndarray], np.ndarray, np.ndarray]:
    """All candidate code estimates for a pre-blockified array.

    Returns (d1, d2, qres, coef_q, pred_reg, reg_bad): the order-1/order-2
    Lorenzo differences of the prequantized grid, the regression residual
    bin indices, the quantized coefficient streams, the regression
    prediction, and the bad-fit block mask.
    """
    b = blocks.shape[1] if blocks.ndim > 1 else 1
    d1 = block_lorenzo_filter(qfull, 1)
    d2 = block_lorenzo_filter(d1, 1)  # second application == order 2
    coef_q, pred_reg, reg_bad = block_plane_fit(blocks, b, eb)
    with np.errstate(invalid="ignore", over="ignore"):
        qres = np.rint((blocks - pred_reg) / (2.0 * eb))
    qres = np.where(np.isfinite(qres), qres, 0.0)
    return d1, d2, qres, coef_q, pred_reg, reg_bad


class BlockHybridCompressor:
    """Block-level multi-predictor hybrid engine (module docstring above).

    Follows the :class:`pipeline.SZ3Compressor` module protocol (preprocessor
    slot, quantizer/encoder/lossless stages, ``compress``/``spec``), so the
    chunked engines can contest it per chunk and compose ``LogTransform``
    into it for PW_REL, and ``pipeline.decompress`` rebuilds it from the
    self-describing v5 header.
    """

    kind = "hybrid"

    def __init__(
        self,
        preprocessor: Optional[pre_mod.Preprocessor] = None,
        quantizer: Optional[quant_mod.QuantizerBase] = None,
        encoder: Optional[enc_mod.Encoder] = None,
        lossless: Optional[ll_mod.LosslessBackend] = None,
        conf: Optional[CompressionConfig] = None,
        block_side: Optional[int] = None,
    ):
        self.preprocessor = preprocessor or pre_mod.Identity()
        self.quantizer = quantizer or quant_mod.LinearScaleQuantizer()
        self.encoder = encoder or enc_mod.HuffmanEncoder()
        self.lossless = lossless or ll_mod.Zstd()
        self.conf = conf or CompressionConfig()
        self.block_side = block_side

    # -- spec (self-describing container) ------------------------------------
    def spec(self) -> Dict[str, Any]:
        return {
            "kind": self.kind,
            "preprocessor": self.preprocessor.name,
            "quantizer": self.quantizer.name,
            "quant_radius": self.quantizer.radius,
            "encoder": self.encoder.name,
            "lossless": self.lossless.name,
        }

    # -- selection-contest hook (chunking.select_pipeline) -------------------
    def estimate_error(
        self, sample: np.ndarray, abs_eb: float, conf: CompressionConfig
    ) -> float:
        """Estimated coded bits/element on ``sample`` — the chunk-level
        analogue of ``Predictor.estimate_error``, so ``select_pipeline`` can
        contest the hybrid engine against whole pipelines.

        Runs the real per-block contest on the sample's estimated codes and
        prices the winning population (plus coefficient and tag side
        channels) with the shared ``code_bits`` entropy model, normalized by
        the UNPADDED element count so tiling overhead on awkward shapes is
        visible to the contest.
        """
        x = np.asarray(sample, np.float64)
        if x.size == 0:
            return 0.0
        if x.ndim == 0:
            x = x.reshape(1)
        b = block_side_for(x.ndim, self.block_side)
        xp, _ = pad_to_blocks(x, b)
        blocks = blockify(xp, b)
        nb = blocks.shape[0]
        with np.errstate(invalid="ignore", over="ignore"):
            scaled = blocks / (2.0 * abs_eb)
        qfull = np.where(np.isfinite(scaled), scaled, 0.0)
        qfull = np.rint(np.clip(qfull, -(2.0**62), 2.0**62))
        d1, d2, qres, coef_q, _pred, reg_bad = _candidate_codes(
            blocks, qfull, abs_eb
        )
        tags = _select_tags(qfull, d1, d2, qres, coef_q, reg_bad)
        cand = np.stack(
            [c.reshape(nb, -1) for c in (qfull, d1, d2, qres)]
        )
        win = np.take_along_axis(
            cand, tags.reshape(1, nb, 1).astype(np.int64), axis=0
        )[0]
        pooled = [win.reshape(-1)]
        use_reg = tags == TAG_REG
        for qc in coef_q:
            pooled.append(np.diff(qc[use_reg], prepend=0))
        allq = np.concatenate(pooled)
        bits_per_code = _int_code_bits(allq, conf.quant_radius)
        return (bits_per_code * allq.size + 2.0 * nb) / x.size

    # -- compression ----------------------------------------------------------
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
        pre = self.preprocessor
        if conf.mode == ErrorBoundMode.PW_REL and isinstance(pre, pre_mod.Identity):
            # PW_REL-native: auto-compose the log-domain conversion so the
            # pointwise bound holds by construction (no eb*absmax degradation)
            pre = pre_mod.LogTransform()
        pdata, conf2, pre_meta = pre.forward(data, conf)
        abs_eb = pl_mod.resolve_bound(pdata, conf2)
        self.quantizer.begin(abs_eb, pdata.dtype)
        with tel.span("predict", bytes=pdata.nbytes):  # per-block contest
            codes, tag_bytes, hmeta = self._compress_blocks(pdata, conf2)
        with tel.span("huffman", bytes=codes.nbytes):
            enc_bytes = self.encoder.encode(codes)
        q_bytes = self.quantizer.save()
        spec = self.spec()
        spec["preprocessor"] = pre.name  # the EFFECTIVE preprocessor (PW_REL
        # auto-composes LogTransform even when the slot holds Identity)
        header = {
            "v": _VERSION5,
            "kind": "hybrid",
            "spec": spec,
            "shape": list(data.shape),
            "pshape": list(pdata.shape),
            "dtype": data.dtype.str,
            "pdtype": pdata.dtype.str,
            "mode": conf.mode.value,
            "eb": float(conf.eb),
            "abs_eb": float(abs_eb),
            "n_codes": int(codes.size),
            **(
                {"eb_rel": float(conf.eb_rel)}
                if conf.eb_rel is not None
                else {}
            ),
            "enc_len": len(enc_bytes),
            "q_len": len(q_bytes),
            "tag_len": len(tag_bytes),
            "pre_meta": pl_mod._clean_meta(pre_meta),
            "hyb_meta": pl_mod._clean_meta(hmeta),
        }
        with tel.span("lossless", bytes=len(enc_bytes) + len(q_bytes) + len(tag_bytes)):
            body = self.lossless.compress(enc_bytes + q_bytes + tag_bytes)
        blob = pack_container(header, body)
        if tel.enabled():
            counts = {TAG_NAMES[t]: int(hmeta["counts"][t]) for t in range(4)}
            tel.record_decision(tel.make_decision(
                "sz3_hybrid",
                max(counts, key=counts.get),
                scope="block-summary",
                candidates=list(TAG_NAMES),
                estimates={k: float(v) for k, v in counts.items()},
                realized_bits=8.0 * len(blob) / max(1, data.size),
                n_elems=int(data.size),
                fallbacks=int(hmeta["nfail"]),
                extra={"counts": counts, "n_reg": int(hmeta["n_reg"]),
                       "nb": int(hmeta["nb"])},
            ))
        meta = None
        if with_stats:
            meta = dict(hmeta)
            meta.pop("fail_mask", None)
            meta.pop("fail_vals", None)
            meta["tag_shares"] = {
                TAG_NAMES[t]: hmeta["counts"][t] / max(1, hmeta["nb"])
                for t in range(4)
            }
        return CompressionResult(
            blob=blob,
            ratio=data.nbytes / max(1, len(blob)),
            codes=codes if with_stats else None,
            meta=meta,
        )

    def _compress_blocks(
        self, pdata: np.ndarray, conf: CompressionConfig
    ) -> Tuple[np.ndarray, bytes, Dict[str, Any]]:
        """Tile, contest, and emit the shared code stream + side channels."""
        quantizer = self.quantizer
        x64 = np.asarray(pdata, np.float64)
        if x64.ndim == 0:
            x64 = x64.reshape(1)
        nd = x64.ndim
        b = block_side_for(nd, self.block_side)
        xp, work_shape = pad_to_blocks(x64, b)
        blocks = blockify(xp, b)  # (nb,) + (b,)*nd
        nb = blocks.shape[0]
        eb = quantizer.eb
        # prequantize once for all integer-grid candidates; fail marks points
        # (non-finite / cast-rounding) the grid cannot represent in bound
        with tel.span("quantize", bytes=blocks.nbytes):
            qfull, _recon, fail = quantizer.prequantize(blocks)
        d1, d2, qres, coef_q, pred_reg, reg_bad = _candidate_codes(
            blocks, qfull, eb
        )
        tags = _select_tags(qfull, d1, d2, qres, coef_q, reg_bad)
        use_reg = tags == TAG_REG
        # shared code stream, in decode order: the delta-coded coefficient
        # streams of regression-winning blocks, then the integer-grid data
        # codes grouped by tag (block order within each group), then the
        # float-domain regression residual codes
        parts: List[np.ndarray] = []
        for qc in coef_q:
            kept = qc[use_reg]
            parts.append(quantizer.quantize_int_diff(np.diff(kept, prepend=0)))
        for tag, d in ((TAG_ZERO, qfull), (TAG_LOR1, d1), (TAG_LOR2, d2)):
            parts.append(quantizer.quantize_int_diff(d[tags == tag].reshape(-1)))
        dcodes, _ = quantizer.quantize(
            blocks[use_reg].reshape(-1), pred_reg[use_reg].reshape(-1)
        )
        codes = np.concatenate([p.astype(dcodes.dtype) for p in parts] + [dcodes])
        meta: Dict[str, Any] = {
            "bs": int(b),
            "padded_shape": list(xp.shape),
            "work_shape": list(work_shape),
            "nb": int(nb),
            "n_reg": int(use_reg.sum()),
            "counts": [int((tags == t).sum()) for t in range(4)],
        }
        int_fail = fail[~use_reg]
        nfail = int(int_fail.sum())
        meta["nfail"] = nfail
        if nfail:
            meta["fail_mask"] = _pack_mask(int_fail)
            meta["fail_vals"] = blocks[~use_reg][int_fail].tobytes()
        return codes, _pack_tags(tags), meta

    # -- decompression (pipeline.decompress dispatch target) ------------------
    @staticmethod
    def _decompress_body(blob: bytes, header: Dict[str, Any], body_off: int) -> np.ndarray:
        spec = header["spec"]
        quantizer = quant_mod.make(spec["quantizer"], radius=spec["quant_radius"])
        encoder = enc_mod.make(spec["encoder"])
        enc_len = guard_alloc(header["enc_len"], "enc_len")
        q_len = guard_alloc(header["q_len"], "q_len")
        tag_len = guard_alloc(header["tag_len"], "tag_len")
        total = guard_alloc(enc_len + q_len + tag_len, "hybrid body")
        body = ll_mod.make(spec["lossless"]).decompress_bounded(
            container_body(blob, body_off), total
        )
        if len(body) != total:
            raise ContainerError(
                f"hybrid body decompressed to {len(body)} bytes; header "
                f"declares {total} (enc+q+tag)"
            )
        enc_bytes = body[:enc_len]
        q_bytes = body[enc_len : enc_len + q_len]
        tag_bytes = body[enc_len + q_len : enc_len + q_len + tag_len]
        pdtype = np.dtype(header["pdtype"])
        quantizer.begin(header["abs_eb"], pdtype)
        quantizer.load(q_bytes)
        hm = header["hyb_meta"]
        b = guard_count(hm["bs"], 1 << 12, "hybrid block side")
        if b < 1:
            raise ContainerError("corrupt hybrid container: block side < 1")
        padded_shape = guard_shape(hm["padded_shape"], 8, "padded_shape")
        work_shape = guard_shape(hm["work_shape"], 8, "work_shape")
        nd = len(padded_shape)
        blk_elems = b**nd
        nb_limit = int(np.prod(padded_shape, dtype=np.int64)) // max(1, blk_elems) + 1
        nb = guard_count(hm["nb"], nb_limit, "hybrid block count")
        n_reg = guard_count(hm["n_reg"], nb, "hybrid regression count")
        guard_alloc(nb * blk_elems * 8, "hybrid block grid")
        n_codes = guard_count(
            header["n_codes"], 2 * nb * blk_elems + 4096, "n_codes"
        )
        codes = np.asarray(encoder.decode(enc_bytes, n_codes))
        if tag_len != (nb + 3) // 4:
            raise ContainerError(
                f"corrupt hybrid container: tag channel holds {tag_len} "
                f"bytes, {(nb + 3) // 4} expected for {nb} blocks"
            )
        eb = quantizer.eb
        tags = _unpack_tags(tag_bytes, nb)
        use_reg = tags == TAG_REG
        blk = b**nd
        pos = 0
        # 1. regression coefficient streams (delta-coded, winning blocks only)
        qhat: List[np.ndarray] = []
        for k in range(nd + 1):
            dq = quantizer.recover_int_diff(codes[pos : pos + n_reg])
            pos += n_reg
            ceb = eb / 2.0 if k == 0 else eb / (2.0 * b)
            qhat.append(np.cumsum(dq).astype(np.float64) * (2.0 * ceb))
        # 2. integer-grid groups: zero (identity), lorenzo order 1 / order 2
        n_int = nb - n_reg
        int_blocks = np.empty((n_int,) + (b,) * nd, np.float64)
        int_tags = tags[~use_reg]
        for tag, order in ((TAG_ZERO, 0), (TAG_LOR1, 1), (TAG_LOR2, 2)):
            cnt = int((tags == tag).sum())
            d = quantizer.recover_int_diff(codes[pos : pos + cnt * blk])
            pos += cnt * blk
            d = d.reshape((cnt,) + (b,) * nd)
            q = block_lorenzo_inverse(d, order) if order else d
            int_blocks[int_tags == tag] = quantizer.dequantize_int(q).astype(
                np.float64
            )
        if hm.get("nfail"):
            fl = _unpack_mask(hm["fail_mask"], n_int * blk).reshape(
                (n_int,) + (b,) * nd
            )
            int_blocks[fl] = np.frombuffer(hm["fail_vals"], np.float64)
        # 3. regression residuals against the coefficient-rebuilt planes
        cs = block_coords(b, nd)
        pred = qhat[0].reshape((n_reg,) + (1,) * nd)
        for k in range(nd):
            pred = pred + qhat[1 + k].reshape((n_reg,) + (1,) * nd) * cs[k]
        reg_recon = quantizer.recover(pred.reshape(-1), codes[pos:])
        blocks = np.empty((nb,) + (b,) * nd, np.float64)
        blocks[~use_reg] = int_blocks
        blocks[use_reg] = np.asarray(reg_recon, np.float64).reshape(
            (n_reg,) + (b,) * nd
        )
        out = unblockify(blocks, padded_shape, b)
        out = out[tuple(slice(0, s) for s in work_shape)]
        pdata = out.astype(pdtype).reshape(tuple(header["pshape"]))
        conf = CompressionConfig(
            mode=ErrorBoundMode(header["mode"]),
            eb=header["eb"],
            quant_radius=spec["quant_radius"],
        )
        data = pre_mod.make(spec["preprocessor"]).inverse(
            pdata, conf, header["pre_meta"]
        )
        return data.astype(np.dtype(header["dtype"])).reshape(
            tuple(header["shape"])
        )


def sz3_hybrid(block_side: Optional[int] = None, **kw) -> BlockHybridCompressor:
    """Named factory: block-level multi-predictor hybrid engine (v5)."""
    return BlockHybridCompressor(block_side=block_side, **kw)
