"""Compression-pipeline composition (paper §3.3, Algorithm 1).

A compressor is a 5-tuple of module instances.  The driver below is the
paper's Algorithm 1, array-vectorized: it never names a concrete module —
composition is data ("spec"), mirroring SZ3's compile-time template
polymorphism with trace/construction-time polymorphism (DESIGN.md §4.1).

The container format is self-describing: the header records the module spec,
so ``decompress(blob)`` rebuilds the exact pipeline.  Named factory pipelines
(the name -> factory table, ``PIPELINES``, is in ``engines.py``):

  sz3_lr          — composite(Lorenzo+regression) + linear quant + Huffman + zstd   (= SZ2 [8])
  sz3_interp      — interpolation + linear quant + Huffman + zstd                   ([17])
  sz3_truncation  — byte truncation, all other stages bypassed
  sz3_pastri      — pattern + UNPRED-AWARE quant + Huffman + zstd                   (paper §4)
  sz_pastri       — pattern + linear quant + fixed Huffman (no lossless)            (baseline [19])
  sz3_aps         — error-bound-adaptive APS pipeline                               (paper §5)
  sz3_lorenzo     — pure dual-quant Lorenzo (TPU-native fast path)
  sz3_chunked     — streaming chunked engine, per-chunk pipeline selection
                    (chunking.py; emits the v2 container)
  sz3_transform   — blockwise decorrelating transform + exponent-aligned
                    bitplane coding (transform.py; v3 header)
  sz3_auto        — chunked engine whose candidate set spans every coder
                    family (engines.py: ``AUTO_CANDIDATES``)
  sz3_pwr         — first-class pointwise-relative engine: log-composed
                    chunk pipelines, v4 container (chunking.py)
  sz3_quality     — closed-loop quality-targeted rate controller
                    (target PSNR / ratio / bitrate; quality.py)
  sz3_hybrid      — block-level multi-predictor hybrid engine: per-block
                    zero/Lorenzo-1/Lorenzo-2/regression contest feeding one
                    shared entropy stream (blockwise.py; v5 container)
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import msgpack
import numpy as np

from . import encoders as enc_mod
from . import integrity
from . import lossless as ll_mod
from . import telemetry as tel
from . import predictors as pred_mod
from . import preprocess as pre_mod
from . import quantizers as quant_mod
from .config import CompressionConfig, ErrorBoundMode
from .integrity import (
    ContainerError,
    IntegrityError,
    SalvageReport,
    decode_errors,
    guard_alloc,
    guard_count,
    guard_shape,
)

_MAGIC = b"SZ3J"
_VERSION = 1

#: accepted values for the ``verify=`` policy on the decode entry points
VERIFY_MODES = ("strict", "salvage", "off")


def _finite_stats(data: np.ndarray) -> Tuple[float, float]:
    """(value range, abs max) over FINITE elements — a stray nan/inf must
    not blow a REL bound up to nan for every other point.  Cheap common
    path: one min/max pass; the masked pass only runs when needed."""
    if not data.size:
        return 0.0, 0.0
    mn, mx = float(data.min()), float(data.max())
    if not (np.isfinite(mn) and np.isfinite(mx)):
        fin = data[np.isfinite(data)]
        if not fin.size:
            return 0.0, 0.0
        mn, mx = float(fin.min()), float(fin.max())
    return mx - mn, max(abs(mn), abs(mx))


def resolve_bound(data: np.ndarray, conf: CompressionConfig) -> float:
    """The absolute bound ``conf`` resolves to on ``data`` (never 0): the
    finite-range pass and the resolution, in a ``stats`` span."""
    with tel.span("stats", bytes=data.nbytes):
        rng, absmax = _finite_stats(data)
        abs_eb = conf.resolve_abs_eb(rng, absmax)
    if abs_eb <= 0:
        abs_eb = float(np.finfo(np.float64).tiny)
    return abs_eb


def to_host(x) -> np.ndarray:
    """``x`` as a numpy array.  Anything else, a device array above all, is
    read to the host in a ``to_host`` span."""
    if isinstance(x, np.ndarray):
        return x
    with tel.span("to_host") as sp:
        out = np.asarray(x)
        sp.set(bytes=out.nbytes)
    return out


def _clean_meta(meta: Dict[str, Any]) -> Dict[str, Any]:
    """Coerce numpy scalars so msgpack accepts the header."""
    out = {}
    for k, v in meta.items():
        if isinstance(v, (np.integer,)):
            out[k] = int(v)
        elif isinstance(v, (np.floating,)):
            out[k] = float(v)
        elif isinstance(v, np.ndarray):
            out[k] = v.tolist()
        else:
            out[k] = v
    return out


def pack_container(
    header: Dict[str, Any],
    body: bytes,
    chunk_bounds: Optional[Any] = None,
) -> bytes:
    """The container wire format: magic + int64 (header, body) lengths +
    msgpack header + body + integrity trailer.  Single authority — every
    writer (v1 pipelines, truncation, v2 chunked, transform, hybrid, fast)
    must frame through here so readers stay compatible.

    The trailer (see :mod:`.integrity`) sits BEYOND the declared body length,
    so readers that honour the declared lengths skip it: old readers decode
    new blobs, and pre-trailer blobs keep decoding here.  ``chunk_bounds``
    lists body-relative ``(off, len)`` of independently decodable chunks for
    per-chunk checksums (multi-chunk writers pass their chunk table); None
    checksums the whole body as one chunk.  The header gains an ``itg`` flag
    under the header checksum so strict verification can detect a stripped
    trailer.  ``integrity.trailers_disabled()`` suppresses both (overhead
    benchmarking, legacy-fixture generation)."""
    with tel.span("pack", bytes=len(body)):
        if integrity.WRITE_TRAILERS:
            header = dict(header)
            header["itg"] = 1
        hbytes = msgpack.packb(header, use_bin_type=True)
        head = _MAGIC + np.asarray([len(hbytes), len(body)], np.int64).tobytes() + hbytes
        if not integrity.WRITE_TRAILERS:
            return head + body
        with tel.span("integrity", bytes=len(body)):
            trailer = integrity.build_trailer(head, body, chunk_bounds)
        return head + body + trailer


def container_body(blob: bytes, body_off: int) -> bytes:
    """The body slice DECLARED by the prologue — never the raw tail, which
    may carry the integrity trailer (or attacker-appended bytes)."""
    blen = int.from_bytes(blob[12:20], "little", signed=True)
    return blob[body_off : body_off + blen]


@dataclasses.dataclass
class CompressionResult:
    blob: bytes
    ratio: float
    codes: Optional[np.ndarray] = None  # quantization integers (paper Fig 3)
    meta: Optional[Dict[str, Any]] = None


class SZ3Compressor:
    """The general compressor of paper Algorithm 1."""

    kind = "sz3"

    def __init__(
        self,
        preprocessor: pre_mod.Preprocessor = None,
        predictor: pred_mod.Predictor = None,
        quantizer: quant_mod.QuantizerBase = None,
        encoder: enc_mod.Encoder = None,
        lossless: ll_mod.LosslessBackend = None,
        conf: CompressionConfig = None,
    ):
        self.preprocessor = preprocessor or pre_mod.Identity()
        self.predictor = predictor or pred_mod.LorenzoPredictor()
        self.quantizer = quantizer or quant_mod.LinearScaleQuantizer()
        self.encoder = encoder or enc_mod.HuffmanEncoder()
        self.lossless = lossless or ll_mod.Zstd()
        self.conf = conf or CompressionConfig()

    # -- spec (for the self-describing container) ---------------------------
    def spec(self) -> Dict[str, Any]:
        return {
            "kind": self.kind,
            "preprocessor": self.preprocessor.name,
            "predictor": self.predictor.name,
            "quantizer": self.quantizer.name,
            "quant_radius": self.quantizer.radius,
            "encoder": self.encoder.name,
            "lossless": self.lossless.name,
        }

    @staticmethod
    def from_spec(spec: Dict[str, Any]) -> "SZ3Compressor":
        return SZ3Compressor(
            preprocessor=pre_mod.make(spec["preprocessor"]),
            predictor=pred_mod.make(spec["predictor"]),
            quantizer=quant_mod.make(spec["quantizer"], radius=spec["quant_radius"]),
            encoder=enc_mod.make(spec["encoder"]),
            lossless=ll_mod.make(spec["lossless"]),
        )

    # -- Algorithm 1 ---------------------------------------------------------
    def compress(
        self, data: np.ndarray, conf: CompressionConfig = None, with_stats: bool = False
    ) -> CompressionResult:
        conf = conf or self.conf
        data = to_host(data)
        if data.dtype not in (np.float32, np.float64):
            data = data.astype(np.float32)
        pdata, conf2, pre_meta = self.preprocessor.forward(data, conf)  # line 1
        abs_eb = resolve_bound(pdata, conf2)
        self.quantizer.begin(abs_eb, pdata.dtype)
        with tel.span("predict", bytes=pdata.nbytes):  # predict+quantize fused
            codes, pred_meta = self.predictor.compress(pdata, self.quantizer, conf2)  # 2-5
        with tel.span("huffman", bytes=codes.nbytes):
            enc_bytes = self.encoder.encode(codes)  # lines 9-10
        q_bytes = self.quantizer.save()  # line 8
        header = {
            "v": _VERSION,
            "spec": self.spec(),
            "shape": list(data.shape),
            "pshape": list(pdata.shape),
            "dtype": data.dtype.str,
            "pdtype": pdata.dtype.str,
            "mode": conf.mode.value,
            "eb": float(conf.eb),
            "abs_eb": float(abs_eb),
            "block_size": int(conf2.block_size),
            **(
                {"eb_rel": float(conf.eb_rel)}
                if conf.eb_rel is not None
                else {}
            ),
            "interp_kind": conf2.interp_kind,
            "lorenzo_order": int(conf2.lorenzo_order),
            "n_codes": int(codes.size),
            "enc_len": len(enc_bytes),
            "q_len": len(q_bytes),
            "pre_meta": _clean_meta(pre_meta),
            "pred_meta": _clean_meta(pred_meta),
        }
        with tel.span("lossless", bytes=len(enc_bytes) + len(q_bytes)):
            body = self.lossless.compress(enc_bytes + q_bytes)  # line 11
        blob = pack_container(header, body)
        ratio = data.nbytes / max(1, len(blob))
        return CompressionResult(
            blob=blob,
            ratio=ratio,
            codes=codes if with_stats else None,
            meta=pred_meta if with_stats else None,
        )


def parse_header(blob: bytes) -> Tuple[Dict[str, Any], int]:
    """Parse the container prologue; rejects truncated/corrupt blobs with
    :class:`~repro.core.integrity.ContainerError` (a ``ValueError``) instead
    of surfacing numpy index errors from the body.  Every length field is
    bounded by the actual buffer BEFORE any slice or allocation, so a hostile
    prologue cannot direct reads outside the blob or declare absurd sizes."""
    if len(blob) < 20:
        raise ContainerError(
            f"truncated SZ3J container: {len(blob)} bytes, need at least 20"
        )
    if blob[:4] != _MAGIC:
        raise ContainerError("not an SZ3J container")
    lens = np.frombuffer(blob, np.int64, count=2, offset=4)
    hlen, blen = int(lens[0]), int(lens[1])
    if hlen < 0 or blen < 0 or 20 + hlen + blen > len(blob):
        raise ContainerError(
            f"corrupt SZ3J container: header={hlen} body={blen} bytes do not "
            f"fit the {len(blob)}-byte buffer"
        )
    try:
        header = msgpack.unpackb(blob[20 : 20 + hlen], raw=False)
    except Exception as e:
        raise ContainerError(f"corrupt SZ3J container header: {e}") from e
    if not isinstance(header, dict):
        raise ContainerError("corrupt SZ3J container header: not a map")
    return header, 20 + hlen


def decompress(
    blob: bytes, workers: Optional[int] = None, verify: str = "strict"
):
    """Self-describing decompression — rebuilds the pipeline from the header.

    Handles every container generation: v1 single-pipeline blobs, v2
    multi-chunk blobs (per-chunk spec + offsets; see chunking.py), v3
    blockwise-transform blobs, v4 pointwise-relative multi-chunk blobs
    (kind "pwr": chunk blobs carry log-domain side channels in their
    pre_meta), v5 block-hybrid blobs (kind "hybrid": per-block predictor
    tags + coefficient side channels; see blockwise.py), and v6 fast-tier
    blobs (kind "fast": fixed-length truncated-bitplane blocks; see
    fastmode.py).
    ``workers`` parallelizes multi-chunk decode (ignored for
    single-pipeline blobs).

    ``verify`` is the integrity policy (see :mod:`.integrity`):

    * ``"strict"`` (default) — verify the trailer's checksums before decode;
      raise :class:`IntegrityError` naming the first damaged chunk.  Blobs
      written before the trailer era carry no checksums and pass unverified.
    * ``"salvage"`` — decode every intact chunk, fill damaged ones with
      zeros, and return ``(data, SalvageReport)`` instead of the bare array.
    * ``"off"`` — skip checksum verification (malformed-structure errors
      still raise).

    Every malformed-input failure raises a ``ValueError`` subclass
    (:class:`ContainerError` / :class:`IntegrityError`) — never a raw
    ``struct.error`` / ``KeyError`` / ``IndexError`` from the internals.
    """
    if verify not in VERIFY_MODES:
        raise ValueError(f"verify must be one of {VERIFY_MODES}, got {verify!r}")
    blob = bytes(blob)
    with decode_errors("container"):
        header, body_off = parse_header(blob)
        if verify == "salvage":
            return _decompress_salvage(blob, header, body_off, workers)
        if verify == "strict":
            try:
                with tel.span("integrity", bytes=len(blob)):
                    integrity.verify_container(blob, header, body_off)
            except IntegrityError:
                # one counter in the global serving registry, one in the
                # active trace (if any) — failures stay visible either way
                tel.metric_count("sz3_verify_failures_total")
                tel.count("verify_failures")
                raise
        return _decompress_dispatch(blob, header, body_off, workers, verify)


def _decompress_dispatch(
    blob: bytes,
    header: Dict[str, Any],
    body_off: int,
    workers: Optional[int],
    verify: str,
) -> np.ndarray:
    """Route a parsed container to its generation's decoder (checksum policy
    already applied by the caller; ``verify`` propagates to nested chunk
    blobs so a chunked decode verifies — or skips — uniformly)."""
    if header.get("v", _VERSION) >= 2 and header.get("kind") in ("chunked", "pwr"):
        from .chunking import decompress_chunked  # local: avoids import cycle

        return decompress_chunked(
            blob, header, body_off, workers=workers, verify=verify
        )
    spec = header["spec"]
    if not isinstance(spec, dict):
        raise ContainerError("corrupt container: spec is not a map")
    kind = spec.get("kind")
    if kind == "truncation":
        return TruncationCompressor._decompress_body(blob, header, body_off)
    if kind == "transform":  # v3 blockwise-transform containers
        from .transform import TransformCompressor  # local: avoids import cycle

        return TransformCompressor._decompress_body(blob, header, body_off)
    if kind == "hybrid":  # v5 block-level multi-predictor containers
        from .blockwise import BlockHybridCompressor  # local: avoids import cycle

        return BlockHybridCompressor._decompress_body(blob, header, body_off)
    if kind == "fast":  # v6 SZx-style fixed-length containers
        from .fastmode import FastModeCompressor  # local: avoids import cycle

        return FastModeCompressor._decompress_body(blob, header, body_off)
    return _decompress_v1(blob, header, body_off)


def _decompress_v1(
    blob: bytes, header: Dict[str, Any], body_off: int
) -> np.ndarray:
    """The v1 single-pipeline decode path, with every header-declared size
    bounded before allocation (hostile length fields cannot trigger
    decompression bombs or absurd numpy allocations)."""
    spec = header["spec"]
    comp = SZ3Compressor.from_spec(spec)
    dtype = np.dtype(header["dtype"])
    pdtype = np.dtype(header["pdtype"])
    shape = guard_shape(header["shape"], dtype.itemsize, "shape")
    pshape = guard_shape(header["pshape"], pdtype.itemsize, "pshape")
    enc_len = guard_alloc(header["enc_len"], "enc_len")
    q_len = guard_alloc(header["q_len"], "q_len")
    plain_len = guard_alloc(enc_len + q_len, "enc_len+q_len")
    with tel.span("lossless", bytes=plain_len):
        body = comp.lossless.decompress_bounded(
            container_body(blob, body_off), plain_len
        )
    if len(body) != plain_len:
        raise ContainerError(
            f"v1 body decompressed to {len(body)} bytes; header declares "
            f"{plain_len} (enc_len={enc_len} + q_len={q_len})"
        )
    enc_bytes = body[:enc_len]
    q_bytes = body[enc_len:]
    n_elems = int(np.prod(pshape, dtype=np.int64)) if pshape else 1
    n_codes = guard_count(
        header["n_codes"], 2 * n_elems + 4096, "n_codes"
    )
    comp.quantizer.begin(header["abs_eb"], pdtype)
    comp.quantizer.load(q_bytes)
    with tel.span("huffman", bytes=len(enc_bytes)):
        codes = comp.encoder.decode(enc_bytes, n_codes)
    conf = CompressionConfig(
        mode=ErrorBoundMode(header["mode"]),
        eb=header["eb"],
        block_size=header["block_size"],
        interp_kind=header["interp_kind"],
        lorenzo_order=header["lorenzo_order"],
        quant_radius=spec["quant_radius"],
    )
    with tel.span("predict", bytes=n_elems * pdtype.itemsize):
        pdata = comp.predictor.decompress(
            np.asarray(codes),
            pshape,
            pdtype,
            comp.quantizer,
            conf,
            header["pred_meta"],
        )
    data = comp.preprocessor.inverse(pdata, conf, header["pre_meta"])
    return data.astype(dtype).reshape(shape)


def _decompress_salvage(
    blob: bytes, header: Dict[str, Any], body_off: int, workers: Optional[int]
):
    """``verify="salvage"``: recover what the damage spares.

    Multi-chunk containers (v2 "chunked" / v4 "pwr") localize loss to the
    chunk level: every chunk whose checksum passes — or, without a trailer,
    whose decode succeeds — is recovered byte-exact; damaged chunks are
    zero-filled and named in the report.  Single-body generations
    (v1/v3/v5/v6) are all-or-nothing: one entropy stream, so a failed decode
    loses the whole array (zero-filled, one damage record).  A damaged
    HEADER is not salvageable — shape/dtype/chunk table are untrustworthy —
    and raises :class:`IntegrityError`.
    """
    res = integrity.inspect(blob, header, body_off)
    if res.has_trailer and not res.header_ok:
        raise IntegrityError(
            "container header bytes fail their checksum — shape, dtype and "
            "chunk table are untrustworthy, nothing can be salvaged",
            region="header",
        )
    if header.get("v", _VERSION) >= 2 and header.get("kind") in ("chunked", "pwr"):
        from .chunking import salvage_chunked  # local: avoids import cycle

        return salvage_chunked(
            blob, header, body_off, workers=workers, inspect_result=res
        )
    dtype = np.dtype(header["dtype"])
    shape = guard_shape(header["shape"], dtype.itemsize, "shape")
    n = int(np.prod(shape, dtype=np.int64)) if shape else 1
    report = SalvageReport(total_chunks=1, checksummed=res.has_trailer)
    reason = None
    if res.has_trailer and not res.whole_ok:
        reason = "checksum"
    else:
        try:
            with decode_errors("container"):
                data = _decompress_dispatch(blob, header, body_off, workers, "off")
            report.recovered.append(0)
            return data, report
        except ValueError:
            reason = "decode-error"
    report.damage.append(integrity.ChunkDamage(0, 0, n, reason))
    return np.zeros(shape, dtype), report


class TruncationCompressor:
    """SZ3-Truncation (paper §6.2): keep the k most-significant bytes of each
    value, bypass every other stage.  ~1 GB/s-class throughput in the paper;
    unbounded absolute error (bounded relative error per exponent)."""

    kind = "truncation"

    def __init__(self, keep_bytes: int = 2, lossless: str = "none"):
        self.keep_bytes = keep_bytes
        self.lossless = ll_mod.make(lossless)

    def compress(self, data, conf=None, with_stats=False) -> CompressionResult:
        data = np.asarray(data)
        itemsize = data.dtype.itemsize
        k = min(self.keep_bytes, itemsize)
        # big-endian view so byte 0 is the most significant
        be = data.astype(data.dtype.newbyteorder(">"))
        raw = be.view(np.uint8).reshape(-1, itemsize)
        kept = np.ascontiguousarray(raw[:, :k]).tobytes()
        body = self.lossless.compress(kept)
        header = {
            "v": _VERSION,
            "spec": {"kind": "truncation", "k": k, "lossless": self.lossless.name},
            "shape": list(data.shape),
            "dtype": data.dtype.str,
        }
        blob = pack_container(header, body)
        return CompressionResult(blob=blob, ratio=data.nbytes / max(1, len(blob)))

    @staticmethod
    def _decompress_body(blob, header, body_off):
        spec = header["spec"]
        dt = np.dtype(header["dtype"])
        k = guard_count(spec["k"], dt.itemsize, "truncation keep_bytes")
        if k < 1:
            raise ContainerError("corrupt container: truncation keep_bytes < 1")
        shape = guard_shape(header["shape"], dt.itemsize, "shape")
        n = int(np.prod(shape, dtype=np.int64)) if shape else 1
        kept = ll_mod.make(spec["lossless"]).decompress_bounded(
            container_body(blob, body_off), n * k
        )
        if len(kept) != n * k:
            raise ContainerError(
                f"truncation body holds {len(kept)} bytes; header declares "
                f"{n}x{k}"
            )
        raw = np.zeros((n, dt.itemsize), np.uint8)
        raw[:, :k] = np.frombuffer(kept, np.uint8).reshape(n, k)
        be = raw.reshape(-1).view(dt.newbyteorder(">"))
        return be.astype(dt).reshape(shape)


class AdaptiveAPSCompressor:
    """The APS adaptive pipeline (paper §5.2, Fig 5).

    error bound >= threshold : 3-D multialgorithm (Lorenzo+regression) pipeline
    error bound <  threshold : transpose so time is innermost, 1-D Lorenzo,
                               unpred-aware quantizer with the restricted bin
                               (eb clamped to 0.5 => exact for integer counts),
                               fixed Huffman, zstd.
    """

    kind = "aps"

    def __init__(self, threshold: float = 0.5, time_axis: int = 0):
        self.threshold = threshold
        self.time_axis = time_axis

    def _low_pipeline(self, ndim: int) -> SZ3Compressor:
        perm = tuple(i for i in range(ndim) if i != self.time_axis) + (self.time_axis,)
        return SZ3Compressor(
            preprocessor=pre_mod.Transpose(perm=perm, flatten=True),
            predictor=pred_mod.LorenzoPredictor(order=1),
            quantizer=quant_mod.UnpredAwareQuantizer(),
            encoder=enc_mod.FixedHuffmanEncoder(),
            lossless=ll_mod.Zstd(),
        )

    def _high_pipeline(self) -> SZ3Compressor:
        return SZ3Compressor(
            predictor=pred_mod.CompositePredictor(),
            quantizer=quant_mod.LinearScaleQuantizer(),
            encoder=enc_mod.HuffmanEncoder(),
            lossless=ll_mod.Zstd(),
        )

    def compress(self, data, conf: CompressionConfig = None, with_stats=False):
        conf = conf or CompressionConfig()
        data = np.asarray(data)
        rng, absmax = _finite_stats(data)
        abs_eb = conf.resolve_abs_eb(rng, absmax)
        if abs_eb < self.threshold:
            # restricted quantization bin: integer-valued data becomes
            # lossless (paper: "SZ3-APS turns out to be lossless in this case")
            is_integral = bool(np.all(np.rint(data) == data))
            eff = conf.replace(
                mode=ErrorBoundMode.ABS, eb=0.5 if is_integral else abs_eb
            )
            return self._low_pipeline(data.ndim).compress(data, eff, with_stats)
        eff = conf.replace(mode=ErrorBoundMode.ABS, eb=abs_eb)
        return self._high_pipeline().compress(data, eff, with_stats)


# ---------------------------------------------------------------------------
# named pipeline factories (paper §6.2 + §4 + §5)
# ---------------------------------------------------------------------------

def sz3_lr(**kw) -> SZ3Compressor:
    return SZ3Compressor(
        predictor=pred_mod.CompositePredictor(),
        quantizer=quant_mod.LinearScaleQuantizer(),
        encoder=enc_mod.HuffmanEncoder(),
        lossless=ll_mod.Zstd(),
        **kw,
    )


def sz3_interp(kind: str = "cubic", **kw) -> SZ3Compressor:
    return SZ3Compressor(
        predictor=pred_mod.InterpolationPredictor(kind=kind),
        quantizer=quant_mod.LinearScaleQuantizer(),
        encoder=enc_mod.HuffmanEncoder(),
        lossless=ll_mod.Zstd(),
        **kw,
    )


def sz3_lorenzo(order: int = 1, device: str = "auto", **kw) -> SZ3Compressor:
    return SZ3Compressor(
        predictor=pred_mod.LorenzoPredictor(order=order, device=device),
        quantizer=quant_mod.LinearScaleQuantizer(),
        encoder=enc_mod.HuffmanEncoder(),
        lossless=ll_mod.Zstd(),
        **kw,
    )


def sz3_truncation(keep_bytes: int = 2) -> TruncationCompressor:
    return TruncationCompressor(keep_bytes=keep_bytes)


def sz_pastri(pattern_size: int = None) -> SZ3Compressor:
    """Baseline SZ-Pastri [19]: linear quantizer (raw unpredictables), fixed
    Huffman, NO lossless stage."""
    return SZ3Compressor(
        predictor=pred_mod.PatternPredictor(pattern_size=pattern_size),
        quantizer=quant_mod.LinearScaleQuantizer(),
        encoder=enc_mod.FixedHuffmanEncoder(),
        lossless=ll_mod.Passthrough(),
    )


def sz_pastri_zstd(pattern_size: int = None) -> SZ3Compressor:
    """SZ-Pastri-with-zstd (paper Table 1 middle rows)."""
    return SZ3Compressor(
        predictor=pred_mod.PatternPredictor(pattern_size=pattern_size),
        quantizer=quant_mod.LinearScaleQuantizer(),
        encoder=enc_mod.FixedHuffmanEncoder(),
        lossless=ll_mod.Zstd(),
    )


def sz3_pastri(pattern_size: int = None) -> SZ3Compressor:
    """SZ3-Pastri (paper §4.2): unpred-aware quantizer + lossless stage."""
    return SZ3Compressor(
        predictor=pred_mod.PatternPredictor(pattern_size=pattern_size),
        quantizer=quant_mod.UnpredAwareQuantizer(),
        encoder=enc_mod.HuffmanEncoder(),
        lossless=ll_mod.Zstd(),
    )


def sz3_aps(threshold: float = 0.5, time_axis: int = 0) -> AdaptiveAPSCompressor:
    return AdaptiveAPSCompressor(threshold=threshold, time_axis=time_axis)
