"""SZx-style ultra-fast fixed-length coder (v6 container, factory ``sz3_fast``).

The prediction pipelines buy ratio with an entropy stage (Huffman + lossless)
whose encode cost dominates end-to-end throughput.  SZx ("An Ultra-fast
Error-bounded Lossy Compressor", PAPERS.md) shows the other end of the
speed-ratio frontier: fixed-length coding with NO entropy pass at all.  This
module is that tier.

Format (all offsets derivable from the header — no in-band markers):

  * the flattened array is partitioned into fixed ``bs``-element blocks
    (256 default, 128 supported); the tail block is padded with its own edge
    value and cropped on decode.
  * each block stores its mean in the storage dtype.  A block is CONSTANT
    when every |x_i - mean| <= eb — 1 tag bit + the mean is its entire
    payload (the SZx constant-block fast path).
  * NONCONSTANT blocks quantize the mean-subtracted residuals on the 2*eb
    grid (``q = rint((x - mean) / (2 eb))``) and store them FIXED-LENGTH:
    the block's required bit
    count ``w = bitlength(max|q|)`` rides a 1-byte side channel, and blocks
    sharing a width are pooled into one truncated-bitplane group (``w + 1``
    planes of offset-binary ``q + 2^w``, MSB-invariant planar layout, packed
    8 values/byte).  No Huffman, no lossless pass (the ``lossless`` slot
    defaults to Passthrough; the spec records whatever is composed in).
  * points the grid cannot represent in bound — non-finite values, residuals
    beyond the 2^30 code clip, cast-rounding stragglers — ride the exact
    fail channel (indices + raw storage-dtype values), so the bound is
    unconditional, same idiom as the quantizer's ``prequantize`` fail mask.

Throughput comes from doing ALL block arithmetic in the storage dtype
(float32 data never touches a float64 temp — half the memory traffic of the
prediction pipelines) with in-place ufuncs.  The decoder reconstructs with
the exact same dtype and operation order, so the encoder can verify every
coded point against the decoder's bit-identical reconstruction and fail the
stragglers — work-dtype rounding costs a few extra fail-channel entries
(the verify threshold keeps a 1e-6 relative margin inside eb), never the
bound.

Error modes: ABS natively; REL / ABS_AND_REL / ABS_OR_REL resolve against
global finite stats; PW_REL composes :class:`preprocess.LogTransform`
automatically (side channels in ``pre_meta``), so the engine is usable as a
per-chunk candidate under every mode.  Container: v6, kind "fast",
auto-detected by ``pipeline.decompress`` (v1-v5 decode unchanged).

Device path: ``kernels/fastmode`` fuses the per-block classify+reduce stage
(mean + max-deviation) into one Pallas pass.  The kernel only produces the
classification hint — constant blocks are re-verified on the host against
the STORED mean and the residual coding always closes with the host-side
reconstruction check feeding the fail channel, so the bound holds on both
routes regardless of device rounding (both-routes verification, same policy
as kernels/lorenzo and kernels/transform).
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from . import lossless as ll_mod
from . import pipeline as pl_mod
from . import preprocess as pre_mod
from . import telemetry as tel
from .config import CompressionConfig, ErrorBoundMode
from .integrity import ContainerError, guard_alloc, guard_count, guard_shape
from .pipeline import CompressionResult, container_body, pack_container

_VERSION6 = 6

#: fixed block length (elements); 128 also supported — both are whole VPU
#: lane multiples so the device classify+reduce kernel tiles them natively
DEFAULT_BS = 256
VALID_BS = (128, 256)

#: residual codes are clipped to +-2^30 (clipped points go to the fail
#: channel) so offset-binary values stay well inside uint32
_Q_CLIP = 1 << 30

#: below this many elements the device round-trip costs more than it saves
_DEVICE_MIN_SIZE = 1 << 16


# ---------------------------------------------------------------------------
# fixed-width planar bit packing (the truncated-bitplane storage)
# ---------------------------------------------------------------------------

def _pack_planes(u: np.ndarray, nplanes: int) -> bytes:
    """Pack unsigned values (< 2^nplanes) as ``nplanes`` planar bitplanes.

    Planar (one plane of all values, then the next) rather than interleaved:
    each plane is a single vectorized mask+packbits pass, and the layout is
    byte-aligned per plane so decode needs no bit cursor.  Planes are pulled
    8 at a time from a contiguous uint8 byte lane of the values (1-byte
    traffic instead of 4-byte), and ``np.packbits`` packs NONZERO-ness, so a
    single masked AND per plane replaces the shift-to-bit-0 dance.
    """
    u = np.ascontiguousarray(u, np.uint32)
    uv = u.view(np.uint8)
    parts = []
    tmp = np.empty(u.size, np.uint8)
    for base in range(0, nplanes, 8):
        lane = base // 8 if np.little_endian else 3 - base // 8
        ub = np.ascontiguousarray(uv[lane::4])
        for p in range(base, min(nplanes, base + 8)):
            np.bitwise_and(ub, np.uint8(1 << (p - base)), out=tmp)
            parts.append(np.packbits(tmp))
    return b"".join(part.tobytes() for part in parts)


def _unpack_planes(buf: bytes, offset: int, n: int, nplanes: int) -> Tuple[np.ndarray, int]:
    """Inverse of :func:`_pack_planes`; returns (values, bytes consumed)."""
    nbytes_plane = (n + 7) // 8
    u = np.zeros(n, np.uint32)
    pos = offset
    for p in range(nplanes):
        plane = np.unpackbits(
            np.frombuffer(buf, np.uint8, count=nbytes_plane, offset=pos),
            count=n,
        )
        u |= plane.astype(np.uint32) << np.uint32(p)
        pos += nbytes_plane
    return u, pos - offset


def _required_bits(maxmag: np.ndarray) -> np.ndarray:
    """Per-block magnitude bit count: bitlength(max|q|), 0 for all-zero."""
    m = np.asarray(maxmag, np.int64)
    w = np.zeros(m.shape, np.uint8)
    nz = m > 0
    if nz.any():
        # float log2 is exact for the powers of two that sit on the boundary
        # (|q| <= 2^30 keeps the mantissa honest)
        w[nz] = (np.floor(np.log2(m[nz].astype(np.float64))).astype(np.int64) + 1).astype(np.uint8)
    return w


class FastModeCompressor:
    """SZx-style fixed-length block coder (module docstring above).

    Exposes the same module protocol as the Algorithm-1 pipelines
    (``preprocessor`` slot, ``compress``/``spec``/``estimate_error``), so the
    chunked engines can contest it per chunk — including under PW_REL via the
    LogTransform composition — and ``pipeline.decompress`` rebuilds it from
    the self-describing v6 header.
    """

    kind = "fast"

    def __init__(
        self,
        bs: int = DEFAULT_BS,
        preprocessor: Optional[pre_mod.Preprocessor] = None,
        lossless: Optional[ll_mod.LosslessBackend] = None,
        conf: Optional[CompressionConfig] = None,
        device: str = "auto",
    ):
        if int(bs) not in VALID_BS:
            raise ValueError(f"fast-mode block size must be one of {VALID_BS}")
        self.bs = int(bs)
        self.preprocessor = preprocessor or pre_mod.Identity()
        # Passthrough by default: a lossless pass would reintroduce the very
        # latency this tier exists to shed (compose Zstd explicitly if the
        # extra ratio is worth it)
        self.lossless = lossless or ll_mod.Passthrough()
        self.conf = conf or CompressionConfig()
        self.device = device

    # -- spec (self-describing container) ------------------------------------
    def spec(self) -> Dict[str, Any]:
        return {
            "kind": self.kind,
            "bs": self.bs,
            "preprocessor": self.preprocessor.name,
            "lossless": self.lossless.name,
        }

    # -- selection-contest hook (chunking.select_pipeline) -------------------
    def estimate_error(
        self, sample: np.ndarray, abs_eb: float, conf: CompressionConfig
    ) -> float:
        """Estimated coded bits/element on ``sample`` — same currency as the
        other pipelines' estimators.  Fixed-length coding makes this almost
        exact: constant blocks pay the mean + tag, nonconstant blocks pay
        ``w + 1`` bits/element plus the mean/width side channels."""
        x64 = np.asarray(sample, np.float64).reshape(-1)
        if x64.size == 0:
            return 0.0
        bs = self.bs
        itembits = 8.0 * np.dtype(
            sample.dtype if sample.dtype in (np.float32, np.float64) else np.float32
        ).itemsize
        eb = max(float(abs_eb), float(np.finfo(np.float64).tiny))
        xb, _n = _pad_blocks_1d(x64, bs)
        means = xb.mean(axis=1)
        means = np.where(np.isfinite(means), means, 0.0)
        resid = xb - means[:, None]
        with np.errstate(invalid="ignore", over="ignore"):
            dev = np.abs(resid).max(axis=1)
        const = dev <= eb
        q = np.where(np.isfinite(resid), resid, 0.0) / (2.0 * eb)
        mq = np.abs(np.rint(np.clip(q, -_Q_CLIP, _Q_CLIP))).max(axis=1)
        w = _required_bits(mq[~const].astype(np.int64))
        bits = (
            # every block: 1 tag bit + the stored mean
            xb.shape[0] * (1.0 + itembits)
            # nonconstant blocks: width byte + (w+1) bits per element
            + (w.astype(np.float64) + 1.0).sum() * bs
            + w.size * 8.0
        )
        return bits / x64.size

    # -- device routing -------------------------------------------------------
    def _device_stats(self, xb: np.ndarray) -> Optional[Tuple[np.ndarray, np.ndarray]]:
        """(means in storage dtype, max-deviation hint) via the Pallas
        classify+reduce kernel, or None when the host route should run."""
        if self.device == "off":
            return None
        if self.device != "force" and xb.size < _DEVICE_MIN_SIZE:
            return None
        from ..kernels import routing
        from ..kernels.fastmode import ops as fops

        if self.device != "force" and not routing.on_tpu():
            return None
        with tel.span("device_transfer", bytes=xb.nbytes):
            means32, dev32 = fops.block_stats(xb.astype(np.float32, copy=False))
        return means32, dev32.astype(np.float64)

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
            # PW_REL-native: compose the log-domain conversion so the
            # pointwise bound holds by construction
            pre = pre_mod.LogTransform()
        pdata, conf2, pre_meta = pre.forward(data, conf)
        abs_eb = pl_mod.resolve_bound(pdata, conf2)
        with tel.span("quantize", bytes=pdata.nbytes):
            body_parts, fmeta = self._encode_blocks(pdata, abs_eb)
        spec = self.spec()
        spec["preprocessor"] = pre.name  # the EFFECTIVE preprocessor
        header = {
            "v": _VERSION6,
            "kind": "fast",
            "spec": spec,
            "shape": list(data.shape),
            "pshape": list(pdata.shape),
            "dtype": data.dtype.str,
            "pdtype": pdata.dtype.str,
            "mode": conf.mode.value,
            "eb": float(conf.eb),
            "abs_eb": float(abs_eb),
            **(
                {"eb_rel": float(conf.eb_rel)}
                if conf.eb_rel is not None
                else {}
            ),
            "pre_meta": pl_mod._clean_meta(pre_meta),
            "fast_meta": pl_mod._clean_meta(fmeta),
        }
        with tel.span("lossless", bytes=sum(len(p) for p in body_parts)):
            body = self.lossless.compress(b"".join(body_parts))
        blob = pack_container(header, body)
        if tel.enabled():
            nb, n_const = int(fmeta["nb"]), int(fmeta["n_const"])
            tel.record_decision(tel.make_decision(
                "sz3_fast",
                "constant" if n_const * 2 > nb else "fixed_length",
                scope="block-summary",
                candidates=["constant", "fixed_length"],
                estimates={"constant": float(n_const),
                           "fixed_length": float(nb - n_const)},
                realized_bits=8.0 * len(blob) / max(1, data.size),
                n_elems=int(data.size),
                fallbacks=int(fmeta["nfail"]),
                device="device" if fmeta.get("device") else "host",
            ))
        meta = None
        if with_stats:
            meta = {k: v for k, v in fmeta.items() if not isinstance(v, bytes)}
        return CompressionResult(
            blob=blob, ratio=data.nbytes / max(1, len(blob)), meta=meta
        )

    def _encode_blocks(
        self, pdata: np.ndarray, abs_eb: float
    ) -> Tuple[List[bytes], Dict[str, Any]]:
        bs = self.bs
        pdtype = pdata.dtype
        wd = pdtype.type  # ALL block arithmetic runs in the storage dtype
        flat = np.asarray(pdata).reshape(-1)
        n = int(flat.size)
        if n == 0:
            return [b""], {
                "n": 0, "nb": 0, "n_const": 0, "nfail": 0,
                "const_len": 0, "means_len": 0, "w_len": 0, "planes_len": 0,
            }
        xb, nb = _pad_blocks_1d(flat, bs)
        # the verify threshold keeps a relative margin inside eb: work-dtype
        # rounding in the residual/verify passes can under-report a true
        # error by a few ulps, and 1e-6 >> eps for both float32 and float64 —
        # points inside the margin fail to exact storage instead
        eb_strict = float(abs_eb) * (1.0 - 1e-6)
        dev_stats = self._device_stats(xb)
        if dev_stats is not None:
            means_st = dev_stats[0].astype(pdtype, copy=False)
            dev_hint = dev_stats[1]
        else:
            with np.errstate(invalid="ignore", over="ignore"):
                # f64 accumulator: one read pass either way, and block sums
                # can overflow a float32 accumulator for extreme data
                means_st = xb.mean(axis=1, dtype=np.float64).astype(pdtype)
            dev_hint = None
        # blocks whose mean is non-finite (an inf/nan inside) restart from a
        # masked mean so the REST of the block still codes cheaply; the
        # non-finite points themselves go to the fail channel
        bad = ~np.isfinite(means_st)
        if bad.any():
            xbad = xb[bad].astype(np.float64)
            fin = np.isfinite(xbad)
            cnt = np.maximum(fin.sum(axis=1), 1)
            means_st = means_st.copy()
            means_st[bad] = (
                np.where(fin, xbad, 0.0).sum(axis=1) / cnt
            ).astype(pdtype)
            dev_hint = None  # hint no longer matches the stored means
        resid = xb - means_st[:, None]  # storage dtype, the only big temp
        if dev_hint is not None:
            # device hint classifies; constant blocks are then re-VERIFIED on
            # the host against the stored mean (float32 kernel rounding must
            # never widen the bound)
            const = dev_hint <= eb_strict
            if const.any():
                with tel.span("verify") as sp, np.errstate(invalid="ignore"):
                    exact = np.abs(resid[const]).max(axis=1) <= eb_strict
                    sp.set(bytes=exact.size * bs * pdtype.itemsize)
                idx = np.flatnonzero(const)
                const[idx[~exact]] = False
            gmin = gmax = None  # hint is approximate; probe exactly below
        else:
            with np.errstate(invalid="ignore"):
                # max(resid), -min(resid): the deviation without an |resid|
                # temp; nan devs compare False -> nonconstant
                rmax = resid.max(axis=1)
                rmin = resid.min(axis=1)
                dev = np.maximum(rmax, -rmin)
            const = dev <= eb_strict
            gmin, gmax = rmin.min(), rmax.max()  # nan-propagating
        nonconst = ~const
        n_nc = int(nonconst.sum())
        fail_idx = np.zeros(0, np.int64)
        q = np.zeros((0, bs), np.int32)
        w = np.zeros(0, np.uint8)
        if n_nc:
            twoeb = wd(2.0 * float(abs_eb))
            inv = wd(1.0 / (2.0 * float(abs_eb)))
            np.multiply(resid, inv, out=resid)
            if gmin is None:
                with np.errstate(invalid="ignore"):
                    lo, hi = float(resid.min()), float(resid.max())
            else:
                # the block reductions already scanned resid — scale them
                # instead of two more full passes (probe only; an off-by-ulp
                # vs the elementwise scaling still leaves |q| <= 2^30 + 1,
                # well inside the uint32 packing headroom)
                lo, hi = float(gmin) * float(inv), float(gmax) * float(inv)
            if not (lo >= -float(_Q_CLIP) and hi <= float(_Q_CLIP)):
                # non-finite or beyond the code clip — rare, so the sanitize
                # passes only run when the cheap min/max probe trips (the
                # affected points land in the fail channel via the verify)
                np.nan_to_num(resid, copy=False, nan=0.0, posinf=0.0, neginf=0.0)
                np.clip(resid, -float(_Q_CLIP), float(_Q_CLIP), out=resid)
            np.rint(resid, out=resid)
            all_nc = n_nc == nb
            q = (resid if all_nc else resid[nonconst]).astype(np.int32)
            # verify against the decoder's exact reconstruction — same dtype,
            # same operation order — built in place; whatever lands out of
            # bound is stored raw.  After rint, resid == q in the work dtype
            # (both sides of the int32 round trip are exact), so the all-
            # nonconstant case reuses the resid buffer outright.
            with tel.span("verify", bytes=n_nc * bs * pdtype.itemsize):
                if all_nc:
                    err, x_nc, means_nc = resid, xb, means_st
                else:
                    err = q.astype(pdtype)
                    x_nc, means_nc = xb[nonconst], means_st[nonconst]
                np.multiply(err, twoeb, out=err)
                np.add(means_nc[:, None], err, out=err)
                np.subtract(x_nc, err, out=err)  # err is now the coding error
                np.abs(err, out=err)
                with np.errstate(invalid="ignore"):
                    fail_mask = ~(err <= eb_strict)
                if fail_mask.any():
                    # fail positions in the ORIGINAL flat index space (row-
                    # major nonzero keeps them sorted; padding cropped)
                    block_idx = np.flatnonzero(nonconst)
                    rows, cols = np.nonzero(fail_mask)
                    ff = block_idx[rows] * bs + cols
                    fail_idx = ff[ff < n].astype(np.int64)
            w = _required_bits(np.maximum(q.max(axis=1), -q.min(axis=1)))
        const_bytes = np.packbits(const).tobytes()
        means_bytes = means_st.tobytes()
        w_bytes = w.tobytes()
        plane_parts: List[bytes] = []
        for width in np.unique(w):
            width = int(width)
            if width == 0:
                continue  # all-zero residuals: the mean is the payload
            vals = q[w == width].reshape(-1)
            # offset-binary q + 2^w via two's-complement wraparound (the true
            # value is in [0, 2^31], so the low 32 bits ARE the value)
            plane_parts.append(
                _pack_planes(
                    vals.view(np.uint32) + np.uint32(1 << width), width + 1
                )
            )
        planes_bytes = b"".join(plane_parts)
        fmeta: Dict[str, Any] = {
            "n": n,
            "nb": int(nb),
            "n_const": int(const.sum()),
            "nfail": int(fail_idx.size),
            "const_len": len(const_bytes),
            "means_len": len(means_bytes),
            "w_len": len(w_bytes),
            "planes_len": len(planes_bytes),
            "device": 1 if dev_stats is not None else 0,  # routing taken
        }
        if fail_idx.size:
            fmeta["fail_idx"] = fail_idx.tobytes()
            fmeta["fail_vals"] = flat[fail_idx].tobytes()
        return [const_bytes, means_bytes, w_bytes, planes_bytes], fmeta

    # -- decompression (pipeline.decompress dispatch target) ------------------
    @staticmethod
    def _decompress_body(
        blob: bytes, header: Dict[str, Any], body_off: int
    ) -> np.ndarray:
        spec = header["spec"]
        pdtype = np.dtype(header["pdtype"])
        bs = guard_count(spec["bs"], 1 << 20, "fast block size")
        if bs < 1:
            raise ContainerError("corrupt fast container: block size < 1")
        fm = header["fast_meta"]
        # header claims are internally over-determined — recompute the
        # derivable ones and reject any inconsistency before allocating
        n = int(fm["n"])
        if n < 0:
            raise ContainerError("corrupt fast container: negative n")
        guard_alloc(n * pdtype.itemsize, "fast element count")
        nb = int(fm["nb"])
        if nb != (n + bs - 1) // bs:
            raise ContainerError(
                f"corrupt fast container: nb={nb} inconsistent with "
                f"n={n}, bs={bs}"
            )
        conf = CompressionConfig(
            mode=ErrorBoundMode(header["mode"]),
            eb=header["eb"],
            eb_rel=header.get("eb_rel"),
        )
        if n == 0:
            flat = np.zeros(0, pdtype)
        else:
            const_len, means_len = int(fm["const_len"]), int(fm["means_len"])
            w_len = int(fm["w_len"])
            n_const = guard_count(fm["n_const"], nb, "n_const")
            n_nc = nb - n_const
            if const_len != (nb + 7) // 8 or means_len != nb * pdtype.itemsize:
                raise ContainerError(
                    "corrupt fast container: const/means channel lengths "
                    "inconsistent with block count"
                )
            if w_len != n_nc:
                raise ContainerError(
                    "corrupt fast container: width channel length "
                    f"{w_len} != nonconstant block count {n_nc}"
                )
            planes_len = guard_alloc(fm["planes_len"], "planes_len")
            total = const_len + means_len + w_len + planes_len
            body = ll_mod.make(spec["lossless"]).decompress_bounded(
                container_body(blob, body_off), guard_alloc(total, "fast body")
            )
            if len(body) != total:
                raise ContainerError(
                    f"fast body decompressed to {len(body)} bytes; header "
                    f"declares {total}"
                )
            pos = 0
            const = np.unpackbits(
                np.frombuffer(body, np.uint8, count=const_len), count=nb
            ).astype(bool)
            pos += const_len
            means = np.frombuffer(body, pdtype, count=nb, offset=pos)
            pos += means_len
            w = np.frombuffer(body, np.uint8, count=w_len, offset=pos)
            pos += w_len
            abs_eb = float(header["abs_eb"])
            guard_alloc(n_nc * bs * 8, "fast residual grid")
            q = np.zeros((n_nc, bs), np.int64)
            for width in np.unique(w):
                width = int(width)
                sel = w == width
                if width == 0:
                    continue
                cnt = int(sel.sum())
                u, used = _unpack_planes(body, pos, cnt * bs, width + 1)
                pos += used
                q[sel] = u.astype(np.int64).reshape(cnt, bs) - (1 << width)
            # reconstruction runs in the STORAGE dtype with the same
            # operation order the encoder verified against — bit-identical
            # by IEEE determinism, so the encoder-side bound check covers
            # exactly these values
            out = np.empty((nb, bs), pdtype)
            out[:] = means[:, None]
            if n_nc:
                qe = q.astype(pdtype)
                np.multiply(qe, pdtype.type(2.0 * abs_eb), out=qe)
                out[~const] += qe
            flat = out.reshape(-1)[:n]
            if fm.get("nfail"):
                idx = np.frombuffer(fm["fail_idx"], np.int64)
                # explicit bounds check: a negative corrupt index would
                # silently wrap via numpy fancy indexing, an out-of-range one
                # would raise a raw IndexError — both must be ContainerError
                if idx.size and (int(idx.min()) < 0 or int(idx.max()) >= n):
                    raise ContainerError(
                        "corrupt fast container: fail-channel index outside "
                        f"[0, {n})"
                    )
                vals = np.frombuffer(fm["fail_vals"], pdtype)
                if vals.size != idx.size:
                    raise ContainerError(
                        "corrupt fast container: fail-channel index/value "
                        "counts differ"
                    )
                flat[idx] = vals
        dtype = np.dtype(header["dtype"])
        shape = guard_shape(header["shape"], dtype.itemsize, "shape")
        pshape = guard_shape(header["pshape"], pdtype.itemsize, "pshape")
        pdata = flat.reshape(pshape)
        data = pre_mod.make(spec["preprocessor"]).inverse(
            pdata, conf, header["pre_meta"]
        )
        return data.astype(dtype).reshape(shape)


def _pad_blocks_1d(x: np.ndarray, bs: int) -> Tuple[np.ndarray, int]:
    """(nb, bs) view of the flat array (a VIEW when no tail pad is needed —
    callers must not write through it), tail padded with its edge value (the
    pad rides the tail block's own statistics and is cropped on decode)."""
    n = x.size
    nb = (n + bs - 1) // bs
    pad = nb * bs - n
    if pad:
        edge = x[-1] if np.isfinite(x[-1]) else x.dtype.type(0)
        x = np.concatenate([x, np.full(pad, edge, x.dtype)])
    return x.reshape(nb, bs), nb


def sz3_fast(
    bs: int = DEFAULT_BS, lossless: str = "none", device: str = "auto", **kw
) -> FastModeCompressor:
    """Named factory: the SZx-style ultra-fast fixed-length tier (v6)."""
    return FastModeCompressor(
        bs=bs, lossless=ll_mod.make(lossless), device=device, **kw
    )
