"""Serving launcher: ``python -m repro.launch.serve --arch <id> --kv int8``.

Batched greedy decode with the (optionally int8-quantized) KV cache —
the paper's quantizer on the serving path.  ``--offload-kv chunked``
additionally streams the finished cache through the chunked compression
engine (repro.core.chunking) frame by frame — the bounded-memory offload
path for evicting sequences to host/disk under heavy traffic.  The reduced
smoke config runs unless ``--no-smoke``; ``main(argv)`` returns the decoded
token ids and the offload byte counts.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

import repro.configs as configs
from repro import models
from repro.core import telemetry
from repro.parallel import ParallelPlan

from .compile_cache import use_compile_cache

log = telemetry.get_logger("serve")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-8b", choices=configs.ARCHS)
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction, default=True,
                    help="use the reduced config; --no-smoke runs the "
                         "published widths")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--tokens", type=int, default=16)
    ap.add_argument("--kv", default="bf16", choices=["bf16", "int8"])
    ap.add_argument(
        "--offload-kv",
        default="none",
        choices=["none", "chunked", "auto", "hybrid", "quality", "fast"],
        help="'chunked': prediction-pipeline candidates only; 'auto': adds "
        "the sz3_transform and sz3_hybrid candidates (KV channels are often "
        "oscillatory, and mixed hot/cold sequences suit per-block "
        "selection); 'hybrid': the block-hybrid engine only (per-block "
        "predictor selection inside every chunk); 'quality': closed-loop "
        "rate control to --offload-psnr dB instead of a hand-picked error "
        "bound; 'fast': the SZx-style fixed-length tier only — lowest "
        "latency on the eviction path, trading ratio for speed",
    )
    ap.add_argument("--offload-eb", type=float, default=1e-3)
    ap.add_argument(
        "--offload-psnr",
        type=float,
        default=60.0,
        help="PSNR target (dB) for --offload-kv quality",
    )
    ap.add_argument(
        "--offload-workers",
        type=int,
        default=1,
        help="chunk-compression threads for the KV offload stream",
    )
    ap.add_argument(
        "--offload-async",
        action=argparse.BooleanOptionalAction,
        default=False,
        help="route the offload through the async multi-tenant service "
        "(repro.serve.offload): leaves compress concurrently on the worker "
        "pool and verification reads go through the coalescing per-chunk "
        "fetch path instead of full-container decodes",
    )
    ap.add_argument(
        "--offload-executor",
        default="thread",
        choices=["thread", "process"],
        help="worker pool flavor for --offload-async",
    )
    ap.add_argument(
        "--offload-verify",
        action=argparse.BooleanOptionalAction,
        default=True,
        help="strict-decode every offloaded frame on read-back (checksum "
        "trailers verified) before counting it evicted; --no-offload-verify "
        "skips the read-back pass",
    )
    ap.add_argument(
        "--metrics",
        action=argparse.BooleanOptionalAction,
        default=False,
        help="dump the Prometheus-style metrics page (decode-step and "
        "offload-frame latency percentiles, verify-failure counters) and the "
        "per-stage offload trace summary before exiting",
    )
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = configs.get_smoke(args.arch) if args.smoke else configs.get(args.arch)
    plan = ParallelPlan(kv_cache_dtype=args.kv)
    params = models.init_params(jax.random.PRNGKey(0), cfg, plan)
    enc_frames = None
    if cfg.family == "encdec":
        enc_frames = jax.random.normal(
            jax.random.PRNGKey(1), (args.batch, cfg.enc_seq, cfg.d_model),
            cfg.param_dtype,
        )
    cache = models.init_cache(
        params, cfg, plan, args.batch, args.tokens + 8, enc_frames=enc_frames
    )
    step = jax.jit(
        lambda p, c, t: models.decode_step(p, c, t, cfg, plan), donate_argnums=1
    )
    tok = jax.random.randint(jax.random.PRNGKey(2), (args.batch, 1), 0, cfg.vocab)
    out = [tok]
    t0 = time.perf_counter()
    for _ in range(args.tokens):
        ts = time.perf_counter()
        logits, cache = step(params, cache, tok)
        tok = jnp.argmax(logits, -1, keepdims=True).astype(jnp.int32)
        tok.block_until_ready()
        telemetry.metric_observe(
            "sz3_decode_step_seconds", time.perf_counter() - ts
        )
        out.append(tok)
    dt = time.perf_counter() - t0
    seqs = np.concatenate([np.asarray(t) for t in out], axis=1)
    log.info(
        "decode_done", arch=args.arch, kv=args.kv,
        tok_per_s=args.tokens * args.batch / dt,
        sample=str(seqs[0][:12].tolist()),
    )
    tr = offload = None
    if args.offload_kv in ("chunked", "auto", "hybrid", "quality", "fast"):
        candidates = None
        if args.offload_kv == "auto":
            candidates = "auto"
        elif args.offload_kv == "hybrid":
            candidates = ("sz3_hybrid",)
        elif args.offload_kv == "fast":
            candidates = ("sz3_fast",)
        scope = (
            telemetry.trace("kv_offload") if args.metrics
            else _NullScope()
        )
        with scope as tr:
            if args.offload_async and args.offload_kv != "quality":
                offload = offload_cache_async(
                    cache,
                    eb=args.offload_eb,
                    workers=args.offload_workers,
                    candidates=candidates,
                    verify=args.offload_verify,
                    executor=args.offload_executor,
                )
            else:
                offload = offload_cache(
                    cache,
                    eb=args.offload_eb,
                    workers=args.offload_workers,
                    candidates=candidates,
                    target_psnr=args.offload_psnr if args.offload_kv == "quality" else None,
                    verify=args.offload_verify,
                )
    if args.metrics:
        print(telemetry.prometheus_text(), end="")
        if tr is not None:
            print(telemetry.trace_summary(tr))
    return {"tokens": seqs, "offload": offload}


class _NullScope:
    def __enter__(self):
        return None

    def __exit__(self, *exc) -> bool:
        return False


def _iter_kv_leaves(cache):
    """Yield ``(arr, src_dtype_name, src_itemsize)`` per cache leaf.

    ``arr`` is the 2-D float32 working copy the compressor consumes, or
    ``None`` for leaves rejected by the size/dtype filter (callers count
    those as skipped).  ``src_itemsize`` is the itemsize of the leaf's OWN
    dtype — bf16 pages are 2 B/elem at rest, and offload accounting must
    charge what eviction actually frees, not the float32 working copy.
    """
    for leaf in jax.tree.leaves(cache):
        dt = getattr(leaf, "dtype", None)
        # jnp.issubdtype, not numpy dtype.kind: bfloat16 is kind 'V' to numpy
        if dt is None or not jnp.issubdtype(dt, jnp.floating) or leaf.size < 1024:
            yield None, None, 0
            continue
        a = np.asarray(jnp.asarray(leaf, jnp.float32))
        arr = np.ascontiguousarray(a.reshape(a.shape[0], -1) if a.ndim > 1 else a)
        sdt = np.dtype(dt)
        yield arr, sdt.name, sdt.itemsize


def offload_cache(
    cache,
    eb: float = 1e-3,
    chunk_bytes: int = 1 << 20,
    workers: int = 1,
    candidates=None,
    target_psnr: float = None,
    verify: bool = True,
):
    """Stream every float cache leaf through the chunked engine; report totals.

    Frames are produced (and could be written to host/disk) one chunk at a
    time — working memory stays bounded by one chunk regardless of cache size.
    ``candidates="auto"`` (or an explicit name tuple) widens the per-chunk
    contest to the transform coder family.  ``target_psnr`` switches to the
    closed-loop quality-targeted controller: instead of a hand-picked error
    bound, each chunk is compressed at whatever bound hits the PSNR floor,
    and the achieved PSNR is reported alongside the ratio.

    ``verify=True`` strict-decodes every frame on read-back (checksum
    trailers verified, ``repro.core.integrity``) before the bytes are counted
    as safely evicted — the eviction path never trades a live KV page for a
    silently corrupt one.  Verification time is reported separately so the
    cost of the read-back pass is visible.
    """
    from repro.core import (
        AUTO_CANDIDATES,
        CompressionConfig,
        ErrorBoundMode,
        QualityCompressor,
        decompress as sz3_decompress,
    )
    from repro.core.chunking import DEFAULT_CANDIDATES, compress_stream

    if candidates is None:
        candidates = DEFAULT_CANDIDATES
    elif candidates == "auto":
        candidates = AUTO_CANDIDATES
    conf = CompressionConfig(mode=ErrorBoundMode.REL, eb=eb)
    quality = (
        QualityCompressor(
            target_psnr=target_psnr,
            candidates=candidates,
            chunk_bytes=chunk_bytes,
            workers=workers,
        )
        if target_psnr is not None
        else None
    )
    n_in = n_out = n_leaves = n_frames = n_skipped = 0
    worst_psnr = None  # None until a leaf actually qualifies
    src_dtypes = set()
    t_verify = 0.0

    def _verify_frame(frame: bytes) -> float:
        """Strict read-back decode, timed into the request-latency histogram;
        failures are counted (globally and in any active trace) and re-raised."""
        tv = time.perf_counter()
        try:
            sz3_decompress(frame, verify="strict")
        except Exception:
            telemetry.metric_count("sz3_offload_verify_failures_total")
            raise
        dv = time.perf_counter() - tv
        telemetry.metric_observe("sz3_offload_verify_seconds", dv)
        return dv

    t0 = time.perf_counter()
    for arr, src_name, src_itemsize in _iter_kv_leaves(cache):
        if arr is None:
            n_skipped += 1
            continue
        tl = time.perf_counter()
        if quality is not None:
            res = quality.compress(arr)
            n_out += len(res.blob)
            psnr = res.meta["quality"]["achieved_psnr"]
            worst_psnr = psnr if worst_psnr is None else min(worst_psnr, psnr)
            if verify:
                t_verify += _verify_frame(res.blob)
                n_frames += 1
        else:
            for frame in compress_stream(
                arr, conf, candidates=candidates, chunk_bytes=chunk_bytes,
                workers=workers,
            ):
                n_out += len(frame)
                # payload frames only: the stream prologue is not a container
                if verify and frame[:4] == b"SZ3J":
                    t_verify += _verify_frame(frame)
                    n_frames += 1
        telemetry.metric_observe(
            "sz3_offload_leaf_seconds", time.perf_counter() - tl
        )
        # source-dtype bytes: eviction frees the leaf AT REST (bf16 = 2
        # B/elem), not the float32 working copy the compressor consumed —
        # counting arr.nbytes inflated bf16 ratios ~2x
        n_in += arr.size * src_itemsize
        src_dtypes.add(src_name)
        n_leaves += 1
    dt = time.perf_counter() - t0
    telemetry.metric_count("sz3_offload_leaves_total", n_leaves)
    if n_skipped:
        telemetry.metric_count("sz3_offload_leaves_skipped_total", n_skipped)
    telemetry.metric_count("sz3_offload_bytes_in_total", n_in)
    telemetry.metric_count("sz3_offload_bytes_out_total", n_out)
    fields = dict(
        leaves=n_leaves,
        skipped=n_skipped,
        src_dtype=",".join(sorted(src_dtypes)) if src_dtypes else None,
        ratio=n_in / max(1, n_out),
        MB_per_s=n_in / 1e6 / max(dt, 1e-9),
    )
    if verify:
        fields.update(verified_frames=n_frames, verify_seconds=t_verify)
    if quality is not None:
        psnr_field = (
            {} if worst_psnr is None else {"worst_leaf_psnr_db": worst_psnr}
        )
        log.info(
            "kv_offload", mode="quality", target_psnr_db=target_psnr,
            **psnr_field, **fields,
        )
    else:
        log.info("kv_offload", mode="chunked_stream", rel_eb=eb, **fields)
    return n_in, n_out


def offload_cache_async(
    cache,
    eb: float = 1e-3,
    chunk_bytes: int = 1 << 20,
    workers: int = 4,
    candidates=None,
    verify: bool = True,
    executor: str = "thread",
):
    """Offload every qualifying cache leaf through the async service.

    Leaves become pages of one ``kv`` tenant and compress concurrently on
    the service's worker pool; with ``verify`` each page's chunk 0 is
    fetched back through the coalescing read path (strict per-chunk CRC
    validation) before the bytes count as evicted.  Accounting matches
    :func:`offload_cache`: source-dtype bytes in, container bytes out.
    """
    import asyncio

    from repro.core import ErrorBoundMode
    from repro.serve.offload import OffloadService

    async def _run():
        svc = OffloadService(
            workers=workers,
            executor=executor,
            eb=eb,
            mode=ErrorBoundMode.REL,
            candidates=candidates,
            chunk_bytes=chunk_bytes,
            verify="strict" if verify else "off",
        )
        n_in = n_out = n_leaves = n_skipped = 0
        src_dtypes = set()
        t0 = time.perf_counter()
        try:
            puts = []
            for i, (arr, src_name, src_itemsize) in enumerate(
                _iter_kv_leaves(cache)
            ):
                if arr is None:
                    n_skipped += 1
                    continue
                n_in += arr.size * src_itemsize
                src_dtypes.add(src_name)
                puts.append(svc.put("kv", f"leaf{i}", arr))
            reports = await asyncio.gather(*puts)
            n_leaves = len(reports)
            n_out = sum(r["n_out"] for r in reports)
            if verify:
                await asyncio.gather(
                    *[svc.fetch("kv", r["page"], 0) for r in reports]
                )
        finally:
            await svc.close()
        dt = time.perf_counter() - t0
        telemetry.metric_count("sz3_offload_leaves_total", n_leaves)
        if n_skipped:
            telemetry.metric_count("sz3_offload_leaves_skipped_total", n_skipped)
        telemetry.metric_count("sz3_offload_bytes_in_total", n_in)
        telemetry.metric_count("sz3_offload_bytes_out_total", n_out)
        log.info(
            "kv_offload", mode="async_service", rel_eb=eb, leaves=n_leaves,
            skipped=n_skipped,
            src_dtype=",".join(sorted(src_dtypes)) if src_dtypes else None,
            ratio=n_in / max(1, n_out), MB_per_s=n_in / 1e6 / max(dt, 1e-9),
            workers=workers, executor=executor,
        )
        return n_in, n_out

    return asyncio.run(_run())


if __name__ == "__main__":
    main()
