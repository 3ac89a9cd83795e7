"""Async KV-offload service + cached decode state (PR 9).

Covers the serving tentpole and its satellites: O(chunk) random access with
per-chunk CRC isolation, the reusable Huffman decode-table handle, the
bounded decode-state LRU, gauge metrics, the coalescing async service
(concurrent byte-identity, eviction, typed fault isolation), and the offload
accounting fixes in ``launch/serve``.
"""
import asyncio
import os

import numpy as np
import pytest

from repro.core import (
    CompressionConfig,
    ErrorBoundMode,
    IntegrityError,
    decompress,
    decompress_chunk,
    encoders,
    parse_chunked_index,
    sz3_chunked,
    telemetry,
)
from repro.serve.offload import (
    DecodeStateCache,
    OffloadError,
    OffloadService,
    blob_key,
)


def _field(shape=(96, 96), seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal(shape)
    for ax in range(x.ndim):
        x = np.cumsum(x, axis=ax) / np.sqrt(x.shape[ax])
    return x.astype(np.float32)


@pytest.fixture(scope="module")
def container():
    data = _field()
    conf = CompressionConfig(mode=ErrorBoundMode.ABS, eb=1e-3)
    blob = sz3_chunked(chunk_bytes=4096).compress(data, conf).blob
    return data, blob


def _corrupt_chunk(blob, idx, chunk):
    off, ln = idx.bounds[chunk]
    lo = idx.body_off + off + ln // 2
    return blob[:lo] + bytes([blob[lo] ^ 0xFF]) + blob[lo + 1 :]


# ---------------------------------------------------------------------------
# parse split + O(chunk) strict random access
# ---------------------------------------------------------------------------

class TestChunkedIndex:
    def test_parsed_reads_equal_unparsed(self, container):
        _, blob = container
        idx = parse_chunked_index(blob)
        assert idx.n_chunks > 4
        assert idx.chunk_crcs is not None and idx.header_ok
        for c in (0, 1, idx.n_chunks - 1):
            a = decompress_chunk(blob, c)
            b = decompress_chunk(blob, c, parsed=idx)
            assert np.array_equal(a, b)

    def test_chunks_reassemble_to_full_decode(self, container):
        data, blob = container
        idx = parse_chunked_index(blob)
        parts = [decompress_chunk(blob, c, parsed=idx) for c in range(idx.n_chunks)]
        whole = np.concatenate(parts, axis=0).reshape(data.shape)
        assert np.array_equal(whole, decompress(blob))

    def test_corrupt_other_chunk_does_not_fail_read(self, container):
        """THE satellite pin: strict random access is O(chunk) — a corrupt
        sibling chunk must not fail the requested read."""
        _, blob = container
        idx = parse_chunked_index(blob)
        bad = _corrupt_chunk(blob, idx, chunk=2)
        # the undamaged chunk reads fine, byte-identical, under strict verify
        assert np.array_equal(
            decompress_chunk(bad, 0, verify="strict"),
            decompress_chunk(blob, 0),
        )
        # the damaged chunk itself raises, localized to its index
        with pytest.raises(IntegrityError) as ei:
            decompress_chunk(bad, 2, verify="strict")
        assert ei.value.chunk_index == 2
        # and the whole-container strict decode still refuses the blob
        with pytest.raises(IntegrityError):
            decompress(bad, verify="strict")

    def test_header_damage_fails_every_read(self, container):
        _, blob = container
        bad = blob[:22] + bytes([blob[22] ^ 0xFF]) + blob[23:]
        with pytest.raises(ValueError):
            decompress_chunk(bad, 0, verify="strict")

    def test_verify_off_skips_crc(self, container):
        _, blob = container
        idx = parse_chunked_index(blob)
        bad = _corrupt_chunk(blob, idx, chunk=1)
        # verify="off" reaches the nested decode; it may raise a decode error
        # or return garbage, but must not raise on the UNDAMAGED chunk
        out = decompress_chunk(bad, 0, verify="off")
        assert np.array_equal(out, decompress_chunk(blob, 0))

    def test_rejects_non_chunked_blob(self):
        with pytest.raises(ValueError):
            parse_chunked_index(b"garbage not a container")


# ---------------------------------------------------------------------------
# huffman decode-table handle + LRU
# ---------------------------------------------------------------------------

class TestHuffmanHandle:
    def test_handle_decode_equals_plain(self):
        rng = np.random.default_rng(2)
        codes = rng.integers(0, 200, 5000)
        enc = encoders.HuffmanEncoder()
        buf = enc.encode(codes)
        h = encoders.huffman_decode_handle(buf)
        assert h is not None
        a = enc.decode(buf, codes.size)
        b = enc.decode(buf, codes.size, handle=h)
        c = enc.decode(buf, codes.size, handle=h)  # reuse
        assert np.array_equal(a, codes) and np.array_equal(b, codes)
        assert np.array_equal(c, codes)

    def test_empty_stream_handle_is_none(self):
        enc = encoders.HuffmanEncoder()
        buf = enc.encode(np.zeros(0, np.int64))
        assert encoders.huffman_decode_handle(buf) is None
        assert enc.decode(buf, 0).size == 0

    def test_table_cache_lru_bound_and_stats(self):
        encoders.clear_table_cache()
        rng = np.random.default_rng(3)
        enc = encoders.HuffmanEncoder()
        # distinct alphabets -> distinct length signatures -> distinct entries
        bufs = []
        for k in range(5):
            codes = rng.integers(0, 10 + 17 * k, 2000)
            bufs.append((enc.encode(codes), codes))
        old_max = encoders._TABLE_CACHE_MAX
        encoders._TABLE_CACHE_MAX = 3
        try:
            encoders.clear_table_cache()
            for buf, codes in bufs:
                assert np.array_equal(enc.decode(buf, codes.size), codes)
            stats = encoders.table_cache_stats()
            assert stats["size"] <= 3
            assert stats["evictions"] >= 2
            # hot entry hits
            enc.decode(bufs[-1][0], bufs[-1][1].size)
            assert encoders.table_cache_stats()["hits"] > stats["hits"] - 1
        finally:
            encoders._TABLE_CACHE_MAX = old_max
            encoders.clear_table_cache()


# ---------------------------------------------------------------------------
# telemetry gauges
# ---------------------------------------------------------------------------

class TestGauges:
    def test_gauge_set_add_snapshot_prometheus(self):
        reg = telemetry.MetricsRegistry()
        reg.gauge("sz3_serve_queue_depth", 3)
        assert reg.gauge_add("sz3_serve_queue_depth", 2) == 5.0
        assert reg.gauge_add("sz3_serve_queue_depth", -5) == 0.0
        reg.gauge("sz3_serve_pages", 7)
        snap = reg.snapshot()
        assert snap["gauges"]["sz3_serve_pages"] == 7.0
        text = reg.prometheus_text()
        assert "# TYPE sz3_serve_pages gauge" in text
        assert "sz3_serve_pages 7" in text
        reg.reset()
        assert reg.snapshot()["gauges"] == {}


# ---------------------------------------------------------------------------
# decode-state cache
# ---------------------------------------------------------------------------

class TestDecodeStateCache:
    def test_index_identity_and_hit(self, container):
        _, blob = container
        cache = DecodeStateCache(max_entries=4)
        i1 = cache.index_for(blob)
        i2 = cache.index_for(blob)
        assert i1 is i2
        s = cache.stats()
        assert s["hits"] == 1 and s["misses"] == 1

    def test_lru_eviction_under_bound(self):
        conf = CompressionConfig(mode=ErrorBoundMode.ABS, eb=1e-3)
        comp = sz3_chunked(chunk_bytes=4096)
        blobs = [comp.compress(_field(seed=s), conf).blob for s in range(4)]
        assert len({blob_key(b) for b in blobs}) == 4
        cache = DecodeStateCache(max_entries=2)
        for b in blobs:
            cache.index_for(b)
        s = cache.stats()
        assert s["entries"] == 2 and s["evictions"] == 2
        # most-recent two are resident, oldest two were evicted
        cache.index_for(blobs[-1])
        assert cache.stats()["hits"] == 1
        cache.index_for(blobs[0])
        assert cache.stats()["misses"] == 5

    def test_chunk_result_cache_budget(self, container):
        _, blob = container
        idx = parse_chunked_index(blob)
        arrs = [decompress_chunk(blob, c, parsed=idx) for c in range(3)]
        budget = arrs[0].nbytes * 2  # room for two chunks, not three
        cache = DecodeStateCache(max_entries=4, max_chunk_bytes=budget)
        for c, a in enumerate(arrs):
            cache.put_chunk(blob, c, a)
        s = cache.stats()
        assert s["chunk_entries"] == 2 and s["chunk_evictions"] == 1
        assert s["chunk_bytes"] <= budget
        # LRU: chunk 0 was evicted, chunk 2 is hot
        assert cache.get_chunk(blob, 0) is None
        hot = cache.get_chunk(blob, 2)
        assert hot is not None and np.array_equal(hot, arrs[2])
        assert not hot.flags.writeable

    def test_invalidate_drops_index_and_chunks(self, container):
        _, blob = container
        cache = DecodeStateCache()
        cache.index_for(blob)
        cache.put_chunk(blob, 0, decompress_chunk(blob, 0))
        cache.invalidate(blob)
        s = cache.stats()
        assert s["entries"] == 0 and s["chunk_entries"] == 0


# ---------------------------------------------------------------------------
# the async service
# ---------------------------------------------------------------------------

class TestOffloadService:
    def test_put_fetch_roundtrip_and_report(self):
        data = _field(seed=5)

        async def run():
            async with OffloadService(workers=2, chunk_bytes=4096) as svc:
                rep = await svc.put("t", "p", data)
                assert rep["n_in"] == data.nbytes and rep["chunks"] > 1
                assert rep["ratio"] == pytest.approx(
                    data.nbytes / rep["n_out"]
                )
                whole = await svc.fetch("t", "p")
                np.testing.assert_allclose(whole, data, atol=1e-3)

        asyncio.run(run())

    def test_concurrent_fetches_byte_identical_to_serial(self, container):
        """Acceptance criterion: 4-worker concurrent fetch == serial."""
        _, blob = container
        n = parse_chunked_index(blob).n_chunks
        serial = [decompress_chunk(blob, c) for c in range(n)]

        async def run():
            async with OffloadService(workers=4, coalesce_ms=1.0) as svc:
                await svc.put_compressed("t", "p", blob)
                outs = await asyncio.gather(
                    *[svc.fetch("t", "p", c) for c in range(n)]
                )
                for a, b in zip(outs, serial):
                    assert a.dtype == b.dtype and np.array_equal(a, b)

        asyncio.run(run())

    def test_coalesced_equals_unbatched(self, container):
        _, blob = container
        n = parse_chunked_index(blob).n_chunks
        order = list(np.random.default_rng(7).integers(0, n, 24))

        async def run():
            telemetry.reset_metrics()
            async with OffloadService(workers=2, coalesce_ms=3.0) as svc:
                await svc.put_compressed("t", "p", blob)
                batched = await asyncio.gather(
                    *[svc.fetch("t", "p", int(c)) for c in order]
                )
            async with OffloadService(workers=2, coalesce_ms=0.0) as svc0:
                await svc0.put_compressed("t", "p", blob)
                unbatched = await asyncio.gather(
                    *[svc0.fetch("t", "p", int(c)) for c in order]
                )
            for a, b in zip(batched, unbatched):
                assert np.array_equal(a, b)
            counters = telemetry.METRICS.snapshot()["counters"]
            # the 3 ms window must actually coalesce: fewer batches than
            # requests on the batching service
            assert counters["sz3_serve_batches_total"] < 2 * len(order)
            assert counters["sz3_serve_batched_requests_total"] >= 2 * len(order)

        asyncio.run(run())

    def test_fault_isolated_to_owning_request(self, container):
        """Acceptance criterion: a fault-injected frame surfaces a typed
        error to exactly the owning request; siblings complete."""
        _, blob = container
        idx = parse_chunked_index(blob)
        bad = _corrupt_chunk(blob, idx, chunk=3)

        async def run():
            async with OffloadService(workers=2, coalesce_ms=2.0) as svc:
                await svc.put_compressed("t", "bad", bad)
                results = await asyncio.gather(
                    *[svc.fetch("t", "bad", c) for c in range(5)],
                    return_exceptions=True,
                )
                for c, r in enumerate(results):
                    if c == 3:
                        assert isinstance(r, OffloadError)
                        assert r.cause_type == "IntegrityError"
                        assert r.chunk == 3 and r.chunk_index == 3
                        assert r.tenant == "t" and r.page == "bad"
                    else:
                        assert isinstance(r, np.ndarray)
                        assert np.array_equal(r, decompress_chunk(blob, c))

        asyncio.run(run())

    def test_service_lru_eviction_under_bound(self):
        conf = CompressionConfig(mode=ErrorBoundMode.ABS, eb=1e-3)
        comp = sz3_chunked(chunk_bytes=4096)
        blobs = [comp.compress(_field(seed=10 + s), conf).blob for s in range(3)]

        async def run():
            async with OffloadService(workers=2, cache_entries=2) as svc:
                for i, b in enumerate(blobs):
                    await svc.put_compressed("t", f"p{i}", b)
                s = svc.cache.stats()
                assert s["entries"] == 2 and s["evictions"] >= 1
                # evicted page still FETCHES fine (cache re-parses on miss)
                out = await svc.fetch("t", "p0", 0)
                assert np.array_equal(out, decompress_chunk(blobs[0], 0))

        asyncio.run(run())

    def test_evict_and_unknown_page(self, container):
        _, blob = container

        async def run():
            async with OffloadService(workers=1) as svc:
                await svc.put_compressed("t", "p", blob)
                assert await svc.evict("t", "p") is True
                assert await svc.evict("t", "p") is False
                with pytest.raises(OffloadError):
                    await svc.fetch("t", "p", 0)

        asyncio.run(run())

    def test_queue_depth_gauge_returns_to_zero(self, container):
        _, blob = container

        async def run():
            telemetry.reset_metrics()
            async with OffloadService(workers=2, coalesce_ms=1.0) as svc:
                await svc.put_compressed("t", "p", blob)
                await asyncio.gather(*[svc.fetch("t", "p", c) for c in range(6)])
            assert telemetry.METRICS.gauge_value("sz3_serve_queue_depth") == 0.0
            hist = telemetry.METRICS.snapshot()["histograms"]
            assert hist["sz3_serve_request_seconds"]["count"] == 6

        asyncio.run(run())

    @pytest.mark.slow
    def test_process_executor_smoke(self, container):
        _, blob = container

        async def run():
            async with OffloadService(
                workers=2, executor="process", coalesce_ms=1.0
            ) as svc:
                await svc.put_compressed("t", "p", blob)
                outs = await asyncio.gather(
                    *[svc.fetch("t", "p", c) for c in range(3)]
                )
                for c, a in enumerate(outs):
                    assert np.array_equal(a, decompress_chunk(blob, c))

        asyncio.run(run())

    def test_process_workers_are_host_only(self, monkeypatch):
        """Spawned workers pin JAX to the CPU whatever the parent's
        environment says: the parent holds the chip."""
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)

        async def run():
            async with OffloadService(workers=1, executor="process") as svc:
                loop = svc._ensure_started()
                return await loop.run_in_executor(
                    svc._executor, os.getenv, "JAX_PLATFORMS"
                )

        assert asyncio.run(run()) == "cpu"

    def test_service_survives_two_event_loops(self, container):
        _, blob = container
        svc = OffloadService(workers=1, coalesce_ms=0.5)

        async def put():
            await svc.put_compressed("t", "p", blob)

        async def fetch():
            out = await svc.fetch("t", "p", 0)
            assert np.array_equal(out, decompress_chunk(blob, 0))
            await svc.close()

        asyncio.run(put())
        asyncio.run(fetch())


# ---------------------------------------------------------------------------
# offload accounting fixes (launch/serve satellites)
# ---------------------------------------------------------------------------

class TestOffloadAccounting:
    @pytest.fixture(scope="class")
    def jnp(self):
        jnp = pytest.importorskip("jax.numpy")
        return jnp

    def _cache(self, jnp, seed=0):
        rng = np.random.default_rng(seed)
        k = np.cumsum(rng.standard_normal((64, 256)), axis=0)
        return {
            "k_bf16": jnp.asarray(k, jnp.bfloat16),
            "v_f32": jnp.asarray(rng.standard_normal((64, 256)), jnp.float32),
            "pos_i32": jnp.zeros((4,), jnp.int32),  # skipped: not float
            "tiny": jnp.zeros((8, 8), jnp.float32),  # skipped: < 1024 elems
        }

    def test_n_in_counts_source_dtype_bytes(self, jnp):
        from repro.launch.serve import offload_cache

        telemetry.reset_metrics()
        n_in, n_out = offload_cache(
            self._cache(jnp), eb=1e-3, chunk_bytes=1 << 14, verify=False
        )
        # bf16 leaf at 2 B/elem + f32 leaf at 4 B/elem — NOT 4 B for both
        assert n_in == 64 * 256 * 2 + 64 * 256 * 4
        assert n_out > 0
        counters = telemetry.METRICS.snapshot()["counters"]
        assert counters["sz3_offload_leaves_skipped_total"] == 2
        assert counters["sz3_offload_bytes_in_total"] == n_in

    def test_quality_mode_all_skipped_no_inf_psnr(self, jnp, caplog):
        import logging

        from repro.launch.serve import offload_cache

        telemetry.reset_metrics()
        empty = {"pos": jnp.zeros((4,), jnp.int32)}
        with caplog.at_level(logging.INFO, logger="repro.telemetry.serve"):
            n_in, n_out = offload_cache(empty, target_psnr=60.0)
        assert (n_in, n_out) == (0, 0)
        text = " ".join(r.getMessage() for r in caplog.records)
        assert "worst_leaf_psnr_db" not in text
        assert "inf" not in text
        counters = telemetry.METRICS.snapshot()["counters"]
        assert counters["sz3_offload_leaves_skipped_total"] == 1

    def test_async_service_offload_matches_accounting(self, jnp):
        from repro.launch.serve import offload_cache_async

        telemetry.reset_metrics()
        n_in, n_out = offload_cache_async(
            self._cache(jnp), eb=1e-3, chunk_bytes=1 << 14, workers=2
        )
        assert n_in == 64 * 256 * 2 + 64 * 256 * 4
        assert 0 < n_out < n_in
