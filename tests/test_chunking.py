"""Chunked streaming engine: round-trips, v1/v2 container compatibility,
per-chunk error bounds, streaming == one-shot, adaptive selection."""
import io

import numpy as np
import pytest

from repro.core import (
    ChunkedCompressor,
    CompressionConfig,
    ErrorBoundMode,
    compress_stream,
    decompress,
    decompress_chunk,
    decompress_stream,
    frames_to_blob,
    parse_header,
    read_frames,
    select_pipeline,
    sz3_lorenzo,
    sz3_lr,
    write_frames,
)
from repro.core.chunking import DEFAULT_CANDIDATES, chunk_slices


def _smooth(shape, dtype, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal(shape)
    for ax in range(x.ndim):
        x = np.cumsum(x, axis=ax) / np.sqrt(x.shape[ax])
    return x.astype(dtype)


def _gamess_like(n_blocks=1500, pattern=96, seed=7):
    """Periodic pattern scaled per block (the paper's GAMESS ERI structure)."""
    rng = np.random.default_rng(seed)
    t = np.linspace(0, 1, pattern)
    base = np.exp(-6 * t) * np.sin(24 * t)
    scales = np.exp(rng.normal(-2.0, 1.0, n_blocks))
    x = scales[:, None] * base[None, :] + rng.normal(0, 1e-4, (n_blocks, pattern))
    return x.reshape(-1)


# ---------------------------------------------------------------------------
# chunk geometry
# ---------------------------------------------------------------------------

def test_chunk_slices_cover_exactly():
    slices = chunk_slices((100, 7), itemsize=8, chunk_bytes=7 * 8 * 9)
    rows = [s.stop - s.start for s in slices]
    assert sum(rows) == 100
    assert all(r <= 9 for r in rows)
    assert slices[0].start == 0 and slices[-1].stop == 100


def test_chunk_slices_huge_row_still_one_row():
    # a single row larger than the budget must still make progress
    slices = chunk_slices((4, 1000), itemsize=8, chunk_bytes=16)
    assert len(slices) == 4


# ---------------------------------------------------------------------------
# round-trips
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [np.float32, np.float64])
@pytest.mark.parametrize("shape", [(4000,), (120, 40), (24, 20, 18)])
def test_roundtrip_dtypes_shapes_multichunk(dtype, shape):
    x = _smooth(shape, dtype)
    eng = ChunkedCompressor(chunk_bytes=x.nbytes // 5)  # force ~5 chunks
    conf = CompressionConfig(mode=ErrorBoundMode.ABS, eb=1e-3)
    res = eng.compress(x, conf, with_stats=True)
    assert len(res.meta["chunks"]) >= 4
    xhat = decompress(res.blob)
    assert xhat.shape == x.shape and xhat.dtype == x.dtype
    assert np.abs(x.astype(np.float64) - xhat.astype(np.float64)).max() <= 1e-3


def test_roundtrip_int_input_casts_like_v1():
    x = np.arange(5000, dtype=np.int32).reshape(50, 100)
    res = ChunkedCompressor(chunk_bytes=4000).compress(
        x, CompressionConfig(mode=ErrorBoundMode.ABS, eb=0.5)
    )
    xhat = decompress(res.blob)
    assert xhat.dtype == np.float32  # same cast rule as the v1 driver
    assert np.abs(x - xhat.astype(np.float64)).max() <= 0.5


def test_error_bound_preserved_per_chunk_rel_mode():
    # REL resolves against GLOBAL range; every chunk must honour that bound
    x = _smooth((300, 64), np.float64, seed=3)
    x[200:] *= 50.0  # chunks with very different local ranges
    conf = CompressionConfig(mode=ErrorBoundMode.REL, eb=1e-4)
    abs_eb = 1e-4 * (x.max() - x.min())
    res = ChunkedCompressor(chunk_bytes=x.nbytes // 6).compress(x, conf)
    xhat = decompress(res.blob)
    assert np.abs(x - xhat).max() <= abs_eb * (1 + 1e-12)


# ---------------------------------------------------------------------------
# container compatibility
# ---------------------------------------------------------------------------

def test_v1_blobs_still_decode():
    x = _smooth((40, 40), np.float32)
    conf = CompressionConfig(mode=ErrorBoundMode.ABS, eb=1e-3)
    blob = sz3_lorenzo().compress(x, conf).blob
    header, _ = parse_header(blob)
    assert header["v"] == 1
    xhat = decompress(blob)
    assert np.abs(x.astype(np.float64) - xhat.astype(np.float64)).max() <= 1e-3


def test_v2_header_records_chunk_table():
    x = _smooth((200, 32), np.float64)
    res = ChunkedCompressor(chunk_bytes=x.nbytes // 4).compress(x, None)
    header, body_off = parse_header(res.blob)
    assert header["v"] == 2 and header["kind"] == "chunked"
    chunks = header["chunks"]
    assert sum(c["n0"] for c in chunks) == 200
    # offsets tile the body exactly
    assert chunks[0]["off"] == 0
    for a, b in zip(chunks, chunks[1:]):
        assert b["off"] == a["off"] + a["len"]
    # chunks tile the DECLARED body exactly; the integrity trailer (if
    # written) sits beyond it, so compare against blen rather than len(blob)
    blen = int.from_bytes(res.blob[12:20], "little")
    assert chunks[-1]["off"] + chunks[-1]["len"] == blen
    assert body_off + blen <= len(res.blob)
    for c in chunks:
        assert c["pipeline"] in DEFAULT_CANDIDATES


def test_random_access_single_chunk():
    x = _smooth((160, 48), np.float64)
    eng = ChunkedCompressor(chunk_bytes=x.nbytes // 4)
    res = eng.compress(x)
    header, _ = parse_header(res.blob)
    full = decompress(res.blob)
    row = 0
    for i, c in enumerate(header["chunks"]):
        part = decompress_chunk(res.blob, i)
        np.testing.assert_array_equal(part, full[row : row + c["n0"]])
        row += c["n0"]


# ---------------------------------------------------------------------------
# streaming
# ---------------------------------------------------------------------------

def test_stream_equals_one_shot_blob_and_data():
    x = _smooth((300, 50), np.float64, seed=11)
    conf = CompressionConfig(mode=ErrorBoundMode.ABS, eb=1e-3)
    cb = x.nbytes // 5
    one_shot = ChunkedCompressor(chunk_bytes=cb).compress(x, conf)
    frames = list(compress_stream(x, conf, chunk_bytes=cb))
    assert frames_to_blob(frames) == one_shot.blob
    parts = list(decompress_stream(frames))
    np.testing.assert_array_equal(np.concatenate(parts), decompress(one_shot.blob))


def test_stream_file_roundtrip_bounded_frames():
    x = _smooth((256, 32), np.float32, seed=5)
    conf = CompressionConfig(mode=ErrorBoundMode.ABS, eb=1e-2)
    buf = io.BytesIO()
    write_frames(compress_stream(x, conf, chunk_bytes=x.nbytes // 8), buf)
    buf.seek(0)
    parts = list(decompress_stream(read_frames(buf)))
    assert len(parts) >= 8
    xhat = np.concatenate(parts)
    assert np.abs(x.astype(np.float64) - xhat.astype(np.float64)).max() <= 1e-2


def test_stream_of_slabs_roundtrips():
    slabs = [_smooth((64, 16), np.float64, seed=s) for s in range(3)]
    conf = CompressionConfig(mode=ErrorBoundMode.ABS, eb=1e-3)
    frames = list(compress_stream(iter(slabs), conf, chunk_bytes=1 << 13))
    xhat = np.concatenate(list(decompress_stream(frames)))
    x = np.concatenate(slabs)
    assert np.abs(x - xhat).max() <= 1e-3


# ---------------------------------------------------------------------------
# adaptive selection
# ---------------------------------------------------------------------------

def test_select_pipeline_returns_candidate_and_scores():
    x = _smooth((64, 64), np.float64)
    name, scores = select_pipeline(
        x, 1e-3, CompressionConfig(mode=ErrorBoundMode.ABS, eb=1e-3)
    )
    assert name in DEFAULT_CANDIDATES
    assert set(scores) <= set(DEFAULT_CANDIDATES)


def test_heterogeneous_data_gets_heterogeneous_pipelines():
    rng = np.random.default_rng(0)
    smooth = _smooth((150, 64), np.float64)
    noise = rng.standard_normal((150, 64)) * 30.0
    x = np.concatenate([smooth, noise])
    res = ChunkedCompressor(chunk_bytes=x.nbytes // 10).compress(
        x, CompressionConfig(mode=ErrorBoundMode.ABS, eb=1e-3), with_stats=True
    )
    picked = {c["pipeline"] for c in res.meta["chunks"]}
    assert len(picked) >= 2, res.meta["chunks"]
    xhat = decompress(res.blob)
    assert np.abs(x - xhat).max() <= 1e-3


def test_chunked_ratio_matches_one_shot_on_gamess_like():
    # acceptance criterion: abs eb 1e-3, ratio within +-5% of the one-shot
    # pipeline (and the bound verified)
    x = _gamess_like()
    conf = CompressionConfig(mode=ErrorBoundMode.ABS, eb=1e-3)
    res = ChunkedCompressor(chunk_bytes=x.nbytes // 4).compress(x, conf)
    xhat = decompress(res.blob)
    assert np.abs(x - xhat).max() <= 1e-3
    best_one_shot = max(
        sz3_lorenzo().compress(x, conf).ratio, sz3_lr().compress(x, conf).ratio
    )
    assert res.ratio >= 0.95 * best_one_shot, (res.ratio, best_one_shot)


# ---------------------------------------------------------------------------
# integration: checkpoint codec
# ---------------------------------------------------------------------------

def test_checkpoint_leaf_uses_chunked_codec_and_roundtrips():
    from repro.ft.checkpoint import LeafPolicy, decode_leaf, encode_leaf

    x = _smooth((1024, 1024), np.float32, seed=2)  # 4 MiB -> chunked codec
    blob, meta = encode_leaf(x, LeafPolicy("lossy", 1e-4))
    # big leaves ride the hybrid (prediction+transform) chunked codec; the
    # legacy "sz3_chunked_rel" tag still decodes (decode_leaf accepts both)
    assert meta["codec"] == "sz3_auto_rel"
    legacy_meta = dict(meta, codec="sz3_chunked_rel")
    assert np.array_equal(decode_leaf(blob, legacy_meta), decode_leaf(blob, meta))
    xhat = decode_leaf(blob, meta)
    assert xhat.shape == x.shape and xhat.dtype == x.dtype
    abs_eb = 1e-4 * float(x.max() - x.min())
    assert np.abs(x.astype(np.float64) - xhat.astype(np.float64)).max() <= abs_eb

    small = _smooth((64, 64), np.float32, seed=2)  # stays on the v1 codec
    blob, meta = encode_leaf(small, LeafPolicy("lossy", 1e-4))
    assert meta["codec"] == "sz3_lorenzo_rel"
    decode_leaf(blob, meta)


# ---------------------------------------------------------------------------
# strided probe sampling (the piecewise-selection bias fix)
# ---------------------------------------------------------------------------

def _piecewise_chunk(n=1 << 18, seed=7):
    """Oscillatory edges, smooth centre: a single centred probe sees ONLY the
    smooth regime and mis-ranks candidates for the 2/3 of the chunk that is
    oscillatory (transform's home turf)."""
    rng = np.random.default_rng(seed)
    t = np.arange(n, dtype=np.float64)
    x = (
        np.sin(0.91 * np.pi * t)
        + 0.05 * np.cumsum(rng.standard_normal(n)) / np.sqrt(n)
    ).astype(np.float32)
    mid = slice(n // 2 - 20000, n // 2 + 20000)
    x[mid] = (np.cumsum(rng.standard_normal(40000)) * 0.01).astype(np.float32)
    return x


def test_sample_block_probes_span_piecewise_regimes():
    """The sample must contain material from the chunk's edges, not just its
    centre: the oscillatory edges have O(1) point-to-point jumps, the smooth
    centre has O(1e-2) ones."""
    from repro.core.chunking import SAMPLE_BUDGET, _sample_block

    x = _piecewise_chunk()
    s = _sample_block(x)
    assert s.size <= SAMPLE_BUDGET
    assert np.abs(np.diff(s.astype(np.float64))).max() > 0.5, (
        "sample saw no oscillatory content — probe placement regressed to "
        "the centre-only block"
    )
    # determinism: same chunk -> same sample (parallel byte-identity relies
    # on selection being a pure function of the chunk)
    assert np.array_equal(s, _sample_block(x))


def test_strided_probes_fix_piecewise_selection_bias():
    """Regression pin for the centred-sample bias: on the piecewise fixture
    the full-array best candidate is the transform coder; multi-probe
    sampling must rank it first, while the old single centred probe
    (probes=1) demonstrably picks a smooth-regime pipeline instead."""
    from repro.core.chunking import _sample_block
    from repro.core import AUTO_CANDIDATES

    x = _piecewise_chunk()
    conf = CompressionConfig(mode=ErrorBoundMode.ABS, eb=1e-3)
    winner, _ = select_pipeline(x, 1e-3, conf, AUTO_CANDIDATES)
    assert winner == "sz3_transform", winner
    # the old behaviour is _sample_block with a single probe: its sample is
    # entirely smooth-centre data, so transform cannot win there
    old_sample = _sample_block(x, probes=1)
    assert np.abs(np.diff(old_sample.astype(np.float64))).max() < 0.5


def test_sample_block_shapes_and_budget():
    from repro.core.chunking import SAMPLE_BUDGET, _sample_block

    for shape in [(64, 64, 64), (1, 1 << 20), (1 << 20,), (4096,), (10, 10)]:
        arr = np.zeros(shape, np.float32)
        s = _sample_block(arr)
        assert s.ndim == arr.ndim
        assert s.size <= max(arr.size, SAMPLE_BUDGET)
        if arr.size > SAMPLE_BUDGET:
            assert s.size >= SAMPLE_BUDGET // 2, (shape, s.shape)
