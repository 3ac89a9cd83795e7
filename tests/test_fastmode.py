"""Tests for the SZx-style ultra-fast fixed-length tier (v6, ``sz3_fast``).

Covers the format contract (round-trip + pointwise bound across shapes,
dtypes and edge sizes), the constant-block fast path, the exact fail channel
(non-finite values and clip-range stragglers), the device classify+reduce
route (kernel-vs-ref agreement and both-routes bound), the bits/element
estimator, and the ``speed_tier`` selection knob that makes the tier win the
chunked contest when throughput is priced in.
"""
import numpy as np
import pytest

from repro.core import (
    CompressionConfig,
    ErrorBoundMode,
    FastModeCompressor,
    PIPELINES,
    decompress,
    parse_header,
    sz3_chunked,
    sz3_fast,
)
from repro.core.chunking import select_pipeline


def _bound_ok(x, xhat, eb, slack=1e-6):
    x64 = np.asarray(x, np.float64)
    fin = np.isfinite(x64)
    err = np.abs(x64[fin] - np.asarray(xhat, np.float64)[fin])
    return float(err.max(initial=0.0)) <= eb * (1 + slack)


def _smooth(n, seed=0, dtype=np.float32):
    rng = np.random.default_rng(seed)
    return np.cumsum(rng.standard_normal(n)).astype(dtype)


# ---------------------------------------------------------------------------
# round-trip + bound across shapes / dtypes / edge sizes
# ---------------------------------------------------------------------------
@pytest.mark.parametrize(
    "shape,dtype",
    [
        ((1000,), np.float32),
        ((37, 53), np.float64),
        ((5,), np.float32),  # smaller than one block
        ((256,), np.float32),  # exactly one block
        ((4, 4, 17), np.float64),
        ((0,), np.float32),  # empty
    ],
)
def test_roundtrip_abs(shape, dtype):
    rng = np.random.default_rng(int(np.prod(shape)) + 1)
    x = np.cumsum(rng.standard_normal(shape).reshape(-1)).reshape(shape)
    x = x.astype(dtype)
    eb = 1e-3
    blob = sz3_fast().compress(x, CompressionConfig(ErrorBoundMode.ABS, eb)).blob
    xhat = decompress(blob)
    assert xhat.shape == x.shape and xhat.dtype == x.dtype
    assert _bound_ok(x, xhat, eb)
    header, _ = parse_header(blob)
    assert header["v"] == 6 and header["kind"] == "fast"


def test_roundtrip_rel():
    x = _smooth(20000, seed=7)
    conf = CompressionConfig(ErrorBoundMode.REL, 1e-4)
    xhat = decompress(sz3_fast().compress(x, conf).blob)
    assert _bound_ok(x, xhat, 1e-4 * float(x.max() - x.min()))


@pytest.mark.parametrize("bs", [128, 256])
def test_block_sizes(bs):
    x = _smooth(5000, seed=3)
    blob = sz3_fast(bs=bs).compress(
        x, CompressionConfig(ErrorBoundMode.ABS, 1e-3)
    ).blob
    header, _ = parse_header(blob)
    assert header["spec"]["bs"] == bs
    assert _bound_ok(x, decompress(blob), 1e-3)


def test_invalid_block_size_rejected():
    with pytest.raises(ValueError, match="block size"):
        sz3_fast(bs=100)


# ---------------------------------------------------------------------------
# constant-block fast path
# ---------------------------------------------------------------------------
def test_constant_array_all_const_blocks():
    x = np.full(5000, 3.25, np.float32)
    res = sz3_fast().compress(
        x, CompressionConfig(ErrorBoundMode.ABS, 1e-6), with_stats=True
    )
    assert res.meta["n_const"] == res.meta["nb"]
    assert np.array_equal(decompress(res.blob), x)  # mean == the exact value
    # 1 tag bit + one mean per 256 elements: ~50x+ on constant data
    assert res.ratio > 30


def test_mixed_const_and_coded_blocks():
    rng = np.random.default_rng(11)
    x = np.concatenate(
        [np.full(1024, -2.5), np.cumsum(rng.standard_normal(1024)), np.zeros(512)]
    ).astype(np.float32)
    res = sz3_fast().compress(
        x, CompressionConfig(ErrorBoundMode.ABS, 1e-3), with_stats=True
    )
    assert 0 < res.meta["n_const"] < res.meta["nb"]
    assert _bound_ok(x, decompress(res.blob), 1e-3)


# ---------------------------------------------------------------------------
# exact fail channel: non-finite values and clip-range stragglers
# ---------------------------------------------------------------------------
def test_nonfinite_exact_restore():
    x = _smooth(4096, seed=5)
    x[5], x[99], x[2047] = np.nan, np.inf, -np.inf
    xhat = decompress(
        sz3_fast().compress(x, CompressionConfig(ErrorBoundMode.ABS, 1e-3)).blob
    )
    assert np.isnan(xhat[5]) and xhat[99] == np.inf and xhat[2047] == -np.inf
    assert _bound_ok(x, xhat, 1e-3)


def test_clip_range_outlier_rides_fail_channel():
    # a residual of ~1e30/(2e-6) quantization steps blows the 2^30 code clip;
    # the point must come back exact through the fail channel, not clamped
    x = _smooth(2048, seed=9)
    x[7] = np.float32(1e30)
    res = sz3_fast().compress(
        x, CompressionConfig(ErrorBoundMode.ABS, 1e-6), with_stats=True
    )
    xhat = decompress(res.blob)
    assert xhat[7] == x[7]
    assert res.meta["nfail"] >= 1
    assert _bound_ok(x, xhat, 1e-6)


# ---------------------------------------------------------------------------
# device route: kernel-vs-ref agreement and both-routes bound
# ---------------------------------------------------------------------------
def test_kernel_matches_reference_stats():
    jax = pytest.importorskip("jax")
    from repro.kernels.fastmode import ops as fops

    rng = np.random.default_rng(2)
    xb = np.cumsum(rng.standard_normal((64, 256)), axis=1).astype(np.float32)
    means_k, dev_k = fops.block_stats(xb)
    means_r, dev_r = fops.ref_block_stats(jax.numpy.asarray(xb))
    np.testing.assert_allclose(means_k, np.asarray(means_r), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(dev_k, np.asarray(dev_r), rtol=1e-5, atol=1e-5)


def test_device_route_bound_holds():
    pytest.importorskip("jax")
    # f64 input through the f32 kernel hint: classification may differ from
    # the host route, but the bound must close identically (host verify)
    x = _smooth(70000, seed=4, dtype=np.float64)
    conf = CompressionConfig(ErrorBoundMode.ABS, 1e-3)
    xhat = decompress(sz3_fast(device="force").compress(x, conf).blob)
    assert _bound_ok(x, xhat, 1e-3)
    # host route on the same data for cross-checking the decode path
    assert _bound_ok(x, decompress(sz3_fast(device="off").compress(x, conf).blob), 1e-3)


# ---------------------------------------------------------------------------
# estimator + selection knob
# ---------------------------------------------------------------------------
def test_estimate_error_orders_workloads():
    comp = FastModeCompressor()
    conf = CompressionConfig(ErrorBoundMode.ABS, 1e-3)
    rng = np.random.default_rng(6)
    const_bits = comp.estimate_error(np.full(4096, 1.5, np.float32), 1e-3, conf)
    noisy_bits = comp.estimate_error(
        rng.standard_normal(4096).astype(np.float32) * 100, 1e-3, conf
    )
    assert 0 < const_bits < 1.0  # ~tag + mean amortized over 256 elements
    assert noisy_bits > 5 * const_bits


def test_throughput_tier_selects_fast():
    x = _smooth(1 << 15, seed=8)
    conf = CompressionConfig(ErrorBoundMode.ABS, 1e-3)
    candidates = ("sz3_lorenzo", "sz3_fast", "sz3_hybrid")
    ratio_winner, _ = select_pipeline(x, 1e-3, conf, candidates)
    tp_winner, costs = select_pipeline(
        x, 1e-3, conf, candidates, speed_tier="throughput"
    )
    assert tp_winner == "sz3_fast"
    assert set(costs) == set(candidates)
    # ratio mode still prices coded bits only — smooth data favours Lorenzo
    assert ratio_winner != "sz3_fast"


def test_chunked_throughput_tier_end_to_end():
    x = _smooth(1 << 16, seed=10)
    conf = CompressionConfig(ErrorBoundMode.ABS, 1e-3)
    eng = sz3_chunked(chunk_bytes=1 << 16, speed_tier="throughput")
    res = eng.compress(x, conf, with_stats=True)
    assert all(c["pipeline"] == "sz3_fast" for c in res.meta["chunks"])
    assert _bound_ok(x, decompress(res.blob), 1e-3)


def test_invalid_speed_tier_rejected():
    with pytest.raises(ValueError, match="speed_tier"):
        sz3_chunked(speed_tier="nope")


def test_fast_registered_everywhere():
    from repro.core import AUTO_CANDIDATES

    assert "sz3_fast" in PIPELINES
    assert "sz3_fast" in AUTO_CANDIDATES
