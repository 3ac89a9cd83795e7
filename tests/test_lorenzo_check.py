"""The Lorenzo device route's bound check, certified on the chip.

``lorenzo_check`` flags every point whose reconstruction under
either decode route can come near the bound, and the host re-checks only
those.  Its containers must be byte for byte those of the full host check
(``lorenzo_device_fail``, which the route keeps as its fallback when more
points are flagged than ``CHECK_CAPACITY`` holds): the same codes, the same
unpredictable channel and the same ``pred_meta`` (``nfail``, ``fail_mask``,
``fail_vals``).  The kernels run in Pallas interpret mode here.
"""
import numpy as np
import pytest

from repro.core import CompressionConfig, ErrorBoundMode, decompress, parse_header
from repro.core import quantizers, sz3_lorenzo
from repro.core import telemetry as tel
from repro.core.predictors import lorenzo_device_fail
from repro.kernels.lorenzo import ops as lops

RADIUS = 32768
COUNTERS = ("verify_device", "verify_rechecked", "verify_host_fallback")


def _smooth(shape, eb, rng):
    """A random walk along every axis spanning about 2,000 eb."""
    x = rng.standard_normal(shape)
    for ax in range(len(shape)):
        x = np.cumsum(x, axis=ax)
    x = (x - x.min()) / max(float(np.ptp(x)), 1e-30)
    return ((x - 0.5) * 2000 * eb).astype(np.float32)


def _adversarial(shape, eb, rng):
    """A smooth field with 5% of its points on half-grid values (k + 1/2) 2eb,
    moved by -1, 0 or +1 ulp; 0.1% at magnitudes whose |q| lies just under
    ``PIPELINE_SAFE``; and 0.1% spikes whose differences pass the radius."""
    flat = _smooth(shape, eb, rng).reshape(-1)
    n = flat.size
    pos = rng.permutation(n)
    k, m = n // 20, max(4, n // 1000)
    half = ((rng.integers(-500, 500, k) + 0.5) * 2 * eb).astype(np.float32)
    step = rng.integers(-1, 2, k)
    half = np.where(step > 0, np.nextafter(half, np.float32(np.inf)), half)
    half = np.where(step < 0, np.nextafter(half, np.float32(-np.inf)), half)
    flat[pos[:k]] = half
    top = (lops.PIPELINE_SAFE - 1.0 - rng.random(m)) * 2 * eb
    flat[pos[k:k + m]] = (np.where(rng.random(m) < 0.5, -top, top)).astype(np.float32)
    spike = (RADIUS + rng.integers(0, 1000, m)) * 2 * eb * np.where(rng.random(m) < 0.5, -1, 1)
    flat[pos[k + m:k + 2 * m]] += spike.astype(np.float32)
    assert float(np.abs(flat).max()) / (2 * eb) < lops.PIPELINE_SAFE
    return flat.reshape(shape)


def _all_ties(shape, eb, rng):
    """Every point on a half-grid value: every point is flagged."""
    return ((rng.integers(-500, 500, shape) + 0.5) * 2 * eb).astype(np.float32)


KINDS = {"smooth": _smooth, "adversarial": _adversarial}


@pytest.fixture
def full_host_check(monkeypatch):
    """Make every device encode report an overflowing candidate buffer, so
    the route takes its full host check: the bytes of the check it replaced."""
    real = lops.encode_checked

    def overflowing(*args, **kw):
        enc = real(*args, **kw)
        return enc._replace(n=lops.CHECK_CAPACITY + 1, idx=enc.idx[:0])

    def use(on):
        monkeypatch.setattr(lops, "encode_checked", overflowing if on else real)

    return use


def _compress(x, eb):
    conf = CompressionConfig(mode=ErrorBoundMode.ABS, eb=eb)
    with tel.trace() as tr:
        res = sz3_lorenzo(device="force").compress(x, conf, with_stats=True)
    counters = {k: v for k, v in tr.counters.items() if k in COUNTERS}
    return res, counters


def _expected_fail(x, eb):
    """The fail mask and raw differences of the full host check, computed
    from the encode kernel's output directly."""
    mode = "2d" if x.ndim == 2 else "1d"
    _, draw = lops.lorenzo_encode(x.reshape(-1, x.shape[-1]), eb=eb, radius=RADIUS, mode=mode)
    draw = np.asarray(draw).reshape(x.shape)
    quantizer = quantizers.LinearScaleQuantizer(radius=RADIUS)
    quantizer.begin(eb, np.float32)
    return lorenzo_device_fail(x, draw, quantizer).reshape(-1), draw.reshape(-1)


def _assert_meta(meta, fail, x):
    assert meta["device"] == 1
    assert meta["nfail"] == int(fail.sum())
    if fail.any():
        assert meta["fail_mask"] == np.packbits(fail).tobytes()
        assert meta["fail_vals"] == x.reshape(-1)[fail].astype(np.float64).tobytes()
    else:
        assert "fail_mask" not in meta and "fail_vals" not in meta


@pytest.mark.parametrize("kind", sorted(KINDS))
@pytest.mark.parametrize("eb", [1e-2, 1e-3, 1e-4])
@pytest.mark.parametrize("shape", [(8192,), (64, 256), (257, 1000)])
def test_bytes_equal_full_host_check(full_host_check, shape, eb, kind):
    x = KINDS[kind](shape, eb, np.random.default_rng([len(shape), int(-np.log10(eb))]))
    full_host_check(False)
    res, counters = _compress(x, eb)
    assert counters["verify_device"] == 1 and "verify_host_fallback" not in counters
    assert 0 <= counters["verify_rechecked"] <= lops.CHECK_CAPACITY
    full_host_check(True)
    oracle, oracle_counters = _compress(x, eb)
    assert oracle_counters == {"verify_device": 1, "verify_host_fallback": 1}
    assert res.blob == oracle.blob
    assert np.array_equal(res.codes, oracle.codes) and res.codes.dtype == np.uint16
    fail, draw = _expected_fail(x, eb)
    _assert_meta(res.meta, fail, x)
    if kind == "adversarial":
        assert fail.any() and (np.abs(draw) >= RADIUS).any()
    header, _ = parse_header(res.blob)
    assert header["pred_meta"]["nfail"] == res.meta["nfail"]
    err = np.abs(decompress(res.blob).astype(np.float64) - x.astype(np.float64))
    assert err.max() <= eb


@pytest.mark.parametrize("kind", ["smooth", "adversarial", "all_ties"])
def test_flags_cover_every_failing_point(kind):
    """The flags cover each point the full host check fails and each
    unpredictable difference, in ascending order; the smooth field flags
    few (the first search size), the adversarial one more, ties all."""
    eb, shape = 1e-3, (257, 1000)
    make = {"all_ties": _all_ties, **KINDS}[kind]
    x = make(shape, eb, np.random.default_rng(11))
    codes, draw = lops.lorenzo_encode(x, eb=eb, radius=RADIUS, mode="2d")
    codes, n, cand = lops.lorenzo_check(x, codes, draw, eb=eb, mode="2d")
    n = int(n)
    fail, raw = _expected_fail(x, eb)
    assert np.array_equal(np.asarray(draw).reshape(-1), raw)
    if kind == "all_ties":
        assert n == x.size
        return
    assert (n <= lops._CHECK_FIRST) == (kind == "smooth") and n <= lops.CHECK_CAPACITY
    idx = np.asarray(cand[0])[:n]
    assert np.all(np.diff(idx) > 0)
    flagged = np.zeros(x.size, bool)
    flagged[idx] = True
    assert not (fail & ~flagged).any()
    assert not ((np.asarray(codes).reshape(-1) == 0) & ~flagged).any()
    if kind == "smooth":
        # the margin is about 2^-21 |x| / eb of the bound: ~5e-4 of the points
        assert n < x.size // 100


def test_overflow_takes_the_full_host_check(full_host_check):
    eb, shape = 1e-3, (257, 1000)
    x = _all_ties(shape, eb, np.random.default_rng(5))
    assert x.size > lops.CHECK_CAPACITY
    res, counters = _compress(x, eb)
    assert counters == {"verify_device": 1, "verify_host_fallback": 1}
    fail, _ = _expected_fail(x, eb)
    _assert_meta(res.meta, fail, x)
    full_host_check(True)
    assert _compress(x, eb)[0].blob == res.blob
    err = np.abs(decompress(res.blob).astype(np.float64) - x.astype(np.float64))
    assert err.max() <= eb


def test_numpy_route_has_no_device_counters():
    x = _smooth((64, 256), 1e-3, np.random.default_rng(2))
    conf = CompressionConfig(mode=ErrorBoundMode.ABS, eb=1e-3)
    with tel.trace() as tr:
        res = sz3_lorenzo(device="off").compress(x, conf, with_stats=True)
    assert "device" not in res.meta
    assert not set(COUNTERS) & set(tr.counters)


@pytest.mark.parametrize("eb", [2.0 ** -101, 1e-30, 1e-3, 1.0, 3e5])
def test_check_floor_lies_below_the_margin(eb):
    f = lops._check_floor(eb)
    assert isinstance(f, np.float32)
    assert float(f) <= eb * (1 - 2.0 ** -20) - 2.0 ** -100
    if eb >= 2.0 ** -90:
        assert float(f) >= eb * (1 - 2.0 ** -19)
