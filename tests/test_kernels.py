"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.kernels.bitplane import (
    bitplane_decode,
    bitplane_encode,
    ref_encode as bp_ref_encode,
)
from repro.kernels.kvquant import (
    kv_dequant_matmul,
    kv_quantize,
    ref_dequant_matmul,
    ref_quantize,
)
from repro.kernels.lorenzo import (
    lorenzo_decode,
    lorenzo_encode,
    ref_decode,
    ref_encode,
)
from repro.kernels.transform import (
    ref_fwd as tf_ref_fwd,
    ref_inv as tf_ref_inv,
    transform_fwd,
    transform_inv,
)


@pytest.mark.parametrize(
    "shape", [(100, 300), (256, 512), (7, 50), (1, 1000), (513, 129), (8, 128)]
)
@pytest.mark.parametrize("mode", ["1d", "2d"])
@pytest.mark.parametrize("eb", [1e-1, 1e-3])
def test_lorenzo_kernel_equals_ref(shape, mode, eb):
    rng = np.random.default_rng(abs(hash((shape, mode))) % 1000)
    x = np.cumsum(rng.normal(size=shape).astype(np.float32), axis=1)
    c_k, d_k = lorenzo_encode(jnp.asarray(x), eb=eb, mode=mode)
    c_r, d_r = ref_encode(x, eb, mode=mode)
    np.testing.assert_array_equal(np.asarray(c_k), np.asarray(c_r))
    np.testing.assert_array_equal(np.asarray(d_k), np.asarray(d_r))
    xh_k = lorenzo_decode(d_k, eb=eb, mode=mode)
    xh_r = ref_decode(d_r, eb, mode=mode)
    np.testing.assert_array_equal(np.asarray(xh_k), np.asarray(xh_r))
    tol = eb + np.abs(x).max() * 3e-7  # f32 reciprocal-grid tolerance
    assert np.max(np.abs(np.asarray(xh_k) - x)) <= tol


@pytest.mark.parametrize("dtype", [np.float32, np.float16])
def test_lorenzo_kernel_dtypes(dtype):
    rng = np.random.default_rng(0)
    x = np.cumsum(rng.normal(size=(64, 256)).astype(dtype), axis=1)
    c_k, d_k = lorenzo_encode(jnp.asarray(x, jnp.float32), eb=1e-2, mode="2d")
    xh = lorenzo_decode(d_k, eb=1e-2, mode="2d")
    assert np.max(np.abs(np.asarray(xh) - x.astype(np.float32))) <= 1e-2 + 1e-4


@pytest.mark.parametrize("n", [5, 100, 16384, 40000])
def test_bitplane_kernel(n):
    rng = np.random.default_rng(n)
    vals = rng.integers(0, 2**32, size=n, dtype=np.uint32)
    w_k = np.asarray(bitplane_encode(jnp.asarray(vals)))
    w_r = np.asarray(bp_ref_encode(vals))
    np.testing.assert_array_equal(w_k[:, : w_r.shape[1]], w_r)
    back = np.asarray(bitplane_decode(jnp.asarray(w_k), n))
    np.testing.assert_array_equal(back, vals)


def test_bitplane_sparsity_structure():
    """Small-magnitude values must leave significant planes all-zero (the
    §4.2 compressibility property)."""
    vals = np.arange(4096, dtype=np.uint32) % 16  # only 4 low bits used
    w = np.asarray(bitplane_encode(jnp.asarray(vals)))
    assert np.all(w[4:, :] == 0)


# ---------------------------------------------------------------------------
# host codec vs device kernel parity (the two bitplane implementations must
# agree on plane CONTENT: the unpred-aware quantizer serializes with the host
# codec today and may hand the same integers to the kernel on TPU)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [0, 1, 31, 32, 33, 1000, 16384, 40009])
def test_bitplane_host_kernel_parity(n):
    """Host ``quantizers.bitplane_encode/decode`` and ``kernels/bitplane``
    (interpret mode) round-trip the same values AND store identical bits per
    plane, including tail/partial-word and empty inputs."""
    from repro.core.quantizers import (
        bitplane_decode as host_decode,
        bitplane_encode as host_encode,
    )

    rng = np.random.default_rng(n)
    # magnitudes within uint32 so both codecs can represent them
    vals = rng.integers(0, 1 << 32, size=n, dtype=np.uint64).astype(np.uint32)

    # round-trips
    host_back, consumed = host_decode(host_encode(vals.astype(np.int64)))
    assert consumed == len(host_encode(vals.astype(np.int64)))
    np.testing.assert_array_equal(host_back, vals.astype(np.int64))
    kern_back = np.asarray(bitplane_decode(bitplane_encode(jnp.asarray(vals)), n))
    np.testing.assert_array_equal(kern_back, vals)
    if n == 0:
        return

    # plane-content parity: both codecs must store exactly ((vals >> p) & 1)
    # for every plane p (the host packs big-endian bits MSB-plane-first, the
    # kernel packs little-endian words plane-row-major — same content)
    blob = host_encode(vals.astype(np.int64))
    header = np.frombuffer(blob, np.int64, count=2)
    nplanes = int(header[1])
    assert nplanes == max(1, int(vals.max()).bit_length())
    nbytes_plane = (n + 7) // 8
    pos = 16 + nbytes_plane  # skip header + sign bitmap (all zero here)
    words = np.asarray(bitplane_encode(jnp.asarray(vals)))
    for i, p in enumerate(range(nplanes - 1, -1, -1)):  # host is MSB-first
        host_bits = np.unpackbits(
            np.frombuffer(blob, np.uint8, count=nbytes_plane, offset=pos + i * nbytes_plane),
            count=n,
        )
        expect = ((vals >> np.uint32(p)) & np.uint32(1)).astype(np.uint8)
        np.testing.assert_array_equal(host_bits, expect)
        kern_bits = (
            (words[p][np.arange(n) // 32] >> (np.arange(n) % 32).astype(np.uint32)) & 1
        ).astype(np.uint8)
        np.testing.assert_array_equal(kern_bits, expect)


def test_bitplane_host_kernel_parity_signed_tail():
    """Signed host values: the kernel codec sees magnitudes; the host sign
    bitmap must round-trip alongside (tail length 3 exercises partial bytes
    AND partial words)."""
    from repro.core.quantizers import bitplane_decode as host_decode
    from repro.core.quantizers import bitplane_encode as host_encode

    vals = np.asarray([5, -1, (1 << 31), -(1 << 20), 0, -7, 123456789, -3, 9, 2, -2], np.int64)
    back, _ = host_decode(host_encode(vals))
    np.testing.assert_array_equal(back, vals)
    mags = np.abs(vals).astype(np.uint32)
    kern = np.asarray(bitplane_decode(bitplane_encode(jnp.asarray(mags)), mags.size))
    np.testing.assert_array_equal(kern, mags)


# ---------------------------------------------------------------------------
# blockwise transform kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(8, 128), (64, 256), (12, 132), (4, 640)])
@pytest.mark.parametrize("mode", ["1d", "2d"])
def test_transform_kernel_equals_ref(shape, mode):
    rng = np.random.default_rng(abs(hash((shape, mode))) % 1000)
    x = rng.normal(size=shape).astype(np.float32)
    c_k = np.asarray(transform_fwd(jnp.asarray(x), mode=mode))
    c_r = np.asarray(tf_ref_fwd(x, mode=mode))
    np.testing.assert_allclose(c_k, c_r, rtol=1e-6, atol=1e-6)
    b_k = np.asarray(transform_inv(jnp.asarray(c_k), mode=mode))
    b_r = np.asarray(tf_ref_inv(c_r, mode=mode))
    np.testing.assert_allclose(b_k, b_r, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(b_k, x, rtol=1e-5, atol=1e-5)


def test_transform_kernel_orthonormal():
    """The shared basis must be orthonormal — the error-bound analysis in
    core/transform.py (L_inf amplification of the inverse) depends on it."""
    from repro.kernels.transform.ref import AMP_1AXIS, MAT

    np.testing.assert_allclose(MAT @ MAT.T, np.eye(4), atol=1e-15)
    assert abs(AMP_1AXIS - np.abs(MAT).sum(axis=0).max()) < 1e-15


@pytest.mark.parametrize("shape", [(300, 96), (512, 128), (64, 64), (33, 200)])
def test_kvquant_kernel(shape):
    rng = np.random.default_rng(abs(hash(shape)) % 997)
    T, C = shape
    x = rng.normal(0, 2, size=shape).astype(np.float32) * (1 + np.arange(C))[None, :]
    q_k, s_k = kv_quantize(jnp.asarray(x))
    q_r, s_r = ref_quantize(x)
    np.testing.assert_array_equal(np.asarray(q_k), np.asarray(q_r))
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_r), rtol=1e-6)
    deq = np.asarray(q_k).astype(np.float32) * np.asarray(s_k)[None, :]
    assert np.all(np.abs(deq - x) <= np.asarray(s_k)[None, :] * 0.5001)
    a = rng.normal(size=(48, T)).astype(np.float32)
    o_k = np.asarray(kv_dequant_matmul(jnp.asarray(a), q_k, s_k))
    o_r = np.asarray(ref_dequant_matmul(a, q_r, s_r))
    bound = 1e-6 * (np.abs(a) @ np.abs(deq)) + 1e-4
    assert np.all(np.abs(o_k - o_r) <= bound)
