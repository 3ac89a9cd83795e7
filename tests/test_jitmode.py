"""Property tests for the pure-jax codec facade (core/jitmode).

Two contracts, checked over arbitrary float32 inputs:

  * the decoded error obeys ``BlockCodes.bound()`` (resp. ``GridCodes``)
    both eagerly and under ``jax.jit``;
  * the jit path is BIT-identical to the host (numpy) mirror — codes,
    side channels, and decoded values — so a gradient encoded on device
    and decoded on a host (or vice versa, as elastic restore does) never
    disagrees.

Hypothesis drives the sweep when installed (CI test extras have it); a
deterministic adversarial corpus — subnormals, huge offsets, constants,
ragged tails, sign flips — covers the same properties where it is not.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import jitmode

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    from hypothesis.extra import numpy as hnp

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - exercised locally, not in CI
    HAVE_HYPOTHESIS = False


POLICIES = [
    "int8:bs=256",
    "int4:bs=64",
    "int8:mode=abs:eb=1e-3:bs=128",
    "grid:eb=1e-3:bs=256",
    "grid:eb=1e-4:mode=abs:bs=128",
]

#: deterministic fallback corpus: the shapes of data that have actually
#: broken quantizers in this repo's history
_CORPUS = [
    np.zeros(300, np.float32),
    np.full(511, 7.25, np.float32),
    np.linspace(-1e4, 1e4, 1000).astype(np.float32),
    (np.logspace(-40, 30, 777, dtype=np.float64)).astype(np.float32),
    np.array([1e-39, -1e-39, 5e-38, 0.0, 1.0], np.float32),  # subnormals
    np.cumsum(np.ones(2048, np.float32)) + 1e6,  # huge offset, lorenzo regime
    np.where(np.arange(513) % 2 == 0, 1.0, -1.0).astype(np.float32),
    np.repeat(np.float32(3.0), 64) * np.float32(2.0) ** -120,
]


def _fit_policy(x: np.ndarray, spec: str) -> jitmode.JitPolicy:
    """The grid tier's ABS bound is only meaningful inside its documented
    domain (``|x - base|/(2*eb) < 2**23``: int32 codes on a fixed grid),
    so for grid policies the property scales ``eb`` to the data range —
    exactly how a caller picks an ABS bound for known data."""
    import dataclasses

    pol = jitmode.JitPolicy.parse(spec)
    if pol.tier == "grid" and x.size:
        rng = float(np.max(np.abs(x)))
        if rng > 0:
            pol = dataclasses.replace(pol, eb=max(pol.eb, rng * 2.0**-20))
    return pol


def _check_bound(x: np.ndarray, spec: str):
    pol = _fit_policy(x, spec)
    c = jitmode.encode(jnp.asarray(x), pol)
    back = np.asarray(jitmode.decode(c))
    bound = np.asarray(c.bound())
    nb = bound.shape[0]
    err = np.pad(np.abs(back - x), (0, nb * pol.bs - x.size)).reshape(nb, pol.bs)
    assert (err.max(axis=1) <= bound).all(), (spec, err.max(), bound.max())


def _check_jit_vs_eager_vs_host(x: np.ndarray, spec: str):
    pol = _fit_policy(x, spec)
    c_e = jitmode.encode(jnp.asarray(x), pol)
    c_j = jax.jit(jitmode.encode, static_argnums=1)(jnp.asarray(x), pol)
    fields = ("codes", "scale", "tags", "base") if pol.tier != "grid" else (
        "codes", "tags", "base")
    for f in fields:
        a, b = np.asarray(getattr(c_e, f)), np.asarray(getattr(c_j, f))
        np.testing.assert_array_equal(a, b, err_msg=f"{spec}:{f} jit!=eager")
    d_e = np.asarray(jitmode.decode(c_e))
    d_j = np.asarray(jax.jit(jitmode.decode)(c_j))
    if pol.tier == "grid":
        # the 2*eb grid is an arbitrary float, so decode's base + grid*q may
        # contract into an fma under jit, shifting the result by up to one
        # ulp of the PRODUCT grid*q: the grid tier pins bit identity for
        # ENCODE (the wire format) and product-ulp closeness for decode —
        # the same representation-slack term GridCodes.bound() budgets for
        q = np.asarray(c_e.codes, np.int64)
        lor = np.cumsum(q, axis=-1)
        sel = np.where(
            (np.asarray(c_e.tags) == jitmode.PREDICTOR_TAGS["lorenzo1"])[
                :, None],
            lor, q)
        grid = np.float32(2.0 * pol.eb)
        slack = (np.abs(np.asarray(c_e.base))[:, None]
                 + grid * np.abs(sel)) * np.float32(2.0**-22)
        diff = np.abs(d_e - d_j)
        diff = np.pad(diff, (0, slack.size - diff.size)).reshape(slack.shape)
        assert (diff <= slack).all(), (spec, diff.max(), slack.max())
        return
    np.testing.assert_array_equal(d_e, d_j, err_msg=f"{spec} decode jit!=eager")
    # host (numpy) mirror covers the fixed tier end to end
    c_h = jitmode.encode_host(x, pol)
    for f in fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(c_e, f)), np.asarray(getattr(c_h, f)),
            err_msg=f"{spec}:{f} jax!=host")
    np.testing.assert_array_equal(
        d_e, jitmode.decode_host(c_h), err_msg=f"{spec} decode jax!=host")


@pytest.mark.parametrize("spec", POLICIES)
def test_bound_holds_corpus(spec):
    for x in _CORPUS:
        _check_bound(x, spec)


@pytest.mark.parametrize("spec", POLICIES)
def test_jit_bit_identical_corpus(spec):
    for x in _CORPUS:
        _check_jit_vs_eager_vs_host(x, spec)


def test_bound_holds_inside_jit():
    """The bound contract survives jit end to end: encode, decode, and the
    bound computation itself all traced into one program."""
    pol = jitmode.JitPolicy.parse("int8:bs=128")

    @jax.jit
    def roundtrip_err(x):
        c = jitmode.encode(x, pol)
        back = jitmode.decode(c)
        nb = c.bound().shape[0]
        err = jnp.abs(back - x)
        err = jnp.pad(err, (0, nb * pol.bs - x.shape[0]))
        return err.reshape(nb, pol.bs).max(axis=1) - c.bound()

    rng = np.random.default_rng(11)
    for x in [rng.standard_normal(5000).astype(np.float32) * 100, _CORPUS[5]]:
        slack = np.asarray(roundtrip_err(jnp.asarray(x)))
        assert (slack <= 0).all(), slack.max()


if HAVE_HYPOTHESIS:

    _arrays = hnp.arrays(
        np.float32,
        st.integers(1, 3000),
        # the bounds must be exact float32 values: Hypothesis refuses a
        # width-32 strategy whose bound (1e30) float32 cannot represent
        elements=st.floats(
            float(np.float32(-1e30)), float(np.float32(1e30)), width=32,
            allow_nan=False, allow_infinity=False,
        ),
    )

    @settings(max_examples=40, deadline=None)
    @given(x=_arrays, spec=st.sampled_from(POLICIES))
    def test_bound_holds_property(x, spec):
        _check_bound(x, spec)

    @settings(max_examples=25, deadline=None)
    @given(x=_arrays, spec=st.sampled_from(POLICIES))
    def test_jit_bit_identical_property(x, spec):
        _check_jit_vs_eager_vs_host(x, spec)
