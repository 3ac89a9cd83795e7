"""Pure-jax codec facade: the SZ3 block-predictor contest inside jit/shard_map.

The host engines (``sz3_chunked``/``sz3_hybrid``/``sz3_fast``…) buy ratio with
entropy coding and self-describing byte containers — neither traces under
``jax.jit``, so the in-training compression paths (gradient all-gather,
optimizer moments, KV-cache prefill) historically hand-rolled their own int8
block quantizer and never saw the paper's composability.  This module is the
jit-friendly face of the framework: the same per-block predictor contest as
``sz3_hybrid`` (zero / Lorenzo-1 / mean-centered), priced by the same
code-bits currency, emitting SZx-style fixed-length codes (``core/fastmode``'s
coding discipline) as plain arrays that compose with ``shard_map`` collectives
and ``jit`` donation.

Two tiers:

  * **fixed tier** (:func:`encode` / :func:`decode`) — fixed ``bits``-wide
    codes (int8, or int4 packed two-per-byte) with a per-block scale adapted
    to the selected predictor's residual range.  The bound contract is
    per-block: ``|x - x̂| <= BlockCodes.bound()`` with the scale
    ``snap(max(absmax_resid_b / radius, 2*eb, SCALE_FLOOR))`` — the
    paper's value-range-relative (REL) mode at block granularity, with
    ``eb`` acting as an absolute grid floor and the mantissa-grid snap
    buying exact decode arithmetic (see :func:`_snap_scale`).  Codes never
    clip (the scale absorbs the range), so the bound is unconditional for
    finite inputs.
    This is the wire tier: bytes on the all-gather are
    ``bits/8`` per element plus three small per-block side channels.
  * **grid tier** (:func:`encode_grid` / :func:`decode_grid`) — int32 codes
    on the fixed ``2*eb`` grid: the exact ABS bound of the host engines,
    for consumers that need ``eb`` honored pointwise rather than per-block
    REL.  Exact while ``|x - base| / (2*eb) < 2**23`` (float32 integer
    window); the host engines remain the fallback beyond it.

Predictor selection: per block, the winner minimizes the fixed-length coded
bits on the quantization grid — ``bs * (bitlength(max|q_p|) + 1)`` — which is
the fixed-length analog of ``sz3_hybrid``'s ``_int_code_bits`` pricing (an
entropy coder prices the bin population; a fixed-length coder's price IS its
width).  All predictors carry the same side-channel cost (base + scale +
tag), so the argmin reduces to the smallest radius-normalized residual range.
The three predictors mirror the hybrid engine's in-graph-representable
subset:

  * ``zero``     — codes the value itself (``base = 0``);
  * ``lorenzo1`` — order-1 Lorenzo along the block, dual-quantized via the
    integer-grid trick (``q_i = t_i - t_{i-1}``, ``t = rint((x-x_0)/scale)``)
    so decode's integer cumsum reconstructs ``t`` exactly; ``base`` stores
    the block's first element;
  * ``mean``     — mean-centered coding with the center stored per block.
    The center is the block *midrange* ``(min+max)/2`` rather than the
    arithmetic mean: it strictly minimizes the residual absmax (what the
    scale — and therefore the bound — is built from), and min/max reductions
    are order-exact in floating point, which keeps the whole encoder
    bit-deterministic across jit / eager / the numpy host path (a float sum
    is not reassociation-stable, so an arithmetic mean would break the
    jit-vs-host bit-identity contract that tests pin).

Every reduction used (max, min, abs-max) is order-exact and every elementwise
op is correctly rounded, so ``jit(encode)``, eager ``encode``, and the numpy
reference ``encode_host`` produce bit-identical codes — pinned by
``tests/test_jitmode.py``.

Host fallback: anything outside a jit region that wants the *prediction*
engines (entropy-coded containers, integrity trailers, random access) should
route through the engine table — :func:`host_compress` / :func:`host_decompress`
are the facade's thin door to ``engines.PIPELINES`` for exactly that
(``ft/checkpoint.py`` is the house consumer).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

SCALE_FLOOR = 1e-12

#: predictor name -> tag (the 2-bit side-channel vocabulary, hybrid's idiom)
PREDICTOR_TAGS = {"zero": 0, "lorenzo1": 1, "mean": 2}
_TAG_NAMES = {v: k for k, v in PREDICTOR_TAGS.items()}

#: grid-tier codes are clipped here (same guard as fastmode's ``_Q_CLIP``)
_GRID_CLIP = 1 << 30


# ---------------------------------------------------------------------------
# policy
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class JitPolicy:
    """In-loop compression policy: (mode, eb, tier) as one parseable knob.

    ``tier`` picks the container width of the fixed tier (``int8`` /
    ``int4``) or the exact-grid tier (``grid``).  ``mode`` names the bound
    semantics: ``rel`` (per-block REL, ``eb`` only floors the grid) or
    ``abs`` (``eb`` is the grid: fixed tier floors the scale at ``2*eb``,
    grid tier honors it pointwise).
    """

    tier: str = "int8"  # "int8" | "int4" | "grid"
    mode: str = "rel"  # "rel" | "abs"
    eb: float = 0.0
    bs: int = 512
    predictors: Tuple[str, ...] = ("zero", "lorenzo1", "mean")

    def __post_init__(self):
        if self.tier not in ("int8", "int4", "grid"):
            raise ValueError(f"unknown jit codec tier {self.tier!r}")
        if self.mode not in ("rel", "abs"):
            raise ValueError(f"unknown bound mode {self.mode!r}")
        if self.tier == "grid" and self.eb <= 0:
            raise ValueError("grid tier needs a positive eb")
        if self.bs < 2:
            raise ValueError("block size must be >= 2")
        if self.bs > 8192:
            # _snap_scale's exact-product budget: 3 + bits(bs*radius) <= 24
            raise ValueError("block size above 8192 breaks exact decode")
        if self.tier == "int4" and self.bs % 2:
            raise ValueError("int4 packing needs an even block size")
        bad = set(self.predictors) - set(PREDICTOR_TAGS)
        if bad or not self.predictors:
            raise ValueError(f"unknown predictors {sorted(bad)}")

    @property
    def bits(self) -> int:
        return {"int8": 8, "int4": 4, "grid": 32}[self.tier]

    @property
    def radius(self) -> int:
        return 127 if self.tier == "int8" else 7

    @classmethod
    def parse(cls, spec: str) -> "JitPolicy":
        """Parse ``"int8"``, ``"int4:eb=1e-5"``,
        ``"int8:mode=abs:eb=1e-3:bs=256:pred=zero+lorenzo1"``."""
        parts = [p for p in str(spec).split(":") if p]
        if not parts:
            raise ValueError("empty compression policy")
        kw: Dict[str, Any] = {"tier": parts[0]}
        for part in parts[1:]:
            if "=" not in part:
                raise ValueError(f"policy field {part!r} is not key=value")
            k, v = part.split("=", 1)
            if k == "eb":
                kw["eb"] = float(v)
            elif k == "bs":
                kw["bs"] = int(v)
            elif k == "mode":
                kw["mode"] = v
            elif k == "pred":
                kw["predictors"] = tuple(v.split("+"))
            else:
                raise ValueError(f"unknown policy field {k!r}")
        return cls(**kw)


# ---------------------------------------------------------------------------
# code containers (pytrees: compose with shard_map collectives / donation)
# ---------------------------------------------------------------------------

@partial(
    jax.tree_util.register_dataclass,
    data_fields=["codes", "scale", "tags", "base"],
    meta_fields=["n", "bits", "bs"],
)
@dataclasses.dataclass
class BlockCodes:
    """Fixed-tier codes for one flat vector (all leaves gatherable arrays).

    ``codes`` is int8 ``(nb, bs)``, or uint8 ``(nb, bs//2)`` when
    ``bits == 4`` (two two's-complement nibbles per byte, low nibble first).
    """

    codes: jnp.ndarray
    scale: jnp.ndarray  # f32 (nb,)
    tags: jnp.ndarray  # uint8 (nb,), PREDICTOR_TAGS values
    base: jnp.ndarray  # f32 (nb,): 0 / first element / midrange
    n: int  # valid elements (tail block padding cropped on decode)
    bits: int
    bs: int

    def wire_bytes(self) -> int:
        """Bytes this shard contributes to a code all-gather."""
        return sum(
            int(np.prod(a.shape)) * np.dtype(a.dtype).itemsize
            for a in (self.codes, self.scale, self.tags, self.base)
        )

    def bound(self) -> jnp.ndarray:
        """Per-block error bound: ``scale/2`` plus float32 representation
        slack.

        The reconstruction is assembled as ``base + scale*q`` in float32, so
        a few ulps of the addends sit on top of the quantization half-grid —
        when the Lorenzo predictor codes fine structure riding a large
        offset (its prime case), the half-ulp of ``|base|`` can exceed the
        half-grid itself and is physically unavoidable (the true value and
        its reconstruction are both float32 near ``base``).  The slack term
        is ``2**-22 * (|base| + scale*max|q_sum|)`` per block: four ulps of
        each addend, computed from the actual codes.  Zero-predictor blocks
        (``base == 0``) pay essentially none.
        """
        mag = _sel_magnitude(self.codes, self.tags, self.bits)
        slack = (jnp.abs(self.base) + self.scale * mag) * jnp.float32(2.0**-22)
        return self.scale * 0.5 + slack


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["codes", "tags", "base"],
    meta_fields=["n", "eb", "bs"],
)
@dataclasses.dataclass
class GridCodes:
    """Grid-tier codes: int32 on the fixed ``2*eb`` grid (ABS bound)."""

    codes: jnp.ndarray  # int32 (nb, bs)
    tags: jnp.ndarray  # uint8 (nb,)
    base: jnp.ndarray  # f32 (nb,)
    n: int
    eb: float
    bs: int

    def bound(self) -> jnp.ndarray:
        """Per-block ``eb`` plus the same float32 representation slack as
        :meth:`BlockCodes.bound` (see there) — the grid value is exact but
        its float32 assembly ``base + 2*eb*q`` is not."""
        mag = _sel_magnitude(self.codes, self.tags, 32)
        grid = jnp.float32(2.0 * self.eb)
        slack = (jnp.abs(self.base) + grid * mag) * jnp.float32(2.0**-22)
        return jnp.float32(self.eb) + slack


# ---------------------------------------------------------------------------
# block plumbing
# ---------------------------------------------------------------------------

def _snap_scale(x: jnp.ndarray) -> jnp.ndarray:
    """Snap x > 0 up to the 3-bit-mantissa grid ``(k/8) * 2**e``, k in 4..8.

    The fixed tier snaps its scale onto this grid so the decode product
    ``scale * q`` is EXACT in float32: ``k * q`` needs at most 3 + 21 bits
    for any admissible block (|q_sum| <= bs * radius <= 8192 * 127 < 2**21),
    and a small-integer times a power of two never rounds.  Then whether the
    compiler contracts ``base + scale*q`` into an fma or not, the result is
    bit-identical by IEEE semantics — which is what makes the jit/eager/
    numpy bit-identity contract robust rather than a property of one XLA
    version's fusion choices (XLA elides optimization_barrier on CPU and
    LLVM contracts mul+add even through a select, so there is no reliable
    compiler-level hammer).  Cost: the snapped scale is at most 8/7 of the
    tightest admissible one (~0.2 bits of bound looseness), reflected
    honestly in ``bound()`` which is defined off the stored scale.
    """
    m, e = jnp.frexp(x)  # x = m * 2**e, m in [0.5, 1)
    k = jnp.ceil(m * 8.0)  # 4..8; exact (pow2 multiply, integral ceil)
    return jnp.ldexp(k.astype(jnp.float32), e - 3)


def _sel_magnitude(codes, tags, bits) -> jnp.ndarray:
    """Per-block max integer magnitude of the reconstruction term
    (``max|q|`` direct, ``max|cumsum q|`` under Lorenzo) — feeds the
    representation-slack term of the bound helpers."""
    q = _unpack_int4(codes) if bits == 4 else codes.astype(jnp.int32)
    lor = jnp.cumsum(q, axis=-1)
    sel = jnp.where((tags == PREDICTOR_TAGS["lorenzo1"])[..., None], lor, q)
    if sel.shape[-1] == 0:
        return jnp.zeros(sel.shape[:-1], jnp.float32)
    return jnp.max(jnp.abs(sel), axis=-1).astype(jnp.float32)

def _block_view(x: jnp.ndarray, bs: int) -> Tuple[jnp.ndarray, int]:
    """(nb, bs) f32 view of a flat vector, tail padded with the edge value
    (the pad rides the tail block's statistics and is cropped on decode)."""
    n = x.shape[0]
    nb = -(-n // bs) if n else 0
    pad = nb * bs - n
    x = x.astype(jnp.float32)
    if pad:
        x = jnp.pad(x, (0, pad), mode="edge")
    return x.reshape(nb, bs), nb


def _block_stats(xb: jnp.ndarray):
    """Order-exact per-block statistics all three predictors price from."""
    prev = jnp.concatenate([xb[..., :1] * 0, xb[..., :-1]], axis=-1)
    d = (xb - prev).at[..., 0].set(0.0)  # first code is 0 under lorenzo1
    a_lor = jnp.max(jnp.abs(d), axis=-1)
    a_zero = jnp.max(jnp.abs(xb), axis=-1)
    mx = jnp.max(xb, axis=-1)
    mn = jnp.min(xb, axis=-1)
    a_mean = (mx - mn) * 0.5
    center = (mx + mn) * 0.5
    return a_zero, a_lor, a_mean, center


def _select(
    a_zero, a_lor, a_mean, predictors: Sequence[str], radius: int
) -> jnp.ndarray:
    """argmin of radius-normalized residual range == argmin fixed-length
    code bits (all side channels cost the same, see module docstring).

    The normalization multiplies by a float32 reciprocal instead of
    dividing: XLA strength-reduces division by a non-power-of-two constant
    differently inside a fused jit graph than in eager dispatch, which
    would put jit and eager one ulp apart on the selected scale — an
    explicit reciprocal multiply is the same op everywhere (including the
    numpy host mirror), keeping the encoder bit-deterministic.
    """
    cost = {
        # lorenzo keeps one code of headroom: |t_i - t_{i-1}| can exceed
        # |d_i|/scale by the two rints' crossterm, so its scale normalizes
        # by radius-1 — priced identically so selection sees the true bound
        "zero": a_zero * np.float32(1.0 / radius),
        "lorenzo1": a_lor * np.float32(1.0 / (radius - 1)),
        "mean": a_mean * np.float32(1.0 / radius),
    }
    enabled = [(PREDICTOR_TAGS[p], cost[p]) for p in predictors]
    stack = jnp.stack([c for _, c in enabled], axis=-1)
    # the floor makes subnormal-range blocks tie exactly: XLA flushes
    # subnormal intermediates to zero inconsistently between jit and eager,
    # so comparing raw sub-1e-38 costs would let the argmin disagree across
    # paths; floored ties resolve to the first enabled predictor everywhere
    stack = jnp.maximum(stack, jnp.float32(SCALE_FLOOR))
    pick = jnp.argmin(stack, axis=-1)  # first min wins: deterministic ties
    tag_map = jnp.asarray([t for t, _ in enabled], jnp.uint8)
    return tag_map[pick], jnp.min(stack, axis=-1)


def _pack_int4(codes: jnp.ndarray) -> jnp.ndarray:
    """int8 codes in [-8, 7] -> uint8 nibbles, low nibble = even element."""
    u = codes.astype(jnp.uint8)
    lo = u[..., 0::2] & 0xF
    hi = u[..., 1::2] & 0xF
    return lo | (hi << 4)


def _unpack_int4(packed: jnp.ndarray) -> jnp.ndarray:
    """Inverse of :func:`_pack_int4` -> int32 codes."""
    lo = (packed & 0xF).astype(jnp.int32)
    hi = ((packed >> 4) & 0xF).astype(jnp.int32)
    lo = jnp.where(lo > 7, lo - 16, lo)
    hi = jnp.where(hi > 7, hi - 16, hi)
    out = jnp.stack([lo, hi], axis=-1)
    return out.reshape(out.shape[:-2] + (-1,))


# ---------------------------------------------------------------------------
# fixed tier
# ---------------------------------------------------------------------------

def encode_blocks(
    xb: jnp.ndarray, policy: JitPolicy
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Core fixed-tier encoder on pre-blocked data ``(..., nb, bs)``.

    Returns ``(codes, scale, tags, base)`` with leading dims preserved —
    the flat-vector :func:`encode` and the shaped consumers
    (``compression/opt_state.py``) both sit on top of this.
    """
    radius = policy.radius
    xb = xb.astype(jnp.float32)
    a_zero, a_lor, a_mean, center = _block_stats(xb)
    tags, a_eff = _select(a_zero, a_lor, a_mean, policy.predictors, radius)
    scale = _snap_scale(
        jnp.maximum(a_eff, jnp.float32(max(2.0 * policy.eb, SCALE_FLOOR)))
    )
    base = jnp.where(
        tags == PREDICTOR_TAGS["lorenzo1"],
        xb[..., 0],
        jnp.where(tags == PREDICTOR_TAGS["mean"], center, 0.0),
    )
    t = jnp.rint((xb - base[..., None]) / scale[..., None])
    prev_t = jnp.concatenate([t[..., :1] * 0, t[..., :-1]], axis=-1)
    codes = jnp.where(
        (tags == PREDICTOR_TAGS["lorenzo1"])[..., None], t - prev_t, t
    )
    codes = jnp.clip(codes, -radius, radius).astype(jnp.int8)
    if policy.bits == 4:
        codes = _pack_int4(codes)
    return codes, scale, tags, base


def decode_blocks(
    codes: jnp.ndarray,
    scale: jnp.ndarray,
    tags: jnp.ndarray,
    base: jnp.ndarray,
    bits: int,
) -> jnp.ndarray:
    """Inverse of :func:`encode_blocks` -> f32 blocks ``(..., nb, bs)``."""
    q = _unpack_int4(codes) if bits == 4 else codes.astype(jnp.int32)
    lor = jnp.cumsum(q, axis=-1)  # integer cumsum: reconstructs t exactly
    sel = jnp.where((tags == PREDICTOR_TAGS["lorenzo1"])[..., None], lor, q)
    # scale is on the 3-bit mantissa grid (see _snap_scale), so this product
    # is exact and the sum single-rounded whether or not XLA contracts to fma
    return base[..., None] + scale[..., None] * sel.astype(jnp.float32)


def encode(x: jnp.ndarray, policy: JitPolicy):
    """Encode a flat vector (jit/shard_map-safe); dispatches on tier."""
    if policy.tier == "grid":
        return encode_grid(x, policy)
    flat = x.reshape(-1)
    xb, _nb = _block_view(flat, policy.bs)
    codes, scale, tags, base = encode_blocks(xb, policy)
    return BlockCodes(
        codes=codes,
        scale=scale,
        tags=tags,
        base=base,
        n=int(flat.shape[0]),
        bits=policy.bits,
        bs=policy.bs,
    )


def decode(c) -> jnp.ndarray:
    """Flat f32 reconstruction, tail padding cropped."""
    if isinstance(c, GridCodes):
        return decode_grid(c)
    xb = decode_blocks(c.codes, c.scale, c.tags, c.base, c.bits)
    return xb.reshape(-1)[: c.n]


def encode_lastaxis(x: jnp.ndarray, policy: JitPolicy):
    """Block the LAST axis of a shaped array and encode each block.

    Returns ``(codes, scale, tags, base, orig_last)`` with leading dims
    preserved (codes ``(*lead, nb, bs_or_packed)``, side channels
    ``(*lead, nb)``) — the shaped-consumer entry point (optimizer moments
    keep the parameter's leading shape so PartitionSpecs apply unchanged;
    KV prefill keeps ``(..., tokens)`` leading dims).
    """
    x = x.astype(jnp.float32)
    last = x.shape[-1]
    pad = (-last) % policy.bs
    if pad:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)], mode="edge")
    nb = x.shape[-1] // policy.bs
    xb = x.reshape(x.shape[:-1] + (nb, policy.bs))
    codes, scale, tags, base = encode_blocks(xb, policy)
    return codes, scale, tags, base, last


def decode_lastaxis(codes, scale, tags, base, orig_last: int, bits: int):
    """Inverse of :func:`encode_lastaxis` -> ``(*lead, orig_last)`` f32."""
    xb = decode_blocks(codes, scale, tags, base, bits)
    return xb.reshape(xb.shape[:-2] + (-1,))[..., :orig_last]


# ---------------------------------------------------------------------------
# grid tier (exact ABS bound)
# ---------------------------------------------------------------------------

def encode_grid(x: jnp.ndarray, policy: JitPolicy) -> GridCodes:
    """Int32 codes on the fixed ``2*eb`` grid: ``|x - x̂| <= eb`` pointwise
    while ``|x - base|/(2*eb) < 2**23`` (see module docstring)."""
    if policy.eb <= 0:
        raise ValueError("grid tier needs a positive eb")
    flat = x.reshape(-1)
    xb, _nb = _block_view(flat, policy.bs)
    a_zero, a_lor, a_mean, center = _block_stats(xb)
    # same argmin, unnormalized: grid width is common so code bits are
    # monotone in the residual range
    tags, _ = _select(a_zero, a_lor, a_mean, policy.predictors, 2)
    base = jnp.where(
        tags == PREDICTOR_TAGS["lorenzo1"],
        xb[..., 0],
        jnp.where(tags == PREDICTOR_TAGS["mean"], center, 0.0),
    )
    inv = jnp.float32(1.0 / (2.0 * policy.eb))
    t = jnp.rint((xb - base[..., None]) * inv)
    t = jnp.clip(t, -_GRID_CLIP, _GRID_CLIP).astype(jnp.int32)
    prev_t = jnp.concatenate([t[..., :1] * 0, t[..., :-1]], axis=-1)
    codes = jnp.where(
        (tags == PREDICTOR_TAGS["lorenzo1"])[..., None], t - prev_t, t
    )
    return GridCodes(
        codes=codes,
        tags=tags,
        base=base,
        n=int(flat.shape[0]),
        eb=float(policy.eb),
        bs=policy.bs,
    )


def decode_grid(c: GridCodes) -> jnp.ndarray:
    q = c.codes
    lor = jnp.cumsum(q, axis=-1)
    sel = jnp.where((c.tags == PREDICTOR_TAGS["lorenzo1"])[..., None], lor, q)
    # unlike the fixed tier, the 2*eb grid is an arbitrary float, so the
    # product can round and a contracted fma may differ from the eager /
    # numpy path by one ulp — the grid tier therefore guarantees bit
    # identity for ENCODE (the wire format) and the bound for decode, not
    # cross-path decode bit identity (tests pin exactly that asymmetry)
    xb = c.base[..., None] + jnp.float32(2.0 * c.eb) * sel.astype(jnp.float32)
    return xb.reshape(-1)[: c.n]


def grid_code_bits(c: GridCodes) -> float:
    """Fixed-length coded size of a grid-tier result in bits/element — the
    accounting the bench rows report (per-block width = bitlength(max|q|),
    plus the base/tag/width side channels)."""
    q = np.asarray(c.codes)
    if q.size == 0:
        return 0.0
    m = np.abs(q).max(axis=-1).astype(np.int64)
    w = np.zeros(m.shape, np.float64)
    nz = m > 0
    w[nz] = np.floor(np.log2(m[nz].astype(np.float64))) + 1.0
    per_block = c.bs * (w + 1.0) + 32.0 + 8.0 + 2.0
    return float(per_block.sum() / max(1, c.n))


# ---------------------------------------------------------------------------
# numpy host reference (bit-identical to the traced path; tests pin this)
# ---------------------------------------------------------------------------

def encode_host(x: np.ndarray, policy: JitPolicy) -> BlockCodes:
    """Numpy mirror of :func:`encode` — same op order, same reductions."""
    flat = np.asarray(x, np.float32).reshape(-1)
    n = flat.size
    nb = -(-n // policy.bs) if n else 0
    pad = nb * policy.bs - n
    if pad:
        flat = np.pad(flat, (0, pad), mode="edge")
    xb = flat.reshape(nb, policy.bs)
    radius = policy.radius
    d = np.diff(xb, axis=-1, prepend=xb[..., :1])
    d[..., 0] = 0.0
    a_lor = np.abs(d).max(axis=-1) if xb.size else np.zeros(nb, np.float32)
    a_zero = np.abs(xb).max(axis=-1) if xb.size else np.zeros(nb, np.float32)
    mx = xb.max(axis=-1) if xb.size else np.zeros(nb, np.float32)
    mn = xb.min(axis=-1) if xb.size else np.zeros(nb, np.float32)
    a_mean = ((mx - mn) * np.float32(0.5)).astype(np.float32)
    center = ((mx + mn) * np.float32(0.5)).astype(np.float32)
    cost = {
        "zero": a_zero * np.float32(1.0 / radius),
        "lorenzo1": a_lor * np.float32(1.0 / (radius - 1)),
        "mean": a_mean * np.float32(1.0 / radius),
    }
    enabled = [(PREDICTOR_TAGS[p], cost[p]) for p in policy.predictors]
    stack = np.stack([c for _, c in enabled], axis=-1)
    stack = np.maximum(stack, np.float32(SCALE_FLOOR))  # mirrors _select
    pick = np.argmin(stack, axis=-1)
    tag_map = np.asarray([t for t, _ in enabled], np.uint8)
    tags = tag_map[pick]
    a_eff = np.min(stack, axis=-1)
    scale = np.maximum(
        a_eff, np.float32(max(2.0 * policy.eb, SCALE_FLOOR))
    ).astype(np.float32)
    m, e = np.frexp(scale)  # mantissa-grid snap, mirrors _snap_scale
    scale = np.ldexp(np.ceil(m * 8.0).astype(np.float32), e - 3).astype(
        np.float32
    )
    base = np.where(
        tags == PREDICTOR_TAGS["lorenzo1"],
        xb[..., 0] if xb.size else np.zeros(nb, np.float32),
        np.where(tags == PREDICTOR_TAGS["mean"], center, np.float32(0.0)),
    ).astype(np.float32)
    t = np.rint((xb - base[..., None]) / scale[..., None]).astype(np.float32)
    prev_t = np.concatenate([t[..., :1] * 0, t[..., :-1]], axis=-1)
    codes = np.where(
        (tags == PREDICTOR_TAGS["lorenzo1"])[..., None], t - prev_t, t
    )
    codes = np.clip(codes, -radius, radius).astype(np.int8)
    if policy.bits == 4:
        u = codes.astype(np.uint8)
        codes = (u[..., 0::2] & 0xF) | ((u[..., 1::2] & 0xF) << 4)
    return BlockCodes(
        codes=codes, scale=scale, tags=tags, base=base,
        n=n, bits=policy.bits, bs=policy.bs,
    )


def decode_host(c: BlockCodes) -> np.ndarray:
    """Numpy mirror of :func:`decode`."""
    codes = np.asarray(c.codes)
    if c.bits == 4:
        lo = (codes & 0xF).astype(np.int32)
        hi = ((codes >> 4) & 0xF).astype(np.int32)
        lo = np.where(lo > 7, lo - 16, lo)
        hi = np.where(hi > 7, hi - 16, hi)
        q = np.stack([lo, hi], axis=-1).reshape(codes.shape[:-1] + (-1,))
    else:
        q = codes.astype(np.int32)
    lor = np.cumsum(q, axis=-1)
    sel = np.where(
        (np.asarray(c.tags) == PREDICTOR_TAGS["lorenzo1"])[..., None], lor, q
    )
    xb = np.asarray(c.base)[..., None] + np.asarray(c.scale)[..., None] * sel.astype(
        np.float32
    )
    return xb.reshape(-1)[: c.n].astype(np.float32)


# ---------------------------------------------------------------------------
# host fallback: the prediction engines of the engine table
# ---------------------------------------------------------------------------

def host_compress(arr: np.ndarray, engine: str = "sz3_auto", conf=None):
    """Route a host-side array through a named pipeline (the facade's
    door to the entropy-coded engines for non-jit contexts)."""
    from .engines import PIPELINES

    if engine not in PIPELINES:
        raise KeyError(
            f"unknown engine {engine!r}; registered: {sorted(PIPELINES)}"
        )
    comp = PIPELINES[engine]()
    return comp.compress(np.asarray(arr), conf)


def host_decompress(blob: bytes) -> np.ndarray:
    from . import pipeline as pl_mod

    return pl_mod.decompress(blob)
