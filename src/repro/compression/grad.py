"""Error-bounded gradient compression for the data-parallel reduction.

Schedule (per train step, inside a dp-manual shard_map region):

  1. flatten the grad tree to one f32 vector, cast bf16;
  2. psum_scatter over the DP axes (ring reduce-scatter, bf16);
  3. add the persistent error-feedback residual, encode the local shard with
     the jit codec facade (``core/jitmode``): per-block predictor contest
     (zero / Lorenzo-1 / mean) at fixed radius, int8 or packed int4 codes,
     per-block scales snapped to the 3-bit-mantissa grid (exact decode
     products, so jit/eager/host decode bit-identically — core/jitmode).
     The residual update (error feedback)
     makes the scheme unbiased over time — the quantization error is
     *carried*, i.e. exactly SZ's error-bound contract applied temporally;
  4. all_gather the codes + side channels (scale/tag/base per block),
     decode, unflatten to the recorded per-leaf dtypes.

Collective bytes per device: ~2N (RS bf16) + N*bits/8 + side channels (AG),
vs ~4N for a bf16 all-reduce — a >=1.3x (int8) / ~1.6x (int4) cut of the
dominant DP collective term (:func:`collective_bytes` is the accounting the
bench rows and regression gates use).

The legacy ``quantize_shard``/``dequantize_shard`` API is kept as the
zero-predictor special case of the facade (same wire layout as the pre-PR
hand-rolled quantizer, now sharing one code path with everything else).
"""
from __future__ import annotations

from typing import Any, Dict, Sequence, Tuple, Union

import jax
import jax.numpy as jnp

from ..core import jitmode
from ..core.jitmode import JitPolicy

BLOCK = 512
SCALE_FLOOR = jitmode.SCALE_FLOOR

#: each DP shard holds a whole number of 8-block row groups.  The TPU tiles
#: a (blocks, bs) array in groups of 8 rows; a shard that ends inside one
#: makes the compiler emit relayout copies whose compile time grows with the
#: vector (over 20 minutes for 0.46B parameters on a v5e 2x2, against
#: seconds when aligned)
ROW_GROUP = 8

PolicyLike = Union[int, str, JitPolicy]


def as_policy(policy: PolicyLike) -> JitPolicy:
    """Accept legacy bit counts (8/4), spec strings, or JitPolicy."""
    if isinstance(policy, JitPolicy):
        return policy
    if isinstance(policy, str):
        return JitPolicy.parse(policy)
    if policy in (8, 4):
        return JitPolicy(tier=f"int{policy}", bs=BLOCK)
    raise ValueError(f"bad gradient compression policy {policy!r}")


def _flatten_tree(tree) -> Tuple[jnp.ndarray, Any]:
    leaves, treedef = jax.tree.flatten(tree)
    flat = jnp.concatenate([l.reshape(-1).astype(jnp.float32) for l in leaves])
    meta = (treedef, [(l.shape, l.dtype) for l in leaves])
    return flat, meta


def _unflatten_tree(flat, meta):
    treedef, shapes = meta
    out, pos = [], 0
    for shp, dt in shapes:
        n = 1
        for s in shp:
            n *= s
        # restore the RECORDED leaf dtype: force-casting to f32 here would
        # silently widen bf16 params' gradients after the reduction
        out.append(flat[pos : pos + n].reshape(shp).astype(dt))
        pos += n
    return jax.tree.unflatten(treedef, out)


def _zero_policy(bits: int) -> JitPolicy:
    return JitPolicy(tier=f"int{bits}", bs=BLOCK, predictors=("zero",))


def quantize_shard(x: jnp.ndarray, bits: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Blockwise symmetric quantization; returns (codes int8, scales f32).

    Zero-predictor fixed tier of the jit facade: flat codes, per-block
    mantissa-snapped scales, bound scale/2 per block (plus f32 slack).
    """
    c = jitmode.encode(x, _zero_policy(bits))
    return c.codes.reshape(-1), c.scale


def dequantize_shard(codes, scale, n: int, bits: int) -> jnp.ndarray:
    nb = scale.shape[0]
    per = BLOCK // 2 if bits == 4 else BLOCK
    zeros = jnp.zeros((nb,), jnp.uint8)
    xb = jitmode.decode_blocks(
        codes.reshape(nb, per), scale, zeros, zeros.astype(jnp.float32), bits
    )
    return xb.reshape(-1)[:n]


def compressed_reduce_flat(
    flat: jnp.ndarray,  # per-replica partial grad vector (local view)
    feedback: jnp.ndarray,  # local error-feedback shard, (ceil(N/dp),)
    dp_axes: Sequence[str],
    policy: PolicyLike,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Inside a dp-manual shard_map: returns (reduced flat vector, new feedback)."""
    pol = as_policy(policy)
    axes = tuple(dp_axes)
    dp = 1
    for a in axes:
        dp *= jax.lax.axis_size(a)
    n = flat.shape[0]
    pad = feedback.shape[0] * dp - n  # the feedback fixes the shard length
    assert pad >= 0, "feedback shard shorter than the gradient vector"
    fp = jnp.pad(flat, (0, pad)).astype(jnp.bfloat16)
    shard = jax.lax.psum_scatter(fp, axes, scatter_dimension=0, tiled=True)
    shard = shard.astype(jnp.float32) / dp + feedback
    m = shard.shape[0]
    c = jitmode.encode(shard, pol)
    new_feedback = shard - jitmode.decode(c)
    gathered = jax.tree.map(
        lambda a: jax.lax.all_gather(a, axes, tiled=True), c
    )
    # each shard's blocks carry their own tail padding (m need not divide
    # the block size), so crop per shard before re-flattening
    xb = jitmode.decode_blocks(
        gathered.codes, gathered.scale, gathered.tags, gathered.base, pol.bits
    )
    out = xb.reshape(dp, -1)[:, :m].reshape(-1)[:n]
    return out, new_feedback


def init_feedback(params, dp: int, bs: int = BLOCK) -> jnp.ndarray:
    """Zero error-feedback vector; its length (global, ``dp`` shards) pads
    the gradient vector to whole ``ROW_GROUP``-block groups of ``bs`` per
    shard."""
    n = sum(int(jnp.size(l)) for l in jax.tree.leaves(params))
    n_pad = n + ((-n) % (dp * bs * ROW_GROUP))
    return jnp.zeros((n_pad,), jnp.float32)


def compressed_reduce_tree(grads, feedback, dp_axes, policy: PolicyLike):
    flat, meta = _flatten_tree(grads)
    out, fb = compressed_reduce_flat(flat, feedback, dp_axes, policy)
    return _unflatten_tree(out, meta), fb


def collective_bytes(n: int, dp: int, policy: PolicyLike) -> Dict[str, float]:
    """Per-device DP-collective byte model for one reduction of n floats.

    Baseline: bf16 all-reduce ~= reduce-scatter + all-gather at 2 B/elem
    => 4n.  Compressed: bf16 reduce-scatter (2n) + code all-gather
    (n*bits/8 plus scale/tag/base side channels per block).
    """
    pol = as_policy(policy)
    n_pad = n + ((-n) % max(dp, 1))
    m = n_pad // max(dp, 1)
    nb = -(-m // pol.bs)
    code_bytes_shard = nb * pol.bs * pol.bits // 8 + nb * (4 + 1 + 4)
    rs = 2.0 * n_pad
    ag = float(dp * code_bytes_shard)
    baseline = 4.0 * n_pad
    return {
        "baseline_bf16_allreduce": baseline,
        "rs_bytes": rs,
        "ag_bytes": ag,
        "compressed_total": rs + ag,
        "cut_vs_bf16_allreduce": baseline / (rs + ag),
    }
