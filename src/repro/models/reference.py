"""Plain float32 reference of the dense decoder LM and of the compressed
data-parallel reduction, for checking the system against.

Written from the model's published description, not from ``models/lm.py``:
straightforward ``jax.numpy`` in float32, every matrix product at
``Precision.HIGHEST`` (a TPU otherwise multiplies float32 in bfloat16
passes), no kernels, no remat, no sharding, no chunking of the attention or
of the loss.  It takes the program's parameter tree (``models.init_params``
for a dense config) and casts every leaf up to float32.

The dense family as ``configs/qwen1_5_0_5b.CONFIG`` states it:

* token embedding, then ``n_layers`` pre-norm blocks
  ``x += attn(rmsnorm(x)); x += swiglu(rmsnorm(x))``, a final RMSNorm and
  the tied head ``h @ embed.T``;
* RMSNorm ``x / sqrt(mean(x^2) + 1e-6) * w``;
* multi-head attention with biases on Q, K and V, rotary position
  embedding on Q and K (the two halves of each head rotated,
  ``theta = cfg.rope_theta``), scores scaled by ``1/sqrt(head_dim)``, causal
  softmax, output projection without bias;
* SwiGLU ``(silu(x @ w1) * (x @ w3)) @ w2``;
* loss: mean next-token cross-entropy over the positions whose label is in
  ``[0, vocab)``.

Departures from the published Qwen1.5-0.5B (``Qwen/Qwen1.5-0.5B``,
``config.json``), each one the program's own, so that the two compare:

* the embedding holds ``cfg.padded_vocab`` rows (151,936 padded to a
  multiple of 256: 152,064); the published vocabulary is 151,936;
* the padding rows take part as rows of the head: their logits enter the
  softmax and they get a gradient like any other row;
* no sliding window and no attention dropout, as the published config
  trains (``use_sliding_window`` false).

Beside the model, the two references of the compressed DP reduction
(``compression/grad.py``): :func:`dp_mean`, the exact float32 mean of the
per-chip gradients, and :func:`block_bound`, the largest pointwise bound the
fixed-tier jit codec (``core/jitmode.py``) may claim for a block.
"""
from __future__ import annotations

import math
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
EPS = 1e-6


def _f32(tree):
    return jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), tree)


def _mm(x, w, operand_dtype):
    if operand_dtype is not None:
        x = x.astype(operand_dtype).astype(jnp.float32)
        w = w.astype(operand_dtype).astype(jnp.float32)
    return jnp.matmul(x, w, precision=HIGHEST)


def _rmsnorm(x, w):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + EPS) * w


def _rope(x, theta: float):
    """``x``: (S, H, hd); the first and second halves of each head rotated."""
    s, _, hd = x.shape
    inv = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
    ang = np.arange(s, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[:, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def _block(x, p, cfg, operand_dtype):
    s, d = x.shape
    h, hd = cfg.n_heads, cfg.hd
    a = p["attn"]
    y = _rmsnorm(x, p["ln1"]["w"])
    q = (_mm(y, a["wq"], operand_dtype) + a["bq"]).reshape(s, h, hd)
    k = (_mm(y, a["wk"], operand_dtype) + a["bk"]).reshape(s, cfg.n_kv_heads, hd)
    v = (_mm(y, a["wv"], operand_dtype) + a["bv"]).reshape(s, cfg.n_kv_heads, hd)
    q, k = _rope(q, cfg.rope_theta), _rope(k, cfg.rope_theta)
    rep = h // cfg.n_kv_heads
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    if operand_dtype is not None:
        q, k, v = (t.astype(operand_dtype).astype(jnp.float32) for t in (q, k, v))
    scores = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) / math.sqrt(hd)
    causal = np.tril(np.ones((s, s), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    if operand_dtype is not None:
        probs = probs.astype(operand_dtype).astype(jnp.float32)
    o = jnp.einsum("hqk,khd->qhd", probs, v, precision=HIGHEST).reshape(s, h * hd)
    x = x + _mm(o, a["wo"], operand_dtype)
    y = _rmsnorm(x, p["ln2"]["w"])
    m = p["mlp"]
    g = jax.nn.silu(_mm(y, m["w1"], operand_dtype)) * _mm(y, m["w3"], operand_dtype)
    return x + _mm(g, m["w2"], operand_dtype)


def nll_sum(params, tokens, labels, cfg, operand_dtype=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Summed next-token negative log-likelihood of one sequence and the
    number of positions it sums over.  ``tokens`` and ``labels`` are ``(S,)``;
    a label outside ``[0, vocab)`` is not a target.

    ``operand_dtype`` rounds every operand of a matrix product (weights,
    activations, attention probabilities) to that dtype, keeping float32
    arithmetic: a reading of what a lower precision would give.  ``None``
    is the reference."""
    if cfg.family != "dense" or cfg.mlp_act != "swiglu" or not cfg.tie_embeddings:
        raise ValueError(f"the reference covers the tied SwiGLU dense family, not {cfg.name}")
    p = _f32(params)
    x = p["embed"][tokens]

    def layer(x, bp):
        return _block(x, bp, cfg, operand_dtype), None

    x, _ = jax.lax.scan(layer, x, p["blocks"])
    x = _rmsnorm(x, p["final_norm"]["w"])
    logits = _mm(x, p["embed"].T, operand_dtype)
    mask = (labels >= 0) & (labels < cfg.vocab)
    safe = jnp.where(mask, labels, 0)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, safe[:, None], axis=-1)[:, 0]
    return jnp.sum(jnp.where(mask, lse - gold, 0.0)), jnp.sum(mask)


def loss_and_grad(params, tokens, labels, cfg, *, dp: int = 1, operand_dtype=None):
    """Loss and float32 gradient of a batch ``(B, S)`` under data parallelism
    over ``dp`` chips: the batch split into ``dp`` contiguous shares, as the
    program's batch sharding splits it; each share's mean next-token loss
    and its gradient; then the mean over the shares (:func:`dp_mean`).  The
    gradient is accumulated one sequence at a time into one float32 tree of
    the parameters' structure, so memory holds the parameters, that tree and
    one sequence's gradient."""
    grad_fn = jax.jit(jax.value_and_grad(
        lambda p, t, y: nll_sum(p, t, y, cfg, operand_dtype), has_aux=True))
    add = jax.jit(lambda acc, g, w: jax.tree.map(lambda a, b: a + w * b, acc, g),
                  donate_argnums=0)
    params = _f32(params)  # the gradient takes its leaves' dtype
    tokens, labels = np.asarray(tokens), np.asarray(labels)
    if tokens.shape[0] % dp:
        raise ValueError(f"batch of {tokens.shape[0]} does not split over {dp} chips")
    per = tokens.shape[0] // dp
    loss, acc = 0.0, None
    for c in range(dp):
        y_c = labels[c * per:(c + 1) * per]
        count = max(int(np.sum((y_c >= 0) & (y_c < cfg.vocab))), 1)
        w = np.float32(1.0 / (count * dp))
        for t, y in zip(tokens[c * per:(c + 1) * per], y_c):
            (nll, _), g = grad_fn(params, jnp.asarray(t), jnp.asarray(y))
            loss += float(nll) * float(w)
            acc = jax.tree.map(lambda x: x * w, g) if acc is None else add(acc, g, w)
            del g
    return loss, acc


def dp_mean(per_chip: Sequence[Any]):
    """The exact data-parallel reduction: the float32 mean of the per-chip
    gradient trees (each chip's gradient of its own mean loss)."""
    n = len(per_chip)
    return jax.tree.map(lambda *gs: sum(jnp.asarray(g, jnp.float32) for g in gs) / n,
                        *per_chip)


def _snap(x):
    """Up to the 3-bit-mantissa grid ``(k/8) * 2**e``, k in 4..8: the codec's
    scale snap (``jitmode._snap_scale``), monotone in ``x``."""
    m, e = jnp.frexp(x)
    return jnp.ldexp(jnp.ceil(m * 8.0).astype(jnp.float32), e - 3)


def block_bound(values, slack, *, radius: int, bs: int,
                predictors: Sequence[str] = ("zero", "lorenzo1", "mean"),
                eb: float = 0.0):
    """Per block of ``bs`` elements, the largest pointwise bound the jit
    codec's fixed tier may claim for a block whose input lies within
    ``slack`` (elementwise, >= 0) of ``values``.

    ``core/jitmode.py``'s contract, worked out here from the values: a
    predictor ``p`` prices the block by its residual range ``A_p`` (zero:
    ``max|x|``; mean: ``(max - min) / 2``; Lorenzo-1: ``max|x_i - x_{i-1}|``),
    divided by the radius (``radius - 1`` for Lorenzo); the scale is that
    price, floored at ``max(2 eb, 1e-12)``, snapped up to the 3-bit-mantissa
    grid; the bound is half the scale plus the stated float32 slack
    ``2**-22 * (|base| + scale * max|q|)``, where ``base`` is 0, the block's
    first element or its midrange, and ``max|q|`` is at most ``radius`` (at
    most ``bs * radius`` for Lorenzo's running sum).

    Each range is taken at its largest over the inputs ``slack`` allows, and
    every step after it is monotone, so the bound of each predictor is at
    least what the codec computes for it.  The contest picks one predictor,
    the one with the least price; its bound is at most the largest over the
    enabled predictors, which is what this returns, so it holds whichever
    predictor won.  Float32 rounding of this computation is covered by a
    factor ``1 + 2**-20`` on each range and on the result.
    """
    up = jnp.float32(1.0 + 2.0 ** -20)
    v = jnp.asarray(values, jnp.float32).reshape(-1, bs)
    s = jnp.asarray(slack, jnp.float32).reshape(-1, bs)
    hi, lo = v + s, v - s
    absmax = jnp.max(jnp.maximum(jnp.abs(hi), jnp.abs(lo)), axis=-1) * up
    floor = jnp.float32(max(2.0 * eb, 1e-12))
    out = []
    for p in predictors:
        if p == "zero":
            rng, rad, base, mag = absmax, radius, jnp.zeros_like(absmax), radius
        elif p == "mean":
            rng = (jnp.max(hi, axis=-1) - jnp.min(lo, axis=-1)) * 0.5 * up
            rad, base, mag = radius, absmax, radius
        elif p == "lorenzo1":
            d = jnp.abs(v[:, 1:] - v[:, :-1]) + s[:, 1:] + s[:, :-1]
            rng = (jnp.max(d, axis=-1) if bs > 1 else jnp.zeros_like(absmax)) * up
            rad, base, mag = radius - 1, jnp.abs(v[:, 0]) + s[:, 0], bs * radius
        else:
            raise ValueError(f"unknown predictor {p!r}")
        scale = _snap(jnp.maximum(rng * jnp.float32(1.0 / rad) * up, floor))
        out.append((scale * 0.5 + (base + scale * mag) * jnp.float32(2.0 ** -22)) * up)
    return jnp.max(jnp.stack(out), axis=0)
