"""Model FLOPs of a training step of the dense decoder, from its widths.

Convention: ``6 * N`` a token plus causal attention, where ``N`` counts the
weights of the matrix products a token passes through (per layer Q, K, V
and output projections and the three SwiGLU matrices; once, the tied head
over the published vocabulary).  The embedding lookup is no product, and
biases and norms are left out.  Attention's scores and weighted sum take
``2 * 2 * S * d`` a token a layer in the forward pass over all ``S`` keys,
half of that under a causal mask, and three times the forward pass for
forward and backward: ``6 * L * S * d``.  Recomputation (remat) is not
counted: these are the operations the model needs, not those the program
runs.
"""
from __future__ import annotations

from typing import Any, Dict


def matmul_params(model: Dict[str, Any]) -> int:
    d, ff, L = model["d_model"], model["d_ff"], model["n_layers"]
    hd = model["head_dim"]
    q = model["n_heads"] * hd
    kv = model["n_kv_heads"] * hd
    per_layer = d * q + 2 * d * kv + q * d + 3 * d * ff
    return L * per_layer + d * model["vocab"]


def train_flops_per_token(model: Dict[str, Any], seq: int) -> float:
    return 6.0 * matmul_params(model) + 6.0 * model["n_layers"] * seq * model["d_model"]
