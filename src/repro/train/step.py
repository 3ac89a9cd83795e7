"""Train-step factory: microbatched, remat'd, pjit-sharded, optionally with
error-bounded gradient compression on the DP reduction.

Two modes:
  * baseline  — plain pjit: XLA inserts the DP all-reduce (bf16/f32).
  * compressed (plan.grad_policy / plan.grad_compress_bits) — the step body
    runs inside a shard_map that is MANUAL over ALL mesh axes, so the DP
    reduction is OUR schedule: reduce-scatter bf16 -> error-feedback encode
    with the jit codec facade (per-block predictor contest, core/jitmode) ->
    all-gather codes + side channels (repro/compression/grad.py).  Full
    manual (not dp-only) both sidesteps an XLA-CPU partial-manual
    partitioner crash (parallel/compat.py) and keeps model compute purely
    local — params are replicated inside the region, so the model axis just
    duplicates work on CPU test meshes.

State = {params, opt{m,v,step}, feedback?}.  All specs are derived from
parallel/specs.py so the launchers and examples share one source of truth.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .. import models
from ..compression import grad as gradc
from ..models.common import ModelConfig
from ..optim import AdamWConfig, init_state, update, warmup_cosine
from ..parallel import compat
from ..parallel.plan import ParallelPlan
from ..parallel.specs import batch_specs, param_specs

#: Compressed-moment side channels: trailing path names with the parameter's
#: leading spec and an unsharded blocks dim (codes keeps the full rank)
_SIDE_CHANNELS = ("scale", "tags", "base")


def _moment_spec(pspec: P, leaf_ndim: int, compressed: bool):
    if not compressed:
        return pspec
    entries = tuple(pspec) + (None,) * (leaf_ndim - len(tuple(pspec)))
    side = P(*entries[:-1], None)
    return {"codes": P(*entries), **{k: side for k in _SIDE_CHANNELS}}


def state_specs(state, cfg: ModelConfig, plan: ParallelPlan, opt_cfg: AdamWConfig):
    params = state["params"] if isinstance(state, dict) and "params" in state else state
    pspecs = param_specs(params, cfg, plan)
    flat_pspecs = {
        _pstr(path): spec
        for path, spec in jax.tree_util.tree_flatten_with_path(pspecs)[0]
    }

    def moment_tree(moments):
        """Spec tree structurally identical to the actual moment pytree."""

        def leaf_spec(path, leaf):
            names = [_key(p) for p in path]
            # strip trailing Compressed field names; all have the parameter's
            # rank (side channels swap the last dim for n_blocks)
            if names and names[-1] in ("codes",) + _SIDE_CHANNELS:
                pstr = "/".join(names[:-1])
                base = flat_pspecs.get(pstr, P())
                nd = leaf.ndim
                entries = tuple(base) + (None,) * (nd - len(tuple(base)))
                if names[-1] == "codes":
                    return P(*entries)
                return P(*entries[:-1], None)
            pstr = "/".join(names)
            return flat_pspecs.get(pstr, P())

        return jax.tree_util.tree_map_with_path(leaf_spec, moments)

    if plan.grad_compression() is not None and plan.mesh is not None:
        # compressed mode: the step body is manual over the whole mesh with
        # params/opt replicated inside (no FSDP there) — the AOT shardings
        # must match the region's view or jit inserts reshards every step
        specs = {
            "params": jax.tree.map(lambda _: P(), state["params"]),
            "opt": jax.tree.map(lambda _: P(), state["opt"]),
        }
    else:
        specs = {
            "params": pspecs,
            "opt": {
                "m": moment_tree(state["opt"]["m"]),
                "v": moment_tree(state["opt"]["v"]),
                "step": P(),
            },
        }
    if plan.grad_compression() is not None:
        b = plan.batch_axes if len(plan.batch_axes) > 1 else plan.batch_axes[0]
        specs["feedback"] = P(b)
    return specs


def _key(p) -> str:
    if hasattr(p, "key"):
        return str(p.key)
    if hasattr(p, "name"):
        return str(p.name)
    return str(p)


def _pstr(path) -> str:
    return "/".join(_key(p) for p in path)


def init_train_state(key, cfg: ModelConfig, plan: ParallelPlan, opt_cfg: AdamWConfig):
    params = models.init_params(key, cfg, plan)
    state = {"params": params, "opt": init_state(params, opt_cfg)}
    pol = plan.grad_compression()
    if pol is not None:
        state["feedback"] = gradc.init_feedback(params, plan.dp, pol.bs)
    return state


def _microbatched_grads(loss_fn, params, batch, n_micro: int, accum_dtype=jnp.float32):
    if n_micro <= 1:
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        return loss, grads

    def reshape(x):
        return x.reshape((n_micro, x.shape[0] // n_micro) + x.shape[1:])

    mbatch = jax.tree.map(reshape, batch)
    zero = jax.tree.map(lambda p: jnp.zeros(p.shape, accum_dtype), params)

    def body(carry, mb):
        loss_acc, g_acc = carry
        loss, g = jax.value_and_grad(loss_fn)(params, mb)
        g_acc = jax.tree.map(lambda a, b: a + b.astype(accum_dtype), g_acc, g)
        return (loss_acc + loss, g_acc), None

    (loss, grads), _ = jax.lax.scan(body, (jnp.float32(0.0), zero), mbatch)
    inv = 1.0 / n_micro
    return loss * inv, jax.tree.map(lambda g: (g.astype(jnp.float32) * inv), grads)


def make_train_step(
    cfg: ModelConfig,
    plan: ParallelPlan,
    opt_cfg: AdamWConfig = AdamWConfig(),
    total_steps: int = 10000,
    attn_mode: str = "blocked",
) -> Callable:
    """Returns train_step(state, batch) -> (state, metrics)."""

    def loss_fn(params, batch):
        return models.loss_fn(params, batch, cfg, plan, attn_mode=attn_mode)

    dp_axes = tuple(plan.batch_axes)
    grad_pol = plan.grad_compression()

    def step_core(state, batch, *, inner_plan: ParallelPlan):
        def lf(params, b):
            return models.loss_fn(params, b, cfg, inner_plan, attn_mode=attn_mode)

        loss, grads = _microbatched_grads(
            lf,
            state["params"],
            batch,
            plan.microbatches,
            accum_dtype=jnp.dtype(plan.grad_accum_dtype),
        )
        new_state = dict(state)
        if grad_pol is not None:
            grads, fb = gradc.compressed_reduce_tree(
                grads, state["feedback"], dp_axes, grad_pol
            )
            loss = jax.lax.pmean(loss, dp_axes)
            new_state["feedback"] = fb
        lr_scale = warmup_cosine(state["opt"]["step"], total=total_steps)
        params, opt, metrics = update(
            state["params"], grads, state["opt"], opt_cfg, lr_scale
        )
        new_state["params"] = params
        new_state["opt"] = opt
        metrics["loss"] = loss
        return new_state, metrics

    if grad_pol is not None and plan.mesh is not None:
        # manual over ALL mesh axes (see module docstring); the body sees
        # purely local arrays, so the inner plan drops the mesh entirely —
        # sharding constraints elide and model compute runs the local path
        inner_plan = dataclasses.replace(plan, mesh=None, batch_axes=())

        def train_step(state, batch):
            sspecs = state_specs_cached(state)
            b = dp_axes if len(dp_axes) > 1 else dp_axes[0]

            def body(state, batch):
                return step_core(state, batch, inner_plan=inner_plan)

            bspec = jax.tree.map(
                lambda x: P(*((b,) + (None,) * (x.ndim - 1))), batch
            )
            out = compat.shard_map(
                body,
                plan.mesh,
                axis_names=set(plan.mesh.axis_names),
                in_specs=(sspecs, bspec),
                out_specs=(sspecs, {"grad_norm": P(), "loss": P()}),
                check_vma=False,
            )(state, batch)
            return out

        def state_specs_cached(state):
            # inside the manual region params are replicated over dp (no
            # FSDP in compressed mode); feedback is dp-sharded.
            def rep(x):
                return P()

            sp = {
                "params": jax.tree.map(rep, state["params"]),
                "opt": jax.tree.map(rep, state["opt"]),
            }
            b = dp_axes if len(dp_axes) > 1 else dp_axes[0]
            sp["feedback"] = P(b)
            return sp

        # the factory's contract is a callable that just works, eagerly
        # too, so the manual region is jitted here.  jit_train_step may wrap
        # this again with explicit shardings; nested jit is inlined at trace
        # time.
        return jax.jit(train_step)

    def train_step(state, batch):
        return step_core(state, batch, inner_plan=plan)

    return train_step


def _named(tree, mesh):
    return jax.tree.map(
        lambda s: jax.sharding.NamedSharding(mesh, s) if isinstance(s, P) else s,
        tree,
        is_leaf=lambda s: isinstance(s, P),
    )


def state_shardings(state, cfg: ModelConfig, plan: ParallelPlan, opt_cfg: AdamWConfig):
    """NamedShardings of the train state on ``plan.mesh`` (for device_put)."""
    return _named(state_specs(state, cfg, plan, opt_cfg), plan.mesh)


def jit_train_step(
    train_step,
    state,
    cfg: ModelConfig,
    plan: ParallelPlan,
    opt_cfg: AdamWConfig,
    batch_shapes: Dict[str, jax.ShapeDtypeStruct],
):
    """AOT-jit with explicit in/out shardings (the dry-run entry point)."""
    if plan.mesh is None:
        return jax.jit(train_step)
    sshard = state_shardings(state, cfg, plan, opt_cfg)
    metric_specs = {"grad_norm": P(), "loss": P()}
    return jax.jit(
        train_step,
        in_shardings=(sshard, _named(batch_specs(batch_shapes, plan), plan.mesh)),
        out_shardings=(sshard, _named(metric_specs, plan.mesh)),
        donate_argnums=(0,),
    )
