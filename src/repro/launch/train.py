"""Training launcher: ``python -m repro.launch.train --arch <id> [--no-smoke]``.

Selects any assigned architecture config (the reduced smoke variant unless
``--no-smoke``), builds the per-cell parallel plan (single device, or a mesh
over the local devices with ``--mesh``), and runs the full production loop:
sharded train step, microbatching, SZ3-compressed checkpoints, deterministic
resumable data, heartbeat monitoring.  ``main(argv)`` returns the final
state, the per-step losses and wall seconds (the first step's include its
compilation) and the checkpoint manager, so a driver script can check them
in the same process.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

import repro.configs as configs
from repro.data import make_pipeline
from repro.ft import CheckpointManager, HeartbeatMonitor
from repro.optim import AdamWConfig
from repro.parallel import ParallelPlan
from repro.train.step import (
    init_train_state,
    jit_train_step,
    make_train_step,
    state_shardings,
)

from .compile_cache import use_compile_cache


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b", choices=configs.ARCHS)
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction, default=True,
                    help="use the reduced config; --no-smoke runs the "
                         "published widths")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_launch_train",
                    help="checkpoint directory; a run resumes from the "
                         "newest checkpoint found there")
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--compress-moments", action="store_true")
    ap.add_argument("--mesh", default="",
                    help="mesh shape as data=N[,model=M]; needs that many "
                         "devices (XLA_FLAGS=--xla_force_host_platform_"
                         "device_count=K simulates K on CPU)")
    ap.add_argument("--compress-grads", default="", metavar="POLICY",
                    help="error-bounded DP gradient reduction: a jitmode "
                         "policy spec ('int8', 'int4:bs=256', "
                         "'int8:eb=1e-6:pred=zero+lorenzo1+mean') or plain "
                         "8/4; needs --mesh with data>1")
    ap.add_argument("--compress-opt", default="", metavar="POLICY",
                    help="compressed optimizer moments with this jitmode "
                         "policy spec (implies --compress-moments)")
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = configs.get_smoke(args.arch) if args.smoke else configs.get(args.arch)
    mesh, names = None, ()
    if args.mesh:
        from .mesh import make_debug_mesh

        pairs = [kv.split("=") for kv in args.mesh.split(",")]
        names = tuple(k for k, _ in pairs)
        shape = tuple(int(v) for _, v in pairs)
        mesh = make_debug_mesh(shape, names)
    grad_policy = args.compress_grads
    if grad_policy in ("8", "4"):  # bare bit width -> default policy
        grad_policy = f"int{grad_policy}"
    plan = ParallelPlan(
        mesh=mesh,
        model_axis="model" if "model" in names else None,
        microbatches=args.microbatches,
        grad_policy=grad_policy,
    )
    opt = AdamWConfig(
        lr=args.lr,
        compress_moments=args.compress_moments or bool(args.compress_opt),
        moment_policy=args.compress_opt,
    )
    print(f"arch={cfg.name} family={cfg.family} ~{cfg.n_flop_params()/1e6:.0f}M params")

    pipe = make_pipeline(cfg, seq=args.seq, global_batch=args.batch)
    mgr = CheckpointManager(args.ckpt_dir, keep=2)
    mon = HeartbeatMonitor(["host0"], timeout_s=600)

    state = init_train_state(jax.random.PRNGKey(0), cfg, plan, opt)
    start = 0
    if mgr.list_steps():
        host, extra = mgr.restore(jax.tree.map(np.asarray, state))
        state = jax.tree.map(jnp.asarray, host)
        start = int(extra.get("next_step", 0))
        print(f"resumed at step {start}")

    step_fn = make_train_step(cfg, plan, opt, total_steps=args.steps)
    if mesh is None:
        step_fn = jax.jit(step_fn, donate_argnums=0)
    else:
        # place the state on the mesh as the step's shardings say, so the
        # first step does not start from arrays on one device
        batch0 = {k: jnp.asarray(v) for k, v in pipe.batch_at(start).items()}
        step_fn = jit_train_step(step_fn, state, cfg, plan, opt, batch0)
        state = jax.device_put(state, state_shardings(state, cfg, plan, opt))
    losses, seconds = [], []
    t0 = time.perf_counter()
    for k in range(start, args.steps):
        batch = {k2: jnp.asarray(v) for k2, v in pipe.batch_at(k).items()}
        state, m = step_fn(state, batch)
        losses.append(float(m["loss"]))
        dt = time.perf_counter() - t0
        t0 = time.perf_counter()
        seconds.append(dt)
        mon.beat("host0", dt)
        if k % 5 == 0 or k == args.steps - 1:
            print(f"step {k:4d} loss={losses[-1]:.4f} "
                  f"({args.batch * args.seq / dt:,.0f} tok/s)")
        if (k + 1) % args.ckpt_every == 0:
            mgr.save(k + 1, state, extra={"next_step": k + 1})
    mgr.wait()
    print("done; checkpoints:", mgr.list_steps())
    return {"state": state, "losses": losses, "step_seconds": seconds,
            "ckpt": mgr, "cfg": cfg}


if __name__ == "__main__":
    main()
