import os

# a CPU-only tool: 512 simulated host devices, and never the accelerator —
# a chip belongs to one process, and this one (or a per-cell child, which
# inherits the environment) must not take it from the program that runs
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run (deliverable e): lower + compile every assigned
(architecture x input-shape) cell on the production meshes, record
memory/cost/roofline terms.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen1.5-0.5b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--mesh single|multi|both]
  PYTHONPATH=src python -m repro.launch.dryrun --list

Results land in results/dryrun/<mesh>/<arch>__<shape>[__variant].json and are
consumed by benchmarks/roofline.py and EXPERIMENTS.md.

v5e constants for the roofline terms (per brief): 197 TFLOP/s bf16/chip,
819 GB/s HBM, ~50 GB/s/link ICI.  HLO FLOPs/bytes/collectives come from the
while-trip-corrected parser (hlo_cost.py) because compiled.cost_analysis()
counts loop bodies once; both raw and corrected values are recorded.
"""
import argparse
import dataclasses
import functools
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

import repro.configs as configs
from repro import models
from repro.configs.shapes import SHAPES, cell_skip_reason, input_specs
from repro.launch import hlo_cost
from repro.launch.mesh import make_production_mesh
from repro.launch.plans import make_cell_plan
from repro.serve.step import cache_specs, jit_serve_step, make_serve_step
from repro.train.step import init_train_state, jit_train_step, make_train_step
from repro.parallel.specs import batch_specs, param_specs

PEAK_FLOPS = 197e12  # bf16 / chip
HBM_BW = 819e9  # B/s / chip
LINK_BW = 50e9  # B/s / link


def _mem_dict(mem) -> dict:
    out = {}
    for k in (
        "argument_size_in_bytes",
        "output_size_in_bytes",
        "temp_size_in_bytes",
        "generated_code_size_in_bytes",
        "alias_size_in_bytes",
        "peak_memory_in_bytes",
    ):
        v = getattr(mem, k, None)
        if v is not None:
            out[k] = int(v)
    return out


def lower_cell(arch: str, shape: str, mesh, multi_pod: bool, overrides=None):
    cfg = configs.get(arch)
    cell = SHAPES[shape]
    plan, opt_cfg = make_cell_plan(arch, cfg, cell, mesh, multi_pod, overrides)
    key = jax.random.PRNGKey(0)
    specs = input_specs(cfg, cell)

    if cell.kind == "train":
        state_shapes = jax.eval_shape(
            functools.partial(init_train_state, key, cfg, plan, opt_cfg)
        )
        step = make_train_step(cfg, plan, opt_cfg)
        jstep = jit_train_step(step, state_shapes, cfg, plan, opt_cfg, specs)
        lowered = jstep.lower(state_shapes, specs)
    elif cell.kind == "prefill":
        pspecs = param_specs(
            jax.eval_shape(functools.partial(models.init_params, key, cfg, plan)),
            cfg,
            plan,
        )
        bspecs = batch_specs(specs, plan)
        sh = lambda tree: jax.tree.map(
            lambda s: jax.NamedSharding(plan.mesh, s),
            tree,
            is_leaf=lambda s: isinstance(s, jax.sharding.PartitionSpec),
        )

        def prefill(params, batch):
            return models.prefill_logits(params, batch, cfg, plan)

        params_shapes = jax.eval_shape(
            functools.partial(models.init_params, key, cfg, plan)
        )
        lowered = jax.jit(
            prefill, in_shardings=(sh(pspecs), sh(bspecs))
        ).lower(params_shapes, specs)
    else:  # decode
        params_shapes = jax.eval_shape(
            functools.partial(models.init_params, key, cfg, plan)
        )
        if cfg.family == "encdec":
            frames = jax.ShapeDtypeStruct(
                (cell.batch, cfg.enc_seq, cfg.d_model), cfg.param_dtype
            )
            cache_shapes = jax.eval_shape(
                functools.partial(
                    models.init_cache, cfg=cfg, plan=plan, batch=cell.batch,
                    max_len=cell.seq,
                ),
                params_shapes,
                enc_frames=frames,
            )
        else:
            cache_shapes = jax.eval_shape(
                functools.partial(
                    models.init_cache,
                    None,
                    cfg,
                    plan,
                    cell.batch,
                    cell.seq,
                )
            )
        serve = make_serve_step(cfg, plan)
        jstep = jit_serve_step(serve, params_shapes, cache_shapes, cfg, plan)
        lowered = jstep.lower(params_shapes, cache_shapes, specs["tokens"])
    return lowered, cfg, cell, plan


def analyze_cell(arch, shape, mesh, multi_pod, overrides=None, keep_hlo=False):
    t0 = time.time()
    lowered, cfg, cell, plan = lower_cell(arch, shape, mesh, multi_pod, overrides)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    chips = mesh.size
    mem = _mem_dict(compiled.memory_analysis())
    raw_cost = dict(compiled.cost_analysis() or {})
    text = compiled.as_text()
    cost = hlo_cost.analyze(text, n_devices=chips)

    compute_s = cost.flops / PEAK_FLOPS
    dot_compute_s = cost.dot_flops / PEAK_FLOPS
    memory_s = cost.hbm_bytes / HBM_BW
    collective_s = cost.collective_bytes / LINK_BW
    terms = {"compute": compute_s, "memory": memory_s, "collective": collective_s}
    bottleneck = max(terms, key=terms.get)

    # MODEL_FLOPS = 6*N*D (train) / 2*N*D (inference), D = global tokens
    n_params = cfg.n_flop_params()
    tokens = cell.batch * (cell.seq if cell.kind != "decode" else 1)
    mult = 6 if cell.kind == "train" else 2
    model_flops = mult * n_params * tokens
    hlo_flops_global = cost.dot_flops * chips

    result = {
        "arch": arch,
        "shape": shape,
        "mesh": "multi" if multi_pod else "single",
        "chips": chips,
        "kind": cell.kind,
        "overrides": overrides or {},
        "plan": {
            "batch_axes": list(plan.batch_axes),
            "fsdp_axes": list(plan.fsdp_axes),
            "seq_axes": list(plan.seq_axes),
            "microbatches": plan.microbatches,
            "kv_cache_dtype": plan.kv_cache_dtype,
            "remat": plan.remat,
        },
        "timing": {"lower_s": t_lower, "compile_s": t_compile},
        "memory_analysis": mem,
        "cost_analysis_raw": {
            k: float(v)
            for k, v in raw_cost.items()
            if isinstance(v, (int, float)) and k in ("flops", "bytes accessed")
        },
        "hlo_corrected": {
            "flops_per_chip": cost.flops,
            "dot_flops_per_chip": cost.dot_flops,
            "hbm_bytes_per_chip": cost.hbm_bytes,
            "collective_bytes_per_chip": cost.collective_bytes,
            "per_collective": dict(cost.per_collective),
            "while_trips": cost.while_trips,
        },
        "roofline": {
            "compute_s": compute_s,
            "dot_compute_s": dot_compute_s,
            "memory_s": memory_s,
            "collective_s": collective_s,
            "bottleneck": bottleneck,
            "model_flops": model_flops,
            "hlo_dot_flops_global": hlo_flops_global,
            "useful_flops_ratio": model_flops / max(1.0, hlo_flops_global),
        },
    }
    if keep_hlo:
        result["hlo_text_len"] = len(text)
    return result


def cell_list():
    out = []
    for arch in configs.ARCHS:
        cfg = configs.get(arch)
        for shape, cell in SHAPES.items():
            out.append((arch, shape, cell_skip_reason(cfg, cell)))
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--variant", default=None, help="json overrides for the plan")
    ap.add_argument("--tag", default=None, help="suffix for variant result files")
    ap.add_argument("--force", action="store_true")
    ap.add_argument(
        "--isolate",
        action="store_true",
        help="run each cell in a subprocess (fatal XLA crashes can't kill the sweep)",
    )
    args = ap.parse_args()

    if args.isolate and (args.all or (args.arch and args.shape)):
        import subprocess
        import sys

        for mesh_kind in (["single", "multi"] if args.mesh == "both" else [args.mesh]):
            cells = (
                cell_list()
                if args.all
                else [(args.arch, args.shape, None)]
            )
            for arch, shape, _ in cells:
                tag = f"__{args.tag}" if args.tag else ""
                path = Path(args.out) / mesh_kind / f"{arch}__{shape}{tag}.json"
                if path.exists() and not args.force:
                    print(f"[skip-existing] {path}", flush=True)
                    continue
                cmd = [
                    sys.executable, "-m", "repro.launch.dryrun",
                    "--arch", arch, "--shape", shape, "--mesh", mesh_kind,
                    "--out", args.out,
                ]
                if args.variant:
                    cmd += ["--variant", args.variant]
                if args.tag:
                    cmd += ["--tag", args.tag]
                if args.force:
                    cmd += ["--force"]
                r = subprocess.run(cmd, timeout=3600)
                if r.returncode != 0:
                    err = path.with_suffix(".error.json")
                    if not err.exists():
                        err.write_text(json.dumps({
                            "arch": arch, "shape": shape, "mesh": mesh_kind,
                            "error": f"subprocess exited {r.returncode} (fatal crash)",
                        }, indent=2))
                    print(f"  FATAL (rc={r.returncode}) {arch} {shape}", flush=True)
        return

    if args.list:
        for arch, shape, skip in cell_list():
            print(f"{arch:20s} {shape:12s} {'SKIP: ' + skip if skip else 'run'}")
        return

    overrides = json.loads(args.variant) if args.variant else None
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    cells = (
        [(a, s, sk) for a, s, sk in cell_list()]
        if args.all
        else [
            (
                args.arch,
                args.shape,
                cell_skip_reason(configs.get(args.arch), SHAPES[args.shape]),
            )
        ]
    )

    for mesh_kind in meshes:
        multi = mesh_kind == "multi"
        mesh = make_production_mesh(multi_pod=multi)
        out_dir = Path(args.out) / mesh_kind
        out_dir.mkdir(parents=True, exist_ok=True)
        for arch, shape, skip in cells:
            tag = f"__{args.tag}" if args.tag else ""
            path = out_dir / f"{arch}__{shape}{tag}.json"
            if path.exists() and not args.force:
                print(f"[skip-existing] {path}")
                continue
            if skip:
                path.write_text(
                    json.dumps(
                        {"arch": arch, "shape": shape, "mesh": mesh_kind,
                         "skipped": skip},
                        indent=2,
                    )
                )
                print(f"[SKIP] {arch} {shape}: {skip}")
                continue
            print(f"[dryrun] {arch} {shape} mesh={mesh_kind} ...", flush=True)
            try:
                res = analyze_cell(arch, shape, mesh, multi, overrides)
                path.write_text(json.dumps(res, indent=2))
                r = res["roofline"]
                print(
                    f"  ok: compile={res['timing']['compile_s']:.1f}s "
                    f"compute={r['compute_s']:.4f}s memory={r['memory_s']:.4f}s "
                    f"collective={r['collective_s']:.4f}s -> {r['bottleneck']}",
                    flush=True,
                )
            except Exception as e:
                err = {"arch": arch, "shape": shape, "mesh": mesh_kind,
                       "error": str(e), "traceback": traceback.format_exc()}
                path.with_suffix(".error.json").write_text(json.dumps(err, indent=2))
                print(f"  FAILED: {e}", flush=True)


if __name__ == "__main__":
    main()
