"""The training cell on the CPU at the SMOKE widths, its controls, and its
per-layer readers (``train.mfu``, ``collective.exposed_ms``).

``qwen1.5-0.5b_dp4.int8_grads`` runs on 4 simulated CPU devices
(``--xla_force_host_platform_device_count=4``, in a child process: the count
must be set before JAX starts), from a copy of the benchmark whose
configuration names the program's SMOKE preset and its widths.  The run must
be correct, and each control must make it incorrect:

* ``int4``: the program reduces with the int4 policy, read against the
  configuration's int8 bound: ``grad_err_over_bound`` >= 2;
* ``rotate``: every all-gather's result rotated by one chip, so each chip
  decodes its neighbour's codes: ``grad_rel_err`` >= 3 x its limit;
* ``diverge``: the all-gather's result rotated by a chip on the second chip,
  by two on the third, and so on; the first chip's copy is right, the
  others are not: ``replica_mismatch`` >= 1.

One more control is the reading of the next precision below the program's:

* ``fp8``: the reference's loss and gradient with every matrix operand
  rounded to float8 e4m3 are put in the program's place (the moment update
  rebuilt so that the applied gradient is that gradient, with no residual),
  and the runner's own checks read them.

On the chip, at the cell's own size, run this file as a script; it runs each
``control:seconds:seed`` in turn in one process and prints one JSON line for
each:

    python chipbench/tests/test_train_cell.py none:30:7 fp8:0:8 rotate:0:9
"""
from __future__ import annotations

import contextlib
import json
import os
import subprocess
import sys
import textwrap
import time
from pathlib import Path

import pytest

CELL = "qwen1.5-0.5b_dp4.int8_grads"
BENCH = Path(__file__).resolve().parents[1]


def _fp8_in_program(runner):
    """``runner.grad_checks`` fed the float8 reference in the program's place."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from lib import lm_reference

    real = runner.grad_checks

    def checks(before, after, batch, cfg, **kw):
        with jax.default_device(kw.get("device")):
            loss8, g8 = lm_reference.loss_and_grad(
                before["params"], batch["tokens"], batch["labels"], cfg, dp=kw["dp"],
                operand_dtype=jnp.float8_e4m3fn)
            g8 = runner._flat(jax.device_get(g8))
        m0 = runner._flat(before["m"])
        r0 = np.asarray(before["r"], np.float32)
        b1 = np.float32(kw["b1"])
        clip = runner.clip_factor(after["grad_norm"], kw["grad_clip"])
        m1 = b1 * m0 + (np.float32(1.0) - b1) * clip * (g8 + r0[: m0.size])
        return real(before, dict(after, m=m1, r=np.zeros_like(r0), loss=loss8),
                    batch, cfg, **kw)

    return checks


@contextlib.contextmanager
def control(name: str):
    """The program, or the runner's reading of it, altered as the control
    ``name`` says (``none``: as it is)."""
    import jax
    import jax.numpy as jnp

    from repro.compression import grad as gradc

    if name == "none":
        yield
        return
    if name == "int4":
        real = gradc.compressed_reduce_flat

        def patched(flat, feedback, dp_axes, policy):
            return real(flat, feedback, dp_axes, "int4")

        target, attr = gradc, "compressed_reduce_flat"
    elif name in ("rotate", "diverge"):
        real = jax.lax.all_gather

        def patched(x, axis_name, **kw):
            out = real(x, axis_name, **kw)
            chips = 1 if name == "rotate" else jax.lax.axis_index(axis_name)
            return jnp.roll(out, x.shape[0] * chips, axis=0)

        target, attr = jax.lax, "all_gather"
    elif name == "fp8":
        from lib import spec

        real = spec.load_module

        def patched(path, mod_name):
            mod = real(path, mod_name)
            if hasattr(mod, "grad_checks"):
                mod.grad_checks = _fp8_in_program(mod)
            return mod

        target, attr = spec, "load_module"
    else:
        raise ValueError(f"unknown control {name!r}")
    setattr(target, attr, patched)
    try:
        yield
    finally:
        setattr(target, attr, real)


def main(argv) -> int:
    sys.path[:0] = [str(BENCH.parent / "src"), str(BENCH)]
    import run as run_mod

    for arg in argv:
        name, seconds, seed = arg.split(":")
        t0 = time.perf_counter()
        with control(name):
            line = run_mod.run_cell(CELL, int(seed), float(seconds), False, t_start=t0)
        print(json.dumps({"control": name, "seed": int(seed), "seconds": float(seconds),
                          "wall_s": time.perf_counter() - t0, "line": line}), flush=True)
    return 0


# -- CPU tests ---------------------------------------------------------------

def _smoke_root(tmp_path: Path) -> Path:
    """A copy of the benchmark with the training cell at the SMOKE preset."""
    from conftest import make_tiny_root
    from repro import configs

    root = make_tiny_root(tmp_path)
    path = root / "chipbench" / "configs" / "qwen1.5-0.5b_dp4.json"
    conf = json.loads(path.read_text())
    smoke = configs.get_smoke(conf["model"]["arch"])
    conf["model"].update(preset="smoke", head_dim=smoke.hd, **{
        k: getattr(smoke, k) for k in ("family", "n_layers", "d_model", "n_heads",
                                       "n_kv_heads", "d_ff", "vocab", "qkv_bias",
                                       "tie_embeddings", "mlp_act", "dtype", "rope_theta")})
    conf["assumed"]["embedding_rows"] = smoke.padded_vocab
    path.write_text(json.dumps(conf))
    traffic = root / "chipbench" / "traffic" / "int8_grads.json"
    t = json.loads(traffic.read_text())
    t["seq"] = 64
    traffic.write_text(json.dumps(t))
    return root


def _run_in_child(root: Path, name: str, seed: int) -> dict:
    code = textwrap.dedent(f"""
        import json, os, sys
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        os.environ["JAX_PLATFORMS"] = "cpu"
        sys.path[:0] = [{str(BENCH.parent / 'src')!r}, {str(BENCH)!r}, {str(BENCH / 'tests')!r}]
        import run as run_mod
        from test_train_cell import control
        run_mod.BENCH_DIR = run_mod.Path({str(root / 'chipbench')!r})
        with control({name!r}):
            line = run_mod.run_cell({CELL!r}, {seed}, 0.0, False, require_tpu=False,
                                    root=run_mod.Path({str(root)!r}))
        print(json.dumps(line))
    """)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                       timeout=900, env=env, cwd=str(root))
    assert r.returncode == 0, r.stderr[-4000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def smoke_root(tmp_path_factory):
    return _smoke_root(tmp_path_factory.mktemp("train_cell"))


def test_train_cell_is_correct(smoke_root):
    line = _run_in_child(smoke_root, "none", seed=2**31 + 11)
    assert line["correct"] is True, line["checks"]
    assert line["device"]["count"] == 4
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert line["metrics"]["compress_gbps"]["value"] > 0
    assert set(line["metrics"]) == {"compress_gbps", "setup_s"}
    assert line["window_compiles"] == 0
    checks = line["checks"]
    assert 0 < checks["grad_err_over_bound"]["value"] <= 1.0
    assert checks["grad_rel_err"]["value"] < checks["grad_rel_err"]["limit"]
    assert checks["replica_mismatch"]["value"] == 0


def test_int4_control_is_incorrect(smoke_root):
    line = _run_in_child(smoke_root, "int4", seed=5)
    assert line["correct"] is False
    assert line["checks"]["grad_err_over_bound"]["value"] >= 2.0


def test_rotated_gather_control_is_incorrect(smoke_root):
    line = _run_in_child(smoke_root, "rotate", seed=6)
    assert line["correct"] is False
    c = line["checks"]["grad_rel_err"]
    assert c["value"] >= 3 * c["limit"]


def test_fp8_control_is_incorrect(smoke_root):
    line = _run_in_child(smoke_root, "fp8", seed=8)
    assert line["correct"] is False
    checks = line["checks"]
    # the float8 gradient, applied with no residual, fails by its error alone
    assert checks["grad_rel_err"]["value"] > checks["grad_rel_err"]["limit"]
    assert checks["grad_err_over_bound"]["value"] == 0.0
    assert checks["replica_mismatch"]["value"] == 0


def test_diverged_replica_control_is_incorrect(smoke_root):
    line = _run_in_child(smoke_root, "diverge", seed=7)
    assert line["correct"] is False
    checks = line["checks"]
    assert checks["replica_mismatch"]["value"] >= 1
    # the first chip's copy holds the codec's bound, which reads that copy
    assert checks["grad_err_over_bound"]["value"] <= checks["grad_err_over_bound"]["limit"]


class _Ctx:
    """What the two readers look at, made by hand."""

    def __init__(self, spans=(), device=None, steps=0):
        self.config = json.loads((BENCH / "configs" / "qwen1.5-0.5b_dp4.json").read_text())
        self.traffic = json.loads((BENCH / "traffic" / "int8_grads.json").read_text())
        self.spans, self.device, self.chips = list(spans), device, 4
        self.work = {"traced_steps": steps}

    def peaks(self):
        return {"bf16_flops_per_s": 197e12}


def _reader(name):
    from lib.spec import load_module

    return load_module(BENCH / "metrics" / f"{name}.py", name)


def test_mfu_reads_the_train_step_spans():
    from lib.flops import matmul_params, train_flops_per_token

    ctx = _Ctx(spans=[{"name": "train_step", "seconds": 0.2, "attrs": {"tokens": 8192}}] * 5)
    model = ctx.config["model"]
    # 24 x (4 x 1024^2 + 3 x 1024 x 2816) + 1024 x 151,936
    assert matmul_params(model) == 463_863_808
    want = 100 * 5 * 8192 * train_flops_per_token(model, 512) / 1.0 / (4 * 197e12)
    assert _reader("train.mfu").read(ctx) == pytest.approx(want)
    assert _reader("train.mfu").read(_Ctx()) is None


def test_exposed_collective_time():
    from lib.devtrace import DeviceTrace, Event

    ms = 1e6
    ops = {d: [Event(0, 10 * ms, "%fusion.1 = f32[] fusion()"),
               Event(5 * ms, (20 + d) * ms, "%all-gather-start.2 = (...) all-gather-start()"),
               Event(30 * ms, 40 * ms, "%reduce-scatter.1 = bf16[] reduce-scatter()"),
               Event(35 * ms, 45 * ms, "%fusion.7 = f32[] fusion()")]
           for d in range(4)}
    dt = DeviceTrace(window=(0, 100 * ms), ops=ops, modules={d: [] for d in range(4)})
    # per chip (10 + d) ms of all-gather alone and 5 ms of reduce-scatter
    # alone, averaged over chips (16.5 ms), over 3 steps
    assert _reader("collective.exposed_ms").read(_Ctx(device=dt, steps=3)) == pytest.approx(5.5)
    quiet = DeviceTrace(window=(0, ms), ops={0: [Event(0, ms, "%fusion = f32[] fusion()")]},
                        modules={0: []})
    assert _reader("collective.exposed_ms").read(_Ctx(device=quiet, steps=3)) is None


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
