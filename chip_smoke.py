#!/usr/bin/env python3
"""Drive the SZ3 main path once on a TPU and check what comes out.

    python chip_smoke.py [--seed N]      # one chip: codec, jitmode, train, serve
    python chip_smoke.py --four-chips    # compressed data-parallel training, 4 chips

Everything runs in this one process (a chip belongs to one process at a
time), from the files of the checkout plus data generated from ``--seed``.

Phases on one chip:

* ``codec``   — a 2-D float32 field at the SDRBench CESM-ATM per-field shape
  (1800 x 3600), at REL 1e-3 and 1e-4, through ``sz3_lorenzo``,
  ``sz3_transform``, ``sz3_fast`` and ``Sz3Codec(predictor="auto")`` (the
  chunked engine).  The device route must be taken, the pointwise bound must
  hold on the decoded output, and each single pipeline's ratio must be within
  1% of the same call with ``device="off"``.
* ``jitmode`` — ``core/jitmode`` encode/decode jitted on the chip (int8,
  int4) on 4096 x 1024; the per-block bound must hold, and whether the chip's
  decode is bit-identical to ``decode_host`` on the same codes is printed.
* ``train``   — ``qwen1.5-0.5b`` at its published widths through
  ``repro.launch.train``: 4 steps, seq 512, batch 4, ``--compress-opt int8``,
  one compressed checkpoint saved and restored within each leaf's bound.
* ``serve``   — the same model through ``repro.launch.serve``: batch 4,
  16 decode tokens, ``--offload-kv chunked`` with every frame strict-verified.

``--four-chips`` runs only ``qwen1.5-0.5b`` on ``--mesh data=4`` for 8 steps
with ``--compress-grads int8`` and then uncompressed, checks that the state
spans 4 devices, and prints both loss trajectories and the band between them.

Each phase prints one line of numbers.  A failed check raises, which ends the
script with a non-zero code; no phase catches its own failure.  The last
line of standard output is ``{"ok": true, "device": {...}}``.  With no TPU,
or outside a checkout, the script exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
ARCH = "qwen1.5-0.5b"
FIELD_SHAPE = (1800, 3600)  # SDRBench CESM-ATM, one 2-D field
JIT_SHAPE = (4096, 1024)
REL_BOUNDS = (1e-3, 1e-4)


def _say(phase: str, **fields) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()), flush=True)


def _check(ok, what) -> None:
    """A failed check ends the run (unlike ``assert``, also under -O)."""
    if not ok:
        raise RuntimeError(f"chip_smoke: check failed: {what}")


def _require_tpu(count: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX found {devs[0].platform}")
    if len(devs) < count:
        raise SystemExit(f"chip_smoke: needs {count} TPU chips, found {len(devs)}")
    return devs


# ---------------------------------------------------------------------------
# codec
# ---------------------------------------------------------------------------

def _check_roundtrip(x, blob, abs_eb):
    from repro.core import decompress

    y = decompress(blob)
    _check(y.shape == x.shape and y.dtype == x.dtype, (y.shape, y.dtype))
    err = float(abs(y.astype("f8") - x.astype("f8")).max())
    _check(err <= abs_eb, f"bound broken: max err {err} > {abs_eb}")
    return err


def phase_codec(seed: int, shape=FIELD_SHAPE) -> None:
    import numpy as np

    from benchmarks.datasets import gaussian_random_field
    from repro.codec import Sz3Codec
    from repro.core import CompressionConfig, ErrorBoundMode, sz3_lorenzo
    from repro.core.fastmode import sz3_fast
    from repro.core.telemetry import explain
    from repro.core.transform import sz3_transform

    x = gaussian_random_field(shape, 2.8, seed)  # climate 2-D, very smooth
    rng = float(x.max() - x.min())
    pipelines = {"sz3_lorenzo": sz3_lorenzo, "sz3_transform": sz3_transform,
                 "sz3_fast": sz3_fast}
    for rel in REL_BOUNDS:
        conf = CompressionConfig(mode=ErrorBoundMode.REL, eb=rel)
        abs_eb = rel * rng
        for name, make in pipelines.items():
            t0 = time.perf_counter()
            res = make(device="auto").compress(x, conf, with_stats=True)
            err = _check_roundtrip(x, res.blob, abs_eb)
            dt = time.perf_counter() - t0
            _check(res.meta.get("device") == 1, f"{name}: host route taken")
            host = make(device="off").compress(x, conf, with_stats=True)
            _check(not host.meta.get("device"), f"{name}: device route taken with device=\"off\"")
            rdiff = res.ratio / host.ratio - 1.0
            _say("codec", pipeline=name, rel=rel, shape="x".join(map(str, shape)),
                 seconds=f"{dt:.3f}", ratio=f"{res.ratio:.4f}",
                 host_ratio=f"{host.ratio:.4f}", ratio_diff=f"{rdiff:+.5f}",
                 err_over_bound=f"{err / abs_eb:.6f}",
                 nfail=int(res.meta.get("nfail", 0)),
                 host_nfail=int(host.meta.get("nfail", 0)), route="device")
            _check(abs(rdiff) <= 0.01, f"{name}: ratio {rdiff:+.4%} off the host route")
        codec = Sz3Codec(eb_mode="rel", eb_rel=rel, predictor="auto")
        t0 = time.perf_counter()
        blob = codec.encode(x)
        y = np.asarray(codec.decode(blob)).reshape(x.shape)
        dt = time.perf_counter() - t0
        err = float(np.abs(y.astype("f8") - x.astype("f8")).max())
        _check(err <= abs_eb, f"chunked: bound broken: {err} > {abs_eb}")
        winners = sorted({r["winner"] for r in explain(blob)})
        _say("codec", pipeline="Sz3Codec(auto)", rel=rel,
             shape="x".join(map(str, shape)), seconds=f"{dt:.3f}",
             ratio=f"{x.nbytes / len(blob):.4f}",
             err_over_bound=f"{err / abs_eb:.6f}", winners="+".join(winners))


# ---------------------------------------------------------------------------
# jitmode
# ---------------------------------------------------------------------------

def phase_jitmode(seed: int, shape=JIT_SHAPE) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import jitmode

    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    # smooth rows with noise: every predictor of the contest has blocks to win
    x = jnp.cumsum(jax.random.normal(k1, shape), axis=1) * 0.05
    x = x + 0.01 * jax.random.normal(k2, shape)
    for tier in ("int8", "int4"):
        policy = jitmode.JitPolicy(tier=tier)
        enc = jax.jit(lambda a: jitmode.encode(a, policy))
        t0 = time.perf_counter()
        codes = enc(x)
        y = jax.jit(jitmode.decode)(codes).block_until_ready()
        dt = time.perf_counter() - t0
        host_codes = jax.tree.map(np.asarray, codes)
        y_host = jitmode.decode_host(host_codes)
        y_dev = np.asarray(y)
        identical = bool(np.array_equal(y_dev.view(np.uint32), y_host.view(np.uint32)))
        ref = jitmode.encode_host(np.asarray(x), policy)
        codes_same = all(
            np.array_equal(np.asarray(a), np.asarray(b))
            for a, b in zip(jax.tree.leaves(host_codes), jax.tree.leaves(ref))
        )
        err = np.abs(y_dev - np.asarray(x).reshape(-1)).reshape(-1, policy.bs)
        bound = np.asarray(codes.bound())
        worst = float((err.max(axis=1) / bound).max())
        _say("jitmode", tier=tier, shape="x".join(map(str, shape)),
             seconds=f"{dt:.3f}", ratio=f"{x.size * 4 / codes.wire_bytes():.4f}",
             err_over_bound=f"{worst:.6f}", decode_bit_identical_to_host=identical,
             codes_equal_encode_host=codes_same, route="device")
        _check(worst <= 1.0, f"jitmode {tier}: per-block bound broken ({worst})")


# ---------------------------------------------------------------------------
# train / serve through the launchers
# ---------------------------------------------------------------------------

def _train(argv, ckpt_dir):
    from repro.launch import train

    t0 = time.perf_counter()
    out = train.main(argv + ["--arch", ARCH, "--ckpt-dir", ckpt_dir])
    out["seconds"] = time.perf_counter() - t0
    _check(all(math.isfinite(v) for v in out["losses"]), out["losses"])
    return out


def _check_restore(out) -> float:
    """Restore the newest checkpoint; every leaf within its policy's bound."""
    import jax
    import numpy as np

    from repro.ft.checkpoint import CheckpointPolicy, _path_str

    mgr = out["ckpt"]
    live = jax.tree.map(np.asarray, out["state"])
    restored, _extra = mgr.restore(live)
    policy = CheckpointPolicy()
    worst = 0.0
    pairs = zip(jax.tree_util.tree_flatten_with_path(live)[0], jax.tree.leaves(restored))
    for (path, a), b in pairs:
        pol = policy.for_path(_path_str(path))
        _check(a.shape == b.shape and a.dtype == b.dtype, _path_str(path))
        if pol.mode == "lossy" and a.dtype.kind == "f" and a.size:
            bound = pol.rel_eb * float(a.max() - a.min())
            err = float(np.abs(a.astype("f8") - b.astype("f8")).max())
            _check(err <= bound, f"{_path_str(path)}: {err} > {bound}")
            worst = max(worst, err / bound if bound else 0.0)
        else:
            _check(np.array_equal(a, b), f"{_path_str(path)} not restored exactly")
    return worst


def phase_train(ckpt_root: str, smoke: bool = False, seq: int = 512) -> None:
    argv = ["--steps", "4", "--seq", str(seq), "--batch", "4",
            "--compress-opt", "int8", "--ckpt-every", "4"]
    out = _train(argv + (["--smoke"] if smoke else ["--no-smoke"]),
                 str(Path(ckpt_root) / "train"))
    _check(len(out["losses"]) == 4, out["losses"])
    _check(out["ckpt"].list_steps() == [4], out["ckpt"].list_steps())
    t0 = time.perf_counter()
    worst = _check_restore(out)
    _say("train", arch=out["cfg"].name, seconds=f"{out['seconds']:.3f}",
         first_step_seconds=f"{out['step_seconds'][0]:.3f}",
         losses=",".join(f"{v:.4f}" for v in out["losses"]),
         restore_seconds=f"{time.perf_counter() - t0:.3f}",
         restore_err_over_bound=f"{worst:.6f}", route="device")


def phase_serve(smoke: bool = False) -> None:
    import numpy as np

    from repro.core import telemetry
    from repro.launch import serve

    def verified():
        h = telemetry.METRICS.snapshot()["histograms"].get("sz3_offload_verify_seconds")
        return int(h["count"]) if h else 0

    before = verified()
    t0 = time.perf_counter()
    out = serve.main(["--arch", ARCH, "--batch", "4", "--tokens", "16",
                      "--offload-kv", "chunked", "--offload-verify",
                      "--smoke" if smoke else "--no-smoke"])
    dt = time.perf_counter() - t0
    toks = np.asarray(out["tokens"])
    n_in, n_out = out["offload"]
    frames = verified() - before
    _check(toks.shape == (4, 17), toks.shape)
    _check(n_in > 0 and n_out > 0 and frames > 0, (n_in, n_out, frames))
    _say("serve", arch=ARCH if not smoke else "smoke", seconds=f"{dt:.3f}",
         tokens=toks.shape[1] - 1, offload_ratio=f"{n_in / n_out:.4f}",
         strict_verified_frames=frames, route="device")


def phase_four_chips(ckpt_root: str, smoke: bool = False, seq: int = 512) -> None:
    import jax

    runs = {}
    for name, extra in (("int8", ["--compress-grads", "int8"]), ("none", [])):
        argv = ["--mesh", "data=4", "--steps", "8", "--seq", str(seq),
                "--batch", "8", "--ckpt-every", "1000",
                "--smoke" if smoke else "--no-smoke"] + extra
        out = _train(argv, str(Path(ckpt_root) / name))
        spans = {len(leaf.sharding.device_set) for leaf in jax.tree.leaves(out["state"])}
        devices = {d.id for leaf in jax.tree.leaves(out["state"])
                   for d in leaf.sharding.device_set}
        _check(spans == {4} and len(devices) == 4, (spans, devices))
        runs[name] = out
        _say("four_chips", grads=name, seconds=f"{out['seconds']:.3f}",
             first_step_seconds=f"{out['step_seconds'][0]:.3f}",
             state_devices=sorted(devices),
             losses=",".join(f"{v:.4f}" for v in out["losses"]))
    band = [abs(a - b) for a, b in zip(runs["int8"]["losses"], runs["none"]["losses"])]
    _say("four_chips", band_max=f"{max(band):.5f}",
         band=",".join(f"{v:.5f}" for v in band))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only compressed data-parallel training on 4 chips")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        raise SystemExit("chip_smoke: run from a checkout of the repository")
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    devs = _require_tpu(4 if args.four_chips else 1)
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        if args.four_chips:
            phase_four_chips(tmp)
        else:
            phase_codec(args.seed)
            phase_jitmode(args.seed)
            phase_train(tmp)
            phase_serve()
    d = devs[0]
    print(json.dumps({"ok": True, "device": {"platform": d.platform,
                                             "kind": d.device_kind,
                                             "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
