#!/usr/bin/env python3
"""Run one cell of the chip benchmark and print its result line.

    python chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  The cell, its configuration, its traffic
mix and its per-layer metrics are found by name from ``BENCHMARK.json``
(``chipbench/lib/spec.py``).  With ``--trace 0`` the last line of standard
output carries the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics read from the program's spans and a device profile.  The
numbers that decide ``correct`` are printed last on standard error and under
``checks`` in the result line.  Without a TPU, or with fewer chips than the
cell asks for, the run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up is measured from here

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


def _fail(msg: str, code: int = 2) -> int:
    print(f"chipbench: {msg}", file=sys.stderr, flush=True)
    return code


def setup_jax():
    """Persistent compile cache at the checkout's fixed path, small
    programs included."""
    import jax

    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, trace_dir=None, root: Path = ROOT,
             t_start: float = T_START):
    """Run one cell; returns the result line as a dict.  Raises
    ``SystemExit`` (non-zero) when the chips the cell needs are missing."""
    from lib.harness import Harness, result_line
    from lib.spec import load_cell

    cell = load_cell(workload, root=root, bench_dir=BENCH_DIR)
    jax = setup_jax()
    devices = jax.devices()
    if require_tpu:
        if devices[0].platform != "tpu":
            raise SystemExit(_fail(f"needs a TPU, JAX found {devices[0].platform}", 3))
        if len(devices) < cell.chips:
            raise SystemExit(_fail(f"{workload} needs {cell.chips} chips, "
                                   f"found {len(devices)}", 3))
    h = Harness(cell, seed=seed, seconds=seconds, trace=trace,
                devices=devices[: cell.chips], t_start=t_start, trace_dir=trace_dir)
    res = cell.runner.run(h)
    return result_line(h, res)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the device profile here (default: a temporary "
                         "directory, deleted after it is read)")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        return _fail(f"no program under {ROOT / 'src' / 'repro'}: run from a checkout")
    if args.seed < 0:
        return _fail("--seed must be non-negative")
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(BENCH_DIR))
    line = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                    trace_dir=args.trace_dir)
    for name, c in line["checks"].items():
        ok = c["value"] <= c["limit"]
        print(f"check {name} value={c['value']!r} limit={c['limit']!r} "
              f"{'ok' if ok else 'FAILED'}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
