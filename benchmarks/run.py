"""Benchmark registry — one entry per paper table/figure + the framework
integration benches.  Prints ``name,us_per_call,
derived`` CSV lines per the harness contract; detailed per-bench output goes
to stdout above each summary line.

  PYTHONPATH=src python -m benchmarks.run            # reduced sizes
  PYTHONPATH=src python -m benchmarks.run --full     # paper-scale sizes
  PYTHONPATH=src python -m benchmarks.run --only gamess
"""
from __future__ import annotations

import argparse
import json
import time
import traceback


def _timed(name, fn, full):
    t0 = time.perf_counter()
    try:
        derived = fn(full)
        dt = (time.perf_counter() - t0) * 1e6
        print(f"{name},{dt:.0f},ok")
        return {"name": name, "us": dt, "status": "ok", "derived": derived}
    except Exception as e:
        dt = (time.perf_counter() - t0) * 1e6
        traceback.print_exc()
        print(f"{name},{dt:.0f},FAILED:{e}")
        return {"name": name, "us": dt, "status": f"FAILED:{e}", "derived": None}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None)
    ap.add_argument(
        "--json",
        default=None,
        metavar="PATH",
        help="write results (name/us/status/derived rows) to a JSON artifact",
    )
    args = ap.parse_args()

    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    from . import (
        bench_aps,
        bench_chunked,
        bench_gamess,
        bench_integrations,
        bench_pipelines,
        bench_sustainability,
        bench_throughput,
    )

    benches = {
        "gamess_table1_fig4": bench_gamess.main,  # paper Table 1 + Fig 4
        "aps_fig6": bench_aps.main,  # paper Fig 6
        "pipelines_fig7": bench_pipelines.main,  # paper Fig 7
        "throughput_fig8": bench_throughput.main,  # paper Fig 8
        "chunked_streaming": bench_chunked.main,  # chunked engine vs one-shot
        "sustainability_s6_1": bench_sustainability.main,  # paper §6.1/Table 2
        "integrations": bench_integrations.main,  # beyond-paper (grad/kv/opt/ckpt)
    }
    print("name,us_per_call,derived")
    results = []
    for name, fn in benches.items():
        if args.only and args.only not in name:
            continue
        results.append(_timed(name, fn, args.full))
    if args.json:
        from repro.core import lossless

        doc = {
            "full": args.full,
            "lossless_backend": lossless.effective_backend("zstd"),
            "results": results,
        }
        with open(args.json, "w") as f:
            json.dump(doc, f, default=str, indent=1)
        print(f"wrote {args.json}")


if __name__ == "__main__":
    main()
