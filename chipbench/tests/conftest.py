"""CPU tests of the benchmark: ``python -m pytest chipbench/tests``.

They run the harness with its look for a chip skipped, on a copy of the
benchmark whose fields are shrunk to a size the CPU holds.
"""
from __future__ import annotations

import json
import os
import shutil
import sys
from pathlib import Path

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent
for p in (str(ROOT / "src"), str(BENCH_DIR)):
    if p not in sys.path:
        sys.path.insert(0, p)

#: small enough for the CPU, large enough for the chunked engine's contest
TINY_SHAPE = [96, 160]


@pytest.fixture
def tiny_root(tmp_path):
    return make_tiny_root(tmp_path)


def make_tiny_root(tmp_path: Path) -> Path:
    """A copy of BENCHMARK.json and chipbench/ at the tiny sizes above."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH_DIR, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    for f in (tmp_path / "chipbench" / "configs").glob("*.json"):
        cfg = json.loads(f.read_text())
        if cfg.get("runner") == "codec_stream":
            cfg["shape"] = TINY_SHAPE
        f.write_text(json.dumps(cfg))
    return tmp_path


def run_tiny(root: Path, workload: str, seed: int = 7, seconds: float = 0.5,
             trace: bool = False):
    """One run of ``workload`` from the copy at ``root``, on the CPU."""
    import run as run_mod

    run_mod.BENCH_DIR = root / "chipbench"
    try:
        return run_mod.run_cell(workload, seed, seconds, trace, require_tpu=False,
                                root=root)
    finally:
        run_mod.BENCH_DIR = BENCH_DIR
