"""The control, which every cell's comparison has to fail.

The configuration states a pointwise bound, REL 1e-3.  The control breaks
it: the codec resolves its bound at four times REL (a looser bound buys
ratio and speed, the step that would tempt a change).

On the CPU the test runs at the tiny size of ``conftest.py``.  On the chip,
at a cell's own size, run this file as a script; it prints one JSON line
per seed:

    python chipbench/tests/test_controls.py cesm_atm_2d.auto 11 12 13
"""
from __future__ import annotations

import contextlib
import json
import sys
from pathlib import Path

import pytest

if __name__ != "__main__":
    from conftest import run_tiny

CONTROL_FACTOR = 4.0


@contextlib.contextmanager
def loose_codec():
    """Sz3Codec with its REL bound multiplied by ``CONTROL_FACTOR``."""
    from repro.codec import Sz3Codec

    real = Sz3Codec.__init__

    def init(self, *a, **kw):
        kw["eb_rel"] = kw["eb_rel"] * CONTROL_FACTOR
        real(self, *a, **kw)

    Sz3Codec.__init__ = init
    try:
        yield
    finally:
        Sz3Codec.__init__ = real


@pytest.mark.parametrize("cell", ["cesm_atm_2d.auto", "cesm_atm_2d.lorenzo"])
def test_codec_control_is_incorrect(tiny_root, cell):
    for seed in (1, 2, 3):
        with loose_codec():
            line = run_tiny(tiny_root, cell, seed=seed, seconds=0.0)  # one pass
        assert line["correct"] is False
        assert line["checks"]["max_err_over_bound"]["value"] > 1.5


def main(argv) -> int:
    cell, *seeds = argv
    bench = Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(bench.parent / "src"), str(bench)]
    import run as run_mod

    for seed in (int(s) for s in seeds):
        with loose_codec():
            line = run_mod.run_cell(cell, seed, 0.0, False)  # one pass of the pool
        print(json.dumps({"seed": seed, "correct": line["correct"],
                          "checks": line["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
