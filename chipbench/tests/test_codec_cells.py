"""A whole codec run on the CPU, and the faults that must make it incorrect."""
from __future__ import annotations

import json

import numpy as np
import pytest

from conftest import run_tiny
from lib.spec import load_cell

CODEC_CELLS = ["cesm_atm_2d.auto", "cesm_atm_2d.lorenzo"]


def _schema(line, e2e):
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(line)[-1] == "checks"
    assert set(line["device"]) >= {"platform", "kind", "count", "memory_peak_bytes"}
    for name in e2e:
        m = line["metrics"][name]
        assert set(m) == {"value", "unit"} and m["value"] > 0
    for c in line["checks"].values():
        assert set(c) == {"value", "limit"}
    json.dumps(line)  # one JSON object


@pytest.mark.parametrize("cell", CODEC_CELLS)
def test_codec_run_is_correct(tiny_root, cell):
    line = run_tiny(tiny_root, cell)
    e2e = [m["name"] for m in load_cell(cell, root=tiny_root,
                                        bench_dir=tiny_root / "chipbench").end_to_end]
    assert {"compress_gbps", "ratio", "setup_s"} <= set(e2e)
    assert set(line["metrics"]) == set(e2e)
    _schema(line, e2e)
    assert line["correct"] is True
    assert line["attempted"] >= 8 and line["failed"] == 0
    assert 0 < line["checks"]["max_err_over_bound"]["value"] <= 1.0
    assert line["window_compiles"] == 0


def _alter_decode(monkeypatch, how):
    from repro.codec import Sz3Codec

    real = Sz3Codec.decode

    def decode(self, buf, out=None):
        return how(np.array(real(self, buf)))

    monkeypatch.setattr(Sz3Codec, "decode", decode)


def _one_value_off(y):
    y.reshape(-1)[y.size // 2] += 1.0  # far beyond REL 1e-3 of a field in [0, 1]
    return y


def _wrong_shape(y):
    return y.reshape(-1)


@pytest.mark.parametrize("fault", [_one_value_off, _wrong_shape])
@pytest.mark.parametrize("cell", CODEC_CELLS)
def test_codec_answer_altered_is_incorrect(tiny_root, monkeypatch, cell, fault):
    _alter_decode(monkeypatch, fault)
    line = run_tiny(tiny_root, cell)
    assert line["correct"] is False
    # every call has its shape checked; one call of each of the 8 fields its values
    assert line["failed"] >= 8
