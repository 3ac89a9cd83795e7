"""The yardstick on its own: trace reduction, the roofline arithmetic, the
peak table, and cells and metrics found by name."""
from __future__ import annotations

import json
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from conftest import BENCH_DIR, run_tiny
from lib import devtrace
from lib.spec import SpecError, load_cell, load_module, peaks_for

DATA = Path(__file__).resolve().parent / "data"


# ---------------------------------------------------------------------------
# trace reduction on synthetic events
# ---------------------------------------------------------------------------

def _ev(start, dur, name):
    return NS(start_ns=start, duration_ns=dur, name=name)


def _planes(ops, host, modules=(), dev=0):
    return [
        NS(name="/host:CPU", lines=[NS(name="python", events=host)]),
        NS(name=f"/device:TPU:{dev}", lines=[NS(name="XLA Ops", events=ops),
                                              NS(name="XLA Modules", events=list(modules))]),
    ]


def test_union_and_subtract():
    assert devtrace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert devtrace.subtract([(0, 10)], [(2, 3), (5, 6)]) == [(0, 2), (3, 5), (6, 10)]
    assert devtrace.intersect([(0, 4), (6, 9)], [(3, 7)]) == [(3, 4), (6, 7)]


def test_busy_idle_and_attribution():
    host = [_ev(0, 100, devtrace.WINDOW), _ev(10, 40, "chipbench.encode"),
            _ev(50, 40, "chipbench.decode")]
    ops = [_ev(20, 10, "%fusion.1 = f32[8] fusion()"),
           _ev(25, 10, "%fusion.1 = f32[8] fusion()"),  # overlaps the first
           _ev(60, 5, "%copy.2 = f32[8] copy()")]
    t = devtrace.reduce(_planes(ops, host), [0])
    assert t.window_s == pytest.approx(100e-9)
    assert t.busy_s == pytest.approx(20e-9)  # [20, 35) and [60, 65)
    gaps = dict(map(tuple, t.idle_by_host(10)))
    assert gaps["encode"] == pytest.approx(25e-9)
    assert gaps["decode"] == pytest.approx(35e-9)
    assert gaps[devtrace.BETWEEN] == pytest.approx(20e-9)
    assert sum(gaps.values()) == pytest.approx(t.window_s - t.busy_s)
    assert t.top_ops(1) == [["fusion.1", pytest.approx(20e-9)]]


def test_events_outside_the_window_are_clipped():
    host = [_ev(100, 100, devtrace.WINDOW)]
    ops = [_ev(50, 100, "%a = f32[] add()"), _ev(250, 10, "%b = f32[] add()")]
    t = devtrace.reduce(_planes(ops, host), [0])
    assert t.busy_s == pytest.approx(50e-9)


def test_missing_window_is_an_error():
    with pytest.raises(ValueError, match="chipbench.window"):
        devtrace.reduce(_planes([], []), [0])


# ---------------------------------------------------------------------------
# a trace recorded on the chip (cesm_atm_2d.lorenzo, --trace 1, a v5e)
# ---------------------------------------------------------------------------

def test_recorded_chip_trace():
    t = devtrace.reduce_file(str(DATA / "lorenzo_window.xplane.pb"), [0])
    assert 29 < t.window_s < 32
    assert 0 < t.busy_s < 0.01 * t.window_s
    k = json.loads((BENCH_DIR / "kernels" / "lorenzo.json").read_text())
    assert sum(m.name.startswith(tuple(k["encode_programs"])) for m in t.modules[0]) == 12
    secs = t.module_seconds(k["encode_programs"])
    assert 0 < secs < t.busy_s
    gaps = dict(map(tuple, t.idle_by_host(10)))
    assert set(gaps) >= {"encode", "decode"}
    assert sum(gaps.values()) == pytest.approx(t.window_s - t.busy_s, rel=1e-9)
    # 12 encodes of 1800 x 3600 at 12 B an element, at 819 GB/s
    roof = load_module(BENCH_DIR / "metrics" / "lorenzo_roofline.py", "roof")
    ctx = NS(device=t, work={"traced_encode_elements": 12 * 1800 * 3600},
             kernel=lambda name: k,
             peaks=lambda: peaks_for("TPU v5 lite"))
    share = roof.read(ctx)
    assert share == pytest.approx(100 * 12 * 1800 * 3600 * 12 / 819e9 / secs)
    assert 0 < share <= 100


# ---------------------------------------------------------------------------
# the compile counter and peaks
# ---------------------------------------------------------------------------

def test_window_counts_compiles():
    import jax
    import jax.numpy as jnp

    from lib.harness import Harness

    h = Harness(NS(), seed=1, seconds=0.0, trace=False, devices=jax.devices()[:1],
                t_start=0.0)
    x = jnp.arange(7.0)
    jax.jit(lambda x: x * 5 - 2)(x).block_until_ready()
    h.begin_window()
    jax.jit(lambda x: x * 3 + 1)(x).block_until_ready()
    h.end_window()
    jax.jit(lambda x: x * 7 + 4)(x).block_until_ready()
    assert h.window_compiles == 1


def test_unknown_device_kind_is_refused():
    assert peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(SpecError, match="no peaks"):
        peaks_for("TPU v9 imaginary")


# ---------------------------------------------------------------------------
# found by name: a new cell, traffic mix and metric as new files only
# ---------------------------------------------------------------------------

def test_new_cell_and_metric_from_files_only(tiny_root):
    bench_file = tiny_root / "BENCHMARK.json"
    before = {p: p.read_bytes() for p in (tiny_root / "chipbench").rglob("*") if p.is_file()}
    (tiny_root / "chipbench" / "traffic" / "transform.json").write_text(json.dumps(
        {"predictor": "transform"}))
    (tiny_root / "chipbench" / "metrics" / "pairs.count.py").write_text(
        "def read(ctx):\n    return float(ctx.work['traced_encode_bytes'] > 0)\n")
    bench = json.loads(bench_file.read_text())
    bench["workloads"].append({"name": "cesm_atm_2d.transform", "config": "cesm_atm_2d",
                               "traffic": "transform", "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if "workloads" in m and "cesm_atm_2d.auto" in m["workloads"]:
            m["workloads"].append("cesm_atm_2d.transform")
    bench["per_layer"].append({"name": "pairs.count", "unit": "x", "better": "higher",
                               "source": "program_span", "layer": "test",
                               "moves": "compress_gbps",
                               "workloads": ["cesm_atm_2d.transform"]})
    bench_file.write_text(json.dumps(bench))

    cell = load_cell("cesm_atm_2d.transform", root=tiny_root,
                     bench_dir=tiny_root / "chipbench")
    assert [m["name"] for m in cell.per_layer] == ["pairs.count"]
    line = run_tiny(tiny_root, "cesm_atm_2d.transform")
    assert line["correct"] is True
    assert set(line["metrics"]) == {"compress_gbps", "decompress_gbps", "ratio", "setup_s"}
    after = {p: p.read_bytes() for p in before}
    assert after == before  # no file that was there was edited


def test_unknown_workload_is_refused(tiny_root):
    with pytest.raises(SpecError, match="unknown workload"):
        load_cell("no_such.cell", root=tiny_root, bench_dir=tiny_root / "chipbench")
