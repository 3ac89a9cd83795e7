"""The program's stage spans (``sz3.<span>`` on the profiler's clock,
``lib/stages.py``) and the per-layer readers of the program's host stages."""
from __future__ import annotations

import json
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from conftest import BENCH_DIR
from lib import devtrace
from lib import stages as stages_lib
from lib.spec import load_module, peaks_for

DATA = Path(__file__).resolve().parent / "data"


def _ev(start, dur, name):
    return NS(start_ns=start, duration_ns=dur, name=name)


def _planes(ops, host, dev=0):
    return [
        NS(name="/host:CPU", lines=[NS(name="python", events=host)]),
        NS(name=f"/device:TPU:{dev}", lines=[NS(name="XLA Ops", events=ops)]),
    ]


def _reduce(planes):
    t = devtrace.reduce(planes, [0])
    return t, stages_lib.read_stages(planes, t.window)


def _stage_planes(extra_lines=()):
    """An encode with a device route and a decode of one chunk, on 0-100 ns."""
    host = [_ev(0, 100, devtrace.WINDOW), _ev(10, 40, "chipbench.encode"),
            _ev(10, 5, "sz3.to_host"), _ev(15, 25, "sz3.predict"),
            _ev(15, 10, "sz3.device_transfer"), _ev(25, 10, "sz3.verify"),
            _ev(50, 40, "chipbench.decode"), _ev(50, 30, "sz3.chunk"),
            _ev(55, 15, "sz3.huffman")]
    ops = [_ev(18, 4, "%k = f32[8] custom-call()"), _ev(60, 2, "%c = f32[8] copy()")]
    planes = _planes(ops, host)
    planes[0].lines.extend(NS(name=f"worker{i}", events=evs)
                           for i, evs in enumerate(extra_lines))
    return planes


def test_idle_by_stage_charges_the_innermost_path():
    t, st = _reduce(_stage_planes())
    assert [e.name for e in st][:2] == ["to_host", "predict"]
    stages = dict(map(tuple, stages_lib.idle_by_stage(t, st, 100)))
    want = {"encode/to_host": 5, "encode/predict/device_transfer": 6,
            "encode/predict/verify": 10, "encode/predict": 5, "encode/-": 10,
            # the chunk's own time is in no stage
            "decode/huffman": 13, "decode/-": 25, devtrace.BETWEEN + "/-": 20}
    assert stages == {k: pytest.approx(v * 1e-9) for k, v in want.items()}
    assert sum(stages.values()) == pytest.approx(t.window_s - t.busy_s)
    assert stages_lib.idle_by_stage(t, st, 2) == [["decode/-", pytest.approx(25e-9)],
                                  [devtrace.BETWEEN + "/-", pytest.approx(20e-9)]]
    # the benchmark's calls read as before
    gaps = dict(map(tuple, t.idle_by_host(10)))
    assert gaps == {"encode": pytest.approx(36e-9), "decode": pytest.approx(38e-9),
                    devtrace.BETWEEN: pytest.approx(20e-9)}


def test_idle_by_stage_splits_overlapping_threads():
    worker = [_ev(30, 10, "sz3.huffman"), _ev(40, 5, "sz3.chunk"),
              _ev(41, 2, "sz3.lossless")]
    t, st = _reduce(_stage_planes([worker]))
    stages = dict(map(tuple, stages_lib.idle_by_stage(t, st, 100)))
    assert stages["encode/predict/verify"] == pytest.approx(7.5e-9)  # 25-30, half of 30-35
    assert stages["encode/predict"] == pytest.approx(2.5e-9)  # half of 35-40
    assert stages["encode/huffman"] == pytest.approx(5e-9)
    assert stages["encode/lossless"] == pytest.approx(2e-9)
    assert stages["encode/-"] == pytest.approx(8e-9)  # 40-50 less the lossless
    assert sum(stages.values()) == pytest.approx(t.window_s - t.busy_s)


def test_busy_share_reads_the_chip_inside_a_stage():
    t, st = _reduce(_stage_planes())
    assert stages_lib.busy_share(t, st, "device_transfer", "encode") == pytest.approx(0.4)
    assert stages_lib.busy_share(t, st, "huffman", "decode") == pytest.approx(2 / 15)
    assert stages_lib.busy_share(t, st, "huffman", "encode") is None
    assert stages_lib.busy_share(t, [], "device_transfer", "encode") is None


def _span(name, seconds, children=(), **attrs):
    s = {"name": name, "seconds": seconds}
    if attrs:
        s["attrs"] = attrs
    if children:
        s["children"] = list(children)
    return s


def _spans():
    """One encode and one decode of the auto cell's shape."""
    integrity = _span("integrity", 0.004, bytes=4_000_000)
    chunk = _span("chunk", 0.4, [
        _span("select", 0.07),
        _span("stats", 0.01, bytes=26_000_000),
        _span("predict", 0.1, [_span("device_transfer", 0.012, bytes=26_000_000),
                               _span("verify", 0.05, bytes=26_000_000)]),
        _span("huffman", 0.15, [_span("integrity", 0.001)]),
        _span("lossless", 0.02),
        _span("pack", 0.006, [integrity], bytes=4_000_000),
    ])
    enc = _span("encode", 0.46, [_span("to_host", 0.013, bytes=26_000_000), chunk])
    dec = _span("decode", 0.35, [
        _span("chunk", 0.3, [_span("integrity", 0.003), _span("lossless", 0.01),
                             _span("huffman", 0.22), _span("predict", 0.02)]),
        _span("unpack", 0.01),
    ])
    return [enc, dec]


def _without(spans, names):
    """The tree with the spans named ``names`` taken out, their children
    in their place."""
    out = []
    for s in spans:
        kids = _without(s.get("children", []), names)
        if s["name"] in names:
            out.extend(kids)
        else:
            out.append({**s, "children": kids})
    return out


def _older_spans():
    """:func:`_spans` as the program wrote it before ``to_host``, ``stats``,
    ``verify``, ``pack``, ``unpack`` and decode's ``chunk`` spans."""
    enc, dec = _without(_spans(), {"to_host", "stats", "verify", "pack", "unpack"})
    return [enc, {**dec, "children": _without(dec["children"], {"chunk"})}]


OLD_SPAN_READERS = ["select.gbps", "entropy.gbps", "entropy_decode.gbps",
                    "device_call.gbps"]


@pytest.mark.parametrize("name", OLD_SPAN_READERS)
def test_new_spans_leave_the_old_readers_alone(name):
    reader = load_module(BENCH_DIR / "metrics" / f"{name}.py", name)
    work = {"traced_encode_bytes": 26_000_000, "traced_decode_bytes": 26_000_000}
    old = reader.read(NS(spans=_older_spans(), work=work))
    assert old is not None
    assert reader.read(NS(spans=_spans(), work=work)) == old


def test_host_stage_readers():
    to_host = load_module(BENCH_DIR / "metrics" / "to_host.gbps.py", "to_host")
    verify = load_module(BENCH_DIR / "metrics" / "verify.gbps.py", "verify")
    work = {"traced_encode_bytes": 2 * 26_000_000}
    ctx = NS(spans=_spans() * 2, work=work)
    assert to_host.read(ctx) == pytest.approx(26e6 / 1e9 / 0.013)
    assert verify.read(ctx) == pytest.approx(26e6 / 1e9 / 0.05)
    # a program that has neither span reports nothing
    ctx = NS(spans=_older_spans(), work=work)
    assert to_host.read(ctx) is None and verify.read(ctx) is None


def test_recorded_chip_trace_reads_as_before():
    """A window recorded with no program spans on the profiler: its idle
    gaps and device readings as first reduced, to the last digit, and every
    idle second charged to no stage."""
    t, st = stages_lib.reduce_file(str(DATA / "lorenzo_window.xplane.pb"), [0])
    assert st == []
    assert t.idle_by_host(10) == [["encode", 24.711177338], ["decode", 5.433204357],
                                  [devtrace.BETWEEN, 0.001086018]]
    assert stages_lib.idle_by_stage(t, st, 10) == [
        [f"{k}/-", pytest.approx(v, rel=1e-12)] for k, v in t.idle_by_host(10)]
    k = json.loads((BENCH_DIR / "kernels" / "lorenzo.json").read_text())
    ctx = NS(device=t, work={"traced_encode_elements": 12 * 1800 * 3600},
             kernel=lambda name: k, peaks=lambda: peaks_for("TPU v5 lite"))
    roof = load_module(BENCH_DIR / "metrics" / "lorenzo_roofline.py", "roof")
    idle = load_module(BENCH_DIR / "metrics" / "device_idle.codec.py", "idle")
    assert roof.read(ctx) == 61.228276068281055
    assert idle.read(ctx) == 99.97459648934317


def test_recorded_window_with_program_spans():
    """cesm_atm_2d.lorenzo on a v5e with the trace's annotation factory set
    to ``jax.profiler.TraceAnnotation``: two passes of the pool."""
    t, st = stages_lib.reduce_file(str(DATA / "lorenzo_stages.xplane.pb"), [0])
    marks = [(e.start, e.end) for e in t.host]
    assert sorted(e.name for e in t.host) == ["chipbench.decode"] * 16 + ["chipbench.encode"] * 16
    assert {e.name for e in st} == {"to_host", "stats", "predict", "device_transfer",
                                    "verify", "huffman", "lossless", "pack", "integrity"}
    # nested inside the benchmark's calls, which are there once each
    assert all(any(a <= e.start and e.end <= b for a, b in marks) for e in st)
    assert not {"encode", "decode"} & {e.name for e in st}
    stages = dict(map(tuple, stages_lib.idle_by_stage(t, st, 100)))
    assert sum(stages.values()) == pytest.approx(t.window_s - t.busy_s, rel=1e-9)
    assert list(stages)[:2] == ["encode/huffman", "encode/predict/verify"]
    unnamed = sum(v for k, v in stages.items() if k.endswith("/-"))
    assert unnamed < 0.05 * t.window_s
    busy = stages_lib.busy_share(t, st, "device_transfer", "encode")
    assert 0 < busy < 0.05
