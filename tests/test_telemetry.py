"""Tests for the telemetry spine (``repro.core.telemetry``).

Covers the tracing core (span nesting, deterministic merge of parallel
worker span trees, disabled-path no-op semantics), the streaming histogram
(percentile accuracy against a numpy reference within the bucket-width
bound), the pinned selection-decision record schema (every multi-candidate
engine must emit schema-valid records, from the live trace AND recovered
from the blob alone via ``explain``), the metrics registry / Prometheus
exposition, and the structured key=value logger.
"""
import concurrent.futures as cf
import json
import logging
import threading

import numpy as np
import pytest

from repro.core import (
    CompressionConfig,
    ErrorBoundMode,
    decompress,
    sz3_auto,
    sz3_chunked,
    sz3_fast,
    sz3_hybrid,
    sz3_lorenzo,
    sz3_quality,
    telemetry,
)


def _smooth(n, seed=0):
    rng = np.random.default_rng(seed)
    return np.cumsum(rng.standard_normal(n)).astype(np.float32)


REL3 = CompressionConfig(mode=ErrorBoundMode.REL, eb=1e-3)


# ---------------------------------------------------------------------------
# span nesting + deterministic merge
# ---------------------------------------------------------------------------

def test_span_nesting_tree():
    with telemetry.trace("t") as tr:
        with telemetry.span("outer"):
            with telemetry.span("inner", bytes=4):
                pass
            with telemetry.span("inner2"):
                pass
    (outer,) = tr.root.children
    assert outer.name == "outer"
    assert [c.name for c in outer.children] == ["inner", "inner2"]
    assert outer.children[0].attrs["bytes"] == 4
    assert outer.seconds >= sum(c.seconds for c in outer.children) >= 0.0


def test_parallel_worker_spans_merge_deterministically():
    """Worker-thread spans land under the root and serialize in ``order``
    attr order, independent of completion order."""

    def work(i):
        with telemetry.span("chunk", order=i):
            with telemetry.span("predict"):
                pass
        return i

    trees = []
    for attempt in range(3):
        with telemetry.trace("t") as tr:
            with cf.ThreadPoolExecutor(max_workers=4) as pool:
                # reversed submission order: completion order != index order
                list(pool.map(telemetry.propagate(work), range(8)))
        trees.append(tr.to_dict()["spans"])
    orders = [s["attrs"]["order"] for s in trees[0]]
    assert orders == list(range(8))
    names = [s["name"] for s in trees[0]]
    assert names == ["chunk"] * 8
    # structurally identical across runs (timings differ, structure must not)
    def strip(spans):
        return [
            {
                "name": s["name"],
                "attrs": s.get("attrs"),
                "children": strip(s.get("children", [])),
            }
            for s in spans
        ]
    assert strip(trees[0]) == strip(trees[1]) == strip(trees[2])


def test_contextvar_does_not_leak_without_propagate():
    """A worker task NOT wrapped in propagate() records nothing — the trace
    is context-scoped, not global."""
    def work(_):
        telemetry.count("leaked")
        with telemetry.span("leaked_span"):
            pass

    with telemetry.trace("t") as tr:
        with cf.ThreadPoolExecutor(max_workers=2) as pool:
            list(pool.map(work, range(4)))
    assert tr.counters == {}
    assert tr.root.children == []


def test_nested_traces_innermost_wins():
    with telemetry.trace("outer") as outer:
        telemetry.count("outer_only")
        with telemetry.trace("inner") as inner:
            telemetry.count("inner_only")
    assert "inner_only" in inner.counters
    assert "inner_only" not in outer.counters
    assert "outer_only" in outer.counters


# ---------------------------------------------------------------------------
# disabled-path no-op semantics
# ---------------------------------------------------------------------------

def test_disabled_path_is_noop():
    assert telemetry.current() is None
    assert not telemetry.enabled()
    s = telemetry.span("predict", bytes=10)
    with s as sp:
        sp.set(extra=1)  # must not raise
    # the no-op span is a shared singleton: nothing allocated, nothing kept
    assert telemetry.span("huffman") is s
    telemetry.count("x")
    telemetry.observe("y", 1.0)
    telemetry.record_decision(telemetry.make_decision("e", "w"))
    assert telemetry.current() is None


def test_untraced_compress_deterministic_and_traced_roundtrips():
    """With no trace active the selection info is never computed and the
    container is byte-identical run to run (the pinned frame-stream identity
    relies on this); under a trace, ``sel`` entries embed in the chunk table
    (bytes may differ) but the reconstruction must stay identical."""
    data = _smooth(1 << 14)
    comp = sz3_chunked(chunk_bytes=1 << 14)
    plain = comp.compress(data, REL3).blob
    assert comp.compress(data, REL3).blob == plain
    with telemetry.trace("t"):
        traced = comp.compress(data, REL3).blob
    np.testing.assert_array_equal(decompress(plain), decompress(traced))
    # untraced containers carry no sel entries — nothing paid when off
    from repro.core import parse_header

    header, _ = parse_header(plain)
    assert all("sel" not in c for c in header["chunks"])
    traced_header, _ = parse_header(traced)
    assert any("sel" in c for c in traced_header["chunks"])


def test_serial_parallel_traces_structurally_identical():
    data = _smooth(1 << 15)
    trees = []
    blobs = []
    for workers in (1, 4):
        comp = sz3_chunked(chunk_bytes=1 << 13, workers=workers)
        with telemetry.trace("t") as tr:
            blobs.append(comp.compress(data, REL3).blob)
        trees.append(tr.to_dict()["spans"])

    def strip(spans):
        return [
            {"name": s["name"], "children": strip(s.get("children", []))}
            for s in spans
        ]

    assert blobs[0] == blobs[1]
    assert strip(trees[0]) == strip(trees[1])


# ---------------------------------------------------------------------------
# annotation factory: spans on another tracer's clock
# ---------------------------------------------------------------------------

class _Annotator:
    """Records the enter/exit of every annotation it hands out, per thread."""

    def __init__(self):
        self.events = []
        self.calls = 0

    def __call__(self, name):
        self.calls += 1
        outer = self

        class _Scope:
            def __enter__(self):
                outer.events.append(("enter", name, threading.get_ident()))

            def __exit__(self, *exc):
                outer.events.append(("exit", name, threading.get_ident()))

        return _Scope()


def _balanced(events):
    """True when each thread's enter/exit events nest like brackets."""
    stacks = {}
    for kind, name, thread in events:
        stack = stacks.setdefault(thread, [])
        if kind == "enter":
            stack.append(name)
        elif not stack or stack.pop() != name:
            return False
    return all(not s for s in stacks.values())


def test_annotator_sees_spans_in_nesting_order():
    ann = _Annotator()

    def work(i):
        with telemetry.span("chunk", order=i):
            with telemetry.span("huffman"):
                pass

    with telemetry.trace("t", annotate=ann):
        with telemetry.span("outer"):
            with telemetry.span("inner"):
                pass
        with cf.ThreadPoolExecutor(max_workers=2) as pool:
            list(pool.map(telemetry.propagate(work), range(2)))
    main = threading.get_ident()
    assert [(k, n) for k, n, t in ann.events if t == main] == [
        ("enter", "sz3.outer"), ("enter", "sz3.inner"),
        ("exit", "sz3.inner"), ("exit", "sz3.outer"),
    ]
    workers = [e for e in ann.events if e[2] != main]
    assert len(workers) == 8  # two chunks, each chunk > huffman, in a worker
    assert _balanced(ann.events)
    for thread in {t for _, _, t in workers}:
        names = [n for _, n, t in workers if t == thread]
        assert names[:2] == ["sz3.chunk", "sz3.huffman"]


def test_annotator_unused_without_trace():
    ann = _Annotator()
    with telemetry.trace("t", annotate=ann):
        pass
    with telemetry.span("predict") as sp:  # no trace active any more
        assert sp is telemetry.span("huffman")
    assert ann.calls == 0


@pytest.mark.parametrize("predictor", ["auto", "lorenzo"])
def test_annotated_trace_leaves_blobs_alone(predictor):
    from repro.codec import Sz3Codec

    data = np.cumsum(
        np.random.default_rng(11).standard_normal((96, 160)).astype(np.float32), 0
    )
    codec = Sz3Codec(eb_mode="rel", eb_rel=1e-3, predictor=predictor)
    with telemetry.trace("t"):
        plain = codec.encode(data)
    with telemetry.trace("t", annotate=_Annotator()):
        annotated = codec.encode(data)
    assert annotated == plain


@pytest.mark.parametrize("predictor", ["auto", "lorenzo"])
def test_codec_emits_host_stage_spans(predictor):
    import jax.numpy as jnp

    from repro.codec import Sz3Codec

    data = np.cumsum(
        np.random.default_rng(12).standard_normal((96, 160)).astype(np.float32), 0
    )
    codec = Sz3Codec(eb_mode="rel", eb_rel=1e-3, predictor=predictor)
    with telemetry.trace("t") as tr:
        blob = codec.encode(jnp.asarray(data))
    totals = tr.stage_totals()
    for stage in ("to_host", "stats", "pack", "integrity"):
        assert totals[stage]["calls"] >= 1, stage
    assert totals["to_host"]["calls"] == 1
    assert totals["to_host"]["bytes"] == data.nbytes
    (pack,) = [s for s in tr.root.children if s.name == "pack"]
    assert [c.name for c in pack.children] == ["integrity"]
    with telemetry.trace("t") as tr:
        out = codec.decode(blob)
    assert out.shape == data.shape
    names = set(tr.stage_totals())
    assert "huffman" in names and "to_host" not in names
    if predictor == "auto":
        chunks = [s for s in tr.root.children if s.name == "chunk"]
        assert [s.attrs["order"] for s in chunks] == list(range(len(chunks)))
        assert chunks and "unpack" in names


def test_device_lorenzo_route_emits_verify():
    from repro.core import SZ3Compressor
    from repro.core.predictors import LorenzoPredictor

    x = np.cumsum(
        np.random.default_rng(13).standard_normal((64, 256)).astype(np.float32), -1
    )
    comp = SZ3Compressor(predictor=LorenzoPredictor(device="force"))
    with telemetry.trace("t") as tr:
        comp.compress(x, CompressionConfig(mode=ErrorBoundMode.ABS, eb=1e-3))
    (predict,) = [s for s in tr.root.children if s.name == "predict"]
    assert [c.name for c in predict.children] == ["device_transfer", "verify"]
    (verify,) = [c for c in predict.children if c.name == "verify"]
    assert verify.children == []  # its device decode is no device_transfer
    assert verify.attrs["bytes"] == x.nbytes


# ---------------------------------------------------------------------------
# streaming histogram
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dist", ["lognormal", "uniform", "exponential"])
def test_histogram_percentiles_vs_numpy(dist):
    rng = np.random.default_rng(7)
    vals = {
        "lognormal": rng.lognormal(0.0, 2.0, 20_000),
        "uniform": rng.uniform(1e-3, 1e3, 20_000),
        "exponential": rng.exponential(5.0, 20_000),
    }[dist]
    h = telemetry.StreamingHistogram()
    for v in vals:
        h.observe(v)
    # bucket width is 2**(1/16)-1 (~4.4%) relative — assert within 5%
    for q in (0.5, 0.9, 0.99):
        ref = float(np.quantile(vals, q))
        got = h.quantile(q)
        assert abs(got - ref) / ref < 0.05, (q, got, ref)
    snap = h.snapshot()
    assert snap["count"] == vals.size
    assert snap["min"] == pytest.approx(vals.min())
    assert snap["max"] == pytest.approx(vals.max())
    assert snap["sum"] == pytest.approx(vals.sum(), rel=1e-9)


def test_histogram_zero_and_negative_bucket():
    h = telemetry.StreamingHistogram()
    for v in [0.0, -1.0, 0.0, 5.0]:
        h.observe(v)
    assert h.n == 4
    assert h.quantile(0.0) <= 0.0
    assert h.quantile(1.0) == pytest.approx(5.0, rel=0.05)


def test_histogram_merge_equals_combined():
    rng = np.random.default_rng(11)
    a, b = rng.lognormal(0, 1, 5000), rng.lognormal(1, 1, 5000)
    ha, hb, hc = (telemetry.StreamingHistogram() for _ in range(3))
    for v in a:
        ha.observe(v)
        hc.observe(v)
    for v in b:
        hb.observe(v)
        hc.observe(v)
    ha.merge(hb)
    assert ha.n == hc.n
    assert ha.quantile(0.5) == pytest.approx(hc.quantile(0.5))
    assert ha.snapshot()["max"] == hc.snapshot()["max"]


# ---------------------------------------------------------------------------
# pinned decision-record schema, every engine
# ---------------------------------------------------------------------------

def _engines():
    rng = np.random.default_rng(3)
    smooth = np.cumsum(rng.standard_normal((64, 256)).astype(np.float32), 0)
    return [
        ("sz3_chunked", sz3_chunked(chunk_bytes=1 << 14), smooth, REL3),
        ("sz3_auto", sz3_auto(chunk_bytes=1 << 14), smooth, REL3),
        ("sz3_hybrid", sz3_hybrid(), smooth, REL3),
        ("sz3_fast", sz3_fast(), smooth,
         CompressionConfig(mode=ErrorBoundMode.ABS, eb=1e-3)),
    ]


@pytest.mark.parametrize("name,comp,data,conf", _engines(),
                         ids=[e[0] for e in _engines()])
def test_decision_records_trace_and_blob(name, comp, data, conf):
    with telemetry.trace("t") as tr:
        res = comp.compress(data, conf)
    assert tr.decisions, f"{name}: no decision records in trace"
    for rec in tr.decisions:
        telemetry.validate_decision(rec)
        assert rec["engine"] == name
        assert rec["winner"] in rec["candidates"]
        assert json.loads(json.dumps(rec)) == rec  # JSON-serializable
    # recovered from the container alone (no trace): same engine + winners
    from_blob = telemetry.explain(res.blob)
    assert from_blob, f"{name}: explain(blob) returned nothing"
    for rec in from_blob:
        telemetry.validate_decision(rec)
        assert rec["engine"] == name
    assert [r["winner"] for r in from_blob] == [
        r["winner"] for r in tr.decisions
    ]


def test_quality_decision_records():
    data = np.cumsum(
        np.random.default_rng(5).standard_normal((48, 128)).astype(np.float32), 0
    )
    q = sz3_quality(target_psnr=55.0, chunk_bytes=1 << 14)
    with telemetry.trace("t") as tr:
        res = q.compress(data)
    assert tr.decisions
    for rec in tr.decisions:
        telemetry.validate_decision(rec)
        assert rec["engine"] == "sz3_quality"
        # achieved-quality record rides along in extra
        assert rec["extra"] and "quality" in rec["extra"]
    from_blob = telemetry.explain(res.blob)
    assert from_blob and all(
        r["engine"] == "sz3_quality" for r in from_blob
    )
    for rec in from_blob:
        telemetry.validate_decision(rec)


def test_explain_single_pipeline_blob():
    data = _smooth(4096)
    res = sz3_lorenzo().compress(data, REL3)
    recs = telemetry.explain(res.blob)
    assert len(recs) == 1
    telemetry.validate_decision(recs[0])
    assert recs[0]["scope"] == "array"


def test_validate_decision_rejects_bad_records():
    good = telemetry.make_decision("e", "w", candidates=["w"])
    telemetry.validate_decision(good)
    with pytest.raises(ValueError):
        telemetry.validate_decision({**good, "unknown_field": 1})
    with pytest.raises(ValueError):
        bad = dict(good)
        del bad["engine"]
        telemetry.validate_decision(bad)
    with pytest.raises(ValueError):
        telemetry.validate_decision({**good, "winner": "not-a-candidate"})


def test_trial_runoffs_do_not_pollute_decision_stream():
    """The chunked contest trial-compresses candidates and the winning
    sub-engine may itself be multi-candidate (hybrid inside a chunk):
    exactly one record per chunk, all from the outer engine."""
    data = _smooth(1 << 15)
    comp = sz3_auto(chunk_bytes=1 << 13)
    with telemetry.trace("t") as tr:
        res = comp.compress(data, REL3)
    n_chunks = len(
        [r for r in telemetry.explain(res.blob) if r["scope"] == "chunk"]
    )
    assert len(tr.decisions) == n_chunks
    assert {r["engine"] for r in tr.decisions} == {"sz3_auto"}
    assert [r["index"] for r in tr.decisions] == list(range(n_chunks))


# ---------------------------------------------------------------------------
# stage spans on the engine paths + summary rendering
# ---------------------------------------------------------------------------

def test_compress_emits_stage_spans():
    data = _smooth(1 << 14)
    with telemetry.trace("t") as tr:
        sz3_chunked(chunk_bytes=1 << 13).compress(data, REL3)
    totals = tr.stage_totals()
    for stage in ("chunk", "select", "predict", "huffman", "lossless",
                  "integrity"):
        assert stage in totals, f"missing stage span: {stage}"
        assert totals[stage]["calls"] >= 1
    text = telemetry.trace_summary(tr)
    assert "predict" in text and "calls" in text


def test_trace_json_roundtrip(tmp_path):
    data = _smooth(1 << 13)
    with telemetry.trace("t") as tr:
        sz3_fast().compress(
            data, CompressionConfig(mode=ErrorBoundMode.ABS, eb=1e-3)
        )
    p = tmp_path / "trace.json"
    tr.save_json(str(p))
    doc = json.loads(p.read_text())
    assert doc["name"] == "t"
    assert doc["decisions"] and doc["spans"]
    assert doc["seconds"] >= 0


# ---------------------------------------------------------------------------
# metrics registry + Prometheus exposition
# ---------------------------------------------------------------------------

def test_metrics_registry_and_prometheus_text():
    telemetry.reset_metrics()
    try:
        telemetry.metric_count("sz3_requests_total")
        telemetry.metric_count("sz3_requests_total", 2)
        for v in (0.01, 0.02, 0.04):
            telemetry.metric_observe("sz3_decode_step_seconds", v)
        text = telemetry.prometheus_text()
        assert 'sz3_requests_total 3' in text
        assert "# TYPE sz3_requests_total counter" in text
        assert "# TYPE sz3_decode_step_seconds summary" in text
        assert 'sz3_decode_step_seconds{quantile="0.5"}' in text
        assert "sz3_decode_step_seconds_count 3" in text
    finally:
        telemetry.reset_metrics()


# ---------------------------------------------------------------------------
# structured logger
# ---------------------------------------------------------------------------

class _ListHandler(logging.Handler):
    def __init__(self):
        super().__init__(level=logging.DEBUG)
        self.records = []

    def emit(self, record):
        self.records.append(record)


def _capture(name):
    """The telemetry namespace manages its own handler (propagate=False so
    app-level root handlers never double-print), so capture by attaching a
    handler to the named logger directly rather than via caplog/root."""
    log = telemetry.get_logger(name)
    h = _ListHandler()
    py = logging.getLogger(f"repro.telemetry.{name}")
    old = py.level
    py.addHandler(h)
    py.setLevel(logging.DEBUG)
    return log, h, (py, old)


def test_kv_logger_format():
    log, h, (py, old) = _capture("testmod")
    try:
        log.info("thing_done", n=3, rate=1234.5678, note="two words")
    finally:
        py.removeHandler(h)
        py.setLevel(old)
    assert len(h.records) == 1
    msg = h.records[0].getMessage()
    assert msg.startswith("thing_done ")
    assert "n=3" in msg
    assert "rate=1234.57" in msg
    assert 'note="two words"' in msg


def test_kv_logger_single_record_per_event():
    """One event == one logging call == one atomic line (the fix for
    interleaved multi-print status output from worker threads)."""
    log, h, (py, old) = _capture("atomic")
    try:
        log.info("ev", a=1, b=2, c=3)
    finally:
        py.removeHandler(h)
        py.setLevel(old)
    assert len(h.records) == 1
    assert "\n" not in h.records[0].getMessage()
