"""What every cell shares: the window, its spans, the profile and the result.

A runner (``runners/<name>.py``) gets a :class:`Harness`, makes its data,
warms up, calls :meth:`Harness.begin_window`, runs its loop inside
``harness.span(...)`` scopes, calls :meth:`Harness.end_window` and then
checks what the window produced.  It returns a :class:`RunResult`; the
harness turns that into the result line.
"""
from __future__ import annotations

import contextlib
import glob
import math
import os
import shutil
import tempfile
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

from . import devtrace
from .spec import Cell, peaks_for

#: JAX records this for every program it lowers, whether the compiled
#: program then comes from the persistent cache or from the compiler
_COMPILE_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


@dataclass
class Check:
    """One number compared with its limit; ``value <= limit`` is correct."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclass
class RunResult:
    e2e: Dict[str, float]
    checks: List[Check]
    attempted: int
    failed: int
    #: counts of the work done while traced, for the per-layer readers
    work: Dict[str, float] = field(default_factory=dict)


class Harness:
    def __init__(self, cell: Cell, *, seed: int, seconds: float, trace: bool,
                 devices, t_start: float, trace_dir: Optional[str] = None):
        self.cell = cell
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.devices = list(devices)
        self.t_start = t_start
        self.setup_s: Optional[float] = None
        self.window_compiles = 0
        self.memory_peak_bytes: Optional[int] = None
        self.spans: List[Dict[str, Any]] = []
        self.device_trace: Optional[devtrace.DeviceTrace] = None
        self._keep_trace = trace_dir is not None
        self._trace_dir = trace_dir
        self._tel = None
        self._tel_scope = None
        self._profile_scope = None

    # -- compile counter -----------------------------------------------------
    def _on_duration(self, name: str, secs: float, **kw) -> None:
        if name == _COMPILE_EVENT:
            self.window_compiles += 1

    # -- the window ----------------------------------------------------------
    def begin_window(self) -> float:
        """End set-up; returns the window's deadline on ``perf_counter``."""
        from repro.core import telemetry

        if self.trace:
            self._tel_scope = telemetry.trace("chipbench")
            self._tel = self._tel_scope.__enter__()
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        now = time.perf_counter()
        self.setup_s = now - self.t_start
        return now + self.seconds

    def end_window(self) -> None:
        """Close the window and read the device memory peak, before any
        reference runs on the device."""
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        if self._tel_scope is not None:
            self._tel_scope.__exit__(None, None, None)
            self.spans = self._tel.to_dict()["spans"]
            self._tel_scope = None
        self.stop_profile()
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in self.devices]
        peaks = [p for p in peaks if p is not None]
        self.memory_peak_bytes = max(peaks) if peaks else None

    def span(self, name: str):
        """Mark one call into the system: a profiler annotation and a
        telemetry span, in traced runs only."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax
        from repro.core import telemetry

        stack = contextlib.ExitStack()
        stack.enter_context(jax.profiler.TraceAnnotation(devtrace.MARK_PREFIX + name))
        stack.enter_context(telemetry.span(name))
        return stack

    # -- the device profile --------------------------------------------------
    def start_profile(self) -> None:
        """Start the device profile (traced runs only; call in set-up)."""
        if not self.trace or self._profile_scope is not None:
            return
        import jax

        if self._trace_dir is None:
            self._trace_dir = tempfile.mkdtemp(prefix="chipbench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # the benchmark's annotations suffice
        jax.profiler.start_trace(self._trace_dir, profiler_options=opts)
        self._profile_scope = jax.profiler.TraceAnnotation(devtrace.WINDOW)
        self._profile_scope.__enter__()

    def stop_profile(self) -> None:
        """Stop the device profile and reduce it.  Idempotent."""
        if self._profile_scope is None:
            return
        import jax

        self._profile_scope.__exit__(None, None, None)
        self._profile_scope = None
        jax.profiler.stop_trace()
        paths = glob.glob(os.path.join(self._trace_dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not paths:
            raise RuntimeError(f"the profiler wrote no trace under {self._trace_dir}")
        self.device_trace = devtrace.reduce_file(
            max(paths, key=os.path.getmtime), [d.id for d in self.devices])
        if not self._keep_trace:
            shutil.rmtree(self._trace_dir, ignore_errors=True)


def result_line(h: Harness, res: RunResult) -> Dict[str, Any]:
    """The last line of standard output; ``checks`` comes last."""
    cell = h.cell
    metrics: Dict[str, Dict[str, Any]] = {}
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    if not h.trace:
        values = dict(res.e2e, setup_s=h.setup_s)
        for m in cell.end_to_end:
            if m["name"] not in values:
                raise RuntimeError(f"runner reported no {m['name']!r}")
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    else:
        ctx = ReadContext(h, res)
        for m in cell.per_layer:
            value = cell.metric_reader(m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": units[m["name"]]}
    d0 = h.devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(h.devices), "memory_peak_bytes": h.memory_peak_bytes}
    line: Dict[str, Any] = {
        "correct": all(c.ok for c in res.checks) and res.failed == 0,
        "attempted": res.attempted,
        "failed": res.failed,
        "metrics": metrics,
        "device": device,
        "window_compiles": h.window_compiles,
    }
    if h.trace and h.device_trace is not None:
        dt = h.device_trace
        device["busy_s"] = dt.busy_s
        device["window_s"] = dt.window_s
        line["breakdown"] = {"device_ops": dt.top_ops(10), "idle_gaps": dt.idle_by_host(10)}
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit} for c in res.checks}
    return line


class ReadContext:
    """What a per-layer reader (``metrics/<name>.py``) may look at."""

    def __init__(self, h: Harness, res: RunResult):
        self.cell = h.cell
        self.config = h.cell.config
        self.traffic = h.cell.traffic
        self.spans = h.spans
        self.work = res.work
        self.device = h.device_trace
        self.chips = len(h.devices)
        self._kind = h.devices[0].device_kind

    def peaks(self) -> Dict[str, Any]:
        return peaks_for(self._kind, self.cell.bench_dir)

    def kernel(self, name: str) -> Dict[str, Any]:
        return self.cell.kernel(name)
