"""Reduce a JAX profiler trace (``*.xplane.pb``) to device busy time, idle
gaps and attributed kernel time.

Layout of a TPU trace as JAX 0.9 writes it: one plane per chip named
``/device:TPU:<id>`` with ``XLA Ops`` and ``Async XLA Ops`` lines (one event
per HLO op as it ran) and an ``XLA Modules`` line (one event per jitted program, named
``jit_<function>(<fingerprint>)``); host threads are lines of ``/host:CPU``,
where ``jax.profiler.TraceAnnotation`` scopes appear.  Device and host events
share one clock (nanoseconds from the start of the session).

The traced window is the host annotation ``chipbench.window``; the
benchmark's calls into the system are annotated ``chipbench.<what>``, and an
idle gap on the device is charged to the call the host was in.
"""
from __future__ import annotations

from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Sequence, Tuple

WINDOW = "chipbench.window"
MARK_PREFIX = "chipbench."
#: idle time while the host was in none of the benchmark's calls
BETWEEN = "between calls"

Interval = Tuple[float, float]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint cover of the given intervals."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def length(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def subtract(base: Sequence[Interval], cut: Sequence[Interval]) -> List[Interval]:
    """``base`` less ``cut``; both sorted and disjoint (as ``union`` gives)."""
    out, j = [], 0
    for a, b in base:
        while j < len(cut) and cut[j][1] <= a:
            j += 1
        k, start = j, a
        while k < len(cut) and cut[k][0] < b:
            if cut[k][0] > start:
                out.append((start, cut[k][0]))
            start = max(start, cut[k][1])
            k += 1
        if start < b:
            out.append((start, b))
    return out


def intersect(x: Sequence[Interval], y: Sequence[Interval]) -> List[Interval]:
    """Intersection of two sorted, disjoint interval lists."""
    out, i, j = [], 0, 0
    while i < len(x) and j < len(y):
        a, b = max(x[i][0], y[j][0]), min(x[i][1], y[j][1])
        if a < b:
            out.append((a, b))
        if x[i][1] < y[j][1]:
            i += 1
        else:
            j += 1
    return out


def op_name(hlo_text: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``fusion.3``."""
    return hlo_text.split(" = ", 1)[0].lstrip("%")


@dataclass
class Event:
    start: float  # ns
    end: float
    name: str


@dataclass
class DeviceTrace:
    """The reduced profile of one traced window (times in seconds)."""

    window: Interval
    ops: Dict[int, List[Event]]
    modules: Dict[int, List[Event]]
    host: List[Event] = field(default_factory=list)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def _mean(self, per_device: Dict[int, float]) -> float:
        return sum(per_device.values()) / max(1, len(per_device))

    def busy(self, dev: int) -> List[Interval]:
        return union((e.start, e.end) for e in self.ops[dev])

    @property
    def busy_s(self) -> float:
        return self._mean({d: length(self.busy(d)) for d in self.ops}) / 1e9

    def module_seconds(self, prefixes: Sequence[str]) -> float:
        """Device seconds of the ops run inside programs whose name starts
        with one of ``prefixes``, averaged over the chips."""
        per = {}
        for d in self.ops:
            mods = union((m.start, m.end) for m in self.modules[d]
                         if m.name.startswith(tuple(prefixes)))
            per[d] = length(intersect(self.busy(d), mods))
        return self._mean(per) / 1e9

    def top_ops(self, n: int) -> List[List]:
        """The ``n`` op names that took most device time, averaged over
        chips: ``[[name, seconds], ...]``."""
        tot: Dict[str, float] = defaultdict(float)
        for evs in self.ops.values():
            for e in evs:
                tot[op_name(e.name)] += (e.end - e.start) / 1e9 / len(self.ops)
        return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]

    def idle_by_host(self, n: int) -> List[List]:
        """Idle device time, averaged over chips, by the benchmark call the
        host was in: ``[[call, seconds], ...]``, largest first."""
        marks = defaultdict(list)
        for e in self.host:
            marks[e.name[len(MARK_PREFIX):]].append((e.start, e.end))
        marks = {k: union(v) for k, v in marks.items()}
        tot: Dict[str, float] = defaultdict(float)
        for d in self.ops:
            idle = subtract([self.window], self.busy(d))
            rest = idle
            for name, iv in marks.items():
                tot[name] += length(intersect(idle, iv)) / 1e9 / len(self.ops)
                rest = subtract(rest, iv)
            tot[BETWEEN] += length(rest) / 1e9 / len(self.ops)
        return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]
                if v > 0]


def reduce(planes, device_ids: Sequence[int]) -> DeviceTrace:
    """Reduce the planes of a ``ProfileData`` (or anything shaped alike)."""
    planes = list(planes)
    window, host = None, []
    for pl in planes:
        if not pl.name.startswith("/host:"):
            continue
        for ln in pl.lines:
            for e in ln.events:
                if e.name == WINDOW:
                    window = (e.start_ns, e.start_ns + e.duration_ns)
                elif e.name.startswith(MARK_PREFIX):
                    host.append(Event(e.start_ns, e.start_ns + e.duration_ns, e.name))
    if window is None:
        raise ValueError(f"no {WINDOW!r} annotation in the trace")
    lo, hi = window
    ops, modules = {}, {}
    by_name = {pl.name: pl for pl in planes}
    for d in device_ids:
        pl = by_name.get(f"/device:TPU:{d}")
        if pl is None:
            raise ValueError(f"no plane for /device:TPU:{d} in the trace")
        ops[d], modules[d] = [], []
        for ln in pl.lines:
            dest = {"XLA Ops": ops, "Async XLA Ops": ops, "XLA Modules": modules}.get(ln.name)
            if dest is None:
                continue
            for e in ln.events:
                a, b = max(e.start_ns, lo), min(e.start_ns + e.duration_ns, hi)
                if b > a:
                    dest[d].append(Event(a, b, e.name))
    host = [Event(max(e.start, lo), min(e.end, hi), e.name) for e in host
            if e.end > lo and e.start < hi]
    return DeviceTrace(window=window, ops=ops, modules=modules, host=host)


def reduce_file(path: str, device_ids: Sequence[int]) -> DeviceTrace:
    from jax.profiler import ProfileData

    return reduce(ProfileData.from_file(path).planes, device_ids)
