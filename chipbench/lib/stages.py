"""The program's stage spans on the profiler's clock, and the device idle
time charged to them.

A telemetry trace given ``annotate=jax.profiler.TraceAnnotation``
(``repro.core.telemetry``) writes each program span as a ``sz3.<span>``
annotation on the profiler's line of the thread that ran it, on the clock of
the device ops.  :func:`read_stages` takes those events from a profile;
:func:`idle_by_stage` charges the device's idle time to the benchmark call
the host was in (``chipbench.<call>``, as ``DeviceTrace.idle_by_host`` does)
and to the program spans open then; :func:`busy_share` says how much of the
time in one span the chip was running an op.
"""
from __future__ import annotations

from collections import defaultdict
from dataclasses import dataclass
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from . import devtrace
from .devtrace import (BETWEEN, MARK_PREFIX, DeviceTrace, Interval, intersect,
                       length, subtract, union)

STAGE_PREFIX = "sz3."
#: program spans that group stages and are left out of a stage path
GROUPING_SPANS = ("chunk",)
#: the stage path of time in no program span
NO_STAGE = "-"


@dataclass
class StageEvent:
    start: float  # ns
    end: float
    name: str  # without the prefix
    thread: int  # the host line it was on


def read_stages(planes, window: Interval) -> List[StageEvent]:
    """The ``sz3.<span>`` events of the host planes, clipped to ``window``."""
    lo, hi = window
    out, thread = [], 0
    for pl in planes:
        if not pl.name.startswith("/host:"):
            continue
        for ln in pl.lines:
            thread += 1
            for e in ln.events:
                a, b = e.start_ns, e.start_ns + e.duration_ns
                if e.name.startswith(STAGE_PREFIX) and b > lo and a < hi:
                    out.append(StageEvent(max(a, lo), min(b, hi),
                                          e.name[len(STAGE_PREFIX):], thread))
    return out


def stage_path(names: Sequence[str]) -> str:
    """The open spans' names, outermost first, grouping spans left out:
    ``predict/verify``, or ``-`` where none is left."""
    return "/".join(n for n in names if n not in GROUPING_SPANS) or NO_STAGE


def _thread_paths(events: Sequence[StageEvent]) -> List[Tuple[float, float, str]]:
    """One thread's nested spans as sorted, disjoint ``(start, end, path)``
    covering the time in which any of them is open."""
    out: List[Tuple[float, float, str]] = []
    stack: List[Tuple[float, str]] = []  # (end, name), outermost first
    t = 0.0

    def emit(upto: float) -> None:
        nonlocal t
        if upto > t:
            out.append((t, upto, stage_path([n for _, n in stack])))
        t = upto

    for e in sorted(events, key=lambda e: (e.start, -e.end)):
        while stack and stack[-1][0] <= e.start:
            emit(stack[-1][0])
            stack.pop()
        if stack:
            emit(e.start)
        t = e.start
        stack.append((min(e.end, stack[-1][0]) if stack else e.end, e.name))
    while stack:
        emit(stack[-1][0])
        stack.pop()
    return out


def _pieces(stages: Sequence[StageEvent]) -> List[Tuple[float, float, List[str]]]:
    """Sorted, disjoint ``(start, end, paths)``: the stage path of each
    thread in a program span there, for the time any thread is in one."""
    by_thread: Dict[int, List[StageEvent]] = defaultdict(list)
    for e in stages:
        by_thread[e.thread].append(e)
    starts, ends = defaultdict(list), defaultdict(list)
    for th, evs in by_thread.items():
        for a, b, path in _thread_paths(evs):
            starts[a].append((th, path))
            ends[b].append(th)
    active: Dict[int, str] = {}
    xs = sorted(set(starts) | set(ends))
    out = []
    for x, nxt in zip(xs, xs[1:] + [None]):
        for th in ends.get(x, ()):
            del active[th]
        for th, path in starts.get(x, ()):
            active[th] = path
        if active and nxt is not None:
            out.append((x, nxt, list(active.values())))
    return out


def _overlaps(x: Sequence[Interval], pieces: Sequence[Tuple]) -> Iterable[Tuple[int, float]]:
    """``(j, overlap)`` for each overlap of the sorted, disjoint intervals
    ``x`` with ``pieces[j]``, sorted and disjoint ``(start, end, ...)``."""
    i = j = 0
    while i < len(x) and j < len(pieces):
        a, b = max(x[i][0], pieces[j][0]), min(x[i][1], pieces[j][1])
        if a < b:
            yield j, b - a
        if x[i][1] < pieces[j][1]:
            i += 1
        else:
            j += 1


def _marks(dt: DeviceTrace) -> Dict[str, List[Interval]]:
    """The benchmark's calls: ``{call: sorted disjoint intervals}``."""
    marks = defaultdict(list)
    for e in dt.host:
        marks[e.name[len(MARK_PREFIX):]].append((e.start, e.end))
    return {k: union(v) for k, v in marks.items()}


def idle_by_stage(dt: DeviceTrace, stages: Sequence[StageEvent], n: int) -> List[List]:
    """Idle device time, averaged over chips, by the benchmark call the host
    was in and the program spans open then: ``[["encode/predict/verify",
    seconds], ...]``, largest first.  Threads in program spans at once share
    the time equally; time in none is ``<call>/-``.  All entries sum to the
    idle time of the window."""
    marks = _marks(dt)
    pieces = _pieces(stages)
    tot: Dict[str, float] = defaultdict(float)
    scale = 1e9 * len(dt.ops)

    def charge(call: str, part: List[Interval]) -> None:
        named = 0.0
        for j, ov in _overlaps(part, pieces):
            paths = pieces[j][2]
            for path in paths:
                tot[f"{call}/{path}"] += ov / len(paths) / scale
            named += ov
        tot[f"{call}/{NO_STAGE}"] += (length(part) - named) / scale

    for d in dt.ops:
        idle = subtract([dt.window], dt.busy(d))
        rest = idle
        for name, iv in marks.items():
            charge(name, intersect(idle, iv))
            rest = subtract(rest, iv)
        charge(BETWEEN, rest)
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n] if v > 0]


def busy_share(dt: DeviceTrace, stages: Sequence[StageEvent], stage: str,
               under: str) -> Optional[float]:
    """Share of the time the host spent in program span ``stage`` within the
    benchmark call ``under`` in which the chip ran an op, averaged over
    chips; None where the host was never there."""
    iv = intersect(union((e.start, e.end) for e in stages if e.name == stage),
                   _marks(dt).get(under, []))
    total = length(iv)
    if total <= 0:
        return None
    return sum(length(intersect(dt.busy(d), iv)) / total for d in dt.ops) / len(dt.ops)


def reduce_file(path: str, device_ids: Sequence[int]) -> Tuple[DeviceTrace, List[StageEvent]]:
    """A profile's device trace (``devtrace.reduce``) and its stage events."""
    from jax.profiler import ProfileData

    planes = list(ProfileData.from_file(path).planes)
    dt = devtrace.reduce(planes, device_ids)
    return dt, read_stages(planes, dt.window)
