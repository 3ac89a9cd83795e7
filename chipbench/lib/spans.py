"""Reading the program's telemetry spans (``repro.core.telemetry``).

The spans arrive as the nested dicts of ``Trace.to_dict()["spans"]``:
``{"name", "seconds", "attrs"?, "children"?}``.  The benchmark wraps each
call it makes in a span of its own (``encode``, ``decode``), so a reader can
tell on which side of the codec a stage ran.
"""
from __future__ import annotations

from typing import Dict, Iterable, Iterator, Sequence, Tuple

Span = Dict


def walk(spans: Sequence[Span], ancestors: Tuple[str, ...] = ()) -> Iterator[Tuple[Span, Tuple[str, ...]]]:
    """Every span with the names of the spans it is nested in."""
    for s in spans:
        yield s, ancestors
        yield from walk(s.get("children", ()), ancestors + (s["name"],))


def self_seconds(span: Span) -> float:
    """A span's seconds less those its children cover."""
    return span["seconds"] - sum(c["seconds"] for c in span.get("children", ()))


def total(spans: Sequence[Span], names: Iterable[str], *, under: str,
          not_under: Sequence[str] = (), self_time: bool = False) -> Tuple[float, int]:
    """Seconds and ``bytes`` of the spans named ``names`` nested in ``under``
    and in none of ``not_under``."""
    names = set(names)
    secs, nbytes = 0.0, 0
    for s, anc in walk(spans):
        if s["name"] in names and under in anc and not set(not_under) & set(anc):
            secs += self_seconds(s) if self_time else s["seconds"]
            nbytes += int(s.get("attrs", {}).get("bytes", 0))
    return secs, nbytes


def gbps(nbytes: float, seconds: float):
    """GB/s, or None where no time was spent (nothing to read)."""
    return nbytes / 1e9 / seconds if seconds > 0 and nbytes > 0 else None
