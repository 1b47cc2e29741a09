"""Interval arithmetic of the trace reduction: the union of device-op
intervals, the idle gaps between them, and a name for each gap from the host
spans open at its middle. Times are numbers on one clock (nanoseconds in the
benchmark); an interval is ``(start, end)`` with ``end >= start``."""

from __future__ import annotations

from typing import Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]
BETWEEN_QUERIES = "between queries"
UNATTRIBUTED = "unattributed"


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Disjoint sorted intervals covering exactly what the inputs cover.
    Nested and overlapping inputs count once; touching ones merge."""
    out: List[List[float]] = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1][1] = end
        else:
            out.append([start, end])
    return [(s, e) for s, e in out]


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    """The parts of ``intervals`` inside ``[lo, hi]``."""
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def covered(intervals: Iterable[Interval], lo: float, hi: float) -> float:
    """Length of ``[lo, hi]`` covered by the union of ``intervals``."""
    return sum(e - s for s, e in union(clip(intervals, lo, hi)))


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The idle stretches of ``[lo, hi]``: what a disjoint sorted ``busy``
    (as ``union`` returns it) leaves uncovered, the ends included."""
    out, at = [], lo
    for s, e in clip(busy, lo, hi):
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def name_gap(gap: Interval, queries: Sequence[Interval],
             spans: Sequence[Tuple[float, float, str]]) -> str:
    """What the host was doing at the middle of an idle gap: the innermost
    (shortest) span open there; "between queries" when no query was running;
    "unattributed" inside a query that no span covers."""
    mid = (gap[0] + gap[1]) / 2.0
    if not any(s <= mid <= e for s, e in queries):
        return BETWEEN_QUERIES
    best: Optional[Tuple[float, str]] = None
    for s, e, name in spans:
        if s <= mid <= e and (best is None or e - s < best[0]):
            best = (e - s, name)
    return best[1] if best else UNATTRIBUTED


def idle_by_name(busy: Sequence[Interval], lo: float, hi: float,
                 queries: Sequence[Interval],
                 spans: Sequence[Tuple[float, float, str]]) -> dict:
    """Idle time of ``[lo, hi]`` summed by the name of each gap."""
    out: dict = {}
    for gap in gaps(busy, lo, hi):
        name = name_gap(gap, queries, spans)
        out[name] = out.get(name, 0.0) + (gap[1] - gap[0])
    return out
