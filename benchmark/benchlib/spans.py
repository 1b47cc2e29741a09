"""The program's own host spans (`blaze_tpu.obs.tracer`) for the readers that
turn them into per-layer metrics. `run.py` resets the tracer where the traced
window starts and switches it off only after the readers ran, so a reader
takes `TRACER.snapshot()` as it stands.

A `Span` has `start` and `end` in seconds on the host's `perf_counter` clock
(the one `QueryRecord.t0` is read on: no anchor, no second clock), `key`
`"<cat>:<name>"`, the recording thread's `tid` and the event's `args`. A span
belongs to the traced query in whose `[t0, t0 + seconds)` it starts. A
program without the tracer, or one that records no span a reader asks for,
gives the reader nothing to read: it returns None, it does not raise."""

from __future__ import annotations

import collections
import fnmatch
from typing import Dict, Iterable, List, Sequence

from benchlib import intervals as iv

Span = collections.namedtuple("Span", "start end key tid args")


def load() -> List[Span]:
    """Every complete span the program's tracer holds, oldest first."""
    try:
        from blaze_tpu.obs.tracer import TRACER
    except ImportError:
        return []
    epoch_s = TRACER.perf_epoch_ns / 1e9
    out = []
    for ev in TRACER.snapshot():
        if ev.get("ph") != "X":
            continue
        start = epoch_s + ev["ts"] / 1e6
        out.append(Span(start, start + ev["dur"] / 1e6,
                        f"{ev['cat']}:{ev['name']}", ev.get("tid"),
                        ev.get("args") or {}))
    out.sort(key=lambda s: s.start)
    return out


def matching(spans: Iterable[Span], patterns: Sequence[str]) -> List[Span]:
    """Spans whose key matches one of the shell-style ``patterns``
    (`"sync:*"`, `"scan:decode_wait"`)."""
    return [s for s in spans
            if any(fnmatch.fnmatchcase(s.key, p) for p in patterns)]


def of_query(spans: Iterable[Span], record) -> List[Span]:
    lo, hi = record.t0, record.t0 + record.seconds
    return [s for s in spans if lo <= s.start < hi]


def by_thread(spans: Iterable[Span]) -> Dict[object, List[Span]]:
    out: Dict[object, List[Span]] = {}
    for s in spans:
        out.setdefault(s.tid, []).append(s)
    return out


def thread_seconds(spans: Iterable[Span]) -> float:
    """Seconds the spans cover, each thread by itself and the threads added
    up: spans nested or overlapping on one thread count once, spans of tasks
    that ran side by side count each (so the sum can exceed the query's
    time, as the operators' self times do)."""
    return sum(e - s
               for group in by_thread(spans).values()
               for s, e in iv.union((x.start, x.end) for x in group))
