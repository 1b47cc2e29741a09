"""From a profiler trace (`*.xplane.pb`, read with `jax.profiler.ProfileData`)
to the few lists the metrics need, and from those lists to numbers.

What a TPU trace holds (looked at by hand, PERF.md section 6): one plane per
chip named `/device:TPU:<n>`, with a line `XLA Modules` (one event per
executable launch, named `jit_<fn>(<fingerprint>)`) and a line `XLA Ops` (one
event per HLO operation that ran, nested inside the module's interval); and a
plane `/host:CPU` with one line per host thread, where the benchmark's
`TraceAnnotation`s land by name. All planes share one clock.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

from benchlib import intervals as iv

DEVICE_PLANE_RE = re.compile(r"^/device:TPU:(\d+)$")
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
ANNOTATION_PREFIX = "bench_"
ANCHOR = "bench_anchor"
_MODULE_ID_RE = re.compile(r"\(\d+\)$")
# HLO operations that move data between chips. An `XLA Ops` event is named by
# the instruction's text, `%all-to-all.3 = ... all-to-all(...)`, or by its name
_COLLECTIVES = (r"(?:all-to-all|all-reduce|all-gather|reduce-scatter"
                r"|collective-permute|collective-broadcast|ragged-all-to-all)")
COLLECTIVE_RE = re.compile(
    rf"^%?{_COLLECTIVES}|\s{_COLLECTIVES}(?:-start|-done)?\(", re.IGNORECASE)

Event = Tuple[float, float, str]  # start_ns, duration_ns, name


@dataclasses.dataclass
class Trace:
    """The events of one trace the reduction reads. ``launches`` and ``ops``
    are per chip, keyed by the chip's ordinal; ``annotations`` are the host
    events whose name starts with ``bench_``."""

    launches: Dict[int, List[Event]]
    ops: Dict[int, List[Event]]
    annotations: List[Event]

    @property
    def chips(self) -> List[int]:
        return sorted(self.ops)


class TraceError(Exception):
    pass


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise TraceError(f"no *.xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str, require_tpu: bool = True) -> Trace:
    """Read one xplane file. A trace without a TPU plane, or a TPU plane
    without the two lines, is an error that says what was found. Only a
    rehearsal on the CPU passes ``require_tpu=False``: it gets the
    annotations and one placeholder chip on which nothing ran."""
    import jax.profiler

    data = jax.profiler.ProfileData.from_file(path)
    launches: Dict[int, List[Event]] = {}
    ops: Dict[int, List[Event]] = {}
    annotations: List[Event] = []
    seen = []
    for plane in data.planes:
        seen.append(plane.name)
        m = DEVICE_PLANE_RE.match(plane.name)
        if m:
            chip = int(m.group(1))
            lines = {line.name: line for line in plane.lines}
            for want, into in ((MODULE_LINE, launches), (OP_LINE, ops)):
                if want not in lines:
                    raise TraceError(
                        f"plane {plane.name} has no line {want!r}: "
                        f"{sorted(lines)}")
                into[chip] = [(e.start_ns, e.duration_ns, e.name)
                              for e in lines[want].events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(ANNOTATION_PREFIX):
                        annotations.append((e.start_ns, e.duration_ns, e.name))
    if not ops:
        if require_tpu:
            raise TraceError(f"no /device:TPU:<n> plane in {path}: {seen}")
        launches, ops = {0: []}, {0: []}
    return Trace(launches, ops, sorted(annotations))


def clock_offset_ns(trace: Trace, anchor_perf_ns: int) -> float:
    """What to add to a `perf_counter_ns()` reading to get the trace's clock:
    the benchmark reads the host clock just before it opens the annotation
    `bench_anchor`, and the trace says when that annotation began."""
    for start, _dur, name in trace.annotations:
        if name == ANCHOR:
            return start - anchor_perf_ns
    raise TraceError(f"the trace has no {ANCHOR!r} annotation")


def module_name(event_name: str) -> str:
    """`jit_agg_partial(123456)` -> `jit(agg_partial)`: the name JAX's
    compile log gives the same program, without the fingerprint."""
    name = _MODULE_ID_RE.sub("", event_name)
    return f"jit({name[4:]})" if name.startswith("jit_") else name


def _ends(events: Sequence[Event]) -> List[iv.Interval]:
    return [(s, s + d) for s, d, _n in events]


@dataclasses.dataclass
class Reduction:
    """Numbers of one traced window ``[lo, hi]`` (trace clock, ns)."""

    window_s: float
    busy_s: Dict[int, float]               # per chip, union of op intervals
    busy_per_query_s: List[Dict[int, float]]
    launches_per_query: List[Dict[int, int]]
    collective_s: Dict[int, float]         # per chip, union of collective ops
    device_ops: List[Tuple[str, float]]    # seconds by program, mean over chips
    idle_gaps: List[Tuple[str, float]]     # seconds by gap name, mean over chips

    @property
    def mean_busy_s(self) -> float:
        return sum(self.busy_s.values()) / len(self.busy_s)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.mean_busy_s / self.window_s


def reduce(trace: Trace, queries: Sequence[iv.Interval],
           spans: Sequence[Tuple[float, float, str]] = (),
           top: int = 10) -> Reduction:
    """``queries`` are the traced queries' intervals and ``spans`` the host
    spans ``(start, end, name)``, both on the trace's clock. The window runs
    from the first query's start to the last one's end."""
    if not queries:
        raise TraceError("no traced query")
    lo, hi = min(q[0] for q in queries), max(q[1] for q in queries)
    chips = trace.chips
    busy, busy_q, launches_q, coll = {}, [{} for _ in queries], \
        [{} for _ in queries], {}
    by_program: Dict[str, float] = {}
    by_gap: Dict[str, float] = {}
    for chip in chips:
        merged = iv.union(_ends(trace.ops[chip]))
        busy[chip] = iv.covered(merged, lo, hi) / 1e9
        for i, (qs, qe) in enumerate(queries):
            busy_q[i][chip] = iv.covered(merged, qs, qe) / 1e9
            launches_q[i][chip] = sum(
                1 for s, _d, _n in trace.launches.get(chip, ()) if qs <= s < qe)
        coll[chip] = iv.covered(
            _ends([e for e in trace.ops[chip] if COLLECTIVE_RE.search(e[2])]),
            lo, hi) / 1e9
        for s, d, name in trace.launches.get(chip, ()):
            if lo <= s < hi:
                key = module_name(name)
                by_program[key] = by_program.get(key, 0.0) + d / 1e9
        for name, ns in iv.idle_by_name(merged, lo, hi, queries, spans).items():
            by_gap[name] = by_gap.get(name, 0.0) + ns / 1e9
    n = len(chips)

    def ranked(d):
        return sorted(((k, v / n) for k, v in d.items()),
                      key=lambda kv: -kv[1])[:top]

    return Reduction((hi - lo) / 1e9, busy, busy_q, launches_q, coll,
                     ranked(by_program), ranked(by_gap))
