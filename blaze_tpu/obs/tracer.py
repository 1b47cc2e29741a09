"""Chrome-trace-event span recorder (Perfetto-loadable).

The reference engine's tunability hinges on per-operator time attribution
pushed into the Spark UI (PAPER.md §metrics); Flare-style native engines add
timelines on top. Here a process-global :class:`Tracer` collects *complete*
trace events (``"ph": "X"``) for query / stage / task / operator / spill /
shuffle-fetch / kernel-dispatch work, serializable as Chrome trace JSON that
``chrome://tracing`` and https://ui.perfetto.dev load directly.

Design constraints:

- **Near-zero overhead when disabled** (the default): every recording site
  checks the single ``TRACER.enabled`` bool; ``span()`` returns a shared
  no-op context manager without allocating.
- **Worker re-basing**: worker processes record spans against their own
  monotonic clock and ship ``(events, wall_epoch_ns)`` back with task
  results; :meth:`Tracer.absorb` re-bases them onto the driver timeline via
  the wall-clock epochs (same machine, so wall clocks agree), keeping the
  worker's real pid so Perfetto renders one track per process.
- **Bounded memory**: the event buffer is capped (``trace_max_events``);
  overflow drops new events and counts them (also published as the
  ``blaze_obs_tracer_events_dropped_total`` registry counter) rather than
  growing unboundedly during a soak.
- **Flight recorder**: independent of the explicit enable/disable above, a
  small always-on ring buffer (``flight_recorder_events``, a deque) keeps
  the most recent span events so incident bundles (obs/dump.py) can show
  what the engine was doing right before a failure — without paying the
  full trace buffer's memory or requiring tracing to have been on.
- **Per-batch detail only under full tracing**: the spans that name what a
  task thread is doing batch by batch — ``op`` self-time segments
  (ops/base.py), ``scan:decode`` / ``scan:decode_wait`` (ops/parquet.py),
  ``transfer:stage`` (core/batch.py), ``shuffle:fetch_wait``
  (ops/shuffle/reader.py), ``sync:<what>`` (utils/device.wait_int) and
  ``kernel`` dispatches — are gated on ``TRACER.enabled`` (:meth:`Tracer.
  detail`), never on ``active``: they would flood the ring, and with
  tracing off a site costs one attribute read.
- **Whose span it is**: every event carries ``args.stage`` / ``args.part``
  / ``args.q`` from the recording thread's task context
  (utils/logutil.task_context), so spans of one query share an identifier.
- **One clock with the device**: while full tracing is on, a span in
  context-manager form also opens a ``jax.profiler.TraceAnnotation``
  named ``blaze/<cat>:<name>``, so a profiler trace shows it on the host
  thread's line beside the device's ``XLA Ops``.
- **CPU beside wall, where a metric reads it**: under full tracing the
  spans of the categories in :data:`CPU_STAMPED` — ``task`` (a task thread's
  whole run) and ``transfer`` (an upload's or a pull's copying) — also carry
  ``args.cpu_us``, the recording thread's own CPU clock
  (``time.thread_time_ns``) across them, so ``dur - cpu_us`` is the time the
  thread did not run: waiting for the interpreter, blocked in a call that
  let it go, or descheduled. The context-manager form stamps it inside its
  wall stamps; a :meth:`Tracer.complete` site hands in its own. A read of
  that clock is a system call (6-24 us on a sandboxed host), so no per-batch
  wait and no operator segment is stamped; the ring's spans carry none.
"""

from __future__ import annotations

import collections
import os
import threading
import time
from typing import Any, Dict, List, Optional

from blaze_tpu.obs.telemetry import get_registry
from blaze_tpu.utils.logutil import task_context

_EVENTS_DROPPED = get_registry().counter(
    "blaze_obs_tracer_events_dropped_total",
    "trace events dropped because the tracer buffer was full")


class _NoopSpan:
    __slots__ = ()

    def __enter__(self):
        return self

    def set(self, **kw):
        pass

    def __exit__(self, *exc):
        return False


_NOOP = _NoopSpan()


# categories whose spans carry ``args.cpu_us`` under full tracing: the two
# whose CPU a benchmark metric reads (``op_cpu_s``, ``stage_cpu_s``)
CPU_STAMPED = frozenset({"task", "transfer"})


class _Span:
    __slots__ = ("_tracer", "name", "cat", "args", "_t0", "_c0", "_note")

    def __init__(self, tracer: "Tracer", name: str, cat: str,
                 args: Optional[dict]):
        self._tracer = tracer
        self.name = name
        self.cat = cat
        self.args = args

    def __enter__(self):
        self._note = self._c0 = None
        full = self._tracer.enabled
        if full:
            # the same span in the profiler's own trace (a no-op unless a
            # jax.profiler session is running); it stamps its start when built
            from jax.profiler import TraceAnnotation

            self._note = TraceAnnotation(f"blaze/{self.cat}:{self.name}")
            self._note.__enter__()
        self._t0 = time.perf_counter_ns()
        if full and self.cat in CPU_STAMPED:
            # the CPU stamps lie inside the wall stamps: cpu_us <= dur
            self._c0 = time.thread_time_ns()
        return self

    def set(self, **kw):
        """Attach/overwrite span args from inside the span body."""
        if self.args is None:
            self.args = {}
        self.args.update(kw)

    def __exit__(self, *exc):
        cpu_ns = None if self._c0 is None else \
            time.thread_time_ns() - self._c0
        dur_ns = time.perf_counter_ns() - self._t0
        if self._note is not None:
            self._note.__exit__(*exc)
        self._tracer._record(self.name, self.cat, self._t0, dur_ns, self.args,
                             cpu_ns)
        return False


class Tracer:
    """Thread-safe trace-event buffer with a monotonic timeline anchored to
    a wall-clock epoch (the re-basing anchor for worker spans)."""

    def __init__(self):
        self._mu = threading.Lock()
        self.enabled = False
        self._events: List[dict] = []
        self.max_events = 1_000_000
        self.dropped = 0
        # flight-recorder ring: always-on unless sized to 0; deque.append is
        # atomic under the GIL, so ring writes take no lock
        self.ring_max = 2048
        self._ring: Optional[collections.deque] = collections.deque(
            maxlen=self.ring_max)
        self.pid = os.getpid()
        # both epochs captured back to back: timeline t=0 <-> wall_epoch_ns
        self.wall_epoch_ns = time.time_ns()
        self.perf_epoch_ns = time.perf_counter_ns()

    # -- control --------------------------------------------------------------

    def enable(self):
        self.enabled = True

    def disable(self):
        self.enabled = False

    @property
    def active(self) -> bool:
        """True when span events should be built at all: either full tracing
        is on, or the flight-recorder ring wants them."""
        return self.enabled or self._ring is not None

    def set_ring(self, n: int):
        """Resize the flight-recorder ring (keeping the newest events); 0
        disables it entirely."""
        n = max(0, int(n))
        if n == self.ring_max and (self._ring is not None) == (n > 0):
            return
        with self._mu:
            self.ring_max = n
            if n == 0:
                self._ring = None
            else:
                old = list(self._ring) if self._ring is not None else []
                self._ring = collections.deque(old[-n:], maxlen=n)

    def ring_snapshot(self, last: Optional[int] = None) -> List[dict]:
        """The newest ring events (all of them, or just the last N)."""
        ring = self._ring
        if ring is None:
            return []
        events = list(ring)
        return events[-last:] if last is not None else events

    def reset(self):
        with self._mu:
            self._events = []
            self.dropped = 0
            if self._ring is not None:
                self._ring.clear()
            self.wall_epoch_ns = time.time_ns()
            self.perf_epoch_ns = time.perf_counter_ns()

    # -- recording ------------------------------------------------------------

    def span(self, name: str, cat: str = "engine",
             args: Optional[dict] = None):
        """Context manager timing a block; no-op (and allocation-free) when
        neither tracing nor the flight-recorder ring wants events."""
        if not self.active:
            return _NOOP
        return _Span(self, name, cat, args)

    def detail(self, name: str, cat: str, args: Optional[dict] = None):
        """:meth:`span` for a per-batch site: recorded under full tracing
        only, never for the flight-recorder ring alone."""
        if not self.enabled:
            return _NOOP
        return _Span(self, name, cat, args)

    def instant(self, name: str, cat: str = "engine",
                args: Optional[dict] = None):
        if not self.active:
            return
        ts = (time.perf_counter_ns() - self.perf_epoch_ns) / 1e3
        self._append({"ph": "i", "name": name, "cat": cat, "ts": ts, "s": "t",
                      "pid": self.pid, "tid": threading.get_ident(),
                      **({"args": args} if args else {})})

    def complete(self, name: str, cat: str, t0_ns: int, dur_ns: int,
                 args: Optional[dict] = None, cpu_ns: Optional[int] = None):
        """Record a complete event from explicit perf_counter_ns stamps (for
        sites that cannot use the context manager, e.g. generators).
        ``cpu_ns`` is the recording thread's ``thread_time_ns`` across the
        same stretch, from a site that stamped it (full tracing only)."""
        if not self.active:
            return
        self._record(name, cat, t0_ns, dur_ns, args, cpu_ns)

    def _record(self, name, cat, t0_ns, dur_ns, args, cpu_ns=None):
        ev = {"ph": "X", "name": name, "cat": cat,
              "ts": (t0_ns - self.perf_epoch_ns) / 1e3,
              "dur": dur_ns / 1e3,
              "pid": self.pid, "tid": threading.get_ident()}
        ctx = task_context()
        if ctx is not None:
            # whose span it is; a key the site set itself wins
            args = {"stage": ctx[0], "part": ctx[1], "q": ctx[2],
                    **(args or {})}
        if cpu_ns is not None:
            args = {**(args or {}), "cpu_us": cpu_ns / 1e3}
        if args:
            ev["args"] = args
        self._append(ev)

    def _append(self, ev: dict):
        ring = self._ring
        if ring is not None:
            ring.append(ev)  # atomic; overwrite-oldest is the point
        if not self.enabled:
            return
        full = False
        with self._mu:
            if len(self._events) >= self.max_events:
                self.dropped += 1
                full = True
            else:
                self._events.append(ev)
        if full:
            _EVENTS_DROPPED.inc()

    # -- worker shipping / re-basing ------------------------------------------

    def drain(self) -> List[dict]:
        """Snapshot AND clear the buffer (worker side: events ship with the
        task reply; keeping them would re-ship on the next task)."""
        with self._mu:
            events, self._events = self._events, []
            return events

    def absorb(self, events: List[dict], wall_epoch_ns: int):
        """Fold a remote process's events into this timeline. Remote ``ts``
        values are µs since the remote epoch; shift by the wall-clock delta
        between the two epochs so both processes share one time axis."""
        if not events:
            return
        delta_us = (wall_epoch_ns - self.wall_epoch_ns) / 1e3
        absorbed_drops = 0
        with self._mu:
            for i, ev in enumerate(events):
                if len(self._events) >= self.max_events:
                    absorbed_drops = len(events) - i
                    self.dropped += absorbed_drops
                    break
                ev = dict(ev)
                ev["ts"] = ev.get("ts", 0.0) + delta_us
                self._events.append(ev)
        if absorbed_drops:
            _EVENTS_DROPPED.inc(absorbed_drops)

    # -- export ---------------------------------------------------------------

    def snapshot(self) -> List[dict]:
        with self._mu:
            return list(self._events)

    def to_chrome_trace(self, process_name: str = "blaze_tpu-driver") -> Dict[str, Any]:
        """Perfetto/chrome://tracing-loadable JSON object. Spans carry a
        stable per-attribution-category ``cname`` (same work, same color,
        across traces and rounds), and each stage's shuffle-write spans are
        linked to the downstream fetch spans with flow events so the
        cross-stage critical path is visible as arrows."""
        from blaze_tpu.obs.attribution import CATEGORY_CNAME, classify_span

        events = []
        writes_by_stage: Dict[Any, dict] = {}
        fetches: List[dict] = []
        for ev in self.snapshot():
            cat = classify_span(ev.get("name", ""), ev.get("cat", ""))
            if cat is not None:
                ev = dict(ev)
                ev["cname"] = CATEGORY_CNAME[cat]
            if cat == "shuffle_write":
                stage = (ev.get("args") or {}).get("stage")
                if stage is not None:
                    writes_by_stage.setdefault(stage, ev)
            elif cat == "shuffle_fetch":
                fetches.append(ev)
            events.append(ev)
        flows = []
        for fe in fetches:
            stage = (fe.get("args") or {}).get("stage")
            we = writes_by_stage.get(stage)
            if we is None:
                continue
            fid = f"shuffle_{stage}"
            flows.append({"ph": "s", "name": fid, "cat": "shuffle_flow",
                          "id": fid, "ts": we["ts"] + we.get("dur", 0.0),
                          "pid": we.get("pid", self.pid),
                          "tid": we.get("tid", 0)})
            flows.append({"ph": "f", "bp": "e", "name": fid,
                          "cat": "shuffle_flow", "id": fid, "ts": fe["ts"],
                          "pid": fe.get("pid", self.pid),
                          "tid": fe.get("tid", 0)})
        pids = {e.get("pid", self.pid) for e in events} | {self.pid}
        meta = []
        for pid in sorted(pids):
            name = process_name if pid == self.pid else f"blaze_tpu-worker-{pid}"
            meta.append({"ph": "M", "name": "process_name", "pid": pid,
                         "tid": 0, "args": {"name": name}})
        counters = self._timeline_counter_events()
        return {"traceEvents": meta + events + flows + counters,
                "displayTimeUnit": "ms",
                "otherData": {"dropped_events": self.dropped,
                              "wall_epoch_ns": self.wall_epoch_ns}}

    def _timeline_counter_events(self) -> List[dict]:
        """Sampled timeline series (inflight, ingest lag, memmgr bytes) as
        Chrome counter events ("ph":"C") — Perfetto renders them as load
        curves under the spans. Timeline timestamps are wall-clock; spans
        are epoch-relative, so convert through ``wall_epoch_ns``."""
        counters: List[dict] = []
        try:
            from blaze_tpu.obs.timeline import (COUNTER_TRACK_SERIES,
                                                get_timeline)

            tl = get_timeline()
            for series in COUNTER_TRACK_SERIES:
                for t, v in (tl.series_since(series, 0.0) or []):
                    counters.append(
                        {"ph": "C", "name": series, "cat": "timeline",
                         "pid": self.pid, "tid": 0,
                         "ts": (t * 1e9 - self.wall_epoch_ns) / 1e3,
                         "args": {series: v}})
        except Exception:
            pass  # the trace export never fails for a health-plane hiccup
        return counters


TRACER = Tracer()


def get_tracer() -> Tracer:
    return TRACER


def configure_from(conf) -> Tracer:
    """Enable/disable the process tracer from a Config (Session/worker call
    this; BLAZE_TPU_TRACE=1 force-enables for ad-hoc runs)."""
    TRACER.set_ring(getattr(conf, "flight_recorder_events", TRACER.ring_max))
    if getattr(conf, "trace_enable", False) or \
            os.environ.get("BLAZE_TPU_TRACE", "") not in ("", "0"):
        TRACER.max_events = getattr(conf, "trace_max_events", TRACER.max_events)
        TRACER.enable()
    else:
        TRACER.disable()
    return TRACER
