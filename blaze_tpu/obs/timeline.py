"""Live health plane: time-series history, SLOs, burn rates, health states.

Every other observability plane is point-in-time — ``/metrics`` scrapes
current counters, profiles and attribution describe one finished query.
This module records how the process behaves *over time* and judges it
continuously:

- **Timeline sampler**: a background thread samples the process
  :class:`~blaze_tpu.obs.telemetry.MetricsRegistry` every
  ``timeline_interval_s`` into fixed-size ring buffers. Counters become
  windowed per-second rates (``<name>:rate``), gauges become samples
  (``<name>``), histograms become interval p50/p95/p99 via bucket-snapshot
  deltas (``<name>:p99`` — ``telemetry.delta_snapshot``). On top of the
  generic pass, derived serve/cache/ingest series: ingest lag in versions
  (appended version minus the newest version any fresh cache entry
  covers), refresh backlog, admission queue depth, per-tenant
  deadline-miss ratio (``DERIVED_SERIES``).
- **SLO evaluator**: declarative objectives from ``Config.slo_specs``
  (``"<subsystem>:<series><op><threshold>"``) checked per sample with
  Google-SRE-style fast/slow burn-rate windows: a breaching sample spends
  error budget; burn = breaching fraction / ``slo_error_budget_ratio``.
  ``degraded`` fires on the fast window alone (catches onset), ``critical``
  only when BOTH windows burn past ``slo_critical_burn`` (confirms it is
  sustained — the multiwindow rule that keeps one hiccup from paging).
- **Health state machine**: each subsystem in :data:`SUBSYSTEMS` is the
  worst state across its SLOs; every transition appends to a bounded
  history, closes the previous state's interval, and writes exactly one
  incident bundle through ``obs/dump.record_incident`` (kind ``health``).
  Served live at ``GET /debug/health`` and
  ``GET /debug/timeseries?name=&since=`` (runtime/http.py): the health
  *history* (critical intervals, degraded time), not just the end state.

The sampler binds to the newest driver :class:`Session` (weakly) and
stops when that session closes — no thread outlives its session. When
``timeline_enabled`` is false nothing starts and the only hot-path cost
is one attribute check in :meth:`Timeline.note_outcome` (guarded by
test_timeline.py's <5% overhead test, same bar as the other planes).
"""

from __future__ import annotations

import re
import threading
import time
import weakref
from typing import Dict, List, Optional, Tuple

from blaze_tpu.obs.telemetry import (Counter, Gauge, Histogram,
                                     delta_snapshot, get_registry,
                                     quantile_from_snapshot)

_reg = get_registry()
_TL_SAMPLES = _reg.counter(
    "blaze_timeline_samples_total",
    "timeline sampler passes completed")
_TL_SAMPLE_SECONDS = _reg.histogram(
    "blaze_timeline_sample_seconds",
    "wall time of one sampler pass over the registry + derived probes")
_TL_SERIES = _reg.gauge(
    "blaze_timeline_series_count",
    "live time-series ring buffers held by the timeline")
_SLO_BREACHES = _reg.counter(
    "blaze_slo_breaches_total",
    "samples that breached an SLO objective, by slo key")
_SLO_TRANSITIONS = _reg.counter(
    "blaze_slo_transitions_total",
    "subsystem health-state transitions, by subsystem and entered state")

# health taxonomy (validated by scripts/check_metrics_names.py): the
# subsystems the state machine tracks and the states it moves between
SUBSYSTEMS = ("serve", "cache", "ingest", "memmgr", "shuffle", "workers")
HEALTH_STATES = ("healthy", "degraded", "critical")
_SEVERITY = {s: i for i, s in enumerate(HEALTH_STATES)}

# derived series the sampler computes beyond the generic registry pass;
# per-tenant / per-table variants append ".<tenant>" / ".<table>"
DERIVED_SERIES = (
    "serve_queue_depth_count",
    "serve_inflight_count",
    "serve_deadline_miss_ratio",
    "serve_p99_ms",
    "cache_stale_served_rate",
    "cache_refresh_backlog_count",
    "cache_hit_ratio",
    "ingest_lag_versions",
    "ingest_append_rate",
    "ingest_rows_rate",
    "memmgr_used_bytes",
    "shuffle_tier_degraded_rate",
    "worker_deaths_rate",
)

# sampled series exported as Chrome-trace counter tracks ("ph": "C") by
# Tracer.to_chrome_trace — Perfetto renders them as load curves under the
# spans
COUNTER_TRACK_SERIES = ("serve_inflight_count", "ingest_lag_versions",
                        "memmgr_used_bytes")

# top-level keys of health_report() — what GET /debug/health serves
HEALTH_FIELDS = ("enabled", "interval_s", "wall_s", "samples", "subsystems",
                 "slo", "transitions", "intervals", "degraded_s",
                 "critical_s", "critical_intervals", "degraded_ratio")


class Ring:
    """Fixed-size append-only ring of ``(t, value)`` samples. Writers and
    readers share the timeline lock; the ring itself is just index math."""

    __slots__ = ("_buf", "_n", "_head")

    def __init__(self, maxlen: int):
        self._buf: List[Optional[Tuple[float, float]]] = [None] * max(
            2, int(maxlen))
        self._n = 0
        self._head = 0  # next write slot

    def append(self, t: float, v: float):
        self._buf[self._head] = (t, v)
        self._head = (self._head + 1) % len(self._buf)
        self._n = min(self._n + 1, len(self._buf))

    def items(self) -> List[Tuple[float, float]]:
        """Samples oldest -> newest."""
        if self._n < len(self._buf):
            return [s for s in self._buf[:self._n]]
        return self._buf[self._head:] + self._buf[:self._head]

    def since(self, t0: float) -> List[Tuple[float, float]]:
        return [s for s in self.items() if s[0] >= t0]

    def last(self) -> Optional[Tuple[float, float]]:
        if not self._n:
            return None
        return self._buf[(self._head - 1) % len(self._buf)]

    def __len__(self):
        return self._n


_SLO_RE = re.compile(
    r"^\s*([a-z_]+)\s*:\s*([a-z0-9_.]+)\s*(<=|>=|==|<|>)\s*"
    r"([0-9.eE+-]+)\s*$")

_OPS = {
    "<=": lambda v, t: v <= t,
    "<": lambda v, t: v < t,
    "==": lambda v, t: v == t,
    ">=": lambda v, t: v >= t,
    ">": lambda v, t: v > t,
}


class SloSpec:
    """One parsed objective: ``subsystem:series op threshold``. ``check``
    returns True while the objective is MET (the sample spends no
    budget)."""

    __slots__ = ("subsystem", "series", "op", "threshold", "key",
                 "ring", "state", "burn_fast", "burn_slow", "last_value")

    def __init__(self, subsystem: str, series: str, op: str,
                 threshold: float):
        if subsystem not in SUBSYSTEMS:
            raise ValueError(f"slo subsystem {subsystem!r} not in "
                             f"{SUBSYSTEMS}")
        self.subsystem = subsystem
        self.series = series
        self.op = op
        self.threshold = threshold
        self.key = f"{subsystem}:{series}{op}{threshold:g}"
        self.ring: Ring = Ring(1024)  # (t, 1.0 breach / 0.0 ok)
        self.state = "healthy"
        self.burn_fast = 0.0
        self.burn_slow = 0.0
        self.last_value: Optional[float] = None

    def check(self, value: float) -> bool:
        return _OPS[self.op](value, self.threshold)

    def snapshot(self) -> dict:
        return {"series": self.series, "op": self.op,
                "threshold": self.threshold, "state": self.state,
                "burn_fast": round(self.burn_fast, 4),
                "burn_slow": round(self.burn_slow, 4),
                "last_value": self.last_value}


def parse_slo_specs(text: str) -> List[SloSpec]:
    """Parse the ``slo_specs`` grammar; raises ValueError on a malformed
    entry (a typo'd objective silently skipped would read as healthy)."""
    out = []
    for part in (text or "").split(";"):
        if not part.strip():
            continue
        m = _SLO_RE.match(part)
        if m is None:
            raise ValueError(f"malformed slo spec {part!r} (want "
                             f"'<subsystem>:<series><op><threshold>')")
        sub, series, op, thr = m.groups()
        out.append(SloSpec(sub, series, op, float(thr)))
    return out


class Timeline:
    """The process-global health plane (one per driver process, like the
    tracer and the registry). All series state behind one lock; the
    sampler thread is the only writer, HTTP/artifact readers snapshot."""

    _HISTORY_MAX = 512

    def __init__(self):
        self._mu = threading.RLock()
        self.enabled = False
        self.interval_s = 1.0
        self.ring = 512
        self._series: Dict[str, Ring] = {}
        self._tick: Dict[str, float] = {}  # series -> value at current tick
        self._prev_counters: Dict[str, float] = {}
        self._prev_labeled: Dict[str, Dict] = {}
        self._prev_hists: Dict[str, dict] = {}
        self._slos: List[SloSpec] = []
        self._sub_state: Dict[str, str] = {s: "healthy" for s in SUBSYSTEMS}
        self._sub_since: Dict[str, float] = {}
        self._transitions: List[dict] = []
        self._intervals: List[dict] = []  # closed non-healthy intervals
        self._samples = 0
        self._started_wall: Optional[float] = None
        self._last_t: Optional[float] = None
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        self._session = None  # weakref.ref to the bound Session
        self._conf = None
        # fast/slow burn-rate knobs (configure_from overwrites)
        self.fast_window_s = 10.0
        self.slow_window_s = 60.0
        self.budget_ratio = 0.1
        self.degraded_burn = 1.0
        self.critical_burn = 2.0
        # per-(tenant, outcome) tallies noted by the serve scheduler since
        # the last sample (the deadline-miss-ratio source); own mutex so
        # the hot path never waits on a sampler pass
        self._note_mu = threading.Lock()
        self._outcomes: Dict[Tuple[str, str], int] = {}

    # -- hot-path hook ---------------------------------------------------------

    def note_outcome(self, tenant: str, outcome: str):
        """Called by the serve scheduler on every terminal outcome; one
        attribute check when the plane is off (the <5% guard)."""
        if not self.enabled:
            return
        with self._note_mu:
            k = (tenant, outcome)
            self._outcomes[k] = self._outcomes.get(k, 0) + 1

    # -- lifecycle -------------------------------------------------------------

    def configure(self, conf):
        self.interval_s = max(0.05, float(
            getattr(conf, "timeline_interval_s", 1.0)))
        self.ring = max(16, int(getattr(conf, "timeline_ring", 512)))
        self.fast_window_s = float(getattr(conf, "slo_fast_window_s", 10.0))
        self.slow_window_s = float(getattr(conf, "slo_slow_window_s", 60.0))
        self.budget_ratio = max(1e-6, float(
            getattr(conf, "slo_error_budget_ratio", 0.1)))
        self.degraded_burn = float(getattr(conf, "slo_degraded_burn", 1.0))
        self.critical_burn = float(getattr(conf, "slo_critical_burn", 2.0))
        self._conf = conf
        specs = parse_slo_specs(getattr(conf, "slo_specs", "") or "")
        with self._mu:
            # keep rings of unchanged objectives so a reconfigure (new
            # session, same specs) does not forget burn history mid-soak
            old = {sl.key: sl for sl in self._slos}
            self._slos = [old.get(sl.key, sl) for sl in specs]

    def start(self, session):
        """Bind to ``session`` and ensure the sampler thread runs. A
        second session rebinds the existing thread (the plane is
        process-global, like the tracer)."""
        with self._mu:
            self._session = weakref.ref(session)
            self.enabled = True
            if self._thread is not None and self._thread.is_alive():
                return
            self._stop = threading.Event()
            self._thread = threading.Thread(
                target=self._loop, name="blaze-timeline", daemon=True)
            self._thread.start()

    def stop(self):
        with self._mu:
            t, self._thread = self._thread, None
            self._session = None
            self.enabled = False
            self._stop.set()
        if t is not None and t.is_alive():
            t.join(timeout=5.0)

    def detach(self, session):
        """Session close hook: stop only when the closing session is the
        bound one (history is kept — soaks read it after close)."""
        ref = self._session
        if ref is not None and ref() is session:
            self.stop()

    def reset(self):
        """Forget all series, SLO burn history and health history (test
        isolation / soak phase boundaries)."""
        with self._mu:
            self._series.clear()
            self._tick.clear()
            self._prev_counters.clear()
            self._prev_labeled.clear()
            self._prev_hists.clear()
            for sl in self._slos:
                sl.ring = Ring(1024)
                sl.state = "healthy"
                sl.burn_fast = sl.burn_slow = 0.0
                sl.last_value = None
            self._sub_state = {s: "healthy" for s in SUBSYSTEMS}
            self._sub_since = {}
            self._transitions = []
            self._intervals = []
            self._samples = 0
            self._started_wall = None
            self._last_t = None
        with self._note_mu:
            self._outcomes.clear()

    def _loop(self):
        while not self._stop.wait(self.interval_s):
            try:
                self.sample_once()
            except Exception:
                # the health plane must never take down the engine it is
                # watching; a failed pass skips one sample
                pass

    # -- sampling --------------------------------------------------------------

    def _push(self, name: str, t: float, v: float):
        ring = self._series.get(name)
        if ring is None:
            ring = self._series[name] = Ring(self.ring)
        ring.append(t, v)
        self._tick[name] = v

    def sample_once(self, now: Optional[float] = None):
        """One sampler pass: generic registry sweep, derived probes, SLO
        evaluation, health transitions. ``now`` is injectable for
        deterministic tests."""
        t0 = time.perf_counter()
        now = time.time() if now is None else now
        with self._mu:
            if self._started_wall is None:
                self._started_wall = now
            dt = (now - self._last_t) if self._last_t is not None else None
            self._tick = {}
            self._sample_registry(now, dt)
            self._sample_derived(now, dt)
            self._eval_slos(now)
            self._eval_health(now)
            self._last_t = now
            self._samples += 1
            _TL_SERIES.set(len(self._series))
        _TL_SAMPLES.inc()
        _TL_SAMPLE_SECONDS.observe(time.perf_counter() - t0)

    def _sample_registry(self, now: float, dt: Optional[float]):
        for name, inst in get_registry().instruments().items():
            if isinstance(inst, Counter):
                cur = float(inst.total())
                prev = self._prev_counters.get(name)
                self._prev_counters[name] = cur
                if dt and prev is not None:
                    # clamp: reset_values() between samples shrinks totals
                    self._push(f"{name}:rate", now,
                               max(0.0, cur - prev) / dt)
            elif isinstance(inst, Gauge):
                v = inst.value() if inst._fn is not None else None
                if v is None:
                    vals = [s for s in inst.series().values()
                            if isinstance(s, (int, float))]
                    v = float(sum(vals)) if vals else None
                if v is not None:
                    self._push(name, now, float(v))
            elif isinstance(inst, Histogram):
                merged = _merged_snapshot(inst)
                if merged is None:
                    continue
                prev = self._prev_hists.get(name)
                self._prev_hists[name] = merged
                delta = delta_snapshot(merged, prev)
                if delta["count"] > 0:
                    for q, suffix in ((0.50, ":p50"), (0.95, ":p95"),
                                      (0.99, ":p99")):
                        qv = quantile_from_snapshot(delta, q)
                        if qv is not None:
                            self._push(f"{name}{suffix}", now, qv)

    def _labeled_delta(self, name: str, key: str,
                       cur: Dict) -> Dict:
        prev = self._prev_labeled.get(key)
        self._prev_labeled[key] = cur
        if prev is None:
            # First observation: the cumulative totals are history from
            # before the sampler attached, not activity in this interval.
            return {}
        out = {}
        for k, v in cur.items():
            p = prev.get(k, 0)
            out[k] = v - p if v >= p else v  # clamp across reset_values
        return out

    def _sample_derived(self, now: float, dt: Optional[float]):
        sess = self._session() if self._session is not None else None
        rate = (lambda d: d / dt) if dt else (lambda d: 0.0)

        # serve: scheduler probe + per-tenant deadline-miss ratio
        sched = getattr(sess, "serve_scheduler", None) \
            if sess is not None else None
        if sched is not None:
            try:
                probe = sched.health_probe()
                self._push("serve_queue_depth_count", now,
                           float(probe["queue_depth"]))
                self._push("serve_inflight_count", now,
                           float(probe["inflight"]))
            except Exception:
                pass
        with self._note_mu:
            outcomes, self._outcomes = self._outcomes, {}
        per_tenant: Dict[str, List[int]] = {}
        for (tenant, outcome), n in outcomes.items():
            tot = per_tenant.setdefault(tenant, [0, 0])
            tot[0] += n
            if outcome == "deadline":
                tot[1] += n
        all_n = sum(t[0] for t in per_tenant.values())
        all_miss = sum(t[1] for t in per_tenant.values())
        self._push("serve_deadline_miss_ratio", now,
                   (all_miss / all_n) if all_n else 0.0)
        for tenant, (n, miss) in per_tenant.items():
            if n:
                self._push(f"serve_deadline_miss_ratio.{tenant}", now,
                           miss / n)
        e2e = get_registry().instruments().get("blaze_serve_e2e_seconds")
        p99 = self._tick.get("blaze_serve_e2e_seconds:p99") \
            if isinstance(e2e, Histogram) else None
        if p99 is not None:
            self._push("serve_p99_ms", now, p99 * 1e3)

        # cache + ingest: stale-served rate, lag/backlog probe, hit ratio
        stale = get_registry().instruments().get("blaze_cache_stale_total")
        served = 0
        if isinstance(stale, Counter):
            served = sum(v for k, v in stale.series().items()
                         if dict(k).get("result") == "served")
        d = self._labeled_delta("blaze_cache_stale_total", "stale_served",
                                {"served": served})
        self._push("cache_stale_served_rate", now, rate(d.get("served", 0)))
        cache = getattr(sess, "cache", None) if sess is not None else None
        if cache is not None:
            try:
                probe = cache.ingest_lag_probe()
                self._push("ingest_lag_versions", now,
                           float(probe["ingest_lag_versions"]))
                self._push("cache_refresh_backlog_count", now,
                           float(probe["refresh_backlog"]))
                for table, lag in probe["per_table"].items():
                    self._push(f"ingest_lag_versions.{table}", now,
                               float(lag))
            except Exception:
                pass
        hits = get_registry().instruments().get("blaze_cache_hits_total")
        misses = get_registry().instruments().get("blaze_cache_misses_total")
        if isinstance(hits, Counter) and isinstance(misses, Counter):
            d = self._labeled_delta(
                "blaze_cache_hit_ratio", "hit_ratio",
                {"hits": hits.total(), "misses": misses.total()})
            lookups = d.get("hits", 0) + d.get("misses", 0)
            if lookups:
                self._push("cache_hit_ratio", now,
                           d.get("hits", 0) / lookups)

        # ingest append/row rates from the registry counters
        self._push("ingest_append_rate", now,
                   self._tick.get("blaze_ingest_appends_total:rate", 0.0))
        self._push("ingest_rows_rate", now,
                   self._tick.get("blaze_ingest_rows_total:rate", 0.0))

        # memmgr / shuffle / workers
        try:
            from blaze_tpu.runtime.memmgr import MemManager

            mm = MemManager._instance
            self._push("memmgr_used_bytes", now,
                       float(mm.used) if mm is not None else 0.0)
        except Exception:
            pass
        self._push("shuffle_tier_degraded_rate", now, self._tick.get(
            "blaze_shuffle_tier_degraded_total:rate", 0.0))
        self._push("worker_deaths_rate", now, self._tick.get(
            "blaze_cluster_worker_deaths_total:rate", 0.0))

    # -- SLO / health evaluation -----------------------------------------------

    def _burn(self, ring: Ring, now: float, window: float) -> float:
        vals = [v for t, v in ring.items() if t >= now - window]
        if not vals:
            return 0.0
        return (sum(vals) / len(vals)) / self.budget_ratio

    def _eval_slos(self, now: float):
        for sl in self._slos:
            val = self._tick.get(sl.series)
            if val is None:
                continue  # no data this tick: no budget spent
            ok = sl.check(val)
            sl.last_value = val
            sl.ring.append(now, 0.0 if ok else 1.0)
            if not ok:
                _SLO_BREACHES.labels(slo=sl.key).inc()
            sl.burn_fast = self._burn(sl.ring, now, self.fast_window_s)
            sl.burn_slow = self._burn(sl.ring, now, self.slow_window_s)
            if sl.burn_fast >= self.critical_burn and \
                    sl.burn_slow >= self.critical_burn:
                sl.state = "critical"
            elif sl.burn_fast >= self.degraded_burn:
                sl.state = "degraded"
            else:
                sl.state = "healthy"

    def _eval_health(self, now: float):
        worst: Dict[str, SloSpec] = {}
        for sl in self._slos:
            cur = worst.get(sl.subsystem)
            if cur is None or _SEVERITY[sl.state] > _SEVERITY[cur.state]:
                worst[sl.subsystem] = sl
        for sub in SUBSYSTEMS:
            sl = worst.get(sub)
            new = sl.state if sl is not None else "healthy"
            old = self._sub_state[sub]
            if new == old:
                continue
            since = self._sub_since.get(sub, self._started_wall or now)
            if old != "healthy":
                self._intervals.append(
                    {"subsystem": sub, "state": old,
                     "start": since, "end": now})
                del self._intervals[:-self._HISTORY_MAX]
            trans = {"t": now, "subsystem": sub, "from": old, "to": new,
                     "slo": sl.key if sl is not None else None,
                     "value": sl.last_value if sl is not None else None,
                     "burn_fast": round(sl.burn_fast, 4) if sl else None,
                     "burn_slow": round(sl.burn_slow, 4) if sl else None}
            self._transitions.append(trans)
            del self._transitions[:-self._HISTORY_MAX]
            self._sub_state[sub] = new
            self._sub_since[sub] = now
            _SLO_TRANSITIONS.labels(subsystem=sub, state=new).inc()
            self._record_transition_incident(trans)

    def _record_transition_incident(self, trans: dict):
        from blaze_tpu.obs.dump import record_incident

        record_incident(
            "health", f"{trans['subsystem']}:{trans['from']}-{trans['to']}",
            conf=self._conf, extra=dict(trans))

    # -- read side -------------------------------------------------------------

    def names(self) -> List[str]:
        with self._mu:
            return sorted(self._series)

    def series_since(self, name: str,
                     since: float = 0.0) -> Optional[List[List[float]]]:
        """Samples of one series as ``[[t, v], ...]`` (None for an unknown
        name — the HTTP 404)."""
        with self._mu:
            ring = self._series.get(name)
            if ring is None:
                return None
            return [[t, v] for t, v in ring.since(since)]

    def latest(self, name: str) -> Optional[float]:
        with self._mu:
            ring = self._series.get(name)
            last = ring.last() if ring is not None else None
            return last[1] if last is not None else None

    def health_report(self, now: Optional[float] = None) -> dict:
        """The /debug/health payload and the artifact ``health`` section:
        current per-subsystem states, SLO burn rates, the transition
        history, and the interval accounting gates judge (any critical
        interval, degraded-time ratio)."""
        now = time.time() if now is None else now
        with self._mu:
            end = self._last_t if self._last_t is not None else now
            intervals = list(self._intervals)
            for sub, st in self._sub_state.items():
                if st != "healthy":
                    intervals.append(
                        {"subsystem": sub, "state": st,
                         "start": self._sub_since.get(
                             sub, self._started_wall or end),
                         "end": end, "open": True})
            degraded_s = sum(iv["end"] - iv["start"] for iv in intervals)
            critical = [iv for iv in intervals if iv["state"] == "critical"]
            critical_s = sum(iv["end"] - iv["start"] for iv in critical)
            wall_s = (end - self._started_wall) \
                if self._started_wall is not None else 0.0
            return {
                "enabled": self.enabled,
                "interval_s": self.interval_s,
                "wall_s": round(wall_s, 3),
                "samples": self._samples,
                "subsystems": {
                    sub: {"state": st,
                          "since": self._sub_since.get(sub)}
                    for sub, st in self._sub_state.items()},
                "slo": {sl.key: sl.snapshot() for sl in self._slos},
                "transitions": list(self._transitions),
                "intervals": intervals,
                "degraded_s": round(degraded_s, 3),
                "critical_s": round(critical_s, 3),
                "critical_intervals": len(critical),
                "degraded_ratio": round(degraded_s / wall_s, 4)
                if wall_s > 0 else 0.0,
            }


def _merged_snapshot(inst: Histogram) -> Optional[dict]:
    """One snapshot merged across every label set (the sampler tracks the
    instrument, not its label fan-out)."""
    merged = None
    for key in list(inst.series()):
        st = inst.snapshot(**dict(key))
        if st is None:
            continue
        if merged is None:
            merged = {"buckets": dict(st["buckets"]), "sum": st["sum"],
                      "count": st["count"]}
        else:
            for i, c in st["buckets"].items():
                merged["buckets"][i] = merged["buckets"].get(i, 0) + c
            merged["sum"] += st["sum"]
            merged["count"] += st["count"]
    return merged


TIMELINE = Timeline()


def get_timeline() -> Timeline:
    return TIMELINE


def configure_from(conf, session=None) -> Timeline:
    """Session/worker hook: apply knobs and (driver side, when a session
    is given and the plane is enabled) start the sampler bound to it.
    BLAZE_TPU_TIMELINE=0/1 force-overrides. Never raises — the health
    plane failing to start must not fail the session."""
    import os

    try:
        TIMELINE.configure(conf)
    except ValueError:
        pass  # malformed slo_specs: keep the previous objectives
    env = os.environ.get("BLAZE_TPU_TIMELINE", "")
    if env:
        enabled = env not in ("0", "false", "no")
    else:
        enabled = bool(getattr(conf, "timeline_enabled", True))
    if not enabled:
        TIMELINE.stop()
    elif session is not None:
        TIMELINE.start(session)
    return TIMELINE
