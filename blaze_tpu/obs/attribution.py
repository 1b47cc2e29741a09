"""The why-is-it-slow plane: exclusive wall-time attribution, critical-path
extraction, and the fusion/placement decision audit.

The tracer (obs/tracer.py) already records *what happened* — kernel
dispatches, shuffle fetches, spills, operator spans — but every perf
investigation starts by re-deriving *where the wall went* by hand. This
module closes that gap in three layers:

- **Exclusive decomposition** (:func:`exclusive_times`): every span the
  tracer emits is classified into a fixed category taxonomy
  (:data:`CATEGORIES`) and a priority interval sweep attributes each
  instant of the query window to exactly ONE category — the most specific
  span active at that instant (a kernel dispatch inside a task inside an
  operator counts as kernel time, not three times). By construction
  ``sum(categories) <= wall`` (a union of intervals).
  Like DEVICE_STATS deltas, the per-query binding is by time window —
  exact for a query running alone (bench/tests), an upper bound under
  concurrency. Worker spans participate because they were already absorbed
  onto the driver timeline (``Tracer.absorb``) before the query finishes.

- **Critical path** (:func:`critical_path`): the stage spans of one query
  form a sequential dependency chain (stage N+1 reads stage N's shuffle
  output); within each stage the longest task is the binding constraint,
  and its operator spans say which operator to blame. Rendered in
  ``explain_analyze``, ``/debug/queries`` and the fingerprint profile.

- **Decision audit**: `ir/fusion.py` and `runtime/placement.py` call the
  ``note_*`` hooks here so artifacts can answer "why did fusion break this
  chain" (``fusion_break_reasons``), "what fraction of fusable operators
  actually fused" (``fused_op_fraction`` — the ROADMAP item 1 coverage
  tripwire), and "why did placement decline the device". Counters live in
  the process registry, so worker-side decisions merge into the driver via
  the existing telemetry-delta path for free.

Everything here is read-side and best-effort: attribution never raises
into the execution path, and with tracing + flight recorder both off the
only cost is one ``TRACER.active`` check per query.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from blaze_tpu.obs.telemetry import get_registry
from blaze_tpu.obs.tracer import TRACER

# -- taxonomy ------------------------------------------------------------------

# Display/schema order. "framework" is the explicit remainder bucket: task/
# operator machinery time not claimed by a more specific category.
CATEGORIES = (
    "queue_wait",
    "jit_compile",
    "kernel_compute",
    "collective",
    "transfer",
    "shuffle_write",
    "shuffle_fetch",
    "spill",
    "framework",
)

# Sweep priority, most specific first: at any instant the highest-priority
# active category owns the time. jit_compile outranks everything (a compile
# stall is never "kernel compute"); collective outranks kernel_compute so a
# mesh exchange doesn't read as plain dispatch; framework is last — it only
# collects time no specific span covers.
PRIORITY = (
    "jit_compile",
    "collective",
    "kernel_compute",
    "transfer",
    "spill",
    "shuffle_write",
    "shuffle_fetch",
    "queue_wait",
    "framework",
)

# Profile/artifact field names, one per category.
CATEGORY_FIELDS = tuple(f"{c}_time_ns" for c in CATEGORIES)

# Stable Chrome trace-viewer palette names per category (satellite: Perfetto
# renders the same work in the same color across traces and rounds).
CATEGORY_CNAME = {
    "queue_wait": "grey",
    "jit_compile": "terrible",
    "kernel_compute": "thread_state_running",
    "collective": "rail_animation",
    "transfer": "yellow",
    "shuffle_write": "rail_response",
    "shuffle_fetch": "thread_state_iowait",
    "spill": "bad",
    "framework": "generic_work",
}

_ATTR_SECONDS = get_registry().counter(
    "blaze_attr_exclusive_seconds",
    "exclusive wall seconds attributed per category across finished queries")

_EPS_US = 1.0  # ignore sub-µs slivers from float boundary arithmetic


def classify_span(name: str, cat: str) -> Optional[str]:
    """Map one tracer span (its name + tracer category) to an attribution
    category, or None for container/meta spans (query, stage, instants)
    that must not claim exclusive time themselves."""
    if cat == "kernel":
        return "jit_compile" if name.startswith("jit_compile") \
            else "kernel_compute"
    if cat == "collective":
        return "collective"
    if cat == "transfer":
        return "transfer"
    if cat == "spill":
        return "spill"
    if cat == "shuffle":
        return "shuffle_write" if name.startswith("shuffle_write") \
            else "shuffle_fetch"
    if cat == "queue":
        return "queue_wait"
    if cat in ("operator", "task"):
        return "framework"
    return None  # "stage", "query", instants, metadata


# -- exclusive decomposition ---------------------------------------------------


def exclusive_times(events: List[dict], t0_us: float,
                    t1_us: float) -> Dict[str, float]:
    """Priority interval sweep over classified spans clipped to the window
    ``[t0_us, t1_us]``. Returns exclusive µs per category; the values sum
    to the union of all classified spans within the window, hence never
    exceed the window length."""
    ncat = len(PRIORITY)
    prio = {c: i for i, c in enumerate(PRIORITY)}
    points: List[Tuple[float, int, int]] = []  # (time, +1/-1, cat_idx)
    for ev in events:
        if ev.get("ph") != "X":
            continue
        c = classify_span(ev.get("name", ""), ev.get("cat", ""))
        if c is None:
            continue
        s = float(ev.get("ts", 0.0))
        e = s + float(ev.get("dur", 0.0))
        s = max(s, t0_us)
        e = min(e, t1_us)
        if e <= s:
            continue
        ci = prio[c]
        points.append((s, 1, ci))
        points.append((e, -1, ci))
    points.sort(key=lambda p: p[0])
    active = [0] * ncat
    out = [0.0] * ncat
    prev: Optional[float] = None
    for t, delta, ci in points:
        if prev is not None and t > prev:
            for i in range(ncat):
                if active[i]:
                    out[i] += t - prev
                    break
        active[ci] += delta
        prev = t
    return {PRIORITY[i]: out[i] for i in range(ncat)}


# -- critical path -------------------------------------------------------------


def _overlaps(ev: dict, lo: float, hi: float) -> bool:
    s = float(ev.get("ts", 0.0))
    return s < hi and s + float(ev.get("dur", 0.0)) > lo


def _op_summary(events: List[dict], lo: float, hi: float,
                pid: Optional[int] = None, tid: Optional[int] = None,
                top: int = 3) -> List[dict]:
    """Top operators by self time among operator spans inside the window
    (optionally pinned to one process/thread — the critical task's)."""
    agg: Dict[str, float] = {}
    for ev in events:
        if ev.get("ph") != "X" or ev.get("cat") != "operator":
            continue
        if pid is not None and ev.get("pid") != pid:
            continue
        if tid is not None and ev.get("tid") != tid:
            continue
        s = float(ev.get("ts", 0.0))
        if s < lo - _EPS_US or s + float(ev.get("dur", 0.0)) > hi + _EPS_US:
            continue
        args = ev.get("args") or {}
        self_ms = args.get("self_time_ms")
        if self_ms is None:
            self_ms = float(ev.get("dur", 0.0)) / 1e3
        name = ev.get("name", "?")
        agg[name] = agg.get(name, 0.0) + float(self_ms)
    ranked = sorted(agg.items(), key=lambda kv: (-kv[1], kv[0]))[:top]
    return [{"op": k, "self_time_ms": round(v, 3)} for k, v in ranked]


def critical_path(events: List[dict], t0_us: float,
                  t1_us: float) -> List[dict]:
    """Longest dependent chain through one query's span DAG. Stages are
    sequential by construction (each reads its upstream's shuffle output),
    so the chain is: per stage, its slowest task (with that task's top
    operators); between and after stages, driver/result segments. Segment
    structure (kinds, names, operator names) is deterministic for a fixed
    plan — only the times move."""
    evs = [e for e in events if e.get("ph") == "X" and _overlaps(e, t0_us, t1_us)]
    stages = sorted((e for e in evs if e.get("cat") == "stage"),
                    key=lambda e: float(e.get("ts", 0.0)))
    segments: List[dict] = []
    cursor = t0_us
    for s in stages:
        s0 = max(float(s.get("ts", 0.0)), t0_us)
        s1 = min(float(s.get("ts", 0.0)) + float(s.get("dur", 0.0)), t1_us)
        if s1 <= s0:
            continue
        if s0 - cursor > _EPS_US:
            segments.append({"kind": "driver", "name": "driver",
                             "dur_ms": round((s0 - cursor) / 1e3, 3)})
        name = s.get("name", "stage")
        try:
            stage_id: Optional[int] = int(name.rsplit("_", 1)[-1])
        except (ValueError, IndexError):
            stage_id = None
        tasks = [t for t in evs if t.get("cat") == "task"
                 and _overlaps(t, s0, s1)
                 and (stage_id is None
                      or (t.get("args") or {}).get("stage") in (None, stage_id))]
        seg = {"kind": "stage", "name": name, "stage": stage_id,
               "dur_ms": round((s1 - s0) / 1e3, 3), "operators": []}
        if tasks:
            crit = max(tasks, key=lambda t: float(t.get("dur", 0.0)))
            c0 = float(crit.get("ts", 0.0))
            c1 = c0 + float(crit.get("dur", 0.0))
            seg["task"] = (crit.get("args") or {}).get("map")
            seg["task_ms"] = round(float(crit.get("dur", 0.0)) / 1e3, 3)
            seg["operators"] = _op_summary(
                evs, c0, c1, pid=crit.get("pid"), tid=crit.get("tid"))
        segments.append(seg)
        cursor = max(cursor, s1)
    if t1_us - cursor > _EPS_US:
        seg = {"kind": "result", "name": "result",
               "dur_ms": round((t1_us - cursor) / 1e3, 3),
               "operators": _op_summary(evs, cursor, t1_us)}
        segments.append(seg)
    return segments


def critical_path_lines(segments: List[dict]) -> List[str]:
    """Compact text rendering for explain_analyze / /debug/queries."""
    lines = []
    for seg in segments or []:
        parts = [seg.get("name", seg.get("kind", "?")),
                 f"{seg.get('dur_ms', 0.0):.1f}ms"]
        if seg.get("task") is not None:
            parts.append(f"task {seg['task']} ({seg.get('task_ms', 0.0):.1f}ms)")
        ops = seg.get("operators") or []
        if ops:
            parts.append("ops: " + ", ".join(
                f"{o['op']} {o['self_time_ms']:.1f}ms" for o in ops))
        lines.append(" ".join(parts))
    return lines


# -- per-query entry point -----------------------------------------------------


def query_attribution(t0_perf_ns: int, dur_ns: int,
                      events: Optional[List[dict]] = None,
                      note_totals: bool = True) -> dict:
    """Exclusive category decomposition + critical path for one query's
    ``[t0, t0+dur]`` window on this process's tracer timeline. Uses the
    full trace buffer when tracing is on, else the flight-recorder ring
    (partial coverage — the ring only keeps the newest spans). Never
    raises; returns ns integers satisfying ``sum(categories) <= wall_ns``.
    """
    tr = TRACER
    if events is None:
        events = tr.snapshot() if tr.enabled else tr.ring_snapshot()
    t0_us = (t0_perf_ns - tr.perf_epoch_ns) / 1e3
    t1_us = t0_us + dur_ns / 1e3
    cats_us = exclusive_times(events, t0_us, t1_us)
    wall_ns = max(0, int(dur_ns))
    cats_ns = {c: int(cats_us.get(c, 0.0) * 1000.0) for c in CATEGORIES}
    attributed = sum(cats_ns.values())
    if wall_ns and attributed > wall_ns:
        # float boundary slack only; rescale to keep the invariant exact
        scale = wall_ns / attributed
        cats_ns = {c: int(v * scale) for c, v in cats_ns.items()}
        attributed = sum(cats_ns.values())
    if note_totals:
        for c, v in cats_ns.items():
            if v > 0:
                _ATTR_SECONDS.labels(category=c).inc(v / 1e9)
    return {
        "categories": {f"{c}_time_ns": cats_ns[c] for c in CATEGORIES},
        "wall_ns": wall_ns,
        "attributed_ns": attributed,
        "coverage_fraction": round(attributed / wall_ns, 4) if wall_ns else 0.0,
        "critical_path": critical_path(events, t0_us, t1_us),
    }


def note_queue_wait(seconds: float) -> None:
    """Admission wait is spent BEFORE a query's execute window opens, so
    the per-query sweep never sees it — the serve scheduler books it into
    the process totals directly (and emits the queue span for traces)."""
    if seconds > 0:
        _ATTR_SECONDS.labels(category="queue_wait").inc(float(seconds))


# -- decision audit ------------------------------------------------------------

# Why fusion ended a chain at a boundary (ir/fusion.py) or never started
# one. Closed vocabulary — check_metrics_names.py lints it.
FUSION_BREAK_REASONS = (
    "blocking_op",        # structural boundary: agg/sort/join/exchange/scan
    "host_schema",        # a schema in/out of the chain is not fully device
    "pyudf",              # python UDF in the expression tree
    "unfusable_expr",     # expression fails the pure-device trace check
    "schema_error",       # schema resolution raised mid-walk
    "cost_below_min_saved",  # saved dispatches < fusion_min_saved_dispatches
    "agg_filter_guard",   # filter left for the fused_filter_agg kernel
)

PLACEMENT_DECLINE_REASONS = (
    "conf_forced_host",          # device_placement="host"
)

_TM_FUSION_BREAKS = get_registry().counter(
    "blaze_fusion_break_reasons_total",
    "fusion chain boundaries by reason the chain could not continue")
_TM_FUSION_OPS_FUSED = get_registry().counter(
    "blaze_fusion_ops_fused_total",
    "narrow operators absorbed into FusedStage chains")
_TM_FUSION_OPS_ELIGIBLE = get_registry().counter(
    "blaze_fusion_ops_eligible_total",
    "narrow operators of fusable kind seen by the fusion pass")
_TM_PLACE_DECISIONS = get_registry().counter(
    "blaze_placement_decisions_total",
    "stage placement decisions by chosen side")
_TM_PLACE_DECLINES = get_registry().counter(
    "blaze_placement_decline_reasons_total",
    "device-placement declines by reason the host side won")


def note_fusion_break(reason: str) -> None:
    _TM_FUSION_BREAKS.labels(reason=reason).inc()


def note_fusion_chain(fused_ops: int, eligible_ops: int) -> None:
    if eligible_ops:
        _TM_FUSION_OPS_ELIGIBLE.inc(eligible_ops)
    if fused_ops:
        _TM_FUSION_OPS_FUSED.inc(fused_ops)


def note_placement(where: str, reason: Optional[str] = None) -> None:
    _TM_PLACE_DECISIONS.labels(where=where).inc()
    if reason:
        _TM_PLACE_DECLINES.labels(reason=reason).inc()


def _by_label(counter, label: str) -> Dict[str, int]:
    out: Dict[str, int] = {}
    for key, v in counter.series().items():
        name = dict(key).get(label)
        if name is not None:
            out[name] = out.get(name, 0) + int(v)
    return dict(sorted(out.items()))


def audit_snapshot() -> dict:
    """Raw audit totals (for per-query deltas: snapshot at query start,
    pass back to :func:`decision_audit` at the end)."""
    return {
        "ops_fused": _TM_FUSION_OPS_FUSED.total(),
        "ops_eligible": _TM_FUSION_OPS_ELIGIBLE.total(),
        "fusion_break_reasons": _by_label(_TM_FUSION_BREAKS, "reason"),
        "placement_decisions": _by_label(_TM_PLACE_DECISIONS, "where"),
        "placement_decline_reasons": _by_label(_TM_PLACE_DECLINES, "reason"),
    }


def decision_audit(since: Optional[dict] = None) -> dict:
    """The fusion/placement decision-audit section for profiles and
    artifacts: counts (since ``since``, a prior :func:`audit_snapshot`)
    plus the ``fused_op_fraction`` coverage tripwire (None when nothing
    eligible ran — distinguishable from a measured 0.0)."""
    now = audit_snapshot()
    if since:
        def delta_map(k):
            prev = since.get(k) or {}
            return {r: v - prev.get(r, 0) for r, v in (now.get(k) or {}).items()
                    if v - prev.get(r, 0) > 0}

        now = {
            "ops_fused": now["ops_fused"] - since.get("ops_fused", 0),
            "ops_eligible": now["ops_eligible"] - since.get("ops_eligible", 0),
            "fusion_break_reasons": delta_map("fusion_break_reasons"),
            "placement_decisions": delta_map("placement_decisions"),
            "placement_decline_reasons": delta_map("placement_decline_reasons"),
        }
    elig = now["ops_eligible"]
    now["fused_op_fraction"] = round(now["ops_fused"] / elig, 4) if elig else None
    return now
