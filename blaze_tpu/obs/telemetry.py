"""Process-wide metrics registry with Prometheus exposition.

The reference engine's per-query ``MetricNode`` tree (auron/src/metrics.rs)
answers "what did THIS query cost"; a serving fleet also needs the
continuous view — counters/gauges/histograms you can scrape at any moment,
latency distributions per outcome class, spill/shuffle volume over time.
This module is that layer: one :class:`MetricsRegistry` per process holding
typed instruments, rendered as Prometheus text at ``GET /metrics`` and as
exact machine-readable values at ``GET /debug/metrics?format=raw``.

Design constraints:

- **Hot-path cost**: instruments are *lock-striped* — each instrument owns
  its own small mutex, so concurrent task threads updating different
  instruments never contend; one update is a dict upsert under that lock
  (well under 1µs). When the registry is disabled every mutator returns on
  a single attribute check, so handles cached at call sites become no-ops.
- **Log-bucketed histograms**: latency and byte values span 6+ orders of
  magnitude; buckets are exponential with 4 per octave (bounds 2^(k/4),
  ~19% relative width) stored sparsely, so one histogram covers ns..hours
  or bytes..TB without per-instrument bound tuning.
- **Naming convention**: ``blaze_<area>_<name>_<unit>`` with the unit drawn
  from a fixed vocabulary — enforced at registration time here and
  statically by ``scripts/check_metrics_names.py``. Registering one name
  with two different types raises.
- **Worker shipping**: worker processes mutate their own (child) registry;
  :meth:`MetricsRegistry.drain_deltas` snapshots-and-zeroes counters and
  histograms so the delta rides back in the task reply (same pattern as
  the tracer's span shipping), and :meth:`merge_deltas` folds it into the
  driver registry (runtime/cluster.py does this on first task completion).
"""

from __future__ import annotations

import math
import os
import re
import threading
from typing import Callable, Dict, List, Optional, Tuple

ALLOWED_UNITS = ("total", "seconds", "bytes", "count", "rows", "ratio")

_SEGMENT_RE = re.compile(r"^[a-z][a-z0-9]*$")

# histogram bucketing: 4 buckets per power of two; bucket k holds values in
# [2^(k/4), 2^((k+1)/4)) — ~19% relative width, sparse storage; the reported
# Prometheus `le` for bucket k is 2^((k+1)/4), which is a valid inclusive
# upper bound for everything the bucket holds
BUCKETS_PER_OCTAVE = 4
_MIN_IDX = -160  # 2^-40: below any observable seconds/bytes value
_MAX_IDX = 240   # 2^60: above any


def bucket_index(value: float) -> int:
    """Sparse log-bucket index for a non-negative observation."""
    if value <= 0:
        return _MIN_IDX
    idx = math.floor(math.log2(value) * BUCKETS_PER_OCTAVE)
    return max(_MIN_IDX, min(_MAX_IDX, int(idx)))


def bucket_upper_bound(idx: int) -> float:
    """Inclusive upper bound (Prometheus ``le``) of bucket ``idx``."""
    return 2.0 ** ((idx + 1) / BUCKETS_PER_OCTAVE)


def validate_name(name: str):
    """Enforce ``blaze_<area>_<name>_<unit>`` (>= 4 segments, known unit)."""
    parts = name.split("_")
    if len(parts) < 4 or parts[0] != "blaze":
        raise ValueError(
            f"instrument name {name!r} must follow blaze_<area>_<name>_<unit>")
    for p in parts[1:]:
        if not _SEGMENT_RE.match(p):
            raise ValueError(
                f"instrument name {name!r}: segment {p!r} must be [a-z0-9]+")
    if parts[-1] not in ALLOWED_UNITS:
        raise ValueError(
            f"instrument name {name!r}: unit {parts[-1]!r} not in "
            f"{ALLOWED_UNITS}")


def _label_key(kw: dict) -> Tuple[Tuple[str, str], ...]:
    return tuple(sorted((str(k), str(v)) for k, v in kw.items()))


def _label_str(key: Tuple[Tuple[str, str], ...], extra: str = "") -> str:
    inner = ",".join(f'{k}="{v}"' for k, v in key)
    if extra:
        inner = f"{inner},{extra}" if inner else extra
    return "{" + inner + "}" if inner else ""


class _Instrument:
    kind = "untyped"

    def __init__(self, registry: "MetricsRegistry", name: str, help: str = ""):
        self._reg = registry
        self.name = name
        self.help = help
        self._mu = threading.Lock()  # per-instrument lock (striping)
        self._series: Dict[Tuple, object] = {}
        self._bound: Dict[Tuple, object] = {}

    def labels(self, **kw):
        """Bound child for one label set; cached, so hot call sites can keep
        the returned handle and skip the dict/tuple work entirely."""
        key = _label_key(kw)
        b = self._bound.get(key)
        if b is None:
            with self._mu:
                b = self._bound.setdefault(key, self._bind(key))
        return b

    def _bind(self, key):
        raise NotImplementedError

    def clear(self):
        with self._mu:
            self._series.clear()
            self._bound.clear()

    def series(self) -> Dict[Tuple, object]:
        """Snapshot of every label series: ``{label_key_tuple: value}``
        (read-side accessor for audit/attribution aggregation)."""
        with self._mu:
            return dict(self._series)


class _BoundCounter:
    __slots__ = ("_c", "_key")

    def __init__(self, c: "Counter", key):
        self._c = c
        self._key = key

    def inc(self, n: int = 1):
        self._c._inc(self._key, n)


class Counter(_Instrument):
    kind = "counter"

    def inc(self, n: int = 1):
        self._inc((), n)

    def _inc(self, key, n):
        if not self._reg.enabled:
            return
        with self._mu:
            self._series[key] = self._series.get(key, 0) + n

    def _bind(self, key):
        return _BoundCounter(self, key)

    def value(self, **kw) -> int:
        with self._mu:
            return int(self._series.get(_label_key(kw), 0))

    def total(self) -> int:
        with self._mu:
            return int(sum(self._series.values()))


class _BoundGauge:
    __slots__ = ("_g", "_key")

    def __init__(self, g: "Gauge", key):
        self._g = g
        self._key = key

    def set(self, v):
        self._g._set(self._key, v)


class Gauge(_Instrument):
    kind = "gauge"

    def __init__(self, registry, name, help=""):
        super().__init__(registry, name, help)
        self._fn: Optional[Callable[[], float]] = None

    def set(self, v):
        self._set((), v)

    def _set(self, key, v):
        if not self._reg.enabled:
            return
        with self._mu:
            self._series[key] = v

    def set_function(self, fn: Callable[[], float]):
        """Collect-time callback (unlabeled): evaluated at exposition, so
        gauges mirroring live state (headroom, queue depth) cost nothing
        between scrapes. Re-binding replaces the previous callback."""
        self._fn = fn

    def remove(self, **kw):
        """Drop one label set (e.g. a released per-query memory group) so
        exposition cardinality tracks live state, not history."""
        key = _label_key(kw)
        with self._mu:
            self._series.pop(key, None)
            self._bound.pop(key, None)

    def _bind(self, key):
        return _BoundGauge(self, key)

    def value(self, **kw):
        if self._fn is not None and not kw:
            try:
                return self._fn()
            except Exception:
                return None
        with self._mu:
            return self._series.get(_label_key(kw))


class _BoundHistogram:
    __slots__ = ("_h", "_key")

    def __init__(self, h: "Histogram", key):
        self._h = h
        self._key = key

    def observe(self, v):
        self._h._observe(self._key, v)


class Histogram(_Instrument):
    kind = "histogram"

    def observe(self, v):
        self._observe((), v)

    def _observe(self, key, v):
        if not self._reg.enabled:
            return
        idx = bucket_index(v)
        with self._mu:
            st = self._series.get(key)
            if st is None:
                st = self._series[key] = [{}, 0.0, 0]  # buckets, sum, count
            st[0][idx] = st[0].get(idx, 0) + 1
            st[1] += v
            st[2] += 1

    def _bind(self, key):
        return _BoundHistogram(self, key)

    def snapshot(self, **kw) -> Optional[dict]:
        with self._mu:
            st = self._series.get(_label_key(kw))
            if st is None:
                return None
            return {"buckets": dict(st[0]), "sum": st[1], "count": st[2]}

    def count(self, **kw) -> int:
        st = self.snapshot(**kw)
        return st["count"] if st else 0

    def snapshot_delta(self, prev: Optional[dict], **kw) -> Optional[dict]:
        """Interval view since ``prev`` (a previous :meth:`snapshot` of the
        SAME label set): :func:`delta_snapshot` of a snapshot taken now. A
        caller that CHAINS intervals keeps the snapshot it took and calls
        :func:`delta_snapshot` itself: a second ``snapshot()`` for the next
        ``prev`` would be a second read, and what lands between the two is
        counted twice."""
        cur = self.snapshot(**kw)
        return None if cur is None else delta_snapshot(cur, prev)

    def quantile(self, q: float, **kw) -> Optional[float]:
        st = self.snapshot(**kw)
        if not st or not st["count"]:
            return None
        pairs = [(bucket_upper_bound(i), c)
                 for i, c in sorted(st["buckets"].items())]
        cum = []
        run = 0
        for le, c in pairs:
            run += c
            cum.append((le, run))
        return quantile_from_le_buckets(cum, q)


def delta_snapshot(cur: dict, prev: Optional[dict]) -> dict:
    """``cur`` less ``prev``, two :meth:`Histogram.snapshot` dicts of one
    series: bucket-vector subtraction for windowed quantiles. A snapshot is
    taken under the instrument lock, so a concurrent ``observe()`` either
    lands fully in it or not at all — its buckets sum to its count, and
    buckets only grow, which makes every delta non-negative. A shrunk count
    (``reset_values`` between samples) returns ``cur`` whole instead of a
    negative delta."""
    if not prev or cur["count"] < prev["count"]:
        return cur
    buckets = {}
    prev_buckets = prev["buckets"]
    for i, c in cur["buckets"].items():
        d = c - prev_buckets.get(i, 0)
        if d > 0:
            buckets[i] = d
    return {"buckets": buckets,
            "sum": cur["sum"] - prev["sum"],
            "count": cur["count"] - prev["count"]}


def quantile_from_snapshot(snap: Optional[dict],
                           q: float) -> Optional[float]:
    """Quantile of one ``snapshot()``/``snapshot_delta()`` dict — how the
    timeline sampler turns an interval bucket delta into a windowed
    p50/p95/p99 without touching the live instrument again."""
    if not snap or not snap.get("count"):
        return None
    cum = []
    run = 0
    for i in sorted(snap["buckets"]):
        run += snap["buckets"][i]
        cum.append((bucket_upper_bound(int(i)), run))
    return quantile_from_le_buckets(cum, q)


def quantile_from_le_buckets(pairs: List[Tuple[float, int]],
                             q: float) -> Optional[float]:
    """Nearest-rank quantile from cumulative ``(le, cum_count)`` pairs (the
    shape both our exposition and a parsed Prometheus scrape produce), with
    log-linear interpolation inside the winning bucket."""
    pairs = sorted((le, c) for le, c in pairs)
    if not pairs:
        return None
    total = pairs[-1][1]
    if total <= 0:
        return None
    target = max(1, math.ceil(q * total))
    prev_le, prev_cum = None, 0
    for le, cum in pairs:
        if cum >= target:
            if not math.isfinite(le):
                return prev_le  # everything above the last finite bound
            if prev_le is None or prev_le <= 0:
                return le
            frac = (target - prev_cum) / max(cum - prev_cum, 1)
            return prev_le * (le / prev_le) ** frac
        prev_le, prev_cum = le, cum
    return pairs[-1][0] if math.isfinite(pairs[-1][0]) else prev_le


class MetricsRegistry:
    """Typed instrument registry. ``counter``/``gauge``/``histogram`` are
    idempotent by name (same name returns the same instrument; same name
    with a different type raises)."""

    def __init__(self, enabled: bool = True):
        self._mu = threading.Lock()
        self._instruments: Dict[str, _Instrument] = {}
        self.enabled = enabled

    # -- registration ----------------------------------------------------------

    def _get(self, cls, name: str, help: str) -> _Instrument:
        inst = self._instruments.get(name)
        if inst is None:
            validate_name(name)
            with self._mu:
                inst = self._instruments.get(name)
                if inst is None:
                    inst = self._instruments[name] = cls(self, name, help)
        if type(inst) is not cls:
            raise ValueError(
                f"instrument {name!r} already registered as {inst.kind}, "
                f"cannot re-register as {cls.kind}")
        return inst

    def counter(self, name: str, help: str = "") -> Counter:
        return self._get(Counter, name, help)

    def gauge(self, name: str, help: str = "") -> Gauge:
        return self._get(Gauge, name, help)

    def histogram(self, name: str, help: str = "") -> Histogram:
        return self._get(Histogram, name, help)

    def instruments(self) -> Dict[str, _Instrument]:
        with self._mu:
            return dict(sorted(self._instruments.items()))

    def reset_values(self):
        """Zero every instrument but KEEP registrations: handles cached at
        call sites (module globals, operator state) stay valid."""
        for inst in self.instruments().values():
            inst.clear()

    # -- exposition ------------------------------------------------------------

    def to_prometheus(self) -> str:
        """Prometheus text format 0.0.4."""
        lines: List[str] = []
        for name, inst in self.instruments().items():
            if inst.help:
                lines.append(f"# HELP {name} {inst.help}")
            lines.append(f"# TYPE {name} {inst.kind}")
            if isinstance(inst, Gauge) and inst._fn is not None:
                v = inst.value()
                if v is not None:
                    lines.append(f"{name} {_fmt_val(v)}")
            with inst._mu:
                series = sorted(inst._series.items())
            for key, st in series:
                if isinstance(inst, Histogram):
                    buckets, total, count = dict(st[0]), st[1], st[2]
                    cum = 0
                    for idx in sorted(buckets):
                        cum += buckets[idx]
                        le = 'le="%.6g"' % bucket_upper_bound(idx)
                        lines.append(
                            f"{name}_bucket{_label_str(key, le)} {cum}")
                    inf_le = 'le="+Inf"'
                    lines.append(
                        f"{name}_bucket{_label_str(key, inf_le)} {count}")
                    lines.append(f"{name}_sum{_label_str(key)} {_fmt_val(total)}")
                    lines.append(f"{name}_count{_label_str(key)} {count}")
                else:
                    lines.append(f"{name}{_label_str(key)} {_fmt_val(st)}")
        return "\n".join(lines) + "\n"

    def to_raw(self) -> dict:
        """Exact values, JSON-shaped: no humanized strings to re-parse."""
        out: Dict[str, dict] = {}
        for name, inst in self.instruments().items():
            entry = {"type": inst.kind, "help": inst.help, "series": []}
            if isinstance(inst, Gauge) and inst._fn is not None:
                v = inst.value()
                if v is not None:
                    entry["series"].append({"labels": {}, "value": v})
            with inst._mu:
                series = sorted(inst._series.items())
            for key, st in series:
                labels = dict(key)
                if isinstance(inst, Histogram):
                    entry["series"].append(
                        {"labels": labels,
                         "buckets": {str(i): c for i, c in sorted(st[0].items())},
                         "sum": st[1], "count": st[2]})
                else:
                    entry["series"].append({"labels": labels, "value": st})
            out[name] = entry
        return out

    def to_human(self) -> dict:
        """Humanized registry view for the default ``/debug/metrics``:
        bytes/seconds values rendered readable, histograms summarized as
        count + estimated p50/p95/p99."""
        from blaze_tpu.obs.explain import fmt_bytes, fmt_ns

        def render(name, v):
            if v is None:
                return None
            if name.endswith("_bytes"):
                return fmt_bytes(int(v))
            if name.endswith("_seconds"):
                return fmt_ns(int(v * 1e9))
            return v

        out: Dict[str, dict] = {}
        for name, inst in self.instruments().items():
            entry = {"type": inst.kind, "series": {}}
            if isinstance(inst, Histogram):
                with inst._mu:
                    keys = list(inst._series)
                for key in keys:
                    kw = dict(key)
                    st = inst.snapshot(**kw)
                    if st is None:
                        continue
                    entry["series"][_label_str(key) or "-"] = {
                        "count": st["count"],
                        "mean": render(name, st["sum"] / st["count"])
                        if st["count"] else None,
                        "p50": render(name, inst.quantile(0.50, **kw)),
                        "p95": render(name, inst.quantile(0.95, **kw)),
                        "p99": render(name, inst.quantile(0.99, **kw)),
                    }
            else:
                if isinstance(inst, Gauge) and inst._fn is not None:
                    entry["series"]["-"] = render(name, inst.value())
                with inst._mu:
                    series = sorted(inst._series.items())
                for key, st in series:
                    entry["series"][_label_str(key) or "-"] = render(name, st)
            if entry["series"]:
                out[name] = entry
        return out

    # -- worker delta shipping -------------------------------------------------

    def drain_deltas(self) -> dict:
        """Snapshot AND zero counters/histograms (gauges ship last value but
        are not zeroed; collect-time callback gauges are process-local and
        never ship). The worker attaches this to its task reply."""
        out: Dict[str, dict] = {}
        for name, inst in self.instruments().items():
            if isinstance(inst, Gauge) and inst._fn is not None:
                continue
            with inst._mu:
                if not inst._series:
                    continue
                series = []
                for key, st in sorted(inst._series.items()):
                    labels = dict(key)
                    if isinstance(inst, Histogram):
                        series.append(
                            {"labels": labels,
                             "buckets": {str(i): c for i, c in st[0].items()},
                             "sum": st[1], "count": st[2]})
                    else:
                        series.append({"labels": labels, "value": st})
                if isinstance(inst, (Counter, Histogram)):
                    inst._series.clear()
            out[name] = {"type": inst.kind, "help": inst.help,
                         "series": series}
        return out

    def merge_deltas(self, payload: dict):
        """Fold a worker's :meth:`drain_deltas` payload into this registry
        (driver side; counters/histogram buckets add, gauges last-write)."""
        if not self.enabled or not payload:
            return
        for name, entry in payload.items():
            kind = entry.get("type")
            try:
                if kind == "counter":
                    inst = self.counter(name, entry.get("help", ""))
                elif kind == "gauge":
                    inst = self.gauge(name, entry.get("help", ""))
                elif kind == "histogram":
                    inst = self.histogram(name, entry.get("help", ""))
                else:
                    continue
            except ValueError:
                continue  # type conflict with a driver instrument: skip
            for s in entry.get("series", []):
                key = _label_key(s.get("labels") or {})
                if kind == "counter":
                    inst._inc(key, int(s.get("value") or 0))
                elif kind == "gauge":
                    inst._set(key, s.get("value"))
                else:
                    with inst._mu:
                        st = inst._series.get(key)
                        if st is None:
                            st = inst._series[key] = [{}, 0.0, 0]
                        for i, c in (s.get("buckets") or {}).items():
                            i = int(i)
                            st[0][i] = st[0].get(i, 0) + int(c)
                        st[1] += float(s.get("sum") or 0.0)
                        st[2] += int(s.get("count") or 0)


def _fmt_val(v) -> str:
    if isinstance(v, bool):
        return "1" if v else "0"
    if isinstance(v, int):
        return str(v)
    try:
        f = float(v)
    except (TypeError, ValueError):
        return "0"
    return f"{f:.9g}"


# -- scrape-side helper (tests) -----------------------------------------------


_SAMPLE_RE = re.compile(
    r'^([a-zA-Z_:][a-zA-Z0-9_:]*)(?:\{(.*)\})?\s+(\S+)$')
_LABEL_RE = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="([^"]*)"')


def parse_prometheus_text(text: str) -> Dict[str, dict]:
    """Parse Prometheus text exposition into
    ``{name: {"type": ..., "samples": [(labels_dict, value), ...]}}``.
    ``_bucket``/``_sum``/``_count`` sample families appear under their own
    suffixed names."""
    out: Dict[str, dict] = {}
    for line in text.splitlines():
        line = line.strip()
        if not line:
            continue
        if line.startswith("# TYPE "):
            parts = line.split()
            if len(parts) >= 4:
                out.setdefault(parts[2], {"type": parts[3], "samples": []})
            continue
        if line.startswith("#"):
            continue
        m = _SAMPLE_RE.match(line)
        if not m:
            continue
        name, labels_str, val_str = m.groups()
        labels = dict(_LABEL_RE.findall(labels_str or ""))
        try:
            value = float(val_str) if val_str != "+Inf" else math.inf
        except ValueError:
            continue
        out.setdefault(name, {"type": None, "samples": []})
        out[name]["samples"].append((labels, value))
    return out


# -- process-global registry ---------------------------------------------------

REGISTRY = MetricsRegistry()


def get_registry() -> MetricsRegistry:
    return REGISTRY


def configure_from(conf) -> MetricsRegistry:
    """Enable/disable the process registry from a Config (Session/worker
    call this; BLAZE_TPU_TELEMETRY=0/1 force-overrides for ad-hoc runs)."""
    env = os.environ.get("BLAZE_TPU_TELEMETRY", "")
    if env:
        REGISTRY.enabled = env not in ("0", "false", "no")
    else:
        REGISTRY.enabled = bool(getattr(conf, "telemetry_enabled", True))
    return REGISTRY
