"""Per-query stats plane: structured runtime statistics for adaptive use.

The runtime already *measures* everything an adaptive driver needs — radix
bucket histograms, DEVICE_STATS transfer counts, per-operator self-time,
per-reducer map-output sizes — but emitted them as scattered trace instants
and global counters. This module is the structured substrate ROADMAP items
1/3/4 stand on: every stage commit feeds a per-query :class:`StatsPlane`,
and a completed query folds into one compact ``QueryProfile`` dict

- per-stage map-output partition sizes + row counts (all three zero-copy
  shuffle tiers: the offsets index is written by every tier, rows ride the
  writer's ``part_rows_<pid>`` metrics),
- key-skew summaries promoted from the ``radix_bucket_histogram`` trace
  instants into structured records (min/p50/max bucket weight, hot ids),
- per-operator estimated-vs-actual cardinalities (estimates from
  ``ir/estimates.py`` on the logical plan, actuals from executor
  ``output_rows``),
- per-operator and per-stage self time (``compute_time_ns``: the host
  clock of ``elapsed_compute_time_ns``, the wait for the device included),
- residency (device/mapped/host byte deltas + the zero-copy tripwires) and
  spill/recovery events.

Profiles are keyed by the canonical **plan fingerprint** (sha256 of the
path-normalized plan JSON) and persisted to ``conf.profile_store_dir``
like incident bundles — capped, GC'd, atomic — so a future AQE pass or a
plan-fingerprint cache reads "last observed stats for this plan shape" in
O(1) via ``Session.profile(...)`` or ``GET /debug/profiles/<fingerprint>``.

Worker-side stats ride task replies (``reply["stats"]`` from
:func:`_StatsHub.drain_all_merged`) and merge driver-side exactly like the
telemetry deltas of the worker pool. With ``conf.stats_enabled = False``
every hook is one attribute check — the disabled path stays inside the
test-guarded <5% overhead budget.
"""

from __future__ import annotations

import contextlib
import hashlib
import json
import os
import re
import threading
from collections import deque
from typing import Dict, List, Optional

# -- field-name schema ---------------------------------------------------------
# Every key a QueryProfile may contain, by section. scripts/
# check_metrics_names.py lints these against the snake_case convention so
# profile keys stay greppable.

PROFILE_FIELDS = (
    "fingerprint", "query_id", "label", "state", "unix_time", "wall_s",
    "rows", "nparts", "operators", "stages",
    "residency", "spills", "recovery", "truncated",
    "attribution", "critical_path", "decision_audit", "cache",
)
STAGE_FIELDS = (
    "stage", "kind", "num_tasks", "partitions", "partition_bytes",
    "partition_rows", "total_bytes", "total_rows", "max_partition_bytes",
    "median_partition_bytes", "partition_skew_ratio", "truncated", "skew",
    "compute_time_ns", "recovered_tasks",
)
OPERATOR_FIELDS = (
    "op", "est_rows", "actual_rows", "compute_time_ns",
)
SKEW_FIELDS = (
    "buckets", "min_bucket_rows", "p50_bucket_rows", "max_bucket_rows",
    "hot_bucket_ids", "radix_passes",
)
RESIDENCY_FIELDS = (
    "to_device_bytes", "to_host_bytes", "mapped_bytes", "shm_bytes_mapped",
    "serde_elided_batches", "shuffle_bytes_serialized", "codes_shuffle_bytes",
)
SPILL_FIELDS = ("spill_count", "spilled_bytes", "mem_spill_count")
RECOVERY_FIELDS = ("kind", "stage", "detail")

# attribution plane (obs/attribution.py): per-category exclusive times plus
# the sweep's own accounting; CRITICAL_PATH/AUDIT keys mirror the segment
# and decision_audit dicts query_attribution/decision_audit emit.
from blaze_tpu.obs.attribution import CATEGORY_FIELDS as _CATEGORY_FIELDS

ATTRIBUTION_FIELDS = _CATEGORY_FIELDS + (
    "wall_ns", "attributed_ns", "coverage_fraction")
CRITICAL_PATH_FIELDS = (
    "kind", "name", "stage", "dur_ms", "task", "task_ms", "operators", "op",
    "self_time_ms",
)
AUDIT_FIELDS = (
    "ops_fused", "ops_eligible", "fused_op_fraction", "fusion_break_reasons",
    "placement_decisions", "placement_decline_reasons",
)

# result/subplan cache plane (blaze_tpu/cache/): the ``cache`` profile
# section (subplan hits noted during execution) plus the cache_* tripwire
# block soak/serve artifacts embed via QueryCache.stats_fields()
CACHE_FIELDS = (
    "cache_hits", "cache_misses", "cache_stale", "cache_stale_served",
    "cache_evictions", "cache_refreshes", "cache_subplan_hits",
    "cache_degraded_puts", "cache_bytes", "cache_entries",
    "cache_served_bytes", "cache_served",
)

ALL_PROFILE_FIELDS = (PROFILE_FIELDS + STAGE_FIELDS + OPERATOR_FIELDS +
                      SKEW_FIELDS + RESIDENCY_FIELDS + SPILL_FIELDS +
                      RECOVERY_FIELDS + ATTRIBUTION_FIELDS +
                      CRITICAL_PATH_FIELDS + AUDIT_FIELDS + CACHE_FIELDS)

_SAFE_ID = re.compile(r"[^A-Za-z0-9_.-]+")

# arrays recorded per stage are capped so a 10k-reducer exchange cannot
# bloat the profile store; ``truncated`` marks the cut
MAX_PARTITIONS_RECORDED = 256
MAX_OPERATORS_RECORDED = 128
MAX_RECOVERY_EVENTS = 64

SELF_TIME_METRIC = "elapsed_compute_time_ns"


# -- plan fingerprint ----------------------------------------------------------


def _normalize_paths(v):
    """Strings containing '/' collapse to their basename: the canonical
    form must not change because the same plan runs from a different tmp
    work dir (fingerprint stability across runs/sessions)."""
    if isinstance(v, str):
        return v.rsplit("/", 1)[-1] if "/" in v else v
    if isinstance(v, list):
        return [_normalize_paths(x) for x in v]
    if isinstance(v, dict):
        return {k: _normalize_paths(x) for k, x in v.items()}
    return v


def plan_fingerprint(plan) -> str:
    """24-hex-char sha256 of the path-normalized canonical plan JSON.
    Falls back to the plan-shape repr when serde chokes (UDF closures);
    never raises."""
    try:
        from blaze_tpu.ir.serde import plan_to_json

        raw = json.loads(plan_to_json(plan))
        canon = json.dumps(_normalize_paths(raw), sort_keys=True, default=str)
    except Exception:
        try:
            from blaze_tpu.obs.dump import _plan_shape

            canon = repr(_plan_shape(plan))
        except Exception:
            canon = type(plan).__name__
    return hashlib.sha256(canon.encode()).hexdigest()[:24]


# -- skew ----------------------------------------------------------------------


def _acc_elementwise(dst: List[int], src) -> None:
    for i, v in enumerate(src):
        if i < len(dst):
            dst[i] += int(v)
        else:
            dst.append(int(v))


def skew_summary(rec: Optional[dict]) -> Optional[dict]:
    """Structured skew record from an accumulated radix histogram: min/p50/
    max live-bucket row weight plus the hottest bucket ids (> 2x median)."""
    if not rec:
        return None
    rows = rec.get("bucket_rows") or []
    live = sorted(r for r in rows if r > 0)
    if not live:
        return None
    med = live[len(live) // 2]
    hot = [i for i, r in enumerate(rows) if r > 2 * med]
    hot.sort(key=lambda i: -rows[i])
    return {
        "buckets": len(rows),
        "min_bucket_rows": int(live[0]),
        "p50_bucket_rows": int(med),
        "max_bucket_rows": int(live[-1]),
        "hot_bucket_ids": hot[:8],
        "radix_passes": int(rec.get("radix_passes") or 0),
    }


def _merge_radix(dst: Optional[dict], src: Optional[dict]) -> Optional[dict]:
    if not src:
        return dst
    if not dst:
        return {"bucket_rows": list(src.get("bucket_rows") or []),
                "bucket_groups": list(src.get("bucket_groups") or []),
                "radix_passes": int(src.get("radix_passes") or 0)}
    _acc_elementwise(dst["bucket_rows"], src.get("bucket_rows") or [])
    _acc_elementwise(dst["bucket_groups"], src.get("bucket_groups") or [])
    dst["radix_passes"] += int(src.get("radix_passes") or 0)
    return dst


# -- the process-global hub ----------------------------------------------------


class _StatsHub:
    """Scoped accumulation point for stats noted deep inside operator code
    (the radix histogram in agg_device). Driver task closures set a
    thread-local scope key per (query, stage); worker processes set none —
    their notes pool under ``None`` and ride the task reply via
    :meth:`drain_all_merged`. One ``enabled`` check when stats are off."""

    _MAX_SCOPES = 256  # backstop for scopes recovery re-runs leave behind

    def __init__(self):
        self._mu = threading.Lock()
        self._tls = threading.local()
        self._scopes: Dict = {}
        self.enabled = True

    def configure_from(self, conf) -> None:
        self.enabled = bool(getattr(conf, "stats_enabled", True))

    @contextlib.contextmanager
    def scoped(self, key):
        prev = getattr(self._tls, "key", None)
        self._tls.key = key
        try:
            yield
        finally:
            self._tls.key = prev

    def note_radix(self, rows, groups) -> None:
        """Accumulate one radix pass's per-bucket (rows, groups) histogram
        under the current scope."""
        if not self.enabled:
            return
        key = getattr(self._tls, "key", None)
        r = [int(x) for x in rows]
        g = [int(x) for x in groups]
        with self._mu:
            rec = self._scopes.get(key)
            if rec is None:
                if len(self._scopes) >= self._MAX_SCOPES:
                    self._scopes.pop(next(iter(self._scopes)))
                rec = self._scopes[key] = {"bucket_rows": [],
                                           "bucket_groups": [],
                                           "radix_passes": 0}
            _acc_elementwise(rec["bucket_rows"], r)
            _acc_elementwise(rec["bucket_groups"], g)
            rec["radix_passes"] += 1

    def drain(self, key) -> Optional[dict]:
        with self._mu:
            return self._scopes.pop(key, None)

    def drain_all_merged(self) -> dict:
        """Worker side: pop every scope, merged — the ``reply["stats"]``
        payload. Empty dict when nothing was noted."""
        with self._mu:
            scopes, self._scopes = self._scopes, {}
        merged: Optional[dict] = None
        for rec in scopes.values():
            merged = _merge_radix(merged, rec)
        return merged or {}


STATS_HUB = _StatsHub()


def configure(conf) -> None:
    STATS_HUB.configure_from(conf)


# -- the per-query plane -------------------------------------------------------


class StatsPlane:
    """Driver-side accumulator for ONE query. Stage commits call
    ``on_map_stage``/``on_collect_stage``; pool replies fold in via
    ``merge_task_stats``; recovery paths call ``note_recovery``; and
    ``finalize_into`` builds the QueryProfile onto the query record the
    session keeps in ``query_log``. Every entry point is best-effort and
    never raises into the execution path."""

    RESULT_STAGE = -1

    def __init__(self, plan, conf):
        self.conf = conf
        self.fingerprint = plan_fingerprint(plan)
        try:
            from blaze_tpu.ir.estimates import estimate_plan

            self.estimates = estimate_plan(plan)
        except Exception:
            self.estimates = []
        self._mu = threading.Lock()
        self._stages: Dict[int, dict] = {}
        self._worker_radix: Dict[int, dict] = {}
        self._recovery: List[dict] = []
        self._cache_notes: List[dict] = []
        self._attribution: Optional[dict] = None
        try:
            from blaze_tpu.utils.device import DEVICE_STATS

            self._dev0 = DEVICE_STATS.snapshot()
        except Exception:
            self._dev0 = {}
        # fusion/placement decision-audit counters are process-global (and
        # absorb worker deltas); the snapshot delta is per-query by the same
        # exact-alone/upper-bound-concurrent argument as DEVICE_STATS
        try:
            from blaze_tpu.obs.attribution import audit_snapshot

            self._audit0 = audit_snapshot()
        except Exception:
            self._audit0 = None

    def scope_key(self, stage: int):
        """The STATS_HUB scope driver task threads of ``stage`` run under
        (``RESULT_STAGE`` for result-partition streams)."""
        return (id(self), stage)

    # -- stage commits --------------------------------------------------------

    def on_map_stage(self, stage: int, kind: str, num_tasks: int,
                     num_reducers: int, indexes=None) -> None:
        """One exchange's map side committed. ``indexes`` is the
        ``[(data_path, offsets)]`` list every tier writes (process-tier
        offsets are LOGICAL, still per-reducer sizes); None for transports
        without one (RSS push, mesh collective)."""
        try:
            rec = {"stage": stage, "kind": kind, "num_tasks": num_tasks,
                   "partitions": num_reducers,
                   "truncated": num_reducers > MAX_PARTITIONS_RECORDED}
            if indexes:
                sizes = [0] * num_reducers
                for _, offsets in indexes:
                    n = min(num_reducers, len(offsets) - 1)
                    for r in range(n):
                        sizes[r] += int(offsets[r + 1] - offsets[r])
                rec["total_bytes"] = sum(sizes)
                live = sorted(s for s in sizes if s > 0)
                if live:
                    med = live[len(live) // 2]
                    rec["max_partition_bytes"] = live[-1]
                    rec["median_partition_bytes"] = med
                    rec["partition_skew_ratio"] = round(
                        live[-1] / med, 2) if med else 0.0
                rec["partition_bytes"] = sizes[:MAX_PARTITIONS_RECORDED]
            radix = STATS_HUB.drain(self.scope_key(stage))
            with self._mu:
                radix = _merge_radix(radix, self._worker_radix.pop(stage, None))
                rec["skew"] = skew_summary(radix)
                self._stages[stage] = rec
        except Exception:
            pass

    def on_collect_stage(self, stage: int, kind: str, num_tasks: int,
                         blocks) -> None:
        """A collect/broadcast stage committed its in-memory blocks (the
        ``("batches"|"bytes", …)`` list of ``_collect_child_chunks``)."""
        try:
            total = 0
            for b in blocks or []:
                if b and b[0] == "bytes":
                    total += len(b[1])
                elif b and b[0] == "batches":
                    for x in b[1]:
                        try:
                            total += x.nbytes()
                        except Exception:
                            pass
            rec = {"stage": stage, "kind": kind, "num_tasks": num_tasks,
                   "partitions": 1, "partition_bytes": [total],
                   "total_bytes": total, "truncated": False}
            radix = STATS_HUB.drain(self.scope_key(stage))
            with self._mu:
                radix = _merge_radix(radix, self._worker_radix.pop(stage, None))
                rec["skew"] = skew_summary(radix)
                self._stages[stage] = rec
        except Exception:
            pass

    def merge_task_stats(self, stage: int, rec: Optional[dict]) -> None:
        """Fold one worker task reply's drained hub record into the stage
        (driver-side merge, like the pool's telemetry deltas)."""
        if not rec:
            return
        with self._mu:
            self._worker_radix[stage] = _merge_radix(
                self._worker_radix.get(stage), rec)

    def note_attribution(self, attr: Optional[dict]) -> None:
        """Attach the per-query exclusive decomposition + critical path
        (``obs.attribution.query_attribution`` output) before finalize."""
        if attr:
            with self._mu:
                self._attribution = attr

    def note_cache_subplan(self, fingerprint: str, nbytes: int) -> None:
        """Record one exchange subtree served from the subplan cache —
        surfaces in the profile's ``cache`` section and in
        explain_analyze's cache line."""
        with self._mu:
            if len(self._cache_notes) < MAX_RECOVERY_EVENTS:
                self._cache_notes.append(
                    {"fingerprint": fingerprint, "nbytes": int(nbytes)})

    def note_recovery(self, kind: str, stage: Optional[int] = None,
                      detail=None) -> None:
        with self._mu:
            if len(self._recovery) < MAX_RECOVERY_EVENTS:
                self._recovery.append({
                    "kind": kind, "stage": stage,
                    "detail": str(detail)[:200] if detail is not None else None,
                })

    # -- finalize -------------------------------------------------------------

    def finalize_into(self, query: dict, session_metrics, state: str):
        """Build the QueryProfile and attach it as ``query["stats"]``.
        Called by ``finish_query`` before the record enters the query log;
        returns the profile (or None on any internal failure)."""
        try:
            profile = self._build(query, session_metrics, state)
        except Exception:
            return None
        query["stats"] = profile
        return profile

    def _build(self, query: dict, session_metrics, state: str) -> dict:
        from blaze_tpu.obs.explain import merge_partition_metrics

        # merged positional metric trees, result stage first then exchange
        # stages in id order — the same walk explain_analyze renders
        trees = []  # (shape, merged MetricNode or None)
        parts = [session_metrics.get_named(k)
                 for k in (query.get("result_keys") or [])]
        parts = [p for p in parts if p is not None]
        if query.get("shape") is not None:
            trees.append((query["shape"],
                          merge_partition_metrics(parts) if parts else None))
        for stage in (query.get("stages") or []):
            node = session_metrics.get_named(f"stage_{stage['id']}")
            task_parts = []
            if node is not None:
                task_parts = [node.get_named(f"map_{m}")
                              for m in range(stage.get("num_tasks") or 0)]
                task_parts = [p for p in task_parts if p is not None]
            trees.append((stage["shape"],
                          merge_partition_metrics(task_parts)
                          if task_parts else None))

        operators = self._operator_records(trees)
        stages = self._stage_records(query, session_metrics)
        # result-partition streams note radix skew under the RESULT_STAGE
        # scope (there is no stage commit for the final stage: drain here)
        result_skew = skew_summary(
            STATS_HUB.drain(self.scope_key(self.RESULT_STAGE)))
        if result_skew:
            stages.append({"stage": self.RESULT_STAGE, "kind": "result",
                           "num_tasks": query.get("nparts") or 0,
                           "partitions": query.get("nparts") or 0,
                           "truncated": False, "skew": result_skew})

        def tree_total(metric: str) -> int:
            return sum(t.total(metric) for _, t in trees if t is not None)

        residency = {
            "shm_bytes_mapped": tree_total("shm_bytes_mapped"),
            "serde_elided_batches": tree_total("serde_elided_batches"),
            "shuffle_bytes_serialized": tree_total("shuffle_bytes_serialized"),
            "codes_shuffle_bytes": tree_total("codes_shuffle_bytes"),
        }
        # DEVICE_STATS is process-global: the snapshot delta is exact for a
        # query running alone (bench/tests) and an upper bound under
        # concurrent queries
        try:
            from blaze_tpu.utils.device import DEVICE_STATS

            d1 = DEVICE_STATS.snapshot()
            for k in ("to_device_bytes", "to_host_bytes", "mapped_bytes"):
                residency[k] = max(0, d1.get(k, 0) - self._dev0.get(k, 0))
        except Exception:
            pass

        spills = {
            "spill_count": tree_total("spill_count"),
            "spilled_bytes": tree_total("spilled_bytes"),
            "mem_spill_count": tree_total("mem_spill_count"),
        }
        with self._mu:
            recovery = list(self._recovery)
            cache_notes = list(self._cache_notes)
            attribution = self._attribution

        audit = None
        try:
            from blaze_tpu.obs.attribution import decision_audit

            audit = decision_audit(self._audit0)
        except Exception:
            pass

        extra = {}
        if attribution is not None:
            extra["attribution"] = {
                k: v for k, v in attribution.items() if k != "critical_path"}
            extra["attribution"].update(attribution.get("categories") or {})
            extra["attribution"].pop("categories", None)
            extra["critical_path"] = attribution.get("critical_path") or []
        if audit is not None:
            extra["decision_audit"] = audit
        if cache_notes:
            extra["cache"] = {
                "cache_subplan_hits": len(cache_notes),
                "cache_served_bytes": sum(n["nbytes"]
                                          for n in cache_notes),
                "cache_served": [n["fingerprint"] for n in cache_notes],
            }

        return {
            **extra,
            "fingerprint": self.fingerprint,
            "query_id": query.get("id"),
            "label": query.get("label"),
            "state": state,
            "unix_time": query.get("started_unix"),
            "wall_s": round(float(query.get("wall_s") or 0.0), 6),
            "rows": query.get("rows"),
            "nparts": query.get("nparts"),
            "operators": operators,
            "stages": stages,
            "residency": residency,
            "spills": spills,
            "recovery": recovery,
            "truncated": len(operators) >= MAX_OPERATORS_RECORDED or
                         any(s.get("truncated") for s in stages),
        }

    def _operator_records(self, trees) -> List[dict]:
        from blaze_tpu.ir.estimates import normalize_op_name

        est_queue: Dict[str, deque] = {}
        for e in self.estimates:
            est_queue.setdefault(e["op"], deque()).append(e["est_rows"])
        operators: List[dict] = []

        def walk(shape, node):
            if len(operators) >= MAX_OPERATORS_RECORDED:
                return
            name, children = shape
            if not name.startswith("+ "):  # fused pseudo-children: no metrics
                vals = dict(node.values) if node is not None else {}
                q = est_queue.get(normalize_op_name(name))
                operators.append({
                    "op": name,
                    "est_rows": q.popleft() if q else None,
                    "actual_rows": int(vals.get("output_rows", 0)),
                    "compute_time_ns": int(vals.get(SELF_TIME_METRIC, 0)),
                })
            for i, c in enumerate(children):
                cn = None
                if node is not None and i < len(node.children):
                    cn = node.children[i]
                walk(c, cn)

        for shape, merged in trees:
            walk(shape, merged)
        return operators

    def _stage_records(self, query: dict, session_metrics) -> List[dict]:
        with self._mu:
            stages = {sid: dict(rec) for sid, rec in self._stages.items()}
            # a pending worker radix rec whose stage commit never fired
            # (e.g. failure mid-stage) still surfaces
            for sid, radix in self._worker_radix.items():
                rec = stages.setdefault(sid, {"stage": sid, "kind": "partial",
                                              "num_tasks": 0, "partitions": 0,
                                              "truncated": False})
                rec["skew"] = skew_summary(radix)
            recovered: Dict[Optional[int], int] = {}
            for ev in self._recovery:
                recovered[ev.get("stage")] = recovered.get(ev.get("stage"), 0) + 1
        out = []
        for sid in sorted(stages):
            rec = stages[sid]
            node = session_metrics.get_named(f"stage_{sid}")
            if node is not None:
                nparts = int(rec.get("partitions") or 0)
                rows = [node.total(f"part_rows_{r}")
                        for r in range(min(nparts, MAX_PARTITIONS_RECORDED))]
                if any(rows):
                    rec["partition_rows"] = rows
                    rec["total_rows"] = sum(
                        node.total(f"part_rows_{r}") for r in range(nparts))
                rec["compute_time_ns"] = node.total(SELF_TIME_METRIC)
            if sid in recovered:
                rec["recovered_tasks"] = recovered[sid]
            out.append(rec)
        return out


def stage_summary_line(stage_rec: dict) -> str:
    """One-line per-stage summary for /debug/queries and explain output:
    partition count, total bytes, max/median ratio, hot radix buckets."""
    from blaze_tpu.obs.explain import fmt_bytes

    parts = [f"stage {stage_rec.get('stage')}",
             f"[{stage_rec.get('kind')}]",
             f"partitions={stage_rec.get('partitions')}"]
    if stage_rec.get("total_bytes") is not None:
        parts.append(f"bytes={fmt_bytes(stage_rec['total_bytes'])}")
    if stage_rec.get("total_rows") is not None:
        # "row_count=" not "rows=": explain-analyze consumers treat "rows="
        # lines as per-operator metric lines (which always carry "batches=")
        parts.append(f"row_count={stage_rec['total_rows']}")
    if stage_rec.get("partition_skew_ratio") is not None:
        parts.append(f"max/med={stage_rec['partition_skew_ratio']}")
    skew = stage_rec.get("skew")
    if skew:
        parts.append(
            f"radix[p50={skew['p50_bucket_rows']} max={skew['max_bucket_rows']}"
            f" hot={skew['hot_bucket_ids']}]")
    if stage_rec.get("recovered_tasks"):
        parts.append(f"recovered={stage_rec['recovered_tasks']}")
    return " ".join(parts)


# -- profile store -------------------------------------------------------------


def _conf(conf):
    if conf is not None:
        return conf
    from blaze_tpu.config import get_config

    return get_config()


def save_profile(profile: dict, conf=None) -> Optional[str]:
    """Persist one QueryProfile under ``<fingerprint>.json`` (the latest
    run of a plan shape overwrites: the store answers "last observed stats
    for this fingerprint"). Atomic write, mtime-GC'd to
    ``conf.profile_store_max`` when the write added a file (a rewrite of a
    stored fingerprint leaves the count as it was, so nothing is listed);
    never raises."""
    try:
        conf = _conf(conf)
        out_dir = getattr(conf, "profile_store_dir", "") or ""
        cap = int(getattr(conf, "profile_store_max", 0) or 0)
        if not out_dir or cap <= 0:
            return None
        fp = _SAFE_ID.sub("-", str(profile.get("fingerprint") or ""))
        if not fp:
            return None
        os.makedirs(out_dir, exist_ok=True)
        path = os.path.join(out_dir, fp + ".json")
        tmp = f"{path}.tmp{os.getpid()}"
        added = not os.path.exists(path)
        with open(tmp, "w") as f:
            json.dump(profile, f, default=str)
        os.replace(tmp, path)
        if not added:
            return fp
        # GC by mtime — fingerprints are content hashes, so unlike incident
        # ids a lexical sort is NOT chronological here
        names = [n for n in os.listdir(out_dir) if n.endswith(".json")]
        if len(names) > cap:
            def mtime(n):
                try:
                    return os.path.getmtime(os.path.join(out_dir, n))
                except OSError:
                    return 0.0

            names.sort(key=mtime)
            for n in names[:-cap]:
                try:
                    os.unlink(os.path.join(out_dir, n))
                except OSError:
                    pass
        return fp
    except Exception:
        return None


def list_profiles(conf=None) -> List[dict]:
    """Summaries of every stored profile, newest first."""
    conf = _conf(conf)
    out_dir = getattr(conf, "profile_store_dir", "") or ""
    if not out_dir or not os.path.isdir(out_dir):
        return []
    names = [n for n in os.listdir(out_dir) if n.endswith(".json")]

    def mtime(n):
        try:
            return os.path.getmtime(os.path.join(out_dir, n))
        except OSError:
            return 0.0

    names.sort(key=mtime, reverse=True)
    out = []
    for name in names:
        try:
            with open(os.path.join(out_dir, name)) as f:
                p = json.load(f)
            out.append({"fingerprint": p.get("fingerprint", name[:-5]),
                        "label": p.get("label"),
                        "state": p.get("state"),
                        "wall_s": p.get("wall_s"),
                        "rows": p.get("rows"),
                        "unix_time": p.get("unix_time"),
                        "stages": len(p.get("stages") or [])})
        except (OSError, ValueError):
            continue
    return out


def load_profile(fingerprint: str, conf=None) -> Optional[dict]:
    """Full stored profile by fingerprint (sanitized: no path traversal)."""
    conf = _conf(conf)
    out_dir = getattr(conf, "profile_store_dir", "") or ""
    safe = _SAFE_ID.sub("-", str(fingerprint))
    if not out_dir or not safe:
        return None
    try:
        with open(os.path.join(out_dir, safe + ".json")) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None
