"""Profile artifact dumping: trace JSON + metrics snapshot (+ explain text)
and incident forensic bundles (the flight-recorder dump path).

The helper ``scripts/profile_query.py`` writes its artifacts with:

- ``<tag>_trace.json``    — Chrome trace events; load in https://ui.perfetto.dev
- ``<tag>_metrics.json``  — the session metric tree with humanized durations
- ``<tag>_explain.txt``   — EXPLAIN ANALYZE text (when provided)

Incident bundles: :func:`record_incident` is called by ``Session`` /
``QueryScheduler`` when a query fails, sheds, is cancelled or misses its
deadline. Each bundle is one JSON file under ``conf.incident_dir`` holding
everything needed to ask "why did THIS query die": the plan shape, its
per-operator metric snapshot, MemManager group state, the scheduler's view
at the time, the last flight-recorder spans, and the exception. The
directory is capped at ``conf.incident_max_bundles`` (oldest deleted
first), and bundles are served at ``GET /debug/incidents[/<id>]``.
"""

from __future__ import annotations

import json
import os
import re
import time
import traceback as _traceback
from typing import List, Optional

from blaze_tpu.obs.explain import humanize_metrics_dict
from blaze_tpu.obs.telemetry import get_registry
from blaze_tpu.obs.tracer import TRACER

_INCIDENT_BUNDLES = get_registry().counter(
    "blaze_obs_incident_bundles_total",
    "forensic incident bundles written, by terminal kind")

_SAFE_ID = re.compile(r"[^A-Za-z0-9_.-]+")


def dump_profile(session, out_dir: str, tag: str,
                 explain_text: Optional[str] = None) -> dict:
    """Write the current trace buffer + session metrics (and optional
    explain output) under ``out_dir``; returns {artifact: path}."""
    os.makedirs(out_dir, exist_ok=True)
    paths = {}

    trace_path = os.path.join(out_dir, f"{tag}_trace.json")
    with open(trace_path, "w") as f:
        json.dump(TRACER.to_chrome_trace(f"blaze_tpu {tag}"), f)
    paths["trace"] = trace_path

    metrics_path = os.path.join(out_dir, f"{tag}_metrics.json")
    with open(metrics_path, "w") as f:
        json.dump(humanize_metrics_dict(session.metrics.to_dict()), f,
                  indent=2)
    paths["metrics"] = metrics_path

    if explain_text is not None:
        explain_path = os.path.join(out_dir, f"{tag}_explain.txt")
        with open(explain_path, "w") as f:
            f.write(explain_text + "\n")
        paths["explain"] = explain_path
    return paths


# -- incident forensics --------------------------------------------------------


def _plan_shape(node) -> Optional[tuple]:
    """(type name, [child shapes]) for an IR plan node; best-effort."""
    try:
        return (type(node).__name__, [_plan_shape(c) for c in node.children()])
    except Exception:
        return (type(node).__name__, [])


def _conf(conf):
    if conf is not None:
        return conf
    from blaze_tpu.config import get_config
    return get_config()


def record_incident(kind: str, label: str, error: Optional[BaseException] = None,
                    session=None, scheduler_state: Optional[dict] = None,
                    handle=None, query: Optional[dict] = None,
                    conf=None, extra: Optional[dict] = None) -> Optional[str]:
    """Write one forensic bundle for a terminal query outcome; returns the
    incident id, or None when disabled/failed. NEVER raises — forensics must
    not take down the failure path it is documenting."""
    try:
        conf = _conf(conf)
        out_dir = getattr(conf, "incident_dir", "") or ""
        max_bundles = int(getattr(conf, "incident_max_bundles", 0) or 0)
        if not out_dir or max_bundles <= 0:
            return None

        incident_id = "%d_%s_%s" % (
            time.time_ns(), _SAFE_ID.sub("-", kind)[:24],
            _SAFE_ID.sub("-", str(label or "query"))[:48])
        bundle = {
            "id": incident_id,
            "kind": kind,
            "label": label,
            "unix_time": time.time(),
            "error": None,
            "plan_shape": None,
            "metrics": None,
            "memmgr": None,
            "scheduler": scheduler_state,
            "handle": None,
            "spans": TRACER.ring_snapshot(last=256),
            "tracer_dropped": TRACER.dropped,
        }
        if extra:
            # caller-specific context (e.g. worker_lost: wid/pid/exit code)
            bundle["extra"] = extra
        try:
            # chaos forensics: which injected faults had fired by the time
            # this incident was recorded (empty dict when no failpoint
            # armed/fired — omitted to keep bundles stable)
            from blaze_tpu.runtime import failpoints
            fp = failpoints.fired()
            if fp:
                bundle["failpoints"] = fp
        except Exception:
            pass
        if error is not None:
            bundle["error"] = {
                "type": type(error).__name__,
                "message": str(error),
                "traceback": "".join(_traceback.format_exception(
                    type(error), error, error.__traceback__))[-8192:],
            }
        if handle is not None:
            try:
                bundle["handle"] = handle.snapshot()
            except Exception:
                pass
            if getattr(handle, "plan", None) is not None:
                bundle["plan_shape"] = _plan_shape(handle.plan)
        if session is not None:
            if query is None and label:
                # find the query record this terminal outcome belongs to
                with session._qlog_mu:
                    candidates = [q for q in list(session.inflight.values())
                                  + session.query_log[::-1]
                                  if q.get("label") == label]
                query = candidates[0] if candidates else None
            if query is not None:
                if bundle["plan_shape"] is None:
                    bundle["plan_shape"] = query.get("shape")
                from blaze_tpu.runtime.metrics import query_metric_snapshot
                bundle["metrics"] = query_metric_snapshot(
                    session.metrics, query)
        try:
            from blaze_tpu.runtime.memmgr import MemManager
            mm = MemManager._instance
            if mm is not None:
                bundle["memmgr"] = mm.stats()
        except Exception:
            pass

        os.makedirs(out_dir, exist_ok=True)
        path = os.path.join(out_dir, incident_id + ".json")
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(bundle, f, indent=1, default=str)
        os.replace(tmp, path)

        # cap the directory: ids are time_ns-prefixed, so lexical sort of the
        # fixed-width prefix is chronological — drop oldest beyond the cap
        bundles = sorted(n for n in os.listdir(out_dir)
                         if n.endswith(".json"))
        for name in bundles[:-max_bundles]:
            try:
                os.unlink(os.path.join(out_dir, name))
            except OSError:
                pass

        _INCIDENT_BUNDLES.labels(kind=kind).inc()
        return incident_id
    except Exception:
        return None


def list_incidents(conf=None) -> List[dict]:
    """Summaries of every bundle on disk, newest first."""
    conf = _conf(conf)
    out_dir = getattr(conf, "incident_dir", "") or ""
    if not out_dir or not os.path.isdir(out_dir):
        return []
    out = []
    for name in sorted(os.listdir(out_dir), reverse=True):
        if not name.endswith(".json"):
            continue
        try:
            with open(os.path.join(out_dir, name)) as f:
                b = json.load(f)
            out.append({"id": b.get("id", name[:-5]),
                        "kind": b.get("kind"),
                        "label": b.get("label"),
                        "unix_time": b.get("unix_time"),
                        "error_type": (b.get("error") or {}).get("type"),
                        "spans": len(b.get("spans") or [])})
        except (OSError, ValueError):
            continue
    return out


def load_incident(incident_id: str, conf=None) -> Optional[dict]:
    """Full bundle by id (id is sanitized: no path traversal)."""
    conf = _conf(conf)
    out_dir = getattr(conf, "incident_dir", "") or ""
    safe = _SAFE_ID.sub("-", str(incident_id))
    if not out_dir or not safe:
        return None
    path = os.path.join(out_dir, safe + ".json")
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None
