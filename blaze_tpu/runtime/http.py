"""HTTP profiling/observability service.

Reference: the feature-gated poem server started lazily on first
``callNative`` (``auron/src/http/mod.rs:26-100``) with ``/debug/pprof/profile``
(CPU pprof) and ``/debug/pprof/heap`` (jemalloc). Here a stdlib HTTP server
bound to a free port exposes:

- ``/metrics``                 — Prometheus text exposition of the process
  metrics registry (obs/telemetry.py): serve SLO histograms, memmgr pool
  gauges, spill/shuffle/kernel counters — the scrape target
- ``/debug/metrics``           — the session metric tree as JSON (with
  human-readable renderings of every ``*_time_ns`` value) plus a humanized
  ``registry`` view; ``?format=raw`` returns exact integer values for both
- ``/debug/incidents``         — flight-recorder incident bundle index
  (newest first); ``/debug/incidents/<id>`` returns one full forensic
  bundle (plan shape, metrics, memmgr/scheduler state, ring spans, error)
- ``/debug/pprof/profile?seconds=N&frequency=H`` — wall-clock stack sampling
  across ALL threads (sys._current_frames), pprof-style aggregated stacks
- ``/debug/memory``            — process RSS + memory-manager accounting
  (spill count/bytes/time and per-consumer usage)
- ``/debug/config``            — the active engine config
- ``/debug/device``            — device residency: transfer bytes/calls,
  syncs and jitted-kernel dispatch counts (utils/device.DEVICE_STATS)
- ``/debug/trace``             — Chrome-trace-event JSON of recorded spans
  (query/stage/task/operator/spill/shuffle-fetch/kernel); load the payload
  in Perfetto or chrome://tracing. Requires ``Config.trace_enable`` (or
  BLAZE_TPU_TRACE=1); worker-process spans appear as separate pids.
- ``/debug/queries``           — live in-flight queries (serve scheduler
  queue + running, session executions with elapsed time) followed by the
  session's recent finished query log as recorded for explain_analyze
- ``/serve/submit`` (POST)     — submit a plan to the serving scheduler:
  JSON body with ``plan_b64`` (base64 of ir/protoserde plan bytes) or
  ``spark_plan`` (Spark-plan JSON for frontend/converter), plus optional
  ``priority``/``deadline_s``/``label``/``tenant``; 503 + typed body when
  Overloaded, 429 + ``Retry-After`` header when the full queue is merely
  backpressured (retry later instead of shedding)
- ``/serve/queries``           — scheduler snapshot (queued + running)
- ``/serve/status?id=N``       — one query's state/elapsed/error
- ``/serve/cancel?id=N``       — flip a query's cancel token
- ``/serve/result?id=N&timeout_s=T`` — block (bounded) for a result; the
  table returns as columns JSON
- ``/debug/cache``             — result/subplan cache snapshot (entries,
  hit/miss/stale/eviction counters, resident bytes) plus ingest table
  versions; 404 when ``cache_enabled=false``
- ``/ingest`` (POST)           — append-only streaming ingest: JSON body
  ``{"table": name, "rows": {col: [...]}}`` appends one batch to the named
  ingest table and bumps its version (dependent cache entries go stale —
  refreshed incrementally or recomputed on the next hit, never served)
- ``/debug/health``            — live health plane: per-subsystem states,
  SLO burn rates, transition/interval history (obs/timeline.py)
- ``/debug/timeseries``        — sampled time series; no params lists the
  series names, ``?name=&since=`` returns one series' ``[[t, v], ...]``

Start with ``ProfilingService.start(session)``; idempotent per process."""

from __future__ import annotations

import dataclasses
import json
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional
from urllib.parse import parse_qs, urlparse


def _query_record(q: dict) -> dict:
    """One /debug/queries entry: the query record with its nested-tuple
    plan shape replaced by indented outline lines (fused operators show as
    "+ <Op> (fused)" pseudo-children under their FusedStageExec)."""
    d = {k: v for k, v in q.items() if k not in ("shape", "stats")}
    if q.get("shape"):
        from blaze_tpu.obs.explain import shape_lines

        d["plan"] = shape_lines(q["shape"])
    stats = q.get("stats")
    if stats and stats.get("stages"):
        from blaze_tpu.obs.stats import stage_summary_line

        d["stage_stats"] = [stage_summary_line(s) for s in stats["stages"]]
        d["fingerprint"] = stats.get("fingerprint")
    if stats and stats.get("attribution"):
        d["attribution"] = stats["attribution"]
    if stats and stats.get("critical_path"):
        from blaze_tpu.obs.attribution import critical_path_lines

        d["critical_path"] = critical_path_lines(stats["critical_path"])
    return d


class ProfilingService:
    _instance: Optional["ProfilingService"] = None
    _lock = threading.Lock()

    def __init__(self, server: ThreadingHTTPServer, port: int):
        self.server = server
        self.port = port

    @classmethod
    def start(cls, session=None) -> "ProfilingService":
        with cls._lock:
            if cls._instance is not None:
                if session is not None:
                    cls._instance.server.blaze_session = session
                return cls._instance

            class Handler(BaseHTTPRequestHandler):
                def log_message(self, *args):
                    pass

                def _send(self, body: str, ctype: str = "application/json",
                          status: int = 200, headers=None):
                    data = body.encode()
                    self.send_response(status)
                    self.send_header("Content-Type", ctype)
                    self.send_header("Content-Length", str(len(data)))
                    for k, v in (headers or {}).items():
                        self.send_header(k, v)
                    self.end_headers()
                    self.wfile.write(data)

                def _scheduler(self):
                    sess = getattr(self.server, "blaze_session", None)
                    return getattr(sess, "serve_scheduler", None) \
                        if sess is not None else None

                def do_GET(self):
                    url = urlparse(self.path)
                    if url.path == "/metrics":
                        # Prometheus text exposition (scrape target)
                        from blaze_tpu.obs.telemetry import get_registry

                        self._send(
                            get_registry().to_prometheus(),
                            ctype="text/plain; version=0.0.4; charset=utf-8")
                    elif url.path == "/debug/metrics":
                        from blaze_tpu.obs.explain import humanize_metrics_dict
                        from blaze_tpu.obs.telemetry import get_registry

                        sess = getattr(self.server, "blaze_session", None)
                        tree = sess.metrics.to_dict() if sess is not None else {}
                        reg = get_registry()
                        fmt = parse_qs(url.query).get("format", [""])[0]
                        if fmt == "raw":
                            # exact integers: what soak scripts cross-check
                            body = {"session": tree, "registry": reg.to_raw()}
                            self._send(json.dumps(body, indent=2))
                        else:
                            body = humanize_metrics_dict(tree)
                            body["registry"] = reg.to_human()
                            self._send(json.dumps(body, indent=2))
                    elif url.path == "/debug/incidents":
                        from blaze_tpu.obs.dump import list_incidents

                        sess = getattr(self.server, "blaze_session", None)
                        conf = getattr(sess, "conf", None)
                        self._send(json.dumps(list_incidents(conf), indent=2))
                    elif url.path.startswith("/debug/incidents/"):
                        from blaze_tpu.obs.dump import load_incident

                        sess = getattr(self.server, "blaze_session", None)
                        conf = getattr(sess, "conf", None)
                        incident_id = url.path[len("/debug/incidents/"):]
                        bundle = load_incident(incident_id, conf)
                        if bundle is None:
                            self._send(json.dumps(
                                {"error": f"no incident {incident_id!r}"}),
                                status=404)
                        else:
                            self._send(json.dumps(bundle, indent=2,
                                                  default=str))
                    elif url.path == "/debug/profiles":
                        from blaze_tpu.obs.stats import list_profiles

                        sess = getattr(self.server, "blaze_session", None)
                        conf = getattr(sess, "conf", None)
                        self._send(json.dumps(list_profiles(conf), indent=2))
                    elif url.path.startswith("/debug/profiles/"):
                        from blaze_tpu.obs.stats import load_profile

                        sess = getattr(self.server, "blaze_session", None)
                        conf = getattr(sess, "conf", None)
                        fp = url.path[len("/debug/profiles/"):]
                        # in-memory first: a fresh profile may not have hit
                        # the store yet (or the store dir was cleaned)
                        profile = (getattr(sess, "profiles", {}) or {}).get(fp) \
                            if sess is not None else None
                        if profile is None:
                            profile = load_profile(fp, conf)
                        if profile is None:
                            self._send(json.dumps(
                                {"error": f"no profile {fp!r}"}), status=404)
                        else:
                            self._send(json.dumps(profile, indent=2,
                                                  default=str))
                    elif url.path == "/debug/trace":
                        from blaze_tpu.obs.tracer import TRACER

                        self._send(json.dumps(
                            TRACER.to_chrome_trace("blaze_tpu driver")))
                    elif url.path == "/debug/queries":
                        sess = getattr(self.server, "blaze_session", None)
                        body = []
                        # in-flight first, finished log LAST: consumers key
                        # off "the most recent finished query is queries[-1]"
                        sched = self._scheduler()
                        if sched is not None:
                            snap = sched.snapshot()
                            body.extend(snap["queued"] + snap["running"])
                        now = time.time()
                        for q in list(getattr(sess, "inflight", {}).values()
                                      if sess is not None else []):
                            mg = q.get("mem_group") or ""
                            if mg.startswith("serve_"):
                                continue  # already shown via the scheduler
                            d = _query_record(q)
                            d["elapsed_s"] = round(
                                now - q.get("started_unix", now), 3)
                            body.append(d)
                        log = list(getattr(sess, "query_log", []) or [])
                        # plan shapes are nested tuples — render compactly
                        body += [_query_record(q) for q in log]
                        self._send(json.dumps(body, indent=2, default=str))
                    elif url.path == "/serve/queries":
                        sched = self._scheduler()
                        if sched is None:
                            self._send(json.dumps(
                                {"error": "no serve scheduler attached"}),
                                status=404)
                        else:
                            self._send(json.dumps(sched.snapshot(), indent=2,
                                                  default=str))
                    elif url.path in ("/serve/status", "/serve/cancel",
                                      "/serve/result"):
                        sched = self._scheduler()
                        q = parse_qs(url.query)
                        if sched is None or "id" not in q:
                            self._send(json.dumps(
                                {"error": "no scheduler or missing id"}),
                                status=404)
                            return
                        qid = int(q["id"][0])
                        if url.path == "/serve/status":
                            st = sched.status(qid)
                            self._send(json.dumps(st, indent=2, default=str),
                                       status=200 if st is not None else 404)
                        elif url.path == "/serve/cancel":
                            ok = sched.cancel(qid)
                            self._send(json.dumps({"qid": qid,
                                                   "cancelled": ok}),
                                       status=200 if ok else 404)
                        else:  # /serve/result
                            with sched._mu:
                                h = sched._handles.get(qid)
                            if h is None:
                                self._send(json.dumps(
                                    {"error": f"unknown query {qid}"}),
                                    status=404)
                                return
                            timeout = min(float(
                                q.get("timeout_s", ["60"])[0]), 600.0)
                            try:
                                table = h.result(timeout=timeout)
                            except TimeoutError as exc:
                                self._send(json.dumps({"error": str(exc)}),
                                           status=408)
                                return
                            except BaseException as exc:
                                from blaze_tpu.serve import (Overloaded,
                                                             QueryRetryable)

                                body = {"error": type(exc).__name__,
                                        "reason": str(exc),
                                        "state": h.state}
                                if isinstance(exc, QueryRetryable):
                                    # infrastructure loss: safe to resubmit;
                                    # forensics at /debug/incidents/<id>
                                    body["retryable"] = True
                                    body["incident_id"] = exc.incident_id
                                    status = 503
                                elif isinstance(exc, Overloaded):
                                    status = 503
                                else:
                                    status = 500
                                self._send(json.dumps(body), status=status)
                                return
                            self._send(json.dumps(
                                {"qid": qid, "rows": table.num_rows,
                                 "columns": table.to_pydict()},
                                default=str))
                    elif url.path == "/debug/cache":
                        sess = getattr(self.server, "blaze_session", None)
                        cache = getattr(sess, "cache", None) \
                            if sess is not None else None
                        if cache is None:
                            self._send(json.dumps(
                                {"error": "result cache disabled"}),
                                status=404)
                        else:
                            body = cache.snapshot()
                            body["ingest"] = sess.ingest.snapshot()
                            self._send(json.dumps(body, indent=2,
                                                  default=str))
                    elif url.path == "/debug/pprof/profile":
                        # sampling profiler across ALL threads (cProfile only
                        # hooks the calling thread; engine work runs on task
                        # pool threads) — the pprof-style stack aggregate
                        q = parse_qs(url.query)
                        seconds = min(float(q.get("seconds", ["5"])[0]), 60)
                        hz = float(q.get("frequency", ["100"])[0])
                        self._send(_sample_profile(seconds, hz), "text/plain")
                    elif url.path == "/debug/memory":
                        from blaze_tpu.runtime.memmgr import MemManager

                        rss = _read_rss()
                        mm = MemManager._instance
                        body = {
                            "process_rss_bytes": rss,
                            "mem_manager": None if mm is None else mm.stats(),
                        }
                        self._send(json.dumps(body, indent=2))
                    elif url.path == "/debug/config":
                        from blaze_tpu.config import get_config

                        self._send(json.dumps(dataclasses.asdict(get_config()),
                                              indent=2, default=str))
                    elif url.path == "/debug/device":
                        from blaze_tpu.utils.device import DEVICE_STATS

                        self._send(json.dumps(DEVICE_STATS.snapshot(), indent=2))
                    elif url.path == "/debug/health":
                        from blaze_tpu.obs.timeline import get_timeline

                        self._send(json.dumps(
                            get_timeline().health_report(), indent=2))
                    elif url.path == "/debug/timeseries":
                        from blaze_tpu.obs.timeline import get_timeline

                        tl = get_timeline()
                        q = parse_qs(url.query)
                        name = q.get("name", [""])[0]
                        if not name:
                            self._send(json.dumps(
                                {"series": tl.names(),
                                 "enabled": tl.enabled,
                                 "interval_s": tl.interval_s}, indent=2))
                        else:
                            since = float(q.get("since", ["0"])[0])
                            samples = tl.series_since(name, since)
                            if samples is None:
                                self._send(json.dumps(
                                    {"error": f"no series {name!r}"}),
                                    status=404)
                            else:
                                self._send(json.dumps(
                                    {"name": name, "samples": samples}))
                    else:
                        self.send_response(404)
                        self.end_headers()

                def do_POST(self):
                    url = urlparse(self.path)
                    if url.path == "/ingest":
                        self._post_ingest()
                        return
                    if url.path != "/serve/submit":
                        self.send_response(404)
                        self.end_headers()
                        return
                    sched = self._scheduler()
                    if sched is None:
                        self._send(json.dumps(
                            {"error": "no serve scheduler attached"}),
                            status=503)
                        return
                    from blaze_tpu.serve import Backpressure, Overloaded

                    try:
                        length = int(self.headers.get("Content-Length", 0))
                        req = json.loads(self.rfile.read(length) or b"{}")
                        if "plan_b64" in req:
                            import base64

                            from blaze_tpu.ir.protoserde import \
                                plan_from_bytes

                            plan = plan_from_bytes(
                                base64.b64decode(req["plan_b64"]))
                        elif "spark_plan" in req:
                            from blaze_tpu.frontend.converter import \
                                SparkPlanConverter

                            conv = SparkPlanConverter(
                                tables=req.get("tables") or {})
                            plan = conv.convert(
                                json.dumps(req["spark_plan"])).plan
                        else:
                            self._send(json.dumps(
                                {"error": "need plan_b64 or spark_plan"}),
                                status=400)
                            return
                        deadline = req.get("deadline_s")
                        h = sched.submit(
                            plan, priority=int(req.get("priority", 0)),
                            deadline_s=float(deadline)
                            if deadline is not None else None,
                            label=req.get("label"),
                            tenant=req.get("tenant"))
                    except Backpressure as exc:
                        # retryable overload: the queue is full but
                        # draining — 429 + Retry-After tells well-behaved
                        # clients exactly when to come back
                        self._send(json.dumps(
                            {"error": "Backpressure", "reason": exc.reason,
                             "retry_after_s": round(exc.retry_after_s, 3)}),
                            status=429,
                            headers={"Retry-After":
                                     f"{exc.retry_after_s:.3f}"})
                        return
                    except Overloaded as exc:
                        # typed load shed: clients back off, they don't retry
                        # into the same wall
                        self._send(json.dumps({"error": "Overloaded",
                                               "reason": exc.reason}),
                                   status=503)
                        return
                    except Exception as exc:
                        self._send(json.dumps(
                            {"error": f"{type(exc).__name__}: {exc}"}),
                            status=400)
                        return
                    self._send(json.dumps({"qid": h.qid, "state": h.state,
                                           "label": h.label}))

                def _post_ingest(self):
                    # append-only streaming ingest: JSON rows become one
                    # batch of the named ingest table; the bumped version
                    # marks dependent cache entries stale (never served —
                    # refreshed incrementally or recomputed on next hit)
                    sess = getattr(self.server, "blaze_session", None)
                    if sess is None:
                        self._send(json.dumps(
                            {"error": "no session attached"}), status=503)
                        return
                    try:
                        import pyarrow as pa

                        length = int(self.headers.get("Content-Length", 0))
                        req = json.loads(self.rfile.read(length) or b"{}")
                        name = req.get("table")
                        rows = req.get("rows")
                        if not name or not isinstance(rows, dict) or not rows:
                            self._send(json.dumps(
                                {"error": "need table and non-empty rows"}),
                                status=400)
                            return
                        batch = pa.RecordBatch.from_pydict(rows)
                        version = sess.append(
                            name, [batch],
                            num_partitions=int(req.get("num_partitions", 2)))
                    except Exception as exc:
                        self._send(json.dumps(
                            {"error": f"{type(exc).__name__}: {exc}"}),
                            status=400)
                        return
                    self._send(json.dumps({"table": name, "version": version,
                                           "rows": batch.num_rows}))

            server = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
            server.blaze_session = session
            port = server.server_address[1]
            t = threading.Thread(target=server.serve_forever, daemon=True,
                                 name="blaze-http")
            t.start()
            cls._instance = ProfilingService(server, port)
            return cls._instance

    @classmethod
    def stop(cls):
        with cls._lock:
            if cls._instance is not None:
                cls._instance.server.shutdown()
                cls._instance.server.server_close()  # release the listen fd
                cls._instance = None


def _sample_profile(seconds: float, hz: float) -> str:
    """Wall-clock stack sampling over every thread via sys._current_frames
    (the all-thread analogue of the reference's pprof CPU profile)."""
    import sys
    import traceback
    from collections import Counter

    interval = 1.0 / max(hz, 1.0)
    deadline = time.time() + seconds
    stacks: Counter = Counter()
    samples = 0
    me = threading.get_ident()
    while time.time() < deadline:
        for tid, frame in sys._current_frames().items():
            if tid == me:
                continue
            stack = tuple(
                f"{fs.filename.rsplit('/', 1)[-1]}:{fs.lineno}:{fs.name}"
                for fs in traceback.extract_stack(frame)[-25:]
            )
            stacks[stack] += 1
        samples += 1
        time.sleep(interval)
    lines = [f"# wall-clock samples: {samples} over {seconds}s across threads",
             "function calls sampled (top stacks):"]
    for stack, count in stacks.most_common(40):
        lines.append(f"--- {count} samples")
        lines.extend(f"    {s}" for s in stack[-12:])
    return "\n".join(lines) + "\n"


def _read_rss() -> int:
    try:
        with open("/proc/self/statm") as f:
            pages = int(f.read().split()[1])
        import os

        return pages * os.sysconf("SC_PAGE_SIZE")
    except Exception:
        return -1
