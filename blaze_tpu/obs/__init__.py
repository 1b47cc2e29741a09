"""Observability: span tracing (Chrome trace events), the process metrics
registry (Prometheus exposition), EXPLAIN ANALYZE rendering, incident
forensics, and the per-query stats plane. See ``obs/tracer.py``,
``obs/telemetry.py``, ``obs/explain.py``, ``obs/dump.py`` and
``obs/stats.py``."""

from blaze_tpu.obs.dump import (dump_profile, list_incidents, load_incident,
                                record_incident)
from blaze_tpu.obs.explain import (fmt_bytes, fmt_ns, humanize_metrics_dict,
                                   merge_partition_metrics, op_shape,
                                   render_explain_analyze)
from blaze_tpu.obs.stats import (StatsPlane, list_profiles, load_profile,
                                 plan_fingerprint, save_profile,
                                 stage_summary_line)
from blaze_tpu.obs.telemetry import (REGISTRY, Counter, Gauge, Histogram,
                                     MetricsRegistry, get_registry,
                                     parse_prometheus_text)
from blaze_tpu.obs.tracer import TRACER, Tracer, configure_from, get_tracer

__all__ = [
    "TRACER", "Tracer", "configure_from", "get_tracer",
    "REGISTRY", "MetricsRegistry", "Counter", "Gauge", "Histogram",
    "get_registry", "parse_prometheus_text",
    "fmt_ns", "fmt_bytes", "humanize_metrics_dict", "op_shape",
    "merge_partition_metrics", "render_explain_analyze", "dump_profile",
    "record_incident", "list_incidents", "load_incident",
    "StatsPlane", "plan_fingerprint", "stage_summary_line", "save_profile",
    "load_profile", "list_profiles",
]
