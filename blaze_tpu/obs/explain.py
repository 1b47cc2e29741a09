"""EXPLAIN ANALYZE rendering + duration formatting.

DataFusion's ``EXPLAIN ANALYZE`` prints the physical tree with per-operator
``metrics=[output_rows=…, elapsed_compute=…]``; the reference engine gets the
same picture by mirroring its native metric tree into the Spark UI per node.
Here :func:`render_explain_analyze` walks the *operator shape* (name tree)
positionally against the task metric trees (which mirror it by construction:
``Operator.execute_child(i)`` writes into ``metrics.child(i)``), merging all
partitions/tasks of a stage into one annotated tree.

Time metrics follow the ``*_time_ns`` suffix convention and render as
human-readable durations (:func:`fmt_ns`).
"""

from __future__ import annotations

from typing import List, Optional, Tuple

from blaze_tpu.runtime.metrics import MetricNode

# metrics rendered inline with dedicated labels (everything else *_time_ns
# renders generically, counters render raw)
_PRIMARY = ("output_rows", "output_batches", "elapsed_compute_time_ns")


def fmt_ns(ns: int) -> str:
    """Human duration from nanoseconds: 2h05m / 4m12s / 1.23s / 45.6ms /
    7.8us / 90ns. Hour/minute tiers keep long soak counters readable
    (5025.37s is not a duration anyone can parse at a glance)."""
    ns = int(ns)
    if ns >= 3_600_000_000_000:
        h, rem = divmod(ns, 3_600_000_000_000)
        return f"{h}h{rem // 60_000_000_000:02d}m"
    if ns >= 60_000_000_000:
        m, rem = divmod(ns, 60_000_000_000)
        return f"{m}m{rem // 1_000_000_000:02d}s"
    if ns >= 1_000_000_000:
        return f"{ns / 1e9:.2f}s"
    if ns >= 1_000_000:
        return f"{ns / 1e6:.1f}ms"
    if ns >= 1_000:
        return f"{ns / 1e3:.1f}us"
    return f"{ns}ns"


def fmt_bytes(n: int) -> str:
    n = int(n)
    for unit, div in (("GB", 1 << 30), ("MB", 1 << 20), ("KB", 1 << 10)):
        if n >= div:
            return f"{n / div:.1f}{unit}"
    return f"{n}B"


def humanize_metrics_dict(d: dict) -> dict:
    """Recursively annotate a ``MetricNode.to_dict()`` tree: every
    ``*_time_ns`` value gains a rendered sibling under ``durations`` so
    ``/debug/metrics`` shows 12.3ms instead of raw nanosecond integers."""
    values = d.get("values") or {}
    out = {"name": d.get("name"), "values": values}
    durations = {k: fmt_ns(v) for k, v in values.items()
                 if k.endswith("_time_ns")}
    if durations:
        out["durations"] = durations
    out["children"] = [humanize_metrics_dict(c) for c in d.get("children") or []]
    return out


# -- operator shapes ----------------------------------------------------------


def op_shape(op) -> Tuple[str, list]:
    """Lightweight ``(name, [child shapes])`` mirror of an operator tree —
    what the session records per stage so explain can label the positional
    metric tree without keeping operators (or plans) alive.

    A FusedStageExec additionally lists its absorbed operators as "+"-marked
    pseudo-children (outermost-first, after the real child shapes so the
    positional metric match is undisturbed): the fusion boundary stays
    visible in EXPLAIN ANALYZE / ``/debug/queries`` even though the whole
    stage executed as one operator with one self-time."""
    children = [op_shape(c) for c in op.children]
    fused = getattr(op, "fused_op_names", None)
    if fused:
        children += [(f"+ {n} (fused)", []) for n in reversed(fused)]
    return (op.name, children)


def shape_lines(shape: Tuple[str, list], indent: int = 0) -> List[str]:
    """Indented plan outline from an ``op_shape`` tree, metrics-free — the
    compact form ``/debug/queries`` embeds so fusion boundaries (the
    "+ …(fused)" pseudo-children) are visible per query without the full
    EXPLAIN ANALYZE."""
    name, children = shape
    lines = [("  " * indent) + name]
    for c in children:
        lines.extend(shape_lines(c, indent + 1))
    return lines


def merge_partition_metrics(parts: List[MetricNode]) -> MetricNode:
    """Fold per-partition/task metric trees (identical positional shape)
    into one aggregate tree, keeping the first real node name seen."""
    merged = MetricNode("merged")

    def fold(dst: MetricNode, src_dict: dict):
        # adopt the first REAL operator name (auto-created placeholder names
        # embed a "." path prefix; executed nodes carry bare class names)
        name = src_dict.get("name") or ""
        if name and "." not in name and \
                ("." in dst.name or dst.name == "merged"):
            dst.name = name
        for k, v in (src_dict.get("values") or {}).items():
            dst.add(k, v)
        for i, c in enumerate(src_dict.get("children") or []):
            fold(dst.child(i), c)

    for p in parts:
        fold(merged, p.to_dict())
    return merged


def _node_line(name: str, node: Optional[MetricNode]) -> str:
    if name.startswith("+ "):
        # fused pseudo-child: absorbed into the enclosing FusedStageExec,
        # which carries the stage's single self-time — no metrics of its own
        return name
    if node is None:
        return f"{name}  [not executed]"
    values = dict(node.values)
    rows = values.pop("output_rows", 0)
    batches = values.pop("output_batches", 0)
    elapsed = values.pop("elapsed_compute_time_ns", 0)
    parts = [f"rows={rows}", f"batches={batches}",
             f"elapsed_compute={fmt_ns(elapsed)}"]
    spill_count = values.pop("spill_count", 0)
    spill_bytes = values.pop("spilled_bytes", 0)
    spill_time = values.pop("spill_io_time_ns", 0)
    if spill_count:
        parts.append(f"spill[count={spill_count} bytes={fmt_bytes(spill_bytes)}"
                     f" time={fmt_ns(spill_time)}]")
    mem_spills = values.pop("mem_spill_count", 0)
    mem_spill_size = values.pop("mem_spill_size", 0)
    mem_spill_time = values.pop("mem_spill_time_ns", 0)
    if mem_spills:
        parts.append(f"mem_spill[count={mem_spills}"
                     f" size={fmt_bytes(mem_spill_size)}"
                     f" time={fmt_ns(mem_spill_time)}]")
    # shuffle writers record per-reducer row counts (stats plane feed);
    # summarize instead of printing one key per partition
    part_rows = sorted(values.pop(k) for k in list(values)
                       if k.startswith("part_rows_"))
    if part_rows:
        mid = part_rows[len(part_rows) // 2]
        parts.append(f"part_rows[n={len(part_rows)}"
                     f" total={sum(part_rows)}"
                     f" max={part_rows[-1]} med={mid}]")
    for k in sorted(values):
        v = values[k]
        parts.append(f"{k[:-8]}={fmt_ns(v)}" if k.endswith("_time_ns")
                     else f"{k}={v}")
    return f"{name}  " + " ".join(parts)


def render_annotated_tree(shape: Tuple[str, list],
                          metrics: Optional[MetricNode],
                          indent: int = 0) -> List[str]:
    name, children = shape
    pad = "  " * indent
    lines = [pad + _node_line(name, metrics)]
    for i, child in enumerate(children):
        child_metrics = None
        if metrics is not None and i < len(metrics.children):
            child_metrics = metrics.children[i]
        lines.extend(render_annotated_tree(child, child_metrics, indent + 1))
    return lines


def render_explain_analyze(query: dict, session_metrics: MetricNode) -> str:
    """Render one executed query (the record ``Session.execute`` keeps in
    ``session._last_query``) as an EXPLAIN ANALYZE text block: the result
    stage tree first, then each exchange stage it ran, all annotated."""
    lines = [
        f"== Query {query['id']}: wall {fmt_ns(int(query['wall_s'] * 1e9))},"
        f" {query['rows']} rows out,"
        f" {query['nparts']} result partition(s) ==",
    ]
    result_parts = [session_metrics.get_named(k)
                    for k in query["result_keys"]]
    result_parts = [p for p in result_parts if p is not None]
    merged = merge_partition_metrics(result_parts) if result_parts else None
    lines.extend(render_annotated_tree(query["shape"], merged))
    stats = query.get("stats") or {}
    stage_stats = {s.get("stage"): s for s in stats.get("stages") or []}
    for stage in query["stages"]:
        sid = stage["id"]
        lines.append(f"-- Stage {sid} [{stage['kind']}]"
                     f" ({stage['num_tasks']} task(s)) --")
        srec = stage_stats.get(sid)
        if srec is not None:
            from blaze_tpu.obs.stats import stage_summary_line

            lines.append("   " + stage_summary_line(srec))
        stage_node = session_metrics.get_named(f"stage_{sid}")
        task_parts = []
        if stage_node is not None:
            task_parts = [stage_node.get_named(f"map_{m}")
                          for m in range(stage["num_tasks"])]
            task_parts = [p for p in task_parts if p is not None]
        merged = merge_partition_metrics(task_parts) if task_parts else None
        lines.extend(render_annotated_tree(stage["shape"], merged))
    cache = stats.get("cache")
    if cache:
        # subtrees whose map stages never ran: served from the subplan
        # cache as staged batch references (blaze_tpu/cache/)
        lines.append(
            f"-- Cache: {cache.get('cache_subplan_hits', 0)} subtree(s) "
            f"served from subplan cache "
            f"({cache.get('cache_served_bytes', 0)} bytes, fingerprints "
            f"{', '.join(cache.get('cache_served') or [])}) --")
    ops = stats.get("operators") or []
    paired = [o for o in ops if o.get("est_rows") is not None]
    if paired:
        # the AQE signal: ordered estimate-vs-observed cardinalities
        lines.append("-- Cardinality (estimated vs actual) --")
        for o in paired:
            lines.append(
                f"   {o['op']}: est={o['est_rows']}"
                f" actual={o['actual_rows']}")
    attr = stats.get("attribution")
    if attr:
        from blaze_tpu.obs.attribution import CATEGORIES

        wall = int(attr.get("wall_ns") or 0)
        lines.append("-- Wall-time attribution (exclusive) --")
        parts = []
        for c in CATEGORIES:
            v = int(attr.get(f"{c}_time_ns") or 0)
            if v:
                pct = f" ({100.0 * v / wall:.0f}%)" if wall else ""
                parts.append(f"{c}={fmt_ns(v)}{pct}")
        cov = attr.get("coverage_fraction")
        parts.append(f"coverage={cov:.2f}" if cov is not None else "coverage=?")
        lines.append("   " + " ".join(parts))
    cp = stats.get("critical_path")
    if cp:
        from blaze_tpu.obs.attribution import critical_path_lines

        lines.append("-- Critical path --")
        lines.extend("   " + ln for ln in critical_path_lines(cp))
    return "\n".join(lines)
