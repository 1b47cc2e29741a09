"""Metric tree mirroring the plan tree.

Reference: JVM ``MetricNode`` (MetricNode.scala) mirrored by the native
``ExecutionPlanMetricsSet`` and pushed back at task end
(``auron/src/metrics.rs``). Canonical names follow
``NativeHelper.getDefaultNativeMetrics:94-125``.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional


class MetricNode:
    def __init__(self, name: str, children: Optional[List["MetricNode"]] = None):
        self.name = name
        self.children = children or []
        self.values: Dict[str, int] = {}
        self._named: Dict[str, "MetricNode"] = {}
        self._mu = threading.Lock()

    def add(self, metric: str, value: int):
        with self._mu:
            self.values[metric] = self.values.get(metric, 0) + int(value)

    def set(self, metric: str, value: int):
        with self._mu:
            self.values[metric] = int(value)

    def get(self, metric: str) -> int:
        with self._mu:
            return self.values.get(metric, 0)

    def child(self, i: int) -> "MetricNode":
        with self._mu:
            while len(self.children) <= i:
                self.children.append(MetricNode(f"{self.name}.child{len(self.children)}"))
            return self.children[i]

    def named_child(self, key: str) -> "MetricNode":
        """Keyed child for driver-side groupings (stages vs result
        partitions) so namespaces cannot collide."""
        with self._mu:
            node = self._named.get(key)
            if node is None:
                node = self._named[key] = MetricNode(f"{self.name}.{key}")
                self.children.append(node)
            return node

    def get_named(self, key: str) -> Optional["MetricNode"]:
        """Existing keyed child or None — the read-only counterpart of
        ``named_child`` (explain/debug rendering must not grow the tree)."""
        with self._mu:
            return self._named.get(key)

    def timer(self, metric: str) -> "Timer":
        return Timer(self, metric)

    def to_dict(self) -> dict:
        # snapshot under the lock: /debug/metrics and explain_analyze read
        # this tree while task threads mutate values/children concurrently
        with self._mu:
            name = self.name
            values = dict(self.values)
            children = list(self.children)
        return {
            "name": name,
            "values": values,
            "children": [c.to_dict() for c in children],
        }

    def total(self, metric: str) -> int:
        with self._mu:
            own = self.values.get(metric, 0)
            children = list(self.children)
        return own + sum(c.total(metric) for c in children)

    def totals(self, metrics) -> Dict[str, int]:
        """Totals of several metrics in ONE tree walk. ``total()`` per
        name re-walks the whole tree each time — fine for a single
        lookup, quadratic for periodic samplers and tripwire blocks that
        want 20+ names at once."""
        out = {m: 0 for m in metrics}
        stack = [self]
        while stack:
            node = stack.pop()
            with node._mu:
                for m in out:
                    out[m] += node.values.get(m, 0)
                stack.extend(node.children)
        return out

    def merge_dict(self, d: dict):
        """Fold a serialized metric tree (to_dict of a remote task) into
        this node — how worker-process task metrics reach the driver's tree
        (reference: update_spark_metric_node pushing native metrics into the
        JVM MetricNode mirror at task end). Children merge POSITIONALLY:
        remote node names embed the remote root's prefix, and name-keyed
        merging would give pool and in-driver runs different tree shapes.
        Auto-created child placeholders do adopt the remote OPERATOR name
        (bare class names, no '.' path prefix) so pool-run task trees render
        with real node labels in /debug/metrics and explain_analyze."""
        name = d.get("name") or ""
        if name and "." not in name:
            with self._mu:
                if "." in self.name:
                    self.name = name
        for k, v in (d.get("values") or {}).items():
            self.add(k, v)
        for i, c in enumerate(d.get("children") or []):
            self.child(i).merge_dict(c)


# Invariant "tripwire" counters: cheap global counts whose expected
# relationship flags a silently-degraded fast path — a plan can produce
# correct results at 10x the cost and no test notices, but a diffed counter
# does. chip_smoke.py and the benchmark's ``counters_must`` read these, so a
# regression shows up as a number, not a slowdown hunt. Current invariants:
#   split_gathers == split_batches   range split gathers ONCE per batch
#   window_group_loops == 0          segmentable windows (counters +
#                                    default-frame aggs) never take the
#                                    buffered per-group loop
#   window_segments > 0              on window-bearing plans: the segmented
#                                    path actually ran (and saw partitions)
#   ipc_decode_in_prefetch > 0       on shuffle-bearing plans: frame decode
#                                    happens in the reader's worker pool,
#                                    not on the consumer thread
#   fused_stages > 0                 on plans with fusable narrow chains:
#                                    whole-stage fusion engaged (fused_ops
#                                    counts the operators it absorbed)
#   jit_cache_misses ~ #shapes       fused closures compile once per
#                                    (fingerprint, capacity bucket); misses
#                                    growing with batch count is a
#                                    recompile storm
#   fused_fallback_batches == 0      fused stages executed their jitted
#                                    closure, not the eager fallback
#   agg_reintern_rows == 0           var-width agg keys cross the exchange
#                                    as dictionary codes; merge tables never
#                                    re-encode decoded values per batch
#   agg_radix_buckets > 0            on high-cardinality int-keyed aggs:
#                                    the radix-partitioned device kernel ran
#                                    (counts buckets scanned per pass)
#   codes_shuffle_bytes              bytes shipped as codes+dictionaries by
#                                    the code-carrying shuffle (0 on plans
#                                    without dictionary columns)
#   shuffle_bytes_serialized         bytes pushed through the classic IPC
#                                    serde on shuffle-write paths; ~0 on
#                                    same-host runs with zero_copy_shuffle
#                                    (raw segments replace serde frames)
#   shm_bytes_mapped                 frame payload bytes served to readers
#                                    from mmap'd shm segments (no decode)
#   serde_elided_batches             batches exchanged as in-process
#                                    references (process tier) with serde
#                                    skipped entirely
#   shuffle_tier_degraded            map outputs that fell back from the
#                                    shm tier to the spill dir on ENOSPC,
#                                    and on the device tier every batch
#                                    (host-backed input) or map output (a
#                                    failed placement, the byte budget)
#                                    that went the host way (0 on healthy
#                                    runs; > 0 proves the degrade path ran
#                                    instead of the query failing)
#   sharded_stages                   exchanges lowered onto the device
#                                    mesh's all-to-all collective; 0 with
#                                    multichip off, > 0 proves the
#                                    multichip path actually engaged
#   mesh_tasks_off_primary           tasks a multichip session ran on a
#                                    chip other than the mesh's first (every
#                                    task runs on the chip of its partition:
#                                    parallel/mesh.task_chip); 0 where every
#                                    task of a mesh is funnelled to chip 0
#   mesh_host_resident_exchanges == 0  ... on a mesh whose exchanges fit
#                                    mesh_device_resident_max_bytes: mesh
#                                    exchanges whose reducer batches went to
#                                    host RAM because the live queries'
#                                    device-resident exchanges had taken the
#                                    budget
#   device_shuffle_bytes             device-resident column bytes (planes
#                                    with their padding) handed between
#                                    stages by reference — the "device"
#                                    shuffle tier's routed sub-batches and
#                                    the elided collects — with no pull
#                                    and no upload: the device twin of
#                                    serde_elided_batches
#   collective_bytes                 bytes moved by mesh all-to-all
#                                    collectives in place of shuffle file
#                                    writes (MeshBatchExchange wire bytes)
#   smj_device_joins                 sort-merge join partitions joined by
#                                    the device programs (ops/joins/smj.py:
#                                    fixed-width device keys, no condition)
#   smj_host_joins == 0              ... on plans whose join keys all live
#                                    on the device: partitions that took the
#                                    host's key interning instead (var-width
#                                    or host-resident keys, a condition)
#   window_device_batches            window batches computed by the device
#                                    program (ops/window_device.py: the rank
#                                    family and running-frame SUM / COUNT /
#                                    MIN / MAX over fixed-width keys)
#   window_host_batches == 0         ... on plans of such windows: batches
#                                    that took a host path (default or
#                                    offset frames, var-width or float keys,
#                                    AVG, float arguments)
#   wide_host_batches == 0           ... on plans over money: batches of the
#                                    device program with a wide-decimal
#                                    result past int64, which left as the
#                                    type's host decimal128 column and not
#                                    as one int64 device plane
#   window_rows                      rows every window operator saw, either
#                                    path (what the benchmark's
#                                    window_roofline_share counts bytes from)
#   coded_key_batches                batches a join, an Expand or an
#                                    aggregation stage worked through with
#                                    a var-width column as CODES (int32
#                                    codes and validity on the device, one
#                                    host dictionary: core/batch.CodedColumn)
#   host_key_batches == 0            ... on plans whose names are read
#                                    dictionary-encoded: batches whose coded
#                                    column was turned into a host column
#                                    for an operator to work on (one that
#                                    does not take coded columns, an
#                                    expression over the values, the host
#                                    aggregation table, a key encoded on
#                                    the host), counted on the node the
#                                    asking operator passes in
#                                    (ColumnarBatch.coded_to_host, the
#                                    operator's ExprEvaluator)
#   rollup_rows                      rows out of ExpandExec (what the
#                                    benchmark's rollup_roofline_share
#                                    counts a rollup's bytes from)
#   dict_entries                     entries of the dictionaries scans
#                                    adopted (one a coded column and scan
#                                    task, grown where row groups differ)
#   dict_remap_rows                  rows whose codes went through a remap
#                                    table because two dictionaries met
#                                    (core/dictionary.unify: a scan's row
#                                    groups, a window's batches, a concat
#                                    that was handed the operator's node);
#                                    0 where one dictionary serves a
#                                    column throughout
#   join_generic_batches == 0        ... on unique-single-key inner joins
#                                    over device and coded columns: probe
#                                    batches that left jit(bhj_inner_fast)
#                                    (device_inner_batches counts its own)
#                                    for the generic probe
#   moment_device_batches >= 1       STDDEV_SAMP over an integral argument:
#                                    aggregation batches (partial or merge)
#                                    whose exact moments a device kernel
#                                    reduced (jit(agg_dense_partial),
#                                    jit(agg_partial), jit(agg_merge))
#   moment_host_batches == 0         ... none went through the generic table
#                                    (a DOUBLE or decimal argument's double
#                                    state always does)
#   moment_exact_groups              groups whose finish passed 2^53 and went
#                                    by Python integers (ops/aggfns
#                                    moment_stddev)
#   merge_slot_sorted_batches        FINAL / PARTIAL_MERGE merges reduced by
#                                    one sort of their packed integer key id
#                                    (jit(agg_merge_sorted)) in place of
#                                    jit(agg_merge)'s lex_order_traced
TRIPWIRE_METRICS = (
    "split_batches",
    "split_gathers",
    "window_segments",
    "window_group_loops",
    "streamed_partitions",
    "ipc_decode_in_prefetch",
    "fused_stages",
    "fused_ops",
    "jit_cache_hits",
    "jit_cache_misses",
    "fused_fallback_batches",
    "agg_reintern_rows",
    "agg_radix_buckets",
    "codes_shuffle_bytes",
    "shuffle_bytes_serialized",
    "shm_bytes_mapped",
    "serde_elided_batches",
    "shuffle_tier_degraded",
    "sharded_stages",
    "mesh_tasks_off_primary",
    "mesh_host_resident_exchanges",
    "device_shuffle_bytes",
    "collective_bytes",
    "smj_device_joins",
    "smj_host_joins",
    "window_device_batches",
    "window_host_batches",
    "wide_host_batches",
    "window_rows",
    "coded_key_batches",
    "host_key_batches",
    "rollup_rows",
    "dict_entries",
    "dict_remap_rows",
    "join_generic_batches",
    "moment_device_batches",
    "moment_host_batches",
    "moment_exact_groups",
    "merge_slot_sorted_batches",
)


def tripwire_totals(node: "MetricNode") -> Dict[str, int]:
    """Totals of the tripwire counters for a metric tree (session root or a
    single query)."""
    return node.totals(TRIPWIRE_METRICS)


class Timer:
    """Accumulates nanoseconds into a metric. The reference subtracts
    downstream send-wait so self-time is accurate
    (WrappedSender.exclude_time, execution_context.rs:705-730); here operator
    generators naturally exclude consumer time because timing stops at yield.
    """

    def __init__(self, node: MetricNode, metric: str):
        self.node = node
        self.metric = metric

    def __enter__(self):
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        self.node.add(self.metric, time.perf_counter_ns() - self._t0)
        return False


def query_metric_snapshot(session_metrics: "MetricNode", query: dict) -> dict:
    """Per-operator metric snapshot for ONE query record (the dict
    ``Session.execute`` keeps in ``query_log``/``inflight``): the merged
    result-partition tree plus each exchange stage's merged task tree —
    the metrics half of an incident bundle, shaped like ``to_dict()``."""
    from blaze_tpu.obs.explain import merge_partition_metrics

    out = {"result": None, "stages": {}}
    parts = [session_metrics.get_named(k)
             for k in (query.get("result_keys") or [])]
    parts = [p for p in parts if p is not None]
    if parts:
        out["result"] = merge_partition_metrics(parts).to_dict()
    for stage in (query.get("stages") or []):
        sid = stage.get("id")
        stage_node = session_metrics.get_named(f"stage_{sid}")
        if stage_node is None:
            continue
        task_parts = [stage_node.get_named(f"map_{m}")
                      for m in range(stage.get("num_tasks") or 0)]
        task_parts = [p for p in task_parts if p is not None]
        if task_parts:
            out["stages"][str(sid)] = \
                merge_partition_metrics(task_parts).to_dict()
    return out
