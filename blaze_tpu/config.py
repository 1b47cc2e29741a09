"""Engine configuration.

Mirrors the reference's three-tier conf system keyed ``spark.auron.*``
(``spark-extension/src/main/java/.../AuronConf.java:23-130`` and
``auron-jni-bridge/src/conf.rs:32-111``): one typed source of truth the whole
engine reads. Here it is a process-global dataclass with context overrides; a
frontend (Spark plugin) would populate it from SparkConf.
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
from typing import Optional


@dataclasses.dataclass
class Config:
    # Rows per batch. The reference defaults to 10000 (AuronConf.BATCH_SIZE).
    # Ours is much larger, chosen for a link that is gone; not measured on
    # the chip. Powers of two match the capacity bucketing and XLA tiling.
    batch_size: int = 262144

    # Suggested in-memory bytes per batch (reference: suggested_batch_mem_size,
    # datafusion-ext-commons/src/lib.rs:74-118).
    suggested_batch_mem_size: int = 8 << 20

    # Fraction of the process memory budget handed to the memory manager
    # (reference: MEMORY_FRACTION=0.6, MemManager::init(total * fraction)).
    memory_fraction: float = 0.6
    # Total memory budget in bytes; None = derive from system.
    memory_total: Optional[int] = None
    # How long an under-share producer blocks for peers to spill before
    # spilling itself (reference waits on a condvar with a 10s timeout,
    # memmgr/mod.rs:301-421; shorter default keeps single-threaded stalls
    # bounded).
    mem_wait_timeout_s: float = 2.0

    # AQE skew-join splitting (reference: isSkewJoin + partial shuffle reads
    # flowing through the IR, AuronConverters.scala:420-489): a reducer
    # whose stream-side bytes exceed factor x median (and the floor) splits
    # into map-subset sub-partitions joined against the full other side.
    skew_join_enable: bool = True
    skew_join_factor: float = 3.0
    skew_join_min_bytes: int = 64 << 20

    # scan column pruning / projection pushdown (reference:
    # ExecuteWithColumnPruning, common/column_pruning.rs:22-48)
    column_pruning_enable: bool = True

    # Device FINAL/PARTIAL_MERGE aggregation buffers all partial-state
    # batches before one merge kernel call; beyond this size it falls back
    # to the spill-capable host table.
    device_merge_max_bytes: int = 256 << 20

    # Mesh-exchange reducer outputs stay device-resident (HBM, held by the
    # resource map until their query is released) only while the TOTAL
    # payload across the live queries' exchanges stays below this — the
    # session charges each resident exchange to its query's resource and
    # gives it back with the query, and anything beyond it materializes to
    # host RAM like shuffle files, so stacked exchanges cannot accumulate
    # unbounded HBM.
    mesh_device_resident_max_bytes: int = 128 << 20

    # Per-device per-round byte budget for the compacted mesh exchange's
    # send buffers. Segment capacity is the max per-(shard, reducer) row
    # count; one skewed reducer would otherwise pad EVERY segment to the
    # hot size. Beyond the budget the exchange runs in multiple bounded
    # rounds over the same compiled step.
    mesh_exchange_round_bytes: int = 256 << 20

    # Multichip device-primary execution: when enabled, a Session without
    # an explicit ``mesh=`` argument builds one over the local devices
    # (parallel/mesh.py make_mesh), runs every task on the chip of its
    # partition and lowers exchanges onto the ICI all-to-all between the
    # chips. Off by default: CI's tier-1 command
    # (JAX_PLATFORMS=cpu) must behave exactly as before. Dev boxes emulate
    # the mesh with XLA_FLAGS=--xla_force_host_platform_device_count=8.
    multichip_enabled: bool = False

    # Device count for the config-built mesh. 0 = all local devices. A
    # request beyond the local device count clamps (escape hatch for
    # sharing a box); 1 still builds a mesh so the code path is identical.
    multichip_devices: int = 0

    # The "device" shuffle tier: a pool-less session whose stages run on an
    # accelerator (mesh or no mesh; also any session with a mesh) routes
    # each batch of a hash, range or round-robin exchange on the chip and
    # commits the device-resident sub-batch references into the
    # MemSegmentRegistry — the rows are never pulled and never uploaded
    # again. On the CPU backend the session negotiates the host "process"
    # tier instead (one memory: numpy routing is the cheaper one). False
    # pins every session back to "process" (escape hatch); the tier also
    # degrades per batch (host-backed input) or per map output (past the
    # byte budget, or when the ``device.put`` failpoint fires).
    device_shuffle_tier: bool = True

    # AQE small-partition coalescing (Spark's coalescePartitions): adjacent
    # reducer partitions below the advisory size merge into one read task
    # when no ancestor relies on the exchange's partition count.
    coalesce_partitions_enable: bool = True
    advisory_partition_bytes: int = 8 << 20

    # Task retry policy for transient failures (deterministic errors fail
    # fast; reference delegates this to Spark's TaskScheduler).
    task_max_retries: int = 2
    task_retry_backoff_s: float = 0.2

    # Fault tolerance for the worker-process pool (runtime/cluster.py) —
    # the standalone analogue of Spark's executor blacklisting + stage
    # abort thresholds:
    #   fault_max_worker_deaths   circuit breaker: more deaths than this
    #                             within ONE map stage aborts the stage with
    #                             a typed WorkerPoolBroken (retryable at the
    #                             serve layer) instead of retrying forever.
    #   fault_exclusion_ttl_s     a worker slot whose process died is
    #                             excluded from pulling new tasks for this
    #                             long (its respawned process gets a cooling
    #                             period; at least one eligible worker is
    #                             always kept so a stage can make progress).
    #   fault_respawn_backoff_s   base of the exponential backoff between a
    #                             worker slot's consecutive respawns.
    #   fault_heartbeat_interval_s  supervisor liveness-probe period: worker
    #                             deaths are noticed between stages, not
    #                             only when a mid-task recv fails.
    fault_max_worker_deaths: int = 4
    fault_exclusion_ttl_s: float = 30.0
    fault_respawn_backoff_s: float = 0.2
    fault_heartbeat_interval_s: float = 0.5

    # Reduce-side verification of map-output footers: the cheap length +
    # magic check always runs; True additionally recomputes the payload
    # crc32 on every open (paranoid mode for chaos soaks/tests).
    shuffle_verify_checksum: bool = False

    # Failpoint fault injection (runtime/failpoints.py): ';'-separated
    # arming spec, e.g. "shm.commit=enospc:every3;frame.decode=corrupt:x2".
    # Ships to worker processes inside every task conf so injection reaches
    # task code; BLAZE_TPU_FAILPOINTS overrides per-process. Empty = off.
    failpoints: str = dataclasses.field(
        default_factory=lambda: os.environ.get("BLAZE_TPU_FAILPOINTS", ""))
    # Seed for the deterministic probability/corruption streams (each site
    # derives its own sub-stream, so runs are reproducible).
    failpoint_seed: int = 0

    # Hard per-task wall-clock timeout on the worker pool, on top of
    # speculation: when EVERY in-flight copy of a task (original and
    # speculative) has been running longer than this, the workers holding
    # them are marked suspect and recycled, the task is charged to the
    # retry budget and rerouted. 0 disables (the default: timeouts are a
    # chaos/serve policy, not a batch default).
    task_timeout_s: float = 0.0

    # Compression codec for shuffle/spill streams: "zstd" | "lz4" | "none".
    # (reference: spark.auron.shuffle.compression.codec, default lz4; we default
    # to zstd level 1 since the python lz4 binding is absent and libzstd is fast)
    shuffle_compression_codec: str = "zstd"
    spill_compression_codec: str = "zstd"
    zstd_level: int = 1

    # Byte-plane transpose of fixed-width columns before compression
    # (reference: io/batch_serde.rs TransposeOpt — boosts ratios).
    serde_transpose: bool = True

    # Partial-agg adaptive skipping (reference: PARTIAL_AGG_SKIPPING_ENABLE,
    # ratio 0.9 after 50k rows — agg_ctx.rs, AuronConf.java).
    partial_agg_skipping_enable: bool = True
    partial_agg_skipping_ratio: float = 0.9
    partial_agg_skipping_min_rows: int = 50_000

    # SortMergeJoin fallback threshold for shuffled-hash-join memory risk
    # (reference: SMJ_FALLBACK_* in AuronConf.java).
    smj_fallback_enable: bool = True
    smj_fallback_rows_threshold: int = 10_000_000
    smj_fallback_mem_size_threshold: int = 1 << 30

    # Spill directory (reference spills via JVM OnHeapSpillManager or disk;
    # we spill device->host->disk files here).
    spill_dir: str = dataclasses.field(
        default_factory=lambda: os.environ.get("BLAZE_TPU_SPILL_DIR", "/tmp/blaze_tpu_spill")
    )

    # Remote-shuffle protocol when a Session runs with rss_sock_path:
    # "native" = the plain push/fetch ops; "celeborn" = the full Celeborn
    # protocol loop (registerShuffle -> framed pushes -> mapperEnd ->
    # commitFiles -> openStream/chunk-fetch), every control + data message
    # wire-framed (reference: AuronCelebornShuffleManager).
    rss_protocol: str = "native"

    # Span tracing (obs/tracer.py): record Chrome-trace events for
    # query/stage/task/operator/spill/shuffle-fetch/kernel spans, served at
    # /debug/trace (Perfetto-loadable) and dumped by scripts/profile_query.py.
    # Off by default: every recording site is behind one bool check, so the
    # disabled path stays near-free (guarded by test_tracing.py's <5%
    # overhead test). BLAZE_TPU_TRACE=1 force-enables.
    trace_enable: bool = False
    # Event-buffer cap: beyond it new events are counted as dropped, not
    # stored (bounds tracer memory during soaks).
    trace_max_events: int = 1_000_000

    # Process-wide metrics registry (obs/telemetry.py): typed counters /
    # gauges / log-bucketed histograms exposed as Prometheus text at
    # GET /metrics and exact values at GET /debug/metrics?format=raw.
    # ON by default — one instrument update is a dict upsert under a
    # per-instrument lock; disabling turns every handle into a no-op
    # (guarded by test_telemetry.py's overhead test).
    # BLAZE_TPU_TELEMETRY=0/1 force-overrides.
    telemetry_enabled: bool = True

    # Flight recorder: the tracer keeps the last N span events in a ring
    # buffer even when full Chrome tracing (trace_enable) is off, so
    # incident bundles can include the moments before a failure. 0 disables
    # the ring.
    flight_recorder_events: int = 2048

    # Failure forensics (obs/dump.py record_incident): when a query fails /
    # sheds / cancels / misses its deadline, a JSON bundle (plan shape,
    # per-operator metrics, memmgr group state, scheduler snapshot, last
    # ring-buffer spans, exception) is written here and served at
    # GET /debug/incidents[/<id>]. The directory is capped at
    # incident_max_bundles (oldest deleted first); <= 0 disables bundles.
    incident_dir: str = dataclasses.field(
        default_factory=lambda: os.environ.get(
            "BLAZE_TPU_INCIDENT_DIR", "/tmp/blaze_tpu_incidents")
    )
    incident_max_bundles: int = 64

    # Query stats plane (obs/stats.py): per-stage partition sizes, key-skew
    # summaries, estimated-vs-actual cardinalities, residency and recovery
    # events, folded into a QueryProfile on query completion. Profiles are
    # keyed by the canonical plan fingerprint and persisted to
    # profile_store_dir (capped at profile_store_max, oldest-mtime deleted
    # first; <= 0 disables persistence), served at GET /debug/profiles.
    stats_enabled: bool = True

    # Attribution plane (obs/attribution.py): classify tracer spans into the
    # fixed category taxonomy and decompose each query's wall into exclusive
    # per-category time (sum <= wall), plus the critical path. Needs tracer
    # events (full trace or the flight-recorder ring); one attribute check
    # per query when off.
    attribution_enabled: bool = True

    profile_store_dir: str = dataclasses.field(
        default_factory=lambda: os.environ.get(
            "BLAZE_TPU_PROFILE_STORE", "/tmp/blaze_tpu_profiles")
    )
    profile_store_max: int = 128

    # Live health plane (obs/timeline.py): a background sampler thread
    # turns registry counters into windowed per-second rates, gauges into
    # samples and histograms into interval p50/p95/p99 (bucket-snapshot
    # deltas), stored in fixed-size ring buffers next to derived
    # serve/cache/ingest series (ingest lag in versions, refresh backlog,
    # admission queue depth, per-tenant deadline-miss ratio). Served at
    # GET /debug/timeseries?name=&since= and summarized by the SLO/health
    # machinery below at GET /debug/health. The sampler binds to the
    # newest Session and stops when that session closes; disabling leaves
    # a single attribute check per site (test_timeline.py's <5% guard).
    # BLAZE_TPU_TIMELINE=0/1 force-overrides.
    timeline_enabled: bool = True
    timeline_interval_s: float = 1.0
    timeline_ring: int = 512

    # Declarative SLOs over timeline series: ';'-separated
    # "<subsystem>:<series><op><threshold>" with op in {<=,<,==,>=,>} and
    # subsystem in obs/timeline.SUBSYSTEMS (serve/cache/ingest/memmgr/
    # shuffle/workers). Each SLO is checked per sample with
    # Google-SRE-style fast/slow burn-rate windows: a breaching sample
    # spends error budget; burn = breaching fraction / budget ratio.
    # degraded fires on the fast window alone (onset), critical only when
    # BOTH windows burn past slo_critical_burn (sustained — the
    # multiwindow rule that keeps one hiccup from paging). A subsystem's
    # health is the worst state across its SLOs; transitions write
    # incident bundles through obs/dump.py.
    slo_specs: str = ("serve:serve_deadline_miss_ratio<=0.05;"
                      "cache:cache_stale_served_rate==0;"
                      "ingest:ingest_lag_versions<=2;"
                      "shuffle:shuffle_tier_degraded_rate==0;"
                      "workers:worker_deaths_rate==0")
    slo_fast_window_s: float = 10.0
    slo_slow_window_s: float = 60.0
    slo_error_budget_ratio: float = 0.1
    slo_degraded_burn: float = 1.0
    slo_critical_burn: float = 2.0

    # Number of host worker threads for IO/decode and task overlap
    # (reference: tokio worker threads conf). Chosen for a link that is
    # gone; not measured on the chip.
    num_io_threads: int = 4

    # Per-operator enable flags (reference: spark.auron.enable.<op>,
    # AuronConverters.scala:99-140). Checked by the plan converter/session.
    enabled_ops: dict = dataclasses.field(default_factory=dict)

    # Trace upstream FilterExec predicates into the device partial-agg
    # kernel. None = auto: ON when the process's backend is the CPU (the
    # compaction it removes is the CPU hot spot, bench 0.37s -> 0.17s), OFF
    # on accelerator backends — chosen for a link that is gone; not measured
    # on the chip. True/False force it.
    fused_filter_agg: Optional[bool] = None

    # Whole-stage fusion (ir/fusion.py): collapse maximal chains of narrow
    # batch-local operators (project / filter / rename / expand, with
    # coalesce-batches as an in-stage staging point) into one FusedStageExec
    # whose body is a single jitted XLA computation per chain fingerprint —
    # one dispatch per batch instead of one eager dispatch per expression
    # node plus a compaction kernel per filter. False restores the exact
    # unfused operator tree (escape hatch, test-guarded).
    fusion_enabled: bool = True

    # Minimum estimated eager dispatches a chain must save before it is
    # worth the fused closure (the SystemML-style cost cut: a lone
    # column-reference projection saves nothing and stays unfused).
    fusion_min_saved_dispatches: int = 1

    # Dense-bucket grouped aggregation: when a partial agg's group keys are
    # integers whose observed range fits a small table, the kernel reduces
    # into range-sized slot tables instead of capacity-sized ones (the
    # TPU-friendly analogue of the reference's hash table, agg_hash_map.rs
    # — one pass, no sort, no 131k-wide tables for 400 groups).
    # None = on, on every backend: each stream decides from its own probed
    # key range (one extra sync a stream): on the chip the sort kernel took
    # 64-75 ms a batch into 6-10 groups, the slot table 0.03-0.04 ms
    # (PERF.md section 6, PR 25).
    # True is the same; False forces the sort kernel (tests compare the two).
    dense_agg: Optional[bool] = None

    # Upper bound on the dense-agg table (product of per-key rounded ranges)
    # in the forms that hold the table: masked and scatter, linear in the
    # slots. Past it a table is radix where radix_agg is on, else slot-sorted
    # (ONE sort of the packed slot id, flat in the slots) while the id fits
    # 62 bits, else the sort kernel.
    dense_agg_max_buckets: int = 65536

    # Radix-partitioned grouped aggregation: the high-cardinality extension
    # of dense_agg. Packed integer keys are bucketed by their high code bits
    # and deduped/accumulated with one scatter pass into a slot table whose
    # size is the product of the per-key rounded ranges — far past
    # dense_agg_max_buckets, bounded by radix_agg_max_slots. Replaces the
    # O(n log n) sort segmentation for wide key ranges (q67-class ~570k
    # groups) on both the partial and the merge side. None = auto: ON when
    # the process's backend is the CPU (a probe sync a stream; its kernel
    # scatters a row at a time and was not measured on the chip: ROADMAP
    # S4). True/False force it.
    radix_agg: Optional[bool] = None

    # Upper bound on the radix slot-table size (product of per-key rounded
    # ranges). Key spaces beyond this fall back to the sort kernel.
    radix_agg_max_slots: int = 1 << 22

    # Number of radix buckets (power of two). Buckets partition the packed
    # key code by its high bits; the per-bucket (rows, groups) histogram
    # feeds the partial-skipping heuristic and the Perfetto skew view.
    radix_agg_buckets: int = 256

    # Ship dictionary codes + dictionaries through the shuffle instead of
    # decoded values: partial-agg output keeps var-width group keys
    # dictionary-encoded, the serde registers each dictionary once per
    # (writer stream, dict) pair, and the final AggTable's _gid_of_values
    # cache translates each incoming dictionary once instead of
    # re-interning every row. False restores the decode-at-the-boundary
    # path.
    codes_shuffle: bool = True

    # Zero-copy data plane (io/shm_segments.py, runtime/segments.py): same-
    # process exchanges pass ColumnarBatch references through an in-memory
    # segment registry (no serde at all); same-host shuffles commit raw
    # offset-indexed column planes into mmap-able segment files under
    # /dev/shm (spill-dir fallback) that readers map instead of decoding.
    # Cross-network / RSS paths keep the classic IPC serde automatically.
    # False restores the serialize-everything path (escape hatch,
    # test-guarded for bit-identical results).
    zero_copy_shuffle: bool = True

    # Force one tier for tests: None = negotiate from placement
    # (pool-less -> "device" on an accelerator or under a mesh, "process"
    # on the CPU backend; local pool -> "shm"); "device" | "process" |
    # "shm" | "ipc" pin the tier. "process"/"device" with a worker pool degrade to "shm" (batch
    # references cannot cross process boundaries).
    zero_copy_tier: Optional[str] = None

    # Directory for shm-tier segment files. None = /dev/shm when writable
    # with at least shm_min_free_bytes free, else the session work dir
    # (plain disk — mmap still works, just without the tmpfs win).
    shm_dir: Optional[str] = None
    shm_min_free_bytes: int = 256 << 20

    # Budget for process-tier in-memory staged partitions per map task;
    # beyond it (or under memmgr spill pressure) the writer degrades to the
    # shm/raw file path for that map output.
    zero_copy_mem_segment_max_bytes: int = 256 << 20

    # Query serving layer (serve/scheduler.py): concurrency slots, queue
    # bounds, and admission control. A query is admitted only when the
    # MemManager's headroom covers its estimated footprint; a full queue or
    # a queue wait past the timeout sheds the query with a typed Overloaded
    # error (graceful degradation instead of OOM — the role Spark's
    # scheduler + YARN admission play for the reference).
    serve_max_concurrent: int = 4
    serve_max_queue: int = 64
    serve_queue_timeout_s: float = 30.0
    # admission estimate floor when the plan-based estimate has no stateful
    # operators (scans/projections still buffer batches)
    serve_default_mem_estimate: int = 64 << 20
    # Serve-layer auto-retry of transient (QueryRetryable-classified)
    # failures: up to serve_retry_max re-executions with capped exponential
    # backoff + jitter, spent only inside the query's remaining deadline
    # budget. 0 disables and restores fail-to-client behavior.
    serve_retry_max: int = 2
    serve_retry_backoff_s: float = 0.25
    serve_retry_backoff_max_s: float = 2.0

    # Multi-tenant weighted-fair queuing (serve/scheduler.py): tenants are
    # declared as "name:weight[:max_concurrent[:mem_quota_mb]]" entries,
    # ';'-separated (e.g. "dash:4;adhoc:2;bulk:1:1:64"). Unknown tenants
    # fall back to serve_tenant_default_weight with no per-tenant caps.
    # Dispatch order is virtual-time WFQ: each query gets
    # vfinish = max(V, tenant.last_vft) + cost/weight and the smallest
    # vfinish among tenant queue heads is admitted next, so a flooding
    # tenant cannot starve light ones.
    serve_tenants: str = ""
    serve_tenant_default_weight: float = 1.0

    # Stage-boundary preemption: a running preemptible query whose tenant
    # has fallen behind in virtual time (or that a higher-priority arrival
    # is waiting on) is asked to pause at its next stage commit. Pausing
    # releases its memory group and slot but PINS committed shuffle
    # segments behind a stage cursor; resume replays the cursor without
    # recomputing finished stages.
    serve_preempt_enable: bool = True
    # head-of-line wait before the dispatcher considers preempting
    serve_preempt_after_s: float = 0.25
    # a victim must have run at least this long (don't thrash short queries)
    serve_preempt_min_run_s: float = 0.1
    # max pauses per query (bounds pause/resume livelock)
    serve_preempt_max: int = 3
    # chaos knob: preempt whenever anything is waiting, regardless of
    # priority/virtual-time ordering (the `preempt` storm mode)
    serve_preempt_aggressive: bool = False

    # Adaptive admission: when QueryScheduler is built without an explicit
    # max_concurrent, the concurrency cap floats between 1 and
    # serve_adaptive_max_concurrent based on MemManager headroom divided by
    # the (profile-refined) per-query estimate. False restores the fixed
    # serve_max_concurrent cap.
    serve_adaptive_admission: bool = True
    serve_adaptive_max_concurrent: int = 16

    # Full-queue backpressure: instead of a hard Overloaded shed, a full
    # queue raises Backpressure (HTTP 429) carrying a Retry-After computed
    # from the observed drain rate, clamped to this ceiling.
    serve_backpressure_enable: bool = True
    serve_retry_after_max_s: float = 5.0

    # Result/subplan cache (blaze_tpu/cache/): fingerprint-keyed reuse of
    # whole-query results and shuffle-map subplans, LRU + bytes-capped as a
    # MemConsumer so admission control sees cache pressure. cache_enabled
    # False is the escape hatch — every consult/fill site is behind it, so
    # the disabled path stays near-free (test_cache.py's <5% overhead
    # guard). Entries record their ingest-table versions; a stale hit with
    # a mergeable plan (final SUM/COUNT/MIN/MAX agg) recomputes only the
    # appended tail and merges (cache_incremental_enabled), else recomputes
    # in full — a stale entry is NEVER served as-is.
    cache_enabled: bool = True
    cache_max_bytes: int = 256 << 20
    cache_max_entries: int = 256
    # subplan (per-exchange) caching scope: "serve" engages it only for
    # scheduler-submitted queries (mem_group serve_*) so direct Session
    # runs keep their exact seed behavior; "all" engages everywhere;
    # "off" disables subplan capture while whole-plan results still cache
    cache_subplan_scope: str = "serve"
    # degrade ladder on eviction/pressure: memory -> spill-dir arrow IPC
    # persistence -> miss. False drops straight to miss.
    cache_spill_enabled: bool = True
    cache_incremental_enabled: bool = True

    # Stage placement (runtime/placement.py): "auto" and "device" run every
    # stage on the process's JAX backend (the chip when there is one);
    # "host" pins stages to the CPU backend, where they run the same jitted
    # kernels.
    device_placement: str = "auto"

    # Capacity bucketing: device buffers are padded up to the next bucket to
    # bound XLA recompilation. Buckets are powers of two >= min_capacity.
    min_capacity: int = 256

    def capacity_for(self, n: int) -> int:
        cap = self.min_capacity
        while cap < n:
            cap <<= 1
        return cap

    def is_op_enabled(self, op: str) -> bool:
        return self.enabled_ops.get(op, True)


_GLOBAL = Config()


def get_config() -> Config:
    return _GLOBAL


def set_config(cfg: Config):
    global _GLOBAL
    _GLOBAL = cfg


@contextlib.contextmanager
def config_override(**kwargs):
    global _GLOBAL
    old = _GLOBAL
    _GLOBAL = dataclasses.replace(old, **kwargs)
    try:
        yield _GLOBAL
    finally:
        _GLOBAL = old
