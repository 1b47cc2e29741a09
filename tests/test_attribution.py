"""The why-is-it-slow plane (ISSUE 17): exclusive wall-time attribution,
critical-path extraction, and the fusion/placement decision audit.

Covers the acceptance surface: the priority interval sweep's exclusivity
invariant ``sum(categories) <= wall`` (unit + real queries + all five
bench shapes over a real 2-worker pool), worker-span merge onto the
driver timeline, critical-path structural stability on a fixed plan,
fusion-break-reason goldens (pyudf / cost_below_min_saved / blocking_op
and the ``fused_op_fraction`` tripwire), the disabled-path overhead
guard, humanized duration rendering above one hour, and Chrome-trace
cname/flow export."""

import os
import time

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from blaze_tpu.config import Config, config_override
from blaze_tpu.ir import exprs as E
from blaze_tpu.ir import nodes as N
from blaze_tpu.ir import types as T
from blaze_tpu.ir.fusion import fuse_plan
from blaze_tpu.obs.attribution import (CATEGORIES, CATEGORY_CNAME,
                                       CATEGORY_FIELDS, audit_snapshot,
                                       classify_span, critical_path,
                                       decision_audit, exclusive_times,
                                       query_attribution)
from blaze_tpu.obs.explain import fmt_ns
from blaze_tpu.obs.tracer import TRACER
from blaze_tpu.runtime.session import Session

F = E.AggFunction
M = E.AggMode
HASH = E.AggExecMode.HASH_AGG


def col(n):
    return E.Column(n)


def lit(v, t):
    return E.Literal(v, t)


def _conf():
    from blaze_tpu.config import get_config

    return get_config()


def _pq_agg_plan(tmp_path, fname="t.parquet", rows=10_000, keys=7):
    """Parquet-backed two-stage agg (pool-shippable: no resource lambdas)."""
    from blaze_tpu.ops.parquet import scan_node_for_files

    path = str(tmp_path / fname)
    pq.write_table(pa.table({"k": [i % keys for i in range(rows)],
                             "v": list(range(rows))}), path)
    scan = scan_node_for_files([path], num_partitions=2)
    partial = N.Agg(scan, HASH, [("k", col("k"))],
                    [N.AggColumn(E.AggExpr(F.SUM, [col("v")], T.I64),
                                 M.PARTIAL, "s")])
    ex = N.ShuffleExchange(partial, N.HashPartitioning([col("k")], 3))
    return N.Agg(ex, HASH, [("k", col("k"))],
                 [N.AggColumn(E.AggExpr(F.SUM, [col("v")], T.I64),
                              M.FINAL, "s")])


def _cat_sum(attr):
    return sum(attr[f] for f in CATEGORY_FIELDS)


# -- classification + the exclusivity sweep (units) ----------------------------


@pytest.mark.quick
def test_classify_span_taxonomy():
    assert classify_span("jit_compile:agg", "kernel") == "jit_compile"
    assert classify_span("agg_sum", "kernel") == "kernel_compute"
    assert classify_span("mesh_exchange", "collective") == "collective"
    assert classify_span("to_host", "transfer") == "transfer"
    assert classify_span("spill", "spill") == "spill"
    assert classify_span("shuffle_write", "shuffle") == "shuffle_write"
    assert classify_span("shuffle_fetch", "shuffle") == "shuffle_fetch"
    assert classify_span("queue_wait", "queue") == "queue_wait"
    assert classify_span("AggExec", "operator") == "framework"
    assert classify_span("task", "task") == "framework"
    # container/meta spans must never claim exclusive time
    assert classify_span("stage_0", "stage") is None
    assert classify_span("query_1", "query") is None


def _X(name, cat, ts, dur, **args):
    return {"ph": "X", "name": name, "cat": cat, "ts": ts, "dur": dur,
            "pid": 1, "tid": 1, "args": args}


@pytest.mark.quick
def test_exclusive_sweep_priority_and_invariant():
    """A kernel inside a task counts once as kernel time; jit outranks
    kernel where they overlap; container spans claim nothing; the values
    tile the window exactly (sum == covered time <= window)."""
    events = [
        _X("stage_0", "stage", 0.0, 100_000.0),        # container: no claim
        _X("task", "task", 0.0, 100_000.0),            # framework remainder
        _X("agg_sum", "kernel", 10_000.0, 20_000.0),   # [10ms, 30ms)
        _X("jit_compile:agg", "kernel", 20_000.0, 20_000.0),  # [20ms, 40ms)
        _X("to_host", "transfer", 50_000.0, 10_000.0),
    ]
    out = exclusive_times(events, 0.0, 100_000.0)
    assert out["jit_compile"] == pytest.approx(20_000.0)
    # [20, 30)ms lost to the higher-priority compile span
    assert out["kernel_compute"] == pytest.approx(10_000.0)
    assert out["transfer"] == pytest.approx(10_000.0)
    assert out["framework"] == pytest.approx(60_000.0)
    assert sum(out.values()) == pytest.approx(100_000.0)
    # clipped window: spans straddling the edges never overflow it
    clipped = exclusive_times(events, 15_000.0, 35_000.0)
    assert sum(clipped.values()) <= 20_000.0 + 1e-6


@pytest.mark.quick
def test_exclusive_sweep_empty_and_unclassified():
    assert sum(exclusive_times([], 0.0, 1000.0).values()) == 0.0
    only_meta = [_X("query_1", "query", 0.0, 1000.0)]
    assert sum(exclusive_times(only_meta, 0.0, 1000.0).values()) == 0.0


# -- per-query attribution on real queries -------------------------------------


@pytest.mark.quick
def test_query_attribution_invariant_in_process(tmp_path):
    with config_override(trace_enable=True,
                         profile_store_dir=str(tmp_path / "p")):
        with Session() as sess:
            out = sess.execute_to_pydict(_pq_agg_plan(tmp_path))
            profile = sess.profile()
    assert len(out["k"]) == 7
    attr = profile["attribution"]
    assert attr["wall_ns"] > 0
    assert _cat_sum(attr) == attr["attributed_ns"] <= attr["wall_ns"]
    assert 0.0 < attr["coverage_fraction"] <= 1.0
    # a real two-stage query spends SOME classified time
    assert attr["attributed_ns"] > 0
    # the critical path reaches the profile with a stage segment
    cp = profile["critical_path"]
    assert any(seg["kind"] == "stage" for seg in cp)
    # and the decision audit is attached with the coverage tripwire
    audit = profile["decision_audit"]
    assert "fused_op_fraction" in audit
    assert audit["placement_decisions"]


@pytest.mark.quick
def test_critical_path_stable_on_fixed_plan(tmp_path):
    """Segment structure (kinds, names, stage ids) is a golden for a fixed
    plan — only the times move between runs."""
    def run():
        with config_override(trace_enable=True):
            with Session() as sess:
                sess.execute_to_pydict(_pq_agg_plan(tmp_path))
                return sess.profile()["critical_path"]

    def shape(cp):
        return [(seg["kind"], seg["name"], seg.get("stage"))
                for seg in cp if seg["kind"] != "driver"]

    cp1, cp2 = run(), run()
    assert shape(cp1) == shape(cp2)
    stage_segs = [seg for seg in cp1 if seg["kind"] == "stage"]
    assert stage_segs
    # the binding task and its operators are attributed
    assert all(seg.get("task_ms", 0) >= 0 for seg in stage_segs)
    assert any(seg.get("operators") for seg in cp1)


@pytest.mark.quick
def test_explain_analyze_renders_attribution(tmp_path):
    with config_override(trace_enable=True):
        with Session() as sess:
            text = sess.explain_analyze(_pq_agg_plan(tmp_path))
    assert "Wall-time attribution (exclusive)" in text
    assert "coverage" in text
    assert "Critical path" in text


# -- chrome trace export: stable colors + shuffle flow links -------------------


@pytest.mark.quick
def test_chrome_trace_cnames_and_shuffle_flows(tmp_path):
    with config_override(trace_enable=True):
        with Session() as sess:
            sess.execute_to_pydict(_pq_agg_plan(tmp_path))
            trace = TRACER.to_chrome_trace()
    evs = trace["traceEvents"]
    named = [e for e in evs if e.get("ph") == "X" and e.get("cname")]
    assert named, "classified spans must carry a stable cname"
    assert all(e["cname"] in CATEGORY_CNAME.values() for e in named)
    # same category -> same color, every time
    for e in named:
        cat = classify_span(e.get("name", ""), e.get("cat", ""))
        assert e["cname"] == CATEGORY_CNAME[cat]
    flows_s = [e for e in evs if e.get("ph") == "s"]
    flows_f = [e for e in evs if e.get("ph") == "f"]
    assert flows_s and flows_f, "shuffle write->fetch flow links missing"
    assert {e["id"] for e in flows_s} & {e["id"] for e in flows_f}


# -- humanized durations above one hour (satellite fix) ------------------------


@pytest.mark.quick
def test_fmt_ns_hours_and_minutes():
    assert fmt_ns(90 * 60 * 1_000_000_000) == "1h30m"
    assert fmt_ns(3600 * 1_000_000_000) == "1h00m"
    assert fmt_ns(25 * 3600 * 1_000_000_000) == "25h00m"
    assert fmt_ns(90 * 1_000_000_000) == "1m30s"
    assert fmt_ns(59 * 1_000_000_000).endswith("s")  # below the minute tier
    assert "h" not in fmt_ns(59 * 60 * 1_000_000_000)


# -- decision-audit goldens ----------------------------------------------------


def _chain_plan(path):
    """project -> filter -> project -> filter: the canonical fusable chain."""
    from blaze_tpu.ops.parquet import scan_node_for_files

    scan = scan_node_for_files([path], num_partitions=2)
    return N.Projection(
        N.Filter(
            N.Projection(
                N.Filter(scan, [E.BinaryExpr(E.BinaryOp.GT, col("a"),
                                             lit(10, T.I64))]),
                [col("a"),
                 E.BinaryExpr(E.BinaryOp.MUL, col("b"), lit(2.0, T.F64)),
                 col("c")],
                ["a", "b2", "c"]),
            [E.BinaryExpr(E.BinaryOp.LT, col("c"), lit(7, T.I64))]),
        [E.BinaryExpr(E.BinaryOp.ADD, col("a"), col("c")), col("b2")],
        ["ac", "b2"])


@pytest.fixture()
def fusion_table(tmp_path):
    rng = np.random.default_rng(11)
    n = 2000
    p = str(tmp_path / "t.parquet")
    pq.write_table(pa.table({
        "a": pa.array(rng.integers(0, 100, n), type=pa.int64()),
        "b": pa.array(rng.standard_normal(n), type=pa.float64()),
        "c": pa.array(rng.integers(0, 10, n), type=pa.int64()),
    }), p)
    return p


@pytest.mark.quick
def test_fusion_audit_fused_chain(fusion_table):
    before = audit_snapshot()
    fused = fuse_plan(_chain_plan(fusion_table), _conf())
    assert isinstance(fused, N.FusedStage)
    audit = decision_audit(before)
    assert audit["ops_fused"] >= 4 and audit["ops_eligible"] >= 4
    assert audit["fused_op_fraction"] > 0.0
    # the chain still ended somewhere structural (the scan below it)
    assert audit["fusion_break_reasons"].get("blocking_op", 0) >= 1


@pytest.mark.quick
def test_fusion_audit_pyudf_break(fusion_table):
    from blaze_tpu.ops.parquet import scan_node_for_files

    scan = scan_node_for_files([fusion_table], num_partitions=2)
    udf = E.PyUDF(
        lambda a: pa.array([v * 2 for v in a.to_pylist()], type=pa.int64()),
        [col("a")], T.I64, "dbl")
    plan = N.Filter(
        N.Projection(
            N.Filter(scan, [E.BinaryExpr(E.BinaryOp.GT, col("a"),
                                         lit(20, T.I64))]),
            [udf, col("c")], ["a2", "c"]),
        [E.BinaryExpr(E.BinaryOp.LT, col("c"), lit(5, T.I64))])
    before = audit_snapshot()
    fuse_plan(plan, _conf())
    audit = decision_audit(before)
    assert audit["fusion_break_reasons"].get("pyudf", 0) >= 1


@pytest.mark.quick
def test_fusion_audit_cost_cut(fusion_table):
    # a lone column-reference projection saves no dispatches: the pass
    # declines on cost and the audit says so (fraction 0.0, not None)
    from blaze_tpu.ops.parquet import scan_node_for_files

    scan = scan_node_for_files([fusion_table])
    plan = N.Projection(scan, [col("a")], ["a"])
    before = audit_snapshot()
    assert fuse_plan(plan, _conf()) is plan
    audit = decision_audit(before)
    assert audit["fusion_break_reasons"].get("cost_below_min_saved", 0) >= 1
    assert audit["ops_fused"] == 0 and audit["ops_eligible"] >= 1
    assert audit["fused_op_fraction"] == 0.0


@pytest.mark.quick
def test_placement_audit_forced_host(tmp_path):
    before = audit_snapshot()
    with config_override(device_placement="host"):
        with Session() as sess:
            b = pa.table({"k": [1, 2, 3], "v": [1, 2, 3]})
            p = str(tmp_path / "s.parquet")
            pq.write_table(b, p)
            from blaze_tpu.ops.parquet import scan_node_for_files
            sess.execute_to_pydict(N.Agg(
                scan_node_for_files([p]), HASH, [("k", col("k"))],
                [N.AggColumn(E.AggExpr(F.SUM, [col("v")], T.I64),
                             M.COMPLETE, "s")]))
    audit = decision_audit(before)
    assert audit["placement_decisions"].get("host", 0) >= 1
    assert audit["placement_decline_reasons"].get("conf_forced_host", 0) >= 1


# -- disabled-path overhead guard ----------------------------------------------


@pytest.mark.quick
def test_attribution_disabled_overhead_under_5_percent(tmp_path):
    """With attribution off the only per-span cost on the hot path is the
    ``TRACER.active`` check; scaled by a generous span count it stays
    under 5% of a real query's wall."""
    plan = _pq_agg_plan(tmp_path, rows=200_000, keys=97)
    with Session(conf=Config(attribution_enabled=False)) as sess:
        t0 = time.perf_counter_ns()
        out = sess.execute_to_pydict(plan)
        wall_ns = time.perf_counter_ns() - t0
        assert len(out["k"]) == 97
        prof = sess.profile()
        assert prof is None or "attribution" not in prof

    ITER = 100_000
    t0 = time.perf_counter_ns()
    for _ in range(ITER):
        TRACER.active  # noqa: B018  — the guard under measurement
    per_check_ns = (time.perf_counter_ns() - t0) / ITER
    overhead_ns = per_check_ns * 10_000  # far more spans than any query emits
    assert overhead_ns < 0.05 * wall_ns, (
        f"disabled attribution {overhead_ns / 1e6:.2f}ms vs query "
        f"{wall_ns / 1e6:.1f}ms: disabled-path overhead exceeds 5%")
    assert per_check_ns < 2_000, f"active check {per_check_ns:.0f}ns"


# -- the five bench shapes over a real 2-worker pool (slow) --------------------


@pytest.fixture(scope="module")
def bench_paths(tmp_path_factory):
    import bench

    bench.ROWS = 60_000
    bench.PARTS = 2
    td = str(tmp_path_factory.mktemp("attrbench"))
    return bench.make_data(td)


@pytest.mark.slow
@pytest.mark.parametrize("shape", ["q01", "q06", "q17", "q47", "q67"])
def test_pool_bench_shapes_exclusivity(bench_paths, shape, tmp_path):
    """The acceptance invariant on every bench shape, workers included:
    worker spans absorbed onto the driver timeline, and
    sum(categories) <= wall exactly."""
    import bench

    plan_fn = {s[0]: s[1] for s in bench.SHAPES}[shape]
    with config_override(trace_enable=True,
                         profile_store_dir=str(tmp_path / "p")):
        with Session(num_worker_processes=2) as sess:
            sess.execute_to_pydict(plan_fn(bench_paths))
            profile = sess.profile()
            events = TRACER.snapshot()
    attr = profile["attribution"]
    assert _cat_sum(attr) == attr["attributed_ns"] <= attr["wall_ns"]
    assert attr["attributed_ns"] > 0
    assert 0.0 < attr["coverage_fraction"] <= 1.0
    # worker-side task spans were absorbed onto the driver timeline
    driver_pid = os.getpid()
    worker_spans = [e for e in events if e.get("ph") == "X"
                    and e.get("pid") not in (None, driver_pid)]
    assert worker_spans, "no worker spans absorbed into the driver trace"
    assert any(e.get("cat") == "task" for e in worker_spans)
    # and the critical path binds each stage to a task
    assert any(seg["kind"] == "stage" and seg.get("task") is not None
               for seg in profile["critical_path"])


@pytest.mark.slow
def test_pool_worker_span_merge_attributes_shuffle(tmp_path):
    """Worker shuffle writes land in the exclusive decomposition: the
    spans ride reply merge (Tracer.absorb) and classify as
    shuffle_write."""
    plan = _pq_agg_plan(tmp_path, rows=50_000, keys=101)
    with config_override(trace_enable=True):
        with Session(num_worker_processes=2) as sess:
            sess.execute_to_pydict(plan)
            events = TRACER.snapshot()
            profile = sess.profile()
    writes = [e for e in events if e.get("name") == "shuffle_write"]
    assert writes, "worker shuffle_write spans missing from driver trace"
    assert all((e.get("args") or {}).get("stage") is not None
               for e in writes)
    attr = profile["attribution"]
    assert _cat_sum(attr) <= attr["wall_ns"]
