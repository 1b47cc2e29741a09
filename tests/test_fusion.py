"""Whole-stage fusion: pass rewrites, golden equality vs the unfused
engine on every bench shape, static + runtime fallbacks, jit-closure reuse
across queries, the escape hatch, and the fused-dispatch-count guard.

The contract under test: with ``fusion_enabled`` on, chains of
project/filter/rename/expand between exchanges execute as ONE jitted
dispatch per batch with results bit-identical to the eager operators; with
it off, the built operator tree is exactly the pre-fusion one."""

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from blaze_tpu.config import config_override
from blaze_tpu.ir import exprs as E
from blaze_tpu.ir import nodes as N
from blaze_tpu.ir import types as T
from blaze_tpu.ir.fusion import fuse_plan
from blaze_tpu.ops.fused import FusedStageExec, clear_fused_cache
from blaze_tpu.runtime.metrics import tripwire_totals
from blaze_tpu.runtime.session import Session
from tests.util import collect_pydict, mem_scan, run_op


def col(n):
    return E.Column(n)


def lit(v, t):
    return E.Literal(v, t)


def _conf():
    from blaze_tpu.config import get_config

    return get_config()


@pytest.fixture(scope="module")
def table_path(tmp_path_factory):
    d = tmp_path_factory.mktemp("fusion")
    rng = np.random.default_rng(11)
    n = 6000
    p = str(d / "t.parquet")
    pq.write_table(pa.table({
        "a": pa.array(rng.integers(0, 100, n), type=pa.int64()),
        "b": pa.array(rng.standard_normal(n), type=pa.float64()),
        "c": pa.array(rng.integers(0, 10, n), type=pa.int64()),
        "d": pa.array(rng.integers(0, 1000, n), type=pa.int64()),
    }), p, row_group_size=1024)
    return p


def _chain_plan(path):
    """project -> filter -> project -> filter over a parquet scan: the
    canonical fusable chain."""
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


def _op_names(op):
    names = [type(op).__name__]
    for c in op.children:
        names.extend(_op_names(c))
    return names


# -- the pass -----------------------------------------------------------------


def test_pass_rewrites_maximal_chain(table_path):
    plan = _chain_plan(table_path)
    fused = fuse_plan(plan, _conf())
    assert isinstance(fused, N.FusedStage)
    assert [type(o).__name__ for o in fused.ops] == \
        ["Filter", "Projection", "Filter", "Projection"]  # innermost-first
    assert not isinstance(fused.child, N.FusedStage)
    # idempotent: re-running over a fused tree is a no-op
    assert fuse_plan(fused, _conf()) is fused


def test_pass_skips_trivial_chain(table_path):
    # a lone column-reference projection saves no dispatches: stays unfused
    from blaze_tpu.ops.parquet import scan_node_for_files

    scan = scan_node_for_files([table_path])
    plan = N.Projection(scan, [col("a")], ["a"])
    assert fuse_plan(plan, _conf()) is plan


def test_pass_leaves_aggs_filter_alone(table_path):
    # a filter directly under Agg feeds the fused_filter_agg device kernel;
    # the chain must start BELOW it
    from blaze_tpu.ops.parquet import scan_node_for_files

    scan = scan_node_for_files([table_path])
    proj = N.Projection(
        scan,
        [col("a"),
         E.BinaryExpr(E.BinaryOp.MUL, col("d"), lit(3, T.I64)),
         E.BinaryExpr(E.BinaryOp.ADD, col("c"), lit(1, T.I64))],
        ["a", "d3", "c1"])
    filt = N.Filter(proj, [E.BinaryExpr(E.BinaryOp.GT, col("d3"),
                                        lit(100, T.I64))])
    agg = N.Agg(filt, E.AggExecMode.HASH_AGG, [("a", col("a"))],
                [N.AggColumn(E.AggExpr(E.AggFunction.SUM, [col("d3")], T.I64),
                             E.AggMode.PARTIAL, "s")])
    fused = fuse_plan(agg, _conf())
    assert isinstance(fused, N.Agg)
    assert isinstance(fused.child, N.Filter), \
        "agg's filter must stay a direct child (fused_filter_agg guard)"
    assert isinstance(fused.child.child, N.FusedStage)


def test_escape_hatch_restores_unfused_tree(table_path):
    from blaze_tpu.runtime.executor import build_operator

    plan = _chain_plan(table_path)
    with config_override(fusion_enabled=False):
        assert fuse_plan(plan, _conf()) is plan
        names = _op_names(build_operator(plan))
        assert "FusedStageExec" not in names
        assert names.count("ProjectExec") == 2
        assert names.count("FilterExec") == 2
    names_on = _op_names(build_operator(plan))
    assert "FusedStageExec" in names_on
    assert "ProjectExec" not in names_on


# -- golden equality ----------------------------------------------------------


def test_chain_golden_equality(table_path):
    plan = _chain_plan(table_path)
    with config_override(fusion_enabled=False):
        off = Session().execute_to_table(plan)
    sess = Session()
    on = sess.execute_to_table(plan)
    assert on.num_rows > 0
    assert on.equals(off)
    trips = tripwire_totals(sess.metrics)
    assert trips["fused_stages"] > 0
    assert trips["fused_fallback_batches"] == 0


def test_expand_rename_chain_golden(table_path):
    # expand (grouping-sets shape) + rename inside one fused stage
    from blaze_tpu.ops.parquet import scan_node_for_files

    scan = scan_node_for_files([table_path], num_partitions=2)
    schema = T.Schema.of(("a", T.I64), ("v", T.I64), ("tag", T.I64))
    plan = N.RenameColumns(
        N.Filter(
            N.Expand(
                N.Filter(scan, [E.BinaryExpr(E.BinaryOp.LT, col("c"),
                                             lit(8, T.I64))]),
                [[col("a"), col("d"), lit(0, T.I64)],
                 [col("a"),
                  E.BinaryExpr(E.BinaryOp.MUL, col("d"), lit(10, T.I64)),
                  lit(1, T.I64)]],
                schema),
            [E.BinaryExpr(E.BinaryOp.GT, col("v"), lit(50, T.I64))]),
        ["g_a", "g_v", "g_tag"])
    fused = fuse_plan(plan, _conf())
    assert isinstance(fused, N.FusedStage)
    with config_override(fusion_enabled=False):
        off = Session().execute_to_table(plan)
    on = Session().execute_to_table(plan)
    assert on.num_rows > 0
    assert on.equals(off)


@pytest.fixture(scope="module")
def bench_paths(tmp_path_factory):
    """The real bench shapes at reduced scale (same generators/seeds)."""
    import bench

    old = bench.ROWS
    bench.ROWS = 40_000
    try:
        yield bench.make_data(str(tmp_path_factory.mktemp("fusion_bench")))
    finally:
        bench.ROWS = old


@pytest.mark.parametrize("shape", ["q01", "q06", "q17", "q47", "q67"])
def test_bench_shape_golden_equality(bench_paths, shape):
    """Every BENCH shape must be bit-identical with fusion on vs off."""
    import bench

    plan_fn = {name: fn for name, fn, *_ in bench.SHAPES}[shape]
    with config_override(fusion_enabled=False):
        off = Session().execute_to_table(plan_fn(bench_paths))
    on = Session().execute_to_table(plan_fn(bench_paths))
    assert on.num_rows == off.num_rows
    assert on.equals(off), f"{shape}: fused result differs from unfused"


# -- fallbacks ----------------------------------------------------------------


def test_unfusable_expr_breaks_chain(table_path):
    # a PyUDF mid-chain must NOT be swallowed: the chain splits around it
    # and results still match the unfused engine
    from blaze_tpu.ops.parquet import scan_node_for_files

    scan = scan_node_for_files([table_path], num_partitions=2)
    udf = E.PyUDF(
        lambda a: pa.array([v * 2 for v in a.to_pylist()], type=pa.int64()),
        [col("a")], T.I64, "dbl")
    plan = N.Filter(
        N.Projection(
            N.Filter(scan, [E.BinaryExpr(E.BinaryOp.GT, col("a"),
                                         lit(20, T.I64))]),
            [udf, col("c")], ["a2", "c"]),
        [E.BinaryExpr(E.BinaryOp.LT, col("c"), lit(5, T.I64))])
    fused = fuse_plan(plan, _conf())

    def has_udf_in_fused(node):
        if isinstance(node, N.FusedStage):
            for op in node.ops:
                if isinstance(op, N.Projection) and any(
                        isinstance(e, E.PyUDF) for e in op.exprs):
                    return True
        return any(has_udf_in_fused(c) for c in node.children())

    assert not has_udf_in_fused(fused)
    with config_override(fusion_enabled=False):
        off = Session().execute_to_table(plan)
    on = Session().execute_to_table(plan)
    assert on.equals(off)


def test_runtime_fallback_on_host_columns():
    # device-typed column that arrives dictionary-encoded (HostColumn at
    # runtime): the static gate can't see it, the per-batch fallback must
    schema = T.Schema.of(("k", T.I64), ("v", T.I64))
    from blaze_tpu.core.batch import ColumnarBatch, HostColumn

    ref = ColumnarBatch.from_pydict({
        "k": pa.array([1, 2, 2, 3, 3, 3, 4, 4], type=pa.int64()),
        "v": pa.array([10, 20, 21, 30, 31, 32, 40, 41], type=pa.int64()),
    }, schema)
    # force the k plane host-resident (the shape a dictionary-encoded device
    # dtype lands in): the static gate saw a device schema, only the
    # operator's per-batch check can catch this
    batch = ColumnarBatch(schema, [
        HostColumn(T.I64, pa.array([1, 2, 2, 3, 3, 3, 4, 4],
                                   type=pa.int64())),
        ref.columns[1],
    ], ref.num_rows)
    scan = mem_scan([[batch]], schema=schema)

    leaf = N.BatchSource(schema, "unused", 1)  # schema carrier for the ops
    filt = N.Filter(leaf, [E.BinaryExpr(E.BinaryOp.GT, col("k"),
                                        lit(1, T.I64))])
    proj = N.Projection(filt, [E.BinaryExpr(E.BinaryOp.ADD, col("k"),
                                            col("v"))], ["kv"])
    node = N.FusedStage(child=leaf, ops=(filt, proj))
    op = FusedStageExec(scan, node)
    out = collect_pydict(op)
    assert out == {"kv": [22, 23, 33, 34, 35, 44, 45]}

    from blaze_tpu.ops.base import ExecContext

    ctx = ExecContext()
    list(op.execute(0, ctx))
    assert ctx.metrics.total("fused_fallback_batches") > 0


def test_in_list_with_null_item_traces_in_fused_closure():
    """An IN list traces inside a fused closure, NULL item included (a
    literal's validity is a tracer there): no eager batches, and the answer
    is the unfused evaluator's — NULL, not false, for a non-match."""
    from blaze_tpu.core.batch import ColumnarBatch

    schema = T.Schema.of(("k", T.I64), ("v", T.I64))
    batch = ColumnarBatch.from_pydict({
        "k": pa.array([1, 2, 3, None, 5], type=pa.int64()),
        "v": pa.array([10, 20, 30, 40, 50], type=pa.int64()),
    }, schema)
    leaf = N.BatchSource(schema, "unused", 1)
    in_list = E.InList(col("k"), [lit(1, T.I64), lit(None, T.I64),
                                  lit(5, T.I64)])
    proj = N.Projection(leaf, [in_list, E.BinaryExpr(
        E.BinaryOp.ADD, col("k"), col("v"))], ["hit", "kv"])
    filt = N.Filter(proj, [E.BinaryExpr(E.BinaryOp.GT, col("kv"),
                                        lit(0, T.I64))])
    clear_fused_cache()
    op = FusedStageExec(mem_scan([[batch]], schema=schema),
                        N.FusedStage(child=leaf, ops=(proj, filt)))
    from blaze_tpu.ops.base import ExecContext
    from blaze_tpu.ops.basic import ProjectExec

    ctx = ExecContext()
    got = pa.Table.from_batches(
        [b.to_arrow() for b in op.execute(0, ctx)]).to_pydict()
    assert ctx.metrics.total("fused_fallback_batches") == 0
    assert ctx.metrics.total("jit_cache_misses") == 1
    assert got == {"hit": [True, None, None, True], "kv": [11, 22, 33, 55]}
    want = collect_pydict(ProjectExec(
        mem_scan([[batch]], schema=schema), [in_list], ["hit"]))
    assert want == {"hit": [True, None, None, None, True]}


def test_jit_closure_reuse_across_queries(table_path):
    clear_fused_cache()
    plan = _chain_plan(table_path)
    s1 = Session()
    t1 = s1.execute_to_table(plan)
    trips1 = tripwire_totals(s1.metrics)
    assert trips1["jit_cache_misses"] >= 1  # first query compiles
    s2 = Session()
    t2 = s2.execute_to_table(plan)
    trips2 = tripwire_totals(s2.metrics)
    assert trips2["jit_cache_misses"] == 0, \
        "second query with the same plan fingerprint recompiled"
    assert trips2["jit_cache_hits"] >= 1
    assert t1.equals(t2)


# -- dispatch-count guard (quick tier) ----------------------------------------


@pytest.mark.quick
def test_fused_dispatch_count_guard(table_path):
    """A filter-heavy pipeline must cost <= 1/3 the counted kernel
    dispatches of the unfused engine (one fused dispatch per batch vs one
    compaction per filter per batch)."""
    from blaze_tpu.ops.parquet import scan_node_for_files
    from blaze_tpu.utils.device import DEVICE_STATS

    scan = scan_node_for_files([table_path], num_partitions=2)
    plan = N.Filter(
        N.Filter(
            N.Filter(
                N.Projection(
                    N.Filter(scan, [E.BinaryExpr(E.BinaryOp.GT, col("a"),
                                                 lit(5, T.I64))]),
                    [col("a"), col("c"), col("d")], ["a", "c", "d"]),
                [E.BinaryExpr(E.BinaryOp.LT, col("c"), lit(9, T.I64))]),
            [E.BinaryExpr(E.BinaryOp.LT, col("d"), lit(900, T.I64))]),
        [E.BinaryExpr(E.BinaryOp.GT, col("d"), lit(50, T.I64))])

    def run(fusion):
        with config_override(fusion_enabled=fusion):
            Session().execute_to_table(plan)  # warmup compiles
            DEVICE_STATS.reset()
            out = Session().execute_to_table(plan)
            return out, DEVICE_STATS.snapshot()["kernel_calls"]

    out_off, unfused_calls = run(False)
    out_on, fused_calls = run(True)
    assert out_on.equals(out_off)
    assert unfused_calls >= 4
    assert fused_calls <= unfused_calls / 3, \
        (fused_calls, unfused_calls)


# -- observability ------------------------------------------------------------


def test_explain_renders_fusion_boundary(table_path):
    plan = _chain_plan(table_path)
    sess = Session()
    text = sess.explain_analyze(plan)
    assert "FusedStageExec" in text
    assert "+ ProjectExec (fused)" in text
    assert "+ FilterExec (fused)" in text
    # absorbed ops carry no self-time of their own
    for line in text.splitlines():
        if "(fused)" in line:
            assert "elapsed_compute" not in line
    # the /debug/queries record embeds the same boundary, compactly
    from blaze_tpu.runtime.http import _query_record

    rec = _query_record(sess.query_log[-1])
    assert any("+ FilterExec (fused)" in ln for ln in rec["plan"])
    assert "shape" not in rec
