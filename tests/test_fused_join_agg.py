"""Join->agg fusion: a unique-single-key inner BroadcastJoin under a
partial hash agg traces INTO the agg kernel (ops/agg_device.FusedJoinSpec)
— the TPC-DS star-join shape. These tests pin: engagement (metric), oracle
equality with/without an interposed filter, null probe keys, and the two
fallbacks (duplicate build keys, non-device columns)."""

import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from blaze_tpu.config import config_override
from blaze_tpu.ir import exprs as E
from blaze_tpu.ir import nodes as N
from blaze_tpu.ir import types as T
from blaze_tpu.runtime.session import Session

F = E.AggFunction


@pytest.fixture(autouse=True)
def _fused():
    """The fuser is opt-in (``fused_filter_agg``): these tests ask for it."""
    with config_override(fused_filter_agg=True):
        yield


def _write(tmp_path, name, table):
    p = str(tmp_path / f"{name}.parquet")
    pq.write_table(table, p)
    return [p]


def _fact(rng, n, null_every=0):
    fk = rng.integers(1, 50, n).astype(object)
    if null_every:
        for i in range(0, n, null_every):
            fk[i] = None
    return pa.table({
        "fk": pa.array(list(fk), type=pa.int64()),
        "v": pa.array(rng.integers(-100, 100, n), type=pa.int64()),
    })


def _dim(rng, dup=False):
    pks = list(range(1, 60))
    if dup:
        pks += [7, 7]
    return pa.table({
        "pk": pa.array(pks, type=pa.int64()),
        "attr": pa.array(rng.integers(0, 5, len(pks)), type=pa.int64()),
    })


def _plan(fact_paths, dim_paths, predicates=None, tag="fja_dim"):
    from blaze_tpu.ops.parquet import scan_node_for_files

    fact = scan_node_for_files(fact_paths, num_partitions=2)
    dim = scan_node_for_files(dim_paths)
    join = N.BroadcastJoin(fact, N.BroadcastExchange(dim),
                           [(E.Column("fk"), E.Column("pk"))],
                           N.JoinType.INNER, N.JoinSide.RIGHT, tag)
    src = N.Filter(join, predicates) if predicates else join
    partial = N.Agg(src, E.AggExecMode.HASH_AGG,
                    [("attr", E.Column("attr"))], [
        N.AggColumn(E.AggExpr(F.SUM, [E.Column("v")]),
                    E.AggMode.PARTIAL, "s"),
        N.AggColumn(E.AggExpr(F.COUNT, []), E.AggMode.PARTIAL, "c"),
    ])
    ex = N.ShuffleExchange(partial, N.HashPartitioning([E.Column("attr")], 2))
    final = N.Agg(ex, E.AggExecMode.HASH_AGG,
                  [("attr", E.Column("attr"))], [
        N.AggColumn(E.AggExpr(F.SUM, [E.Column("v")]), E.AggMode.FINAL, "s"),
        N.AggColumn(E.AggExpr(F.COUNT, []), E.AggMode.FINAL, "c"),
    ])
    return N.Sort(N.ShuffleExchange(final, N.SinglePartitioning(1)),
                  [E.SortOrder(E.Column("attr"))])


def _oracle(fact, dim, pred=None):
    m = fact.to_pandas().merge(dim.to_pandas(), left_on="fk", right_on="pk")
    if pred is not None:
        m = m[pred(m)]
    g = m.groupby("attr").agg(s=("v", "sum"), c=("v", "size")).reset_index()
    return g.sort_values("attr").reset_index(drop=True)


def _run(plan):
    with Session() as sess:
        out = sess.execute_to_table(plan)
        fused = sess.metrics.total("fused_join_stages")
    return out.to_pandas(), fused


def test_fused_join_agg_matches_oracle(tmp_path):
    rng = np.random.default_rng(7)
    fact, dim = _fact(rng, 20_000), _dim(rng)
    fp, dp = _write(tmp_path, "fact", fact), _write(tmp_path, "dim", dim)
    got, fused = _run(_plan(fp, dp, tag="fja_t1"))
    want = _oracle(fact, dim)
    assert fused >= 1, "join fusion must engage on all-int star join"
    assert got.attr.tolist() == want.attr.tolist()
    assert got.s.tolist() == want.s.tolist()
    assert got.c.tolist() == want.c.tolist()


def test_fused_join_agg_null_probe_keys(tmp_path):
    rng = np.random.default_rng(8)
    fact, dim = _fact(rng, 10_000, null_every=7), _dim(rng)
    fp, dp = _write(tmp_path, "fact", fact), _write(tmp_path, "dim", dim)
    got, fused = _run(_plan(fp, dp, tag="fja_t2"))
    want = _oracle(fact, dim)  # merge drops null fk: inner semantics
    assert fused >= 1
    assert got.s.tolist() == want.s.tolist()
    assert got.c.tolist() == want.c.tolist()


def test_fused_join_agg_with_filter_above_join(tmp_path):
    rng = np.random.default_rng(9)
    fact, dim = _fact(rng, 20_000), _dim(rng)
    fp, dp = _write(tmp_path, "fact", fact), _write(tmp_path, "dim", dim)
    preds = [E.BinaryExpr(E.BinaryOp.GT, E.Column("v"), E.Literal(0, T.I64))]
    got, fused = _run(_plan(fp, dp, predicates=preds, tag="fja_t3"))
    want = _oracle(fact, dim, pred=lambda m: m.v > 0)
    assert fused >= 1, "filter + join fuse together"
    assert got.attr.tolist() == want.attr.tolist()
    assert got.s.tolist() == want.s.tolist()
    assert got.c.tolist() == want.c.tolist()


def test_duplicate_build_keys_fall_back_correctly(tmp_path):
    rng = np.random.default_rng(10)
    fact, dim = _fact(rng, 5_000), _dim(rng, dup=True)
    fp, dp = _write(tmp_path, "fact", fact), _write(tmp_path, "dim", dim)
    got, fused = _run(_plan(fp, dp, tag="fja_t4"))
    want = _oracle(fact, dim)  # dup pk 7 duplicates its fact rows
    assert fused == 0, "non-unique build keys must not fuse"
    assert got.s.tolist() == want.s.tolist()
    assert got.c.tolist() == want.c.tolist()


def test_non_device_probe_column_falls_back(tmp_path):
    """A string column in the probe schema disqualifies the static check;
    the ordinary join + agg path must still produce oracle results."""
    rng = np.random.default_rng(11)
    n = 5_000
    fact = pa.table({
        "fk": pa.array(rng.integers(1, 50, n), type=pa.int64()),
        "v": pa.array(rng.integers(-100, 100, n), type=pa.int64()),
        "tag": pa.array(["x"] * n),
    })
    dim = _dim(rng)
    fp, dp = _write(tmp_path, "fact", fact), _write(tmp_path, "dim", dim)
    from blaze_tpu.ops.parquet import scan_node_for_files

    fact_scan = scan_node_for_files(fp, num_partitions=2)
    dim_scan = scan_node_for_files(dp)
    join = N.BroadcastJoin(fact_scan, N.BroadcastExchange(dim_scan),
                           [(E.Column("fk"), E.Column("pk"))],
                           N.JoinType.INNER, N.JoinSide.RIGHT, "fja_dim2")
    partial = N.Agg(join, E.AggExecMode.HASH_AGG,
                    [("attr", E.Column("attr"))], [
        N.AggColumn(E.AggExpr(F.SUM, [E.Column("v")]),
                    E.AggMode.PARTIAL, "s")])
    final = N.Agg(N.ShuffleExchange(partial,
                                    N.HashPartitioning([E.Column("attr")], 2)),
                  E.AggExecMode.HASH_AGG, [("attr", E.Column("attr"))], [
        N.AggColumn(E.AggExpr(F.SUM, [E.Column("v")]), E.AggMode.FINAL, "s")])
    plan = N.Sort(N.ShuffleExchange(final, N.SinglePartitioning(1)),
                  [E.SortOrder(E.Column("attr"))])
    got, fused = _run(plan)
    m = fact.to_pandas().merge(dim.to_pandas(), left_on="fk", right_on="pk")
    want = m.groupby("attr").v.sum().reset_index().sort_values("attr")
    assert got.s.tolist() == want.v.tolist()


def test_chained_star_joins_fuse(tmp_path):
    """TWO stacked dim joins trace into one agg kernel (the q17 star
    shape); results match pandas exactly."""
    from decimal import Decimal

    rng = np.random.default_rng(23)
    n = 30_000
    fact = pa.table({
        "f1": pa.array(rng.integers(1, 40, n), type=pa.int64()),
        "f2": pa.array(rng.integers(1, 20, n), type=pa.int64()),
        "v": pa.array(rng.integers(-50, 50, n), type=pa.int64()),
        # wide decimal rides the fused path as limb planes
        "w": pa.array([Decimal(int(x)).scaleb(-2) for x in
                       rng.integers(10**17, 9 * 10**17, n)],
                      type=pa.decimal128(38, 2)),
    })
    dim1 = pa.table({"pk1": pa.array(np.arange(1, 40), type=pa.int64()),
                     "a1": pa.array(rng.integers(0, 4, 39),
                                    type=pa.int64())})
    dim2 = pa.table({"pk2": pa.array(np.arange(1, 20), type=pa.int64()),
                     "a2": pa.array(rng.integers(0, 3, 19),
                                    type=pa.int64())})
    fp = _write(tmp_path, "fact", fact)
    d1 = _write(tmp_path, "dim1", dim1)
    d2 = _write(tmp_path, "dim2", dim2)
    from blaze_tpu.ops.parquet import scan_node_for_files

    scan = scan_node_for_files(fp, num_partitions=2)
    j1 = N.BroadcastJoin(scan, N.BroadcastExchange(
        scan_node_for_files(d1)), [(E.Column("f1"), E.Column("pk1"))],
        N.JoinType.INNER, N.JoinSide.RIGHT, "chain_d1")
    j2 = N.BroadcastJoin(j1, N.BroadcastExchange(
        scan_node_for_files(d2)), [(E.Column("f2"), E.Column("pk2"))],
        N.JoinType.INNER, N.JoinSide.RIGHT, "chain_d2")
    partial = N.Agg(j2, E.AggExecMode.HASH_AGG,
                    [("a1", E.Column("a1")), ("a2", E.Column("a2"))], [
        N.AggColumn(E.AggExpr(F.SUM, [E.Column("v")]),
                    E.AggMode.PARTIAL, "s"),
        N.AggColumn(E.AggExpr(F.SUM, [E.Column("w")]),
                    E.AggMode.PARTIAL, "ws")])
    final = N.Agg(N.ShuffleExchange(partial,
                                    N.HashPartitioning([E.Column("a1")], 2)),
                  E.AggExecMode.HASH_AGG,
                  [("a1", E.Column("a1")), ("a2", E.Column("a2"))], [
        N.AggColumn(E.AggExpr(F.SUM, [E.Column("v")]), E.AggMode.FINAL, "s"),
        N.AggColumn(E.AggExpr(F.SUM, [E.Column("w")]),
                    E.AggMode.FINAL, "ws")])
    plan = N.Sort(N.ShuffleExchange(final, N.SinglePartitioning(1)),
                  [E.SortOrder(E.Column("a1")), E.SortOrder(E.Column("a2"))])
    with Session() as sess:
        got = sess.execute_to_table(plan).to_pandas()
        fused = sess.metrics.total("fused_join_stages")
    assert fused >= 4, "both joins should fuse on both partitions"
    m = fact.to_pandas().merge(dim1.to_pandas(), left_on="f1",
                               right_on="pk1")
    m = m.merge(dim2.to_pandas(), left_on="f2", right_on="pk2")
    g = m.groupby(["a1", "a2"], as_index=False).agg(s=("v", "sum"),
                                                    ws=("w", "sum"))
    g = g.sort_values(["a1", "a2"]).reset_index(drop=True)
    assert got.a1.tolist() == g.a1.tolist()
    assert got.a2.tolist() == g.a2.tolist()
    assert got.s.tolist() == g.s.tolist()
    assert got.ws.tolist() == g.ws.tolist()


def test_expression_over_wide_column_blocks_fusion(tmp_path):
    """Round-4 review: a device-TYPED expression over a wide decimal
    column (CAST) must keep the agg off the fused path — and still produce
    correct results via the eager path."""
    from decimal import Decimal

    rng = np.random.default_rng(29)
    n = 4000
    fact = pa.table({
        "fk": pa.array(rng.integers(1, 40, n), type=pa.int64()),
        "w": pa.array([Decimal(int(x)).scaleb(-2) for x in
                       rng.integers(10**17, 2 * 10**17, n)],
                      type=pa.decimal128(38, 2)),
    })
    dim = pa.table({"pk": pa.array(np.arange(1, 40), type=pa.int64()),
                    "attr": pa.array(rng.integers(0, 4, 39),
                                     type=pa.int64())})
    fp, dp = _write(tmp_path, "fact", fact), _write(tmp_path, "dim", dim)
    from blaze_tpu.ops.parquet import scan_node_for_files

    join = N.BroadcastJoin(scan_node_for_files(fp, num_partitions=2),
                           N.BroadcastExchange(scan_node_for_files(dp)),
                           [(E.Column("fk"), E.Column("pk"))],
                           N.JoinType.INNER, N.JoinSide.RIGHT, "fja_wexpr")
    agg = N.Agg(join, E.AggExecMode.HASH_AGG, [("attr", E.Column("attr"))], [
        N.AggColumn(E.AggExpr(F.SUM, [E.Cast(E.Column("w"), T.F64)]),
                    E.AggMode.COMPLETE, "s")])
    plan = N.Sort(N.ShuffleExchange(agg, N.SinglePartitioning(1)),
                  [E.SortOrder(E.Column("attr"))])
    with Session() as sess:
        got = sess.execute_to_table(plan).to_pandas()
    m = fact.to_pandas().merge(dim.to_pandas(), left_on="fk", right_on="pk")
    m["wf"] = m.w.astype(float)
    want = m.groupby("attr").wf.sum().sort_index()
    assert got.attr.tolist() == want.index.tolist()
    assert np.allclose(got.s.astype(float).values, want.values)
