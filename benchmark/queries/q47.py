"""q47 class (TPC-DS q47's shape): scan store_sales -> broadcast hash join
with item -> two-stage aggregation over (category, brand) -> sort -> rank
within category -> keep the top ranks. The benchmark's copy of
`bench.plan_q47` / `acero_q47`."""

import numpy as np
import pyarrow as pa

from benchlib import plans

TABLES = ("store_sales", "item")
SCANNED = "store_sales"
# two int64 fact columns (ss_item_sk, ss_quantity)
BYTES_PER_ROW = 2 * 8
ORDERED = False  # rank ties leave the order open: compared as sets
ENGINE_COLUMNS = ("i_category_id", "i_brand_id", "qty")
REFERENCE_COLUMNS = ("i_category_id", "i_brand_id", "ss_quantity_sum")


def plan(data: plans.Dataset, top: int = 5):
    from blaze_tpu.ir import exprs as E
    from blaze_tpu.ir import nodes as N
    from blaze_tpu.ir import types as T

    join = N.BroadcastJoin(
        plans.scan(data, "store_sales"),
        N.BroadcastExchange(plans.scan(data, "item", partitioned=False)),
        [(E.Column("ss_item_sk"), E.Column("i_item_sk"))],
        N.JoinType.INNER, N.JoinSide.RIGHT,
        # the built map is cached per process under this id (see q06)
        "benchmark_q47_items:" + data.paths["item"][0])
    agg = plans.two_stage_agg(
        join, [("i_category_id", E.Column("i_category_id")),
               ("i_brand_id", E.Column("i_brand_id"))],
        [("qty", E.AggExpr(E.AggFunction.SUM, [E.Column("ss_quantity")]))],
        data.shuffle_partitions)
    single = N.ShuffleExchange(agg, N.SinglePartitioning(1))
    srt = N.Sort(single, [E.SortOrder(E.Column("i_category_id")),
                          E.SortOrder(E.Column("qty"), ascending=False)])
    win = N.Window(srt, [N.WindowExpr("rank", "rk")],
                   [E.Column("i_category_id")],
                   [E.SortOrder(E.Column("qty"), ascending=False)])
    return N.Filter(win, [E.BinaryExpr(E.BinaryOp.LTEQ, E.Column("rk"),
                                       E.Literal(top, T.I32))])


def reference(tables, top: int = 5) -> pa.Table:
    joined = tables["store_sales"].join(
        tables["item"], keys="ss_item_sk", right_keys="i_item_sk")
    g = joined.group_by(["i_category_id", "i_brand_id"]).aggregate(
        [("ss_quantity", "sum")])
    keep = plans.rank_at_most(np.asarray(g["i_category_id"]),
                              np.asarray(g["ss_quantity_sum"]), top)
    return g.take(keep)
