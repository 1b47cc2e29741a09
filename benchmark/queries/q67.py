"""q67 class (TPC-DS q67's shape): aggregation over the (item, store) groups
-> exchange -> sort -> rank within item -> keep the top ranks: a window over
very many tiny partitions. The benchmark's copy of `bench.plan_q67` /
`acero_q67`."""

import numpy as np
import pyarrow as pa

from benchlib import plans

TABLES = ("store_sales",)
SCANNED = "store_sales"
# three int64 fact columns (ss_item_sk, ss_store_sk, ss_quantity)
BYTES_PER_ROW = 3 * 8
ORDERED = False  # rank ties leave the order open: compared as sets
ENGINE_COLUMNS = ("ss_item_sk", "ss_store_sk", "qty")
REFERENCE_COLUMNS = ("ss_item_sk", "ss_store_sk", "ss_quantity_sum")


def plan(data: plans.Dataset, top: int = 3):
    from blaze_tpu.ir import exprs as E
    from blaze_tpu.ir import nodes as N
    from blaze_tpu.ir import types as T

    agg = plans.two_stage_agg(
        plans.scan(data, "store_sales"),
        [("ss_item_sk", E.Column("ss_item_sk")),
         ("ss_store_sk", E.Column("ss_store_sk"))],
        [("qty", E.AggExpr(E.AggFunction.SUM, [E.Column("ss_quantity")]))],
        data.shuffle_partitions)
    single = N.ShuffleExchange(agg, N.SinglePartitioning(1))
    srt = N.Sort(single, [E.SortOrder(E.Column("ss_item_sk")),
                          E.SortOrder(E.Column("qty"), ascending=False)])
    win = N.Window(srt, [N.WindowExpr("rank", "rk")],
                   [E.Column("ss_item_sk")],
                   [E.SortOrder(E.Column("qty"), ascending=False)])
    return N.Filter(win, [E.BinaryExpr(E.BinaryOp.LTEQ, E.Column("rk"),
                                       E.Literal(top, T.I32))])


def reference(tables, top: int = 3) -> pa.Table:
    g = tables["store_sales"].group_by(["ss_item_sk", "ss_store_sk"]).aggregate(
        [("ss_quantity", "sum")])
    keep = plans.rank_at_most(np.asarray(g["ss_item_sk"]),
                              np.asarray(g["ss_quantity_sum"]), top)
    return g.take(keep)
