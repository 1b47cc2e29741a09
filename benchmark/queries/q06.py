"""q06 class (TPC-DS q6's shape): scan store_sales -> broadcast hash join
with item -> two-stage aggregation over the categories -> sort.
The benchmark's copy of `bench.plan_q06` / `acero_q06`."""

import pyarrow as pa

from benchlib import plans

TABLES = ("store_sales", "item")
SCANNED = "store_sales"
# three int64-plane fact columns (ss_item_sk, ss_quantity, ss_sales_price)
BYTES_PER_ROW = 3 * 8
ORDERED = True  # sorted by the unique group key
ENGINE_COLUMNS = ("i_category_id", "qty", "revenue")
REFERENCE_COLUMNS = ("i_category_id", "ss_quantity_sum", "ss_sales_price_sum")


def plan(data: plans.Dataset):
    from blaze_tpu.ir import exprs as E
    from blaze_tpu.ir import nodes as N
    from blaze_tpu.ir import types as T

    join = N.BroadcastJoin(
        plans.scan(data, "store_sales"),
        N.BroadcastExchange(plans.scan(data, "item", partitioned=False)),
        [(E.Column("ss_item_sk"), E.Column("i_item_sk"))],
        N.JoinType.INNER, N.JoinSide.RIGHT,
        # the program caches the built map per process under this id: name
        # the data it was built from, so that two datasets never share one
        "benchmark_q06_items:" + data.paths["item"][0])
    agg = plans.two_stage_agg(
        join, [("i_category_id", E.Column("i_category_id"))], [
            ("qty", E.AggExpr(E.AggFunction.SUM, [E.Column("ss_quantity")])),
            ("revenue", E.AggExpr(E.AggFunction.SUM, [E.Column("ss_sales_price")],
                                  T.DecimalType(17, 2))),
        ], data.shuffle_partitions)
    return N.Sort(N.ShuffleExchange(agg, N.SinglePartitioning(1)),
                  [E.SortOrder(E.Column("i_category_id"))])


def reference(tables) -> pa.Table:
    joined = tables["store_sales"].join(
        tables["item"], keys="ss_item_sk", right_keys="i_item_sk")
    g = joined.group_by("i_category_id").aggregate(
        [("ss_quantity", "sum"), ("ss_sales_price", "sum")])
    return g.sort_by("i_category_id")
