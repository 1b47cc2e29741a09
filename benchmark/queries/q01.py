"""q01 class (TPC-DS q1's shape): scan store_returns -> decimal filter ->
two-stage aggregation over the stores -> exchange -> top-100 by total.
The benchmark's copy of `bench.plan_q01` / `acero_q01`."""

import decimal

import pyarrow as pa
import pyarrow.compute as pc

from benchlib import plans

TABLES = ("store_returns",)
SCANNED = "store_returns"
# bytes the plan must read once per scanned row: two int64-plane columns
# (sr_store_sk, sr_return_amt; sr_customer_sk is pruned)
BYTES_PER_ROW = 2 * 8
# the final Sort's key (total) makes the order total: rows compare in order
ORDERED = True
ENGINE_COLUMNS = ("sr_store_sk", "total", "cnt")
REFERENCE_COLUMNS = ("sr_store_sk", "sr_return_amt_sum", "sr_return_amt_count")


def plan(data: plans.Dataset, threshold: str = "500.00", limit: int = 100):
    from blaze_tpu.ir import exprs as E
    from blaze_tpu.ir import nodes as N
    from blaze_tpu.ir import types as T

    filt = N.Filter(plans.scan(data, "store_returns"), [E.BinaryExpr(
        E.BinaryOp.GT, E.Column("sr_return_amt"),
        E.Literal(threshold, T.DecimalType(7, 2)))])
    agg = plans.two_stage_agg(filt, [("sr_store_sk", E.Column("sr_store_sk"))], [
        ("total", E.AggExpr(E.AggFunction.SUM, [E.Column("sr_return_amt")],
                            T.DecimalType(17, 2))),
        ("cnt", E.AggExpr(E.AggFunction.COUNT, [])),
    ], data.shuffle_partitions)
    single = N.ShuffleExchange(agg, N.SinglePartitioning(1))
    return N.Sort(single, [E.SortOrder(E.Column("total"), ascending=False)],
                  fetch_limit=limit)


def reference(tables, threshold: str = "500.00", limit: int = 100) -> pa.Table:
    tbl = tables["store_returns"]
    tbl = tbl.filter(pc.greater(tbl["sr_return_amt"],
                                pa.scalar(decimal.Decimal(threshold))))
    g = tbl.group_by("sr_store_sk").aggregate(
        [("sr_return_amt", "sum"), ("sr_return_amt", "count")])
    return g.sort_by([("sr_return_amt_sum", "descending")]).slice(0, limit)
