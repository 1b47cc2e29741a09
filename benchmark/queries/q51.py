"""q51 class (the store channel of TPC-DS q51: CTE `store_v1` and the outer
query's window): SUM(ss_sales_price) per (item, sale date), the cumulative
SUM of that OVER (PARTITION BY item ORDER BY date ROWS BETWEEN UNBOUNDED
PRECEDING AND CURRENT ROW), then the running MAX of the cumulative over the
same partition, order and frame. q51's `web_cumulative > store_cumulative`
filter and LIMIT need `web_sales`; in their place a two-stage aggregation by
date (COUNT(*), SUM(cume_sales), MAX(store_cumulative)) closes the plan, so
that every window value of every partition reaches the answer. The
configuration's file lists what is left out of the specification's text."""

from decimal import Decimal

import numpy as np
import pyarrow as pa

from benchlib import plans

TABLES = ("store_sales",)
SCANNED = "store_sales"
COLUMNS = ("ss_item_sk", "ss_sold_date_sk", "ss_sales_price")
# two int64 keys and a decimal(7,2), which parquet holds in 4 bytes
BYTES_PER_ROW = 2 * 8 + 4
ORDERED = True  # sorted by the unique group key
ENGINE_COLUMNS = ("ss_sold_date_sk", "items", "cume_sales", "store_cumulative")
REFERENCE_COLUMNS = ENGINE_COLUMNS
# what `window_roofline_share` counts the windows' bytes from: each window
# reads its two key planes and its argument plane and writes its result
WINDOWS = ({"keys": 2, "arguments": 1, "results": 1},
           {"keys": 2, "arguments": 1, "results": 1})
RUNNING = ("rows", None, 0)  # UNBOUNDED PRECEDING .. CURRENT ROW


def _require_device_window():
    """The configuration holds every query to `window_device_batches` >= 1
    and `window_host_batches` == 0, and a program that counts neither ends
    at the harness's `require_counters` — but only after its warm-up query,
    which there runs 18,000 partition loops over Python `Decimal` objects a
    window. So the class asks the same question before anything compiles,
    and such a program ends here, by itself, in the seconds its imports and
    the data take."""
    from blaze_tpu.runtime.metrics import TRIPWIRE_METRICS

    missing = sorted({"window_device_batches", "window_host_batches"}
                     - set(TRIPWIRE_METRICS))
    if missing:
        raise ImportError(
            f"q51: the program reports no counter named {missing} "
            "(`counters_must` of tpcds_sf1_window_chip1): it has no device "
            "window to measure")


def plan(data: plans.Dataset):
    _require_device_window()
    from blaze_tpu.ir import exprs as E
    from blaze_tpu.ir import nodes as N
    from blaze_tpu.ops.parquet import scan_node_for_files

    item, date = E.Column("ss_item_sk"), E.Column("ss_sold_date_sk")
    scan = scan_node_for_files(data.paths["store_sales"],
                               num_partitions=data.scan_partitions,
                               projection=list(COLUMNS))
    daily = plans.two_stage_agg(
        scan, [("ss_item_sk", item), ("ss_sold_date_sk", date)],
        [("sum_sales", E.AggExpr(E.AggFunction.SUM,
                                 [E.Column("ss_sales_price")]))],
        data.shuffle_partitions)
    by_item = N.Sort(
        N.ShuffleExchange(daily, N.HashPartitioning(
            [item], data.shuffle_partitions)),
        [E.SortOrder(item), E.SortOrder(date)])
    order = [E.SortOrder(date)]
    cume = N.Window(by_item, [N.WindowExpr(
        "agg", "cume_sales", agg=E.AggExpr(
            E.AggFunction.SUM, [E.Column("sum_sales")]), frame=RUNNING)],
        [item], order)
    top = N.Window(cume, [N.WindowExpr(
        "agg", "store_cumulative", agg=E.AggExpr(
            E.AggFunction.MAX, [E.Column("cume_sales")]), frame=RUNNING)],
        [item], order)
    closing = plans.two_stage_agg(top, [("ss_sold_date_sk", date)], [
        ("items", E.AggExpr(E.AggFunction.COUNT, [])),
        ("cume_sales", E.AggExpr(E.AggFunction.SUM,
                                 [E.Column("cume_sales")])),
        ("store_cumulative", E.AggExpr(E.AggFunction.MAX,
                                       [E.Column("store_cumulative")])),
    ], data.shuffle_partitions)
    return N.Sort(N.ShuffleExchange(closing, N.SinglePartitioning(1)),
                  [E.SortOrder(date)])


def _cents(column: pa.ChunkedArray) -> np.ndarray:
    """A decimal(p <= 18, 2) column's unscaled values: the low word of each
    16-byte value."""
    arr = column.combine_chunks()
    assert arr.null_count == 0
    return np.frombuffer(arr.buffers()[1], dtype=np.int64,
                         offset=arr.offset * 16, count=len(arr) * 2)[0::2]


def _decimal(cents, precision: int) -> pa.Array:
    return pa.array([Decimal(int(c)).scaleb(-2) for c in cents],
                    type=pa.decimal128(precision, 2))


def reference(tables) -> pa.Table:
    """Plain: Acero's group-by for the daily sums, then numpy over unscaled
    cents (cumulative sums and running maxima per item, in int64 after the
    check that none passes 2^62), the per-day sums in Python integers; typed
    as Spark types them (decimal(27,2), their per-day SUM decimal(37,2))."""
    sales = tables["store_sales"]
    daily = pa.table({
        "item": sales["ss_item_sk"], "date": sales["ss_sold_date_sk"],
        "cents": pa.array(_cents(sales["ss_sales_price"])),
    }).group_by(["item", "date"]).aggregate([("cents", "sum")]).sort_by(
        [("item", "ascending"), ("date", "ascending")])
    item = daily["item"].to_numpy()
    date = daily["date"].to_numpy()
    cents = daily["cents_sum"].to_numpy()
    assert float(np.abs(cents).astype(np.float64).sum()) < 2.0 ** 62
    n = len(item)
    starts = np.flatnonzero(np.concatenate([[True], item[1:] != item[:-1]]))
    ends = np.concatenate([starts[1:], [n]])
    running = np.cumsum(cents)
    before = np.concatenate([[0], running])[starts]  # the sum before an item
    cume = running - np.repeat(before, ends - starts)
    top = np.empty_like(cume)
    for s, e in zip(starts, ends):
        np.maximum.accumulate(cume[s:e], out=top[s:e])
    by_date = np.argsort(date, kind="stable")
    days, first = np.unique(date[by_date], return_index=True)
    return pa.table({
        "ss_sold_date_sk": pa.array(days, type=pa.int64()),
        "items": pa.array(np.diff(np.concatenate([first, [n]])),
                          type=pa.int64()),
        "cume_sales": _decimal(
            np.add.reduceat(cume[by_date].astype(object), first), 37),
        "store_cumulative": _decimal(
            np.maximum.reduceat(top[by_date], first), 27),
    })
