"""q29 class (the store channel of TPC-DS q29, the join q17, q25 and q50
share): store_sales joined with store_returns on (customer, item, ticket),
both fact tables through a hash exchange and a sort into a sort-merge join,
as Spark plans a join whose sides both exceed the broadcast threshold; then
SUM(ss_quantity), SUM(sr_return_quantity) per (item, store), the first 100
groups in key order. The configuration's file lists what is left out of the
specification's text."""

import pyarrow as pa

from benchlib import plans

TABLES = ("store_sales", "store_returns")
SCANNED = "store_sales"
SALES_COLUMNS = ("ss_customer_sk", "ss_item_sk", "ss_ticket_number",
                 "ss_store_sk", "ss_quantity")
RETURNS_COLUMNS = ("sr_customer_sk", "sr_item_sk", "sr_ticket_number",
                   "sr_return_quantity")
# five int64 store_sales columns, and per store_sales row its share of the
# four int64 store_returns columns (Table 3-2 at SF=1: 287,514 / 2,880,404)
BYTES_PER_ROW = 5 * 8 + 4 * 8 * 287_514 / 2_880_404
ORDERED = True  # sorted by the unique group key
ENGINE_COLUMNS = ("ss_item_sk", "ss_store_sk", "qty", "return_qty")
REFERENCE_COLUMNS = ("ss_item_sk", "ss_store_sk", "ss_quantity_sum",
                     "sr_return_quantity_sum")
# the specification's order: customer, item, ticket
KEYS = (("ss_customer_sk", "sr_customer_sk"), ("ss_item_sk", "sr_item_sk"),
        ("ss_ticket_number", "sr_ticket_number"))
# what `smj_roofline_share` counts the join's bytes from
MERGE_JOIN = {"left": ("store_sales", SALES_COLUMNS),
              "right": ("store_returns", RETURNS_COLUMNS), "keys": len(KEYS)}


def _sorted_side(data: plans.Dataset, table: str, columns, keys):
    """Scan of the columns read -> hash exchange on the join keys -> sort."""
    from blaze_tpu.ir import exprs as E
    from blaze_tpu.ir import nodes as N
    from blaze_tpu.ops.parquet import scan_node_for_files

    scan = scan_node_for_files(data.paths[table],
                               num_partitions=data.scan_partitions,
                               projection=list(columns))
    exchange = N.ShuffleExchange(scan, N.HashPartitioning(
        [E.Column(k) for k in keys], data.shuffle_partitions))
    return N.Sort(exchange, [E.SortOrder(E.Column(k)) for k in keys])


def _require_device_join():
    """The configuration holds every query to `smj_device_joins` >= 1 and
    `smj_host_joins` == 0, and a program that counts neither ends at the
    harness's `require_counters` — but only after its warm-up query, which
    compiles a seven-operand 64-bit sort of a million rows: over twenty
    minutes from a cold cache, with the join's host loops behind it. So the
    class asks the same question before anything compiles, and such a
    program ends here, by itself, in the seconds its imports take."""
    from blaze_tpu.runtime.metrics import TRIPWIRE_METRICS

    missing = sorted({"smj_device_joins", "smj_host_joins"}
                     - set(TRIPWIRE_METRICS))
    if missing:
        raise ImportError(
            f"q29: the program reports no counter named {missing} (`counters_must`"
            " of tpcds_sf1_smj_chip1): it has no device sort-merge join to measure")


def plan(data: plans.Dataset, limit: int = 100):
    _require_device_join()
    from blaze_tpu.ir import exprs as E
    from blaze_tpu.ir import nodes as N

    join = N.SortMergeJoin(
        _sorted_side(data, "store_sales", SALES_COLUMNS, [l for l, _ in KEYS]),
        _sorted_side(data, "store_returns", RETURNS_COLUMNS,
                     [r for _, r in KEYS]),
        [(E.Column(l), E.Column(r)) for l, r in KEYS], N.JoinType.INNER)
    agg = plans.two_stage_agg(
        join, [("ss_item_sk", E.Column("ss_item_sk")),
               ("ss_store_sk", E.Column("ss_store_sk"))], [
            ("qty", E.AggExpr(E.AggFunction.SUM, [E.Column("ss_quantity")])),
            ("return_qty", E.AggExpr(E.AggFunction.SUM,
                                     [E.Column("sr_return_quantity")])),
        ], data.shuffle_partitions)
    return N.Sort(N.ShuffleExchange(agg, N.SinglePartitioning(1)),
                  [E.SortOrder(E.Column("ss_item_sk")),
                   E.SortOrder(E.Column("ss_store_sk"))], fetch_limit=limit)


def reference(tables, limit: int = 100) -> pa.Table:
    joined = tables["store_sales"].select(list(SALES_COLUMNS)).join(
        tables["store_returns"].select(list(RETURNS_COLUMNS)),
        keys=[l for l, _ in KEYS], right_keys=[r for _, r in KEYS],
        join_type="inner")
    g = joined.group_by(["ss_item_sk", "ss_store_sk"]).aggregate(
        [("ss_quantity", "sum"), ("sr_return_quantity", "sum")])
    return g.sort_by([("ss_item_sk", "ascending"),
                      ("ss_store_sk", "ascending")]).slice(0, limit)
