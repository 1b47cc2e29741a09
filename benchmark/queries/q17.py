"""q17 class (TPC-DS v3.2 query 17, text kept whole; qualification
substitution YEAR = 2001):

    SELECT i_item_id, i_item_desc, s_state,
           count(ss_quantity), avg(ss_quantity), stddev_samp(ss_quantity),
           stddev_samp(ss_quantity) / avg(ss_quantity),
           ... the same four of sr_return_quantity and of cs_quantity
    FROM store_sales, store_returns, catalog_sales,
         date_dim d1, date_dim d2, date_dim d3, store, item
    WHERE d1.d_quarter_name = '2001Q1' AND d1.d_date_sk = ss_sold_date_sk
      AND i_item_sk = ss_item_sk AND s_store_sk = ss_store_sk
      AND ss_customer_sk = sr_customer_sk AND ss_item_sk = sr_item_sk
      AND ss_ticket_number = sr_ticket_number
      AND sr_returned_date_sk = d2.d_date_sk
      AND d2.d_quarter_name IN ('2001Q1', '2001Q2', '2001Q3')
      AND sr_customer_sk = cs_bill_customer_sk AND sr_item_sk = cs_item_sk
      AND cs_sold_date_sk = d3.d_date_sk
      AND d3.d_quarter_name IN ('2001Q1', '2001Q2', '2001Q3')
    GROUP BY i_item_id, i_item_desc, s_state
    ORDER BY i_item_id, i_item_desc, s_state LIMIT 100

as Spark plans it: each fact table scanned and joined by broadcast to its
filtered `date_dim`, below its own hash exchange; store_sales JOIN
store_returns by sort-merge on (customer, item, ticket); that join's rows
through a second hash exchange on (customer, item) and a sort into a second
sort-merge join with catalog_sales; broadcast joins with `store` and `item`
(the names as dictionary codes); a two-stage aggregation of nine aggregates
(three COUNTs, AVGs, STDDEV_SAMPs) by the three names; the three ratios after
it; the ordered first 100. The configuration's `counters_must` holds the
program to running both merge joins and the moments on the device."""

import math
from fractions import Fraction

import pyarrow as pa
import pyarrow.compute as pc

from benchlib import plans

TABLES = ("store_sales", "store_returns", "catalog_sales", "date_dim", "store",
          "item")
SCANNED = "store_sales"
YEAR = 2001
SALES = ("ss_sold_date_sk", "ss_customer_sk", "ss_item_sk", "ss_ticket_number",
         "ss_store_sk", "ss_quantity")
RETURNS = ("sr_returned_date_sk", "sr_customer_sk", "sr_item_sk",
           "sr_ticket_number", "sr_return_quantity")
CATALOG = ("cs_sold_date_sk", "cs_bill_customer_sk", "cs_item_sk",
           "cs_quantity")
# what the plan must read once, per store_sales row: its six int64 columns
# and its share of the others' (Table 3-2 at SF=1: 287,514 returns and
# 1,441,548 catalog lines to 2,880,404 sales)
BYTES_PER_ROW = 8 * (len(SALES) + len(RETURNS) * 287_514 / 2_880_404
                     + len(CATALOG) * 1_441_548 / 2_880_404)
ORDERED = True  # ORDER BY the whole group key
NAMES = ("i_item_id", "i_item_desc", "s_state")
# (the measure, the prefix of its four output columns) in the text's order
MEASURES = (("ss_quantity", "store_sales_quantity"),
            ("sr_return_quantity", "store_returns_quantity"),
            ("cs_quantity", "catalog_sales_quantity"))
SUFFIXES = ("count", "ave", "stdev", "cov")
ENGINE_COLUMNS = NAMES + tuple(f"{prefix}{suffix}" for _m, prefix in MEASURES
                               for suffix in SUFFIXES)
REFERENCE_COLUMNS = ENGINE_COLUMNS
LIMIT = 100
# the join keys, in the text's order
KEYS_SR = (("ss_customer_sk", "sr_customer_sk"), ("ss_item_sk", "sr_item_sk"),
           ("ss_ticket_number", "sr_ticket_number"))
KEYS_CS = (("sr_customer_sk", "cs_bill_customer_sk"),
           ("sr_item_sk", "cs_item_sk"))
COUNTERS = ("moment_device_batches", "moment_host_batches", "smj_device_joins",
            "smj_host_joins", "coded_key_batches", "host_key_batches")


def quarters(year: int = YEAR):
    """d1's quarter, and d2's and d3's."""
    return [f"{year}Q1"], [f"{year}Q{q}" for q in (1, 2, 3)]


def _require_device_moments():
    """The configuration holds every query to `moment_device_batches` >= 1,
    `smj_device_joins` >= 2 and `coded_key_batches` >= 1, and a program that
    counts none of them ends at the harness's `require_counters`, but only
    after its warm-up query, three fact scans and two merge joins compiled
    cold; one without STDDEV_SAMP cannot build the plan at all. So the class
    asks both questions before anything compiles, and such a program ends
    here, by itself, in the seconds its imports and the data take."""
    from blaze_tpu.ir import exprs as E
    from blaze_tpu.runtime.metrics import TRIPWIRE_METRICS

    missing = sorted(set(COUNTERS) - set(TRIPWIRE_METRICS))
    if missing or not hasattr(E.AggFunction, "STDDEV_SAMP"):
        raise ImportError(
            f"q17: the program has no STDDEV_SAMP or reports no counter named "
            f"{missing} (`counters_must` of tpcds_sf1_star3_chip1): it has no "
            "device moments or device merge joins to measure")


def _dated(data: plans.Dataset, table: str, columns, date_key: str, names,
           origin: str):
    """A fact table's columns read, joined by broadcast to the days whose
    `d_quarter_name` is among ``names``; the date keys dropped."""
    from blaze_tpu.ir import exprs as E
    from blaze_tpu.ir import nodes as N
    from blaze_tpu.ir import types as T
    from blaze_tpu.ops.parquet import scan_node_for_files

    col = E.Column
    quarter = [E.Literal(q, T.STRING) for q in names]
    days = N.Projection(N.Filter(
        scan_node_for_files(data.paths["date_dim"],
                            projection=["d_date_sk", "d_quarter_name"]),
        [E.BinaryExpr(E.BinaryOp.EQ, col("d_quarter_name"), quarter[0])
         if len(quarter) == 1 else E.InList(col("d_quarter_name"), quarter)]),
        [col("d_date_sk")], ["d_date_sk"])
    facts = scan_node_for_files(data.paths[table],
                                num_partitions=data.scan_partitions,
                                projection=list(columns))
    joined = N.BroadcastJoin(
        facts, N.BroadcastExchange(days), [(col(date_key), col("d_date_sk"))],
        N.JoinType.INNER, N.JoinSide.RIGHT,
        f"benchmark_q17_{'_'.join(names)}:" + origin)
    keep = [c for c in columns if c != date_key]
    return N.Projection(joined, [col(c) for c in keep], keep)


def _sorted(data: plans.Dataset, child, keys):
    """Hash exchange on the join keys -> sort: a merge join's side."""
    from blaze_tpu.ir import exprs as E
    from blaze_tpu.ir import nodes as N

    exchange = N.ShuffleExchange(child, N.HashPartitioning(
        [E.Column(k) for k in keys], data.shuffle_partitions))
    return N.Sort(exchange, [E.SortOrder(E.Column(k)) for k in keys])


def _merge_join(data: plans.Dataset, left, right, keys):
    from blaze_tpu.ir import exprs as E
    from blaze_tpu.ir import nodes as N

    return N.SortMergeJoin(
        _sorted(data, left, [l for l, _ in keys]),
        _sorted(data, right, [r for _, r in keys]),
        [(E.Column(l), E.Column(r)) for l, r in keys], N.JoinType.INNER)


def plan(data: plans.Dataset, year: int = YEAR, limit: int = LIMIT):
    """``limit`` None gives every group, sorted (the tests' form)."""
    _require_device_moments()
    from blaze_tpu.ir import exprs as E
    from blaze_tpu.ir import nodes as N
    from blaze_tpu.ops.parquet import scan_node_for_files

    col = E.Column
    d1, d23 = quarters(year)
    # the program caches a built map per process under its id: name the data
    # (and the days) it was built from, so that two datasets never share one
    origin = data.paths["date_dim"][0]
    sales = _dated(data, "store_sales", SALES, "ss_sold_date_sk", d1, origin)
    returns = _dated(data, "store_returns", RETURNS, "sr_returned_date_sk",
                     d23, origin)
    catalog = _dated(data, "catalog_sales", CATALOG, "cs_sold_date_sk", d23,
                     origin)
    returned = _merge_join(data, sales, returns, KEYS_SR)
    carried = ("ss_item_sk", "ss_store_sk", "ss_quantity", "sr_customer_sk",
               "sr_item_sk", "sr_return_quantity")
    returned = N.Projection(returned, [col(c) for c in carried], list(carried))
    again = _merge_join(data, returned, catalog, KEYS_CS)
    store = scan_node_for_files(data.paths["store"],
                                projection=["s_store_sk", "s_state"])
    item = scan_node_for_files(data.paths["item"],
                               projection=["i_item_sk", "i_item_id",
                                           "i_item_desc"])
    named = N.BroadcastJoin(
        N.BroadcastJoin(again, N.BroadcastExchange(store),
                        [(col("ss_store_sk"), col("s_store_sk"))],
                        N.JoinType.INNER, N.JoinSide.RIGHT,
                        "benchmark_q17_stores:" + data.paths["store"][0]),
        N.BroadcastExchange(item), [(col("ss_item_sk"), col("i_item_sk"))],
        N.JoinType.INNER, N.JoinSide.RIGHT,
        "benchmark_q17_items:" + data.paths["item"][0])
    measures = [m for m, _p in MEASURES]
    rows = N.Projection(named, [col(c) for c in (*NAMES, *measures)],
                        [*NAMES, *measures])
    aggs = []
    for measure, prefix in MEASURES:
        for fn, suffix in ((E.AggFunction.COUNT, "count"),
                           (E.AggFunction.AVG, "ave"),
                           (E.AggFunction.STDDEV_SAMP, "stdev")):
            aggs.append((prefix + suffix, E.AggExpr(fn, [col(measure)])))
    agg = plans.two_stage_agg(rows, [(n, col(n)) for n in NAMES], aggs,
                              data.shuffle_partitions)
    out = [col(c) if not c.endswith("cov") else E.BinaryExpr(
        E.BinaryOp.DIV, col(c[:-3] + "stdev"), col(c[:-3] + "ave"))
        for c in ENGINE_COLUMNS]
    order = [E.SortOrder(col(n)) for n in NAMES]
    top = N.Sort(N.ShuffleExchange(N.Sort(agg, order, fetch_limit=limit),
                                   N.SinglePartitioning(1)),
                 order, fetch_limit=limit)
    return N.Projection(top, out, list(ENGINE_COLUMNS))


def _days(dates: pa.Table, names) -> pa.Array:
    return dates.filter(pc.is_in(dates["d_quarter_name"],
                                 value_set=pa.array(names)))["d_date_sk"]


def reference(tables, year: int = YEAR, limit: int = LIMIT) -> pa.Table:
    """Plain, over the same parquet files: Acero's filters and hash joins,
    then each group's n, Σx and Σx² in Python integers. AVG as Spark computes
    it for a long (the sum as a double over the count: both exact under 2^53
    here), STDDEV_SAMP as the correctly rounded square root of
    ``float(Fraction(n·Σx² - (Σx)², n·(n - 1)))``, NULL under two rows, the
    ratio a double division; the groups sorted by the names' bytes."""
    _require_device_moments()
    d1, d23 = quarters(year)
    dates = tables["date_dim"]

    def dated(table, columns, date_key, names):
        t = tables[table].select(list(columns))
        return t.filter(pc.is_in(t[date_key], value_set=_days(dates, names)))

    sales = dated("store_sales", SALES, "ss_sold_date_sk", d1)
    returns = dated("store_returns", RETURNS, "sr_returned_date_sk", d23)
    catalog = dated("catalog_sales", CATALOG, "cs_sold_date_sk", d23)
    joined = sales.join(returns, keys=[l for l, _ in KEYS_SR],
                        right_keys=[r for _, r in KEYS_SR], join_type="inner")
    # Acero keeps the left keys of a join: ss_customer_sk and ss_item_sk
    # stand for sr_customer_sk and sr_item_sk, which equal them
    joined = joined.join(catalog, keys=["ss_customer_sk", "ss_item_sk"],
                         right_keys=[r for _, r in KEYS_CS], join_type="inner")
    joined = joined.join(tables["store"].select(["s_store_sk", "s_state"]),
                         keys="ss_store_sk", right_keys="s_store_sk",
                         join_type="inner")
    joined = joined.join(
        tables["item"].select(["i_item_sk", "i_item_id", "i_item_desc"]),
        keys="ss_item_sk", right_keys="i_item_sk", join_type="inner")
    data = joined.select([*NAMES, *(m for m, _p in MEASURES)]).to_pydict()
    groups = {}
    for row in zip(*(data[c] for c in (*NAMES, *(m for m, _p in MEASURES)))):
        values = groups.setdefault(row[:len(NAMES)], [[] for _ in MEASURES])
        for vals, x in zip(values, row[len(NAMES):]):
            if x is not None:
                vals.append(x)
    out = {c: [] for c in ENGINE_COLUMNS}
    keys = sorted(groups, key=lambda k: tuple(s.encode() for s in k))
    for key in keys if limit is None else keys[:limit]:
        for name, value in zip(NAMES, key):
            out[name].append(value)
        for vals, (_m, prefix) in zip(groups[key], MEASURES):
            n, total = len(vals), sum(vals)
            ave = float(total) / float(n) if n else None
            stdev = None
            if n > 1:
                squares = sum(v * v for v in vals)
                stdev = math.sqrt(float(Fraction(n * squares - total * total,
                                                 n * (n - 1))))
            out[prefix + "count"].append(n)
            out[prefix + "ave"].append(ave)
            out[prefix + "stdev"].append(stdev)
            out[prefix + "cov"].append(
                stdev / ave if stdev is not None and ave else None)
    return pa.table({c: pa.array(out[c], pa.large_utf8() if c in NAMES else
                                 pa.int64() if c.endswith("count") else
                                 pa.float64()) for c in ENGINE_COLUMNS})
