"""q22 class (TPC-DS v3.2 query 22, text kept whole):

    SELECT i_product_name, i_brand, i_class, i_category,
           avg(inv_quantity_on_hand) qoh
    FROM inventory, date_dim, item
    WHERE inv_date_sk = d_date_sk AND inv_item_sk = i_item_sk
      AND d_month_seq BETWEEN [DMS] AND [DMS] + 11
    GROUP BY ROLLUP(i_product_name, i_brand, i_class, i_category)
    ORDER BY qoh, i_product_name, i_brand, i_class, i_category LIMIT 100

as Spark plans it: scan inventory -> BHJ filtered date_dim -> BHJ item (the
payload: the four names) -> coalesce -> Expand x5 -> partial AVG -> hash exchange on the
five grouping keys -> final AVG -> TakeOrdered 100. The four names are
char(50) strings; the program keeps them on the chip as dictionary codes from
the scan of `item` to the final aggregation, and the configuration's
`counters_must` holds it to that."""

import pyarrow as pa
import pyarrow.compute as pc

from benchlib import plans

TABLES = ("inventory", "date_dim", "item")
SCANNED = "inventory"
COLUMNS = ("inv_date_sk", "inv_item_sk", "inv_quantity_on_hand")
BYTES_PER_ROW = 3 * 8  # three int64 columns
ORDERED = True         # ORDER BY every output column: ties are equal rows
NAMES = ("i_product_name", "i_brand", "i_class", "i_category")
ENGINE_COLUMNS = NAMES + ("qoh",)
REFERENCE_COLUMNS = ENGINE_COLUMNS
LIMIT = 100
# what `rollup_roofline_share` counts a rollup's bytes from: each joined row's
# key planes and its measure read once, `sets` rows of the keys, the grouping
# id and the measure written once
ROLLUP = {"keys": len(NAMES), "sets": len(NAMES) + 1, "measures": 1}
COUNTERS = ("coded_key_batches", "host_key_batches", "rollup_rows",
            "dict_entries", "dict_remap_rows")


def _require_coded_columns():
    """The configuration holds every query to `coded_key_batches` >= 1 and
    `host_key_batches` == 0, and a program that counts neither ends at the
    harness's `require_counters` — but only after its warm-up query, which
    there takes 11.7M rows through the generic probe, host projections and
    the host table's string keys. So the class asks the same question before
    anything is read or compiled, and such a program ends here, by itself,
    in the seconds its imports and the data take."""
    from blaze_tpu.runtime.metrics import TRIPWIRE_METRICS

    missing = sorted(set(COUNTERS) - set(TRIPWIRE_METRICS))
    if missing:
        raise ImportError(
            f"q22: the program reports no counter named {missing} "
            "(`counters_must` of tpcds_sf1_inv_rollup_chip1): it keeps no "
            "var-width column on the device as codes")


def plan(data: plans.Dataset, dms: int = 1200, limit: int = LIMIT):
    """``limit`` None gives the whole rollup, sorted (the tests' form)."""
    _require_coded_columns()
    from blaze_tpu.ir import exprs as E
    from blaze_tpu.ir import nodes as N
    from blaze_tpu.ir import types as T
    from blaze_tpu.ops.parquet import scan_node_for_files

    col = E.Column
    year = N.Projection(N.Filter(
        scan_node_for_files(data.paths["date_dim"],
                            projection=["d_date_sk", "d_month_seq"]),
        [E.BinaryExpr(E.BinaryOp.GTEQ, col("d_month_seq"), E.Literal(dms, T.I64)),
         E.BinaryExpr(E.BinaryOp.LTEQ, col("d_month_seq"),
                      E.Literal(dms + 11, T.I64))]),
        [col("d_date_sk")], ["d_date_sk"])
    inventory = scan_node_for_files(data.paths["inventory"],
                                    num_partitions=data.scan_partitions,
                                    projection=list(COLUMNS))
    item = scan_node_for_files(data.paths["item"],
                               projection=["i_item_sk", *NAMES])
    # the program caches a built map per process under its id: name the data
    # (and the year) it was built from, so that two datasets never share one
    origin = data.paths["item"][0]
    in_year = N.BroadcastJoin(
        inventory, N.BroadcastExchange(year),
        [(col("inv_date_sk"), col("d_date_sk"))], N.JoinType.INNER,
        N.JoinSide.RIGHT, f"benchmark_q22_dates_{dms}:" + origin)
    named = N.BroadcastJoin(
        in_year, N.BroadcastExchange(item),
        [(col("inv_item_sk"), col("i_item_sk"))], N.JoinType.INNER,
        N.JoinSide.RIGHT, "benchmark_q22_items:" + origin)
    measure = "inv_quantity_on_hand"
    # the year leaves a fifth of a scan batch: the joined rows are coalesced
    # to batches again (the reference's ExecutionContext.coalesce) before
    # Expand makes five of each
    joined = N.CoalesceBatches(N.Projection(
        named, [col(c) for c in (*NAMES, measure)], [*NAMES, measure]),
        batch_size=0)  # the session's
    # Spark's ResolveGroupingAnalytics: grouping set `lvl` nulls the last
    # `lvl` keys, and spark_grouping_id has a bit a nulled key
    projections = [
        [col(name) if i < len(NAMES) - lvl else E.Literal(None, T.STRING)
         for i, name in enumerate(NAMES)]
        + [E.Literal((1 << lvl) - 1, T.I64), col(measure)]
        for lvl in range(len(NAMES) + 1)]
    expanded = N.Expand(joined, projections, T.Schema.of(
        *[(name, T.STRING) for name in NAMES],
        ("spark_grouping_id", T.I64), (measure, T.I64)))
    keys = [(name, col(name)) for name in (*NAMES, "spark_grouping_id")]
    agg = plans.two_stage_agg(
        expanded, keys, [("qoh", E.AggExpr(E.AggFunction.AVG, [col(measure)]))],
        data.shuffle_partitions)
    order = [E.SortOrder(col(c)) for c in ("qoh", *NAMES)]  # ASC NULLS FIRST
    top = N.Sort(N.ShuffleExchange(N.Sort(agg, order, fetch_limit=limit),
                                   N.SinglePartitioning(1)),
                 order, fetch_limit=limit)
    return N.Projection(top, [col(c) for c in ENGINE_COLUMNS],
                        list(ENGINE_COLUMNS))


def reference(tables, dms: int = 1200, limit: int = LIMIT) -> pa.Table:
    """Plain, over the same parquet files, by Acero alone: filter `date_dim`,
    two hash joins, one `group_by` a grouping set on the DECODED strings, the
    five results concatenated with typed NULLs, sorted (ASC NULLS FIRST,
    strings by their bytes), the first ``limit`` rows. AVG as Spark computes
    it for a long: the sum as a double over the count; the sums here stay
    under 2^53 (at most 1,000 a row over 11.7M rows), so every double is
    exact."""
    _require_coded_columns()
    dates = tables["date_dim"]
    in_year = dates.filter(pc.and_(
        pc.greater_equal(dates["d_month_seq"], dms),
        pc.less_equal(dates["d_month_seq"], dms + 11))).select(["d_date_sk"])
    joined = tables["inventory"].join(
        in_year, keys="inv_date_sk", right_keys="d_date_sk",
        join_type="inner").join(
        tables["item"].select(["i_item_sk", *NAMES]), keys="inv_item_sk",
        right_keys="i_item_sk", join_type="inner")
    measure = "inv_quantity_on_hand"
    sets = []
    for lvl in range(len(NAMES) + 1):
        keep = list(NAMES[:len(NAMES) - lvl])
        if keep:
            g = joined.group_by(keep, use_threads=False).aggregate(
                [(measure, "sum"), (measure, "count")])
            total, count = g[measure + "_sum"], g[measure + "_count"]
        else:
            g = pa.table({})
            total = pa.chunked_array([pa.array(
                [pc.sum(joined[measure]).as_py()], type=pa.int64())])
            count = pa.chunked_array([pa.array(
                [pc.count(joined[measure]).as_py()], type=pa.int64())])
        counted = pc.greater(count, 0)
        qoh = pc.if_else(counted, pc.divide(
            pc.cast(total, pa.float64()),
            pc.cast(pc.if_else(counted, count, 1), pa.float64())), None)
        columns = {name: (g[name].cast(pa.large_utf8()) if name in keep
                          else pa.nulls(len(qoh), pa.large_utf8()))
                   for name in NAMES}
        sets.append(pa.table({**columns, "qoh": qoh}))
    rollup = pa.concat_tables(sets)
    order = pc.sort_indices(rollup, sort_keys=[
        (c, "ascending", "at_start") for c in ("qoh", *NAMES)])
    return rollup.take(order if limit is None else order[:limit])
