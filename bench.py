"""The five BASELINE.json query shapes over a generated TPC-DS-like star
schema, as data, plans and two references (pandas, pyarrow Acero) for the
tests, ``chip_smoke.py`` and ``scripts/profile_query.py``:

  q01  scan -> decimal filter -> two-stage hash agg over an exchange -> top-k
  q06  group-by agg + broadcast hash join (BHJ)
  q17  star-schema multi-way join + shuffle exchange
  q47  sort + window rank within partition (SMJ/window class)
  q67  window rank over MANY tiny partitions (segmented-window class)

Nothing here is timed: the benchmark is ``benchmark/run.py``
(``BENCHMARK.json``), which has its own generator and copies of the plans.

Env knobs: BENCH_ROWS (default 1_000_000 fact rows), BENCH_PARTITIONS
(default 4).
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq

import blaze_tpu  # noqa: F401
from blaze_tpu.ir import exprs as E
from blaze_tpu.ir import nodes as N
from blaze_tpu.ir import types as T

ROWS = int(os.environ.get("BENCH_ROWS", 1_000_000))
PARTS = int(os.environ.get("BENCH_PARTITIONS", 4))
N_ITEMS = 2000
N_STORES = 400
N_CUSTOMERS = 100_000

F = E.AggFunction


# --------------------------------------------------------------------------
# data
# --------------------------------------------------------------------------


def _decimal_array(rng, n, lo, hi, prec=7, scale=2):
    """decimal128(prec, scale) with unscaled values uniform in [lo, hi),
    built straight from the int64 draw: each 16-byte little-endian value is
    the int64 and its sign extension (no python Decimal per row)."""
    limbs = np.empty((n, 2), dtype=np.int64)
    limbs[:, 0] = rng.integers(lo, hi, n)
    limbs[:, 1] = limbs[:, 0] >> 63
    return pa.Array.from_buffers(pa.decimal128(prec, scale), n,
                                 [None, pa.py_buffer(limbs)])


def make_data(tmpdir: str, rows=None, parts=None, seed=42):
    """Star schema: per-partition store_returns (q01) + store_sales fact,
    and item/store dims; ``rows`` fact rows over ``parts`` files each
    (default: the BENCH_ROWS / BENCH_PARTITIONS globals)."""
    rows = ROWS if rows is None else rows
    parts = PARTS if parts is None else parts
    rng = np.random.default_rng(seed)
    # the remainder of an uneven split goes one row each to the first files
    sizes = [rows // parts + (p < rows % parts) for p in range(parts)]
    paths = {"store_returns": [], "store_sales": []}
    for p, per in enumerate(sizes):
        amt = _decimal_array(rng, per, 0, 10_000_00)
        tbl = pa.table({
            "sr_store_sk": pa.array(rng.integers(1, N_STORES, per), type=pa.int64()),
            "sr_customer_sk": pa.array(rng.integers(1, N_CUSTOMERS, per), type=pa.int64()),
            "sr_return_amt": amt,
        })
        path = os.path.join(tmpdir, f"sr_{p}.parquet")
        pq.write_table(tbl, path, row_group_size=128 * 1024)
        paths["store_returns"].append(path)
    # separate stream for the wide-decimal column so the item/store dim
    # draws do not depend on it
    rng_wide = np.random.default_rng(seed * 10 + 1)
    for p, per in enumerate(sizes):
        tbl = pa.table({
            "ss_item_sk": pa.array(rng.integers(1, N_ITEMS, per), type=pa.int64()),
            "ss_store_sk": pa.array(rng.integers(1, N_STORES, per), type=pa.int64()),
            "ss_quantity": pa.array(rng.integers(1, 100, per), type=pa.int64()),
            "ss_sales_price": _decimal_array(rng, per, 0, 500_00),
            # decimal(38,2): per-group sums exceed int64, exercising the
            # three-limb device sum (q17's wcost aggregate)
            "ss_ext_wholesale_cost": _decimal_array(
                rng_wide, per, 10**14, 9 * 10**16, prec=38, scale=2),
        })
        path = os.path.join(tmpdir, f"ss_{p}.parquet")
        pq.write_table(tbl, path, row_group_size=128 * 1024)
        paths["store_sales"].append(path)
    cats = ["Books", "Home", "Electronics", "Music", "Sports", "Shoes",
            "Women", "Men", "Children", "Jewelry"]
    item = pa.table({
        "i_item_sk": pa.array(np.arange(1, N_ITEMS + 1), type=pa.int64()),
        "i_category_id": pa.array(rng.integers(0, len(cats), N_ITEMS), type=pa.int64()),
        "i_brand_id": pa.array(rng.integers(1, 60, N_ITEMS), type=pa.int64()),
        "i_current_price": _decimal_array(rng, N_ITEMS, 0, 300_00),
    })
    paths["item"] = [os.path.join(tmpdir, "item.parquet")]
    pq.write_table(item, paths["item"][0])
    store = pa.table({
        "s_store_sk": pa.array(np.arange(1, N_STORES + 1), type=pa.int64()),
        "s_state_id": pa.array(rng.integers(0, 50, N_STORES), type=pa.int64()),
    })
    paths["store"] = [os.path.join(tmpdir, "store.parquet")]
    pq.write_table(store, paths["store"][0])
    return paths


def _parts(paths) -> int:
    """Scan and shuffle partition count of a generated dataset: one per
    fact file."""
    return len(paths["store_sales"])


def _col(name):
    return E.Column(name)


def _two_stage_agg(child, keys, aggs, nparts):
    partial = N.Agg(child, E.AggExecMode.HASH_AGG, keys, [
        N.AggColumn(agg, E.AggMode.PARTIAL, name) for name, agg, _dt in aggs],
        supports_partial_skipping=True)
    ex = N.ShuffleExchange(partial, N.HashPartitioning(
        [e for _, e in keys], nparts))
    return N.Agg(ex, E.AggExecMode.HASH_AGG, keys, [
        N.AggColumn(agg, E.AggMode.FINAL, name) for name, agg, _dt in aggs])


# --------------------------------------------------------------------------
# shapes: (engine plan, pandas oracle, acero baseline, result check)
# --------------------------------------------------------------------------


def plan_q01(paths):
    from blaze_tpu.ops.parquet import scan_node_for_files

    scan = scan_node_for_files(paths["store_returns"], num_partitions=_parts(paths))
    filt = N.Filter(scan, [E.BinaryExpr(
        E.BinaryOp.GT, _col("sr_return_amt"),
        E.Literal("500.00", T.DecimalType(7, 2)))])
    agg = _two_stage_agg(filt, [("sr_store_sk", _col("sr_store_sk"))], [
        ("total", E.AggExpr(F.SUM, [_col("sr_return_amt")], T.DecimalType(17, 2)), None),
        ("cnt", E.AggExpr(F.COUNT, []), None),
    ], _parts(paths))
    single = N.ShuffleExchange(agg, N.SinglePartitioning(1))
    return N.Sort(single, [E.SortOrder(_col("total"), ascending=False)],
                  fetch_limit=100)


def pandas_q01(dfs):
    import decimal

    df = dfs["store_returns"]
    df = df[df.sr_return_amt > decimal.Decimal("500.00")]
    g = df.groupby("sr_store_sk").agg(total=("sr_return_amt", "sum"),
                                      cnt=("sr_store_sk", "size"))
    return g.sort_values("total", ascending=False).head(100)


def acero_q01(tables):
    import decimal

    import pyarrow.compute as pc

    tbl = tables["store_returns"]
    tbl = tbl.filter(pc.greater(tbl["sr_return_amt"],
                                pa.scalar(decimal.Decimal("500.00"))))
    g = tbl.group_by("sr_store_sk").aggregate(
        [("sr_return_amt", "sum"), ("sr_return_amt", "count")])
    return g.sort_by([("sr_return_amt_sum", "descending")]).slice(0, 100)


def plan_q06(paths):
    from blaze_tpu.ops.parquet import scan_node_for_files

    sales = scan_node_for_files(paths["store_sales"], num_partitions=_parts(paths))
    items = scan_node_for_files(paths["item"])
    join = N.BroadcastJoin(sales, N.BroadcastExchange(items),
                           [(_col("ss_item_sk"), _col("i_item_sk"))],
                           N.JoinType.INNER, N.JoinSide.RIGHT, "bench_items")
    agg = _two_stage_agg(join, [("i_category_id", _col("i_category_id"))], [
        ("qty", E.AggExpr(F.SUM, [_col("ss_quantity")]), None),
        ("revenue", E.AggExpr(F.SUM, [_col("ss_sales_price")], T.DecimalType(17, 2)), None),
    ], _parts(paths))
    return N.Sort(N.ShuffleExchange(agg, N.SinglePartitioning(1)),
                  [E.SortOrder(_col("i_category_id"))])


def pandas_q06(dfs):
    m = dfs["store_sales"].merge(dfs["item"], left_on="ss_item_sk",
                                 right_on="i_item_sk")
    return m.groupby("i_category_id").agg(
        qty=("ss_quantity", "sum"), revenue=("ss_sales_price", "sum")).sort_index()


def acero_q06(tables):
    joined = tables["store_sales"].join(
        tables["item"], keys="ss_item_sk", right_keys="i_item_sk")
    g = joined.group_by("i_category_id").aggregate(
        [("ss_quantity", "sum"), ("ss_sales_price", "sum")])
    return g.sort_by("i_category_id")


def plan_q17(paths):
    from blaze_tpu.ops.parquet import scan_node_for_files

    sales = scan_node_for_files(paths["store_sales"], num_partitions=_parts(paths))
    items = scan_node_for_files(paths["item"])
    stores = scan_node_for_files(paths["store"])
    j1 = N.BroadcastJoin(sales, N.BroadcastExchange(items),
                         [(_col("ss_item_sk"), _col("i_item_sk"))],
                         N.JoinType.INNER, N.JoinSide.RIGHT, "bench_items17")
    j2 = N.BroadcastJoin(j1, N.BroadcastExchange(stores),
                         [(_col("ss_store_sk"), _col("s_store_sk"))],
                         N.JoinType.INNER, N.JoinSide.RIGHT, "bench_stores17")
    agg = _two_stage_agg(j2, [("s_state_id", _col("s_state_id")),
                              ("i_category_id", _col("i_category_id"))], [
        ("n", E.AggExpr(F.COUNT, []), None),
        ("qty", E.AggExpr(F.SUM, [_col("ss_quantity")]), None),
        # wide-decimal SUM: three-int64-limb device states across the
        # exchange (round-2 verdict item 7)
        ("wcost", E.AggExpr(F.SUM, [_col("ss_ext_wholesale_cost")]), None),
    ], _parts(paths))
    return N.Sort(N.ShuffleExchange(agg, N.SinglePartitioning(1)),
                  [E.SortOrder(_col("s_state_id")),
                   E.SortOrder(_col("i_category_id"))])


def pandas_q17(dfs):
    m = dfs["store_sales"].merge(dfs["item"], left_on="ss_item_sk",
                                 right_on="i_item_sk")
    m = m.merge(dfs["store"], left_on="ss_store_sk", right_on="s_store_sk")
    return m.groupby(["s_state_id", "i_category_id"]).agg(
        n=("ss_item_sk", "size"), qty=("ss_quantity", "sum"),
        wcost=("ss_ext_wholesale_cost", "sum")).sort_index()


def acero_q17(tables):
    j = tables["store_sales"].join(tables["item"], keys="ss_item_sk",
                                   right_keys="i_item_sk")
    j = j.join(tables["store"], keys="ss_store_sk", right_keys="s_store_sk")
    g = j.group_by(["s_state_id", "i_category_id"]).aggregate(
        [("ss_item_sk", "count"), ("ss_quantity", "sum"),
         ("ss_ext_wholesale_cost", "sum")])
    return g.sort_by([("s_state_id", "ascending"),
                      ("i_category_id", "ascending")])


def plan_q47(paths):
    from blaze_tpu.ops.parquet import scan_node_for_files

    sales = scan_node_for_files(paths["store_sales"], num_partitions=_parts(paths))
    items = scan_node_for_files(paths["item"])
    join = N.BroadcastJoin(sales, N.BroadcastExchange(items),
                           [(_col("ss_item_sk"), _col("i_item_sk"))],
                           N.JoinType.INNER, N.JoinSide.RIGHT, "bench_items47")
    agg = _two_stage_agg(join, [("i_category_id", _col("i_category_id")),
                                ("i_brand_id", _col("i_brand_id"))], [
        ("qty", E.AggExpr(F.SUM, [_col("ss_quantity")]), None),
    ], _parts(paths))
    single = N.ShuffleExchange(agg, N.SinglePartitioning(1))
    srt = N.Sort(single, [E.SortOrder(_col("i_category_id")),
                          E.SortOrder(_col("qty"), ascending=False)])
    win = N.Window(srt, [N.WindowExpr("rank", "rk")],
                   [_col("i_category_id")],
                   [E.SortOrder(_col("qty"), ascending=False)])
    return N.Filter(win, [E.BinaryExpr(E.BinaryOp.LTEQ, _col("rk"),
                                       E.Literal(5, T.I32))])


def pandas_q47(dfs):
    m = dfs["store_sales"].merge(dfs["item"], left_on="ss_item_sk",
                                 right_on="i_item_sk")
    g = m.groupby(["i_category_id", "i_brand_id"]).ss_quantity.sum().reset_index()
    g["rk"] = g.groupby("i_category_id").ss_quantity.rank(
        method="min", ascending=False)
    return g[g.rk <= 5].sort_values(
        ["i_category_id", "ss_quantity", "i_brand_id"],
        ascending=[True, False, True])


def acero_q47(tables):
    j = tables["store_sales"].join(tables["item"], keys="ss_item_sk",
                                   right_keys="i_item_sk")
    g = j.group_by(["i_category_id", "i_brand_id"]).aggregate(
        [("ss_quantity", "sum")])
    # acero has no window operator: rank the (tiny) agg output in numpy,
    # mirroring what a window-less engine would bolt on
    cat = np.asarray(g["i_category_id"])
    qty = np.asarray(g["ss_quantity_sum"])
    order = np.lexsort((-qty, cat))
    cat_s, qty_s = cat[order], qty[order]
    new_cat = np.concatenate([[True], cat_s[1:] != cat_s[:-1]])
    grp_start = np.maximum.accumulate(np.where(new_cat, np.arange(len(cat_s)), 0))
    new_val = np.concatenate([[True], (qty_s[1:] != qty_s[:-1]) | new_cat[1:]])
    val_start = np.maximum.accumulate(np.where(new_val, np.arange(len(cat_s)), 0))
    rk = val_start - grp_start + 1
    return g.take(order[rk <= 5])


def plan_q67(paths):
    """q67-style window over MANY tiny partitions: top-3 stores per item by
    quantity over the (item, store) agg — the shape the segmented window
    path exists for (hundreds of thousands of window segments; the buffered
    per-group loop paid one python iteration + device dispatch per group)."""
    from blaze_tpu.ops.parquet import scan_node_for_files

    sales = scan_node_for_files(paths["store_sales"], num_partitions=_parts(paths))
    agg = _two_stage_agg(sales, [("ss_item_sk", _col("ss_item_sk")),
                                 ("ss_store_sk", _col("ss_store_sk"))], [
        ("qty", E.AggExpr(F.SUM, [_col("ss_quantity")]), None),
    ], _parts(paths))
    single = N.ShuffleExchange(agg, N.SinglePartitioning(1))
    srt = N.Sort(single, [E.SortOrder(_col("ss_item_sk")),
                          E.SortOrder(_col("qty"), ascending=False)])
    win = N.Window(srt, [N.WindowExpr("rank", "rk")],
                   [_col("ss_item_sk")],
                   [E.SortOrder(_col("qty"), ascending=False)])
    return N.Filter(win, [E.BinaryExpr(E.BinaryOp.LTEQ, _col("rk"),
                                       E.Literal(3, T.I32))])


def pandas_q67(dfs):
    g = dfs["store_sales"].groupby(
        ["ss_item_sk", "ss_store_sk"]).ss_quantity.sum().reset_index()
    g["rk"] = g.groupby("ss_item_sk").ss_quantity.rank(
        method="min", ascending=False)
    return g[g.rk <= 3]


def acero_q67(tables):
    g = tables["store_sales"].group_by(["ss_item_sk", "ss_store_sk"]).aggregate(
        [("ss_quantity", "sum")])
    # acero has no window operator: numpy rank over the agg output (same
    # bolt-on as acero_q47, here over ~N_ITEMS*N_STORES groups)
    key = np.asarray(g["ss_item_sk"])
    qty = np.asarray(g["ss_quantity_sum"])
    order = np.lexsort((-qty, key))
    key_s, qty_s = key[order], qty[order]
    new_key = np.concatenate([[True], key_s[1:] != key_s[:-1]])
    grp_start = np.maximum.accumulate(np.where(new_key, np.arange(len(key_s)), 0))
    new_val = np.concatenate([[True], (qty_s[1:] != qty_s[:-1]) | new_key[1:]])
    val_start = np.maximum.accumulate(np.where(new_val, np.arange(len(key_s)), 0))
    rk = val_start - grp_start + 1
    return g.take(order[rk <= 3])


# What the engine, pandas and Acero must agree on, per shape: the columns
# of each source in one order, and whether row order is part of the answer.
# It is wherever the plan ends in a Sort whose keys make the order total
# (q01's top-100 by total; q06 and q17 by their unique group keys), so the
# final Sort is checked too. q47 and q67 end in a rank filter whose ties
# leave the order open: those are compared as sets.
_CANON = {
    "q01": (True,
            ("sr_store_sk", "total", "cnt"),
            ("sr_store_sk", "total", "cnt"),
            ("sr_store_sk", "sr_return_amt_sum", "sr_return_amt_count")),
    "q06": (True,
            ("i_category_id", "qty", "revenue"),
            ("i_category_id", "qty", "revenue"),
            ("i_category_id", "ss_quantity_sum", "ss_sales_price_sum")),
    "q17": (True,
            ("s_state_id", "i_category_id", "n", "qty", "wcost"),
            ("s_state_id", "i_category_id", "n", "qty", "wcost"),
            ("s_state_id", "i_category_id", "ss_item_sk_count",
             "ss_quantity_sum", "ss_ext_wholesale_cost_sum")),
    "q47": (False,
            ("i_category_id", "i_brand_id", "qty"),
            ("i_category_id", "i_brand_id", "ss_quantity"),
            ("i_category_id", "i_brand_id", "ss_quantity_sum")),
    "q67": (False,
            ("ss_item_sk", "ss_store_sk", "qty"),
            ("ss_item_sk", "ss_store_sk", "ss_quantity"),
            ("ss_item_sk", "ss_store_sk", "ss_quantity_sum")),
}


def canon_rows(name: str, result, source: str):
    """Rows of one shape's answer as comparable tuples. ``source`` says
    whose column names ``result`` carries: "engine" (the plan's output
    table), "pandas" (the pandas_* frame) or "acero" (the acero_* table)."""
    ordered, *cols = _CANON[name]
    cols = cols[("engine", "pandas", "acero").index(source)]
    if source == "pandas":
        frame = result.reset_index()
        data = {c: frame[c].tolist() for c in cols}
    else:
        data = result.select(list(cols)).to_pydict()
    rows = list(zip(*(data[c] for c in cols)))
    return rows if ordered else sorted(rows)


def _check(name: str):
    """The correctness gate of one shape: engine table vs pandas oracle."""
    def check(out, oracle):
        assert canon_rows(name, out, "engine") == \
            canon_rows(name, oracle, "pandas"), \
            f"{name}: engine answer differs from the pandas oracle"
    return check


SHAPES = [
    # (name, plan, pandas oracle, acero reference, check, tables the query
    #  touches — the acero reference reads exactly these, as the engine does)
    ("q01", plan_q01, pandas_q01, acero_q01, _check("q01"), ("store_returns",)),
    ("q06", plan_q06, pandas_q06, acero_q06, _check("q06"),
     ("store_sales", "item")),
    ("q17", plan_q17, pandas_q17, acero_q17, _check("q17"),
     ("store_sales", "item", "store")),
    ("q47", plan_q47, pandas_q47, acero_q47, _check("q47"),
     ("store_sales", "item")),
    ("q67", plan_q67, pandas_q67, acero_q67, _check("q67"), ("store_sales",)),
]


def load_tables(paths, names):
    return {n: pa.concat_tables([pq.read_table(p) for p in paths[n]])
            for n in names}


def load_dfs(paths):
    return {name: tbl.to_pandas()
            for name, tbl in load_tables(paths, paths).items()}
