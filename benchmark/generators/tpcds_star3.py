"""Three fact tables of the TPC-DS star, for q17: the store channel of
`tpcds_star_dated` (its `store_sales`, byte for byte, and its `store_returns`
with the day of the return), the catalog channel's `catalog_sales`, and the
dimensions the query names: `date_dim` with `d_quarter_name`, `store` with
`s_state`, `item` with `i_item_id` and `i_item_desc`. The row counts are
Table 3-2's, read from `tpcds_star3_rows/sf<scale_factor>.json` beside this
file.

The generator is ours, not dsdgen. What it keeps of dsdgen's tables:

- `store_sales` and `store_returns` are `tpcds_star`'s (tickets of 8 to 16
  lines, one store, customer and day a ticket; returns a sample of the sales'
  line items); a return comes 1 to 60 days after its sale
  (`sr_returned_date_sk`);
- `catalog_sales` comes in orders of 4 to 14 lines with one bill customer and
  one day, its items consecutive entries of one permutation of `item` (no item
  twice in an order), quantities 1 to 100, days uniform over the sales' 1,823;
- `item` keeps history as `tpcds_inventory` has it (9,000 item ids in 18,000
  rows, one, two and three revisions in turn); `i_item_id` is dsdgen's
  sixteen-letter business key; `i_item_desc` a sentence of 6 to 20 words, at
  most 200 characters (varchar(200)), drawn for every revision;
- `store.s_state` names `tpcds_star`'s state draw by the fifty states' postal
  codes; `date_dim` is `tpcds_inventory`'s calendar from 1900-01-02 with
  `d_quarter_name` ('2001Q1').

What it sets itself is listed under `assumed` in the configuration's file,
first the planted repurchases: q17 reports items bought in a store, returned,
and bought again by the same customer from the catalog, and independent
channels over 100,000 customers and 18,000 items leave about two such rows in
its quarters. So one store return in four (`REPURCHASE_EVERY`) gets a catalog
line of its customer and item 1 to 180 days after the return, in place of a
drawn line: `catalog_sales` keeps its count.

Under a ``generator_params.table_rows`` cut (a test's star), the rows it does
not give follow from the others (`catalog_sales` in Table 3-2's proportion to
`store_sales`, `date_dim` the calendar) and every sale of either channel falls
in 2001Q1, the quarter q17's store sales are read from, so that a few
thousand rows still reach the joins. Seeded and vectorised: the same seed and
configuration give the same tables, whichever of them are written."""

from __future__ import annotations

import json
import os
from typing import Dict, List, Sequence

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq

from generators import tpcds_inventory, tpcds_star, tpcds_star_dated

TABLES = ("store_sales", "store_returns", "catalog_sales", "date_dim", "store",
          "item")
HERE = os.path.dirname(os.path.realpath(__file__))

RETURN_LAG_DAYS = 60        # a return comes 1..60 days after its sale
REPURCHASE_EVERY = 4        # one store return in four is bought again ...
REPURCHASE_LAG_DAYS = 180   # ... from the catalog 1..180 days after it
ORDER_LINES = (4, 14)       # a catalog order's lines
QUANTITY = (1, 100)
Q1_2001_DATE_SK = 2451911   # date_dim's surrogate key of 2001-01-01
CUT_SALE_DAYS = 90          # under a cut: every sale in 2001Q1
DESC_WORDS = (6, 20)
DESC_CHARS = 200
# the fifty states' postal codes, in the order of `tpcds_star`'s draw 0..49
STATES = ("AL", "AK", "AZ", "AR", "CA", "CO", "CT", "DE", "FL", "GA", "HI",
          "ID", "IL", "IN", "IA", "KS", "KY", "LA", "ME", "MD", "MA", "MI",
          "MN", "MS", "MO", "MT", "NE", "NV", "NH", "NJ", "NM", "NY", "NC",
          "ND", "OH", "OK", "OR", "PA", "RI", "SC", "SD", "TN", "TX", "UT",
          "VT", "VA", "WA", "WV", "WI", "WY")
# i_item_desc's vocabulary (ours)
WORDS = ("able", "about", "above", "across", "actual", "after", "again",
         "almost", "always", "among", "ancient", "annual", "areas", "around",
         "available", "back", "basic", "become", "before", "best", "better",
         "black", "blue", "brown", "careful", "central", "certain", "clear",
         "close", "cold", "common", "complete", "crucial", "current", "dark",
         "deep", "different", "direct", "early", "easy", "economic", "entire",
         "equal", "even", "exact", "extra", "fair", "famous", "final", "fine",
         "firm", "free", "fresh", "full", "general", "glad", "good", "great",
         "green", "happy", "heavy", "high", "human", "important", "large",
         "late", "light", "little", "local", "long", "major", "modern", "much",
         "national", "natural", "necessary", "new", "next", "normal", "old",
         "only", "open", "other", "particular", "past", "personal", "plain",
         "political", "poor", "possible", "present", "private", "public",
         "quick", "real", "recent", "red", "rich", "right", "round", "sharp",
         "short", "simple", "single", "small", "social", "special", "strong",
         "sure", "tall", "true", "usual", "various", "warm", "whole", "wide",
         "young")


class GeneratorError(Exception):
    pass


def table_rows(scale_factor) -> Dict[str, int]:
    path = os.path.join(HERE, "tpcds_star3_rows", f"sf{scale_factor}.json")
    if not os.path.isfile(path):
        raise GeneratorError(
            f"scale_factor {scale_factor!r}: no row counts at {path}")
    with open(path) as f:
        return json.load(f)


def shape(config: dict) -> Dict[str, int]:
    """The tables' rows as this configuration generates them, and the days
    the sales span (``sale_days``) from ``first_sale_date_sk``."""
    params = config["generator_params"]
    cut = params.get("table_rows")
    if not cut:
        rows = table_rows(config["scale_factor"])
        store = tpcds_star.table_rows(config["scale_factor"])
        differ = sorted(k for k in store if k in rows and store[k] != rows[k]
                        and k not in ("source",))
        if differ:
            raise GeneratorError(f"{differ}: tpcds_star's row counts differ")
        return dict(rows, sale_days=tpcds_star_dated.SALE_DAYS,
                    first_sale_date_sk=tpcds_star_dated.FIRST_SALE_DATE_SK)
    rows = dict(cut)
    full = table_rows(1)
    rows.setdefault("catalog_sales", round(
        rows["store_sales"] * full["catalog_sales"] / full["store_sales"]))
    rows.setdefault("date_dim", full["date_dim"])
    return dict(rows, sale_days=CUT_SALE_DAYS,
                first_sale_date_sk=Q1_2001_DATE_SK)


def sale_dates(seed: int, tickets: int, sizes: Dict[str, int]) -> np.ndarray:
    """The day of store tickets 1 .. ``tickets``: `tpcds_star_dated`'s, or
    under a cut the same draw folded into the cut's days."""
    dates = tpcds_star_dated.ticket_dates(seed, tickets)
    if sizes["first_sale_date_sk"] == tpcds_star_dated.FIRST_SALE_DATE_SK:
        return dates
    return sizes["first_sale_date_sk"] + \
        (dates - tpcds_star_dated.FIRST_SALE_DATE_SK) % sizes["sale_days"]


def item_ids(keys: np.ndarray) -> List[str]:
    """dsdgen's sixteen-letter business key of an item id: eight letters A,
    then the id's eight hex digits as the letters A..P, least significant
    first."""
    out = []
    for k in keys.tolist():
        out.append("AAAAAAAA" + "".join(chr(65 + ((k >> (4 * i)) & 15))
                                        for i in range(8)))
    return out


def descriptions(rows: int, rng) -> List[str]:
    counts = rng.integers(DESC_WORDS[0], DESC_WORDS[1] + 1, rows)
    picks = rng.integers(0, len(WORDS), int(counts.sum()))
    out, at = [], 0
    for c in counts.tolist():
        text = " ".join(WORDS[w] for w in picks[at:at + c].tolist())
        at += c
        out.append(text[:DESC_CHARS].rsplit(" ", 1)[0]
                   if len(text) > DESC_CHARS else text)
    return out


def item_table(items: int, rng) -> pa.Table:
    first, count = tpcds_inventory.revisions(items)
    business = np.repeat(np.arange(1, len(first) + 1, dtype=np.int64), count)
    return pa.table({
        "i_item_sk": pa.array(np.arange(1, items + 1), pa.int64()),
        "i_item_id": pa.array(item_ids(business), pa.string()),
        "i_item_desc": pa.array(descriptions(items, rng), pa.string()),
    })


def date_dim_table(days: int) -> pa.Table:
    dates = tpcds_inventory.FIRST_DATE + np.arange(days)
    year = dates.astype("datetime64[Y]").astype(np.int64) + 1970
    quarter = dates.astype("datetime64[M]").astype(np.int64) % 12 // 3 + 1
    names = np.char.add(np.char.add(year.astype(str), "Q"), quarter.astype(str))
    return pa.table({
        "d_date_sk": pa.array(tpcds_inventory.FIRST_DATE_SK +
                              np.arange(days, dtype=np.int64), pa.int64()),
        "d_quarter_name": pa.array(names.tolist(), pa.string()),
    })


def _with_column(path: str, name: str, values: np.ndarray, row_group: int):
    table = pq.read_table(path)
    pq.write_table(table.append_column(name, pa.array(values, pa.int64())),
                   path, row_group_size=row_group)


def generate(directory: str, seed: int, config: dict,
             tables: Sequence[str] = TABLES) -> Dict[str, List[str]]:
    """Writes those of ``tables`` under ``directory``; returns table name ->
    files. Reads from ``config``: ``scale_factor`` and, under
    ``generator_params``, ``fact_files``, ``row_group_rows`` and optionally
    ``table_rows`` (see the module's text)."""
    unknown = sorted(set(tables) - set(TABLES))
    if unknown:
        raise GeneratorError(f"tables {unknown} are not among {list(TABLES)}")
    params = config["generator_params"]
    sizes = shape(config)
    files, row_group = params["fact_files"], params["row_group_rows"]
    # a stream a table beside the store channel's, so that the tables
    # written do not shift one another
    lag_rng, cat_rng, item_rng = (np.random.default_rng([stream, seed])
                                  for stream in (171, 172, 173))
    store_tables = sorted({"store_sales", "store_returns", "store"}
                          & (set(tables) | ({"store_returns"}
                                            if "catalog_sales" in tables
                                            else set())))
    paths = tpcds_star_dated.generate(directory, seed, config, store_tables)
    tickets = sizes["store_sales"] // 8 + 1
    days = sale_dates(seed, tickets, sizes)
    if "store_sales" in paths and \
            sizes["first_sale_date_sk"] != tpcds_star_dated.FIRST_SALE_DATE_SK:
        for path in paths["store_sales"]:
            table = pq.read_table(path)
            ticket = table["ss_ticket_number"].to_numpy()
            table = table.set_column(
                table.schema.get_field_index("ss_sold_date_sk"),
                "ss_sold_date_sk", pa.array(days[ticket - 1], pa.int64()))
            pq.write_table(table, path, row_group_size=row_group)
    returns = None
    if "store_returns" in paths:
        for path in paths["store_returns"]:
            ticket = pq.read_table(path, columns=["sr_ticket_number"])[
                "sr_ticket_number"].to_numpy()
            returned = days[ticket - 1] + \
                lag_rng.integers(1, RETURN_LAG_DAYS + 1, len(ticket))
            _with_column(path, "sr_returned_date_sk", returned, row_group)
        returns = pa.concat_tables([pq.read_table(p, columns=[
            "sr_customer_sk", "sr_item_sk", "sr_returned_date_sk"])
            for p in paths["store_returns"]])
    if "store" in paths:
        (path,) = paths["store"]
        state = pq.read_table(path)["s_state_id"].to_numpy()
        pq.write_table(pa.table({
            "s_store_sk": pa.array(np.arange(1, len(state) + 1), pa.int64()),
            "s_state": pa.array([STATES[s] for s in state.tolist()],
                                pa.string()),
        }), path, row_group_size=row_group)
    if "item" in tables:
        paths["item"] = tpcds_star._write(
            directory, "item", item_table(sizes["item"], item_rng), 1, row_group)
    if "date_dim" in tables:
        paths["date_dim"] = tpcds_star._write(
            directory, "date_dim", date_dim_table(sizes["date_dim"]), 1,
            row_group)
    if "catalog_sales" in tables:
        paths["catalog_sales"] = tpcds_star._write(
            directory, "cs", _catalog_sales(sizes, returns, cat_rng), files,
            row_group)
    return {t: paths[t] for t in tables}


def _catalog_sales(sizes: Dict[str, int], returns: pa.Table, rng) -> pa.Table:
    """Drawn orders, then the planted repurchases as orders of one line."""
    n = sizes["catalog_sales"]
    planted = np.sort(rng.choice(returns.num_rows,
                                 returns.num_rows // REPURCHASE_EVERY,
                                 replace=False))
    drawn = n - len(planted)
    if drawn < 0:
        raise GeneratorError("more planted repurchases than catalog_sales rows")
    lo, hi = ORDER_LINES
    lines = rng.integers(lo, hi + 1, drawn // lo + 1)
    order = np.repeat(np.arange(len(lines), dtype=np.int64), lines)[:drawn]
    line = np.arange(drawn, dtype=np.int64) - \
        np.repeat(np.cumsum(lines) - lines, lines)[:drawn]
    customer = rng.integers(1, sizes["customer"] + 1, len(lines))[order]
    day = (sizes["first_sale_date_sk"] +
           rng.integers(0, sizes["sale_days"], len(lines)))[order]
    permutation = rng.permutation(sizes["item"]).astype(np.int64) + 1
    item = permutation[(rng.integers(0, sizes["item"], len(lines))[order]
                        + line) % sizes["item"]]
    again = returns["sr_returned_date_sk"].to_numpy()[planted] + \
        rng.integers(1, REPURCHASE_LAG_DAYS + 1, len(planted))
    quantity = rng.integers(QUANTITY[0], QUANTITY[1] + 1, n)
    return pa.table({
        "cs_sold_date_sk": pa.array(np.concatenate([day, again]), pa.int64()),
        "cs_bill_customer_sk": pa.array(np.concatenate([
            customer, returns["sr_customer_sk"].to_numpy()[planted]]),
            pa.int64()),
        "cs_item_sk": pa.array(np.concatenate([
            item, returns["sr_item_sk"].to_numpy()[planted]]), pa.int64()),
        "cs_quantity": pa.array(quantity, pa.int64()),
    })
