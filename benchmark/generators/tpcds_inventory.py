"""The inventory star of TPC-DS at a scale factor of the specification's
Table 3-2: `inventory`, `item` with its four names, `date_dim`, with the
table's row counts (read from `tpcds_inventory_rows/sf<scale_factor>.json`
beside this file; a further scale factor is a further file there).

The generator is ours, not dsdgen. What it keeps of dsdgen, as far as the
builder knows it:

- `inventory` is one row a weekly snapshot, item id and warehouse: 261
  Thursdays from 1998-01-01 x the item ids x the warehouses, in that order
  (11,745,000 = 261 x 9,000 x 5 at SF=1: `generate` checks the product
  against the table's count); `inv_quantity_on_hand` 0 to 1,000;
- `item` keeps history: 18,000 rows are 9,000 item ids with one, two and
  three revisions in turn (as `tpcds_star.first_revisions`), and a snapshot
  names the revision in force on its date (a key's revisions split the 261
  weeks evenly), so every surrogate key is referenced;
- `i_product_name` is the surrogate key spelled in dsdgen's ten syllables,
  least significant digit first, so it is unique an item; `i_category` is
  one of the specification's ten, `i_class` one of its category's classes,
  `i_brand` one of its class's brands ("<corporation> #<n>"); the names are
  char(50) in the specification and stored trimmed, as Spark reads them;
- `date_dim` is 73,049 days from 1900-01-02 (`d_date_sk` 2415022) with
  `d_month_seq` the months since January 1900, so 1200..1211 is the year
  2000, one year of the five.

What it sets itself is listed under `assumed` in the configuration's file:
the NULL rates, the uniform draws, the class and brand vocabularies.
Seeded and vectorised: the same seed and configuration give the same tables,
whichever of them are written."""

from __future__ import annotations

import json
import os
from typing import Dict, List, Sequence

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq

TABLES = ("inventory", "item", "date_dim")
HERE = os.path.dirname(os.path.realpath(__file__))

SNAPSHOT_WEEKS = 261               # Thursdays 1998-01-01 .. 2002-12-26
FIRST_DATE_SK = 2415022            # date_dim's surrogate key of 1900-01-02
FIRST_DATE = np.datetime64("1900-01-02")
FIRST_SNAPSHOT = np.datetime64("1998-01-01")
SYLLABLES = ("bar", "ought", "able", "pri", "pres", "ese", "anti", "cally",
             "ation", "eing")
CATEGORIES = ("Women", "Men", "Children", "Shoes", "Music", "Jewelry", "Home",
              "Sports", "Books", "Electronics")
# classes within a category, brands within a class: the vocabularies are ours
CLASS_WORDS = ("dresses", "pants", "shirts", "accessories", "athletic",
               "classical", "rock", "pop", "country", "bracelets", "rings",
               "earings", "pendants", "furniture", "lighting", "bedding",
               "rugs", "camping", "fishing", "golf", "fiction", "history",
               "science", "travel", "audio", "cameras", "stereo",
               "televisions", "infants", "toddlers", "newborn", "mens")
CLASSES_PER_CATEGORY = 16
CORPORATIONS = ("amalg", "importo", "edu pack", "exporti", "scholar", "univ",
                "corp", "brand", "maxi", "nameless")
BRANDS_PER_CLASS = 10
NAME_NULL_RATE = 0.005             # each of the four names, independently
QUANTITY_NULL_RATE = 0.01


class GeneratorError(Exception):
    pass


def table_rows(scale_factor) -> Dict[str, int]:
    path = os.path.join(HERE, "tpcds_inventory_rows", f"sf{scale_factor}.json")
    if not os.path.isfile(path):
        raise GeneratorError(
            f"scale_factor {scale_factor!r}: no row counts at {path}")
    with open(path) as f:
        return json.load(f)


def shape(config: dict) -> Dict[str, int]:
    """Snapshot weeks, item ids, warehouses and the tables' rows as this
    configuration generates them. Table 3-2's rows are checked against the
    product that forms `inventory`; ``generator_params.table_rows`` (a test's
    cut-down star) gives `item` and may give `inventory_weeks`, `warehouse`
    and `date_dim`, and the rest follows."""
    params = config["generator_params"]
    cut = params.get("table_rows")
    rows = dict(cut) if cut else table_rows(config["scale_factor"])
    weeks = rows.get("inventory_weeks", SNAPSHOT_WEEKS)
    warehouses = rows.get("warehouse", 5)
    ids = _item_ids(rows["item"])
    inventory = weeks * ids * warehouses
    if not cut and inventory != rows["inventory"]:
        raise GeneratorError(
            f"{weeks} weeks x {ids} item ids x {warehouses} warehouses = "
            f"{inventory} rows, Table 3-2 has {rows['inventory']}")
    return {"weeks": weeks, "item_ids": ids, "warehouses": warehouses,
            "inventory": inventory, "item": rows["item"],
            "date_dim": rows.get("date_dim", 73049)}


def _item_ids(items: int) -> int:
    """Business keys of an `item` table of ``items`` rows whose keys have
    one, two and three revisions in turn."""
    full, rest = divmod(items, 6)
    return 3 * full + (rest >= 1) + (rest >= 2) + (rest >= 4)  # starts 0, 1, 3


def revisions(items: int):
    """(first surrogate key, number of revisions) of every item id, keys
    from 1; the last id's revisions are cut to the table's end."""
    ids = _item_ids(items)
    count = np.tile(np.array([1, 2, 3], dtype=np.int64), ids // 3 + 1)[:ids]
    first = 1 + np.concatenate([[0], np.cumsum(count)[:-1]])
    count = np.minimum(count, items + 1 - first)
    return first, count


def product_names(keys: np.ndarray) -> List[str]:
    """dsdgen's mk_word over its ten syllables: the key's decimal digits,
    least significant first."""
    out = []
    for k in keys.tolist():
        word = ""
        while k > 0:
            word += SYLLABLES[k % 10]
            k //= 10
        out.append(word)
    return out


def _with_nulls(values: List[str], rng, rate: float) -> pa.Array:
    mask = rng.random(len(values)) < rate
    return pa.array(values, type=pa.string(), mask=mask)


def _int64(values: np.ndarray, mask=None) -> pa.Array:
    return pa.array(values, type=pa.int64(), mask=mask)


def _write(directory, stem, table: pa.Table, files: int, row_group_rows: int):
    n, paths, at = table.num_rows, [], 0
    for p in range(files):
        per = n // files + (p < n % files)
        path = os.path.join(directory, f"{stem}_{p}.parquet")
        pq.write_table(table.slice(at, per), path, row_group_size=row_group_rows)
        paths.append(path)
        at += per
    return paths


def item_table(items: int, rng) -> pa.Table:
    keys = np.arange(1, items + 1, dtype=np.int64)
    category = rng.integers(0, len(CATEGORIES), items)
    klass = rng.integers(0, CLASSES_PER_CATEGORY, items)
    brand = rng.integers(0, BRANDS_PER_CLASS, items)
    class_names = [CLASS_WORDS[(3 * c + k) % len(CLASS_WORDS)]
                   for c, k in zip(category.tolist(), klass.tolist())]
    brand_names = [f"{CORPORATIONS[k % len(CORPORATIONS)]}"
                   f"{CORPORATIONS[c]} #{b + 1}"
                   for c, k, b in zip(category.tolist(), klass.tolist(),
                                      brand.tolist())]
    return pa.table({
        "i_item_sk": _int64(keys),
        "i_product_name": _with_nulls(product_names(keys), rng, NAME_NULL_RATE),
        "i_brand": _with_nulls(brand_names, rng, NAME_NULL_RATE),
        "i_class": _with_nulls(class_names, rng, NAME_NULL_RATE),
        "i_category": _with_nulls([CATEGORIES[c] for c in category.tolist()],
                                  rng, NAME_NULL_RATE),
    })


def date_dim_table(days: int) -> pa.Table:
    dates = FIRST_DATE + np.arange(days)
    months = dates.astype("datetime64[M]").astype(np.int64)  # since 1970-01
    return pa.table({
        "d_date_sk": _int64(FIRST_DATE_SK + np.arange(days, dtype=np.int64)),
        "d_month_seq": _int64(months + 70 * 12),             # since 1900-01
    })


def generate(directory: str, seed: int, config: dict,
             tables: Sequence[str] = TABLES) -> Dict[str, List[str]]:
    """Writes those of ``tables`` under ``directory``; returns table name ->
    files. Reads from ``config``: ``scale_factor`` and, under
    ``generator_params``, ``fact_files``, ``row_group_rows`` and optionally
    ``table_rows`` (see :func:`shape`)."""
    unknown = sorted(set(tables) - set(TABLES))
    if unknown:
        raise GeneratorError(f"tables {unknown} are not among {list(TABLES)}")
    params = config["generator_params"]
    sizes = shape(config)
    # a stream a table, so that the tables written do not shift one another
    item_rng, inv_rng = (np.random.default_rng([stream, seed])
                         for stream in (22, 23))
    files, row_group = params["fact_files"], params["row_group_rows"]
    paths: Dict[str, List[str]] = {}
    if "item" in tables:
        paths["item"] = _write(directory, "item",
                               item_table(sizes["item"], item_rng), 1, row_group)
    if "date_dim" in tables:
        paths["date_dim"] = _write(directory, "date_dim",
                                   date_dim_table(sizes["date_dim"]), 1, row_group)
    if "inventory" in tables:
        weeks, ids, warehouses = (sizes[k] for k in
                                  ("weeks", "item_ids", "warehouses"))
        first, count = revisions(sizes["item"])
        snapshot = (FIRST_SNAPSHOT - FIRST_DATE).astype(np.int64) + \
            FIRST_DATE_SK + 7 * np.arange(weeks, dtype=np.int64)
        # the revision in force in week w of an id with r revisions
        in_force = first[None, :] + \
            (np.arange(weeks, dtype=np.int64)[:, None] * count[None, :]) // weeks
        n = sizes["inventory"]
        quantity = inv_rng.integers(0, 1001, n)
        paths["inventory"] = _write(directory, "inv", pa.table({
            "inv_date_sk": _int64(np.repeat(snapshot, ids * warehouses)),
            "inv_item_sk": _int64(np.repeat(in_force.reshape(-1), warehouses)),
            "inv_quantity_on_hand": _int64(
                quantity, inv_rng.random(n) < QUANTITY_NULL_RATE),
        }), files, row_group)
    return paths
