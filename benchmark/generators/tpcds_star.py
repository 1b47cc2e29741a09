"""The store channel of the TPC-DS star at a scale factor of the
specification's Table 3-2: `store_sales`, `store_returns`, `item`, `store`,
with the table's row counts (read from `tpcds_rows/sf<scale_factor>.json`
beside this file; a further scale factor is a further file there).

The generator is ours, not dsdgen. What it keeps of dsdgen's store channel:

- `store_sales` comes in tickets of 8 to 16 line items; a ticket has one
  store and one customer, and its items are consecutive entries of one
  permutation of `item`, so no item repeats in a ticket;
- `store` and `item` keep history (one, two, three revisions per business
  key, in turn); sales name only the first revision of a store's business
  key, so half of the store keys are ever referenced (six of twelve at
  SF=1), and any revision of an item;
- `store_returns` is a sample of `store_sales`' line items (Table 3-2 gives
  a tenth of them): a return has its sale's store, customer, item and
  ticket, a quantity of 1 up to the quantity sold, and that many times the
  sales price as `sr_return_amt`;
- quantity 1 to 100, wholesale cost 1.00 to 100.00, list price a mark-up of
  0 to 200% on it, sales price a discount of 0 to 100% off that: every
  amount fits the specification's decimal(7,2).

What it leaves out is listed under `assumed` in the configuration files.
Seeded and vectorised: the same seed and configuration give the same tables,
whichever of them are written."""

from __future__ import annotations

import json
import os
from typing import Dict, List, Sequence

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq

CATEGORIES = 10  # the specification's ten item categories
TABLES = ("store_sales", "store_returns", "item", "store")
HERE = os.path.dirname(os.path.realpath(__file__))


class GeneratorError(Exception):
    pass


def table_rows(scale_factor) -> Dict[str, int]:
    path = os.path.join(HERE, "tpcds_rows", f"sf{scale_factor}.json")
    if not os.path.isfile(path):
        raise GeneratorError(
            f"scale_factor {scale_factor!r}: no row counts at {path}")
    with open(path) as f:
        return json.load(f)


def _decimal_7_2(cents: np.ndarray) -> pa.Array:
    """decimal128(7, 2) straight from unscaled int64 values: each 16-byte
    little-endian value is the int64 and its sign extension."""
    limbs = np.empty((len(cents), 2), dtype=np.int64)
    limbs[:, 0] = cents
    limbs[:, 1] = limbs[:, 0] >> 63
    return pa.Array.from_buffers(pa.decimal128(7, 2), len(cents),
                                 [None, pa.py_buffer(limbs)])


def _int64(values: np.ndarray) -> pa.Array:
    return pa.array(values, type=pa.int64())


def first_revisions(rows: int) -> np.ndarray:
    """Surrogate keys (from 1) of the first revision of each business key of
    a dimension that keeps history: business keys have one, two and three
    revisions in turn, so the firsts are 1, 2, 4, 7, 8, 10, ..."""
    firsts, sk, revisions = [], 1, 1
    while sk <= rows:
        firsts.append(sk)
        sk += revisions
        revisions = revisions % 3 + 1
    return np.asarray(firsts, dtype=np.int64)


def _write(directory, stem, table: pa.Table, files: int, row_group_rows: int):
    """``table`` in row order into ``files`` parquet files; the remainder of
    an uneven split goes one row each to the first files."""
    n, paths, at = table.num_rows, [], 0
    for p in range(files):
        per = n // files + (p < n % files)
        path = os.path.join(directory, f"{stem}_{p}.parquet")
        pq.write_table(table.slice(at, per), path, row_group_size=row_group_rows)
        paths.append(path)
        at += per
    return paths


def generate(directory: str, seed: int, config: dict,
             tables: Sequence[str] = TABLES) -> Dict[str, List[str]]:
    """Writes those of ``tables`` under ``directory``; returns table name ->
    files. Reads from ``config``: ``scale_factor`` and, under
    ``generator_params``, ``fact_files``, ``row_group_rows`` and optionally
    ``table_rows`` (row counts in place of Table 3-2's: a configuration that
    sets it is not the table's and says so itself)."""
    unknown = sorted(set(tables) - set(TABLES))
    if unknown:
        raise GeneratorError(f"tables {unknown} are not among {list(TABLES)}")
    params = config["generator_params"]
    rows = params.get("table_rows") or table_rows(config["scale_factor"])
    n, n_returns = rows["store_sales"], rows["store_returns"]
    if n_returns > n:
        raise GeneratorError("more store_returns rows than store_sales rows")
    rng = np.random.default_rng(seed)

    # tickets: sizes 8..16 until they cover the fact table, the last one cut
    sizes = rng.integers(8, 17, n // 8 + 1)
    ticket = np.repeat(np.arange(len(sizes), dtype=np.int64), sizes)[:n]
    line = np.arange(n, dtype=np.int64) - \
        np.repeat(np.cumsum(sizes) - sizes, sizes)[:n]
    stores = first_revisions(rows["store"])
    store = stores[rng.integers(0, len(stores), len(sizes))][ticket]
    customer = rng.integers(1, rows["customer"] + 1, len(sizes))[ticket]
    permutation = rng.permutation(rows["item"]).astype(np.int64) + 1
    item = permutation[(rng.integers(0, rows["item"], len(sizes))[ticket] + line)
                       % rows["item"]]
    quantity = rng.integers(1, 101, n)
    wholesale = rng.integers(100, 10_001, n)                      # cents
    list_price = wholesale * (100 + rng.integers(0, 201, n)) // 100
    sales_price = list_price * (100 - rng.integers(0, 101, n)) // 100
    returned = np.sort(rng.choice(n, n_returns, replace=False))
    return_quantity = rng.integers(1, quantity[returned] + 1)
    item_category = rng.integers(1, CATEGORIES + 1, rows["item"])
    item_brand = rng.integers(1, 1000, rows["item"])
    item_price = rng.integers(9, 10_000, rows["item"])            # cents
    store_state = rng.integers(0, 50, rows["store"])

    files, row_group = params["fact_files"], params["row_group_rows"]
    paths: Dict[str, List[str]] = {}
    if "store_sales" in tables:
        paths["store_sales"] = _write(directory, "ss", pa.table({
            "ss_item_sk": _int64(item),
            "ss_customer_sk": _int64(customer),
            "ss_store_sk": _int64(store),
            "ss_ticket_number": _int64(ticket + 1),
            "ss_quantity": _int64(quantity),
            "ss_sales_price": _decimal_7_2(sales_price),
        }), files, row_group)
    if "store_returns" in tables:
        paths["store_returns"] = _write(directory, "sr", pa.table({
            "sr_item_sk": _int64(item[returned]),
            "sr_customer_sk": _int64(customer[returned]),
            "sr_store_sk": _int64(store[returned]),
            "sr_ticket_number": _int64(ticket[returned] + 1),
            "sr_return_quantity": _int64(return_quantity),
            "sr_return_amt": _decimal_7_2(return_quantity * sales_price[returned]),
        }), files, row_group)
    if "item" in tables:
        paths["item"] = _write(directory, "item", pa.table({
            "i_item_sk": _int64(np.arange(1, rows["item"] + 1)),
            "i_category_id": _int64(item_category),
            "i_brand_id": _int64(item_brand),
            "i_current_price": _decimal_7_2(item_price),
        }), 1, row_group)
    if "store" in tables:
        paths["store"] = _write(directory, "store", pa.table({
            "s_store_sk": _int64(np.arange(1, rows["store"] + 1)),
            "s_state_id": _int64(store_state),
        }), 1, row_group)
    return paths
