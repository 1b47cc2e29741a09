"""`tpcds_star`'s store channel with a sale date: `store_sales` gains
`ss_sold_date_sk`, one date a ticket (as dsdgen: a ticket is one visit to one
store on one day), drawn uniformly from the specification's 1,823 sale days
(`d_date_sk` 2450816 = 1998-01-02 onward; `d_date_sk` and `d_date` ascend
together, so the key stands for the date wherever a class orders by it).

Every other table and every other column of `store_sales` is `tpcds_star`'s,
byte for byte, for the same seed and configuration: this module calls it and
rewrites the `store_sales` files with the one column more. The dates come
from a generator of their own, seeded by the same `--seed`, so they shift
nothing of `tpcds_star`'s draws."""

from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq

from generators import tpcds_star

TABLES = tpcds_star.TABLES
SALE_DAYS = 1823             # five years of sales, 1998-01-02 .. 2002-12-29
FIRST_SALE_DATE_SK = 2450816  # date_dim's surrogate key of 1998-01-02
_DATE_STREAM = 51            # the dates' own random stream beside the seed's


def ticket_dates(seed: int, tickets: int) -> np.ndarray:
    """``ss_sold_date_sk`` of tickets 1 .. ``tickets``, in ticket order."""
    rng = np.random.default_rng([_DATE_STREAM, seed])
    return FIRST_SALE_DATE_SK + rng.integers(0, SALE_DAYS, tickets)


def generate(directory: str, seed: int, config: dict,
             tables: Sequence[str] = TABLES) -> Dict[str, List[str]]:
    """`tpcds_star.generate`'s contract; `store_sales` has `ss_sold_date_sk`
    (int64) after `tpcds_star`'s columns."""
    paths = tpcds_star.generate(directory, seed, config, tables)
    if "store_sales" not in paths:
        return paths
    params = config["generator_params"]
    rows = params.get("table_rows") or \
        tpcds_star.table_rows(config["scale_factor"])
    dates = ticket_dates(seed, rows["store_sales"] // 8 + 1)
    for path in paths["store_sales"]:
        table = pq.read_table(path)
        ticket = table["ss_ticket_number"].to_numpy()
        pq.write_table(
            table.append_column("ss_sold_date_sk",
                                pa.array(dates[ticket - 1], type=pa.int64())),
            path, row_group_size=params["row_group_rows"])
    return paths
