"""What the query classes share: the data a plan is built over, plan
fragments in the program's IR, and the rank the references bolt onto Acero
(which has no window operator)."""

from __future__ import annotations

import dataclasses
from typing import Dict, List

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq


@dataclasses.dataclass
class Dataset:
    """The generated tables and how the configuration partitions them."""

    paths: Dict[str, List[str]]
    scan_partitions: int
    shuffle_partitions: int

    def table(self, name: str) -> pa.Table:
        return pa.concat_tables([pq.read_table(p) for p in self.paths[name]])

    def rows(self, name: str) -> int:
        return sum(pq.ParquetFile(p).metadata.num_rows for p in self.paths[name])


def scan(data: Dataset, table: str, partitioned: bool = True):
    from blaze_tpu.ops.parquet import scan_node_for_files

    if partitioned:
        return scan_node_for_files(data.paths[table],
                                   num_partitions=data.scan_partitions)
    return scan_node_for_files(data.paths[table])


def two_stage_agg(child, keys, aggs, nparts: int):
    """PARTIAL aggregation -> hash exchange on the keys -> FINAL."""
    from blaze_tpu.ir import exprs as E
    from blaze_tpu.ir import nodes as N

    partial = N.Agg(child, E.AggExecMode.HASH_AGG, keys, [
        N.AggColumn(agg, E.AggMode.PARTIAL, name) for name, agg in aggs],
        supports_partial_skipping=True)
    ex = N.ShuffleExchange(partial, N.HashPartitioning(
        [e for _, e in keys], nparts))
    return N.Agg(ex, E.AggExecMode.HASH_AGG, keys, [
        N.AggColumn(agg, E.AggMode.FINAL, name) for name, agg in aggs])


def rank_at_most(group: np.ndarray, value: np.ndarray, k: int) -> np.ndarray:
    """Row indices whose SQL ``rank()`` by ``value`` descending within
    ``group`` is at most ``k`` (ties share a rank, so more than ``k`` rows
    of a group can qualify)."""
    order = np.lexsort((-value, group))
    g, v = group[order], value[order]
    at = np.arange(len(g))
    new_group = np.concatenate([[True], g[1:] != g[:-1]])
    group_start = np.maximum.accumulate(np.where(new_group, at, 0))
    new_value = np.concatenate([[True], (v[1:] != v[:-1]) | new_group[1:]])
    value_start = np.maximum.accumulate(np.where(new_value, at, 0))
    return order[value_start - group_start + 1 <= k]


def rows_of(table: pa.Table, columns, ordered: bool) -> list:
    """A table's rows as comparable tuples; sorted where order is open."""
    data = table.select(list(columns)).to_pydict()
    rows = list(zip(*(data[c] for c in columns)))
    return rows if ordered else sorted(rows)
