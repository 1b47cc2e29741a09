"""Sort-key normalization for device and host sorting.

The reference converts sort/group keys to a byte-comparable row format
(arrow-row RowConverter; key pruning in sort_exec.rs). On TPU we feed
``jax.lax.sort`` *native-dtype* operand pairs — (null_rank u8, value) per
key — because v5e has no native 64-bit and XLA's X64 rewriting does not
implement the f64<->s64 bitcasts the classic u64-key trick needs. XLA's
float sort comparator is already a total order with NaN sorting last
(matching Spark's NaN-is-largest) once NaNs are canonicalized to the
positive quiet NaN; descending is bitwise-NOT for ints and negation for
floats.

Host-side (spill-merge comparisons, numpy is free to bitcast) keys normalize
to a (n, 2k) uint64 matrix via the total-order bit trick. Sorts whose keys
include var-width columns run fully on host via arrow ``sort_indices``
(SURVEY.md §7.4.3).
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

from blaze_tpu.core.batch import ColumnarBatch
from blaze_tpu.exprs.compiler import ExprEvaluator, _broadcast
from blaze_tpu.ir import exprs as E
from blaze_tpu.ir import types as T


def supports_device_sort(schema: T.Schema, sort_orders: List[E.SortOrder]) -> bool:
    from blaze_tpu.utils.device import is_device_dtype

    return all(is_device_dtype(E.infer_type(so.child, schema)) for so in sort_orders)


def device_sortable(batch: ColumnarBatch, sort_orders: List[E.SortOrder]) -> bool:
    """`supports_device_sort` for the batch in hand: a var-width key that is
    a reference to a CODED column sorts on the device too, by its rank
    plane (`coded_rank_plane`)."""
    from blaze_tpu.core.batch import CodedColumn
    from blaze_tpu.exprs.compiler import reference_index
    from blaze_tpu.utils.device import is_device_dtype

    for so in sort_orders:
        if is_device_dtype(E.infer_type(so.child, batch.schema)):
            continue
        idx = reference_index(so.child, batch.schema)
        if idx is None or not isinstance(batch.columns[idx], CodedColumn):
            return False
    return True


def coded_rank_plane(col):
    """A coded column's ORDER as a device plane: the rank of each row's
    entry in value order (core/dictionary.rank: one sort of the dictionary,
    bytes order as Spark's), gathered by code. Ordering a coded column is by
    value, never by code."""
    from blaze_tpu.core import dictionary as D

    ranks = D.rank(col.dictionary)
    table = jnp.asarray(ranks if len(ranks) else np.zeros(1, np.int32))
    return table[col.data]


# ---------------------------------------------------------------------------
# device operands (native dtypes, no 64-bit bitcasts)
# ---------------------------------------------------------------------------


def key_spec(sort_orders: List[E.SortOrder]) -> tuple:
    """Static per-key spec keying the jit cache of the operand kernel."""
    return tuple((so.ascending, so.nulls_first) for so in sort_orders)


def key_operands(batch: ColumnarBatch, sort_orders: List[E.SortOrder],
                 evaluator: Optional[ExprEvaluator] = None) -> List[jnp.ndarray]:
    """Build lax.sort operands [rank0, val0, rank1, val1, ...]; padding rows
    sort last. Normalization of ALL keys runs as one jitted device kernel
    (core/kernels.sort_key_operands) whose cache is keyed by shapes/dtypes +
    the static (ascending, nulls_first) spec; NaNs fold into the u8 rank so
    the operands also order correctly under plain IEEE comparisons (the
    range-partition kernel reuses them)."""
    from blaze_tpu.core import kernels as K

    from blaze_tpu.exprs.compiler import CodedVal

    ev = evaluator or ExprEvaluator([so.child for so in sort_orders], batch.schema)
    datas, valids = [], []
    for so in sort_orders:
        v = ev._eval(so.child, batch)
        if isinstance(v, CodedVal):
            datas.append(coded_rank_plane(v.col))
            valids.append(v.col.validity)
            continue
        data, validity = _broadcast(ev._to_dev(v, batch), batch)
        datas.append(data)
        valids.append(validity)
    return K.sort_key_operands(datas, valids, batch.row_exists_mask(),
                               key_spec(sort_orders))


def packs_to_word(dtype) -> bool:
    """Can :func:`orderable_word_traced` take an operand of this dtype? Not
    f64: the v5e's X64 rewriting has no f64 -> s64 bitcast."""
    dtype = jnp.dtype(dtype)
    return (jnp.issubdtype(dtype, jnp.integer) or dtype == jnp.bool_
            or dtype == jnp.float32)


def orderable_word_traced(val):
    """Traced: the uint64 image of an operand value plane (direction-adjusted,
    NaN-free), whose unsigned order is ``lax.sort``'s order of the plane;
    like its comparator, -0.0 is 0.0 here (`_orderable_bits_np`, the host's
    twin for the spill merge, tells them apart)."""
    if val.dtype == jnp.float32:
        val = jnp.where(val == 0, jnp.float32(0), val)
        bits = jax.lax.bitcast_convert_type(val, jnp.uint32)
        top = jnp.uint32(1 << 31)
        return jnp.where(bits >= top, ~bits, bits | top).astype(jnp.uint64)
    if jnp.issubdtype(val.dtype, jnp.signedinteger):
        return val.astype(jnp.int64).astype(jnp.uint64) ^ jnp.uint64(1 << 63)
    return val.astype(jnp.uint64)


# ---------------------------------------------------------------------------
# host-side normalized keys (merge comparisons)
# ---------------------------------------------------------------------------


def _orderable_u64_np(data: np.ndarray, validity: np.ndarray) -> np.ndarray:
    """numpy total-order normalization to uint64 (ascending)."""
    if data.dtype == np.float64:
        canonical = np.float64("nan")
        d = np.where(np.isnan(data), canonical, data)
        bits = d.view(np.int64)
        u = bits.view(np.uint64)
        return np.where(bits >= 0, u | np.uint64(1 << 63), ~u)
    if data.dtype == np.float32:
        canonical = np.float32("nan")
        d = np.where(np.isnan(data), canonical, data)
        bits = d.view(np.int32)
        u = bits.view(np.uint32).astype(np.uint64)
        return np.where(bits >= 0, u | np.uint64(1 << 31), (~u) & np.uint64(0xFFFFFFFF))
    if data.dtype == np.bool_:
        return data.astype(np.uint64)
    v = data.astype(np.int64)
    return v.view(np.uint64) ^ np.uint64(1 << 63)


def _orderable_bits_np(val: np.ndarray) -> np.ndarray:
    """uint64 image of an already direction-adjusted, NaN-free operand value
    plane (ints stay signed-comparable; floats use the sign-flip trick)."""
    if val.dtype == np.float64:
        bits = val.view(np.int64)
        u = bits.view(np.uint64)
        return np.where(bits >= 0, u | np.uint64(1 << 63), ~u)
    if val.dtype == np.float32:
        bits = val.view(np.int32)
        u = bits.view(np.uint32).astype(np.uint64)
        return np.where(bits >= 0, u | np.uint64(1 << 31), (~u) & np.uint64(0xFFFFFFFF))
    if val.dtype == np.bool_ or val.dtype == np.uint8:
        return val.astype(np.uint64)
    return val.astype(np.int64).view(np.uint64) ^ np.uint64(1 << 63)


def operands_merge_matrix(operands: List, indices: np.ndarray) -> np.ndarray:
    """(len(indices), 2k) uint64 merge-key matrix derived straight from the
    device sort operands — the spill path reuses the operands it just sorted
    with instead of re-evaluating key expressions on the sorted run. Ranks
    and values are already direction/null/NaN-normalized, so each pair maps
    to (rank u64, orderable bits)."""
    mats = []
    for j in range(0, len(operands), 2):
        rank = np.asarray(operands[j])[indices].astype(np.uint64)
        val = np.asarray(operands[j + 1])[indices]
        mats.append(rank)
        mats.append(_orderable_bits_np(val))
    return (np.stack(mats, axis=1) if mats
            else np.zeros((len(indices), 0), np.uint64))


def pack_key_rows(mat_u64: np.ndarray) -> np.ndarray:
    """(n, w) uint64 matrix -> (n,) fixed-width big-endian byte rows whose
    memcmp order equals the row-tuple order, so ONE np.searchsorted replaces
    a per-row python bisect (numpy's S-dtype compare strips trailing NULs,
    which never reorders equal-width buffers — NUL is the smallest byte)."""
    n, w = mat_u64.shape
    if w == 0:
        return np.zeros(n, dtype="S1")
    be = np.ascontiguousarray(mat_u64.astype(">u8"))
    return be.view(f"S{8 * w}").ravel()


def planes_merge_matrix(planes: List[Tuple[np.ndarray, np.ndarray]],
                        sort_orders: List[E.SortOrder]) -> np.ndarray:
    """(n, 2k) uint64 matrix over already-host (data, validity) key planes;
    row tuples compare in sort order."""
    n = len(planes[0][0]) if planes else 0
    mats = []
    for so, (data, validity) in zip(sort_orders, planes):
        key = _orderable_u64_np(data, validity)
        if not so.ascending:
            key = ~key
        key = np.where(validity, key, np.uint64(0))
        rank = np.where(validity, 1, 0 if so.nulls_first else 2).astype(np.uint64)
        mats.append(rank)
        mats.append(key)
    return np.stack(mats, axis=1) if mats else np.zeros((n, 0), np.uint64)


def merge_keys_matrix(batch: ColumnarBatch, sort_orders: List[E.SortOrder]) -> np.ndarray:
    """(n, 2k) uint64 matrix whose row tuples compare in sort order."""
    ev = ExprEvaluator([so.child for so in sort_orders], batch.schema)
    cols = ev.evaluate(batch)
    n = batch.num_rows
    planes = [(np.asarray(c.data[:n]), np.asarray(c.validity[:n])) for c in cols]
    return planes_merge_matrix(planes, sort_orders)


def peer_key_rows(batch: ColumnarBatch, sort_orders: List[E.SortOrder],
                  evaluator: Optional[ExprEvaluator] = None):
    """Canonical per-row ORDER-key rows for window peer-boundary detection.

    Delegates to the join keymap's carryable row encoding (keymap.key_rows)
    so peer equality matches partition-key equality — floats folded
    (-0.0 == 0.0, one NaN payload), nulls grouped as values — and the last
    row is O(1) to carry across batches via keymap.RunningKeyCodes. Sort
    DIRECTION is irrelevant here: peers are equal-key runs, and the input
    is already sorted, so only the equality encoding matters."""
    from blaze_tpu.ops.joins.keymap import key_rows

    ev = evaluator or ExprEvaluator([so.child for so in sort_orders],
                                    batch.schema)
    return key_rows(batch, ev.evaluate(batch))


def host_sort_indices(batch: ColumnarBatch, sort_orders: List[E.SortOrder],
                      evaluator: Optional[ExprEvaluator] = None) -> np.ndarray:
    """Multi-key sort on host via arrow (var-width keys)."""
    ev = evaluator or ExprEvaluator([so.child for so in sort_orders], batch.schema)
    cols = ev.evaluate(batch)
    from blaze_tpu.core import dictionary as D
    from blaze_tpu.core.batch import CodedColumn, decode_dictionary
    from blaze_tpu.utils.device import pull_columns

    n = batch.num_rows
    # a coded key orders by its entries' ranks (one sort of the dictionary,
    # core/dictionary.rank): an int32 key beside the others, nothing decoded
    coded = [c for c in cols if isinstance(c, CodedColumn)]
    ranked = {}
    for c, (codes, valid) in zip(coded, pull_columns(coded, n) if coded else ()):
        ranks = D.rank(c.dictionary)
        ranked[id(c)] = pa.array(
            ranks[codes] if len(ranks) else np.zeros(n, np.int32),
            mask=None if valid.all() else ~valid)
    # pc.sort_indices has no dictionary kernel: decode code-encoded strings
    arrays = [ranked[id(c)] if id(c) in ranked
              else decode_dictionary(c.to_arrow(n), c.dtype) for c in cols]
    placements = {so.nulls_first for so in sort_orders}
    if len(placements) > 1:
        # arrow's sort has one global null placement; mixed per-key
        # placements fall back to a python sort over comparable key tuples
        rows = host_keys_matrix(batch, sort_orders)
        return np.array(sorted(range(batch.num_rows), key=rows.__getitem__),
                        dtype=np.int64)
    tbl = pa.table({f"k{i}": a for i, a in enumerate(arrays)})
    keys = [(f"k{i}", "ascending" if so.ascending else "descending")
            for i, so in enumerate(sort_orders)]
    import warnings

    with warnings.catch_warnings():
        warnings.simplefilter("ignore", FutureWarning)
        idx = pc.sort_indices(
            tbl, options=pc.SortOptions(
                sort_keys=keys,
                null_placement="at_start" if sort_orders[0].nulls_first else "at_end",
            )
        )
    return np.asarray(idx)


def host_keys_matrix(batch: ColumnarBatch, sort_orders: List[E.SortOrder]) -> list:
    """Merge keys for host-sorted (string) runs: python-comparable tuples."""
    ev = ExprEvaluator([so.child for so in sort_orders], batch.schema)
    cols = ev.evaluate(batch)
    arrays = [c.to_arrow(batch.num_rows).to_pylist() for c in cols]
    rows = []
    for i in range(batch.num_rows):
        rows.append(tuple(_host_key_part(arrays[k][i], so)
                          for k, so in enumerate(sort_orders)))
    return rows


class _Rev:
    """Reverses comparison order for descending host keys."""

    __slots__ = ("v",)

    def __init__(self, v):
        self.v = v

    def __lt__(self, other):
        return other.v < self.v

    def __eq__(self, other):
        return self.v == other.v


def _host_key_part(v, so: E.SortOrder):
    null_rank = (0 if so.nulls_first else 2) if v is None else 1
    if v is None:
        return (null_rank, 0)
    return (null_rank, _Rev(v) if not so.ascending else v)
