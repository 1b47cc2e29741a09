"""Columnar batch representation — the unit of data flow between operators.

The reference streams Arrow ``RecordBatch``es of ~``batch_size`` rows between
DataFusion operators. On TPU the equivalent is a struct-of-arrays batch whose
fixed-width columns are dense jax arrays padded to a *capacity bucket* (static
shapes for XLA) with an explicit ``num_rows`` and per-column validity masks.
A variable-width column (string/binary) that arrives dictionary-encoded is a
``CodedColumn``: int32 codes and validity on the device under the same
padding contract, its values ONE Arrow dictionary on the host, held by
reference (core/dictionary.py). Var-width values without a dictionary
(computed strings, nested types) stay host-resident as Arrow arrays.

Padding discipline: rows in ``[num_rows, capacity)`` have ``validity == False``
and ``data == 0`` so that hashes/sorts over padded tails are deterministic.
``validity`` means "row exists AND value is non-null"; "row exists" alone is
``arange(capacity) < num_rows``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa

from blaze_tpu.config import get_config
from blaze_tpu.ir import types as T


# max operands per concat dispatch (see ColumnarBatch.concat)
_CONCAT_FANIN = 64


@functools.lru_cache(maxsize=128)
def _iota_on(capacity: int, device) -> jax.Array:
    return jnp.arange(capacity)


def _iota(capacity: int) -> jax.Array:
    """Device-resident ``arange(capacity)`` per capacity bucket (a handful of
    entries — buckets are powers of two). Keyed by the thread's default
    device: under adaptive placement (runtime/placement.py) host-placed
    stages must not pull a cached accelerator-resident iota into CPU-pinned
    kernels."""
    return _iota_on(capacity, jax.config.jax_default_device)


def _row_mask(capacity: int, n: int) -> jax.Array:
    """Device ``arange(capacity) < n`` mask (validity of a null-free column).
    Only the iota is cached: caching per (capacity, n) would pin unboundedly
    many capacity-sized masks in HBM, while the ``< n`` comparison is an
    async ~free dispatch."""
    return _iota(capacity) < n


def pack_bitmap(validity: np.ndarray) -> pa.Buffer:
    return pa.py_buffer(np.packbits(validity.astype(np.uint8), bitorder="little").tobytes())


def unpack_bitmap(buf, length: int, offset: int = 0) -> np.ndarray:
    if buf is None:
        return np.ones(length, dtype=bool)
    bits = np.unpackbits(np.frombuffer(buf, dtype=np.uint8), bitorder="little")
    return bits[offset : offset + length].astype(bool)


def _decimal128_lo64(arr: pa.Array) -> np.ndarray:
    """Low 64-bit limb of a decimal128 array's unscaled values. Exact for
    precision <= 18 (values fit in int64; low limb == two's-complement value)."""
    buf = arr.buffers()[1]
    raw = np.frombuffer(buf, dtype=np.int64, offset=arr.offset * 16, count=len(arr) * 2)
    return raw[0::2].copy()


def decimal128_limbs(arr: pa.Array):
    """(lo_raw, hi, validity) planes of a decimal128 array: lo_raw is the
    LOW 64 bits as int64 (unsigned semantics — bit 63 may be set), hi the
    signed high 64 bits. value == hi * 2^64 + uint64(lo_raw), exact for any
    precision <= 38. The device-side wide-decimal aggregates (3-limb sums,
    lexicographic min/max) consume these planes."""
    if isinstance(arr, pa.ChunkedArray):
        arr = arr.combine_chunks()
    buf = arr.buffers()[1]
    raw = np.frombuffer(buf, dtype=np.int64, offset=arr.offset * 16,
                        count=len(arr) * 2)
    valid = ~np.asarray(arr.is_null()) if arr.null_count \
        else np.ones(len(arr), bool)
    return raw[0::2].copy(), raw[1::2].copy(), valid


def _int64_to_decimal128(values: np.ndarray, validity: np.ndarray, dt: T.DecimalType) -> pa.Array:
    n = len(values)
    data = np.empty((n, 2), dtype=np.int64)
    data[:, 0] = values
    data[:, 1] = np.where(values < 0, -1, 0)
    return pa.Array.from_buffers(
        pa.decimal128(dt.precision, dt.scale),
        n,
        [pack_bitmap(validity), pa.py_buffer(data)],
    )


class Column:
    """Abstract column. Concrete: DeviceColumn (fixed-width, on device),
    CodedColumn (var-width: codes on device, one dictionary on the host) and
    HostColumn (var-width without a dictionary, nested: Arrow on host)."""

    dtype: T.DataType

    @property
    def is_device(self) -> bool:
        return isinstance(self, DeviceColumn)


def has_planes(col) -> bool:
    """Does the column keep its rows as (data, validity) device planes that
    the movers of core/kernels.py carry: a DeviceColumn's values or a
    CodedColumn's codes?"""
    return isinstance(col, (DeviceColumn, CodedColumn))


@dataclasses.dataclass
class DeviceColumn(Column):
    """Fixed-width column: dense data padded to capacity + validity mask.

    For DecimalType the data carries the *unscaled* value as int64
    (precision <= 18 fast path; see SURVEY.md §7.4.4)."""

    dtype: T.DataType
    data: jax.Array      # shape (capacity,), dtype = dtype.np_dtype (int64 for decimal)
    validity: jax.Array  # shape (capacity,), bool

    @property
    def capacity(self) -> int:
        return self.data.shape[0]

    def nbytes(self) -> int:
        return self.data.nbytes + self.validity.nbytes

    def like(self, data: jax.Array, validity: jax.Array) -> "DeviceColumn":
        """A column of this kind and type over other planes (a mover's
        result)."""
        return DeviceColumn(self.dtype, data, validity)

    def with_capacity(self, capacity: int) -> "DeviceColumn":
        cap = self.capacity
        if capacity == cap:
            return self
        if capacity > cap:
            pad = capacity - cap
            return self.like(jnp.pad(self.data, (0, pad)),
                             jnp.pad(self.validity, (0, pad)))
        return self.like(self.data[:capacity], self.validity[:capacity])

    def take_device(self, indices: jax.Array, valid_mask: jax.Array) -> "DeviceColumn":
        """Gather rows by device indices; valid_mask marks live output rows."""
        idx = jnp.clip(indices, 0, self.capacity - 1)
        data = jnp.where(valid_mask, self.data[idx], jnp.zeros((), self.data.dtype))
        validity = self.validity[idx] & valid_mask
        return DeviceColumn(self.dtype, data, validity)

    def to_arrow(self, num_rows: int) -> pa.Array:
        data = np.asarray(self.data[:num_rows])
        validity = np.asarray(self.validity[:num_rows])
        return _devcol_to_arrow(self.dtype, data, validity, num_rows)

    @staticmethod
    def from_numpy(dt: T.DataType, data: np.ndarray, validity: Optional[np.ndarray], capacity: int) -> "DeviceColumn":
        from blaze_tpu.runtime.failpoints import failpoint
        from blaze_tpu.utils.device import DEVICE_STATS

        failpoint("device.put")
        n = len(data)
        if validity is None or validity.all():
            # null-free column: skip the validity upload entirely — the mask
            # is just "row exists", computed on device and cached per
            # (capacity, num_rows). On a bandwidth-bound host link this saves
            # ``capacity`` bytes per column per batch.
            if n == capacity and data.dtype == dt.np_dtype:
                # full bucket, right dtype: upload the source buffer
                # directly — no zero/copy staging pass (the scan hot path:
                # most batches fill their capacity exactly)
                DEVICE_STATS.add_to_device(data.nbytes)
                return DeviceColumn(dt, jnp.asarray(data),
                                    _row_mask(capacity, n))
            buf = np.zeros(capacity, dtype=dt.np_dtype)
            np.copyto(buf[:n], data, casting="unsafe")
            DEVICE_STATS.add_to_device(buf.nbytes)
            return DeviceColumn(dt, jnp.asarray(buf), _row_mask(capacity, n))
        buf = np.zeros(capacity, dtype=dt.np_dtype)
        vbuf = np.zeros(capacity, dtype=bool)
        np.copyto(buf[:n], np.where(validity, data, np.zeros((), dt.np_dtype)),
                  casting="unsafe")
        vbuf[:n] = validity
        DEVICE_STATS.add_to_device(buf.nbytes + vbuf.nbytes)
        return DeviceColumn(dt, jnp.asarray(buf), jnp.asarray(vbuf))


def _devcol_to_arrow(dt: T.DataType, data: np.ndarray, validity: np.ndarray,
                     num_rows: int) -> pa.Array:
    if isinstance(dt, T.DecimalType):
        return _int64_to_decimal128(data, validity, dt)
    if isinstance(dt, T.BooleanType):
        return pa.Array.from_buffers(
            pa.bool_(), num_rows, [pack_bitmap(validity), pack_bitmap(data)]
        )
    atype = T.to_arrow_type(dt)
    return pa.Array.from_buffers(
        atype, num_rows, [pack_bitmap(validity), pa.py_buffer(np.ascontiguousarray(data))]
    )


@dataclasses.dataclass
class CodedColumn(Column):
    """Var-width column whose rows are int32 codes into ONE dictionary: the
    code and validity planes live on the device, padded to capacity under
    the padding contract (code 0, validity False past ``num_rows`` and at
    NULLs), and move with the movers of core/kernels.py like any plane; the
    dictionary is a host Arrow array (large_utf8 / large_binary, no NULL
    entry needed) held by reference and never copied a batch. Two coded
    columns with different dictionaries meet only through one remap table
    (core/dictionary.unify). A value is decoded only where one is needed:
    ``to_arrow``. ``null_literal`` says the column was made as a typed NULL
    (`nulls_like`): no row of it is valid. A mover does not carry the mark
    on (`like`), so a column without it may hold no valid row either."""

    dtype: T.DataType
    data: jax.Array        # shape (capacity,), int32 codes
    validity: jax.Array    # shape (capacity,), bool
    dictionary: pa.Array
    null_literal: bool = False

    def __post_init__(self):
        from blaze_tpu.core import dictionary as D

        self.dictionary = D.large(self.dictionary)

    @property
    def capacity(self) -> int:
        return self.data.shape[0]

    def nbytes(self) -> int:
        return self.data.nbytes + self.validity.nbytes

    def like(self, data: jax.Array, validity: jax.Array) -> "CodedColumn":
        return CodedColumn(self.dtype, data, validity, self.dictionary)

    with_capacity = DeviceColumn.with_capacity

    def nulls_like(self) -> "CodedColumn":
        """The typed NULL in this column's place: an all-invalid plane over
        the same dictionary (a ROLLUP's nulled key), marked as one."""
        return CodedColumn(self.dtype, _zero_plane(self.capacity, "int32"),
                           _zero_plane(self.capacity, "bool"),
                           self.dictionary, null_literal=True)

    def remapped(self, dictionary: pa.Array,
                 table: Optional[np.ndarray]) -> "CodedColumn":
        """The same rows as codes of ``dictionary``: through ``table`` (its
        codes for this column's; the caller counts the rows as
        ``dict_remap_rows``), or as they stand where it is None."""
        if table is None:
            return CodedColumn(self.dtype, self.data, self.validity, dictionary)
        lookup = jnp.asarray(table if len(table) else np.zeros(1, np.int32))
        return CodedColumn(self.dtype, lookup[self.data], self.validity,
                           dictionary)

    def to_host(self, num_rows: int, pulled=None) -> "HostColumn":
        """The column as a host column over the same dictionary (Arrow's
        dictionary array): the codes are pulled, no value is touched."""
        from blaze_tpu.core import dictionary as D

        codes, valid = pulled if pulled is not None else (
            np.asarray(self.data[:num_rows]),
            np.asarray(self.validity[:num_rows]))
        return HostColumn(self.dtype,
                          D.dictionary_array(self.dictionary, codes, valid))

    def to_arrow(self, num_rows: int, pulled=None) -> pa.Array:
        from blaze_tpu.core import dictionary as D

        codes, valid = pulled if pulled is not None else (
            np.asarray(self.data[:num_rows]),
            np.asarray(self.validity[:num_rows]))
        return D.decode(self.dictionary, codes, valid)

    @staticmethod
    def from_arrow(arr: pa.Array, dt: T.DataType, capacity: int,
                   dictionary: Optional[pa.Array] = None,
                   table: Optional[np.ndarray] = None) -> "CodedColumn":
        """Upload a dictionary array's indices; ``dictionary`` / ``table``
        place them in another dictionary on the way (a scan's unified
        one)."""
        codes = arr.indices
        validity = ~np.asarray(codes.is_null()) if codes.null_count else None
        codes = codes.fill_null(0).to_numpy(zero_copy_only=False)
        if table is not None:
            codes = table[codes]
        planes = DeviceColumn.from_numpy(T.I32, codes.astype(np.int32, copy=False),
                                         validity, capacity)
        return CodedColumn(dt, planes.data, planes.validity,
                           arr.dictionary if dictionary is None else dictionary)


@functools.lru_cache(maxsize=64)
def _zero_plane_on(capacity: int, dtype: str, device) -> jax.Array:
    return jnp.zeros(capacity, dtype=dtype)


def _zero_plane(capacity: int, dtype: str) -> jax.Array:
    """Device-resident zeros per capacity bucket (as `_iota`): the planes of
    a typed NULL."""
    return _zero_plane_on(capacity, dtype, jax.config.jax_default_device)


@dataclasses.dataclass
class HostColumn(Column):
    """Host-resident column (string/binary/nested/decimal>18) as an Arrow array
    of exactly ``num_rows`` values (no padding on host)."""

    dtype: T.DataType
    array: pa.Array

    def __post_init__(self):
        if isinstance(self.array, pa.ChunkedArray):
            self.array = self.array.combine_chunks()

    def nbytes(self) -> int:
        return self.array.nbytes

    def take_host(self, indices: np.ndarray) -> "HostColumn":
        return HostColumn(self.dtype, self.array.take(pa.array(indices, type=pa.int64())))

    def to_arrow(self, num_rows: int) -> pa.Array:
        assert len(self.array) == num_rows, (len(self.array), num_rows)
        return self.array


def decode_dictionary(arr: pa.Array, dt: T.DataType) -> pa.Array:
    """Dictionary array -> plain large_* values array (plain string/binary
    arrays are normalized to large_* too — the engine-wide convention).
    Host kernels without dictionary variants (pc.sort_indices, concat of
    mixed encodings) decode at THIS boundary; code-aware consumers
    (exprs/compiler._dict_fast, the mesh exchange) read the dictionary form
    directly."""
    if isinstance(arr, pa.ChunkedArray):
        arr = arr.combine_chunks()
    if pa.types.is_dictionary(arr.type):
        arr = arr.cast(arr.type.value_type)
    if isinstance(dt, T.StringType) and not pa.types.is_large_string(arr.type):
        arr = arr.cast(pa.large_utf8())
    if isinstance(dt, T.BinaryType) and not pa.types.is_large_binary(arr.type):
        arr = arr.cast(pa.large_binary())
    return arr


def arrow_fixed_planes(arr: pa.Array, dt: T.DataType):
    """Arrow fixed-width array -> (np_data, np_validity) planes in the device
    layout (decimal<=18 as unscaled int64, dates as day int64, bool unpacked)."""
    if isinstance(arr, pa.ChunkedArray):
        arr = arr.combine_chunks()
    n = len(arr)
    if pa.types.is_dictionary(arr.type):
        arr = arr.cast(arr.type.value_type)
    if isinstance(dt, T.DecimalType):
        assert dt.fits_int64, f"decimal({dt.precision},{dt.scale}) exceeds int64 planes"
        validity = unpack_bitmap(arr.buffers()[0], n, arr.offset) \
            if arr.null_count else None
        return _decimal128_lo64(arr), validity
    # None validity = "all valid": lets the upload path skip both the
    # ones() allocation and the .all() scan per column
    validity = ~np.asarray(arr.is_null()) if arr.null_count else None
    if isinstance(dt, T.BooleanType):
        return unpack_bitmap(arr.buffers()[1], n, arr.offset), validity
    if arr.null_count:
        values = arr.fill_null(0).to_numpy(zero_copy_only=False)
    else:
        try:
            # null-free fixed-width: borrow arrow's buffer, no copy
            values = arr.to_numpy(zero_copy_only=True)
        except (pa.ArrowInvalid, pa.ArrowNotImplementedError):
            values = arr.to_numpy(zero_copy_only=False)
    if np.issubdtype(values.dtype, np.datetime64):
        if isinstance(dt, T.DateType):
            values = values.astype("datetime64[D]").view(np.int64)
        else:
            values = values.astype("datetime64[us]").view(np.int64)
    elif values.dtype == np.uint64:
        # the one lossy unsigned mapping — fail loudly on overflow
        checked = values if validity is None else values[validity]
        if n and checked.max(initial=0) > np.iinfo(np.int64).max:
            raise OverflowError("uint64 column exceeds int64 range")
        values = values.astype(np.int64)
    return values, validity


def device_columns(items, capacity: int) -> List["DeviceColumn"]:
    """Upload many columns' (dtype, np_data, np_validity-or-None) planes in
    ONE batched ``jax.device_put`` — ~2x the throughput of per-column puts
    on the CPU backend (measured) and one transfer round instead of k on an
    accelerator link. Staging rules match ``DeviceColumn.from_numpy``:
    null-free full-capacity planes upload the source buffer directly, the
    rest stage into zeroed capacity buffers; all-valid columns skip the
    validity upload (row-exists mask computed on device)."""
    from blaze_tpu.utils.device import DEVICE_STATS

    bufs: List[np.ndarray] = []
    plan = []  # (dt, data_slot, valid_slot_or_None, n)
    for dt, data, validity in items:
        n = len(data)
        if validity is None or validity.all():
            if n == capacity and data.dtype == dt.np_dtype:
                buf = data
            else:
                buf = np.zeros(capacity, dtype=dt.np_dtype)
                np.copyto(buf[:n], data, casting="unsafe")
            plan.append((dt, len(bufs), None, n))
            bufs.append(buf)
        else:
            buf = np.zeros(capacity, dtype=dt.np_dtype)
            np.copyto(buf[:n],
                      np.where(validity, data, np.zeros((), dt.np_dtype)),
                      casting="unsafe")
            vbuf = np.zeros(capacity, dtype=bool)
            vbuf[:n] = validity
            plan.append((dt, len(bufs), len(bufs) + 1, n))
            bufs += [buf, vbuf]
    if not bufs:
        return []
    dev = jax.device_put(bufs)
    DEVICE_STATS.add_to_device(sum(b.nbytes for b in bufs))
    return [
        DeviceColumn(dt, dev[di],
                     dev[vi] if vi is not None else _row_mask(capacity, n))
        for dt, di, vi, n in plan
    ]


def device_columns_mapped(items, capacity: int, num_rows: int,
                          mapped: bool = True) -> List["DeviceColumn"]:
    """Upload columns whose planes are ALREADY capacity-length views over a
    raw shuffle frame (zero-copy data plane): no zeroed staging buffer, no
    copyto, no dtype fix-up — the mapped (possibly readonly) numpy views go
    straight into one batched ``jax.device_put``. Validity-less columns get
    the device row-exists mask. ``mapped=True`` books the bytes as
    DEVICE_STATS mapped (buffers entering jax with the host staging copy
    elided), NOT as to_device transfer — the audit split satellite 3 asks
    for; pass False for raw frames read off plain (unmapped) streams."""
    from blaze_tpu.utils.device import DEVICE_STATS

    bufs: List[np.ndarray] = []
    plan = []  # (dt, data_slot, valid_slot_or_None)
    for dt, data, validity in items:
        assert len(data) == capacity, (len(data), capacity)
        plan.append((dt, len(bufs),
                     len(bufs) + 1 if validity is not None else None))
        bufs.append(data)
        if validity is not None:
            bufs.append(validity)
    if not bufs:
        return []
    dev = jax.device_put(bufs)
    nbytes = sum(b.nbytes for b in bufs)
    if mapped:
        DEVICE_STATS.add_mapped(nbytes)
    else:
        DEVICE_STATS.add_to_device(nbytes)
    return [
        DeviceColumn(dt, dev[di],
                     dev[vi] if vi is not None
                     else _row_mask(capacity, num_rows))
        for dt, di, vi in plan
    ]


def _arrow_to_column(arr: pa.Array, dt: T.DataType, capacity: int) -> Column:
    from blaze_tpu.utils.device import is_device_dtype

    if isinstance(arr, pa.ChunkedArray):
        arr = arr.combine_chunks()
    if pa.types.is_dictionary(arr.type):
        if is_device_dtype(dt) or not isinstance(dt, (T.StringType,
                                                      T.BinaryType)):
            arr = arr.cast(arr.type.value_type)
        else:
            # a dictionary-read string/binary column is coded, always: the
            # codes go up once, predicates, joins, grouping and exchanges
            # work on them, and the dictionary stays one host array
            return CodedColumn.from_arrow(arr, dt, capacity)
    if is_device_dtype(dt):
        values, validity = arrow_fixed_planes(arr, dt)
        return DeviceColumn.from_numpy(dt, values, validity, capacity)
    # host-resident: normalize strings/binary to large_ variants
    if isinstance(dt, T.StringType) and not pa.types.is_large_string(arr.type):
        arr = arr.cast(pa.large_utf8())
    if isinstance(dt, T.BinaryType) and not pa.types.is_large_binary(arr.type):
        arr = arr.cast(pa.large_binary())
    return HostColumn(dt, arr)


def _one_dictionary_a_column(batches, rows):
    """Before a concat: every var-width column either coded in ALL the
    batches, over one dictionary (the others' codes remapped through
    core/dictionary.unify's tables), or coded in none (the coded ones
    handed over as host columns: a batch built without a dictionary, such
    as `ColumnarBatch.empty`, is among them). ``rows[j]`` is what the
    concat takes of batch j. Returns the batches and the rows whose codes
    were remapped."""
    ncols = len(batches[0].columns)
    coded = [i for i in range(ncols)
             if any(isinstance(b.columns[i], CodedColumn) for b in batches)]
    if not coded:
        return batches, 0
    from blaze_tpu.core import dictionary as D

    out = [list(b.columns) for b in batches]
    remapped = 0
    for i in coded:
        col_of = [b.columns[i] for b in batches]
        if not all(isinstance(c, CodedColumn) for c in col_of):
            for cols, b in zip(out, batches):
                if isinstance(cols[i], CodedColumn):
                    cols[i] = cols[i].to_host(b.num_rows)
            continue
        first = col_of[0].dictionary
        if all(c.dictionary is first for c in col_of):
            continue
        unified, tables = D.unify([c.dictionary for c in col_of])
        for cols, n, table in zip(out, rows, tables):
            cols[i] = cols[i].remapped(unified, table)
            remapped += n if table is not None else 0
    return [ColumnarBatch(b.schema, cols, b.num_rows)
            for b, cols in zip(batches, out)], remapped


@dataclasses.dataclass
class ColumnarBatch:
    schema: T.Schema
    columns: List[Column]
    num_rows: int

    def __post_init__(self):
        assert len(self.columns) == len(self.schema), (
            len(self.columns), len(self.schema))

    # --- constructors --------------------------------------------------------

    @staticmethod
    def from_arrow(rb: Union[pa.RecordBatch, pa.Table], schema: Optional[T.Schema] = None,
                   capacity: Optional[int] = None) -> "ColumnarBatch":
        if schema is None:
            schema = T.schema_from_arrow(rb.schema)
        n = rb.num_rows
        cap = capacity or get_config().capacity_for(n)
        from blaze_tpu.utils.device import is_device_dtype, stage_span

        # split device-bound columns out so their planes ride one batched
        # device_put; host columns convert in place
        cols: List[Optional[Column]] = [None] * len(schema)
        dev_items, dev_slots = [], []
        with stage_span(n):
            for i in range(len(schema)):
                arr, dt = rb.column(i), schema.types[i]
                if isinstance(arr, pa.ChunkedArray):
                    arr = arr.combine_chunks()
                if is_device_dtype(dt) and not pa.types.is_dictionary(arr.type):
                    dev_items.append((dt,) + arrow_fixed_planes(arr, dt))
                    dev_slots.append(i)
                else:
                    cols[i] = _arrow_to_column(arr, dt, cap)
            for slot, col in zip(dev_slots, device_columns(dev_items, cap)):
                cols[slot] = col
        return ColumnarBatch(schema, cols, n)

    @staticmethod
    def from_pydict(data: dict, schema: Optional[T.Schema] = None) -> "ColumnarBatch":
        if schema is not None:
            # build in schema order — from_arrow pairs columns positionally
            tbl = pa.table(
                {
                    f.name: pa.array(data[f.name], type=T.to_arrow_type(f.dtype))
                    for f in schema.fields
                }
            )
        else:
            tbl = pa.table(data)
        return ColumnarBatch.from_arrow(tbl, schema)

    @staticmethod
    def empty(schema: T.Schema, capacity: Optional[int] = None) -> "ColumnarBatch":
        from blaze_tpu.utils.device import is_device_dtype

        cap = capacity or get_config().min_capacity
        cols: List[Column] = []
        for f in schema.fields:
            if is_device_dtype(f.dtype):
                cols.append(
                    DeviceColumn(
                        f.dtype,
                        jnp.zeros(cap, dtype=f.dtype.np_dtype),
                        jnp.zeros(cap, dtype=bool),
                    )
                )
            else:
                cols.append(HostColumn(f.dtype, pa.array([], type=T.to_arrow_type(f.dtype))))
        return ColumnarBatch(schema, cols, 0)

    # --- properties ----------------------------------------------------------

    @property
    def capacity(self) -> int:
        for c in self.columns:
            if has_planes(c):
                return c.capacity
        return get_config().capacity_for(self.num_rows)

    def nbytes(self) -> int:
        """Accurate in-memory size (reference: arrow/array_size.rs)."""
        return sum(c.nbytes() for c in self.columns)

    def column(self, i: int) -> Column:
        return self.columns[i]

    def row_exists_mask(self) -> jax.Array:
        return _row_mask(self.capacity, self.num_rows)

    # --- transforms ----------------------------------------------------------

    def select(self, indices: Sequence[int]) -> "ColumnarBatch":
        return ColumnarBatch(
            self.schema.select(indices), [self.columns[i] for i in indices], self.num_rows
        )

    def rename(self, names: Sequence[str]) -> "ColumnarBatch":
        return ColumnarBatch(self.schema.rename(names), self.columns, self.num_rows)

    def with_capacity(self, capacity: int) -> "ColumnarBatch":
        assert capacity >= self.num_rows, (
            f"cannot shrink capacity {capacity} below num_rows {self.num_rows}"
        )
        cols = [
            c.with_capacity(capacity) if has_planes(c) else c
            for c in self.columns
        ]
        return ColumnarBatch(self.schema, cols, self.num_rows)

    def _device_slots(self):
        """The columns whose rows are device planes: fixed-width values and
        var-width codes alike."""
        return [i for i, c in enumerate(self.columns) if has_planes(c)]

    def take(self, indices: np.ndarray) -> "ColumnarBatch":
        """Host-driven row gather (indices must be < num_rows). All device
        columns move in ONE jitted dispatch and one device gather, side by
        side as a matrix of words (core/kernels.take_rows_traced)."""
        from blaze_tpu.core import kernels

        indices = np.asarray(indices, dtype=np.int64)
        n = len(indices)
        cap = get_config().capacity_for(n)
        slots = self._device_slots()
        cols: List[Column] = list(self.columns)
        if slots:
            datas, valids = kernels.gather_planes(
                [self.columns[i].data for i in slots],
                [self.columns[i].validity for i in slots],
                indices, cap, n)
            for k, i in enumerate(slots):
                cols[i] = self.columns[i].like(datas[k], valids[k])
        for i, c in enumerate(self.columns):
            if not has_planes(c):
                cols[i] = c.take_host(indices)
        return ColumnarBatch(self.schema, cols, n)

    def take_nullable(self, indices: np.ndarray) -> "ColumnarBatch":
        """Row gather where index -1 yields an all-null row (outer-join null
        extension); the device columns as in :meth:`take`."""
        from blaze_tpu.core import kernels

        indices = np.asarray(indices, dtype=np.int64)
        n = len(indices)
        null_mask = indices < 0
        cap = get_config().capacity_for(n)
        slots = self._device_slots()
        cols: List[Column] = list(self.columns)
        if slots:
            datas, valids = kernels.gather_planes(
                [self.columns[i].data for i in slots],
                [self.columns[i].validity for i in slots],
                np.where(null_mask, 0, indices), cap, n, null_mask=null_mask)
            for k, i in enumerate(slots):
                cols[i] = self.columns[i].like(datas[k], valids[k])
        pa_idx = None
        for i, c in enumerate(self.columns):
            if not has_planes(c):
                if pa_idx is None:
                    pa_idx = pa.Array.from_pandas(
                        np.where(null_mask, 0, indices), mask=null_mask,
                        type=pa.int64())
                cols[i] = HostColumn(c.dtype, c.array.take(pa_idx))
        schema = T.Schema(
            tuple(T.StructField(f.name, f.dtype, True) for f in self.schema.fields)
        ) if null_mask.any() else self.schema
        return ColumnarBatch(schema, cols, n)

    def slice(self, offset: int, length: int) -> "ColumnarBatch":
        """Contiguous row window: ONE jitted dispatch for all device columns,
        a slice copy a plane (no index is built, nothing gathered); zero-copy
        arrow slices for host columns."""
        from blaze_tpu.core import kernels

        length = max(0, min(length, self.num_rows - offset))
        cap = get_config().capacity_for(length)
        slots = self._device_slots()
        cols: List[Column] = list(self.columns)
        if slots:
            datas, valids = kernels.slice_planes(
                [self.columns[i].data for i in slots],
                [self.columns[i].validity for i in slots],
                offset, length, cap)
            for k, i in enumerate(slots):
                cols[i] = self.columns[i].like(datas[k], valids[k])
        for i, c in enumerate(self.columns):
            if not has_planes(c):
                cols[i] = HostColumn(c.dtype, c.array.slice(offset, length))
        return ColumnarBatch(self.schema, cols, length)

    @staticmethod
    def concat(batches: List["ColumnarBatch"], schema: Optional[T.Schema] = None,
               metrics=None) -> "ColumnarBatch":
        """Coalesce small batches (reference: coalesce_batches_unchecked).
        Device planes concatenate+compact in ONE jitted dispatch of slice
        copies — each batch's planes written whole at its row offset, over the
        padding of the one before, nothing gathered; host arrays via arrow
        concat — no arrow round trip for device data (the round-1 profiler's
        top fixed cost). Coded columns over different dictionaries meet
        through one remap table; the rows remapped are added to the calling
        operator's ``metrics`` node as ``dict_remap_rows``."""
        from blaze_tpu.core import kernels

        if not batches:
            if schema is None:
                raise ValueError("concat of zero batches requires a schema")
            return ColumnarBatch.empty(schema)
        batches = [b for b in batches if b.num_rows > 0] or batches[:1]
        if len(batches) == 1:
            only = batches[0]
            return only.to_columnar() if isinstance(only, RowWindow) else only
        schema = schema or batches[0].schema
        # bound the jit fan-in: concatenating thousands of tiny batches in one
        # traced call unrolls into an HLO whose compile time is quadratic-ish
        # in the operand count (minutes at ~6k inputs). A two-level tree keeps
        # every dispatch at <= _CONCAT_FANIN operands, so signatures repeat
        # and compile once per (fan-in, capacities) shape.
        while len(batches) > _CONCAT_FANIN:
            batches = [
                ColumnarBatch.concat(batches[i:i + _CONCAT_FANIN], schema,
                                     metrics)
                for i in range(0, len(batches), _CONCAT_FANIN)
            ]
        # a part is a batch's first rows, or a window of one (RowWindow):
        # the copy then starts at the window's first row
        rows = [b.num_rows for b in batches]
        starts = None
        if all(isinstance(b, RowWindow) for b in batches):
            starts = [b.start for b in batches]
            batches = [b.batch for b in batches]
        else:  # windows among whole batches: rare, so they are cut first
            batches = [b.to_columnar() if isinstance(b, RowWindow) else b
                       for b in batches]
        total = sum(rows)
        cap = get_config().capacity_for(total)
        batches, remapped = _one_dictionary_a_column(batches, rows)
        if metrics is not None and remapped:
            metrics.add("dict_remap_rows", remapped)
        slots = batches[0]._device_slots()
        ncols = len(batches[0].columns)
        cols: List[Column] = [None] * ncols
        if slots:
            datas, valids = kernels.concat_planes(
                [tuple(b.columns[i].data for b in batches) for i in slots],
                [tuple(b.columns[i].validity for b in batches) for i in slots],
                rows, cap, starts)
            for k, i in enumerate(slots):
                cols[i] = batches[0].columns[i].like(datas[k], valids[k])
        for i in range(ncols):
            if cols[i] is None:
                c0 = batches[0].columns[i]
                arrs = [b.columns[i].to_arrow(b.num_rows) for b in batches]
                if len({a.type for a in arrs}) > 1:
                    # mixed dictionary/plain encodings cannot concat raw
                    arrs = [decode_dictionary(a, c0.dtype) for a in arrs]
                cols[i] = HostColumn(c0.dtype, pa.concat_arrays(arrs))
        return ColumnarBatch(schema, cols, total)

    # --- host boundary -------------------------------------------------------

    def by_type(self) -> "ColumnarBatch":
        """The batch with every column where its TYPE says it lives. The one
        column that is ever elsewhere is a decimal wider than int64 that a
        window left as one int64 device plane, every value of it proved to
        fit (ops/window_device.py); it becomes the type's host column here."""
        stray = [i for i, c in enumerate(self.columns)
                 if isinstance(c, DeviceColumn) and T.is_wide_decimal(c.dtype)]
        if not stray:
            return self
        cols = list(self.columns)
        for i in stray:
            cols[i] = HostColumn(cols[i].dtype, cols[i].to_arrow(self.num_rows))
        return ColumnarBatch(self.schema, cols, self.num_rows)

    def coded_to_host(self, metrics) -> "ColumnarBatch":
        """The batch with every coded column as a host column over the same
        dictionary (Arrow's dictionary array): what an operator that does
        not take coded columns is handed (ops/base.Operator.takes_coded),
        and what one that does falls back to. One pull of the code planes;
        no value is touched. Counted as ``host_key_batches`` on ``metrics``,
        the node of the operator that asked."""
        coded = [i for i, c in enumerate(self.columns)
                 if isinstance(c, CodedColumn)]
        if not coded:
            return self
        from blaze_tpu.utils.device import pull_columns

        metrics.add("host_key_batches", 1)
        cols = list(self.columns)
        pulled = pull_columns([cols[i] for i in coded], self.num_rows)
        for i, p in zip(coded, pulled):
            cols[i] = cols[i].to_host(self.num_rows, p)
        return ColumnarBatch(self.schema, cols, self.num_rows)

    def to_arrow(self) -> pa.RecordBatch:
        from blaze_tpu.utils.device import pull_columns

        pulled = pull_columns(self.columns, self.num_rows)
        arrays = [
            c.to_arrow(self.num_rows) if p is None
            else c.to_arrow(self.num_rows, p) if isinstance(c, CodedColumn)
            else _devcol_to_arrow(c.dtype, p[0], p[1], self.num_rows)
            for c, p in zip(self.columns, pulled)
        ]
        return pa.RecordBatch.from_arrays(arrays, schema=T.schema_to_arrow(self.schema))

    def to_arrow_batches(self):
        return [self.to_arrow()]

    def to_pydict(self) -> dict:
        return self.to_arrow().to_pydict()

    def to_pandas(self):
        return self.to_arrow().to_pandas()

    def __repr__(self):
        return f"ColumnarBatch({self.num_rows} rows, schema={self.schema.names})"


@dataclasses.dataclass
class RowWindow:
    """Rows ``[start, start + num_rows)`` of a batch whose columns are all
    device planes, not copied yet: what the device shuffle tier stages for
    a partition. The exchange's program moves a batch's rows into partition
    order once, every partition's rows are then a window of that one batch,
    and the reduce side's concat copies the windows straight into its
    output (``ColumnarBatch.concat``) — no slice a partition on the map
    side, no padding of each slice to its own capacity bucket."""

    batch: ColumnarBatch
    start: int
    num_rows: int

    @property
    def schema(self) -> T.Schema:
        return self.batch.schema

    def to_columnar(self) -> ColumnarBatch:
        """The window as a batch of its own (a slice copy a plane)."""
        return self.batch.slice(self.start, self.num_rows)


@dataclasses.dataclass
class HostBatch:
    """Host-side mirror of a ColumnarBatch: numpy planes for device columns,
    arrow arrays for host columns. The staging form for shuffle
    split/serialize — ONE device pull, then numpy-speed row routing with no
    further device dispatches (reference: BufferedData stages rows host-side
    before the partition-id radix sort, buffered_data.rs:48-541)."""

    schema: T.Schema
    items: list  # per column: (np_data, np_valid) tuple, or pa.Array
    num_rows: int

    @staticmethod
    def from_batch(batch: ColumnarBatch) -> "HostBatch":
        from blaze_tpu.utils.device import pull_columns

        n = batch.num_rows
        pulled = pull_columns(batch.columns, n)
        # a coded column stages as Arrow's dictionary array over the same
        # dictionary: routing takes and slices its indices, the serializer
        # ships codes and one dictionary, and nothing decodes
        items = [
            c.to_arrow(n) if p is None
            else c.to_host(n, p).array if isinstance(c, CodedColumn)
            else (p[0], p[1])
            for c, p in zip(batch.columns, pulled)
        ]
        return HostBatch(batch.schema, items, n)

    def take(self, indices: np.ndarray) -> "HostBatch":
        pa_idx = None
        items = []
        for it in self.items:
            if isinstance(it, tuple):
                items.append((it[0][indices], it[1][indices]))
            else:
                if pa_idx is None:
                    pa_idx = pa.array(np.asarray(indices, dtype=np.int64),
                                      type=pa.int64())
                items.append(it.take(pa_idx))
        return HostBatch(self.schema, items, len(indices))

    def slice(self, offset: int, length: int) -> "HostBatch":
        items = [
            (it[0][offset:offset + length], it[1][offset:offset + length])
            if isinstance(it, tuple) else it.slice(offset, length)
            for it in self.items
        ]
        return HostBatch(self.schema, items, length)

    def to_columnar(self, capacity: Optional[int] = None) -> ColumnarBatch:
        from blaze_tpu.utils.device import stage_span

        cap = capacity or get_config().capacity_for(self.num_rows)
        with stage_span(self.num_rows):
            cols: List[Column] = [
                DeviceColumn.from_numpy(f.dtype, it[0], it[1], cap)
                if isinstance(it, tuple) else _arrow_to_column(it, f.dtype, cap)
                for f, it in zip(self.schema.fields, self.items)
            ]
        return ColumnarBatch(self.schema, cols, self.num_rows)
