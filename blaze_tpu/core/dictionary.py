"""Dictionaries of coded var-width columns (core/batch.CodedColumn).

A coded column's rows are int32 codes on the device; its values are ONE
Arrow array on the host, held by reference and never copied a batch. A
value is touched only here, once a dictionary (or once a pair of them),
each time under a ``dict:*`` host span:

- ``dict:unify``  two or more dictionaries meet (a scan's row groups, a
  concat): one unified dictionary and a remap table a side, built once for
  that tuple of dictionaries; the rows remapped are returned to the
  caller, whose operator counts them (``dict_remap_rows``);
- ``dict:hash``   Spark's murmur3 of the values BY CODE: the running hash of
  a row is the seed of its next column, so a string key's hash is a function
  of (seed, entry) and no per-entry table exists but for a leading key; the
  native kernel hashes each row's entry bytes in place, by its code, with
  the row's seed (``native.murmur3_codes``) — no string is materialized;
- ``dict:rank``   ordering is by value, not by code: one sort of the
  dictionary gives a rank an entry (bytes order, as Spark's UTF8String);
- ``dict:decode`` the result's rows (``to_arrow``), and the large_* form of
  a dictionary.

Identity of a dictionary is its buffers (address, length, offset): Arrow
hands out a fresh Python wrapper for ``arr.dictionary`` each time, over the
same memory. Cached entries hold the array, so an address is never reused
while its entry lives."""

from __future__ import annotations

import collections
import threading
from typing import List, Optional, Sequence, Tuple

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

from blaze_tpu.obs.tracer import TRACER

_CACHE_ENTRIES = 128


def dict_key(d: pa.Array) -> tuple:
    return (str(d.type), len(d), d.offset,
            tuple(b.address if b is not None else 0 for b in d.buffers()))


def same_dictionary(a: pa.Array, b: pa.Array) -> bool:
    return a is b or dict_key(a) == dict_key(b)


class _Cache:
    """Small LRU keyed by dictionary identity; an entry pins its arrays."""

    def __init__(self):
        self._mu = threading.Lock()
        self._entries: "collections.OrderedDict" = collections.OrderedDict()

    def get(self, key):
        with self._mu:
            hit = self._entries.get(key)
            if hit is not None:
                self._entries.move_to_end(key)
            return hit

    def put(self, key, value):
        with self._mu:
            self._entries[key] = value
            while len(self._entries) > _CACHE_ENTRIES:
                self._entries.popitem(last=False)
        return value


_LARGE, _UNIFY, _RANK, _BYTES = _Cache(), _Cache(), _Cache(), _Cache()


def large(d: pa.Array) -> pa.Array:
    """The dictionary as large_utf8 / large_binary (the engine's convention
    for plain var-width arrays), cast once a dictionary."""
    if pa.types.is_large_string(d.type) or pa.types.is_large_binary(d.type):
        return d
    key = dict_key(d)
    hit = _LARGE.get(key)
    if hit is None:
        target = pa.large_binary() if pa.types.is_binary(d.type) or \
            pa.types.is_fixed_size_binary(d.type) else pa.large_utf8()
        with TRACER.detail("decode", "dict", {"entries": len(d)}):
            hit = _LARGE.put(key, (d, d.cast(target)))
    return hit[1]


def unify(dicts: Sequence[pa.Array]) -> Tuple[pa.Array, List[Optional[np.ndarray]]]:
    """One dictionary for several, and for each the int32 table that maps
    its codes into it (None where they stand as they are). Built once for a
    tuple of dictionaries."""
    first = dicts[0]
    keys = tuple(dict_key(d) for d in dicts)
    if all(k == keys[0] for k in keys):
        return first, [None] * len(dicts)
    hit = _UNIFY.get(keys)
    if hit is not None:
        return hit[1], hit[2]
    with TRACER.detail("unify", "dict",
                       {"entries": sum(len(d) for d in dicts)}):
        chunks = [pa.DictionaryArray.from_arrays(
            pa.array(np.arange(len(d), dtype=np.int32)), large(d))
            for d in dicts]
        unified = pa.chunked_array(chunks).unify_dictionaries()
        out = unified.chunk(0).dictionary
        remaps = []
        for d, chunk in zip(dicts, unified.chunks):
            table = chunk.indices.to_numpy(zero_copy_only=False).astype(np.int32)
            identity = len(table) == 0 or bool(
                (table == np.arange(len(table), dtype=np.int32)).all())
            remaps.append(None if identity else table)
    # where every remap is the identity the first (longest-lived) dictionary
    # that already holds every entry stands for all of them
    if all(r is None for r in remaps):
        for d in dicts:
            if len(d) == len(out):
                out = d
                break
    _UNIFY.put(keys, (list(dicts), out, remaps))
    return out, remaps


class OneDictionary:
    """A stream's coded columns over ONE dictionary a column (a scan task's
    row groups, a window's batches): a batch whose dictionary differs from
    the stream's is remapped into their union, built once. The entries seen
    so far stay a prefix of the union, so batches already handed on, and
    codes carried from one batch to the next, stay valid."""

    def __init__(self):
        self.current = {}

    def keep(self, slot, col, num_rows: int):
        """``col`` (a core/batch.CodedColumn) over slot's dictionary, the
        entries that dictionary grew by and the rows whose codes were
        remapped (the caller's ``dict_entries`` and ``dict_remap_rows``)."""
        cur = self.current.get(slot)
        if cur is None:
            self.current[slot] = col.dictionary
            return col, len(col.dictionary), 0
        if same_dictionary(cur, col.dictionary):
            return col, 0, 0
        if cur.equals(col.dictionary):
            return col.remapped(cur, None), 0, 0
        unified, (stays, table) = unify([cur, col.dictionary])
        assert stays is None, "the stream's entries are a prefix of the union"
        self.current[slot] = unified
        return (col.remapped(unified, table), len(unified) - len(cur),
                num_rows if table is not None else 0)


def _bytes_of(d: pa.Array):
    """(int64 offsets, uint8 data) of the dictionary's entries."""
    key = dict_key(d)
    hit = _BYTES.get(key)
    if hit is None:
        arr = large(d)
        if not pa.types.is_large_binary(arr.type):
            arr = arr.cast(pa.large_binary())
        offsets = np.frombuffer(arr.buffers()[1], dtype=np.int64,
                                count=len(arr) + 1, offset=arr.offset * 8)
        dbuf = arr.buffers()[2]
        data = (np.frombuffer(dbuf, dtype=np.uint8) if dbuf is not None
                else np.zeros(0, dtype=np.uint8))
        hit = _BYTES.put(key, (d, arr, offsets, data))
    return hit[2], hit[3]


def murmur3_by_code(d: pa.Array, codes: np.ndarray, valid: Optional[np.ndarray],
                    seeds: np.ndarray) -> np.ndarray:
    """Spark's murmur3 of each row's VALUE, seeded by the row's running
    hash, read from the dictionary in place by the row's code. Rows that are
    not valid keep their seed (a NULL leaves the hash unchanged)."""
    from blaze_tpu.utils import native

    with TRACER.detail("hash", "dict", {"rows": len(codes), "entries": len(d)}):
        offsets, data = _bytes_of(d)
        return native.murmur3_codes(codes, valid, offsets, data, seeds)


def rank(d: pa.Array) -> np.ndarray:
    """int32 rank of every entry in value order (bytes, unsigned, shorter
    first on a shared prefix: Spark's UTF8String / binary comparison); equal
    values share a rank. One sort of the dictionary, once."""
    key = dict_key(d)
    hit = _RANK.get(key)
    if hit is None:
        with TRACER.detail("rank", "dict", {"entries": len(d)}):
            arr = large(d)
            if not pa.types.is_large_binary(arr.type):
                arr = arr.cast(pa.large_binary())
            ranks = pc.rank(arr, sort_keys="ascending", tiebreaker="dense")
            out = ranks.to_numpy(zero_copy_only=False).astype(np.int32) - 1
        hit = _RANK.put(key, (d, out))
    return hit[1]


def decode(d: pa.Array, codes: np.ndarray, valid: Optional[np.ndarray]) -> pa.Array:
    """The rows' values as a plain large_* array: where a value is needed."""
    with TRACER.detail("decode", "dict", {"rows": len(codes)}):
        return dictionary_array(d, codes, valid).cast(large(d).type)


def dictionary_array(d: pa.Array, codes: np.ndarray,
                     valid: Optional[np.ndarray]) -> pa.DictionaryArray:
    """Host form of coded rows: Arrow's dictionary array over the same
    dictionary (no value is touched)."""
    mask = None if valid is None or valid.all() else ~valid
    indices = pa.array(np.ascontiguousarray(codes, dtype=np.int32), mask=mask)
    return pa.DictionaryArray.from_arrays(indices, large(d))
