"""Spark-exact hash functions: Murmur3_x86_32 (seed 42) and XXH64 (seed 42).

Bit-exactness is mandatory — hash partition routing and hash joins depend on
it (reference: ``datafusion-ext-commons/src/spark_hash.rs``, ``hash/mur.rs``,
``hash/xxhash.rs``; golden vectors in ``spark_hash.rs`` tests are generated
with Spark's ``Murmur3Hash(...).eval()`` / ``XxHash64(...).eval()``).

Semantics (matching Spark's ``hashUnsafeBytes``/``hashLong``/``hashInt``):

- multi-column hashing chains: each row's running hash is the seed for the
  next column; NULL values leave the hash unchanged
- fixed-width values hash as their little-endian bytes: int8/16/32/date/bool
  promote to 4-byte int; int64/timestamp/double are 8 bytes; float is 4
- decimal(p<=18) hashes its unscaled int64 as 8 LE bytes (Spark hashLong)
- byte strings: 4-byte LE words, then each tail byte *sign-extended* through
  a full mix round (murmur3); xxhash64 follows the standard XXH64 tail rules
  with unsigned bytes

Two implementations: jax (device columns, vectorized uint32/uint64 ops that
wrap mod 2^32/2^64 — VPU-friendly, no MXU needed) and numpy (host var-width
columns).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

_C1 = 0xCC9E2D51
_C2 = 0x1B873593

# --------------------------------------------------------------------------
# Murmur3_x86_32 — jax (device)
# --------------------------------------------------------------------------


def _u32(x):
    return x.astype(jnp.uint32)


def _rotl32(x, r: int):
    return (x << r) | (x >> (32 - r))


def _mix_k1(k1):
    k1 = k1 * jnp.uint32(_C1)
    k1 = _rotl32(k1, 15)
    return k1 * jnp.uint32(_C2)


def _mix_h1(h1, k1):
    h1 = h1 ^ k1
    h1 = _rotl32(h1, 13)
    return h1 * jnp.uint32(5) + jnp.uint32(0xE6546B64)


def _fmix(h1, length):
    h1 = h1 ^ length
    h1 = h1 ^ (h1 >> 16)
    h1 = h1 * jnp.uint32(0x85EBCA6B)
    h1 = h1 ^ (h1 >> 13)
    h1 = h1 * jnp.uint32(0xC2B2AE35)
    return h1 ^ (h1 >> 16)


def murmur3_int32(values, seeds):
    """hashInt: values int32-like array, seeds uint32 array -> uint32."""
    w = _u32(values.astype(jnp.int32))
    return _fmix(_mix_h1(_u32(seeds), _mix_k1(w)), jnp.uint32(4))


def murmur3_int64(values, seeds):
    """hashLong: low word then high word."""
    v = values.astype(jnp.int64)
    lo = _u32(v.astype(jnp.uint64) & jnp.uint64(0xFFFFFFFF))
    hi = _u32((v.astype(jnp.uint64) >> jnp.uint64(32)) & jnp.uint64(0xFFFFFFFF))
    h = _mix_h1(_u32(seeds), _mix_k1(lo))
    h = _mix_h1(h, _mix_k1(hi))
    return _fmix(h, jnp.uint32(8))


def murmur3_update_column(hashes, data, validity, dtype_kind: str):
    """One column's contribution to the running row hashes (uint32).

    dtype_kind: "i32" (int8/16/32/date/bool promoted), "i64"
    (int64/timestamp/decimal), "f32", "f64".
    """
    if dtype_kind == "f32":
        word = data.view(jnp.int32) if data.dtype == jnp.float32 else data.astype(jnp.float32).view(jnp.int32)
        new = murmur3_int32(word, hashes)
    elif dtype_kind == "f64":
        word = data.view(jnp.int64) if data.dtype == jnp.float64 else data.astype(jnp.float64).view(jnp.int64)
        new = murmur3_int64(word, hashes)
    elif dtype_kind == "i64":
        new = murmur3_int64(data, hashes)
    else:
        new = murmur3_int32(data, hashes)
    return jnp.where(validity, new, hashes)


# --------------------------------------------------------------------------
# Murmur3_x86_32 — numpy (host, incl. variable-length bytes)
# --------------------------------------------------------------------------


def _np_rotl32(x, r):
    return (x << np.uint32(r)) | (x >> np.uint32(32 - r))


def _np_mix_k1(k1):
    k1 = k1 * np.uint32(_C1)
    k1 = _np_rotl32(k1, 15)
    return k1 * np.uint32(_C2)


def _np_mix_h1(h1, k1):
    h1 = h1 ^ k1
    h1 = _np_rotl32(h1, 13)
    return h1 * np.uint32(5) + np.uint32(0xE6546B64)


def _np_fmix(h1, length):
    h1 = h1 ^ length
    h1 = h1 ^ (h1 >> np.uint32(16))
    h1 = h1 * np.uint32(0x85EBCA6B)
    h1 = h1 ^ (h1 >> np.uint32(13))
    h1 = h1 * np.uint32(0xC2B2AE35)
    return h1 ^ (h1 >> np.uint32(16))


def murmur3_int32_np(values, seeds):
    w = values.astype(np.int32).view(np.uint32)
    return _np_fmix(_np_mix_h1(seeds.astype(np.uint32), _np_mix_k1(w)), np.uint32(4))


def murmur3_int64_np(values, seeds):
    v = values.astype(np.int64).view(np.uint64)
    lo = (v & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    hi = (v >> np.uint64(32)).astype(np.uint32)
    h = _np_mix_h1(seeds.astype(np.uint32), _np_mix_k1(lo))
    h = _np_mix_h1(h, _np_mix_k1(hi))
    return _np_fmix(h, np.uint32(8))


def murmur3_bytes_np(offsets: np.ndarray, data: np.ndarray, seeds: np.ndarray) -> np.ndarray:
    """Spark hashUnsafeBytes over n variable-length byte strings, on the
    host, by the native kernel (native/src/blaze_native.cc).

    offsets: int64 (n+1), data: uint8 concatenated bytes, seeds: uint32 (n,).
    """
    from blaze_tpu.utils import native

    return native.murmur3_bytes(offsets, data, seeds)


# --------------------------------------------------------------------------
# XXH64 — jax (device) and numpy (host)
# --------------------------------------------------------------------------

_P1 = 0x9E3779B185EBCA87
_P2 = 0xC2B2AE3D27D4EB4F
_P3 = 0x165667B19E3779F9
_P4 = 0x85EBCA77C2B2AE63
_P5 = 0x27D4EB2F165667C5


def _rotl64(x, r: int):
    return (x << r) | (x >> (64 - r))


def xxhash64_int64(values, seeds):
    """XXH64 of the 8 LE bytes of each int64, per-row uint64 seeds."""
    u = lambda c: jnp.uint64(c)  # noqa: E731
    v = values.astype(jnp.int64).view(jnp.uint64)
    acc = seeds.astype(jnp.uint64) + u(_P5) + u(8)
    k1 = _rotl64(v * u(_P2), 31) * u(_P1)
    acc = acc ^ k1
    acc = _rotl64(acc, 27) * u(_P1) + u(_P4)
    acc = (acc ^ (acc >> 33)) * u(_P2)
    acc = (acc ^ (acc >> 29)) * u(_P3)
    return acc ^ (acc >> 32)


def xxhash64_int32(values, seeds):
    """XXH64 of the 4 LE bytes of each int32 (Spark promotes small ints)."""
    u = lambda c: jnp.uint64(c)  # noqa: E731
    v = values.astype(jnp.int32).view(jnp.uint32).astype(jnp.uint64)
    acc = seeds.astype(jnp.uint64) + u(_P5) + u(4)
    acc = acc ^ (v * u(_P1))
    acc = _rotl64(acc, 23) * u(_P2) + u(_P3)
    acc = (acc ^ (acc >> 33)) * u(_P2)
    acc = (acc ^ (acc >> 29)) * u(_P3)
    return acc ^ (acc >> 32)


def xxhash64_update_column(hashes, data, validity, dtype_kind: str):
    if dtype_kind == "f32":
        new = xxhash64_int32(data.view(jnp.int32), hashes)
    elif dtype_kind == "f64":
        new = xxhash64_int64(data.view(jnp.int64), hashes)
    elif dtype_kind == "i64":
        new = xxhash64_int64(data, hashes)
    else:
        new = xxhash64_int32(data, hashes)
    return jnp.where(validity, new, hashes)


def _np_rotl64(x, r):
    return (x << np.uint64(r)) | (x >> np.uint64(64 - r))


def xxhash64_int64_np(values, seeds):
    with np.errstate(over="ignore"):
        v = values.astype(np.int64).view(np.uint64)
        acc = seeds.astype(np.uint64) + np.uint64(_P5) + np.uint64(8)
        k1 = _np_rotl64(v * np.uint64(_P2), 31) * np.uint64(_P1)
        acc = acc ^ k1
        acc = _np_rotl64(acc, 27) * np.uint64(_P1) + np.uint64(_P4)
        acc = (acc ^ (acc >> np.uint64(33))) * np.uint64(_P2)
        acc = (acc ^ (acc >> np.uint64(29))) * np.uint64(_P3)
        return acc ^ (acc >> np.uint64(32))


def xxhash64_int32_np(values, seeds):
    with np.errstate(over="ignore"):
        v = values.astype(np.int32).view(np.uint32).astype(np.uint64)
        acc = seeds.astype(np.uint64) + np.uint64(_P5) + np.uint64(4)
        acc = acc ^ (v * np.uint64(_P1))
        acc = _np_rotl64(acc, 23) * np.uint64(_P2) + np.uint64(_P3)
        acc = (acc ^ (acc >> np.uint64(33))) * np.uint64(_P2)
        acc = (acc ^ (acc >> np.uint64(29))) * np.uint64(_P3)
        return acc ^ (acc >> np.uint64(32))


def xxhash64_bytes_np(offsets: np.ndarray, data: np.ndarray, seeds: np.ndarray) -> np.ndarray:
    """Standard XXH64 over n variable-length byte strings (Spark XXH64), on
    the host, by the native kernel (native/src/blaze_native.cc)."""
    from blaze_tpu.utils import native

    return native.xxh64_bytes(offsets, data, seeds)


# --------------------------------------------------------------------------
# Batch-level hashing (mixed device/host columns)
# --------------------------------------------------------------------------


def _dtype_is_fixed(dt) -> bool:
    from blaze_tpu.ir import types as T

    if isinstance(dt, T.DecimalType):
        return dt.fits_int64
    return dt.is_fixed_width


def _host_fixed_words(arr, dt):
    """pa fixed-width array -> (word array for hashing, validity)."""
    import pyarrow as pa

    from blaze_tpu.ir import types as T

    validity = ~np.asarray(arr.is_null()) if arr.null_count else np.ones(len(arr), bool)
    fill = False if pa.types.is_boolean(arr.type) else 0
    vals = arr.fill_null(fill).to_numpy(zero_copy_only=False)
    if np.issubdtype(vals.dtype, np.datetime64):
        if isinstance(dt, T.DateType):
            vals = vals.astype("datetime64[D]").view(np.int64).astype(np.int32)
        else:
            vals = vals.astype("datetime64[us]").view(np.int64)
    elif isinstance(dt, T.DecimalType):
        vals = np.array([int(d.scaleb(dt.scale)) if d is not None else 0
                         for d in arr.to_pylist()], dtype=np.int64)
    elif vals.dtype == np.bool_:
        vals = vals.astype(np.int32)
    elif vals.dtype == np.float64:
        vals = vals.view(np.int64)
    elif vals.dtype == np.float32:
        vals = vals.view(np.int32)
    return vals, validity


def _dtype_kind(dt) -> str:
    from blaze_tpu.ir import types as T

    if isinstance(dt, (T.Float32Type,)):
        return "f32"
    if isinstance(dt, (T.Float64Type,)):
        return "f64"
    if isinstance(dt, (T.Int64Type, T.TimestampType, T.DecimalType)):
        return "i64"
    return "i32"


@functools.partial(jax.jit, static_argnames=("kinds", "is64"))
def _hash_device_run(h, datas, valids, kinds, is64):
    """Fold a run of device columns into the running hashes in one dispatch."""
    for d, v, kind in zip(datas, valids, kinds):
        if is64:
            h = xxhash64_update_column(h, d, v, kind)
        else:
            h = murmur3_update_column(h, d, v, kind)
    return h


def hash_batch(columns, num_rows: int, capacity: int, seed: int = 42,
               algo: str = "murmur3"):
    """Hash a list of core Columns (device or host) into per-row hashes.

    Returns a numpy array of length ``num_rows``: int32 for murmur3, int64
    for xxhash64. Device columns are hashed on device; host (string/binary)
    columns force a host pass over the running hashes.
    """
    import pyarrow as pa

    from blaze_tpu.core.batch import (CodedColumn, DeviceColumn, HostColumn,
                                      decode_dictionary)

    is64 = algo == "xxhash64"
    h_dev: Optional[jnp.ndarray] = None
    h_host: Optional[np.ndarray] = None

    def to_host():
        nonlocal h_host, h_dev
        if h_host is None:
            h_host = np.asarray(h_dev)[:num_rows].copy() if h_dev is not None else np.full(
                num_rows, seed, dtype=np.uint64 if is64 else np.uint32
            )
            h_dev = None
        return h_host

    def to_dev():
        nonlocal h_host, h_dev
        if h_dev is None:
            if h_host is not None:
                buf = np.zeros(capacity, dtype=h_host.dtype)
                buf[:num_rows] = h_host
                h_dev = jnp.asarray(buf)
                h_host = None
            else:
                h_dev = jnp.full(capacity, seed, dtype=jnp.uint64 if is64 else jnp.uint32)
        return h_dev

    # consecutive device columns hash in ONE jitted dispatch (the eager
    # per-op murmur3 chain was a profiler hotspot: ~15 dispatches per column)
    i = 0
    while i < len(columns):
        col = columns[i]
        if isinstance(col, DeviceColumn):
            run = []
            while i < len(columns) and isinstance(columns[i], DeviceColumn):
                run.append(columns[i])
                i += 1
            h_dev = _hash_device_run(
                to_dev(),
                tuple(c.data for c in run),
                tuple(c.validity for c in run),
                tuple(_dtype_kind(c.dtype) for c in run),
                is64)
            continue
        i += 1
        if isinstance(col, CodedColumn) and not is64:
            # Spark's murmur3 of the VALUE by code: the dictionary's entry
            # bytes hashed in place with the row's running hash as seed
            from blaze_tpu.core import dictionary as D

            h = to_host()
            h_host = D.murmur3_by_code(
                col.dictionary, np.asarray(col.data[:num_rows]),
                np.asarray(col.validity[:num_rows]), h)
            continue
        if isinstance(col, CodedColumn):
            col = col.to_host(num_rows)
        if isinstance(col, HostColumn):
            h = to_host()
            arr = col.array
            if pa.types.is_dictionary(arr.type):
                arr = decode_dictionary(arr, col.dtype)
            from blaze_tpu.ir import types as T

            if pa.types.is_decimal(arr.type):
                # Spark hashes wide decimals (p > 18) as the minimal
                # big-endian two's-complement bytes of the unscaled
                # BigInteger (java BigInteger.toByteArray)
                scale = arr.type.scale
                chunks, validity = [], []
                for d in arr.to_pylist():
                    if d is None:
                        validity.append(False)
                        chunks.append(b"")
                    else:
                        validity.append(True)
                        u = int(d.scaleb(scale))
                        nbytes = (u + (u < 0)).bit_length() // 8 + 1
                        chunks.append(u.to_bytes(nbytes, "big", signed=True))
                offsets = np.zeros(len(chunks) + 1, dtype=np.int64)
                np.cumsum([len(b) for b in chunks], out=offsets[1:])
                data = np.frombuffer(b"".join(chunks), dtype=np.uint8)
                validity = np.array(validity, dtype=bool)
                if is64:
                    new = xxhash64_bytes_np(offsets, data, h)
                else:
                    new = murmur3_bytes_np(offsets, data, h)
                h_host = np.where(validity, new, h)
                continue
            if _dtype_is_fixed(col.dtype):
                # fixed-width values living on host (agg keys, f64-on-tpu)
                vals, validity = _host_fixed_words(arr, col.dtype)
                kind = _dtype_kind(col.dtype)
                if is64:
                    new = (xxhash64_int64_np(vals, h) if kind in ("i64", "f64")
                           else xxhash64_int32_np(vals, h))
                else:
                    new = (murmur3_int64_np(vals, h) if kind in ("i64", "f64")
                           else murmur3_int32_np(vals, h))
                h_host = np.where(validity, new, h)
                continue
            if not (pa.types.is_large_string(arr.type) or pa.types.is_large_binary(arr.type)):
                arr = arr.cast(pa.large_binary())
            offsets = np.frombuffer(arr.buffers()[1], dtype=np.int64,
                                    count=len(arr) + 1, offset=arr.offset * 8)
            dbuf = arr.buffers()[2]
            data = (np.frombuffer(dbuf, dtype=np.uint8) if dbuf is not None
                    else np.zeros(0, dtype=np.uint8))
            validity = ~np.asarray(arr.is_null()) if arr.null_count else np.ones(len(arr), bool)
            if is64:
                new = xxhash64_bytes_np(offsets, data, h)
            else:
                new = murmur3_bytes_np(offsets, data, h)
            h_host = np.where(validity, new, h)

    if h_host is not None:
        out = h_host
    else:
        out = np.asarray(h_dev)[:num_rows]
    return out.view(np.int64 if is64 else np.int32)
