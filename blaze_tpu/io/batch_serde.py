"""Compact batch serialization for shuffle and spill streams.

Reference: ``datafusion-ext-commons/src/io/batch_serde.rs`` — a custom
non-IPC format with optional **byte-plane transpose** of fixed-width columns
(TransposeOpt) to boost lz4/zstd ratios, framed inside compressed streams
(``common/ipc_compression.rs``). Here:

- fixed-width (device) columns serialize as raw little-endian planes
  (optionally byte-transposed) + packed validity bitmaps;
- var-width/nested (host) columns serialize as Arrow IPC;
- each batch is one length-prefixed frame, zstd- or lz4-compressed (codec
  from config; lz4 rides the native lib's dlopen of liblz4.so.1 — the
  python binding is absent in this environment).
"""

from __future__ import annotations

import io
import json
import struct
from typing import BinaryIO, Iterator, List, Optional

import numpy as np
import pyarrow as pa

try:
    import zstandard
except ImportError:  # python binding absent: native-lib zstd still serves
    zstandard = None  # when built, else frames degrade to stdlib zlib

from blaze_tpu.config import get_config
from blaze_tpu.core.batch import ColumnarBatch, DeviceColumn, HostColumn, pack_bitmap, unpack_bitmap
from blaze_tpu.ir import types as T
from blaze_tpu.ir.serde import schema_from_json, schema_to_json
from blaze_tpu.utils.device import stage_span

_MAGIC = b"BTB1"

_TM_CODES = None


def _codes_counter():
    global _TM_CODES
    if _TM_CODES is None:
        from blaze_tpu.obs.telemetry import get_registry

        _TM_CODES = get_registry().counter(
            "blaze_agg_codes_shuffle_bytes",
            "bytes shipped as dictionary codes instead of decoded values")
    return _TM_CODES


def dict_identity(dictionary: pa.Array) -> tuple:
    """Stable identity of a dictionary's backing MEMORY. ``take``/``slice``
    of a dictionary column produce fresh python wrappers around the same
    dictionary buffers, so ``id()`` misses exactly where sharing matters
    (per-partition sub-batches of one bucketized batch); buffer addresses
    don't. Safe only while a reference to some wrapper is held (the
    registry entry holds one), which pins the buffers against reuse."""
    from blaze_tpu.core.dictionary import dict_key

    return dict_key(dictionary)


class DictEncodeContext:
    """Per-stream dictionary ref registry (code-carrying shuffle).

    Dictionary-encoded host columns serialize as their CODES in the main
    IPC block plus a stream-scoped dictionary reference: the first frame
    using a dictionary carries it (once), later frames of the same stream
    reference it by number. The registry is keyed by the dictionary's
    backing-buffer identity — the agg table's partial emission shares one
    dictionary across all its sliced/bucketized batches, so a map task's
    keys cross the exchange as one dictionary plus int codes per batch.
    """

    def __init__(self):
        self.refs = {}  # dict_identity -> (dictionary, ref)
        self.next_ref = 0
        self.codes_bytes = 0  # bytes shipped as codes+dicts vs decoded


class DictDecodeContext:
    """Per-stream ref -> dictionary registry on the read side. Decoded
    dictionaries are reused BY OBJECT across every frame that references
    them, so the final agg table's ``_gid_of_values`` identity cache
    translates each incoming dictionary exactly once per stream."""

    def __init__(self):
        self.refs = {}  # ref -> pa.Array


def _maybe_dict_ref(arr, meta: dict, ctx: DictEncodeContext, new_dicts,
                    n: int):
    """Swap a dictionary column for (codes, ref) when profitable."""
    if isinstance(arr, pa.ChunkedArray):
        if arr.num_chunks != 1:
            return arr, meta  # multi-chunk: dictionaries differ per chunk
        arr = arr.chunk(0)
    if not isinstance(arr, pa.DictionaryArray):
        return arr, meta
    d = arr.dictionary
    if dict_identity(d) not in ctx.refs and len(d) > max(4096, 8 * n):
        # oversized shared dictionary (e.g. a whole-file dict behind a
        # heavily filtered batch): re-encode compactly per frame instead
        # of shipping the big dictionary once per stream. The threshold is
        # deliberately loose — a registered dictionary costs nothing on
        # later frames, and an agg emission's dictionary spans all reducer
        # frames sliced from it (len(d) ~ fan_out * n is the normal case,
        # not a pathology) — so only a dictionary dwarfing its first frame
        # is pruned.
        try:
            arr = arr.cast(arr.type.value_type).dictionary_encode()
        except (pa.ArrowInvalid, pa.ArrowNotImplementedError):
            pass
        return arr, meta
    dkey = dict_identity(d)
    ent = ctx.refs.get(dkey)
    if ent is not None:
        ref = ent[1]
    else:
        ref = ctx.next_ref
        ctx.next_ref += 1
        ctx.refs[dkey] = (d, ref)  # holding d pins the buffer addresses
        new_dicts.append((ref, d))
    meta = dict(meta, dict_ref=ref)
    ctx.codes_bytes += max(n, 1) * max(arr.type.index_type.bit_width // 8, 1)
    return arr.indices, meta


def _staged_if_coded(batch):
    """A batch with coded columns serializes from its staged form (one pull;
    the codes ship as Arrow's dictionary arrays over the same dictionary)."""
    from blaze_tpu.core.batch import CodedColumn, HostBatch

    if isinstance(batch, ColumnarBatch) and any(
            isinstance(c, CodedColumn) for c in batch.columns):
        return HostBatch.from_batch(batch)
    return batch


def _host_or_coded(arr, dt, capacity: int):
    """A decoded host array as its column: coded where it arrived as codes
    and a dictionary (the codes go back up; the dictionary is held by
    reference), else the host column."""
    from blaze_tpu.core.batch import _arrow_to_column

    if isinstance(arr, pa.ChunkedArray):
        arr = arr.combine_chunks()
    if pa.types.is_dictionary(arr.type):
        return _arrow_to_column(arr, dt, capacity)
    return HostColumn(dt, arr)


def serialize_batch(batch, transpose: Optional[bool] = None,
                    dict_ctx: Optional[DictEncodeContext] = None) -> bytes:
    """One batch (ColumnarBatch or HostBatch) -> uncompressed payload bytes.
    A HostBatch serializes with zero device traffic (the shuffle writer pulls
    once per input batch, then routes rows host-side)."""
    from blaze_tpu.core.batch import HostBatch

    cfg = get_config()
    if transpose is None:
        transpose = cfg.serde_transpose

    n = batch.num_rows
    batch = _staged_if_coded(batch)
    if isinstance(batch, HostBatch):
        pulled = [it if isinstance(it, tuple) else None for it in batch.items]
        host_arrays = {i: it for i, it in enumerate(batch.items)
                       if not isinstance(it, tuple)}
    else:
        from blaze_tpu.utils.device import pull_columns

        pulled = pull_columns(batch.columns, n)  # one transfer for all columns
        host_arrays = {i: c.to_arrow(n) for i, c in enumerate(batch.columns)
                       if pulled[i] is None}
    buffers: List[bytes] = []
    cols_meta = []
    host_cols = []
    host_idx = []
    new_dicts: List[tuple] = []  # (ref, dictionary) first seen this frame
    for i in range(len(batch.schema)):
        if pulled[i] is not None:
            data = np.ascontiguousarray(pulled[i][0])
            validity = pulled[i][1]
            if transpose and data.dtype.itemsize > 1 and n:
                from blaze_tpu.utils import native

                buffers.append(native.transpose(
                    data, n, data.dtype.itemsize, forward=True).tobytes())
            else:
                buffers.append(data.view(np.uint8).tobytes())
            buffers.append(np.packbits(validity.astype(np.uint8), bitorder="little").tobytes())
            cols_meta.append({"kind": "dev", "transposed": bool(transpose and data.dtype.itemsize > 1)})
        else:
            host_idx.append(i)
            arr = host_arrays[i]
            meta = {"kind": "host"}
            if dict_ctx is not None:
                arr, meta = _maybe_dict_ref(arr, meta, dict_ctx, new_dicts, n)
            host_cols.append(arr)
            cols_meta.append(meta)
    if host_cols:
        sink = io.BytesIO()
        arrays = [a.combine_chunks() if isinstance(a, pa.ChunkedArray) else a
                  for a in host_cols]
        # positional synthetic names: output schemas (e.g. join left++right)
        # may repeat a field name, and a name-keyed restore would alias the
        # duplicates to one IPC column after a shuffle/spill round trip
        hschema = pa.schema(
            [pa.field(f"h{k}", arrays[k].type) for k in range(len(host_idx))]
        )
        rb = pa.RecordBatch.from_arrays(arrays, schema=hschema)
        with pa.ipc.new_stream(sink, hschema) as w:
            w.write_batch(rb)
        ipc_bytes = sink.getvalue()
    else:
        ipc_bytes = b""
    dict_streams: List[tuple] = []
    for ref, d in new_dicts:
        sink = io.BytesIO()
        dschema = pa.schema([pa.field("d", d.type)])
        with pa.ipc.new_stream(sink, dschema) as w:
            w.write_batch(pa.RecordBatch.from_arrays([d], schema=dschema))
        db = sink.getvalue()
        dict_streams.append((ref, db))
        dict_ctx.codes_bytes += len(db)
    hdr = {"schema": schema_to_json(batch.schema), "num_rows": n,
           "cols": cols_meta, "ipc_len": len(ipc_bytes)}
    if dict_streams:
        hdr["dicts"] = [{"ref": r, "len": len(b)} for r, b in dict_streams]
    header = json.dumps(hdr).encode()
    out = io.BytesIO()
    out.write(struct.pack("<I", len(header)))
    out.write(header)
    out.write(ipc_bytes)
    for _r, b in dict_streams:
        out.write(b)
    for b in buffers:
        out.write(struct.pack("<Q", len(b)))
        out.write(b)
    return out.getvalue()


def deserialize_batch(payload,
                      dict_ctx: Optional[DictDecodeContext] = None
                      ) -> ColumnarBatch:
    cfg = get_config()
    buf = payload if isinstance(payload, memoryview) else memoryview(payload)
    (hlen,) = struct.unpack_from("<I", buf, 0)
    header = json.loads(bytes(buf[4 : 4 + hlen]).decode())
    pos = 4 + hlen
    schema = schema_from_json(header["schema"])
    n = header["num_rows"]
    cap = cfg.capacity_for(n)
    ipc_len = header["ipc_len"]
    host_arrays: List[pa.Array] = []
    if ipc_len:
        # py_buffer over the view, not bytes(): arrow reads IPC in place, so
        # an uncompressed frame served off an mmap'd segment decodes with no
        # payload copy at all (the consumer's refs pin the source buffer)
        reader = pa.ipc.open_stream(pa.py_buffer(buf[pos : pos + ipc_len]))
        rb = reader.read_next_batch()
        host_arrays = list(rb.columns)  # positional, matches "host" meta order
    pos += ipc_len
    dict_refs = dict_ctx.refs if dict_ctx is not None else {}
    for dm in header.get("dicts", ()):
        dbuf = pa.py_buffer(buf[pos : pos + dm["len"]])
        pos += dm["len"]
        darr = pa.ipc.open_stream(dbuf).read_next_batch().column(0)
        if isinstance(darr, pa.ChunkedArray):
            darr = darr.combine_chunks()
        dict_refs[dm["ref"]] = darr

    def read_buf():
        # memoryview slice, not bytes(): plane decode below views it via
        # np.frombuffer, which keeps the view (and its source) alive
        nonlocal pos
        (blen,) = struct.unpack_from("<Q", buf, pos)
        pos += 8
        b = buf[pos : pos + blen]
        pos += blen
        return b

    from blaze_tpu.core.batch import device_columns

    cols: List = [None] * len(header["cols"])
    next_host = 0
    dev_items, dev_slots = [], []
    for i, meta in enumerate(header["cols"]):
        f = schema[i]
        if meta["kind"] == "dev":
            raw = read_buf()
            vraw = read_buf()
            npdt = f.dtype.np_dtype
            itemsize = npdt.itemsize
            arr = np.frombuffer(raw, dtype=np.uint8)
            if meta["transposed"] and n:
                from blaze_tpu.utils import native

                arr = native.transpose(arr, n, itemsize, forward=False)
            data = arr.view(npdt).reshape(n) if n else np.zeros(0, dtype=npdt)
            validity = unpack_bitmap(vraw, n) if n else np.zeros(0, dtype=bool)
            dev_items.append((f.dtype, data, validity))
            dev_slots.append(i)
        else:
            arr = host_arrays[next_host]
            next_host += 1
            ref = meta.get("dict_ref")
            if ref is not None:
                d = dict_refs.get(ref)
                if d is None:
                    raise RuntimeError(
                        f"frame references dictionary {ref} but no decode "
                        "context carries it (out-of-order decode?)")
                if isinstance(arr, pa.ChunkedArray):
                    arr = arr.combine_chunks()
                arr = pa.DictionaryArray.from_arrays(arr, d)
            cols[i] = _host_or_coded(arr, f.dtype, cap)
    # all device planes of the batch ride one batched device_put
    with stage_span(n):
        for slot, col in zip(dev_slots, device_columns(dev_items, cap)):
            cols[slot] = col
    return ColumnarBatch(schema, cols, n)


def serialize_batch_raw(batch,
                        dict_ctx: Optional[DictEncodeContext] = None
                        ) -> bytes:
    """One batch -> RAW mappable payload (zero-copy data plane, tier shm).

    Layout: u32 header-json length, header json, arrow-IPC host block,
    stream-dictionary blocks, zero pad to the 64-aligned planes block, then
    per fixed-width column a CAPACITY-length little-endian data plane (zero
    tail past num_rows) and, only for columns with nulls, a raw bool
    validity plane — each plane at a 64-aligned offset recorded in the
    header RELATIVE to the planes-block start. The planes-block start is
    not recorded: readers recompute it from the prefix lengths, so the
    header never depends on its own encoded size. Host columns keep the
    exact classic IPC + dictionary-ref machinery (codes shuffle included).
    The payload is padded so header+payload is a RAW_ALIGN multiple."""
    from blaze_tpu.core.batch import HostBatch

    n = batch.num_rows
    cap = get_config().capacity_for(n)
    batch = _staged_if_coded(batch)
    if isinstance(batch, HostBatch):
        pulled = [it if isinstance(it, tuple) else None for it in batch.items]
        host_arrays = {i: it for i, it in enumerate(batch.items)
                       if not isinstance(it, tuple)}
    else:
        from blaze_tpu.utils.device import pull_columns

        pulled = pull_columns(batch.columns, n)
        host_arrays = {i: c.to_arrow(n) for i, c in enumerate(batch.columns)
                       if pulled[i] is None}
    planes: List[tuple] = []  # (rel_off, np buffer)
    cols_meta = []
    host_cols = []
    host_idx = []
    new_dicts: List[tuple] = []
    rel = 0
    for i in range(len(batch.schema)):
        f = batch.schema[i]
        if pulled[i] is not None:
            data, validity = pulled[i]
            npdt = f.dtype.np_dtype
            buf = np.zeros(cap, dtype=npdt)
            np.copyto(buf[:n], data, casting="unsafe")
            meta = {"kind": "dev", "off": rel}
            planes.append((rel, buf))
            rel = _align_up(rel + buf.nbytes)
            if validity is not None and not validity.all():
                # padded tail stays validity=False, data=0 — the engine-wide
                # padding discipline, preserved bit-for-bit through the map
                vbuf = np.zeros(cap, dtype=bool)
                vbuf[:n] = validity
                np.copyto(buf[:n], np.where(validity, data,
                                            np.zeros((), npdt)),
                          casting="unsafe")
                meta["voff"] = rel
                planes.append((rel, vbuf))
                rel = _align_up(rel + vbuf.nbytes)
            cols_meta.append(meta)
        else:
            host_idx.append(i)
            arr = host_arrays[i]
            meta = {"kind": "host"}
            if dict_ctx is not None:
                arr, meta = _maybe_dict_ref(arr, meta, dict_ctx, new_dicts, n)
            host_cols.append(arr)
            cols_meta.append(meta)
    if host_cols:
        sink = io.BytesIO()
        arrays = [a.combine_chunks() if isinstance(a, pa.ChunkedArray) else a
                  for a in host_cols]
        hschema = pa.schema(
            [pa.field(f"h{k}", arrays[k].type) for k in range(len(host_idx))]
        )
        rb = pa.RecordBatch.from_arrays(arrays, schema=hschema)
        with pa.ipc.new_stream(sink, hschema) as w:
            w.write_batch(rb)
        ipc_bytes = sink.getvalue()
    else:
        ipc_bytes = b""
    dict_streams: List[tuple] = []
    for ref, d in new_dicts:
        sink = io.BytesIO()
        dschema = pa.schema([pa.field("d", d.type)])
        with pa.ipc.new_stream(sink, dschema) as w:
            w.write_batch(pa.RecordBatch.from_arrays([d], schema=dschema))
        db = sink.getvalue()
        dict_streams.append((ref, db))
        dict_ctx.codes_bytes += len(db)
    hdr = {"schema": schema_to_json(batch.schema), "num_rows": n, "cap": cap,
           "cols": cols_meta, "ipc_len": len(ipc_bytes)}
    if dict_streams:
        hdr["dicts"] = [{"ref": r, "len": len(b)} for r, b in dict_streams]
    header = json.dumps(hdr).encode()
    prefix = 4 + len(header) + len(ipc_bytes) + sum(
        len(b) for _r, b in dict_streams)
    # payload-relative planes-block start: chosen so the ABSOLUTE offset
    # (frame header + planes_start) is RAW_ALIGN-aligned when the frame
    # itself starts aligned (guaranteed by the whole-frame padding below)
    planes_start = _align_up(_FRAME_LEN + prefix) - _FRAME_LEN
    end = planes_start + rel
    total = _align_up(_FRAME_LEN + end) - _FRAME_LEN
    out = bytearray(total)
    struct.pack_into("<I", out, 0, len(header))
    pos = 4
    out[pos : pos + len(header)] = header
    pos += len(header)
    out[pos : pos + len(ipc_bytes)] = ipc_bytes
    pos += len(ipc_bytes)
    for _r, b in dict_streams:
        out[pos : pos + len(b)] = b
        pos += len(b)
    for off, buf in planes:
        raw = buf.view(np.uint8).reshape(-1).data if buf.flags.c_contiguous \
            else np.ascontiguousarray(buf).view(np.uint8).reshape(-1).data
        out[planes_start + off : planes_start + off + buf.nbytes] = raw
    return bytes(out)


def deserialize_batch_raw(payload,
                          dict_ctx: Optional[DictDecodeContext] = None,
                          mapped: bool = False) -> ColumnarBatch:
    """Construct a batch OVER a raw frame payload: fixed-width planes become
    numpy views into the payload (no decode, no copy — the views pin the
    source mmap/bytes), uploaded in one batched device_put. ``mapped=True``
    counts the plane bytes as DEVICE_STATS mapped rather than transferred
    (the reader sets it for streams served off an mmap'd segment)."""
    buf = payload if isinstance(payload, memoryview) else memoryview(payload)
    (jlen,) = struct.unpack_from("<I", buf, 0)
    header = json.loads(bytes(buf[4 : 4 + jlen]).decode())
    schema = schema_from_json(header["schema"])
    n = header["num_rows"]
    cap = header["cap"]
    ipc_len = header["ipc_len"]
    pos = 4 + jlen
    host_arrays: List[pa.Array] = []
    if ipc_len:
        reader = pa.ipc.open_stream(pa.py_buffer(buf[pos : pos + ipc_len]))
        host_arrays = list(reader.read_next_batch().columns)
    pos += ipc_len
    dict_refs = dict_ctx.refs if dict_ctx is not None else {}
    for dm in header.get("dicts", ()):
        dbuf = pa.py_buffer(buf[pos : pos + dm["len"]])
        pos += dm["len"]
        darr = pa.ipc.open_stream(dbuf).read_next_batch().column(0)
        if isinstance(darr, pa.ChunkedArray):
            darr = darr.combine_chunks()
        dict_refs[dm["ref"]] = darr
    planes_start = _align_up(_FRAME_LEN + pos) - _FRAME_LEN
    from blaze_tpu.core.batch import device_columns_mapped

    cols: List = [None] * len(header["cols"])
    next_host = 0
    dev_items, dev_slots = [], []
    for i, meta in enumerate(header["cols"]):
        f = schema[i]
        if meta["kind"] == "dev":
            npdt = f.dtype.np_dtype
            data = np.frombuffer(buf, dtype=npdt, count=cap,
                                 offset=planes_start + meta["off"])
            voff = meta.get("voff")
            validity = np.frombuffer(buf, dtype=np.bool_, count=cap,
                                     offset=planes_start + voff) \
                if voff is not None else None
            dev_items.append((f.dtype, data, validity))
            dev_slots.append(i)
        else:
            arr = host_arrays[next_host]
            next_host += 1
            ref = meta.get("dict_ref")
            if ref is not None:
                d = dict_refs.get(ref)
                if d is None:
                    raise RuntimeError(
                        f"frame references dictionary {ref} but no decode "
                        "context carries it (out-of-order decode?)")
                if isinstance(arr, pa.ChunkedArray):
                    arr = arr.combine_chunks()
                arr = pa.DictionaryArray.from_arrays(arr, d)
            cols[i] = _host_or_coded(arr, f.dtype, cap)
    with stage_span(n):
        for slot, col in zip(dev_slots,
                             device_columns_mapped(dev_items, cap, n,
                                                   mapped=mapped)):
            cols[slot] = col
    return ColumnarBatch(schema, cols, n)


_FRAME_FMT = "<4sIQQ"  # magic, flags, compressed len, raw len
_FRAME_LEN = struct.calcsize(_FRAME_FMT)
# flags: low nibble = codec (0=raw, 1=zstd, 2=lz4, 3=zlib); bit 0x10 marks
# a frame that DEFINES a new stream dictionary — readers with a decode
# worker pool must decode such frames in stream order (inline) so the
# dictionary is registered before any pooled frame references it
FRAME_DICT_DEF = 0x10
# bit 0x20 marks a RAW mappable frame (zero-copy data plane): uncompressed
# payload whose fixed-width planes sit at aligned offsets AT CAPACITY
# LENGTH, so a reader constructs numpy views straight over the (mmap'd)
# payload and hands them to jax with no decode and no staging copy
FRAME_RAW_BATCH = 0x20
_CODEC_MASK = 0x0F

# Raw-frame plane alignment. Every raw frame's total size (header +
# payload) is padded to a multiple of RAW_ALIGN, so frame starts — and
# therefore plane offsets — stay 64-byte aligned across arbitrary
# concatenation (partition segments, spill merges). Alignment is a numpy /
# dlpack performance nicety only; correctness never depends on it.
RAW_ALIGN = 64


def _align_up(x: int, a: int = RAW_ALIGN) -> int:
    return (x + a - 1) & ~(a - 1)
# Map-output commit footer magic (runtime/recovery.py appends the footer
# after the last partition segment of a shuffle data file). Defined here so
# whole-file frame iteration can treat it as a clean end-of-stream without
# importing the runtime layer.
MAP_FOOTER_MAGIC = b"BZF1"


def _lz4_compress(payload: bytes):
    """lz4 block compression via the native lib's dlopen'd liblz4 (the
    reference supports lz4 + zstd codecs, ipc_compression.rs:34-260);
    returns None when unavailable so the caller falls back to zstd."""
    from blaze_tpu.utils import native

    l = native.lib()
    if not l.bt_lz4_available():
        return None
    src = np.frombuffer(payload, dtype=np.uint8)
    bound = l.bt_lz4_compress_bound(len(payload))
    if bound <= 0:
        return None
    dst = np.empty(bound, dtype=np.uint8)
    r = l.bt_lz4_compress(src.ctypes.data if len(payload) else None,
                          len(payload), dst.ctypes.data, bound)
    if r <= 0:
        return None
    return dst[:r].tobytes()


def _lz4_decompress(payload: bytes, raw_len: int) -> bytes:
    from blaze_tpu.utils import native

    l = native.lib()
    if not l.bt_lz4_available():
        raise RuntimeError("lz4 frame but liblz4 unavailable")
    src = np.frombuffer(payload, dtype=np.uint8)
    dst = np.empty(max(raw_len, 1), dtype=np.uint8)
    r = l.bt_lz4_decompress(src.ctypes.data, len(payload),
                            dst.ctypes.data, raw_len)
    if r != raw_len:
        raise RuntimeError(f"lz4 decompress failed ({r} != {raw_len})")
    return dst[:raw_len].tobytes()


def _zstd_compress(payload: bytes, level: int) -> bytes:
    from blaze_tpu.utils import native

    l = native.lib()
    src = np.frombuffer(payload, dtype=np.uint8)
    bound = l.bt_zstd_compress_bound(len(payload))
    if bound > 0:  # <= 0: the library was built without zstd headers
        dst = np.empty(bound, dtype=np.uint8)
        r = l.bt_zstd_compress(src.ctypes.data, len(payload),
                               dst.ctypes.data, bound, level)
        if r > 0:
            return dst[:r].tobytes()
    sz = native.system_zstd()
    if sz is not None:
        bound = sz.ZSTD_compressBound(len(payload))
        dst = np.empty(bound, dtype=np.uint8)
        r = sz.ZSTD_compress(dst.ctypes.data, bound,
                             src.ctypes.data, len(payload), level)
        if not sz.ZSTD_isError(r):
            return dst[:r].tobytes()
    if zstandard is None:
        return None  # caller degrades to the zlib frame flavor
    return zstandard.ZstdCompressor(level=level).compress(payload)


def _zstd_decompress(payload: bytes, raw_len: int) -> bytes:
    from blaze_tpu.utils import native

    l = native.lib()
    if raw_len > 0:
        src = np.frombuffer(payload, dtype=np.uint8)
        dst = np.empty(raw_len, dtype=np.uint8)
        r = l.bt_zstd_decompress(src.ctypes.data, len(payload),
                                 dst.ctypes.data, raw_len)
        if r == raw_len:
            return dst.tobytes()
    sz = native.system_zstd()
    if sz is not None and raw_len > 0:
        src = np.frombuffer(payload, dtype=np.uint8)
        dst = np.empty(raw_len, dtype=np.uint8)
        r = sz.ZSTD_decompress(dst.ctypes.data, raw_len,
                               src.ctypes.data, len(payload))
        if r == raw_len:
            return dst.tobytes()
    if zstandard is None:
        raise RuntimeError(
            "zstd frame but neither the native lib nor the python "
            "zstandard binding is available")
    return zstandard.ZstdDecompressor().decompress(payload, max_output_size=raw_len or 0)


class BatchWriter:
    """Length-prefixed compressed frames, one per batch (reference:
    IpcCompressionWriter over lz4/zstd framed streams). Compression runs in
    the native library (native/src/blaze_native.cc); where that was built
    without zstd headers, via the system libzstd or the python binding."""

    def __init__(self, fileobj: BinaryIO, codec: Optional[str] = None,
                 dict_refs: bool = False, raw: bool = False):
        cfg = get_config()
        self.f = fileobj
        self.codec = codec or cfg.shuffle_compression_codec
        self.level = cfg.zstd_level
        self.bytes_written = 0
        self.dict_ctx = DictEncodeContext() if dict_refs else None
        # raw=True emits FRAME_RAW_BATCH mappable frames (zero-copy data
        # plane) instead of compressed serde frames; both flavors share the
        # frame envelope, so spill merges / footers / read_frames are common
        self.raw = raw

    @property
    def codes_bytes(self) -> int:
        return self.dict_ctx.codes_bytes if self.dict_ctx is not None else 0

    def write_batch(self, batch: ColumnarBatch):
        refs_before = self.dict_ctx.next_ref if self.dict_ctx else 0
        codes_before = self.codes_bytes
        if self.raw:
            payload = serialize_batch_raw(batch, dict_ctx=self.dict_ctx)
            raw_len = len(payload)
            flags = FRAME_RAW_BATCH
        else:
            payload = serialize_batch(batch, dict_ctx=self.dict_ctx)
            raw_len = len(payload)
            flags = 0
            if self.codec == "lz4":
                out = _lz4_compress(payload)
                if out is not None:
                    payload, flags = out, 2
                else:  # liblz4 missing: degrade to zstd, stay readable
                    payload, flags = self._zstd_or_zlib(payload)
            elif self.codec != "none":
                payload, flags = self._zstd_or_zlib(payload)
        if self.dict_ctx is not None and self.dict_ctx.next_ref > refs_before:
            flags |= FRAME_DICT_DEF
        if self.codes_bytes > codes_before:
            _codes_counter().inc(self.codes_bytes - codes_before)
        frame = struct.pack(_FRAME_FMT, _MAGIC, flags, len(payload), raw_len)
        self.f.write(frame)
        self.f.write(payload)
        self.bytes_written += len(frame) + len(payload)

    def _zstd_or_zlib(self, payload: bytes):
        """zstd when a backend exists; otherwise stdlib zlib (flag 3) so
        spill/shuffle streams keep compressing in minimal environments."""
        out = _zstd_compress(payload, self.level)
        if out is not None:
            return out, 1
        import zlib

        return zlib.compress(payload, 1), 3


def read_frames(fileobj) -> Iterator[tuple]:
    """Yield raw ``(flags, payload, raw_len)`` frames without decoding —
    frame READS stay sequential (one stream position) while the shuffle
    reader fans DECODE out to worker threads: the ctypes zstd/lz4 one-shots
    release the GIL, so decompression genuinely parallelizes."""
    while True:
        head = fileobj.read(_FRAME_LEN)
        if not head:
            return
        if head[:4] == MAP_FOOTER_MAGIC:
            return  # committed map output's trailing footer, not a frame
        magic, flags, plen, raw_len = struct.unpack(_FRAME_FMT, head)
        assert magic == _MAGIC, f"bad frame magic {magic!r}"
        yield flags, fileobj.read(plen), raw_len


def decode_frame(flags: int, payload, raw_len: int,
                 dict_ctx: Optional[DictDecodeContext] = None,
                 mapped: bool = False) -> ColumnarBatch:
    """Decompress + deserialize one frame (thread-safe for frames without
    the FRAME_DICT_DEF flag; dict-defining frames mutate dict_ctx and must
    decode in stream order). ``mapped`` tags a raw frame served off an
    mmap'd segment for the DEVICE_STATS mapped-vs-copied split."""
    if flags & FRAME_RAW_BATCH:
        return deserialize_batch_raw(payload, dict_ctx=dict_ctx,
                                     mapped=mapped)
    codec = flags & _CODEC_MASK
    if codec == 2:
        payload = _lz4_decompress(payload, raw_len)
    elif codec == 1:
        payload = _zstd_decompress(payload, raw_len)
    elif codec == 3:
        import zlib

        payload = zlib.decompress(payload)
    return deserialize_batch(payload, dict_ctx=dict_ctx)


class BatchReader:
    def __init__(self, fileobj: BinaryIO):
        self.f = fileobj
        self.dict_ctx = DictDecodeContext()

    def __iter__(self) -> Iterator[ColumnarBatch]:
        for flags, payload, raw_len in read_frames(self.f):
            yield decode_frame(flags, payload, raw_len, self.dict_ctx)
