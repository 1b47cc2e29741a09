"""Shuffle read: decode per-partition block objects back into batches.

Reference: ``ipc_reader_exec.rs:132-325`` — pulls ``BlockObject``s (file
segment | byte buffer | readable channel) from a JVM iterator registered in
the resource map and decompresses the framed batch stream. Here the resource
map entry is a callable ``partition -> iterable of blocks`` (or a list for
single-partition readers); blocks are:

- ``("file_segment", path, offset, length)``
- ``("bytes", b)``
- any file-like object positioned at a frame stream
"""

from __future__ import annotations

import io
from typing import Iterable

from blaze_tpu.io.batch_serde import BatchReader
from blaze_tpu.ir import types as T
from blaze_tpu.obs.telemetry import get_registry
from blaze_tpu.obs.tracer import TRACER
from blaze_tpu.ops.base import Operator
from blaze_tpu.utils.logutil import adopt_task_context, task_context

_TM_FETCH_SECS = get_registry().histogram(
    "blaze_shuffle_fetch_seconds",
    "prefetch-side wall time fetching+decoding one partition's blocks")
_TM_SHM_MAPPED = get_registry().counter(
    "blaze_shuffle_shm_mapped_bytes",
    "frame payload bytes served to readers from mmap'd shuffle segments")
_TM_ELIDED = get_registry().counter(
    "blaze_shuffle_serde_elided_total",
    "batches exchanged as in-process references with serde skipped")


class IpcReaderExec(Operator):
    """Decodes shuffle blocks with a prefetch thread so decompress/deser
    overlaps downstream compute (reference: the reducer-side async read in
    ipc_reader_exec.rs)."""

    def __init__(self, schema: T.Schema, resource_id: str, num_partitions: int = 1):
        self.resource_id = resource_id
        self._num_partitions = num_partitions
        super().__init__(schema, [])

    def num_partitions(self):
        return self._num_partitions

    _DECODE_WORKERS = 3

    def _execute(self, partition, ctx, metrics):
        import queue
        import threading
        from concurrent.futures import Future, ThreadPoolExecutor

        from blaze_tpu.io.batch_serde import (FRAME_DICT_DEF,
                                              DictDecodeContext,
                                              decode_frame, read_frames)

        dict_ctx = DictDecodeContext()
        provider = ctx.resources[self.resource_id]
        blocks: Iterable = provider(partition) if callable(provider) else provider
        # the queue holds FUTURES in frame order: frame reads stay sequential
        # on the prefetch thread, decompress + deserialize fan out to the
        # worker pool (ctypes zstd/lz4 one-shots release the GIL), and the
        # consumer resolves in order — bounded in-flight frames =
        # qsize + workers
        q: "queue.Queue" = queue.Queue(maxsize=4)
        stop = threading.Event()
        SENTINEL = object()

        def _put(item) -> bool:
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.1)
                    return True
                except queue.Full:
                    continue
            return False

        use_mmap = bool(ctx.conf.zero_copy_shuffle
                        and ctx.conf.zero_copy_tier != "ipc")

        def _decode(src_path, flags, payload, raw_len, mapped=False):
            try:
                batch = decode_frame(flags, payload, raw_len, dict_ctx,
                                     mapped=mapped)
            except Exception as exc:
                # a frame that fails to decode out of a committed file is a
                # corrupt/torn map output, not a task bug: surface it as the
                # typed fetch failure so lineage RECOMPUTES the output
                # instead of the decode error failing the query
                raise _as_missing(exc, src_path) from exc
            metrics.add("ipc_decode_in_prefetch", 1)
            return batch

        def _materialize(refs):
            # staged references crossed the exchange with serde skipped
            # entirely. Process tier: a host batch, and only the device
            # upload remains. Device tier: windows of the batches the map
            # side routed on the chip (or whole on-chip batches) — copied
            # together into one batch here, with nothing left to do but
            # count the bytes that never touched the host
            from blaze_tpu.core.batch import (ColumnarBatch, HostBatch,
                                              has_planes)

            if isinstance(refs[0], HostBatch):
                (ref,) = refs
                batch = ref.to_columnar()
            else:
                batch = ColumnarBatch.concat(refs, metrics=metrics)
                if batch.columns and all(has_planes(c)
                                         for c in batch.columns):
                    metrics.add("device_shuffle_bytes", int(batch.nbytes()))
            metrics.add("serde_elided_batches", len(refs))
            _TM_ELIDED.inc(len(refs))
            return batch

        def _grouped(refs):
            """Staged references in the groups one ``_materialize`` takes:
            a host batch alone; the device tier's windows together while
            they stay under a batch's rows, as ``CoalesceBatchesExec``
            would merge the pieces, and under the concat's fan-in."""
            from blaze_tpu.core.batch import _CONCAT_FANIN, RowWindow

            group, rows = [], 0
            for ref in refs:
                if not isinstance(ref, RowWindow):
                    if group:
                        yield group
                        group, rows = [], 0
                    yield [ref]
                    continue
                group.append(ref)
                rows += ref.num_rows
                if rows >= ctx.conf.batch_size or len(group) == _CONCAT_FANIN:
                    yield group
                    group, rows = [], 0
            if group:
                yield group

        # the prefetch and decode threads' spans are this task's
        task = task_context()
        pool = ThreadPoolExecutor(max_workers=self._DECODE_WORKERS,
                                  thread_name_prefix="ipc-decode",
                                  initializer=adopt_task_context,
                                  initargs=(task,))

        def produce():
            # the prefetch side is where fetch+decode time actually goes;
            # the consumer side only measures queue wait
            import time

            adopt_task_context(task)
            trace = TRACER.active
            t0 = time.perf_counter_ns()
            nblocks = 0
            pending = []  # in-flight pooled decodes since the last barrier
            try:
                for block in blocks:
                    nblocks += 1
                    if isinstance(block, tuple) and block \
                            and block[0] == "batches":
                        # in-process segment references (zero-copy process
                        # tier): materialize on the decode pool so device
                        # upload overlaps downstream compute like decode does
                        for refs in _grouped(block[1]):
                            fu = pool.submit(_materialize, refs)
                            pending = [f for f in pending if not f.done()]
                            pending.append(fu)
                            if not _put(fu):
                                return
                        continue
                    src_path = block[1] if (isinstance(block, tuple)
                                            and block
                                            and block[0] == "file_segment") \
                        else None
                    from blaze_tpu.runtime.failpoints import failpoint

                    failpoint("shuffle.fetch", src_path)
                    stream = _open_block(block, use_mmap=use_mmap)
                    mapped = getattr(stream, "mapped", False)
                    frames = read_frames(stream)
                    while True:
                        try:
                            frame = next(frames)
                        except StopIteration:
                            break
                        except Exception as exc:
                            # torn/corrupt frame structure (bad magic, short
                            # read): a fetch failure, not a decode bug
                            raise _as_missing(exc, src_path) from exc
                        if mapped:
                            metrics.add("shm_bytes_mapped", len(frame[1]))
                            _TM_SHM_MAPPED.inc(len(frame[1]))
                        if frame[0] & FRAME_DICT_DEF:
                            # dictionary-defining frame: decode INLINE in
                            # stream order, with a barrier first — a spilled
                            # stream segment restarts ref numbering, so a
                            # redefined ref must not swap under a pooled
                            # decode still holding the previous binding
                            for fu in pending:
                                try:
                                    fu.result()
                                except BaseException:
                                    pass  # surfaced via the queue
                            pending = []
                            if not _put(_decode(src_path, *frame,
                                                mapped=mapped)):
                                return
                            continue
                        fu = pool.submit(_decode, src_path, *frame,
                                         mapped=mapped)
                        pending = [f for f in pending if not f.done()]
                        pending.append(fu)
                        if not _put(fu):
                            return
                _put(SENTINEL)
            except BaseException as exc:
                _put(exc)
            finally:
                t1 = time.perf_counter_ns()
                _TM_FETCH_SECS.observe((t1 - t0) / 1e9)
                if trace:
                    import re as _re

                    m = _re.search(r"shuffle_(\d+)", self.resource_id or "")
                    TRACER.complete(
                        "shuffle_fetch", "shuffle", t0, t1 - t0,
                        {"partition": partition, "blocks": nblocks,
                         "stage": int(m.group(1)) if m else None})

        t = threading.Thread(target=produce, daemon=True, name="ipc-prefetch")
        t.start()
        try:
            while True:
                with metrics.timer("shuffle_read_wait_time_ns"), \
                        TRACER.detail("fetch_wait", "shuffle"):
                    item = q.get()
                    if isinstance(item, Future):
                        item = item.result()  # re-raises worker exceptions
                if item is SENTINEL:
                    break
                if isinstance(item, BaseException):
                    raise item
                batch = item
                if batch.schema.names != self.schema.names:
                    batch = batch.rename(self.schema.names)
                metrics.add("ipc_read_batches", 1)
                metrics.add("ipc_read_rows", batch.num_rows)
                yield batch
        finally:
            stop.set()
            while True:
                try:
                    q.get_nowait()
                except queue.Empty:
                    break
            t.join(timeout=5)
            pool.shutdown(wait=False)


def _as_missing(exc: Exception, src_path):
    """Classify a frame-read/decode failure from a file-backed segment as
    the typed fetch failure (ShuffleOutputMissing -> lineage recompute).
    Failures from in-memory blocks (broadcast chunks, process-tier refs)
    have no lineage file to recompute and pass through unchanged."""
    from blaze_tpu.runtime.recovery import ShuffleOutputMissing

    if src_path is None or isinstance(exc, ShuffleOutputMissing):
        return exc
    return ShuffleOutputMissing(
        src_path, f"corrupt frame ({type(exc).__name__}: {exc})")


def _open_block(block, use_mmap: bool = False):
    if isinstance(block, tuple) and block and block[0] == "file_segment":
        _, path, offset, length = block
        if use_mmap:
            # zero-copy plane: map the committed file and serve memoryview
            # slices — raw frames become numpy views over the mapping, and
            # even classic frames decode without per-buffer copies. The
            # mapping outlives an unlink (POSIX) and is freed by refcount
            # once every decoded batch's views die.
            from blaze_tpu.io.shm_segments import (MappedSegmentStream,
                                                   open_mapped)

            try:
                mf = open_mapped(path)
            except OSError:
                from blaze_tpu.runtime.recovery import ShuffleOutputMissing

                raise ShuffleOutputMissing(path, "missing")
            return MappedSegmentStream(mf.view(offset, length))
        try:
            f = open(path, "rb")
        except FileNotFoundError:
            # typed fetch failure: the driver's lineage recovery recomputes
            # the named map output instead of failing the query
            from blaze_tpu.runtime.recovery import ShuffleOutputMissing

            raise ShuffleOutputMissing(path, "missing")
        f.seek(offset)
        return _SegmentReader(f, length)
    if isinstance(block, tuple) and block and block[0] == "bytes":
        return io.BytesIO(block[1])
    if isinstance(block, (bytes, bytearray)):
        return io.BytesIO(block)
    return block  # file-like


class _SegmentReader:
    """Bounded view over an open file (reference: file-segment BlockObject)."""

    def __init__(self, f, length: int):
        self.f = f
        self.remaining = length

    def read(self, n: int = -1) -> bytes:
        if self.remaining <= 0:
            return b""
        if n < 0 or n > self.remaining:
            n = self.remaining
        data = self.f.read(n)
        self.remaining -= len(data)
        return data


class IpcWriterExec(Operator):
    """Streams compressed batch frames to a host consumer callback — the
    broadcast-collect path (reference: ipc_writer_exec.rs; the JVM consumer
    accumulates byte chunks which Spark then torrent-broadcasts)."""

    def __init__(self, child: Operator, consumer_resource_id: str):
        self.consumer_resource_id = consumer_resource_id
        super().__init__(child.schema, [child])

    def _execute(self, partition, ctx, metrics):
        consumer = ctx.resources[self.consumer_resource_id]
        if callable(consumer) and not hasattr(consumer, "write"):
            consumer = consumer(partition)
        from blaze_tpu.io.batch_serde import BatchWriter

        for batch in self.execute_child(0, partition, ctx, metrics):
            buf = io.BytesIO()
            bw = BatchWriter(buf, codec=ctx.conf.shuffle_compression_codec)
            bw.write_batch(batch)
            metrics.add("shuffle_bytes_serialized", bw.bytes_written)
            consumer.write(buf.getvalue())
        return
        yield  # pragma: no cover


class FFIReaderExec(Operator):
    """Imports host-produced Arrow record batches (reference:
    ffi_reader_exec.rs — the ConvertToNative path importing JVM rows via the
    Arrow C Data Interface). The resource is ``partition -> iterable of
    pyarrow.RecordBatch``."""

    def __init__(self, schema: T.Schema, resource_id: str, num_partitions: int = 1):
        self.resource_id = resource_id
        self._num_partitions = num_partitions
        super().__init__(schema, [])

    def num_partitions(self):
        return self._num_partitions

    def _execute(self, partition, ctx, metrics):
        from blaze_tpu.core.batch import ColumnarBatch

        provider = ctx.resources[self.resource_id]
        rbs = provider(partition) if callable(provider) else provider
        for rb in rbs:
            batch = ColumnarBatch.from_arrow(rb, self.schema)
            yield batch


class BatchSourceExec(Operator):
    """Serves pre-materialized ColumnarBatches from the resource map (the
    reducer-side landing of the ICI mesh exchange, parallel/mesh.py — rows
    arrived over a collective, so there is nothing to decode)."""

    def __init__(self, schema: T.Schema, resource_id: str, num_partitions: int = 1):
        self.resource_id = resource_id
        self._num_partitions = num_partitions
        super().__init__(schema, [])

    def num_partitions(self):
        return self._num_partitions

    def _execute(self, partition, ctx, metrics):
        provider = ctx.resources[self.resource_id]
        batches = provider(partition) if callable(provider) else provider[partition]
        # row/batch counting happens once, in Operator.execute
        yield from batches
