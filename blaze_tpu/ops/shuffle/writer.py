"""Shuffle write: bucketize batches by partition id, stage per-partition
compressed frame streams with spill, and produce data + index files.

Reference: ``shuffle_writer_exec.rs`` + ``shuffle/buffered_data.rs`` +
``shuffle/sort_repartitioner.rs`` — staged rows are radix-sorted by
partition id into per-partition IpcCompressionWriter streams; under memory
pressure the staged streams spill; at the end spills merge *by partition
offset* into one data file plus an int64 offset index file (the format
Spark's shuffle fetch serves byte ranges from).

Because each partition's payload is a concatenation of self-delimiting
compressed frames (io/batch_serde.py), merging spills is pure byte-range
concatenation — no decode."""

from __future__ import annotations

import io
import os
import re
import struct
from typing import List, Optional

import numpy as np

from blaze_tpu.core.batch import ColumnarBatch, RowWindow, has_planes
from blaze_tpu.io.batch_serde import BatchWriter
from blaze_tpu.obs.telemetry import get_registry
from blaze_tpu.ops.base import ExecContext, Operator
from blaze_tpu.ops.shuffle.repartitioner import Repartitioner, create_repartitioner
from blaze_tpu.runtime.memmgr import MemConsumer, SpillFile


# rows to accumulate before a bucketize pass (writer-side small-batch
# coalescing); large scan batches pass through untouched
_COALESCE_MIN_ROWS = 32768

_TM_WRITE_BYTES = get_registry().histogram(
    "blaze_shuffle_write_size_bytes", "bytes per committed map output file")
_TM_WRITE_SECS = get_registry().histogram(
    "blaze_shuffle_write_seconds", "wall time of the final merge+publish")
_TM_SERIALIZED = get_registry().counter(
    "blaze_shuffle_serialized_bytes",
    "bytes pushed through the classic IPC serde on shuffle-write paths "
    "(~0 on same-host runs with the zero-copy data plane)")
_TM_TIER_DEGRADED = get_registry().counter(
    "blaze_shuffle_tier_degraded_total",
    "map outputs whose shm-tier commit ran out of tmpfs headroom and "
    "degraded to the spill-dir tier (redirect marker + disk file) instead "
    "of failing the query; on the device tier, batches and map outputs "
    "that went the host way (host-backed input, a failed placement, the "
    "byte budget)")
_TM_DEVICE_RESIDENT = get_registry().counter(
    "blaze_shuffle_device_resident_bytes",
    "column bytes, padding included, committed to the segment registry as "
    "device-resident sub-batch references (the 'device' shuffle tier of a "
    "pool-less session on an accelerator or a mesh: the rows are routed on "
    "the chip and never pulled)")


class _PartitionStreams:
    """In-memory per-partition frame buffers. ``raw=True`` (zero-copy shm
    tier) emits mappable raw frames instead of compressed serde frames —
    the spill/merge/footer plumbing downstream is format-agnostic."""

    def __init__(self, num_partitions: int, codec: str,
                 dict_refs: bool = False, raw: bool = False):
        self.bufs: List[Optional[io.BytesIO]] = [None] * num_partitions
        self.writers: List[Optional[BatchWriter]] = [None] * num_partitions
        self.codec = codec
        self.dict_refs = dict_refs
        self.raw = raw
        self.nbytes = 0
        self.codes_bytes = 0
        self.serialized_bytes = 0  # classic-serde bytes only (tripwire)

    def write(self, pid: int, batch: ColumnarBatch):
        w = self.writers[pid]
        if w is None:
            self.bufs[pid] = io.BytesIO()
            w = self.writers[pid] = BatchWriter(
                self.bufs[pid], codec=self.codec, dict_refs=self.dict_refs,
                raw=self.raw)
        before = w.bytes_written
        cbefore = w.codes_bytes
        w.write_batch(batch)
        self.nbytes += w.bytes_written - before
        if not self.raw:
            self.serialized_bytes += w.bytes_written - before
        self.codes_bytes += w.codes_bytes - cbefore

    def payloads(self):
        for pid, buf in enumerate(self.bufs):
            if buf is not None and buf.tell():
                yield pid, buf.getvalue()


class ShuffleWriterExec(Operator):
    """Writes the child's output into (data_file, index_file); emits no
    batches (the driver/session records the map output, as Spark's
    MapStatus commit does).

    ``mem_sink`` (zero-copy process tier, driver-only: never shipped to a
    worker pool) is a ``(MemSegmentRegistry, stage_id)`` pair — staged
    partitions commit as in-process batch REFERENCES, the data file
    becomes a footer-only lineage marker, and the index keeps logical
    staged sizes so AQE coalescing/skew sizing still sees real bytes.

    ``device_sink`` (the "device" tier, refines ``mem_sink``: what a
    pool-less session negotiates on an accelerator, mesh or no mesh) keeps
    the staged references DEVICE-RESIDENT: a batch whose columns are all
    device planes is routed by one device program
    (``repartitioner.exchange_route``: ids, stable order, one matrix
    gather, offsets) and committed as windows of the one moved batch
    (``core/batch.RowWindow``), which the reduce side's reader copies
    together with no pull and no upload. Degrades to the host staging path
    per batch (host-backed input) or per map output (``device.put``
    failure, the byte budget), each counted by ``shuffle_tier_degraded``,
    and from there exactly like the process tier (spill / budget / pool →
    frames → shm or files)."""

    # a coded var-width column stages as Arrow's dictionary array over its
    # dictionary: codes and one dictionary cross the exchange, not strings
    takes_coded = True

    def __init__(self, child: Operator, partitioning, output_data_file: str,
                 output_index_file: str, mem_sink=None, device_sink=False):
        self.partitioning = partitioning
        self.output_data_file = output_data_file
        self.output_index_file = output_index_file
        self.mem_sink = mem_sink
        self.device_sink = device_sink
        super().__init__(child.schema, [child])

    def _execute(self, partition, ctx, metrics):
        repart = create_repartitioner(self.partitioning, self.children[0].schema)
        state = _WriterState(self, ctx, metrics, repart, map_id=partition)
        ctx.mem.register(state)
        try:
            # self-time lands in elapsed_compute_time_ns via Operator.execute
            for batch in self.execute_child(0, partition, ctx, metrics):
                state.insert(batch)
            import time as _time

            from blaze_tpu.obs.tracer import TRACER

            t0 = _time.perf_counter()
            t0_ns = _time.perf_counter_ns()
            with metrics.timer("shuffle_write_time_ns"):
                state.finish()
            _TM_WRITE_SECS.observe(_time.perf_counter() - t0)
            if TRACER.active:
                m = re.search(r"shuffle_(\d+)", self.output_data_file or "")
                TRACER.complete(
                    "shuffle_write", "shuffle", t0_ns,
                    _time.perf_counter_ns() - t0_ns,
                    {"stage": int(m.group(1)) if m else None,
                     "map": partition})
        finally:
            ctx.mem.unregister(state)
            state.release()
        return
        yield  # pragma: no cover — generator with empty output


class _WriterState(MemConsumer):
    def __init__(self, op: ShuffleWriterExec, ctx: ExecContext, metrics,
                 repart: Repartitioner, map_id: int = 0):
        super().__init__("ShuffleWriter", spillable=True)
        self.op = op
        self.ctx = ctx
        self.metrics = metrics
        self.repart = repart
        self.map_id = map_id
        self.n = repart.num_partitions
        # raw mappable frames whenever the zero-copy plane is on and not
        # pinned to the ipc tier — decided purely from conf so driver
        # threads and pool workers of one run agree on the file format
        self.raw = bool(ctx.conf.zero_copy_shuffle
                        and ctx.conf.zero_copy_tier != "ipc")
        # process tier: stage bucketized sub-batch REFERENCES per reducer
        # instead of any frames at all; degrades to the file path on memory
        # pressure (spill) or past the mem-segment budget
        self.mem_sink = op.mem_sink
        self._mem_parts = {} if self.mem_sink is not None else None
        self._mem_bytes = 0
        # device tier: stage device-resident sub-batch references. Budget
        # is the tighter of the mem-segment cap and the device-resident
        # cap, held against the bytes the staged planes occupy (a routed
        # batch keeps its capacity bucket: up to twice its rows) — past it
        # the staged set degrades like the process tier.
        self.device_sink = bool(getattr(op, "device_sink", False)) \
            and self._mem_parts is not None
        self._mem_budget = ctx.conf.zero_copy_mem_segment_max_bytes
        if self.device_sink:
            self._mem_budget = min(self._mem_budget,
                                   ctx.conf.mesh_device_resident_max_bytes)
        self.streams = self._new_streams()
        # spills: list of (SpillFile-backed raw file, per-partition (off, len))
        self.spills = []
        # small-batch coalescing: aggregations and joins can emit thousands
        # of few-row batches; splitting/serializing each one costs a hash +
        # gather + frame per batch. Buffer until a worthwhile row count.
        self._pending: List[ColumnarBatch] = []
        self._pending_rows = 0
        self._coalesce_min = min(ctx.conf.batch_size, _COALESCE_MIN_ROWS)

    def _new_streams(self) -> _PartitionStreams:
        return _PartitionStreams(self.n, self.ctx.conf.shuffle_compression_codec,
                                 dict_refs=self.ctx.conf.codes_shuffle,
                                 raw=self.raw)

    def insert(self, batch: ColumnarBatch):
        self._pending.append(batch)
        self._pending_rows += batch.num_rows
        if self._pending_rows >= self._coalesce_min:
            self.flush_pending()

    def flush_pending(self):
        if not self._pending:
            return
        batch = self._pending[0] if len(self._pending) == 1 else \
            ColumnarBatch.concat(self._pending)
        self._pending = []
        self._pending_rows = 0
        b0, g0 = self.repart.split_batches, self.repart.split_gathers
        t0 = self.repart.split_time_ns
        c0 = self.streams.codes_bytes
        s0 = self.streams.serialized_bytes
        part_rows = {} if self.ctx.conf.stats_enabled else None
        for pid, sub in self._bucketize(batch):
            if part_rows is not None:
                part_rows[pid] = part_rows.get(pid, 0) + sub.num_rows
            if self._mem_parts is not None:
                self._mem_parts.setdefault(pid, []).append(sub)
                self._mem_bytes += _staged_batch_nbytes(sub)
            else:
                self.streams.write(pid, sub)
        if part_rows:
            # per-reducer row counts for the stats plane (one metric key per
            # partition; the plane folds these into partition_rows and
            # explain summarizes them, so the tree never renders raw lists)
            for pid, rows in part_rows.items():
                self.metrics.add(f"part_rows_{pid}", rows)
        if self._mem_parts is not None and self._mem_bytes > self._mem_budget:
            if self.device_sink:
                self._leave_device_tier()
            self._mem_degrade()
        # hot-path invariant surfaced for soak/tests: one row gather per
        # split batch, never a per-partition take loop
        self.metrics.add("split_batches", self.repart.split_batches - b0)
        self.metrics.add("split_gathers", self.repart.split_gathers - g0)
        self.metrics.add("repartition_time_ns", self.repart.split_time_ns - t0)
        if self.streams.codes_bytes > c0:
            self.metrics.add("codes_shuffle_bytes", self.streams.codes_bytes - c0)
        if self.streams.serialized_bytes > s0:
            self.metrics.add("shuffle_bytes_serialized",
                             self.streams.serialized_bytes - s0)
            _TM_SERIALIZED.inc(self.streams.serialized_bytes - s0)
        self.update_mem_used(self._mem_bytes + self.streams.nbytes)

    def _bucketize(self, batch: ColumnarBatch):
        """Route one coalesced batch to per-partition sub-batches. Device
        tier: route ON-CHIP (one program, one small wait, a window of the
        moved batch a partition) so the staged references stay
        device-resident — but only when every column of the batch is device
        planes (values or codes), and only while device placement succeeds
        (``device.put`` failpoint / OOM degrades this writer to the shm tier
        for the whole map output, matching what the reader expects)."""
        if self.device_sink and self._mem_parts is not None:
            from blaze_tpu.runtime.failpoints import failpoint

            if batch.columns and all(has_planes(c) for c in batch.columns):
                try:
                    failpoint("device.put")
                    return self.repart.bucketize(batch)
                except OSError:
                    self._leave_device_tier()
                    self._mem_degrade()
            else:
                # a host-backed batch goes the host way, this batch only
                self._count_degraded()
        return self.repart.bucketize_host(batch)

    def _count_degraded(self):
        self.metrics.add("shuffle_tier_degraded", 1)
        _TM_TIER_DEGRADED.inc()

    def _leave_device_tier(self):
        """This map output stops staging on the chip (a failed placement,
        the byte budget): counted, and what follows goes the host way."""
        self.device_sink = False
        self._count_degraded()

    def _mem_degrade(self):
        """Leave the process tier for this map output: route the staged
        batch references through the (raw or classic) frame streams and
        continue as an ordinary file-backed write."""
        parts, self._mem_parts = self._mem_parts, None
        self._mem_bytes = 0
        s0 = self.streams.serialized_bytes
        for pid in sorted(parts):
            for sub in parts[pid]:
                self.streams.write(pid, sub.to_columnar()
                                   if isinstance(sub, RowWindow) else sub)
        if self.streams.serialized_bytes > s0:
            self.metrics.add("shuffle_bytes_serialized",
                             self.streams.serialized_bytes - s0)
            _TM_SERIALIZED.inc(self.streams.serialized_bytes - s0)

    def spill(self) -> int:
        if self._mem_parts is not None and self._mem_bytes:
            # memory pressure: staged references become spillable frames
            self._mem_degrade()
        if not self.streams.nbytes:
            return 0
        freed = self.streams.nbytes
        spill = SpillFile("shuffle")
        f = spill._file
        index = {}
        with self.metrics.timer("spill_io_time_ns"):
            for pid, payload in self.streams.payloads():
                index[pid] = (f.tell(), len(payload))
                f.write(payload)
            f.flush()
        self.metrics.add("spill_count", 1)
        self.metrics.add("spilled_bytes", sum(l for _, l in index.values()))
        self.spills.append((spill, index))
        self.streams = self._new_streams()
        return freed

    def finish(self):
        """Publish the map output: process-tier registry commit when every
        staged partition is still held by reference, else the ordinary
        merge of in-memory + spilled frame segments into the data file."""
        from blaze_tpu.runtime.failpoints import failpoint

        self.flush_pending()
        failpoint("map.commit")
        if self._mem_parts is not None and not self.spills \
                and not self.streams.nbytes:
            self._finish_mem()
        else:
            if self._mem_parts is not None:
                self._mem_degrade()
            self._finish_files()

    def _finish_mem(self):
        """Process-tier commit: publish the staged batch references to the
        mem segment registry, plus a footer-only marker data file (passes
        ``verify_map_output``, so lineage sweeps and chaos deletion keep
        operating on files — recompute re-runs this map and republishes
        both) and an index of LOGICAL staged sizes so AQE coalescing and
        skew sizing still see real bytes."""
        import uuid

        from blaze_tpu.runtime.recovery import pack_footer

        registry, stage = self.op.mem_sink
        parts = self._mem_parts
        offsets = np.zeros(self.n + 1, dtype=np.int64)
        device_bytes = 0
        for pid in range(self.n):
            for b in parts.get(pid, ()):
                offsets[pid + 1] += _logical_batch_nbytes(b)
                if isinstance(b, (ColumnarBatch, RowWindow)):
                    device_bytes += _staged_batch_nbytes(b)
            offsets[pid + 1] += offsets[pid]
        registry.commit(stage, self.map_id, parts, int(offsets[self.n]))
        if device_bytes:
            # device tier actually engaged: staged refs are on-chip batches
            _TM_DEVICE_RESIDENT.inc(device_bytes)
        attempt = uuid.uuid4().hex
        tmp = f"{self.op.output_data_file}.tmp.{attempt}"
        os.makedirs(os.path.dirname(tmp) or ".", exist_ok=True)
        with open(tmp, "wb") as out:
            out.write(pack_footer(0, 0))
            out.flush()
            os.fsync(out.fileno())
        os.replace(tmp, self.op.output_data_file)
        itmp = f"{self.op.output_index_file}.tmp.{attempt}"
        with open(itmp, "wb") as idx:
            idx.write(offsets.astype("<i8").tobytes())
            idx.flush()
            os.fsync(idx.fileno())
        os.replace(itmp, self.op.output_index_file)
        self.metrics.add("data_size", int(offsets[self.n]))
        _TM_WRITE_BYTES.observe(int(offsets[self.n]))
        self._mem_parts = {}
        self._mem_bytes = 0

    def _finish_files(self):
        """Merge in-memory + spilled per-partition segments into the final
        data file (partition-major) and write the offset index. BOTH files
        publish via per-attempt unique tmp paths + fsync + atomic
        os.replace, and the data file carries a trailing length/crc32
        footer (runtime/recovery.py): concurrent attempts of the same task
        (retry races, straggler speculation) each write their own staging
        files, completed publishes are whole-file swaps, and a worker
        killed mid-write can never leave a footer-valid torn file — the
        reader verifies the footer and treats a torn file as missing,
        triggering lineage recompute instead of silently short rows."""
        import errno
        import uuid
        import zlib

        from blaze_tpu.io import shm_segments as _shm
        from blaze_tpu.runtime.failpoints import failpoint
        from blaze_tpu.runtime.recovery import (FOOTER_LEN, pack_footer,
                                                write_redirect)

        attempt = uuid.uuid4().hex
        mem = {pid: payload for pid, payload in self.streams.payloads()}

        def _write_data(target: str) -> np.ndarray:
            """Merge into ``target`` via tmp+fsync+atomic replace; the tmp
            file is unlinked on ANY failure (on a filling /dev/shm the
            partial bytes must be given back before the degrade path can
            commit its redirect marker)."""
            offsets = np.zeros(self.n + 1, dtype=np.int64)
            tmp = f"{target}.tmp.{attempt}"
            os.makedirs(os.path.dirname(tmp) or ".", exist_ok=True)
            crc = 0
            try:
                with open(tmp, "wb") as out:
                    def _write(b: bytes):
                        nonlocal crc
                        crc = zlib.crc32(b, crc)
                        out.write(b)

                    for pid in range(self.n):
                        offsets[pid] = out.tell()
                        for spill, index in self.spills:
                            if pid in index:
                                off, ln = index[pid]
                                spill._file.seek(off)
                                _write(spill._file.read(ln))
                        if pid in mem:
                            _write(mem[pid])
                    offsets[self.n] = out.tell()
                    out.write(pack_footer(int(offsets[self.n]), crc))
                    out.flush()
                    os.fsync(out.fileno())
                os.replace(tmp, target)
            except BaseException:
                try:
                    os.unlink(tmp)
                except OSError:
                    pass
                raise
            return offsets

        data_path = self.op.output_data_file
        degrade = False
        if _shm.is_shm_path(data_path):
            # the shm tier checks headroom per-COMMIT (choose_shm_root only
            # probed at root selection) and degrades this (writer, reader)
            # pair to the spill-dir tier — up front when the cushion is
            # gone, or on a mid-commit ENOSPC — instead of failing the query
            need = sum(len(p) for p in mem.values()) + FOOTER_LEN + \
                sum(ln for _, index in self.spills
                    for _, ln in index.values())
            try:
                failpoint("shm.commit")
                degrade = not _shm.shm_headroom_ok(
                    data_path, need, self.ctx.conf.shm_min_free_bytes)
                if not degrade:
                    offsets = _write_data(data_path)
            except OSError as exc:
                if exc.errno != errno.ENOSPC:
                    raise
                degrade = True
        else:
            offsets = _write_data(data_path)
        if degrade:
            fallback = self._degrade_target()
            offsets = _write_data(fallback)
            write_redirect(data_path, fallback)
            self._count_degraded()
        itmp = f"{self.op.output_index_file}.tmp.{attempt}"
        with open(itmp, "wb") as idx:
            idx.write(offsets.astype("<i8").tobytes())
            idx.flush()
            os.fsync(idx.fileno())
        os.replace(itmp, self.op.output_index_file)
        self.metrics.add("data_size", int(offsets[self.n]))
        _TM_WRITE_BYTES.observe(int(offsets[self.n]))
        self.streams = self._new_streams()

    def _degrade_target(self) -> str:
        """Deterministic spill-dir home for a degraded map output: keyed by
        the ORIGINAL path, so a lineage recompute that degrades again
        atomically overwrites the same file instead of accreting copies."""
        import zlib

        orig = self.op.output_data_file
        tag = zlib.crc32(orig.encode()) & 0xFFFFFFFF
        d = os.path.join(self.ctx.conf.spill_dir, "degraded_shuffle")
        os.makedirs(d, exist_ok=True)
        # keep the shuffle_<stage>_map_<m> coordinates in the name so a
        # fetch failure against the DEGRADED file still parses to lineage
        # coordinates (recovery._parse_output_path accepts '_' separators)
        stage_dir = os.path.basename(os.path.dirname(orig))
        return os.path.join(
            d, f"{tag:08x}_{stage_dir}_{os.path.basename(orig)}")

    def release(self):
        for spill, _ in self.spills:
            spill.release()
        self.spills = []


def _host_batch_nbytes(hb) -> int:
    """Logical staged size of a HostBatch's planes/arrays — what the
    process tier books against its budget and records in the logical
    index (stands in for serialized size in AQE's advisory math)."""
    total = 0
    for it in hb.items:
        if isinstance(it, tuple):
            total += it[0].nbytes + it[1].nbytes
        else:
            total += it.nbytes
    return total


def _staged_batch_nbytes(b) -> int:
    """What a staged reference holds, the number the budget is held
    against: a host batch's planes (process tier); on the device tier a
    device-resident batch's planes with their padding, or a window's share
    of the routed batch it points into (the windows of one batch share its
    planes, so their shares add up to them)."""
    if isinstance(b, RowWindow):
        return int(b.batch.nbytes()) * b.num_rows // b.batch.num_rows
    if isinstance(b, ColumnarBatch):
        return int(b.nbytes())
    return _host_batch_nbytes(b)


def _logical_batch_nbytes(b) -> int:
    """The staged ROWS' size, the number the logical index records for
    AQE's advisory math: the same on both tiers for the same rows, so that
    a plan does not change with the tier. A device plane counts its live
    rows (values and validity), not its capacity bucket."""
    if not isinstance(b, (ColumnarBatch, RowWindow)):
        return _host_batch_nbytes(b)
    columns = b.batch.columns if isinstance(b, RowWindow) else b.columns
    return int(sum(
        b.num_rows * (c.data.dtype.itemsize + c.validity.dtype.itemsize)
        if has_planes(c) else c.nbytes() for c in columns))


def read_index_file(path: str) -> np.ndarray:
    with open(path, "rb") as f:
        return np.frombuffer(f.read(), dtype="<i8")


class RssShuffleWriterExec(Operator):
    """Push-style shuffle: partition payloads go to a writer object from the
    resource map instead of local files (reference: RssShuffleWriterExecNode
    pushing through RssPartitionWriterBase.write(partitionId, ByteBuffer) to
    Celeborn/Uniffle). The writer must expose write(pid, bytes) and flush()."""

    def __init__(self, child: Operator, partitioning, rss_writer_resource_id: str):
        self.partitioning = partitioning
        self.rss_writer_resource_id = rss_writer_resource_id
        super().__init__(child.schema, [child])

    def _execute(self, partition, ctx, metrics):
        repart = create_repartitioner(self.partitioning, self.children[0].schema)
        writer = ctx.resources[self.rss_writer_resource_id]
        if callable(writer):
            writer = writer(partition)
        codec = ctx.conf.shuffle_compression_codec
        coalesce_min = min(ctx.conf.batch_size, _COALESCE_MIN_ROWS)
        pending: List[ColumnarBatch] = []
        pending_rows = 0

        def _push(batch):
            b0, g0 = repart.split_batches, repart.split_gathers
            t0 = repart.split_time_ns
            for pid, sub in repart.bucketize_host(batch):
                if ctx.conf.stats_enabled:
                    metrics.add(f"part_rows_{pid}", sub.num_rows)
                buf = io.BytesIO()
                bw = BatchWriter(buf, codec=codec,
                                 dict_refs=ctx.conf.codes_shuffle)
                bw.write_batch(sub)
                if bw.codes_bytes:
                    metrics.add("codes_shuffle_bytes", bw.codes_bytes)
                # RSS always serializes (cross-network path keeps IPC serde)
                metrics.add("shuffle_bytes_serialized", bw.bytes_written)
                _TM_SERIALIZED.inc(bw.bytes_written)
                writer.write(pid, buf.getvalue())
            metrics.add("split_batches", repart.split_batches - b0)
            metrics.add("split_gathers", repart.split_gathers - g0)
            metrics.add("repartition_time_ns", repart.split_time_ns - t0)

        for batch in self.execute_child(0, partition, ctx, metrics):
            pending.append(batch)
            pending_rows += batch.num_rows
            if pending_rows >= coalesce_min:
                _push(pending[0] if len(pending) == 1 else
                      ColumnarBatch.concat(pending, metrics=metrics))
                pending = []
                pending_rows = 0
        if pending:
            _push(pending[0] if len(pending) == 1 else
                  ColumnarBatch.concat(pending, metrics=metrics))
        writer.flush()
        return
        yield  # pragma: no cover

class FileSegmentBlockProvider:
    """Picklable reducer->blocks mapping over map-output data+index files —
    the resource an IpcReader pulls (reference: fetched BlockObjects served
    as file segments, ipc_reader_exec.rs:185-325). Plain data, so it crosses
    the driver->worker process boundary intact."""

    def __init__(self, indexes):
        # [(data_path, offsets int64[num_reducers+1]), ...]
        self.indexes = [(path, np.asarray(offsets)) for path, offsets in indexes]

    def __call__(self, reducer: int):
        from blaze_tpu.runtime.recovery import check_map_output

        blocks = []
        for m, (data, offsets) in enumerate(self.indexes):
            start, end = int(offsets[reducer]), int(offsets[reducer + 1])
            if end > start:
                # footer check per served map file: a deleted/torn upstream
                # output surfaces as ShuffleOutputMissing (with stage+map
                # lineage coordinates) before any segment is decoded; the
                # check resolves degraded-output redirects, so segments are
                # served from wherever the commit actually landed
                resolved = check_map_output(data, offsets=offsets, map_id=m)
                blocks.append(("file_segment", resolved, start, end - start))
        return blocks


class BytesBlockProvider:
    """Picklable provider serving in-memory IPC chunks to every partition
    (broadcast collect, reference: TorrentBroadcast of IPC byte arrays)."""

    def __init__(self, chunks):
        self.chunks = list(chunks)

    def __call__(self, partition: int):
        return [("bytes", b) for b in self.chunks]
