"""Parquet scan and sink.

Scan (reference: ``parquet_exec.rs:69-293`` + ``scan/internal_file_reader.rs``):
the reference decodes parquet on CPU through DataFusion's reader with
JNI-backed IO, row-group pruning and page filtering. The TPU analogue keeps
decode on host CPU — pyarrow's C++ parquet reader with column projection,
predicate pushdown (row-group statistics + dictionary pruning via
``pyarrow.dataset``) — and stages fixed-width columns into device batches; a
prefetch thread overlaps IO/decode with device compute (reference:
async prefetching reader, SURVEY.md §7.4.8).

Sink (reference: ``parquet_sink_exec.rs``): writes batches with optional
hive-style dynamic partitions (the trailing ``num_dyn_parts`` columns become
partition directories).
"""

from __future__ import annotations

import os
import queue
import threading
import time
from typing import Iterator, List, Optional

import pyarrow as pa
import pyarrow.dataset as pads
import pyarrow.parquet as pq

from blaze_tpu.core.batch import ColumnarBatch
from blaze_tpu.exprs.compiler import ExprEvaluator
from blaze_tpu.ir import exprs as E
from blaze_tpu.ir import nodes as N
from blaze_tpu.ir import types as T
from blaze_tpu.obs.tracer import TRACER
from blaze_tpu.ops.base import ExecContext, Operator
from blaze_tpu.utils.logutil import adopt_task_context, task_context

_QUEUE_DEPTH = 4
_SENTINEL = object()


def predicate_to_arrow(expr: Optional[E.Expr], schema: Optional[T.Schema] = None):
    """Best-effort conversion of an IR predicate into a pyarrow.dataset
    expression for row-group/page pruning; None when not convertible (the
    engine's FilterExec still applies the full predicate — pushdown is an
    optimization, like the reference's pruning predicates)."""
    import pyarrow.compute as pc

    if expr is None:
        return None
    try:
        return _convert_pred(expr, pc, schema)
    except NotImplementedError:
        return None


def _convert_pred(e: E.Expr, pc, schema=None):
    B = E.BinaryOp
    if isinstance(e, E.BinaryExpr):
        if e.op in (B.AND, B.OR):
            l = _convert_pred(e.left, pc, schema)
            r = _convert_pred(e.right, pc, schema)
            return l & r if e.op == B.AND else l | r
        fns = {B.EQ: "__eq__", B.NEQ: "__ne__", B.LT: "__lt__", B.LTEQ: "__le__",
               B.GT: "__gt__", B.GTEQ: "__ge__"}
        if e.op in fns:
            l = _convert_operand(e.left, pc, schema)
            r = _convert_operand(e.right, pc, schema)
            return getattr(l, fns[e.op])(r)
    if isinstance(e, E.Not):
        return ~_convert_pred(e.child, pc, schema)
    if isinstance(e, E.IsNotNull):
        return _convert_operand(e.child, pc, schema).is_valid()
    if isinstance(e, E.IsNull):
        return _convert_operand(e.child, pc, schema).is_null()
    if isinstance(e, E.InList) and not e.negated:
        vals = [v.value for v in e.values if isinstance(v, E.Literal)]
        if len(vals) == len(e.values):
            return _convert_operand(e.child, pc, schema).isin(vals)
    raise NotImplementedError


_INT_RANK = {T.Int8Type: 8, T.Int16Type: 16, T.Int32Type: 32, T.Int64Type: 64}


def _operand_dtype(e: E.Expr, schema) -> Optional[T.DataType]:
    if isinstance(e, E.Literal):
        return e.dtype
    if isinstance(e, E.Column) and schema is not None and e.name in schema.names:
        return schema[schema.index_of(e.name)].dtype
    if isinstance(e, E.Cast):
        return e.dtype
    return None


def _cast_is_lossless_widening(src: Optional[T.DataType], dst: T.DataType) -> bool:
    """True only for casts where every source value maps 1:1 to a distinct
    target value, so ``cast(col) OP lit`` filters the same rows as the
    original predicate. Anything else (narrowing, truncation, int64->float64,
    numeric->string, timestamp->date...) must NOT be pushed down: the scanner
    filter is exact, and FilterExec cannot restore rows already dropped."""
    if src is None:
        return False
    if type(src) is type(dst):
        if isinstance(src, T.DecimalType):
            return dst.precision >= src.precision and dst.scale == src.scale
        return True
    if type(src) in _INT_RANK:
        if type(dst) in _INT_RANK:
            return _INT_RANK[type(dst)] >= _INT_RANK[type(src)]
        # f32 holds ints up to 2^24 exactly, f64 up to 2^53
        if isinstance(dst, T.Float32Type):
            return _INT_RANK[type(src)] <= 16
        if isinstance(dst, T.Float64Type):
            return _INT_RANK[type(src)] <= 32
        if isinstance(dst, T.DecimalType):
            digits = {8: 3, 16: 5, 32: 10, 64: 19}[_INT_RANK[type(src)]]
            return dst.precision - dst.scale >= digits
    if isinstance(src, T.Float32Type) and isinstance(dst, T.Float64Type):
        return True
    if isinstance(src, T.DateType) and isinstance(dst, T.TimestampType):
        return True
    return False


def _convert_operand(e: E.Expr, pc, schema=None):
    if isinstance(e, E.Column):
        return pc.field(e.name)
    if isinstance(e, E.Literal):
        if e.value is None:
            raise NotImplementedError
        v = e.value
        if isinstance(e.dtype, T.DecimalType):
            from decimal import Decimal

            v = Decimal(str(v))
        return pc.scalar(v)
    if isinstance(e, E.Cast):
        if not _cast_is_lossless_widening(_operand_dtype(e.child, schema), e.dtype):
            raise NotImplementedError
        return _convert_operand(e.child, pc, schema)
    raise NotImplementedError


def _decoded(batches):
    """``batches`` as they come, each ``next()`` a ``scan:decode`` span
    under full tracing: the decode alone, not the wait for a free slot in
    the prefetch queue (benchmark: ``decode_mrows_s``)."""
    if not TRACER.enabled:
        yield from batches
        return
    batches = iter(batches)
    while True:
        t0 = time.perf_counter_ns()
        try:
            rb = next(batches)
        except StopIteration:
            return
        TRACER.complete("decode", "scan", t0, time.perf_counter_ns() - t0,
                        {"rows": rb.num_rows, "bytes": rb.nbytes})
        yield rb


class ParquetScanExec(Operator):
    def __init__(self, conf: N.FileScanConf, predicate: Optional[E.Expr] = None):
        self.conf = conf
        self.predicate = predicate
        super().__init__(conf.output_schema, [])

    def num_partitions(self):
        return len(self.conf.file_groups)

    def _execute(self, partition, ctx, metrics):
        group = self.conf.file_groups[partition]
        proj_names = [self.conf.file_schema[i].name for i in self.conf.projection]
        # read string/binary columns dictionary-encoded: they go on as coded
        # columns (core/batch.CodedColumn) — the int32 CODES upload once per
        # batch and predicates, joins, grouping and exchanges run on them;
        # the values stay one host dictionary a column and scan
        dict_cols = [self.conf.file_schema[i].name
                     for i in self.conf.projection
                     if isinstance(self.conf.file_schema[i].dtype,
                                   (T.StringType, T.BinaryType))]
        filt = predicate_to_arrow(self.predicate, self.conf.file_schema)
        batch_size = ctx.conf.batch_size
        q: "queue.Queue" = queue.Queue(maxsize=_QUEUE_DEPTH)
        stop = threading.Event()

        def _put(item) -> bool:
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.1)
                    return True
                except queue.Full:
                    continue
            return False

        task = task_context()  # the prefetch thread's spans are this task's

        def produce():
            adopt_task_context(task)
            try:
                for pfile in group.files:
                    if pfile.range is not None:
                        # byte-range split: read the row groups whose start
                        # offset midpoint falls inside [start, end) — the
                        # same ownership rule Spark/parquet splits use, so
                        # every row group is read by exactly one split
                        from blaze_tpu.io import fs as FS

                        pf = pq.ParquetFile(FS.open_input(pfile.path),
                                            read_dictionary=dict_cols)
                        rgs = []
                        for i in range(pf.metadata.num_row_groups):
                            rg = pf.metadata.row_group(i)
                            c = rg.column(0)
                            off = c.dictionary_page_offset or c.data_page_offset
                            if pfile.range.start <= off < pfile.range.end:
                                rgs.append(i)
                        if not rgs:
                            continue
                        for rb in _decoded(pf.iter_batches(
                                batch_size=batch_size, row_groups=rgs,
                                columns=proj_names)):
                            metrics.add("bytes_scanned", rb.nbytes)
                            if not _put((pfile, rb)):
                                return
                        continue
                    from blaze_tpu.io import fs as FS

                    afs, apath = FS.arrow_filesystem(pfile.path)
                    fmt = pads.ParquetFileFormat(
                        read_options=pads.ParquetReadOptions(
                            dictionary_columns=dict_cols))
                    ds = pads.dataset(apath, format=fmt, filesystem=afs)
                    scanner = ds.scanner(columns=proj_names, filter=filt,
                                         batch_size=batch_size)
                    for rb in _decoded(scanner.to_batches()):
                        metrics.add("bytes_scanned", rb.nbytes)
                        if not _put((pfile, rb)):
                            return  # consumer stopped early
                _put(_SENTINEL)
            except BaseException as exc:  # relay errors to the consumer
                _put(exc)

        t = threading.Thread(target=produce, daemon=True, name="parquet-prefetch")
        t.start()
        proj_schema = self.conf.file_schema.select(self.conf.projection)
        # one dictionary a coded column and scan task: a row group's that
        # differs is unified with the ones before it, its codes remapped
        from blaze_tpu.core.batch import CodedColumn
        from blaze_tpu.core.dictionary import OneDictionary

        dictionaries = OneDictionary()
        try:
            while True:
                with TRACER.detail("decode_wait", "scan"):
                    item = q.get()
                if item is _SENTINEL:
                    break
                if isinstance(item, BaseException):
                    raise item
                pfile, rb = item
                if rb.num_rows == 0:
                    continue
                batch = ColumnarBatch.from_arrow(rb, proj_schema)
                for i, c in enumerate(batch.columns):
                    if isinstance(c, CodedColumn):
                        batch.columns[i], grown, remapped = dictionaries.keep(
                            i, c, batch.num_rows)
                        metrics.add("dict_entries", grown)
                        if remapped:
                            metrics.add("dict_remap_rows", remapped)
                if len(self.conf.partition_schema):
                    batch = _attach_partition_values(batch, pfile, self.conf, self.schema)
                yield batch
        finally:
            # unblock and reap the producer even on early generator close
            stop.set()
            while True:
                try:
                    q.get_nowait()
                except queue.Empty:
                    break
            t.join(timeout=5)


def _attach_partition_values(batch: ColumnarBatch, pfile: N.PartitionedFile,
                             conf: N.FileScanConf, out_schema: T.Schema) -> ColumnarBatch:
    """Append constant hive-partition columns (reference: partition values in
    FileScanExecConf, url-decoded partition paths)."""
    from blaze_tpu.exprs.compiler import ExprEvaluator as _Ev
    from blaze_tpu.exprs.compiler import make_literal

    ev = _Ev([], batch.schema)
    cols = list(batch.columns)
    for i, f in enumerate(conf.partition_schema.fields):
        val = pfile.partition_values[i] if i < len(pfile.partition_values) else None
        v = make_literal(val, f.dtype)
        cols.append(ev._to_column(v, batch))
    return ColumnarBatch(out_schema, cols, batch.num_rows)


class ParquetSinkExec(Operator):
    """Writes the child into parquet files under fs_path; emits nothing.
    Dynamic partitioning: the trailing ``num_dyn_parts`` child columns select
    hive-style ``col=value`` directories (reference expects sorted input for
    stability; we group within each batch so ordering is not required)."""

    def __init__(self, child: Operator, fs_path: str, num_dyn_parts: int = 0,
                 props: Optional[dict] = None):
        self.fs_path = fs_path
        self.num_dyn_parts = num_dyn_parts
        self.props = props or {}
        super().__init__(child.schema, [child])

    def _execute(self, partition, ctx, metrics):
        from blaze_tpu.io import fs as FS

        FS.makedirs(self.fs_path)
        writers = {}
        compression = self.props.get("compression", "zstd")
        ndp = self.num_dyn_parts
        data_fields = self.schema.fields[: len(self.schema.fields) - ndp]
        part_fields = self.schema.fields[len(self.schema.fields) - ndp:]
        try:
            for batch in self.execute_child(0, partition, ctx, metrics):
                rb = batch.to_arrow()
                if ndp == 0:
                    self._write(writers, "", rb, partition, compression)
                    continue
                tbl = pa.Table.from_batches([rb])
                import pyarrow.compute as pc

                keys = [f.name for f in part_fields]
                for chunk in tbl.group_by(keys, use_threads=False).aggregate([]).to_pylist():
                    mask = None
                    for k in keys:
                        eq = pc.equal(tbl[k], pa.scalar(chunk[k])) if chunk[k] is not None \
                            else pc.is_null(tbl[k])
                        eq = pc.fill_null(eq, False)
                        mask = eq if mask is None else pc.and_(mask, eq)
                    sub = tbl.filter(mask).select([f.name for f in data_fields])
                    subdir = "/".join(
                        f"{k}={_escape_part(chunk[k])}" for k in keys)
                    for rb2 in sub.to_batches():
                        self._write(writers, subdir, rb2, partition, compression)
            for w in writers.values():
                w.close()
        except BaseException:
            for w in writers.values():
                try:
                    w.close()
                except Exception:
                    pass
            raise
        return
        yield  # pragma: no cover

    def _write(self, writers, subdir, rb, partition, compression):
        from blaze_tpu.io import fs as FS

        key = subdir
        if key not in writers:
            base = self.fs_path.rstrip("/")
            d = f"{base}/{subdir}" if subdir else base
            FS.makedirs(d)
            path = f"{d}/part-{partition:05d}.parquet"
            writers[key] = pq.ParquetWriter(FS.open_output(path), rb.schema,
                                            compression=compression)
        writers[key].write_batch(rb)


def _escape_part(v) -> str:
    """Hive partition-path escaping (reference handles url-encoded paths)."""
    import urllib.parse

    if v is None:
        return "__HIVE_DEFAULT_PARTITION__"
    return urllib.parse.quote(str(v), safe="")


def scan_node_for_files(paths: List[str], num_partitions: int = 1,
                        projection: Optional[List[str]] = None,
                        predicate: Optional[E.Expr] = None) -> N.ParquetScan:
    """Convenience: build a ParquetScan node over local files, splitting files
    round-robin into partitions (driver-side planning helper)."""
    from blaze_tpu.io import fs as FS

    with FS.open_input(paths[0]) as f0:
        schema = T.schema_from_arrow(pq.read_schema(f0))
    groups = [[] for _ in range(num_partitions)]
    for i, p in enumerate(paths):
        size = FS.getsize(p)
        groups[i % num_partitions].append(N.PartitionedFile(p, size))
    if projection is None:
        proj = list(range(len(schema)))
    else:
        # case-insensitive column resolution (reference: schema adaption in
        # scan/mod.rs:34-92 matches file columns case-insensitively)
        lower = {f.name.lower(): i for i, f in enumerate(schema.fields)}
        proj = []
        for n in projection:
            if n in schema.names:
                proj.append(schema.index_of(n))
            elif n.lower() in lower:
                proj.append(lower[n.lower()])
            else:
                schema.index_of(n)  # raises the descriptive KeyError
    conf = N.FileScanConf(
        file_groups=[N.FileGroup(files=g) for g in groups],
        file_schema=schema,
        projection=proj,
    )
    return N.ParquetScan(conf, predicate)
