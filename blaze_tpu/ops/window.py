"""Window functions over partition/order-sorted input.

Reference: ``window_exec.rs`` (489) + ``window/processors/*`` — rank,
dense_rank, row_number and aggregates-over-window driven by a WindowContext
that detects group boundaries via row-format keys; WindowGroupLimit arrives
as ``group_limit``. Input is sorted by (partition_spec, order_spec) — the
converter guarantees it, as Spark does.

Execution is SEGMENTED for the common shapes (rank-family counters and
default-frame aggregates): each input batch is processed in one shot over
segment-boundary masks — partition starts from carryable key rows
(keymap.key_rows / RunningKeyCodes), peer starts from order keys — with a
small carry (counter bases, open aggregate accumulators, the last key row)
threaded across batches. Group structure is data (masks feeding the
restart-at-segment prefix scans in core/kernels), never control flow, so a
batch with 100k tiny partitions costs the same as one with a single
partition. Only the OPEN tail group is withheld until its frame value is
known, and only when aggregates are present; the withheld slices live in a
memmgr-watched _PartitionBuffer, so a single giant group degrades to the
spill path instead of OOM. Explicit ROWS/RANGE offset frames need random
access within the partition and keep the buffer-then-process path (those
partitions must fit at process time — the reference holds the same
constraint per window group)."""

from __future__ import annotations

from typing import Iterator, List, Optional

import numpy as np
import pyarrow as pa

from blaze_tpu.core.batch import (CodedColumn, ColumnarBatch, DeviceColumn,
                                  HostColumn, _arrow_to_column, has_planes)
from blaze_tpu.exprs.compiler import ExprEvaluator
from blaze_tpu.ir import exprs as E
from blaze_tpu.ir import types as T
from blaze_tpu.ir.nodes import WindowExpr
from blaze_tpu.ops.base import Operator
from blaze_tpu.runtime.memmgr import MemConsumer, SpillFile


class _PartitionBuffer(MemConsumer):
    """Memmgr-watched buffer for withheld window rows: batches accumulate in
    memory, spill to a compressed disk stream under pressure (keeping the
    tail batch resident), and replay in order at process time."""

    def __init__(self, schema: T.Schema, metrics):
        super().__init__("WindowExec", spillable=True)
        self.schema = schema
        self.metrics = metrics
        self.mem: List[ColumnarBatch] = []
        self.spills: List["SpillFile"] = []
        self.nbytes = 0

    def append(self, b: ColumnarBatch):
        self.mem.append(b)
        self.nbytes += b.nbytes()
        self.update_mem_used(self.nbytes)

    def spill(self) -> int:
        from blaze_tpu.runtime.memmgr import SpillFile

        if len(self.mem) <= 1:
            return 0
        sp = SpillFile("window")
        with self.metrics.timer("spill_io_time_ns"):
            for b in self.mem[:-1]:
                sp.writer.write_batch(b)
            sp.finish_write()
        self.metrics.add("spill_count", 1)
        self.metrics.add("spilled_bytes", sp.size)
        last = self.mem[-1]
        freed = self.nbytes - last.nbytes()
        self.mem = [last]
        self.nbytes = last.nbytes()
        self.spills.append(sp)
        return freed

    def empty(self) -> bool:
        return not self.mem and not self.spills

    def last(self) -> ColumnarBatch:
        return self.mem[-1]

    def iter_batches(self) -> Iterator[ColumnarBatch]:
        """Stream the buffered rows WITHOUT materializing them: spill files
        replay from disk, resident batches follow. Re-iterable (spill files
        seek to 0 on each pass)."""
        for sp in self.spills:
            yield from sp.read_batches()
        yield from self.mem

    def discard(self):
        """Drop the buffered rows after a pass consumed them."""
        for sp in self.spills:
            sp.release()
        self.spills = []
        self.mem = []
        self.nbytes = 0
        self.update_mem_used(0)

    def drain(self) -> List[ColumnarBatch]:
        batches: List[ColumnarBatch] = []
        for sp in self.spills:
            batches.extend(sp.read_batches())
            sp.release()
        batches.extend(self.mem)
        self.spills = []
        self.mem = []
        self.nbytes = 0
        self.update_mem_used(0)
        return batches

    def release(self):
        for sp in self.spills:
            sp.release()
        self.spills = []


class WindowExec(Operator):
    def __init__(self, child: Operator, window_exprs: List[WindowExpr],
                 partition_spec: List[E.Expr], order_spec: List[E.SortOrder],
                 group_limit: Optional[int] = None, output_window_cols: bool = True):
        self.window_exprs = window_exprs
        self.partition_spec = partition_spec
        self.order_spec = order_spec
        self.group_limit = group_limit
        self.output_window_cols = output_window_cols
        schema = self._output_schema(child.schema)
        super().__init__(schema, [child])

    @property
    def takes_wide_planes(self) -> bool:
        return self._device_spec() is not None

    def _window_fields(self, child_schema: T.Schema) -> List[T.StructField]:
        """The window columns, typed, whether or not they are put out."""
        extra = []
        for w in self.window_exprs:
            if w.kind == "agg":
                arg_t = (E.infer_type(w.agg.args[0], child_schema)
                         if w.agg.args else T.NULL)
                dt = w.return_type or w.agg.return_type or \
                    E.agg_result_type(w.agg.fn, arg_t)
            else:
                dt = w.return_type or (T.I32 if w.kind in ("rank", "dense_rank") else T.I64)
            extra.append(T.StructField(w.name, dt))
        return extra

    def _output_schema(self, child_schema: T.Schema) -> T.Schema:
        if not self.output_window_cols:
            return child_schema
        return T.Schema(child_schema.fields
                        + tuple(self._window_fields(child_schema)))

    def _segmentable(self) -> bool:
        """Rank-family counters and default-frame aggregates compute as
        restart-at-segment scans with only a carry across batches; explicit
        ROWS/RANGE offset frames need random access within the partition and
        keep the buffer-then-process path."""
        return all(w.kind in ("row_number", "rank", "dense_rank")
                   or (w.kind == "agg" and w.frame is None)
                   for w in self.window_exprs)

    def _device_spec(self):
        """The device programs' spec (ops/window_device), or None: this
        window takes the host paths below, and its batches count as
        ``window_host_batches``."""
        from blaze_tpu.ops import window_device as WD

        child_schema = self.children[0].schema
        spec = WD.plan(self.window_exprs, self.partition_spec,
                       self.order_spec, child_schema,
                       [f.dtype for f in self._window_fields(child_schema)])
        if spec is not None and self.group_limit is not None and \
                self._limit_expr() is None:
            return None  # the plane the limit filters on is not computed
        return spec

    def _limit_expr(self) -> Optional[int]:
        """Which window expression's plane ``group_limit`` filters on
        (:meth:`_limit_vals`), if one of them is that plane."""
        kinds = [w.kind for w in self.window_exprs]
        want = "rank" if set(kinds) == {"rank"} else \
            "dense_rank" if set(kinds) == {"dense_rank"} else "row_number"
        return kinds.index(want) if want in kinds else None

    @property
    def takes_coded(self) -> bool:
        """The device program takes a name among its keys as the column's
        int32 codes; the host paths are handed host columns (`_input`)."""
        return self._device_spec() is not None

    def _var_width_keys(self) -> List[E.Expr]:
        child_schema = self.children[0].schema
        return [e for e in list(self.partition_spec)
                + [so.child for so in self.order_spec]
                if T.is_var_width(E.infer_type(e, child_schema))]

    def _input(self, partition, ctx, metrics, batches=None):
        """The child's batches for a HOST path: coded columns as host
        columns over their dictionaries (what `execute_child` hands an
        operator that does not take them)."""
        if batches is None:
            batches = self.execute_child(0, partition, ctx, metrics)
        if not self.takes_coded:
            return batches
        return (b.coded_to_host(metrics) for b in batches)

    def _execute(self, partition, ctx, metrics):
        spec = self._device_spec()
        batches = None
        names = self._var_width_keys() if spec is not None else ()
        if names:
            # a name is a device key only where it arrives coded: the
            # stream's first batch says which (as ops/agg.py does)
            from blaze_tpu.ops.agg import _Peeked

            first = _Peeked(self.execute_child(0, partition, ctx, metrics))
            if first.head is None:
                return
            if not all(isinstance(_argument(e, first.head), CodedColumn)
                       for e in names):
                spec = None
            batches = iter(first)
        if spec is not None:
            yield from self._execute_device(spec, partition, ctx, metrics,
                                            batches)
            return
        if self._segmentable():
            yield from self._execute_segmented(partition, ctx, metrics,
                                               batches)
            return
        child_schema = self.children[0].schema
        # buffered partition slices are memmgr-watched: accumulation spills
        # to disk under pressure, but the partition must fit at process time
        pending = _PartitionBuffer(child_schema, metrics)
        ctx.mem.register(pending)
        bs = ctx.conf.batch_size

        def process_partition() -> Iterator[ColumnarBatch]:
            if pending.empty():
                return
            # tripwire: the segmented path never takes this per-group loop —
            # a nonzero count on a default-frame plan means a fast-path
            # regression
            metrics.add("window_group_loops", 1)
            part = ColumnarBatch.concat(pending.drain(), child_schema, metrics)
            out = self._process_one_partition(part)
            for off in range(0, out.num_rows, bs):
                yield out.slice(off, bs)

        try:
            yield from self._execute_buffered(partition, ctx, metrics,
                                              pending, process_partition,
                                              batches)
        finally:
            ctx.mem.unregister(pending)
            pending.release()

    def _execute_buffered(self, partition, ctx, metrics, pending,
                          process_partition, batches=None):
        from blaze_tpu.ops.joins.keymap import RunningKeyCodes

        part_ev = ExprEvaluator(self.partition_spec,
                                self.children[0].schema) \
            if self.partition_spec else None
        part_keys = RunningKeyCodes()
        started = False
        for batch in self._input(partition, ctx, metrics, batches):
            n = batch.num_rows
            if n == 0:
                continue
            metrics.add("window_host_batches", 1)
            metrics.add("window_rows", n)
            # self-time lands in elapsed_compute_time_ns via Operator.execute
            if part_ev is None:
                ch = np.zeros(n, dtype=bool)
                ch[0] = not started
            else:
                ch = part_keys.change_mask(batch, part_ev.evaluate(batch))
            started = True
            bounds = np.nonzero(ch)[0]
            # a True at row 0 closes the pending partition; later Trues
            # close the piece before them — the carried key row makes the
            # continuation check free (no one-row pylist comparison)
            if not pending.empty() and len(bounds) and bounds[0] == 0:
                yield from process_partition()
            starts = [0] + [int(b) for b in bounds if b > 0]
            ends = starts[1:] + [n]
            for i, (s, e) in enumerate(zip(starts, ends)):
                if i > 0:
                    yield from process_partition()
                pending.append(batch.slice(s, e - s))
        yield from process_partition()

    # -- device execution (counters + running-frame aggregates) ---------------

    def _execute_device(self, spec, partition, ctx, metrics, batches=None):
        """One ``jit(window_scan)`` a batch (ops/window_device): the sorted
        batch's key and argument planes in, the window columns' planes out,
        the carry a device value from batch to batch, exact at any width.
        The host waits only where a result is typed wider than int64
        (``sync:window_carry``, one flag a batch): where every value of the
        batch fits, the column goes on as one int64 device plane; where one
        does not, as the type's host column (``wide_host_batches``)."""
        from blaze_tpu.core import kernels as K
        from blaze_tpu.ops import window_device as WD
        from blaze_tpu.utils.device import wait_int

        child_schema = self.children[0].schema
        result_types = [f.dtype for f in self._window_fields(child_schema)]
        key_exprs = list(self.partition_spec) + \
            [so.child for so in self.order_spec]
        arg_exprs = [w.agg.args[0] if w.kind == "agg" and w.agg.args else None
                     for w in self.window_exprs]
        wide = [WD.is_wide_result(e) for e in spec.exprs]
        limit_at = self._limit_expr() if self.group_limit is not None else None

        def finish(batch, planes, fits):
            n = batch.num_rows
            fits = not any(wide) or bool(wait_int(fits, "window_carry"))
            if not fits:
                metrics.add("wide_host_batches", 1)
            cols = [
                WD.wide_host_column(dt, *p, n) if w and not fits else
                DeviceColumn(dt, p[0], p[-1])
                for dt, p, w in zip(result_types, planes, wide)]
            out = ColumnarBatch(self.schema, list(batch.columns) + cols, n) \
                if self.output_window_cols else batch
            if limit_at is not None:
                out = _keep_at_most(out, cols[limit_at], self.group_limit)
            if out is not None:
                yield out

        # where there is a flag to wait for, a batch leaves once the NEXT
        # one's program is enqueued: the carry is exact either way and needs
        # no flag, so the wait for batch k's falls behind batch k + 1's work
        # and the device is not left idle for it (2.6% of q51's query_s on
        # the chip, PERF.md section 6, PR 31)
        carry = ahead = None
        # the carry holds the last row's codes: one dictionary a stream
        from blaze_tpu.core.dictionary import OneDictionary

        one_dictionary = OneDictionary()
        if batches is None:
            batches = self.execute_child(0, partition, ctx, metrics)
        for batch in batches:
            n = batch.num_rows
            if n == 0:
                continue
            metrics.add("window_rows", n)
            metrics.add("window_device_batches", 1)
            cap = batch.capacity
            keys = [_argument(e, batch) for e in key_exprs]
            for i, k in enumerate(keys):
                if isinstance(k, CodedColumn):
                    keys[i], _grown, remapped = one_dictionary.keep(i, k, n)
                    if remapped:
                        metrics.add("dict_remap_rows", remapped)
            keys = [_planes(k, cap) for k in keys]
            args = [None if e is None else _planes(_argument(e, batch), cap)
                    for e in arg_exprs]
            if carry is None:
                carry = WD.initial_carry(
                    spec, [k[0].dtype for k in keys],
                    [None if a is None else a[0].dtype for a in args])
            planes, carry, fits = K._dispatch(
                WD.window_scan, np.int32(n), tuple(keys), tuple(args), carry,
                spec=spec, cap=cap)
            done, ahead = (ahead, (batch, planes, fits)) if any(wide) else \
                ((batch, planes, fits), None)
            if done is not None:
                yield from finish(*done)
        if ahead is not None:
            yield from finish(*ahead)
        if carry is not None:
            metrics.add("window_segments",
                        wait_int(carry["segments"], "window_carry"))

    # -- segmented execution (counters + default-frame aggregates) ------------

    def _execute_segmented(self, partition, ctx, metrics, batches=None):
        """One pass, one shot per batch: boundary masks + restart-at-segment
        scans (core/kernels) replace the per-group loop entirely. The carry
        across batches is O(1): counter bases, per-aggregate (sum, count,
        extremum) accumulators, and the last partition/order key row inside
        the RunningKeyCodes detectors."""
        from blaze_tpu.core import kernels as K
        from blaze_tpu.ops import sort_keys as SK
        from blaze_tpu.ops.joins.keymap import RunningKeyCodes

        child_schema = self.children[0].schema
        aggs = [w for w in self.window_exprs if w.kind == "agg"]
        has_order = bool(self.order_spec)
        part_ev = ExprEvaluator(self.partition_spec, child_schema) \
            if self.partition_spec else None
        order_ev = ExprEvaluator([so.child for so in self.order_spec],
                                 child_schema) if has_order else None
        part_keys = RunningKeyCodes()
        order_keys = RunningKeyCodes()
        started = False
        c_rn, c_rank, c_dense = 0, 1, 0
        acc = {id(w): [0, 0, None] for w in aggs}   # sum, count, extremum
        # the open tail group, withheld until its frame value is known: its
        # counters are degenerate (rank/dense constant, row_number
        # consecutive), so the buffer carries child rows + three scalars
        hold = _PartitionBuffer(child_schema, metrics)
        ctx.mem.register(hold)
        hold_rn0 = hold_rank = hold_dense = 1

        def flush_hold(close_vals):
            if hold.empty():
                return
            if hold.spills:
                metrics.add("streamed_partitions", 1)
            off = 0
            for hb in hold.iter_batches():
                m = hb.num_rows
                rn_h = hold_rn0 + off + np.arange(m, dtype=np.int64)
                off += m
                rank_h = np.full(m, hold_rank, np.int64)
                dense_h = np.full(m, hold_dense, np.int64)
                sel = self._limit_select(rn_h, rank_h, dense_h)
                if sel is not None:
                    if not len(sel):
                        continue
                    hb = hb.take(sel)
                    rn_h, rank_h, dense_h = rn_h[sel], rank_h[sel], dense_h[sel]
                m = hb.num_rows
                vals = {k: ([v[0]] * m, [v[1]] * m, [v[2]] * m)
                        for k, v in close_vals.items()}
                yield self._emit_rows(hb, rn_h, rank_h, dense_h, vals)
            hold.discard()

        try:
            for batch in self._input(partition, ctx, metrics, batches):
                n = batch.num_rows
                if n == 0:
                    continue
                metrics.add("window_host_batches", 1)
                metrics.add("window_rows", n)
                if part_ev is None:
                    part_start = np.zeros(n, dtype=bool)
                    part_start[0] = not started
                else:
                    part_start = part_keys.change_mask(
                        batch, part_ev.evaluate(batch))
                if has_order:
                    new_peer = part_start | order_keys.push_rows(
                        SK.peer_key_rows(batch, self.order_spec, order_ev))
                else:
                    new_peer = part_start.copy()
                started = True
                metrics.add("window_segments", int(part_start.sum()))
                rn, rank, dense = K.restarting_counters(
                    part_start, new_peer, c_rn, c_rank, c_dense)
                if not aggs:
                    # counters are final the moment they're computed: emit
                    # the whole batch, nothing withheld, nothing buffered
                    sel = self._limit_select(rn, rank, dense)
                    if sel is None:
                        yield self._emit_rows(batch, rn, rank, dense, {})
                    elif len(sel):
                        yield self._emit_rows(batch.take(sel), rn[sel],
                                              rank[sel], dense[sel], {})
                    c_rn, c_rank = int(rn[-1]), int(rank[-1])
                    c_dense = int(dense[-1])
                    continue
                # default frames close at the row's boundary-segment END:
                # the peer group when ordered (RANGE unbounded..current row,
                # peers share the value), the whole partition otherwise
                bmask = new_peer if has_order else part_start
                scans = {id(w): self._seg_agg_scan(w, batch, part_start,
                                                   acc[id(w)])
                         for w in aggs}
                bounds = np.nonzero(bmask)[0]
                if not len(bounds):
                    # the entire batch continues the open group
                    keep = self._trim_tail(rn, rank, dense)
                    if keep:
                        hold.append(batch if keep == n
                                    else batch.slice(0, keep))
                    self._roll_carry(aggs, scans, acc)
                    c_rn, c_rank = int(rn[-1]), int(rank[-1])
                    c_dense = int(dense[-1])
                    continue
                b0 = int(bounds[0])
                hold_from = int(bounds[-1])
                # the boundary at b0 closes the withheld group: its frame
                # value is the carry-seeded cumulative just before it
                close_vals = {}
                for w in aggs:
                    k = id(w)
                    cs, cc, run = scans[k]
                    if b0 > 0:
                        close_vals[k] = (cs[b0 - 1], int(cc[b0 - 1]),
                                         run[b0 - 1] if run is not None
                                         else None)
                    else:
                        close_vals[k] = tuple(acc[k])
                yield from flush_hold(close_vals)
                if hold_from > 0:
                    # rows before the last boundary close within this batch:
                    # backfill each row's value from its segment end
                    j = np.searchsorted(bounds, np.arange(hold_from),
                                        side="right")
                    end_idx = bounds[j] - 1
                    rn_e, rank_e = rn[:hold_from], rank[:hold_from]
                    dense_e = dense[:hold_from]
                    sel = self._limit_select(rn_e, rank_e, dense_e)
                    if sel is None or len(sel):
                        if sel is None:
                            rows = batch.slice(0, hold_from)
                            ei = end_idx
                        else:
                            rows = batch.take(sel)
                            rn_e, rank_e = rn_e[sel], rank_e[sel]
                            dense_e = dense_e[sel]
                            ei = end_idx[sel]
                        vals = {}
                        for w in aggs:
                            k = id(w)
                            cs, cc, run = scans[k]
                            vals[k] = (list(cs[ei]), list(cc[ei]),
                                       list(run[ei]) if run is not None
                                       else [None] * len(ei))
                        yield self._emit_rows(rows, rn_e, rank_e, dense_e,
                                              vals)
                # withhold the open tail group (emits when it closes); rows
                # that can no longer survive the group limit never enter
                keep = self._trim_tail(rn[hold_from:], rank[hold_from:],
                                       dense[hold_from:])
                if keep:
                    hold.append(batch.slice(hold_from, keep))
                    hold_rn0 = int(rn[hold_from])
                    hold_rank = int(rank[hold_from])
                    hold_dense = int(dense[hold_from])
                self._roll_carry(aggs, scans, acc)
                c_rn, c_rank = int(rn[-1]), int(rank[-1])
                c_dense = int(dense[-1])
            yield from flush_hold({k: tuple(v) for k, v in acc.items()})
        finally:
            ctx.mem.unregister(hold)
            hold.release()

    @staticmethod
    def _roll_carry(aggs, scans, acc):
        """Advance the open-partition accumulators to the batch's last row
        (the scans restart at partition starts, so the last value IS the
        open partition's running state)."""
        for w in aggs:
            k = id(w)
            cs, cc, run = scans[k]
            acc[k] = [cs[-1], int(cc[-1]),
                      run[-1] if run is not None else acc[k][2]]

    def _seg_agg_scan(self, w: WindowExpr, batch: ColumnarBatch,
                      part_start: np.ndarray, a):
        """Carry-seeded within-partition cumulatives (sum, count[, running
        extremum]) for one aggregate over one batch. Device-resident
        SUM/AVG/COUNT arguments scan in ONE jitted dispatch
        (kernels.segment_scan_planes); everything else — decimals, host
        columns, MIN/MAX — takes the numpy segmented scans."""
        from blaze_tpu.core import kernels as K

        F = E.AggFunction
        agg = w.agg
        if agg.args and agg.fn in (F.SUM, F.AVG, F.COUNT):
            arg_t = E.infer_type(agg.args[0], batch.schema)
            if not isinstance(arg_t, T.DecimalType):
                col = ExprEvaluator(list(agg.args),
                                    batch.schema).evaluate(batch)[0]
                if isinstance(col, DeviceColumn) and \
                        col.data.shape[0] == batch.capacity and \
                        col.data.dtype != bool:
                    cs, cc = K.segment_scan_planes(
                        col.data, col.validity, batch.row_exists_mask(),
                        part_start, a[0], a[1])
                    return cs, cc, None
        nv, valid = self._agg_arg(w, batch)
        cs, cc = K.segment_cumsum(nv, valid, part_start, a[0], a[1])
        run = None
        if agg.fn in (F.MIN, F.MAX):
            run = K.segment_running_reduce(nv, valid, part_start,
                                           agg.fn == F.MIN, a[2])
        return cs, cc, run

    def _limit_vals(self, rn, rank, dense):
        """The plane group_limit filters on (reference: window_exec.rs:
        227-236): rank() <= K and dense_rank() <= K keep boundary-tied rows;
        anything else limits by row number."""
        kinds = {w.kind for w in self.window_exprs}
        if kinds == {"rank"}:
            return rank
        if kinds == {"dense_rank"}:
            return dense
        return rn

    def _limit_select(self, rn, rank, dense):
        """Surviving-row indices under group_limit, or None for keep-all."""
        if self.group_limit is None:
            return None
        keep = np.nonzero(
            self._limit_vals(rn, rank, dense) <= self.group_limit)[0]
        return None if len(keep) == len(rn) else keep

    def _trim_tail(self, rn, rank, dense) -> int:
        """How many leading rows of the open tail group can still survive
        the group limit. Limit values are nondecreasing within a partition
        (rank/dense constant over the tail, row_number consecutive), so
        survivors form a prefix — rows past rank k are masked out BEFORE the
        remaining window columns are computed or buffered."""
        if self.group_limit is None:
            return len(rn)
        vals = self._limit_vals(rn, rank, dense)
        return int(np.searchsorted(vals, self.group_limit, side="right"))

    def _emit_rows(self, rows: ColumnarBatch, rn, rank, dense, agg_vals):
        """Child rows + computed window columns -> one output batch. ``rows``
        is already group-limited, so aggregate finalization (the python-level
        typed/decimal conversion) runs only on surviving rows."""
        if not self.output_window_cols:
            return rows
        out_cols = list(rows.columns)
        fields = list(rows.schema.fields)
        child_schema = self.children[0].schema
        for w in self.window_exprs:
            if w.kind == "row_number":
                col, dt = DeviceColumn.from_numpy(
                    T.I64, np.asarray(rn, np.int64), None,
                    rows.capacity), T.I64
            elif w.kind == "rank":
                col, dt = DeviceColumn.from_numpy(
                    T.I32, np.asarray(rank).astype(np.int32), None,
                    rows.capacity), T.I32
            elif w.kind == "dense_rank":
                col, dt = DeviceColumn.from_numpy(
                    T.I32, np.asarray(dense).astype(np.int32), None,
                    rows.capacity), T.I32
            else:
                fsum, fcnt, fval = agg_vals[id(w)]
                col, dt = self._agg_result_col(w, child_schema, fsum, fcnt,
                                               fval)
            out_cols.append(col)
            fields.append(T.StructField(w.name, dt))
        return ColumnarBatch(T.Schema(tuple(fields)), out_cols,
                             rows.num_rows)

    # -- shared aggregate plumbing --------------------------------------------

    def _agg_arg(self, w: WindowExpr, batch: ColumnarBatch):
        """(masked_values, valid) for one aggregate's argument over a batch
        — decimals as exact objects, everything else numeric."""
        n = batch.num_rows
        agg = w.agg
        if not agg.args:
            return np.zeros(n, dtype=np.int64), np.ones(n, bool)
        arg_t = E.infer_type(agg.args[0], batch.schema)
        ev = ExprEvaluator(list(agg.args), batch.schema)
        arr = ev.evaluate(batch)[0].to_arrow(n)
        valid = (~np.asarray(arr.is_null())) if arr.null_count \
            else np.ones(n, bool)
        if isinstance(arg_t, T.DecimalType):
            from decimal import Decimal

            nv = np.array([Decimal(0) if v is None else v
                           for v in arr.to_pylist()], dtype=object)
        else:
            nv = arr.fill_null(0).to_numpy(zero_copy_only=False)
            if nv.dtype != object:
                nv = np.where(valid, nv, 0)
        return nv, valid

    def _agg_result_col(self, w: WindowExpr, child_schema: T.Schema,
                        fsum, fcnt, fval):
        """Finalize per-row (sum, count, min/max) frame values into the
        typed output column — shared by the segmented and buffered paths."""
        agg = w.agg
        arg_t = (E.infer_type(agg.args[0], child_schema)
                 if agg.args else T.NULL)
        result_t = w.return_type or agg.return_type or \
            E.agg_result_type(agg.fn, arg_t)
        F = E.AggFunction
        if agg.fn == F.COUNT:
            out = list(fcnt)
        elif agg.fn == F.SUM:
            out = [s if c > 0 else None for s, c in zip(fsum, fcnt)]
        elif agg.fn == F.AVG:
            out = [(s / c if c > 0 else None) for s, c in zip(fsum, fcnt)]
        elif agg.fn in (F.MIN, F.MAX):
            out = [v if c > 0 else None for v, c in zip(fval, fcnt)]
        else:
            raise NotImplementedError(f"window agg {agg.fn}")
        if isinstance(result_t, T.DecimalType):
            from decimal import ROUND_HALF_UP, Decimal

            q = Decimal(1).scaleb(-result_t.scale)
            out = [None if v is None
                   else Decimal(v).quantize(q, rounding=ROUND_HALF_UP)
                   for v in out]
        elif result_t == T.F64:
            out = [None if v is None else float(v) for v in out]
        return HostColumn(result_t,
                          pa.array(out, type=T.to_arrow_type(result_t))), \
            result_t

    # -- per-partition computation (explicit-frame path) ----------------------

    def _single_peer_mask(self, part: ColumnarBatch) -> np.ndarray:
        """Peer-boundary mask within ONE fully-buffered partition."""
        n = part.num_rows
        out = np.zeros(n, dtype=bool)
        if n == 0:
            return out
        if not self.order_spec:
            out[0] = True
            return out
        from blaze_tpu.ops import sort_keys as SK
        from blaze_tpu.ops.joins.keymap import RunningKeyCodes

        return RunningKeyCodes().push_rows(
            SK.peer_key_rows(part, self.order_spec))

    def _process_one_partition(self, part: ColumnarBatch) -> ColumnarBatch:
        n = part.num_rows
        new_peer = self._single_peer_mask(part)
        rn = np.arange(1, n + 1, dtype=np.int64)
        # rank: row number at each peer-group start, broadcast over the group
        peer_start_rn = np.where(new_peer, rn, 0)
        rank = np.maximum.accumulate(peer_start_rn)
        dense = np.cumsum(new_peer)

        out_cols = list(part.columns)
        fields = list(part.schema.fields)
        for w in self.window_exprs:
            if w.kind == "row_number":
                col, dt = DeviceColumn.from_numpy(T.I64, rn, None, part.capacity), T.I64
            elif w.kind == "rank":
                col, dt = DeviceColumn.from_numpy(
                    T.I32, rank.astype(np.int32), None, part.capacity), T.I32
            elif w.kind == "dense_rank":
                col, dt = DeviceColumn.from_numpy(
                    T.I32, dense.astype(np.int32), None, part.capacity), T.I32
            elif w.kind == "agg":
                col, dt = self._window_agg(w, part, new_peer)
            else:
                raise NotImplementedError(f"window function {w.kind}")
            if self.output_window_cols:
                out_cols.append(col)
                fields.append(T.StructField(w.name, dt))
        out = ColumnarBatch(T.Schema(tuple(fields)), out_cols, n) \
            if self.output_window_cols else part
        if self.group_limit is not None:
            keep = np.nonzero(
                self._limit_vals(rn, rank, dense) <= self.group_limit)[0]
            if len(keep) < n:
                out = out.take(keep)
        return out

    def _range_frame_bounds(self, part: ColumnarBatch, lo, hi, n: int):
        """Per-row [start, end) over a RANGE frame: searchsorted against the
        partition's single numeric order key (input is sorted by it). Null
        order keys form their own run whose frame is exactly that run
        (Spark: null peers). Descending orders negate the key axis."""
        if len(self.order_spec) != 1:
            raise NotImplementedError("RANGE frame needs a single order key")
        so = self.order_spec[0]
        ev = ExprEvaluator([so.child], part.schema)
        col = ev.evaluate(part)[0]
        arr = col.to_arrow(n)
        valid = (~np.asarray(arr.is_null())) if arr.null_count else np.ones(n, bool)
        keys = arr.fill_null(0).to_numpy(zero_copy_only=False)
        if np.issubdtype(keys.dtype, np.datetime64):
            keys = keys.view(np.int64)
        if not np.issubdtype(keys.dtype, np.integer):
            keys = keys.astype(np.float64)  # ints stay exact (2^53+ keys)
        if not so.ascending:
            keys = -keys
        start = np.zeros(n, np.int64)
        end_excl = np.full(n, n, np.int64)
        if valid.all():
            nn_lo, nn_hi, kk = 0, n, keys
        elif not valid.any():
            # whole partition is one null peer run: every frame is all of it
            return start, end_excl
        else:
            # the null run is contiguous (sorted input): its rows frame over
            # the run itself for offset bounds; UNBOUNDED sides span the
            # whole partition (Spark UnboundedPreceding/FollowingWindow
            # FunctionFrame starts/ends at the partition edge, nulls
            # included). Non-null rows search the non-null span for offset
            # bounds, partition edges for unbounded ones.
            nn_idx = np.nonzero(valid)[0]
            nn_lo, nn_hi = int(nn_idx[0]), int(nn_idx[-1]) + 1
            if not valid[nn_lo:nn_hi].all():
                raise NotImplementedError("non-contiguous null order keys")
            null_rows = ~valid
            run_lo = 0 if null_rows[0] else nn_hi
            run_hi = nn_lo if null_rows[0] else n
            start[null_rows] = 0 if lo is None else run_lo
            end_excl[null_rows] = n if hi is None else run_hi
            kk = keys[nn_lo:nn_hi]
        # lower bound: key + lo (lo <= 0 for PRECEDING offsets)
        if lo is not None:
            s = np.searchsorted(kk, keys + _offset(keys, lo),
                                side="left") + nn_lo
            start[valid] = s[valid]
        else:
            start[valid] = 0
        if hi is not None:
            e = np.searchsorted(kk, keys + _offset(keys, hi),
                                side="right") + nn_lo
            end_excl[valid] = e[valid]
        else:
            end_excl[valid] = n
        return start, end_excl

    def _window_agg(self, w: WindowExpr, part: ColumnarBatch, new_peer: np.ndarray):
        n = part.num_rows
        agg = w.agg
        child_schema = part.schema
        arg_t = E.infer_type(agg.args[0], child_schema) if agg.args else T.NULL

        if agg.args:
            ev = ExprEvaluator(list(agg.args), part.schema)
            col = ev.evaluate(part)[0]
            arr = col.to_arrow(n)
            valid = (~np.asarray(arr.is_null())) if arr.null_count else np.ones(n, bool)
            if isinstance(arg_t, T.DecimalType):
                from decimal import Decimal

                nv = np.array([Decimal(0) if v is None else v for v in arr.to_pylist()],
                              dtype=object)
            else:
                nv = arr.fill_null(0).to_numpy(zero_copy_only=False)
        else:
            valid = np.ones(n, bool)
            nv = np.zeros(n, dtype=np.int64)

        F = E.AggFunction
        has_order = bool(self.order_spec)
        masked = np.where(valid, nv, 0) if nv.dtype != object else nv
        frame = tuple(w.frame) if w.frame is not None else None
        if frame is not None and frame[0] in ("rows", "range"):
            # explicit frame (reference: SpecifiedWindowFrame). ROWS: per-row
            # [i+lo, i+hi] index windows. RANGE: value windows
            # [key-|lo|, key+hi] resolved by searchsorted over the
            # partition's (already sorted) single order key — CURRENT ROW
            # bounds include peers, matching Spark RANGE semantics.
            lo, hi = frame[1], frame[2]
            idx = np.arange(n)
            if frame[0] == "rows":
                start = np.zeros(n, np.int64) if lo is None else \
                    np.clip(idx + int(lo), 0, n)
                end_excl = np.full(n, n, np.int64) if hi is None else \
                    np.clip(idx + int(hi) + 1, 0, n)
            else:
                start, end_excl = self._range_frame_bounds(part, lo, hi, n)
            end_excl = np.maximum(end_excl, start)
            general_minmax = frame[0] == "range"
            zero = masked[0] * 0 if n else 0  # object-safe (Decimal) zero
            cs0 = np.concatenate([[zero], np.cumsum(masked)])
            cc0 = np.concatenate([[0], np.cumsum(valid.astype(np.int64))])
            fsum = cs0[end_excl] - cs0[start]
            fcnt = cc0[end_excl] - cc0[start]
            if agg.fn in (F.MIN, F.MAX):
                fval = _frame_minmax(nv, valid, lo, hi, start, end_excl,
                                     agg.fn == F.MIN, fcnt > 0,
                                     general=general_minmax)
        elif has_order:
            csum = np.cumsum(masked)
            ccnt = np.cumsum(valid.astype(np.int64))
            # frame value at each row = value at its peer-group END
            grp = np.cumsum(new_peer) - 1
            last_idx_of_grp = np.concatenate([np.nonzero(new_peer)[0][1:] - 1, [n - 1]])
            end_idx = last_idx_of_grp[grp]
            fsum = csum[end_idx]
            fcnt = ccnt[end_idx]
            if agg.fn in (F.MIN, F.MAX):
                accfn = np.minimum if agg.fn == F.MIN else np.maximum
                run = _masked_running(nv, valid, accfn, agg.fn == F.MIN)
                fval = run[end_idx]
        else:
            fsum = np.full(n, masked.sum())
            fcnt = np.full(n, int(valid.sum()))
            if agg.fn in (F.MIN, F.MAX):
                vv = [v for v, ok in zip(nv.tolist(), valid.tolist()) if ok]
                m = (min(vv) if agg.fn == F.MIN else max(vv)) if vv else None
                fval = np.array([m] * n, dtype=object)

        fvals = fval.tolist() if agg.fn in (F.MIN, F.MAX) else [None] * n
        return self._agg_result_col(w, child_schema, fsum.tolist(),
                                    fcnt.tolist(), fvals)


def _argument(expr: E.Expr, batch: ColumnarBatch):
    """A key or an aggregate's argument as a column. A bare column is taken
    as the batch holds it: the evaluator pays two eager dispatches a column
    reference (PERF.md section 7), and a wide decimal that an earlier window
    proved into an int64 plane is wanted as that plane."""
    if isinstance(expr, E.Column):
        return batch.columns[batch.schema.index_of(expr.name)]
    if isinstance(expr, E.BoundReference):
        return batch.columns[expr.index]
    return ExprEvaluator([expr], batch.schema).evaluate(batch)[0]


def _planes(col, capacity: int):
    """A key's or an argument's column as ``jit(window_scan)`` takes it:
    (data, validity), or a wide decimal's host column as its two words."""
    from blaze_tpu.ops import window_device as WD

    if has_planes(col):  # a coded key's plane is its int32 codes
        return col.data, col.validity
    if WD.is_wide_decimal(col.dtype):
        return WD.wide_words(col, capacity)
    col = _arrow_to_column(col.array, col.dtype, capacity)
    return col.data, col.validity


def _keep_at_most(out: ColumnarBatch, limit_col, k: int):
    """``out``'s rows whose ``limit_col`` (a device plane: rank, dense_rank
    or row_number) is at most ``k``: one compaction, or None if no row is."""
    from blaze_tpu.core import kernels as K
    from blaze_tpu.utils.device import wait_array

    mask = limit_col.validity & (limit_col.data <= k)
    if len(out._device_slots()) < len(out.columns):  # host payload columns
        keep = np.nonzero(wait_array(mask, "window_limit")[:out.num_rows])[0]
        return out.take(keep) if len(keep) else None
    count, datas, valids = K.compact_planes(
        [c.data for c in out.columns], [c.validity for c in out.columns], mask)
    if count == 0:
        return None
    if count == out.num_rows:
        return out
    return ColumnarBatch(out.schema, [
        c.like(d, v) for c, d, v in zip(out.columns, datas, valids)], count)


def _offset(keys: np.ndarray, off) -> np.ndarray:
    """Frame offset in the key's dtype (integer keys keep exact int64
    arithmetic; float offsets on int keys promote)."""
    if np.issubdtype(keys.dtype, np.integer) and float(off) == int(off):
        return np.int64(int(off))
    return np.float64(off)


def _frame_minmax(vals, valid, lo, hi, start, end_excl, is_min: bool,
                  has: np.ndarray, general: bool = False) -> np.ndarray:
    """Per-row min/max over ROWS-frame windows [start, end); ``has`` marks
    rows whose frame holds at least one valid value (the caller's fcnt>0).
    Numeric values vectorize: finite (lo, hi) via sentinel-padded sliding
    windows, half-unbounded via running accumulates; object (decimal)
    values fall back to per-row slice scans."""
    n = len(vals)
    out = np.empty(n, dtype=object)
    if n == 0:
        return out
    if lo is not None:
        lo = max(int(lo), -n)  # clamp: a billion-row PRECEDING offset must
    if hi is not None:
        hi = min(int(hi), n)   # not allocate billion-entry sentinel padding
    numeric = vals.dtype != object and not general
    # ``general`` (RANGE value windows): lo/hi are VALUE offsets, so the
    # index-based fast paths below do not apply — use the per-row scan over
    # the exact [start, end) bounds
    if numeric:
        if np.issubdtype(vals.dtype, np.floating):
            sent = np.array(np.inf if is_min else -np.inf, vals.dtype)
        else:
            info = np.iinfo(vals.dtype)
            sent = np.array(info.max if is_min else info.min, vals.dtype)
        x = np.where(valid, vals, sent)
        red = np.minimum if is_min else np.maximum
        if lo is not None and hi is not None:
            w = int(hi) - int(lo) + 1
            if w <= 0:
                out[:] = None
                return out
            pad_lo = max(0, -int(lo))
            pad_hi = max(0, int(hi))
            xp = np.concatenate([np.full(pad_lo, sent, vals.dtype), x,
                                 np.full(pad_hi, sent, vals.dtype)])
            sw = np.lib.stride_tricks.sliding_window_view(xp, w)
            got = (sw.min(axis=1) if is_min else sw.max(axis=1))[
                np.arange(n) + int(lo) + pad_lo]
        elif lo is None:
            run = red.accumulate(x)  # unbounded preceding .. i+hi
            got = run[np.clip(end_excl - 1, 0, n - 1)]
        else:
            run = red.accumulate(x[::-1])[::-1]  # i+lo .. unbounded following
            got = run[np.clip(start, 0, n - 1)]
        out[has] = got[has]
        out[~has] = None
        return out
    better = (lambda a, b: a < b) if is_min else (lambda a, b: a > b)
    for i in range(n):
        s, e = int(start[i]), int(end_excl[i])
        best = None
        for j in range(s, e):
            if valid[j]:
                v = vals[j]
                if best is None or better(v, best):
                    best = v
        out[i] = best
    return out


def _masked_running(vals, valid, accfn, is_min: bool):
    """Running min/max ignoring invalid entries (numpy accumulate with
    sentinel substitution)."""
    if vals.dtype == object:
        out = np.empty(len(vals), dtype=object)
        cur = None
        better = (lambda a, b: a < b) if is_min else (lambda a, b: a > b)
        for i, (v, ok) in enumerate(zip(vals.tolist(), valid.tolist())):
            if ok and (cur is None or better(v, cur)):
                cur = v
            out[i] = cur
        return out
    if np.issubdtype(vals.dtype, np.floating):
        sent = np.inf if is_min else -np.inf
    else:
        info = np.iinfo(vals.dtype)
        sent = info.max if is_min else info.min
    subst = np.where(valid, vals, sent)
    return accfn.accumulate(subst)
