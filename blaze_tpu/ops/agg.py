"""Hash aggregation with typed columnar state, spill, and partial skipping.

Reference: ``agg_exec.rs:44-844`` + ``agg/agg_table.rs`` — an in-memory
hash table of group keys with vectorized accumulator columns, bucketed
sorted spill under memory pressure, and adaptive partial-skipping when the
group cardinality ratio is high.

TPU design (SURVEY.md §7.4.2): accumulators are device arrays updated by XLA
scatter ops; group-key interning happens on host (per-batch dedup via
``np.unique`` on the packed key matrix — vectorized C — then a dict lookup
only on the per-batch *distinct* keys). Spills are partial-state batches
sorted by canonical key bytes; the output phase k-way-merges runs and
re-aggregates chunk-wise, cutting chunks at key boundaries so each chunk is
self-contained (memory-bounded like the reference's bucketed merge).
"""

from __future__ import annotations

import heapq
import pickle
from typing import Iterator, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np
import pyarrow as pa

from blaze_tpu.core.batch import Column, ColumnarBatch, DeviceColumn, HostColumn
from blaze_tpu.exprs.compiler import ExprEvaluator
from blaze_tpu.ir import exprs as E
from blaze_tpu.ir import types as T
from blaze_tpu.ops import aggfns
from blaze_tpu.ops.base import ExecContext, Operator
from blaze_tpu.runtime.memmgr import MemConsumer, SpillFile
from blaze_tpu.utils.device import DEVICE_STATS

_KEY_COL = "#aggkey"

_TM_REINTERN = None


def _reintern_counter():
    """Registry counter for rows whose var-width keys arrived at a merge
    table DECODED (no dictionary) and had to be re-encoded per batch — the
    exact cost the code-carrying shuffle exists to remove. Healthy value
    with ``codes_shuffle`` on: 0."""
    global _TM_REINTERN
    if _TM_REINTERN is None:
        from blaze_tpu.obs.telemetry import get_registry

        _TM_REINTERN = get_registry().counter(
            "blaze_agg_reintern_rows",
            "rows re-interned from decoded var-width keys at a merge table")
    return _TM_REINTERN


class AggExec(Operator):
    def __init__(self, child: Operator, exec_mode: E.AggExecMode,
                 groupings: List[Tuple[str, E.Expr]], aggs: List,
                 supports_partial_skipping: bool = False):
        self.exec_mode = exec_mode
        self.groupings = groupings
        self.aggs = aggs  # list of nodes.AggColumn
        self.supports_partial_skipping = supports_partial_skipping
        schema = self._output_schema(child.schema)
        super().__init__(schema, [child])

    # var-width grouping keys may arrive coded (core/batch.CodedColumn): the
    # device aggers group by the int32 code planes; the host table is handed
    # the same dictionary as a host column (AggTable.process_batch)
    takes_coded = True

    @property
    def takes_wide_planes(self) -> bool:
        """Raw rows' wide-decimal arguments may arrive as a window's proved
        int64 plane: the device aggers cut their limbs from it
        (agg_device._proved_limbs), everything else reads it through the
        evaluator, as its type."""
        return not self.input_is_partial

    @property
    def is_partial_output(self) -> bool:
        return bool(self.aggs) and all(
            a.mode in (E.AggMode.PARTIAL, E.AggMode.PARTIAL_MERGE) for a in self.aggs
        )

    @property
    def input_is_partial(self) -> bool:
        return bool(self.aggs) and all(
            a.mode in (E.AggMode.PARTIAL_MERGE, E.AggMode.FINAL) for a in self.aggs
        )

    def _agg_input_schema(self, child_schema: T.Schema) -> T.Schema:
        """Schema against which agg arg expressions are typed (raw input)."""
        if not self.input_is_partial:
            return child_schema
        # input is partial output: arg types not available; state fields are
        # taken positionally instead
        return child_schema

    def _output_schema(self, child_schema: T.Schema) -> T.Schema:
        from blaze_tpu.ir.aggstate import agg_output_schema

        return agg_output_schema(child_schema, self.groupings, self.aggs,
                                 self.input_is_partial, self.is_partial_output)

    def _make_fns(self, child_schema: T.Schema) -> List[aggfns.AggFunction]:
        if self.input_is_partial:
            # reconstruct arg types from the partial child schema: state
            # fields sit after the groupings in declaration order
            fns = []
            pos = len(self.groupings)
            for a in self.aggs:
                schema, agg, limbs = _partial_arg_schema(a.agg, child_schema, pos)
                fn = aggfns.create_agg_function(agg, schema, limbs=limbs)
                pos += len(fn.state_fields())
                fns.append(fn)
            return fns
        return [aggfns.create_agg_function(a.agg, child_schema) for a in self.aggs]

    def _consolidation_op(self) -> "AggExec":
        """A PARTIAL_MERGE view of this PARTIAL agg, reading its own output
        schema — used to merge one task's per-batch partial states."""
        import dataclasses

        class _SchemaSource(Operator):
            def __init__(self, schema):
                super().__init__(schema, [])

        return AggExec(
            _SchemaSource(self.schema), self.exec_mode,
            [(name, E.Column(name)) for name, _ in self.groupings],
            [dataclasses.replace(a, mode=E.AggMode.PARTIAL_MERGE)
             for a in self.aggs])

    def _try_fuse_join(self, source, partition, ctx, src_metrics):
        """(FusedJoinSpec, build_map) when ``source`` is an inner,
        unconditioned, unique-single-key BroadcastJoin whose two sides are
        all device dtypes — the star-join shape. The statically-eligible
        join's build map loads HERE; when the runtime check then declines
        (duplicate keys, host build columns), the loaded map is returned so
        the caller can drive the unfused probe with it instead of paying a
        second build."""
        from blaze_tpu.ir.nodes import JoinType
        from blaze_tpu.ops.agg_device import FusedJoinSpec
        from blaze_tpu.ops.joins.bhj import BroadcastJoinExec
        from blaze_tpu.utils.device import is_device_dtype

        if not isinstance(source, BroadcastJoinExec):
            return None, None
        if source.join_type != JoinType.INNER or source.condition is not None:
            return None, None
        key_exprs = source._key_exprs(for_build=False)
        if len(key_exprs) != 1:
            return None, None
        probe_schema = source.children[source._probe_child()].schema
        build_schema = source.children[source._build_child()].schema
        from blaze_tpu.ops.agg_device import _is_wide_dec, _touches_wide

        # build side must be fully device; probe side may carry wide
        # decimals (they flatten as limb planes) as long as the KEY never
        # touches one (by name or bound index)
        if not all(is_device_dtype(f.dtype) for f in build_schema.fields):
            return None, None
        if not all(is_device_dtype(f.dtype) or _is_wide_dec(f.dtype)
                   for f in probe_schema.fields):
            return None, None
        if _touches_wide(key_exprs[0], probe_schema):
            return None, None
        bmap = source._load_build_map(partition, ctx, src_metrics)
        if not FusedJoinSpec.runtime_eligible(bmap):
            return None, bmap
        spec = FusedJoinSpec(source, bmap, key_exprs[0],
                             source._probe_child() == 0,
                             probe_schema, build_schema)
        spec.metrics = src_metrics
        return spec, bmap

    def _execute(self, partition, ctx, metrics):
        child_schema = self.children[0].schema
        from blaze_tpu.ops.agg_device import (DevicePartialAgger, coded_keys,
                                              has_var_width_keys,
                                              supports_device_partial)

        device_partial = self.exec_mode == E.AggExecMode.HASH_AGG and \
            supports_device_partial(self, child_schema)
        first = None
        if device_partial and has_var_width_keys(self, child_schema):
            # a var-width key is a device key only where it arrives coded:
            # the stream's first batch says which (a scan's dictionary-read
            # column, a coded join payload, a ROLLUP's Expand over them)
            first = _Peeked(self.execute_child(0, partition, ctx, metrics))
            device_partial = first.head is not None and \
                coded_keys(self, first.head)
            if not device_partial:
                yield from self._execute_table(partition, ctx, metrics,
                                               child_schema, iter(first))
                return
        if device_partial:
            # TPU fast path: per-batch device partials, no host interning.
            # When the child is a fusable FilterExec, its predicate traces
            # into the same jitted kernel (one device call per batch).
            from blaze_tpu.ops.agg_device import supports_fused_filter
            from blaze_tpu.ops.basic import FilterExec

            child_op = self.children[0]
            source = child_op
            fused_preds = None

            # opt-in (conf.fused_filter_agg): chosen for a link that is
            # gone, not measured on the chip
            fuse_ok = ctx.conf.fused_filter_agg
            # non-device agg args keep the agg on the eager path UNLESS
            # they are bare wide-decimal columns, which the fused kernels
            # consume directly as limb-plane jit inputs. Any OTHER traced
            # access to a wide column — a device-typed expression over it
            # (CAST(w AS DOUBLE)) or a grouping touching it — also blocks
            # fusion: the trace would crash on the _WideLimbCol.
            from blaze_tpu.ops.agg_device import (_is_wide_dec,
                                                  _touches_wide)
            from blaze_tpu.utils.device import is_device_dtype as _isdev

            for a in self.aggs:
                if not a.agg.args:
                    continue
                arg = a.agg.args[0]
                at = E.infer_type(arg, child_schema)
                if _is_wide_dec(at) and isinstance(arg, E.Column):
                    continue  # bare wide column: the limb-plane path
                if not _isdev(at) or _touches_wide(arg, child_schema):
                    fuse_ok = False
                    break
            if fuse_ok and any(_touches_wide(ge, child_schema)
                               for _, ge in self.groupings):
                fuse_ok = False
            src_metrics = metrics.child(0)
            if fuse_ok and isinstance(child_op, FilterExec) \
                    and supports_fused_filter(
                    child_op, child_op.children[0].schema):
                source = child_op.children[0]
                fused_preds = child_op.predicates
                src_metrics = src_metrics.child(0)
            # a whole-stage-fused chain directly below the (possibly
            # peeled) filter folds UPWARD into the agg kernel: the scan's
            # project/filter/rename steps trace into the same jitted
            # computation as the partial agg, so scan→project→filter→
            # partial-agg is ONE device call per batch with no
            # materialized intermediate
            from blaze_tpu.ops.fused import FusedStageExec, _FusedSegment
            from blaze_tpu.utils.device import is_device_dtype as _isdev2

            fused_steps = None
            fused_in_schema = None
            if fuse_ok and isinstance(source, FusedStageExec) and \
                    len(source.pipeline) == 1 and \
                    isinstance(source.pipeline[0], _FusedSegment):
                seg = source.pipeline[0]
                if all(st[0] in ("project", "filter", "rename")
                       for st in seg.steps) and \
                        all(_isdev2(f.dtype)
                            for f in seg.in_schema.fields):
                    fused_steps = seg.steps
                    fused_in_schema = seg.in_schema
                    # record the stage's own metrics from this side —
                    # its _execute never runs once absorbed
                    metrics.add("fused_stages", 1)
                    metrics.add("fused_ops", len(source.node.ops))
                    source = source.children[0]
                    src_metrics = src_metrics.child(0)
            # unique-single-key inner BroadcastJoins directly under the
            # (possibly peeled) filter fuse too — CHAINED: a star query's
            # stacked dim joins all trace into the one agg kernel, probing
            # dim tables inline without materializing any joined rows.
            # (not combined with an absorbed step chain: joins below the
            # chain would probe pre-projection rows)
            fused_joins = []
            join_src = None
            while fuse_ok and fused_steps is None:
                spec, loaded_bmap = self._try_fuse_join(
                    source, partition, ctx, src_metrics)
                if spec is None:
                    if loaded_bmap is not None:
                        # statically eligible but runtime-declined: drive
                        # the unfused probe with the ALREADY-LOADED map
                        # rather than letting the join build it again
                        join_src = source._probe_with_map(
                            loaded_bmap, partition, ctx, src_metrics)
                    break
                fused_joins.append(spec)
                probe_idx = source._probe_child()
                source = source.children[probe_idx]
                src_metrics = src_metrics.child(probe_idx)
            if fused_joins:
                metrics.add("fused_join_stages", len(fused_joins))
            agger = DevicePartialAgger(
                self, child_schema, fused_predicates=fused_preds,
                conf=ctx.conf,
                # peeled outer-first; the kernel chains inner-first
                fused_join=list(reversed(fused_joins)),
                fused_steps=fused_steps,
                fused_input_schema=fused_in_schema,
                metrics=metrics)
            if join_src is not None:
                src_iter = join_src
            elif first is not None:
                src_iter = iter(first)  # var-width keys fuse nothing below
            else:
                src_iter = (source.execute(partition, ctx, src_metrics)
                            if source is not child_op else
                            self.execute_child(0, partition, ctx, metrics))
            count_coded = first is not None
            # Per-task consolidation: per-batch partials merge into ONE
            # state batch at stream end (reference parity: AggTable
            # accumulates across the whole partition, agg_table.rs:77-305).
            # This shrinks the exchange payload by the batch count and, on
            # an accelerator, replaces per-batch host pulls in the shuffle
            # writer with a single pull per task. Streaming-safe: staging
            # stops (and batches flow through) once it exceeds the merge
            # budget or cardinality stays near-unique (partial-skipping
            # philosophy — merging near-unique partials is wasted work).
            # adaptive partial skipping on the device path: once the
            # skipper says partials are not reducing, remaining batches
            # route through the passthrough kernel (singleton groups, no
            # dedup/sort). No device kernel feeds it a cardinality signal
            # today (ROADMAP S12c), so it never fires here. passthrough has
            # no trace support, so fused preds/joins/steps keep the skipper
            # off — the work they saved already paid.
            skipper = _PartialSkipper(self, ctx) if (
                self.supports_partial_skipping
                and self.is_partial_output
                and ctx.conf.partial_agg_skipping_enable
                and not agger._needs_trace()
            ) else None
            staged: List[ColumnarBatch] = []
            staged_bytes = 0
            staged_rows = 0
            input_rows = 0
            gave_up = False
            skipping = False
            for batch in src_iter:
                input_rows += batch.num_rows
                if skipping:
                    out = agger.passthrough(batch)
                    metrics.add("partial_skipped_batches", 1)
                    if out is not None and out.num_rows:
                        yield out
                    continue
                # self-time lands in elapsed_compute_time_ns via Operator.execute
                if count_coded:
                    if not coded_keys(self, batch):
                        # a later batch without its dictionary (none is
                        # built upstream today): encode it on the host
                        batch = _encode_var_width_keys(self, batch, metrics)
                    metrics.add("coded_key_batches", 1)
                out = agger.process(batch)
                if skipper is not None and skipper.should_skip():
                    skipping = True
                if out is None or not out.num_rows:
                    continue
                if gave_up or skipping:
                    yield out
                    continue
                staged.append(out)
                staged_bytes += out.nbytes()
                staged_rows += out.num_rows
                if staged_bytes > ctx.conf.device_merge_max_bytes:
                    gave_up = True
                    for o in staged:
                        yield o
                    staged = []
            if len(staged) > 1 and staged_rows <= ctx.conf.batch_size and \
                    input_rows and staged_rows < 0.9 * input_rows:
                merge_op = self._consolidation_op()
                from blaze_tpu.ops.agg_device import (DeviceMergeAgger,
                                                      supports_device_merge)

                if supports_device_merge(merge_op, self.schema):
                    staged = DeviceMergeAgger(
                        merge_op, self.schema, conf=ctx.conf,
                        metrics=metrics).run(staged)
                    metrics.add("partials_consolidated", 1)
            for o in staged:
                if o.num_rows:
                    yield o
            return
        if self.exec_mode == E.AggExecMode.HASH_AGG and self.input_is_partial:
            from blaze_tpu.ops.agg_device import (DeviceMergeAgger,
                                                  supports_device_merge)

            if supports_device_merge(self, child_schema):
                # device merge: all state batches concat on device, one
                # kernel call merges + finalizes — no host key interning
                # (round-1 verdict weak #4). Falls back to the host table
                # when the buffered states outgrow the fallback threshold,
                # or where a var-width key arrives without its dictionary.
                staged = []
                staged_bytes = 0
                src = self.execute_child(0, partition, ctx, metrics)
                too_big = False
                for b in src:
                    staged.append(b)
                    staged_bytes += b.nbytes()
                    if staged_bytes > ctx.conf.device_merge_max_bytes or \
                            not coded_keys(self, b):
                        too_big = True
                        break
                if not too_big:
                    agger = DeviceMergeAgger(self, child_schema,
                                             conf=ctx.conf, metrics=metrics)
                    outs = agger.run(staged)
                    metrics.add("device_merge_batches", len(staged))
                    for out in outs:
                        if out.num_rows:
                            yield out
                    return
                import itertools as _it

                yield from self._execute_table(
                    partition, ctx, metrics, child_schema,
                    _it.chain(staged, src))
                return
        if self.exec_mode == E.AggExecMode.SORT_AGG and self.groupings:
            # input sorted by grouping keys (converter-guaranteed, as for the
            # reference's SortAgg): stream with bounded memory — per-batch
            # mini partials, re-aggregated chunk-wise with chunks cut at key
            # boundaries so no group spans two chunks
            yield from _execute_sorted_impl(self, partition, ctx, metrics)
            return
        yield from self._execute_table(partition, ctx, metrics, child_schema)

    def _execute_table(self, partition, ctx, metrics, child_schema,
                       child_iter=None):
        table = AggTable(self, child_schema, ctx, metrics)
        ctx.mem.register(table)
        try:
            skipper = _PartialSkipper(self, ctx) if (
                self.supports_partial_skipping
                and self.is_partial_output
                and not self.input_is_partial
                and ctx.conf.partial_agg_skipping_enable
            ) else None
            if child_iter is None:
                child_iter = self.execute_child(0, partition, ctx, metrics)
            for batch in child_iter:
                table.process_batch(batch)
                if skipper is not None and skipper.should_skip(table):
                    # adaptive passthrough: flush table, then stream the rest
                    # of the input as single-row groups (reference:
                    # partial-skipping in agg_table.rs)
                    yield from table.output()
                    for rest in child_iter:
                        out = table.passthrough_batch(rest)
                        metrics.add("partial_skipped_batches", 1)
                        if out is not None:
                            yield out
                    return
            yield from table.output()
        finally:
            ctx.mem.unregister(table)
            table.release()


class _Peeked:
    """A batch stream with its first non-empty batch looked at (``head``;
    None for a stream without one); iterating gives the whole stream."""

    def __init__(self, batches):
        self._batches = iter(batches)
        self.head = next((b for b in self._batches if b.num_rows), None)

    def __iter__(self):
        if self.head is not None:
            yield self.head
        yield from self._batches


def _encode_var_width_keys(op: "AggExec", batch: ColumnarBatch, metrics):
    """``batch`` with every var-width grouping key that is not coded made
    so: dictionary-encoded on the host, the codes sent up (counted as
    ``host_key_batches``)."""
    from blaze_tpu.core.batch import CodedColumn, decode_dictionary
    from blaze_tpu.exprs.compiler import reference_index

    cols = list(batch.columns)
    for _, e in op.groupings:
        idx = reference_index(e, batch.schema)
        c = cols[idx] if idx is not None else None
        if isinstance(c, HostColumn) and T.is_var_width(c.dtype):
            arr = decode_dictionary(c.array, c.dtype).dictionary_encode()
            cols[idx] = CodedColumn.from_arrow(arr, c.dtype, batch.capacity)
    metrics.add("host_key_batches", 1)
    return ColumnarBatch(batch.schema, cols, batch.num_rows)


def _execute_sorted_impl(op: "AggExec", partition, ctx, metrics):
    child_schema = op.children[0].schema

    def partial_batches():
        for batch in op.execute_child(0, partition, ctx, metrics):
            if batch.num_rows == 0:
                continue
            t = AggTable(op, child_schema, ctx, metrics)
            t.spillable = False
            t.process_batch(batch)
            yield from t._emit(partial=True, sort_by_key=False, include_key=True)

    yield from _sorted_chunker(op, child_schema, ctx, metrics, partial_batches())


def _sorted_chunker(op: "AggExec", child_schema, ctx, metrics, partial_batches):
    """Re-aggregate a key-sorted stream of partial batches (each carrying the
    #aggkey column) chunk-wise; chunks only cut at key boundaries."""
    bs = ctx.conf.batch_size
    chunk_parts = []
    chunk_rows = 0
    partial_out = op.is_partial_output
    driver_table = AggTable(op, child_schema, ctx, metrics)
    driver_table.spillable = False

    def flush():
        nonlocal chunk_parts, chunk_rows
        if not chunk_parts:
            return
        merged = ColumnarBatch.concat(chunk_parts, chunk_parts[0].schema)
        chunk_parts, chunk_rows = [], 0
        base, _ = _split_key_col(merged)
        sub = driver_table._make_merge_table()
        sub.process_batch(base)
        yield from sub._emit(partial=partial_out)

    last_key = None
    for pb in partial_batches:
        _, keys = _split_key_col(pb, keys_only=True)
        base = pb
        # cut before the first row of a new key once the chunk is full
        start = 0
        for i, k in enumerate(keys):
            if last_key is not None and k != last_key and chunk_rows + (i - start) >= bs:
                if i > start:
                    chunk_parts.append(base.slice(start, i - start))
                    chunk_rows += i - start
                yield from flush()
                start = i
            last_key = k
        if len(keys) > start:
            chunk_parts.append(base.slice(start, len(keys) - start))
            chunk_rows += len(keys) - start
    yield from flush()


def _partial_arg_schema(a: E.AggExpr, child_schema: T.Schema, pos: int):
    """Merge-mode fns still need the *argument* type (e.g. avg's sum scale).
    The raw-input arg expressions are meaningless against the partial child
    schema, so synthesize a one-column schema from the value-typed first
    state field and rewrite the agg to reference it."""
    from blaze_tpu.ir.aggstate import _arg_type_from_state, parse_state_mode

    # single source of truth for state->arg reconstruction (incl. the
    # wide-decimal limb tags): ir/aggstate. The limb-layout decision is the
    # partial producer's — read it off the wire field name, never re-derive
    arg = _arg_type_from_state(a, child_schema, pos)
    m = parse_state_mode(child_schema[pos].name)
    limbs = m[0] if m is not None else False
    schema = T.Schema((T.StructField("arg", arg),))
    if a.args:
        a = E.AggExpr(a.fn, [E.Column("arg")], a.return_type, a.udaf)
    return schema, a, limbs


class _PartialSkipper:
    """Adaptive partial-skipping decision (reference: agg_table.rs): skip
    once the host table has seen ``partial_agg_skipping_min_rows`` rows and
    its whole-table ratio ``num_slots / rows_processed`` passes
    ``partial_agg_skipping_ratio``. Without a table (the device path) there
    is no signal and it never skips."""

    def __init__(self, op: AggExec, ctx: ExecContext):
        self.min_rows = ctx.conf.partial_agg_skipping_min_rows
        self.ratio = ctx.conf.partial_agg_skipping_ratio

    def should_skip(self, table: Optional["AggTable"] = None) -> bool:
        if table is None or table.rows_processed < self.min_rows:
            return False
        return table.num_slots / max(table.rows_processed, 1) > self.ratio


def _adopt_moments(fns, metrics) -> bool:
    """Hands the STDDEV_SAMP functions among ``fns`` the node their finish
    counts on; whether there is one."""
    moments = [fn for fn in fns if isinstance(fn, aggfns.MomentAgg)]
    for fn in moments:
        fn.metrics = metrics
    return bool(moments)


class AggTable(MemConsumer):
    def __init__(self, op: AggExec, child_schema: T.Schema, ctx: ExecContext, metrics):
        super().__init__("AggTable", spillable=True)
        self.op = op
        self.ctx = ctx
        self.metrics = metrics
        self.child_schema = child_schema
        self.fns = op._make_fns(child_schema)
        ng = len(op.groupings)
        self.grouping_names = [n for n, _ in op.groupings]
        if op.input_is_partial:
            self.group_ev = None
            self.agg_evs = None
        else:
            self.group_ev = ExprEvaluator([e for _, e in op.groupings], child_schema)
            self.agg_evs = [
                ExprEvaluator(list(a.agg.args), child_schema) if a.agg.args else None
                for a in op.aggs
            ]
        # state-column positions in partial input
        self.state_pos = []
        pos = ng
        for fn in self.fns:
            k = len(fn.state_fields())
            self.state_pos.append((pos, pos + k))
            pos += k
        self._reset()
        self.spills: List[SpillFile] = []
        self.rows_processed = 0
        self.row_order = 0
        self._moments = _adopt_moments(self.fns, metrics)

    def _reset(self):
        self.key_map = {}
        self.slot_keys: List[bytes] = []
        self.key_values: List[list] = [[] for _ in self.op.groupings]
        self.capacity = 1024
        self.states = [fn.init_state(self.capacity) for fn in self.fns]
        self.num_slots = 0
        # var-width key interning (SURVEY §7.4.3): python values get stable
        # int64 ids, and each distinct pyarrow DICTIONARY caches its
        # code->id translation — so string-keyed batches intern as one
        # vectorized gather instead of a per-row python loop
        self._value_ids: dict = {}
        self._value_list: list = []
        self._value_bytes = 0
        self._dict_gid_cache: dict = {}

    # -- key building ---------------------------------------------------------

    def _grouping_columns(self, batch: ColumnarBatch) -> List[Column]:
        if self.op.input_is_partial:
            return [batch.columns[i] for i in range(len(self.op.groupings))]
        return self.group_ev.evaluate(batch)

    def _intern_keys(self, batch: ColumnarBatch, cols: List[Column]) -> np.ndarray:
        """Map each live row to a global slot id; returns (num_rows,) int64.

        Every column contributes an (int64 plane, validity plane) pair to a
        packed key matrix deduped with one ``np.unique`` pass: device
        columns via their pulled planes, var-width host columns via
        DICTIONARY CODES translated to table-stable value ids (each
        distinct dictionary translates once, then rows are a vectorized
        gather). Only columns pyarrow cannot dictionary-encode fall back
        to the per-row python loop."""
        n = batch.num_rows
        if not cols:  # global aggregate: one slot
            if self.num_slots == 0:
                self.num_slots = 1
                self._ensure_capacity(1)
            return np.zeros(n, dtype=np.int64)
        from blaze_tpu.utils.device import pull_columns

        pulled = pull_columns(cols, n)
        planes = []      # per col: (d64, valid, values_of(uniq_d64, uniq_valid))
        for c, p in zip(cols, pulled):
            if p is not None:
                data, valid = p
                if data.dtype == np.float64:
                    d64 = np.where(valid, data, 0.0).view(np.int64)
                elif data.dtype == np.float32:
                    d64 = np.where(valid, data,
                                   np.float32(0)).view(np.int32).astype(np.int64)
                else:
                    d64 = np.where(valid, data, 0).astype(np.int64)

                def vals_fixed(u64, _uv, _dt=c.dtype):
                    return _int64_to_py(u64, _dt)

                planes.append((d64, valid, vals_fixed, False))
                continue
            if isinstance(c, HostColumn):
                trip = self._host_key_plane(c, n)
                if trip is not None:
                    planes.append(trip)
                    continue
            # generic agg output carried host-side, or un-encodable types
            return self._intern_keys_pyloop(cols, n)
        if len(planes) == 1 and planes[0][3]:
            # single var-width key: its ids are NONNEGATIVE, so nulls fold
            # to -1 and one plain int64 np.unique replaces the packed-void
            # record dedup (~4x faster on 262k-row batches)
            d64, valid, values_of, _ = planes[0]
            keyed = np.where(valid, d64, np.int64(-1))
            uniq, inverse = np.unique(keyed, return_inverse=True)
            lut = np.empty(len(uniq), dtype=np.int64)
            # key bytes MUST be a pure function of the VALUE (the pyloop's
            # pickled tuple): spill-run merging and sorted-streaming cut
            # chunks on byte equality across table epochs, and gids are
            # only stable within one epoch
            vld = uniq >= 0
            vals = values_of(uniq, vld)
            for i in range(len(uniq)):
                key = (vals[i] if vld[i] else None,)
                kb = pickle.dumps(key, protocol=4)
                slot = self.key_map.get(kb)
                if slot is None:
                    slot = self._new_slot(kb)
                    self.key_values[0].append(key[0])
                lut[i] = slot
            return lut[inverse]
        any_dict = any(nn for _d, _v, _vo, nn in planes)
        mats = []
        for d64, valid, _v, _nn in planes:
            mats.append(d64)
            mats.append(np.asarray(valid).astype(np.int64))
        mat = np.column_stack(mats)
        view = np.ascontiguousarray(mat).view(
            np.dtype((np.void, mat.dtype.itemsize * mat.shape[1]))
        ).ravel()
        uniq, inverse = np.unique(view, return_inverse=True)
        lut = np.empty(len(uniq), dtype=np.int64)
        if any_dict:
            # mixed device/var-width keys: gid planes are per-epoch, so the
            # slot key bytes come from the pickled VALUE tuples (the
            # pyloop's stable encoding) — computed per batch-unique key
            uniq_rows = uniq.view(mat.dtype).reshape(len(uniq), mat.shape[1])
            col_vals = []
            col_vld = []
            for ci, (_d, _v, values_of, _nn) in enumerate(planes):
                vld = uniq_rows[:, 2 * ci + 1].astype(bool)
                col_vld.append(vld)
                col_vals.append(values_of(uniq_rows[:, 2 * ci], vld))
            for i in range(len(uniq)):
                key = tuple(col_vals[ci][i] if col_vld[ci][i] else None
                            for ci in range(len(planes)))
                kb = pickle.dumps(key, protocol=4)
                slot = self.key_map.get(kb)
                if slot is None:
                    slot = self._new_slot(kb)
                    for ci in range(len(planes)):
                        self.key_values[ci].append(key[ci])
                lut[i] = slot
            return lut[inverse]
        rep = {}
        for i, u in enumerate(uniq):
            kb = u.tobytes()
            slot = self.key_map.get(kb)
            if slot is None:
                slot = self._new_slot(kb)
                rep[i] = slot
            lut[i] = slot
        if rep:
            uniq_rows = uniq.view(mat.dtype).reshape(len(uniq), mat.shape[1])
            for ci, (_d, _v, values_of, _nn) in enumerate(planes):
                d64 = uniq_rows[:, 2 * ci]
                vld = uniq_rows[:, 2 * ci + 1].astype(bool)
                vals = values_of(d64, vld)
                for i, slot in rep.items():
                    self.key_values[ci].append(vals[i] if vld[i] else None)
        return lut[inverse]

    def _host_key_plane(self, col: HostColumn, n: int):
        """(int64 ids, validity, values_of) for a var-width host column via
        dictionary codes, or None when the type cannot dictionary-encode."""
        import pyarrow as pa

        arr = col.array
        if isinstance(arr, pa.ChunkedArray):
            arr = arr.combine_chunks()
        was_dict = pa.types.is_dictionary(arr.type)
        try:
            if not was_dict:
                if self.op.input_is_partial and (
                        pa.types.is_string(arr.type)
                        or pa.types.is_large_string(arr.type)
                        or pa.types.is_binary(arr.type)
                        or pa.types.is_large_binary(arr.type)):
                    # tripwire: decoded VAR-WIDTH keys crossing the exchange
                    # mean the code-carrying shuffle got bypassed somewhere
                    # upstream (fixed-width keys routed through this plane
                    # are fine — they carry no dictionary to lose)
                    self.metrics.add("agg_reintern_rows", n)
                    _reintern_counter().inc(n)
                arr = arr.dictionary_encode()
            # cache only REUSED dictionaries (pre-encoded file/IPC dicts);
            # self-encoded ones are seen exactly once and caching them
            # would retain a dictionary per batch for the table lifetime
            gids = self._gid_of_values(arr.dictionary, cache=was_dict)
        except (pa.ArrowNotImplementedError, pa.ArrowInvalid, TypeError):
            return None
        codes = arr.indices
        valid = ~np.asarray(codes.is_null()) if codes.null_count \
            else np.ones(n, bool)
        cnp = codes.fill_null(0).to_numpy(zero_copy_only=False).astype(np.int64)
        g = gids[cnp] if len(gids) else np.zeros(n, np.int64)
        # a null stored in the dictionary VALUES (gid -1) is the same NULL
        # group as a null index
        valid = valid & (g >= 0)
        d64 = np.where(valid, g, 0)
        store = self._value_list

        def values_of(u64, _uv):
            return [store[g] if g >= 0 else None for g in u64.tolist()]

        return d64, valid, values_of, True

    def _gid_of_values(self, dictionary, cache: bool = True) -> np.ndarray:
        """Table-stable int64 id per dictionary VALUE (None -> -1); reused
        dictionaries translate once (cached by backing-buffer identity:
        deserialized shuffle frames and file readers hand out fresh python
        wrappers around ONE shared dictionary, so an ``id()`` key would
        miss every batch). Repeated batches over one shuffle-stream or
        file dictionary cost a single gather — the code-carrying
        exchange's "translate once per (map, dict) pair"."""
        dkey = None
        if cache:
            from blaze_tpu.io.batch_serde import dict_identity

            dkey = dict_identity(dictionary)
            ent = self._dict_gid_cache.get(dkey)
            if ent is not None:
                return ent[1]
        vals = dictionary.to_pylist()
        gids = np.empty(len(vals), np.int64)
        vmap = self._value_ids
        store = self._value_list
        self._value_bytes = getattr(self, "_value_bytes", 0)
        for i, v in enumerate(vals):
            if v is None:
                gids[i] = -1
                continue
            g = vmap.get(v)
            if g is None:
                g = len(store)
                vmap[v] = g
                store.append(v)
                self._value_bytes += len(v) if isinstance(
                    v, (str, bytes)) else 16
            gids[i] = g
        if cache:
            # holding the dictionary pins its buffer addresses for the key
            self._dict_gid_cache[dkey] = (dictionary, gids)
        return gids

    def _intern_keys_pyloop(self, cols: List[Column], n: int) -> np.ndarray:
        # last-resort host path: python tuples per row
        pylists = [c.to_arrow(n).to_pylist() for c in cols]
        slots = np.empty(n, dtype=np.int64)
        key_map = self.key_map
        for i in range(n):
            key = tuple(pl[i] for pl in pylists)
            kb = pickle.dumps(key, protocol=4)
            slot = key_map.get(kb)
            if slot is None:
                slot = self._new_slot(kb)
                for ci in range(len(cols)):
                    self.key_values[ci].append(key[ci])
            slots[i] = slot
        return slots

    def _new_slot(self, kb: bytes) -> int:
        slot = self.num_slots
        self.key_map[kb] = slot
        self.slot_keys.append(kb)
        self.num_slots += 1
        self._ensure_capacity(self.num_slots)
        return slot

    def _ensure_capacity(self, n: int):
        if n <= self.capacity:
            return
        while self.capacity < n:
            self.capacity *= 2
        self.states = [
            fn.grow(st, self.capacity) for fn, st in zip(self.fns, self.states)
        ]

    # -- accumulation ---------------------------------------------------------

    def process_batch(self, batch: ColumnarBatch):
        n = batch.num_rows
        if n == 0:
            return
        # the host table groups a coded key through its dictionary as it
        # groups any dictionary-encoded host column (_host_key_plane)
        batch = batch.coded_to_host(self.metrics)
        if self._moments:
            self.metrics.add("moment_host_batches", 1)
            DEVICE_STATS.add_moment(host_batches=1)
        self.rows_processed += n
        cols = self._grouping_columns(batch)
        slots_np = self._intern_keys(batch, cols)
        cap = batch.capacity
        slots_dev = jnp.asarray(_pad_to(slots_np, cap, fill=self.capacity))
        mask = batch.row_exists_mask()
        if self.op.input_is_partial:
            self._merge_states(batch, slots_dev, slots_np, mask)
        else:
            self._update_states(batch, slots_dev, slots_np, mask, n)
        self.row_order += n
        self._account()

    def _update_states(self, batch, slots_dev, slots_np, mask, n):
        from blaze_tpu.exprs.compiler import HostVal, _broadcast, _is_device_type

        ones_np = np.ones(n, dtype=bool)
        for i, (a, fn) in enumerate(zip(self.op.aggs, self.fns)):
            ev = self.agg_evs[i]
            if ev is None:  # count(*)
                self.states[i] = fn.update(self.states[i], slots_dev, None, None, mask)
                continue
            val = ev._eval(a.agg.args[0], batch)
            if fn.host:
                hv = ev._to_host(val, batch)
                order = np.arange(self.row_order, self.row_order + n)
                self.states[i] = fn.update(self.states[i], slots_np, hv.arr,
                                           None, ones_np, order)
            elif isinstance(val, HostVal) and not _is_device_type(val.dtype):
                # device-accumulating fn over a host-resident arg (e.g.
                # count(string_col)) — counts on the host validity mask
                self.states[i] = fn.update(self.states[i], slots_np, val.arr,
                                           None, ones_np)
            else:
                dv = ev._to_dev(val, batch)
                data, validity = _broadcast(dv, batch)
                order = None
                if isinstance(fn, aggfns.FirstAgg):
                    order = jnp.arange(batch.capacity, dtype=jnp.int64) + self.row_order
                self.states[i] = fn.update(self.states[i], slots_dev, data,
                                           validity, mask, order)

    def _merge_states(self, batch, slots_dev, slots_np, mask):
        n = batch.num_rows
        ones_np = np.ones(n, dtype=bool)
        for i, fn in enumerate(self.fns):
            lo, hi = self.state_pos[i]
            pcols = batch.columns[lo:hi]
            if fn.host or any(isinstance(c, HostColumn) for c in pcols):
                self.states[i] = fn.merge(self.states[i], slots_np, pcols, ones_np, n)
            else:
                dcols = [self._as_dev(c, batch) for c in pcols]
                self.states[i] = fn.merge(self.states[i], slots_dev, dcols, mask, n)

    @staticmethod
    def _as_dev(col: Column, batch: ColumnarBatch) -> DeviceColumn:
        if isinstance(col, DeviceColumn):
            return col
        from blaze_tpu.core.batch import _arrow_to_column

        out = _arrow_to_column(col.array, col.dtype, batch.capacity)
        assert isinstance(out, DeviceColumn)
        return out

    def _account(self):
        mem = sum(fn.mem_used(st) for fn, st in zip(self.fns, self.states))
        mem += self.num_slots * 64 + sum(len(k) for k in self.slot_keys)
        # var-width key VALUES live in the gid store, not slot_keys
        mem += getattr(self, "_value_bytes", 0) * 2  # store + id map
        self.update_mem_used(mem)

    # -- passthrough (partial skipping) ---------------------------------------

    def passthrough_batch(self, batch: ColumnarBatch) -> Optional[ColumnarBatch]:
        """Emit each input row as its own group with a singleton state."""
        n = batch.num_rows
        if n == 0:
            return None
        sub = AggTable(self.op, self.child_schema, self.ctx, self.metrics)
        sub.spillable = False
        sub.process_batch(batch)
        parts = list(sub.output())
        return ColumnarBatch.concat(parts, self.op.schema) if parts else None

    # -- spill ----------------------------------------------------------------

    def spill(self) -> int:
        if self.num_slots == 0:
            return 0
        freed = self.mem_used
        spill = SpillFile("agg")
        with self.metrics.timer("spill_io_time_ns"):
            for b in self._partial_batches(sort_by_key=True, include_key=True):
                spill.writer.write_batch(b)
            spill.finish_write()
        self.metrics.add("spilled_bytes", spill.size)
        self.metrics.add("spill_count", 1)
        self.spills.append(spill)
        self._reset()
        return freed

    # -- output ---------------------------------------------------------------

    def _key_columns(self, order: Optional[np.ndarray],
                     dict_encode: bool = False) -> List[Column]:
        cols = []
        schema = self.op.schema
        for ci in range(len(self.op.groupings)):
            vals = self.key_values[ci]
            if order is not None:
                vals = [vals[i] for i in order]
            dt = schema[ci].dtype
            at = T.to_arrow_type(dt)
            arr = pa.array(vals, type=at)
            if dict_encode and (pa.types.is_string(at) or
                                pa.types.is_large_string(at) or
                                pa.types.is_binary(at) or
                                pa.types.is_large_binary(at)):
                # code-carrying shuffle: shuffle-bound partial output keeps
                # var-width keys dictionary-encoded. All batches sliced off
                # this emission share ONE dictionary object, so the writer
                # serializes it once per stream and the FINAL table
                # translates it once (_gid_of_values identity cache) —
                # per-batch re-interning of decoded values disappears
                arr = arr.dictionary_encode()
            cols.append(HostColumn(dt, arr))
        return cols

    def _partial_batches(self, sort_by_key: bool, include_key: bool
                         ) -> Iterator[ColumnarBatch]:
        yield from self._emit(partial=True, sort_by_key=sort_by_key,
                              include_key=include_key)

    def _emit(self, partial: bool, sort_by_key: bool = False,
              include_key: bool = False) -> Iterator[ColumnarBatch]:
        ns = self.num_slots
        if ns == 0:
            if not self.op.groupings and not partial:
                yield self._global_empty_row()
            return
        order = None
        if sort_by_key:
            order = np.argsort(np.array(self.slot_keys, dtype=object), kind="stable")
            order = np.asarray(order, dtype=np.int64)
        key_cols = self._key_columns(
            order,
            dict_encode=(partial and not include_key
                         and self.ctx.conf.codes_shuffle))
        agg_cols: List[Column] = []
        for a, fn, st in zip(self.op.aggs, self.fns, self.states):
            if partial:
                agg_cols.extend(fn.state_columns(st, ns, self.capacity))
            else:
                agg_cols.append(fn.final_column(st, ns, self.capacity))
        if order is not None:
            # host agg columns are in slot order; apply the key sort to them
            # here (device columns are reordered inside _assemble)
            agg_cols = [
                HostColumn(c.dtype, c.array.take(pa.array(order, type=pa.int64())))
                if isinstance(c, HostColumn) else c
                for c in agg_cols
            ]
        # device agg cols are padded to table capacity; cut to ns and reorder
        final_cols: List[Column] = []
        for c in key_cols:
            final_cols.append(c)
        for c in agg_cols:
            if isinstance(c, DeviceColumn):
                c = DeviceColumn(c.dtype, c.data[: max(self.capacity, ns)],
                                 c.validity[: max(self.capacity, ns)])
            final_cols.append(c)
        # partial emission carries state columns regardless of the op's own
        # output mode (spill / sorted-streaming paths emit partials even for
        # COMPLETE/FINAL ops)
        if partial:
            base_schema = T.Schema(
                tuple(
                    T.StructField(n, self.op.schema[i].dtype)
                    for i, (n, _) in enumerate(self.op.groupings)
                ) + tuple(_partial_schema_fields(self.op, self.fns))
            )
        else:
            base_schema = self.op.schema
        schema = base_schema if not include_key else T.Schema(
            base_schema.fields + (T.StructField(_KEY_COL, T.BINARY, False),)
        )
        if include_key:
            keys = self.slot_keys if order is None else [self.slot_keys[i] for i in order]
            final_cols.append(HostColumn(T.BINARY, pa.array(keys, type=pa.large_binary())))
        # assemble: device columns need row reorder via take; build batch then take
        batch = _assemble(schema, final_cols, ns, order)
        bs = self.ctx.conf.batch_size
        for off in range(0, batch.num_rows, bs):
            yield batch.slice(off, bs)

    def _global_empty_row(self) -> ColumnarBatch:
        """Global aggregate over empty input: one row of initial state."""
        cols = []
        for fn, st in zip(self.fns, self.states):
            col = fn.final_column(st, 1, self.capacity)
            if isinstance(col, DeviceColumn):
                col = DeviceColumn(col.dtype, col.data, col.validity)
            cols.append(col)
        schema = self.op.schema
        fixed = []
        for f, c in zip(schema.fields, cols):
            if isinstance(c, HostColumn) and len(c.array) != 1:
                c = HostColumn(c.dtype, c.array.slice(0, 1))
            fixed.append(c)
        return _assemble(schema, fixed, 1, None)

    def output(self) -> Iterator[ColumnarBatch]:
        partial = self.op.is_partial_output
        if not self.spills:
            yield from self._emit(partial=partial)
            return
        # merge spilled runs with the in-memory table
        self.spill()
        yield from self._merge_spills(partial)

    def _merge_spills(self, partial: bool):
        """K-way merge of key-sorted spilled partial runs, re-aggregating
        chunk-wise; chunks cut at key boundaries so no group spans two
        chunks (memory-bounded, reference: bucketed spill merge)."""
        cursors = []
        for rid, s in enumerate(self.spills):
            cur = _AggCursor(rid, iter(s.read_batches()))
            if cur.advance():
                cursors.append(cur)
        heap = [(c.key(), c.rid, c) for c in cursors]
        heapq.heapify(heap)
        chunk_parts: List[ColumnarBatch] = []
        chunk_rows = 0
        bs = self.ctx.conf.batch_size
        last_key = None

        def flush_cursor(cur):
            nonlocal chunk_rows
            if cur.pending:
                chunk_parts.append(cur.batch.take(np.array(cur.pending, np.int64)))
                chunk_rows += len(cur.pending)
                cur.pending = []

        def process_chunk():
            nonlocal chunk_parts, chunk_rows
            for c in cursors:
                flush_cursor(c)
            if not chunk_parts:
                return
            merged = ColumnarBatch.concat(chunk_parts, chunk_parts[0].schema)
            chunk_parts, chunk_rows = [], 0
            base, _ = _split_key_col(merged)
            sub = self._make_merge_table()
            sub.process_batch(base)
            yield from sub._emit(partial=partial)

        while heap:
            key, _, cur = heapq.heappop(heap)
            if last_key is not None and key != last_key and \
                    chunk_rows + sum(len(c.pending) for c in cursors) >= bs:
                yield from process_chunk()
            last_key = key
            cur.pending.append(cur.pos)
            if cur.step():
                heapq.heappush(heap, (cur.key(), cur.rid, cur))
            else:
                flush_cursor(cur)
                if cur.advance():
                    heapq.heappush(heap, (cur.key(), cur.rid, cur))
        yield from process_chunk()

    def _make_merge_table(self) -> "AggTable":
        """A table that consumes partial batches and re-aggregates them."""
        op = AggExec.__new__(AggExec)
        op.exec_mode = self.op.exec_mode
        op.groupings = self.op.groupings
        import dataclasses as _dc

        op.aggs = [
            _dc.replace(a, mode=E.AggMode.PARTIAL_MERGE) if hasattr(a, "mode") else a
            for a in self.op.aggs
        ]
        op.supports_partial_skipping = False
        op.schema = self.op.schema
        op.children = self.op.children
        # partial child schema = our own partial output schema
        pschema = T.Schema(
            tuple(
                [T.StructField(n, self.op.schema[i].dtype)
                 for i, (n, _) in enumerate(self.op.groupings)]
            ) + tuple(
                f for f in _partial_schema_fields(self.op, self.fns)
            )
        )
        t = AggTable(op, pschema, self.ctx, self.metrics)
        t.spillable = False
        return t

    def release(self):
        for s in self.spills:
            s.release()
        self.spills = []


def _partial_schema_fields(op: AggExec, fns) -> List[T.StructField]:
    fields = []
    for a, fn in zip(op.aggs, fns):
        for suffix, dt in fn.state_fields():
            fields.append(T.StructField(f"{a.name}#{suffix}", dt))
    return fields


class _AggCursor:
    __slots__ = ("rid", "it", "batch", "keys", "pos", "pending")

    def __init__(self, rid, it):
        self.rid = rid
        self.it = it
        self.batch = None
        self.keys = None
        self.pos = 0
        self.pending: List[int] = []

    def advance(self) -> bool:
        for b in self.it:
            if b.num_rows == 0:
                continue
            self.batch = b
            _, self.keys = _split_key_col(b, keys_only=True)
            self.pos = 0
            return True
        return False

    def key(self):
        return self.keys[self.pos]

    def step(self) -> bool:
        self.pos += 1
        return self.pos < self.batch.num_rows


def _split_key_col(batch: ColumnarBatch, keys_only: bool = False):
    idx = [i for i, f in enumerate(batch.schema.fields) if f.name != _KEY_COL]
    kidx = [i for i, f in enumerate(batch.schema.fields) if f.name == _KEY_COL]
    keys = None
    if kidx:
        keys = batch.columns[kidx[0]].array.to_pylist()
        keys = [bytes(k) for k in keys]
    if keys_only:
        return None, keys
    return batch.select(idx), keys


def _assemble(schema: T.Schema, cols: List[Column], num_rows: int,
              order: Optional[np.ndarray]) -> ColumnarBatch:
    """Build a batch from per-slot columns, applying slot reordering to
    device columns (host key columns are already ordered)."""
    from blaze_tpu.config import get_config

    from blaze_tpu.core import kernels

    cap = get_config().capacity_for(num_rows)
    out_cols: List[Column] = list(cols)
    dev = [(i, c) for i, c in enumerate(cols) if isinstance(c, DeviceColumn)]
    if dev:
        idx = order if order is not None else np.arange(num_rows)
        datas, valids = kernels.gather_planes(
            [c.data for _, c in dev], [c.validity for _, c in dev],
            np.asarray(idx, dtype=np.int64), cap, num_rows)
        for k, (i, c) in enumerate(dev):
            out_cols[i] = DeviceColumn(c.dtype, datas[k], valids[k])
    for i, c in enumerate(cols):
        if not isinstance(c, DeviceColumn) and len(c.array) > num_rows:
            out_cols[i] = HostColumn(c.dtype, c.array.slice(0, num_rows))
    return ColumnarBatch(schema, out_cols, num_rows)


def _int64_to_py(d64: np.ndarray, dtype: T.DataType) -> list:
    if isinstance(dtype, T.Float64Type):
        return d64.view(np.float64).tolist()
    if isinstance(dtype, T.Float32Type):
        return d64.astype(np.int32).view(np.float32).tolist()
    if isinstance(dtype, T.BooleanType):
        return d64.astype(bool).tolist()
    if isinstance(dtype, T.DecimalType):
        import decimal

        return [decimal.Decimal(int(v)).scaleb(-dtype.scale) for v in d64]
    if isinstance(dtype, T.DateType):
        import datetime

        epoch = datetime.date(1970, 1, 1)
        return [epoch + datetime.timedelta(days=int(v)) for v in d64]
    if isinstance(dtype, T.TimestampType):
        import datetime

        epoch = datetime.datetime(1970, 1, 1)
        return [epoch + datetime.timedelta(microseconds=int(v)) for v in d64]
    return d64.tolist()


def _pad_to(arr: np.ndarray, capacity: int, fill) -> np.ndarray:
    out = np.full(capacity, fill, dtype=arr.dtype if arr.dtype != object else np.int64)
    out[: len(arr)] = arr
    return out
