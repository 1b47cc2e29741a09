"""Device-resident partial aggregation: the TPU fast path.

The general AggTable (ops/agg.py) interns group keys on host — exact for any
type, but it pulls every input batch's key columns across the device
boundary. On this backend transfers cost ~25-90ms each, so for the hot
TPC-DS shape (grouped sum/count/avg/min/max over fixed-width keys) this
module keeps the whole partial stage on device (SURVEY.md §7.2 L2'). Keys
whose observed range fits a slot table reduce straight into it
(``_dense_partial_kernel``); any other keys go the sort path
(``_aggregate_sorted``, the body of ``jit(agg_partial)`` and
``jit(agg_merge)``):

    order the rows by their keys (two-operand sorts) -> one gather of every
    plane into that order -> contiguous segments reduced by prefix scans ->
    one gather of each segment's last row -> a partial batch whose key and
    state columns are still device arrays, the groups in key order.

One jitted call per batch; the only host sync is the group-count scalar.
Per-batch partials are NOT consolidated across batches — they merge at the
final stage (or in the exchange reducer), trading a slightly larger
exchange payload for zero full-width transfers."""

from __future__ import annotations

import functools
import math
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from blaze_tpu.core import kernels as K
from blaze_tpu.core.batch import ColumnarBatch, DeviceColumn
from blaze_tpu.exprs.compiler import ExprEvaluator, _broadcast
from blaze_tpu.ir import exprs as E
from blaze_tpu.ir import types as T
from blaze_tpu.ops.sort_keys import orderable_word_traced
from blaze_tpu.utils.device import (DEVICE_STATS, is_device_dtype,
                                    wait_array, wait_int)

_DEVICE_AGG_FNS = (E.AggFunction.SUM, E.AggFunction.COUNT, E.AggFunction.AVG,
                   E.AggFunction.MIN, E.AggFunction.MAX,
                   E.AggFunction.STDDEV_SAMP)

# jitted fused (filter+partial-agg) kernels, shared across agger instances
_FUSED_KERNELS = {}

# Sentinel returned by _plan_dense when the probe saw no valid keys and
# there is no previous plan to anchor to: "no plan yet, re-probe later"
# as opposed to None's "range too wide, give up on the dense path".
_DEFER_PLAN = object()

# aggregate kinds whose ARG is a wide decimal carried as three int64 limb
# planes (host decimal128 column -> buffer views -> device)
_WIDE_KINDS = ("sum3", "avg3", "minw", "maxw")


def _column_refs(e: E.Expr, out=None):
    if out is None:
        out = set()
    if isinstance(e, E.Column):
        out.add(e.name)
    for c in e.children():
        _column_refs(c, out)
    return out


def _touches_wide(e: E.Expr, schema: T.Schema) -> bool:
    """Does the expression reference a wide-decimal column of ``schema`` —
    by NAME (E.Column) or by INDEX (E.BoundReference, the proto wire
    form)? Gates the fused/jitted paths: only bare wide agg args may read
    wide columns (as limb planes); any other traced access would crash on
    the _WideLimbCol placeholder."""
    if isinstance(e, E.Column):
        try:
            if _is_wide_dec(schema[schema.index_of(e.name)].dtype):
                return True
        except (KeyError, ValueError):
            pass
    if isinstance(e, E.BoundReference):
        if 0 <= e.index < len(schema) and \
                _is_wide_dec(schema[e.index].dtype):
            return True
    return any(_touches_wide(c, schema) for c in e.children())


def _is_wide_dec(dt: T.DataType) -> bool:
    return (isinstance(dt, T.DecimalType) and not dt.fits_int64
            and dt.precision <= 38)


class _WideLimbCol:
    """Wide-decimal column inside a TRACED batch: three int64 limb planes
    + validity (the jit-flattenable representation of a host decimal128
    column). Only the wide-agg arg path reads it; expressions never touch
    it (fusion eligibility gates that)."""

    __slots__ = ("dtype", "l0", "l1", "l2", "validity")

    def __init__(self, dtype, l0, l1, l2, validity):
        self.dtype = dtype
        self.l0, self.l1, self.l2 = l0, l1, l2
        self.validity = validity


def _host_wide_planes(col, capacity: int):
    """HostColumn(decimal>18) -> (l0, l1, l2, validity) jnp planes padded
    to capacity (buffer views + two masks — no per-value python work)."""
    from blaze_tpu.ops.aggfns import _wide_value_limbs

    v0, v1, v2, valid = _wide_value_limbs(col.array)
    pad = capacity - len(v0)
    if pad:
        z = np.zeros(pad, np.int64)
        v0 = np.concatenate([v0, z])
        v1 = np.concatenate([v1, z])
        v2 = np.concatenate([v2, z])
        valid = np.concatenate([valid, np.zeros(pad, bool)])
    return (jnp.asarray(v0), jnp.asarray(v1), jnp.asarray(v2),
            jnp.asarray(valid))


def _proved_limbs(data):
    """The three limb planes of a wide decimal that rides as ONE int64 plane
    (a window's result whose every value the device proved to fit,
    ops/window_device): the low word's two 32-bit chunks and the sign."""
    low = jnp.int64(0xFFFFFFFF)
    return data & low, (data >> 32) & low, data >> 63


def _flatten_cols(batch: ColumnarBatch):
    """jit-argument planes for a batch: 2 per device column, 4 (limbs +
    validity) per wide-decimal column, whether it is a host column or a
    proved int64 plane. The schema determines the layout, so kernels cache
    correctly on (schema, capacity) keys."""
    flat = []
    for c, f in zip(batch.columns, batch.schema.fields):
        if _is_wide_dec(f.dtype):
            flat += [*_proved_limbs(c.data), c.validity] \
                if isinstance(c, DeviceColumn) \
                else list(_host_wide_planes(c, batch.capacity))
        elif isinstance(c, DeviceColumn):
            flat += [c.data, c.validity]
        else:
            raise TypeError(
                f"column {f.name} ({f.dtype}) is not jit-flattenable")
    return flat


def _rebuild_cols(schema: T.Schema, flat, pos: int = 0):
    """Inverse of _flatten_cols inside a trace: (columns, next_pos)."""
    cols = []
    for f in schema.fields:
        if _is_wide_dec(f.dtype):
            cols.append(_WideLimbCol(f.dtype, flat[pos], flat[pos + 1],
                                     flat[pos + 2], flat[pos + 3]))
            pos += 4
        else:
            cols.append(DeviceColumn(f.dtype, flat[pos], flat[pos + 1]))
            pos += 2
    return cols, pos


class FusedJoinSpec:
    """Unique-single-key inner BroadcastJoin traced INTO the partial-agg
    kernel (the TPC-DS star-join shape: fact scan -> dim lookup -> group-by
    on dim attributes). Instead of materializing the joined batch (compact
    + re-gather of every column), the agg kernel probes the sorted dim keys
    with ``sorted_probe_traced``, gathers ONLY the dim columns the group/agg
    expressions touch, and uses the hit mask as the row-exists mask — one
    dispatch, no intermediate rows (reference analogue: the probe loop of
    ``joins/bhj/full_join.rs`` feeding ``agg/agg_table.rs`` without an
    operator boundary; here the fusion is literal, one XLA program)."""

    def __init__(self, join_op, bmap, key_expr, probe_on_left,
                 probe_schema, build_schema):
        self.join_op = join_op
        self.bmap = bmap
        self.key_expr = key_expr
        self.probe_on_left = probe_on_left
        self.probe_schema = probe_schema
        self.build_schema = build_schema
        self.nk = len(bmap.sorted_keys)
        bb = bmap.batch
        self.cap_b = bb.capacity
        self.n_build_cols = len(bb.columns)
        fields = (tuple(probe_schema.fields) + tuple(build_schema.fields)
                  if probe_on_left else
                  tuple(build_schema.fields) + tuple(probe_schema.fields))
        self.joined_schema = T.Schema(fields)
        if bmap._dev_cell[0] is None:
            bmap._dev_cell[0] = jnp.asarray(
                bmap.sorted_keys if self.nk else np.zeros(1, np.int64))
        from blaze_tpu.runtime.metrics import MetricNode

        # overridden by the agg operator with the join's real metric node
        self.metrics = MetricNode("fused_join")

    def trace_view(self) -> "FusedJoinSpec":
        """Copy with the runtime references (bmap, join op, metrics)
        stripped. Jit closures cached forever in _FUSED_KERNELS must capture
        THIS, not the live spec: tracing only needs the structural fields
        (schemas, key expr, nk/cap_b/n_build_cols) — capturing the live spec
        would pin the whole broadcast dim table's device buffers for
        process lifetime."""
        import copy

        view = copy.copy(self)
        view.join_op = view.bmap = view.metrics = None
        return view

    @staticmethod
    def runtime_eligible(bmap) -> bool:
        return bool(bmap.unique_single_key) and all(
            isinstance(c, DeviceColumn) for c in bmap.batch.columns)

    def batch_eligible(self, batch: ColumnarBatch) -> bool:
        # wide-decimal host columns are fine: they flatten as limb planes
        return all(isinstance(c, DeviceColumn) or _is_wide_dec(f.dtype)
                   for c, f in zip(batch.columns, batch.schema.fields))

    def structural_key(self) -> str:
        from blaze_tpu.ir.serde import expr_to_json
        import json

        return "join|%s|%s|%s" % (
            json.dumps(expr_to_json(self.key_expr)),
            ",".join(str(f.dtype) for f in self.build_schema.fields),
            int(self.probe_on_left))

    def shape_key(self):
        return (self.nk, self.cap_b,
                tuple((f.name, str(f.dtype))
                      for f in self.probe_schema.fields))

    def jit_args(self, batch: ColumnarBatch):
        """Extra leading jit arguments: the device-resident sorted dim keys
        and the build planes (identical arrays every call, so jax reuses
        the committed buffers)."""
        flat = [self.bmap._dev_cell[0]]
        for c in self.bmap.batch.columns:
            flat += [c.data, c.validity]
        return flat

    def n_build_planes(self) -> int:
        return 1 + 2 * self.n_build_cols

    def trace_join(self, num_rows, jflat, probe):
        """Traced: (build jflat = [uniq, build planes...], probe = flat
        plane list OR the PREVIOUS join's virtual batch in a chained
        star-join fusion) -> (joined tracer batch, hit mask). Probe-side
        columns — including wide-decimal limb columns — pass through
        untouched; only the hit mask filters them."""
        uniq = jflat[0]
        if isinstance(probe, ColumnarBatch):
            ptb = probe
        else:
            pcols, _ = _rebuild_cols(self.probe_schema, probe)
            ptb = ColumnarBatch(self.probe_schema, pcols, num_rows)
        kev = ExprEvaluator([self.key_expr], self.probe_schema)
        kev._reset_cse(ptb)
        kd, kv = _broadcast(kev._to_dev(kev._eval(self.key_expr, ptb), ptb),
                            ptb)
        from blaze_tpu.ops.joins.keymap import sorted_probe_traced

        cap_p = ptb.capacity
        iota = jnp.arange(cap_p, dtype=jnp.int64)
        exists = iota < num_rows
        # shared canonical-word + sorted-key membership (keymap is the
        # single authority for the key encoding)
        cidx, hit = sorted_probe_traced(uniq, kd, kv & exists, self.nk)
        bcols = []
        for i, f in enumerate(self.build_schema.fields):
            bd, bv = jflat[1 + 2 * i], jflat[2 + 2 * i]
            bcols.append(DeviceColumn(f.dtype, bd[cidx], bv[cidx] & hit))
        pcols = list(ptb.columns)
        cols = pcols + bcols if self.probe_on_left else bcols + pcols
        return ColumnarBatch(self.joined_schema, cols, num_rows), hit

    def materialize(self, batch: ColumnarBatch, metrics):
        """Non-device fallback for a single probe batch: run the join for
        real and feed the joined batch down the unfused agg path."""
        from blaze_tpu.ir.nodes import JoinType

        cols = ExprEvaluator([self.key_expr],
                             self.probe_schema).evaluate(batch)
        out = self.join_op._inner_fast(batch, self.bmap, cols,
                                       self.probe_on_left, metrics)
        if out is not NotImplemented:
            return out
        codes, on_device = self.bmap.probe_codes(batch, cols)
        if on_device:
            metrics.add("device_probe_batches", 1)
        probe_idx, build_idx, counts = self.bmap.probe(codes)
        return self.join_op._emit_probe_batch(
            batch, self.bmap, probe_idx, build_idx, counts, False,
            self.probe_on_left, JoinType.INNER)


def _device_key(e: E.Expr, schema: T.Schema) -> bool:
    """May this grouping key be a device plane: a fixed-width type, or a bare
    reference to a var-width column — which is one where the column arrives
    CODED (core/batch.CodedColumn: int32 codes and validity on the device).
    Whether it does is the stream's to say (`coded_keys`)."""
    dt = E.infer_type(e, schema)
    return is_device_dtype(dt) or (
        T.is_var_width(dt) and isinstance(e, (E.Column, E.BoundReference)))


def coded_keys(op, batch: ColumnarBatch) -> bool:
    """Does ``batch`` carry every var-width grouping key of ``op`` as a coded
    column? (A stream whose first batch does not goes to the host table.)"""
    from blaze_tpu.core.batch import CodedColumn
    from blaze_tpu.exprs.compiler import reference_index

    for _, e in op.groupings:
        idx = reference_index(e, batch.schema)
        if idx is not None and T.is_var_width(batch.schema[idx].dtype) and \
                not isinstance(batch.columns[idx], CodedColumn):
            return False
    return True


def has_var_width_keys(op, child_schema: T.Schema) -> bool:
    return any(T.is_var_width(E.infer_type(e, child_schema))
               for _, e in op.groupings)


def _key_planes(ev: ExprEvaluator, groupings, batch: ColumnarBatch, exists):
    """The grouping keys of ``batch`` as device planes: (data, validity &
    exists) a key (the validity as it stands where ``exists`` is None), and
    beside them the dictionary of each key that is a
    coded column (its plane is the int32 codes; None for the others)."""
    from blaze_tpu.exprs.compiler import CodedVal

    key_data, key_valid, dicts = [], [], []
    for _, e in groupings:
        val = ev._eval(e, batch)
        dicts.append(val.col.dictionary if isinstance(val, CodedVal) else None)
        d, v = _broadcast(ev._to_dev(val, batch), batch)
        key_data.append(d)
        key_valid.append(v if exists is None else v & exists)
    return key_data, key_valid, dicts


def _key_column(dt: T.DataType, dictionary, data, validity):
    """An output key column: coded over ``dictionary`` where the input key
    was, else the type's device column."""
    from blaze_tpu.core.batch import CodedColumn

    if dictionary is not None:
        return CodedColumn(dt, data, validity, dictionary)
    return DeviceColumn(dt, data, validity)


def supports_device_partial(op, child_schema: T.Schema) -> bool:
    """Partial-mode hash agg over device keys and device-mode aggregates."""
    if not op.is_partial_output or op.input_is_partial or not op.groupings:
        return False
    from blaze_tpu.ops import aggfns

    for _, e in op.groupings:
        if not _device_key(e, child_schema):
            return False
    for a in op.aggs:
        if a.agg.fn not in _DEVICE_AGG_FNS:
            return False
        fn = aggfns.create_agg_function(a.agg, child_schema)
        if fn.host:
            return False
        # non-device args are only eligible as wide-decimal limb
        # aggregates (limbs '3'/'w'): the agger extracts their limb
        # planes eagerly from the host decimal128 column. Anything else
        # host-resident stays on the generic table.
        if a.agg.args and not is_device_dtype(
                E.infer_type(a.agg.args[0], child_schema)) and \
                getattr(fn, "limbs", False) not in ("3", "w"):
            return False
    return True


def supports_fused_filter(filter_op, grandchild_schema: T.Schema) -> bool:
    """Can the filter's predicate run inside the agg's jitted kernel? All
    columns must be jit-flattenable — device-resident, or wide decimals
    (which flatten as limb planes but which no PREDICATE may touch) — and
    the predicate must be stateless jax-traceable."""
    from blaze_tpu.exprs.compiler import _contains_stateful

    if getattr(filter_op, "projection", None) is not None:
        return False
    if not all(is_device_dtype(f.dtype) or _is_wide_dec(f.dtype)
               for f in grandchild_schema.fields):
        return False
    if any(_touches_wide(p, grandchild_schema)
           for p in filter_op.predicates):
        return False
    return not any(_contains_stateful(p) for p in filter_op.predicates)


class _TableState:
    """A stream's slot-table state (for one null signature of its keys):
    ``dense_ok`` None = eligibility undecided, False = ineligible or
    refused; ``bucket_state`` the active plan."""

    __slots__ = ("dense_ok", "bucket_state")

    def __init__(self):
        self.dense_ok = self.bucket_state = None


class DevicePartialAgger:
    """Streams batches through the jitted partial kernels: the slot table
    where the probed key range allows it, else the sort path.

    With ``fused_predicates`` set, the upstream FilterExec's predicate is
    traced INTO the kernel (reference: filter-project fusion): the filter
    mask becomes the kernel's row-exists mask, so a filter+partial-agg
    pipeline stage costs one jit call and one scalar sync per batch instead
    of a compaction round trip plus the kernel."""

    def __init__(self, op, child_schema: T.Schema, fused_predicates=None,
                 conf=None, fused_join=None, fused_steps=None,
                 fused_input_schema=None, metrics=None):
        from blaze_tpu.config import get_config

        self.op = op
        self.child_schema = child_schema
        self.fused_predicates = fused_predicates
        # one OR SEVERAL chained unique-key joins traced into the kernel
        # (a star query's stacked dim BHJs); stored inner-first so the
        # probe batch flows join-by-join in plan order
        if fused_join is None:
            self.fused_joins = []
        elif isinstance(fused_join, FusedJoinSpec):
            self.fused_joins = [fused_join]
        else:
            self.fused_joins = list(fused_join)
        # an absorbed upstream fused-stage chain (project/filter/rename
        # steps): batches arrive with fused_input_schema and the steps are
        # traced INTO the kernel ahead of the predicates, so
        # scan->project->filter->partial-agg is one jitted computation
        self.fused_steps = tuple(fused_steps) if fused_steps else ()
        self.input_schema = fused_input_schema if self.fused_steps \
            else child_schema
        self.metrics = metrics
        self.conf = conf or get_config()
        self._fused_cache = {}
        # slot-table path: _dense_ok None = eligibility undecided, False =
        # ineligible/disabled; a bucket state is the active plan
        # ("dense"|"wide", bases, sizes, out_cap)
        # ... kept a NULL SIGNATURE of the stream (which grouping keys
        # arrive as a ROLLUP's typed NULL, `_null_signature`): each grouping
        # set of an Expand has its own key space and plans for itself. A
        # stream without such keys has one signature: one state, one probe.
        self._table_states = {(): _TableState()}
        self._table_state = self._table_states[()]
        # the dictionaries of the batch in hand's coded keys (_key_planes)
        self._key_dicts = [None] * len(op.groupings)
        self.group_ev = ExprEvaluator([e for _, e in op.groupings], child_schema,
                                      metrics)
        self.agg_evs = [
            ExprEvaluator(list(a.agg.args), child_schema, metrics)
            if a.agg.args else None
            for a in op.aggs
        ]
        from blaze_tpu.ops import aggfns

        self.fns = [aggfns.create_agg_function(a.agg, child_schema) for a in op.aggs]
        # static spec per agg: (kind, rescale_pow, acc_dtype) drives the
        # kernel; acc dtype is the declared result/sum dtype so int32/f32
        # args accumulate widened, matching the generic path
        self.specs = []
        for a, fn in zip(op.aggs, self.fns):
            kind = a.agg.fn.value
            rescale = 0
            if isinstance(fn.arg_type, T.DecimalType) and isinstance(
                    fn.result_type, T.DecimalType):
                rescale = fn.result_type.scale - fn.arg_type.scale
            if kind == "avg" and isinstance(fn.arg_type, T.DecimalType):
                rescale = fn.sum_type.scale - fn.arg_type.scale
            lm = getattr(fn, "limbs", False)
            if kind == "sum" and lm == "2":
                # wide-decimal sum: two-int64-limb accumulation on device
                kind, rescale, acc_dt = "sum2", 0, ""
            elif kind == "avg" and lm == "2":
                # wide-decimal avg: limb sum + count on device
                kind, rescale, acc_dt = "avg2", 0, ""
            elif kind == "sum" and lm == "3":
                # wide ARG (19..38 digits): three-limb device accumulation;
                # the arg is a host decimal128 column, evaluated eagerly
                kind, rescale, acc_dt = "sum3", 0, ""
            elif kind == "avg" and lm == "3":
                kind, rescale, acc_dt = "avg3", 0, ""
            elif kind in ("min", "max") and lm == "w":
                kind, rescale, acc_dt = kind + "w", 0, ""
            elif kind == "stddev_samp":
                # an integral argument's exact moments (aggfns.MomentAgg)
                kind, rescale, acc_dt = "moment", 0, "int64"
            elif kind == "sum":
                acc_dt = "int64" if isinstance(fn.result_type, T.DecimalType) \
                    else str(np.dtype(fn.result_type.np_dtype))
            elif kind == "avg":
                acc_dt = "int64" if isinstance(fn.sum_type, T.DecimalType) \
                    else str(np.dtype(fn.sum_type.np_dtype))
            else:
                acc_dt = ""
            self.specs.append((kind, rescale, acc_dt))
        self._moments = any(kind == "moment" for kind, _r, _d in self.specs)

    # the slot-table state of the signature in hand
    _dense_ok = property(lambda self: self._table_state.dense_ok,
                         lambda self, v: setattr(self._table_state, "dense_ok", v))
    _bucket_state = property(
        lambda self: self._table_state.bucket_state,
        lambda self, v: setattr(self._table_state, "bucket_state", v))

    def _flow(self, batch: ColumnarBatch, exists):
        """Traceable per-batch flow: evaluate keys/args, run the segment
        kernel body. Works on real arrays (eager) and tracers (fused jit)."""
        # direct _eval use bypasses evaluate()'s per-batch CSE reset — reset
        # explicitly or batch N would reuse batch N-1's cached arrays
        self.group_ev._reset_cse(batch)
        for ev in self.agg_evs:
            if ev is not None:
                ev._reset_cse(batch)
        key_data, key_valid, self._key_dicts = _key_planes(
            self.group_ev, self.op.groupings, batch, exists)
        args = self._eval_args(batch, exists)
        kernel = _partial_kernel(
            tuple(str(d.dtype) for d in key_data),
            tuple(self.specs),
            tuple("wide3" if isinstance(a[0], tuple) else str(a[0].dtype)
                  for a in args),
            batch.capacity,
        )
        flat = []
        for d, v in zip(key_data, key_valid):
            flat += [d, v]
        for d, v in args:
            flat += ([*d, v] if isinstance(d, tuple) else [d, v])
        return kernel(exists, *flat)

    def _eval_args(self, batch: ColumnarBatch, exists):
        """Per-aggregate (data, valid) pairs; wide-decimal args come back as
        a (l0, l1, l2) plane tuple extracted from the host decimal128
        column (eager only — wide args never enter the jitted fused
        paths)."""
        args = []
        for a, ev, (kind, _r, _d) in zip(self.op.aggs, self.agg_evs,
                                         self.specs):
            if ev is None:
                args.append((jnp.zeros(batch.capacity, jnp.int64), exists))
            elif kind in _WIDE_KINDS:
                arg = a.agg.args[0]
                planes = valid = None
                if isinstance(arg, (E.Column, E.BoundReference)):
                    # bare-column wide args read the batch's limb planes
                    # directly — works in BOTH eager and traced contexts
                    # (_WideLimbCol in a virtual batch, HostColumn eagerly)
                    try:
                        idx = arg.index if isinstance(arg, E.BoundReference) \
                            else batch.schema.index_of(arg.name)
                    except (KeyError, ValueError):
                        idx = None
                    if idx is not None:
                        col = batch.columns[idx]
                        if isinstance(col, _WideLimbCol):
                            planes = (col.l0, col.l1, col.l2)
                            valid = col.validity
                        elif isinstance(col, DeviceColumn):
                            planes, valid = _proved_limbs(col.data), \
                                col.validity
                        else:
                            p4 = _host_wide_planes(col, batch.capacity)
                            planes, valid = p4[:3], p4[3]
                if planes is None:
                    planes, valid = self._wide_arg_planes(
                        ev._eval(arg, batch), batch)
                args.append((planes, valid & exists))
            else:
                dv = ev._to_dev(ev._eval(a.agg.args[0], batch), batch)
                d, val = _broadcast(dv, batch)
                args.append((d, val & exists))
        return args

    def _wide_arg_planes(self, val, batch: ColumnarBatch):
        from blaze_tpu.exprs.compiler import HostVal

        assert isinstance(val, HostVal), "wide decimal args are host-resident"
        arr = val.arr
        if len(arr) == 1 and batch.num_rows != 1:
            import pyarrow as pa

            arr = pa.concat_arrays([arr] * batch.num_rows) \
                if batch.num_rows else arr.slice(0, 0)

        class _ArrCol:
            array = arr

        p4 = _host_wide_planes(_ArrCol, batch.capacity)
        return p4[:3], p4[3]

    def _trace_tb_mask(self, num_rows, flat):
        """Traced: jit inputs -> (tracer batch over the agg's child schema,
        row keep-mask). With ``fused_join`` the batch is the PROBE side and
        the joined tracer batch + hit mask come from the join spec; the
        optional fused predicates then evaluate over the joined schema."""
        if self.fused_joins:
            pos = 0
            jflats = []
            for spec in self.fused_joins:
                nb = spec.n_build_planes()
                jflats.append(flat[pos:pos + nb])
                pos += nb
            tb = None
            mask = None
            pflat = flat[pos:]
            for spec, jf in zip(self.fused_joins, jflats):
                tb, hit = spec.trace_join(num_rows, jf,
                                          pflat if tb is None else tb)
                mask = hit if mask is None else (mask & hit)
        else:
            schema = self.input_schema
            cols, _ = _rebuild_cols(schema, flat)
            tb = ColumnarBatch(schema, cols, num_rows)
            # inline, NOT tb.row_exists_mask(): that helper caches in a
            # module lru_cache a traced call would poison
            mask = jnp.arange(tb.capacity, dtype=jnp.int64) < num_rows
        if self.fused_steps:
            # absorbed upstream chain: project/filter/rename steps trace
            # over the chain's input schema, narrowing the live mask in
            # place (no mid-chain compaction — same discipline as
            # build_fused_closure); the result batch carries the agg's
            # child schema
            from blaze_tpu.exprs.compiler import trace_fused_steps

            cols, mask = trace_fused_steps(self.input_schema,
                                           self.fused_steps,
                                           list(tb.columns), mask,
                                           tb.capacity)
            tb = ColumnarBatch(self.child_schema, cols, num_rows)
        if self.fused_predicates:
            # fresh evaluator per trace: its CSE cache must hold tracers
            # of THIS trace only
            pred_ev = ExprEvaluator(list(self.fused_predicates),
                                    self.child_schema)
            mask = mask & pred_ev.evaluate_predicate(tb)
        return tb, mask

    def _jit_flat(self, batch: ColumnarBatch):
        flat = []
        for spec in self.fused_joins:
            flat += spec.jit_args(batch)
        return flat + self._flat(batch)

    def _trace_clone(self) -> "DevicePartialAgger":
        """The agger instance jit closures may capture: identical structural
        state, but fused_join is a trace_view() so the module-cached kernel
        never pins the broadcast build map's buffers."""
        import copy

        clone = copy.copy(self)
        clone.fused_joins = [s.trace_view() for s in self.fused_joins]
        clone._fused_cache = {}
        return clone

    def _cap_key(self, batch: ColumnarBatch):
        return (batch.capacity,
                tuple((f.name, str(f.dtype)) for f in batch.schema.fields),
                tuple(s.shape_key() for s in self.fused_joins))

    def _fused_fn(self, batch: ColumnarBatch):
        """Jitted (join + predicate + flow), cached at MODULE level by
        structural key — jax.jit caches by function identity, so a
        per-instance closure would recompile for every partition/run."""
        cap_key = self._cap_key(batch)
        fn = self._fused_cache.get(cap_key)
        if fn is not None:
            return fn
        key = (self._structural_key(), cap_key)
        fn = _FUSED_KERNELS.get(key)
        if fn is None:
            agger = self._trace_clone()

            def fused(num_rows, *flat):
                tb, mask = agger._trace_tb_mask(num_rows, flat)
                return agger._flow(tb, mask)

            fn = jax.jit(fused)
            _FUSED_KERNELS[key] = fn
        self._fused_cache[cap_key] = fn
        return fn

    def _needs_trace(self) -> bool:
        """Does per-batch processing go through the jitted fused kernel
        (joins, predicates, or an absorbed step chain traced in)?"""
        return (self.fused_predicates is not None or bool(self.fused_joins)
                or bool(self.fused_steps))

    def _structural_key(self) -> str:
        if getattr(self, "_skey", None) is None:
            from blaze_tpu.ir.serde import expr_to_json

            parts = [expr_to_json(p) for p in (self.fused_predicates or ())]
            parts += [s.structural_key() for s in self.fused_joins]
            if self.fused_steps:
                from blaze_tpu.ir.fusion import fused_fingerprint

                parts.append("steps:" + fused_fingerprint(
                    self.input_schema, self.fused_steps))
            parts += [f"{n}:{expr_to_json(e)}" for n, e in self.op.groupings]
            parts += [f"{a.name}:{a.mode.value}:{expr_to_json(a.agg)}"
                      for a in self.op.aggs]
            self._skey = "|".join(parts)
        return self._skey

    # -- dense-bucket fast path ------------------------------------------------

    def _flat(self, batch: ColumnarBatch):
        return _flatten_cols(batch)

    def _int_keys(self) -> bool:
        for _, e in self.op.groupings:
            dt = E.infer_type(e, self.child_schema)
            if T.is_var_width(dt):
                continue  # a coded key: its plane is int32 codes
            ndt = dt.np_dtype
            if ndt is None or not np.issubdtype(np.dtype(ndt), np.integer):
                return False
        return True

    def _dense_enabled(self) -> bool:
        """Integer-keyed partial aggs may use the slot-table kernel, on every
        backend unless ``dense_agg`` is forced off: whether a stream does is
        decided from its probed key range (_plan_bucketed), at one extra
        sync a stream (``sync:agg_probe``). Measured on the chip in PR 25
        (PERF.md section 6)."""
        if self._dense_ok is None:
            self._dense_ok = self.conf.dense_agg is not False and \
                self._int_keys()
        return self._dense_ok

    def _probe_eager(self, batch: ColumnarBatch):
        """Range probe for the unfused path: evaluates keys eagerly (the
        batch may carry HostColumns the jitted probe cannot flatten) and
        reduces min/max/any on device in one dispatch."""
        exists = batch.row_exists_mask()
        self.group_ev._reset_cse(batch)
        key_data, key_valid, _ = _key_planes(
            self.group_ev, self.op.groupings, batch, None)  # masked in the jit
        return _key_ranges_jit(exists, list(zip(key_data, key_valid)))

    def _probe_fn(self, batch: ColumnarBatch):
        """Jitted range probe for the fused path (all columns device-
        resident by supports_fused_filter): per group key, (any_valid, min,
        max) over rows passing the join + predicate. One dispatch + one
        small sync, once per stream (and once more per range overflow)."""
        cap_key = self._cap_key(batch)
        key = ("probe", self._structural_key(), cap_key)
        fn = _FUSED_KERNELS.get(key)
        if fn is None:
            agger = self._trace_clone()

            def probe(num_rows, *flat):
                tb, mask = agger._trace_tb_mask(num_rows, flat)
                agger.group_ev._reset_cse(tb)
                return _key_ranges(mask, [
                    _broadcast(agger.group_ev._to_dev(
                        agger.group_ev._eval(e, tb), tb), tb)
                    for _, e in agger.op.groupings])

            fn = jax.jit(probe)
            _FUSED_KERNELS[key] = fn
        return fn

    def _plan_table(self, probe: np.ndarray, capacity: int, prev,
                    max_slots: int):
        return _plan_slot_table(probe, capacity, prev, max_slots, self.conf)

    def _plan_bucketed(self, probe: np.ndarray, capacity: int, prev):
        """Pick the slot-table plan for this stream: "dense" when the key
        space fits ``min(dense_agg_max_buckets, capacity)``, else a "wide"
        table of any size whose packed slot id fits ``_SLOT_ID_MAX_SLOTS``
        (slot-sorted, which is flat in the slots; its sizes traced, so one
        program serves every such table of the stream's key types). Returns
        ("dense"|"wide", bases, sizes, out_cap), _DEFER_PLAN, or None (the
        sort kernel)."""
        if not self._dense_enabled():
            return None
        st = self._plan_table(probe, capacity, prev,
                              min(self.conf.dense_agg_max_buckets, capacity))
        if st is _DEFER_PLAN:
            return _DEFER_PLAN
        if st is not None:
            return ("dense",) + st
        st = self._plan_table(probe, capacity, prev, _SLOT_ID_MAX_SLOTS)
        return None if st is None else ("wide",) + st

    def _dense_call(self, batch: ColumnarBatch, bases, sizes, out_cap):
        bases_arr = jnp.asarray(np.asarray(bases, np.int64))
        if self._needs_trace():
            cap_key = self._cap_key(batch)
            key = ("dense", self._structural_key(), cap_key, sizes, out_cap)
            fn = _FUSED_KERNELS.get(key)
            if fn is None:
                agger = self._trace_clone()

                def fused(num_rows, b, *flat):
                    tb, mask = agger._trace_tb_mask(num_rows, flat)
                    return agger._flow_dense(tb, mask, b, sizes, out_cap)

                fn = jax.jit(fused)
                _FUSED_KERNELS[key] = fn
            return fn(jnp.int64(batch.num_rows), bases_arr,
                      *self._jit_flat(batch))
        return self._flow_dense(batch, batch.row_exists_mask(), bases_arr,
                                sizes, out_cap)

    def _flow_dense(self, batch: ColumnarBatch, exists, bases, sizes,
                    out_cap):
        """_flow twin routing to the slot-table kernel."""
        self.group_ev._reset_cse(batch)
        for ev in self.agg_evs:
            if ev is not None:
                ev._reset_cse(batch)
        key_data, key_valid, self._key_dicts = _key_planes(
            self.group_ev, self.op.groupings, batch, exists)
        args = self._eval_args(batch, exists)
        kernel = _dense_partial_kernel(
            tuple(str(d.dtype) for d in key_data), tuple(self.specs),
            tuple("wide3" if isinstance(a[0], tuple) else str(a[0].dtype)
                  for a in args), batch.capacity,
            sizes, out_cap)
        flat = []
        for d, v in zip(key_data, key_valid):
            flat += [d, v]
        for d, v in args:
            flat += ([*d, v] if isinstance(d, tuple) else [d, v])
        return kernel(exists, bases, *flat)

    def _try_dense(self, batch: ColumnarBatch):
        """Slot-table orchestration: probe on first use, run the table's
        kernel (``jit(agg_dense_partial)``, dense or wide), re-probe + widen
        once on range overflow. Returns (outs, num_groups) or None to fall
        back to the sort kernel."""
        nulled = self._null_signature(batch)
        self._table_state = self._table_states.setdefault(nulled, _TableState())
        if not self._dense_enabled():
            return None  # not eligible, or this signature's range refused
        st = self._bucket_state
        prev = None
        for _ in range(2):
            if st is None:
                if self._needs_trace():
                    pr = self._probe_fn(batch)(
                        jnp.int64(batch.num_rows), *self._jit_flat(batch))
                else:
                    pr = self._probe_eager(batch)
                pr = wait_array(pr, "agg_probe")
                if nulled:
                    # a nulled key has no valid row to anchor its range, and
                    # never will: one slot beside the NULL's
                    pr = np.array(pr)
                    pr[list(nulled)] = (1, 0, 0)
                st = self._plan_bucketed(pr, batch.capacity, prev)
                if st is _DEFER_PLAN:
                    # no valid keys in this batch to anchor a plan: sort
                    # fallback for this batch, re-probe on the next one
                    self._bucket_state = None
                    return None
                if st is None:
                    # observed range too wide for even a packed slot id:
                    # stop probing for the rest of this stream (of its
                    # batches with these keys nulled)
                    self._dense_ok = False
                    self._bucket_state = None
                    return None
                self._bucket_state = st
            table, bases, sizes, out_cap = st
            if math.prod(sizes) > out_cap:
                # a table wider than the batch it was planned on: a batch
                # has as many groups as its capacity, as on the sort path
                out_cap = self.conf.capacity_for(
                    min(math.prod(sizes), batch.capacity))
            if table == "wide":
                outs = self._dense_call(batch, _wide_table(bases, sizes),
                                        None, out_cap)
            else:
                outs = self._dense_call(batch, bases, sizes, out_cap)
            # sync; -1 flags range overflow
            num_groups = wait_int(outs[0], "agg_partial")
            if num_groups >= 0:
                DEVICE_STATS.add_agg_batch(
                    dense=True,
                    slot_sorted=_is_slot_sorted(sizes, batch.capacity))
                return outs, num_groups
            prev, st = (bases, sizes), None
        self._bucket_state = None
        return None

    def _null_signature(self, batch: ColumnarBatch) -> tuple:
        """The grouping keys that arrive as a ROLLUP's typed NULL (a coded
        column marked ``null_literal``, `CodedColumn.nulls_like`). Each grouping
        set of an Expand has its own key space — the grand total's is one
        slot, the finest set's every name — so each signature plans its
        slot table for itself, from a probe of its own."""
        from blaze_tpu.core.batch import CodedColumn
        from blaze_tpu.exprs.compiler import reference_index

        nulled = []
        for i, (_, e) in enumerate(self.op.groupings):
            idx = reference_index(e, batch.schema)
            col = batch.columns[idx] if idx is not None else None
            if isinstance(col, CodedColumn) and col.null_literal:
                nulled.append(i)
        return tuple(nulled)

    def _sort_groups(self, outs) -> int:
        """The sort kernel's sync point (it completes here): the batch's
        group count, and the batch counted as the sort path's."""
        DEVICE_STATS.add_agg_batch(dense=False)
        return wait_int(outs[0], "agg_partial")

    def _note_moments(self):
        """A batch whose moment state a kernel of this agger reduces (every
        path of ``process`` and ``passthrough`` is one)."""
        if self._moments:
            _note_moment_batches(self.metrics, 1)

    def process(self, batch: ColumnarBatch) -> Optional[ColumnarBatch]:
        n = batch.num_rows
        if n == 0:
            return None
        self._note_moments()
        if self.fused_joins and \
                not all(s.batch_eligible(batch) for s in self.fused_joins):
            # non-flattenable probe batch: run the joins for real
            # (inner-first), then the eager (unfused) agg flow
            jb = batch
            for spec in self.fused_joins:
                jb = spec.materialize(jb, spec.metrics)
                if jb is None or jb.num_rows == 0:
                    return None
            exists = jb.row_exists_mask()
            if self.fused_predicates:
                exists = ExprEvaluator(
                    list(self.fused_predicates),
                    self.child_schema).evaluate_predicate(jb)
            outs = self._flow(jb, exists)
            num_groups = self._sort_groups(outs)
            if num_groups == 0:
                return None
            return self._assemble(outs, num_groups)
        if self.fused_steps and not self._steps_eligible(batch):
            # non-flattenable chain input: run the absorbed steps for real
            # (the fused stage's eager fallback), then the eager agg flow
            from blaze_tpu.ops.fused import eager_steps

            parts = []
            for sb in eager_steps(self.fused_steps, self.input_schema,
                                  batch):
                if sb.num_rows == 0:
                    continue
                exists = sb.row_exists_mask()
                if self.fused_predicates:
                    exists = exists & ExprEvaluator(
                        list(self.fused_predicates),
                        self.child_schema).evaluate_predicate(sb)
                outs = self._flow(sb, exists)
                num_groups = self._sort_groups(outs)
                if num_groups:
                    parts.append(self._assemble(outs, num_groups))
            if not parts:
                return None
            return parts[0] if len(parts) == 1 else \
                ColumnarBatch.concat(parts, self.op.schema, self.metrics)
        dense = self._try_dense(batch)
        if dense is not None:
            outs, num_groups = dense
        else:
            if self._needs_trace():
                outs = self._fused_fn(batch)(jnp.int64(n),
                                             *self._jit_flat(batch))
            else:
                outs = self._flow(batch, batch.row_exists_mask())
            num_groups = self._sort_groups(outs)
        if num_groups == 0:
            return None
        return self._assemble(outs, num_groups)

    def _steps_eligible(self, batch: ColumnarBatch) -> bool:
        return all(isinstance(c, DeviceColumn) or _is_wide_dec(f.dtype)
                   for c, f in zip(batch.columns, batch.schema.fields))

    def passthrough(self, batch: ColumnarBatch) -> Optional[ColumnarBatch]:
        """Skipped-partial fast path: one singleton partial-state group per
        input row, no dedup, no sort, no probe. Used once the per-bucket
        cardinality heuristic decides partial aggregation is not reducing
        (near-unique keys) — the FINAL stage merges singleton states
        exactly like any other partials, so results are identical. Only
        valid without fused joins/predicates/steps (the caller gates)."""
        n = batch.num_rows
        if n == 0:
            return None
        self._note_moments()
        exists = batch.row_exists_mask()
        self.group_ev._reset_cse(batch)
        for ev in self.agg_evs:
            if ev is not None:
                ev._reset_cse(batch)
        key_data, key_valid, self._key_dicts = _key_planes(
            self.group_ev, self.op.groupings, batch, exists)
        args = self._eval_args(batch, exists)
        kernel = _passthrough_kernel(
            tuple(str(d.dtype) for d in key_data), tuple(self.specs),
            tuple("wide3" if isinstance(a[0], tuple) else str(a[0].dtype)
                  for a in args), batch.capacity)
        flat = []
        for d, v in zip(key_data, key_valid):
            flat += [d, v]
        for d, v in args:
            flat += ([*d, v] if isinstance(d, tuple) else [d, v])
        outs = kernel(exists, *flat)
        # rows stay in place (exists is a prefix mask), so the group count
        # is the batch's row count — no device sync at all
        return self._assemble(outs, n)

    def _assemble(self, outs, num_groups: int) -> ColumnarBatch:
        pos = 1
        cols: List[DeviceColumn] = []
        out_valid_mask = outs[pos]; pos += 1
        schema = self.op.schema
        ci = 0
        for gi, (gname, e) in enumerate(self.op.groupings):
            dt = schema[ci].dtype
            cols.append(_key_column(dt, self._key_dicts[gi], outs[pos],
                                    outs[pos + 1] & out_valid_mask))
            pos += 2
            ci += 1
        for a, fn, (kind, _, _) in zip(self.op.aggs, self.fns, self.specs):
            if kind == "sum2":
                lo, hi, has = outs[pos], outs[pos + 1], outs[pos + 2]; pos += 3
                cols.append(DeviceColumn(T.I64, lo, out_valid_mask))
                cols.append(DeviceColumn(T.I64, hi, out_valid_mask))
                cols.append(DeviceColumn(T.BOOL, has, out_valid_mask))
                ci += 3
            elif kind == "avg2":
                lo, hi, cnt = outs[pos], outs[pos + 1], outs[pos + 2]; pos += 3
                cols.append(DeviceColumn(T.I64, lo, out_valid_mask))
                cols.append(DeviceColumn(T.I64, hi, out_valid_mask))
                cols.append(DeviceColumn(T.I64, cnt, out_valid_mask))
                ci += 3
            elif kind in ("sum",):
                s, has = outs[pos], outs[pos + 1]; pos += 2
                cols.append(DeviceColumn(fn.result_type, s, has & out_valid_mask))
                cols.append(DeviceColumn(T.BOOL, has, out_valid_mask))
                ci += 2
            elif kind == "count":
                c = outs[pos]; pos += 1
                cols.append(DeviceColumn(T.I64, c, out_valid_mask))
                ci += 1
            elif kind == "avg":
                s, c = outs[pos], outs[pos + 1]; pos += 2
                cols.append(DeviceColumn(fn.sum_type, s, (c > 0) & out_valid_mask))
                cols.append(DeviceColumn(T.I64, c, out_valid_mask))
                ci += 2
            elif kind in ("min", "max"):
                v, has = outs[pos], outs[pos + 1]; pos += 2
                cols.append(DeviceColumn(fn.result_type, v, has & out_valid_mask))
                cols.append(DeviceColumn(T.BOOL, has, out_valid_mask))
                ci += 2
            elif kind == "moment":
                cols.extend(DeviceColumn(T.I64, p, out_valid_mask)
                            for p in outs[pos:pos + _MOMENT_PLANES])
                pos += _MOMENT_PLANES
                ci += _MOMENT_PLANES
            elif kind in _WIDE_KINDS:
                a0, a1, a2, last = outs[pos:pos + 4]; pos += 4
                cols.append(DeviceColumn(T.I64, a0, out_valid_mask))
                cols.append(DeviceColumn(T.I64, a1, out_valid_mask))
                cols.append(DeviceColumn(T.I64, a2, out_valid_mask))
                cols.append(DeviceColumn(
                    T.I64 if kind == "avg3" else T.BOOL, last,
                    out_valid_mask))
                ci += 4
        return ColumnarBatch(schema, cols, num_groups)


# the moment state's planes: the count, Σx's three limbs, Σx²'s four
_MOMENT_PLANES = 8


def _note_moment_batches(metrics, batches: int):
    """``moment_device_batches``: on the operator's node and the process's."""
    if metrics is not None:
        metrics.add("moment_device_batches", batches)
    DEVICE_STATS.add_moment(device_batches=batches)


def _key_ranges(mask, keys):
    """Traced: one (any_valid, min, max) int64 row per (data, valid) group
    key over the rows ``mask`` keeps — what _plan_slot_table plans from."""
    info = jnp.iinfo(jnp.int64)
    rows = []
    for d, val in keys:
        val = val & mask
        d64 = d.astype(jnp.int64)
        rows.append(jnp.stack([
            jnp.any(val).astype(jnp.int64),
            jnp.min(jnp.where(val, d64, info.max)),
            jnp.max(jnp.where(val, d64, info.min))]))
    return jnp.stack(rows)


_key_ranges_jit = jax.jit(_key_ranges)


def _plan_slot_table(probe: np.ndarray, capacity: int, prev,
                     max_slots: int, conf):
    """(bases, sizes, out_cap) from probed key ranges, unioned with the
    previous plan on overflow so re-bucketed batches keep fitting. Sizes
    round to powers of two to bound kernel recompiles. None when the slot
    table would exceed ``max_slots``; shared by the partial aggers and the
    merge's slot plan."""
    bases, sizes, S = [], [], 1
    for i, (anyv, kmin, kmax) in enumerate(probe):
        if not anyv:
            if prev is not None:
                # no valid keys observed: keep the previous anchor
                # rather than dragging the union toward [0, 0]
                lo = int(prev[0][i])
                hi = lo + prev[1][i] - 2
            else:
                # No valid keys and nothing to anchor to: planning now
                # would pin an artificial [0, 0] anchor that a later
                # overflow unions with the real key range, potentially
                # blowing past the bucket cap and disabling the dense
                # path for the whole stream. Defer so the next batch
                # re-probes with real keys.
                return _DEFER_PLAN
        else:
            lo, hi = int(kmin), int(kmax)
            if prev is not None:
                plo = int(prev[0][i])
                phi = plo + prev[1][i] - 2
                lo, hi = min(lo, plo), max(hi, phi)
        size = 2
        while size < hi - lo + 2:
            size <<= 1
        bases.append(lo)
        sizes.append(size)
        S *= size
    if S > max_slots:
        return None
    out_cap = conf.capacity_for(min(S, capacity))
    return tuple(bases), tuple(sizes), out_cap


def _canonical_keys(key_data, key_valid):
    """Float keys canonicalized so grouping matches the host intern path:
    -0.0 folds into 0.0, all NaNs group together; nulls zeroed."""
    canon = []
    for d, v in zip(key_data, key_valid):
        if jnp.issubdtype(d.dtype, jnp.floating):
            d = jnp.where(jnp.isnan(d), jnp.array(float("nan"), d.dtype), d)
            d = jnp.where(d == 0, jnp.zeros((), d.dtype), d)
        canon.append(jnp.where(v, d, jnp.zeros((), d.dtype)))
    return canon


def _order_word(d):
    """The uint64 word whose unsigned order is a canonical key plane's
    ascending order (NaN last): ``sort_keys.orderable_word_traced``, and for
    a float of any width its bits, the sign bit flipped and a negative
    value's other bits too."""
    if not jnp.issubdtype(d.dtype, jnp.floating):
        return orderable_word_traced(d)
    width = 8 * d.dtype.itemsize
    bits = jax.lax.bitcast_convert_type(d, jnp.dtype(f"uint{width}"))
    top = bits.dtype.type(1 << (width - 1))
    return jnp.where(bits >= top, ~bits, bits | top).astype(jnp.uint64)


_segmented_scan = K.segmented_scan_traced


def _lex3_pick(is_max: bool):
    """``combine`` of the (has, p2, p1, p0) scan behind minw / maxw: the
    lexicographic extreme of two wide-decimal values (:func:`_segment_lex3`'s
    answer in one pass; the planes are zero where ``has`` is not set)."""

    def pick(a, b):
        ha, a2, a1, a0 = a
        hb, b2, b1, b0 = b
        lo, hi = ((a2, a1, a0), (b2, b1, b0)) if is_max else \
            ((b2, b1, b0), (a2, a1, a0))
        b_wins = (lo[0] < hi[0]) | ((lo[0] == hi[0]) & (
            (lo[1] < hi[1]) | ((lo[1] == hi[1]) & (lo[2] < hi[2]))))
        take_b = hb & (~ha | b_wins)
        return (ha | hb, *(jnp.where(take_b, y, x)
                           for x, y in ((a2, b2), (a1, b1), (a0, b0))))

    return pick


def _is_running_total(op: str, dtype) -> bool:
    """Does :func:`_reduce_sorted` answer ``op`` over planes of ``dtype`` with
    a running total over ALL the rows? A segment's value is then its last
    row's less the last row's of the segment before. Integer sums and counts
    do (an integer sum wraps mod 2^64 either way, so the difference is the
    scatter-add's sum bit for bit); a float sum never."""
    return op in ("count", "any") or (
        op == "add" and jnp.issubdtype(dtype, jnp.integer))


def _reduce_sorted(op: str, new, planes):
    """One request's row planes over rows sorted into contiguous segments
    (``new`` marks each segment's first row): at a segment's last row stands
    its reduction, or its running total (:func:`_is_running_total`). On the
    TPU a prefix scan of 131,072 rows is a fraction of a millisecond where
    the scatter it replaces runs an update at a time (PERF.md section 6,
    PR 29). Everything but the running totals is a scan that restarts at
    ``new``; a float sum adds in that scan's fixed tree order."""
    x = planes[0]
    if op in ("count", "any"):
        # a batch has fewer than 2^31 rows: count in the native width
        return [jnp.cumsum(x, dtype=jnp.int32)]
    if _is_running_total(op, x.dtype):
        return [jnp.cumsum(x, dtype=x.dtype)]
    if op in ("lexmin", "lexmax"):
        p0, p1, p2, m = planes
        has, b2, b1, b0 = _segmented_scan(_lex3_pick(op == "lexmax"), new,
                                          (m, p2, p1, p0))
        return [b0, b1, b2, has]
    fn = {"add": jnp.add, "min": jnp.minimum, "max": jnp.maximum}[op]
    return list(_segmented_scan(lambda a, b: (fn(a[0], b[0]),), new, (x,)))


def _extreme_sentinel(kind: str, dtype):
    """What a row that does not count reads in a min / max reduction."""
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.array(jnp.inf if kind == "min" else -jnp.inf, dtype)
    info = jnp.iinfo(dtype)
    return jnp.array(info.max if kind == "min" else info.min, dtype)


def _partial_requests(spec, arg, exists):
    """One aggregate's reductions over raw rows, for :func:`_aggregate_sorted`:
    a list of ``(op, plane...)``, every plane already neutral on the rows
    that do not count. What :func:`_reduce_aggs` asks of ``_seg_reduce``."""
    kind, rescale, acc_dt = spec
    sa, sv = arg
    sv = sv & exists
    tail = ("count" if kind.startswith("avg") else "any", sv)
    z = jnp.int64(0)
    if kind == "moment":
        from blaze_tpu.ops.aggfns import moment_row_planes

        return [("count", sv)] + [("add", jnp.where(sv, p, z))
                                  for p in moment_row_planes(sa)]
    if kind in ("sum3", "avg3"):
        return [("add", jnp.where(sv, p, z)) for p in sa] + [tail]
    if kind in ("minw", "maxw"):
        return [("lex" + kind[:3], *(jnp.where(sv, p, z) for p in sa), sv)]
    if kind in ("sum2", "avg2"):
        x = sa.astype(jnp.int64)
        return [("add", jnp.where(sv, x & jnp.int64(0xFFFFFFFF), z)),
                ("add", jnp.where(sv, x >> 32, z)), tail]
    if kind in ("sum", "avg"):
        x = sa.astype(jnp.dtype(acc_dt))  # widen BEFORE accumulating
        if rescale:
            x = x * jnp.array(10 ** rescale, x.dtype)
        return [("add", jnp.where(sv, x, jnp.zeros((), x.dtype))), tail]
    if kind == "count":
        return [("count", sv)]
    return [(kind, jnp.where(sv, sa, _extreme_sentinel(kind, sa.dtype))),
            ("any", sv)]


def _merge_requests(kind: str, scols, exists):
    """One aggregate's reductions over partial STATE rows (its (data, valid)
    state columns), for :func:`_reduce_ordered` in ``jit(agg_merge)`` and
    ``jit(agg_merge_sorted)``: the merge's :func:`_partial_requests`."""
    cols = [(d, v & exists) for d, v in scols]

    def add(d, m):
        return ("add", jnp.where(m, d, jnp.zeros((), d.dtype)))

    if kind in ("count", "avg", "moment"):  # each plane its own mask
        return [add(d, v) for d, v in cols]
    # every other state ends in a column that says whether (sum, min, max,
    # the wide kinds) or how many (avg2, avg3) rows it holds
    *vals, (last, last_valid) = cols
    m = vals[0][1] & last.astype(bool) & last_valid
    tail = add(last, m) if kind in ("avg2", "avg3") else ("any", m)
    if kind in ("minw", "maxw"):
        return [("lex" + kind[:3],
                 *(jnp.where(m, d, jnp.int64(0)) for d, _ in vals), m)]
    if kind in ("min", "max"):
        (vd, _), = vals
        return [(kind, jnp.where(m, vd, _extreme_sentinel(kind, vd.dtype))),
                tail]
    return [add(d, m) for d, _ in vals] + [tail]


def _finish_state(kind: str, reduced):
    """Elementwise: one aggregate's reduced planes -> its state planes
    (the limb carries; an empty min / max reads 0, not the sentinel)."""
    if kind in ("sum2", "avg2"):
        slo, shi, last = reduced
        return [slo & jnp.int64(0xFFFFFFFF), shi + (slo >> 32), last]
    if kind in ("sum3", "avg3"):
        from blaze_tpu.ops.aggfns import _limb3_renorm

        s0, s1, s2, last = reduced
        return [*_limb3_renorm(s0, s1, s2), last]
    if kind == "moment":
        from blaze_tpu.ops.aggfns import moment_renorm

        return moment_renorm(*reduced)
    if kind in ("min", "max"):
        acc, has = reduced
        return [jnp.where(has, acc, jnp.zeros((), acc.dtype)), has]
    return list(reduced)


def _take_rows(planes, idx, live):
    """``kernels.take_rows_traced`` over data and bool planes in any order:
    the bool ones travel bit-packed, as its validity planes."""
    is_bool = [p.dtype == jnp.bool_ for p in planes]
    datas, bools = K.take_rows_traced(
        [p for p, b in zip(planes, is_bool) if not b],
        [p for p, b in zip(planes, is_bool) if b], idx, live)
    datas, bools = iter(datas), iter(bools)
    return [next(bools) if b else next(datas) for b in is_bool]


def _segment_value(op: str, x, out_valid):
    """A reduction's plane once the segments' last rows stand at the front:
    a running total less its neighbour's (the first group's neighbour is
    0), a count as int64, an ``any`` as a flag; a scan's value as it is."""
    if not _is_running_total(op, x.dtype):
        return x
    x = x - jnp.concatenate([jnp.zeros(1, x.dtype), x[:-1]])
    x = jnp.where(out_valid, x, jnp.zeros((), x.dtype))
    if op == "add":
        return x
    return x > 0 if op == "any" else x.astype(jnp.int64)


def _aggregate_sorted(exists, key_data, key_valid, kinds, requests):
    """The body ``jit(agg_partial)`` and ``jit(agg_merge)`` share: group the
    rows ``exists`` keeps by their keys and answer every aggregate's
    ``requests`` (:func:`_partial_requests` / :func:`_merge_requests`) a
    group. Returns the kernels' outputs: ``[num_groups, out_valid, (key
    data, key validity)..., state planes...]``, the groups at the front in
    key order (nulls first, then ascending; a null or padding key reads 0,
    a float key its canonical value), zeros past ``num_groups``.

    The order is ``lex_order_traced`` over the canonical key words
    (two-operand sorts only; the 2k + 2-operand sort this replaces compiled
    for five minutes). Its packed word is equal exactly where all the keys
    are, so a segment starts where the sorted word changes; the keys travel
    with the requests' planes. :func:`_reduce_ordered` does the rest."""
    nk = len(key_data)
    iota = jnp.arange(exists.shape[0], dtype=jnp.int32)
    key_valid = [v & exists for v in key_valid]
    canon = _canonical_keys(key_data, key_valid)
    with jax.named_scope("order"):
        columns = []
        for d, v in zip(canon, key_valid):
            # class -1: a null key, before the values; 1: padding, after all
            cls = jnp.where(v, 0, -1)
            if not columns:
                cls = jnp.where(exists, cls, 1)
            columns.append((_order_word(d), cls.astype(jnp.int8)))
        order, word = K.lex_order_traced(columns)
    num_groups, out_valid, keys, states = _reduce_ordered(
        exists, iota, order, word, [*canon, *key_valid], [], kinds, requests)
    outs = [num_groups, out_valid]
    for d, v in zip(keys[:nk], keys[nk:]):
        outs += [d, v]
    return tuple(outs + states)


def _reduce_ordered(exists, iota, order, word, row_keys, sorted_keys, kinds,
                    requests, out_cap=None):
    """Rows in an order that makes every group a contiguous segment ->
    ``(num_groups, out_valid, group keys, state planes)``, the groups at the
    front in that order. The order is handed in: ``order`` the row standing
    at each position with the rows ``exists`` drops last, ``word`` a plane
    in that order that changes exactly where a group starts, ``iota`` the
    positions (the caller's, made before its order, so that the sort path's
    programs stay equation for equation what they were). Its suppliers are ``lex_order_traced`` over any keys
    (:func:`_aggregate_sorted`) and ONE sort of the packed slot id where the
    keys fit a slot table (``jit(agg_dense_partial)`` above its crossover,
    ``jit(agg_merge_sorted)``).
    ``row_keys`` are planes by row and ``sorted_keys`` planes by position
    that say which group a row is in; both come back a group.

    Shaped by what the TPU charges (PERF.md section 6, PR 29; 131,072 rows):
    a row-sized int64 scatter 9 ms, a gather 0.9 ms a 32-bit plane it is
    asked for and no more for a row of many words, a two-operand sort or a
    prefix scan a few tenths. So nothing is scattered and every plane moves
    twice, each time with all the others as one matrix of words:

    order   ONE ``take_rows_traced`` brings ``row_keys`` and every request's
            planes into the order.
    reduce  contiguous segments reduce by prefix scans
            (:func:`_reduce_sorted`); a segment's last row holds its value.
    emit    the groups' last rows go to the front by the stable sort of
            their flags (PR 27's row map) and ONE ``take_rows_traced``, of
            the first ``out_cap`` of them where the caller knows there are
            no more groups; the running totals then subtract their
            neighbour's."""
    with jax.named_scope("order"):
        live = iota < jnp.sum(exists)  # padding sorts last
        new = live & jnp.concatenate(
            [jnp.ones(1, bool), word[1:] != word[:-1]])
        s_rows = _take_rows(
            [*row_keys,
             *(p for reqs in requests for _op, *planes in reqs for p in planes)],
            order, live)
    with jax.named_scope("reduce"):
        s_planes = iter(s_rows[len(row_keys):])
        # per aggregate, (op, row plane) for every plane its requests give
        reduced = [[(op, plane) for op, *planes in reqs
                    for plane in _reduce_sorted(
                        op, new, [next(s_planes) for _ in planes])]
                   for reqs in requests]
    with jax.named_scope("emit"):
        is_last = live & jnp.concatenate([new[1:] | ~live[1:],
                                          jnp.ones(1, bool)])
        num_groups = jnp.sum(is_last)
        out_valid = iota < num_groups
        _, last_row = jax.lax.sort(((~is_last).astype(jnp.uint8), iota),
                                   num_keys=1, is_stable=True)
        if out_cap is not None:
            out_valid, last_row = out_valid[:out_cap], last_row[:out_cap]
        s_keys = [*s_rows[:len(row_keys)], *sorted_keys]
        g_rows = _take_rows(
            [*s_keys, *(plane for agg in reduced for _op, plane in agg)],
            last_row, out_valid)
        g_planes = iter(g_rows[len(s_keys):])
        states = []
        for kind, agg in zip(kinds, reduced):
            states += _finish_state(kind, [
                _segment_value(op, next(g_planes), out_valid)
                for op, _plane in agg])
    return num_groups, out_valid, g_rows[:len(s_keys)], states


# The forms a slot table reduces in, picked by :func:`_table_form` from the
# static shapes. Chip measurements, not knobs: PERF.md section 6 has the
# slot-table kernel's timings at 16..65,536 slots with the form forced each
# way (PR 25: masked against scatter; PR 38: both against slot-sorted).
_MASKED_REDUCE_MAX_SLOTS = 16384  # past it the masked form loses to a scatter
# From it on the slot-sorted form wins. The masked form is linear in the
# slots and the slot-sorted one flat, and they cross between 600 and 2,600
# slots by capacity and kernel; ms a launch, masked / slot-sorted, for one
# int64 sum on two keys and for COUNT(*) + sum3 + maxw on one:
#   131,072 rows  1,024 slots 0.77 / 0.67, 3.68 / 2.17   2,048 1.61 / 0.68, 7.78 / 2.18
#   262,144 rows  1,024 slots 1.31 / 3.63, 6.81 / 6.50   2,048 2.71 / 3.65, 14.3 / 6.51
#                 4,096 slots 5.78 / 3.67, 29.8 / 6.55  16,384 30.5 / 3.80, 123 / 6.75
_SLOT_SORT_MIN_SLOTS = 2048
# The widest table a packed slot id numbers: a table past
# ``dense_agg_max_buckets`` is "wide": slot-sorted,
# whose cost is the sort of the id and not the slots, so what bounds it is the
# id's width. A wide table's sizes are traced and its id is int64 whatever its
# size, so one program a key type and batch capacity serves every wide table
# (each grouping set of a ROLLUP, each re-plan after an overflow), as one
# sort-path program did: a program a table size multiplied the executables a
# process compiles and loads (PERF.md section 6). Its padding rows
# take the id ``_WIDE_SENTINEL``, above every slot.
_SLOT_ID_MAX_SLOTS = (1 << 62) - 1
_WIDE_SENTINEL = 1 << 62


def _wide_table(bases, sizes):
    """A wide table's traced description: int64 rows of the keys' anchors,
    sizes and strides' bit shifts (every size is a power of two)."""
    shifts = [(math.prod(sizes[i + 1:])).bit_length() - 1
              for i in range(len(sizes))]
    return np.array([bases, sizes, shifts], np.int64)


def _sort_slot_ids(seg, iota):
    """ONE sort of the packed slot ids ``seg`` -> (the ids in order, the row
    standing at each position). Padding rows carry an id above every slot
    and sort last; ties in row order, so a float sum adds in one fixed
    order. An int64 id
    sorts as its two uint32 halves: on the chip 0.141 / 0.313 ms at 131,072
    / 262,144 rows, where the int64 operand takes 0.183 / 0.412 and compiles
    as long (PERF.md section 6)."""
    if seg.dtype != jnp.int64:
        return jax.lax.sort((seg, iota), num_keys=2, is_stable=False)
    hi, lo, order = jax.lax.sort(
        ((seg >> 32).astype(jnp.uint32), seg.astype(jnp.uint32), iota),
        num_keys=3, is_stable=False)
    return (hi.astype(jnp.int64) << 32) | lo.astype(jnp.int64), order


def _table_form(nseg: int, rows: int, sortable: bool = False) -> str:
    """How ``rows`` rows reduce into a table of ``nseg`` segments:

    "masked"   a vector reduction a slot (:func:`_seg_reduce`): ``nseg x
               rows`` lane operations a reduction, the fastest form of a
               small table and linear in its slots.
    "sorted"   ONE sort of the packed slot id and :func:`_reduce_ordered`:
               nearly flat in the slots. Only ``jit(agg_dense_partial)`` has
               it (``sortable``): it emits groups, not the table.
    "scatter"  ``.at[seg].<op>``, one serial update a row: what is left to a
               caller that needs the table itself and has at least a slot a
               row (a dense table as large as its batch, the passthrough
               kernel's segment a row, where masked is quadratic in the
               batch)."""
    if sortable and nseg >= _SLOT_SORT_MIN_SLOTS:
        return "sorted"
    if nseg <= _MASKED_REDUCE_MAX_SLOTS and nseg < rows:
        return "masked"
    return "scatter"


def _slot_hits(seg, nseg: int):
    """bool[nseg, rows]: row r routes to slot s. Never materialised: XLA
    fuses it into the reduction that consumes it."""
    return seg[None, :] == jnp.arange(nseg, dtype=seg.dtype)[:, None]


def _seg_reduce(op: str, seg, values, nseg: int, init=None, where=None):
    """Per-segment reduction: ``out[s] = op(init, values[seg == s])`` for s in
    [0, nseg); rows whose ``seg`` is outside (the padding sentinel) drop.
    ``op`` is "add", "min", "max", "any" (bool values) or "count" (bool
    values, int64 counts); ``where`` masks rows out (they contribute
    ``init``). Exact for integers in either form.

    The form follows the static shapes. A table that is small (at most
    ``_MASKED_REDUCE_MAX_SLOTS``) and smaller than the batch is reduced as a
    masked vector reduction, ``seg == slot`` against an iota of slots,
    select, reduce along the rows: ``nseg x rows`` lane operations that XLA
    fuses into one pass. Any other keeps ``.at[seg].<op>(mode="drop")``: on
    the TPU that is one serial update a row (66-88 ns each for an int64
    plane). The passthrough kernel comes here with a segment a row (``nseg``
    is the capacity, where the masked form would be quadratic in the batch),
    so its program is the same at every capacity. Rows that a sort has made
    contiguous segments of need neither form: :func:`_reduce_sorted`."""
    dtype = jnp.dtype(jnp.int64) if op == "count" else values.dtype
    if op in ("add", "count", "any"):
        init = dtype.type(0)
    if _table_form(nseg, seg.shape[0]) == "masked":
        hit = _slot_hits(seg, nseg)
        if where is not None:
            hit = hit & where[None, :]
        if op == "any":
            return jnp.any(hit & values[None, :], axis=1)
        if op == "count":
            # a batch has fewer than 2^31 rows: count in the native width
            return jnp.sum(hit & values[None, :], axis=1,
                           dtype=jnp.int32).astype(dtype)
        fill = jnp.asarray(init, dtype)
        picked = jnp.where(hit, values[None, :], fill)
        if op == "add":
            return jnp.sum(picked, axis=1, dtype=dtype)
        # a row of `picked` with no lane left at `init` (every row of the
        # batch in one segment) must still see it, as the scatter's table does
        if op == "min":
            return jnp.minimum(jnp.min(picked, axis=1), fill)
        return jnp.maximum(jnp.max(picked, axis=1), fill)
    if op in ("add", "count", "any"):
        acc = jnp.zeros(nseg, dtype)
    else:
        acc = jnp.full(nseg, init, dtype)
    if op == "count":
        values = values.astype(dtype)
    if where is not None:
        values = jnp.where(where, values, dtype.type(init))
    if op in ("add", "count"):
        return acc.at[seg].add(values, mode="drop")
    if op == "min":
        return acc.at[seg].min(values, mode="drop")
    return acc.at[seg].max(values, mode="drop")  # "max", and "any" of bools


def _seg_take(table, seg):
    """``table[seg]`` back onto the rows, in the form :func:`_seg_reduce`
    uses for a table of this size: for a small one a select along the slots
    and not a gather a row. Rows outside the table read anything."""
    nseg = table.shape[0]
    if _table_form(nseg, seg.shape[0]) == "masked":
        return jnp.sum(jnp.where(_slot_hits(seg, nseg), table[:, None],
                                 table.dtype.type(0)),
                       axis=0, dtype=table.dtype)
    return table[seg]


def _segment_lex3(p0, p1, p2, m, seg, nseg, is_max: bool):
    """Per-segment lexicographic extreme of (p2, p1, p0) wide-decimal value
    limbs (p2 signed high word decides; p1/p0 nonnegative 32-bit chunks
    break ties). Returns (b0, b1, b2, has), zeros where empty."""
    info = jnp.iinfo(jnp.int64)
    # the low limbs are nonnegative, so -1 is below every candidate of a max
    op, top, low = ("max", info.min, -1) if is_max else \
        ("min", info.max, info.max)
    b2 = _seg_reduce(op, seg, p2, nseg, top, where=m)
    t2 = m & (p2 == _seg_take(b2, seg))
    b1 = _seg_reduce(op, seg, p1, nseg, low, where=t2)
    t1 = t2 & (p1 == _seg_take(b1, seg))
    b0 = _seg_reduce(op, seg, p0, nseg, low, where=t1)
    shas = _seg_reduce("any", seg, m, nseg)
    z = jnp.int64(0)
    return (jnp.where(shas, b0, z), jnp.where(shas, b1, z),
            jnp.where(shas, b2, z), shas)


def _reduce_aggs(specs, args, seg, nseg_total):
    """Per-aggregate segment reductions of the partial kernels whose ``seg``
    is in no order (a masked slot table, passthrough; sorted rows take
    :func:`_partial_requests`). ``args[i]`` is the i-th aggregate's
    already-masked (data, valid) pair aligned with ``specs``; rows route to
    ``seg`` (out-of-range segments drop). Returns one ("kind", arrays...)
    tuple per aggregate, each array of length ``nseg_total``. Every
    reduction goes through :func:`_seg_reduce`, which picks its form from
    ``nseg_total``."""

    def add(x, where=None):
        return _seg_reduce("add", seg, x, nseg_total, where=where)

    def count(sv):
        return _seg_reduce("count", seg, sv, nseg_total)

    def has(sv):
        return _seg_reduce("any", seg, sv, nseg_total)

    outs = []
    for (kind, rescale, acc_dt), (sa, sv) in zip(specs, args):
        if kind in ("sum3", "avg3"):
            # wide ARG (19..38 digits) as three limbs (l0/l1 32-bit chunks,
            # l2 the signed high word wrapping mod 2^64 — exact within
            # decimal(38))
            p0, p1, p2 = sa
            from blaze_tpu.ops.aggfns import _limb3_renorm

            s0, s1, s2 = add(p0, sv), add(p1, sv), add(p2, sv)
            s0, s1, s2 = _limb3_renorm(s0, s1, s2)
            outs.append((kind, s0, s1, s2,
                         count(sv) if kind == "avg3" else has(sv)))
        elif kind in ("minw", "maxw"):
            p0, p1, p2 = sa
            b0, b1, b2, shas = _segment_lex3(p0, p1, p2, sv, seg,
                                             nseg_total, kind == "maxw")
            outs.append((kind, b0, b1, b2, shas))
        elif kind == "moment":
            from blaze_tpu.ops.aggfns import moment_renorm, moment_row_planes

            outs.append((kind, *moment_renorm(count(sv), *(
                add(p, sv) for p in moment_row_planes(sa)))))
        elif kind in ("sum2", "avg2"):
            # wide-decimal sum as two int64 limbs (lo 32 bits, hi rest):
            # per-segment limb sums fit int64 for any capacity, totals
            # renormalize so lo stays in [0, 2^32). avg2 additionally
            # carries the count instead of the has flag
            x = sa.astype(jnp.int64)
            vlo = jnp.where(sv, x & jnp.int64(0xFFFFFFFF), jnp.int64(0))
            vhi = jnp.where(sv, x >> 32, jnp.int64(0))
            slo = add(vlo)
            shi = add(vhi)
            carry = slo >> 32
            slo, shi = slo & jnp.int64(0xFFFFFFFF), shi + carry
            outs.append((kind, slo, shi,
                         count(sv) if kind == "avg2" else has(sv)))
        elif kind in ("sum", "avg"):
            x = sa.astype(jnp.dtype(acc_dt))  # widen BEFORE accumulating
            if rescale:
                x = x * jnp.array(10 ** rescale, x.dtype)
            ssum = add(jnp.where(sv, x, jnp.zeros((), x.dtype)))
            scnt = count(sv)
            outs.append((kind, ssum, scnt > 0 if kind == "sum" else scnt))
        elif kind == "count":
            outs.append(("count", count(sv)))
        else:  # min / max
            sent = _extreme_sentinel(kind, sa.dtype)
            acc = _seg_reduce(kind, seg, jnp.where(sv, sa, sent), nseg_total,
                              sent)
            shas = has(sv)
            outs.append((kind, jnp.where(shas, acc, 0), shas))
    return outs


def _is_slot_sorted(sizes, capacity: int) -> bool:
    """Does ``jit(agg_dense_partial)`` of these static shapes take the
    slot-sorted form?"""
    return _table_form(math.prod(sizes), capacity, sortable=True) == "sorted"


@functools.lru_cache(maxsize=256)
def _dense_partial_kernel(key_dtypes: Tuple[str, ...],
                          specs: Tuple[Tuple[str, int, str], ...],
                          arg_dtypes: Tuple[str, ...], capacity: int,
                          sizes: Tuple[int, ...], out_cap: int):
    """Dense-bucket partial kernel: integer group keys whose observed range
    fits a small table pack into ONE slot id below ``prod(sizes)``
    (``radix_pack``) and reduce by it (the TPU analogue of the reference's
    agg_hash_map.rs one-pass hash table, but with a static-shape range
    table), in the form :func:`_table_form` picks from the static shapes: a
    table of few slots as masked vector reductions and a compaction of the
    table — no sort, no capacity-sized tables, no row-sized scatter; from
    ``_SLOT_SORT_MIN_SLOTS`` slots on by one sort of the slot id and
    :func:`_reduce_ordered` — no scatter at all and nothing of ``slots x
    rows``. Either way the groups come out in ascending slot order.
    With ``sizes`` None the table is wide (past dense_agg_max_buckets, up
    to ``_SLOT_ID_MAX_SLOTS``): slot-sorted, ``bases`` is
    :func:`_wide_table`'s traced description and the id is int64.
    ``bases`` (traced, per key) anchor the ranges so one compiled
    kernel serves every batch of the stream; a key outside its range flips
    the fits flag and the host falls back for that batch. Output arrays are
    ``out_cap``-sized (the compact group bucket), shrinking every downstream
    consumer of the partial batch."""
    nk = len(key_dtypes)
    wide = sizes is None
    if not wide:
        S = math.prod(sizes)
        strides = K.radix_strides(sizes)
    slot_sorted = wide or _is_slot_sorted(sizes, capacity)

    def digit(slot, i):
        return (slot // strides[i]) % sizes[i]

    def group_keys(bases, slot, move, out_valid, digit=digit):
        """The groups' (data, validity) key planes: a key reconstructs
        arithmetically from the slot id (exact for ints; no representative
        row to gather), and ``move`` brings a plane by slot to the groups."""
        planes = []
        for i, kdt in enumerate(key_dtypes):
            code_b = digit(slot, i)
            kdata = (bases[i] + code_b - 1).astype(jnp.dtype(kdt))
            planes.append(jnp.where(out_valid, move(kdata),
                                    jnp.zeros((), jnp.dtype(kdt))))
            planes.append(move(code_b > 0) & out_valid)
        return planes

    def slot_sorted_groups(exists, bases, args, seg, fits, digit=digit):
        iota = jnp.arange(capacity, dtype=jnp.int32)
        with jax.named_scope("order"):
            slot, order = _sort_slot_ids(seg, iota)
        num_groups, out_valid, (slot,), states = _reduce_ordered(
            exists, iota, order, slot, [], [slot],
            [kind for kind, _r, _d in specs],
            [_partial_requests(spec, arg, exists)
             for spec, arg in zip(specs, args)], out_cap)
        with jax.named_scope("emit"):
            keys = group_keys(bases, slot.astype(jnp.int64), lambda x: x,
                              out_valid, digit)
        return (jnp.where(fits, num_groups.astype(jnp.int64), jnp.int64(-1)),
                out_valid, *keys, *states)

    def agg_dense_partial(exists, bases, *flat):
        key_data = [flat[2 * i] for i in range(nk)]
        key_valid = [flat[2 * i + 1] for i in range(nk)]
        args = []
        pos = 2 * nk
        for (kind, _r, _d) in specs:
            if kind in _WIDE_KINDS:
                args.append(((flat[pos], flat[pos + 1], flat[pos + 2]),
                             flat[pos + 3] & exists))
                pos += 4
            else:
                args.append((flat[pos], flat[pos + 1] & exists))
                pos += 2
        # the scopes split the program's device time in a trace
        if wide:  # bases is _wide_table's (3, keys)
            bases, sizes_t, shifts = bases
            with jax.named_scope("pack"):
                seg, fits = K.radix_pack(
                    key_data, key_valid, exists, bases, sizes_t,
                    jnp.int64(1) << shifts, _WIDE_SENTINEL)
            return slot_sorted_groups(
                exists, bases, args, seg, fits,
                lambda slot, i: (slot >> shifts[i]) & (sizes_t[i] - 1))
        with jax.named_scope("pack"):
            seg, fits = K.radix_pack(key_data, key_valid, exists, bases,
                                     sizes, strides)
        if slot_sorted:
            return slot_sorted_groups(exists, bases, args, seg, fits)
        with jax.named_scope("reduce"):
            outs = _reduce_aggs(specs, args, seg, S)
            present = _seg_reduce("any", seg, exists, S)
        with jax.named_scope("emit"):
            num_groups = jnp.sum(present)
            pos = jnp.cumsum(present) - 1
            scat = jnp.where(present, pos, out_cap).astype(jnp.int32)

            def compact(x):
                return jnp.zeros((out_cap,), x.dtype).at[scat].set(
                    x, mode="drop")

            out_valid = jnp.arange(out_cap, dtype=jnp.int32) < num_groups
            results = [jnp.where(fits, num_groups.astype(jnp.int64),
                                 jnp.int64(-1)), out_valid]
            results += group_keys(bases, jnp.arange(S, dtype=jnp.int64),
                                  compact, out_valid)
            for entry in outs:
                for a in entry[1:]:
                    results.append(compact(a))
        return tuple(results)

    return jax.jit(agg_dense_partial)


def _merge_planes(flat, nk: int, state_dtypes):
    """A merge kernel's flat arguments -> (key data, key validity, every
    aggregate's (data, valid) state columns)."""
    states, pos = [], 2 * nk
    for dts in state_dtypes:
        states.append([(flat[pos + 2 * j], flat[pos + 2 * j + 1])
                       for j in range(len(dts))])
        pos += 2 * len(dts)
    return list(flat[0:2 * nk:2]), list(flat[1:2 * nk:2]), states


@functools.lru_cache(maxsize=256)
def _merge_kernel(key_dtypes: Tuple[str, ...], kinds: Tuple[str, ...],
                  state_dtypes: Tuple[Tuple[str, ...], ...], capacity: int):
    """FINAL/PARTIAL_MERGE device kernel: group partial STATE columns by key
    and merge them with each aggregate's merge semantics (round-1 verdict
    weak #4 — the merge stage previously always landed in the host intern
    table). The partial kernel's body (:func:`_aggregate_sorted`) over
    state reductions (:func:`_merge_requests`): sum (sum,has), count (count),
    avg (sum,count), min/max (val,has), the wide kinds their limbs."""
    def agg_merge(exists, *flat):
        key_data, key_valid, states = _merge_planes(flat, len(key_dtypes),
                                                    state_dtypes)
        return _aggregate_sorted(
            exists, key_data, key_valid, kinds,
            [_merge_requests(kind, cols, exists)
             for kind, cols in zip(kinds, states)])

    return jax.jit(agg_merge)


@functools.lru_cache(maxsize=256)
def _slot_merge_kernel(key_dtypes: Tuple[str, ...], kinds: Tuple[str, ...],
                       state_dtypes: Tuple[Tuple[str, ...], ...],
                       capacity: int):
    """FINAL/PARTIAL_MERGE over integer keys whose observed ranges pack into
    one int64 slot id (the partial side's wide table, :func:`_wide_table`'s
    traced description as ``table``): ONE sort of the id
    (:func:`_sort_slot_ids`) in place of ``lex_order_traced``'s ranking of
    every key word, then :func:`_reduce_ordered` over
    :func:`_merge_requests`. The id orders rows as ``_merge_kernel``'s
    order does (per key the null's code 0, then the values ascending, key 0
    most significant; ties in row order), so the outputs are that kernel's
    bit for bit, float sums included, at the same capacity. A key
    reconstructs from its group's id. One program a key type and capacity
    serves every plan; a key outside the table reads ``num_groups`` -1."""
    def agg_merge_sorted(exists, table, *flat):
        key_data, key_valid, states = _merge_planes(flat, len(key_dtypes),
                                                    state_dtypes)
        bases, sizes, shifts = table
        with jax.named_scope("pack"):
            seg, fits = K.radix_pack(key_data, key_valid, exists, bases, sizes,
                                     jnp.int64(1) << shifts, _WIDE_SENTINEL)
        iota = jnp.arange(capacity, dtype=jnp.int32)
        with jax.named_scope("order"):
            slot, order = _sort_slot_ids(seg, iota)
        num_groups, out_valid, (slot,), merged = _reduce_ordered(
            exists, iota, order, slot, [], [slot], kinds,
            [_merge_requests(kind, cols, exists)
             for kind, cols in zip(kinds, states)])
        outs = [jnp.where(fits, num_groups, jnp.int64(-1)), out_valid]
        with jax.named_scope("emit"):
            for i, kdt in enumerate(key_dtypes):
                # past the groups the id reads 0: a null key's code
                code = (slot >> shifts[i]) & (sizes[i] - 1)
                outs += [jnp.where(code > 0, bases[i] + code - 1,
                                   jnp.int64(0)).astype(jnp.dtype(kdt)),
                         code > 0]
        return tuple(outs + merged)

    return jax.jit(agg_merge_sorted)


def supports_device_merge(op, child_schema: T.Schema) -> bool:
    """FINAL / PARTIAL_MERGE hash agg whose keys AND partial state columns
    are device-resident with device-mode aggregate functions."""
    if not op.input_is_partial or not op.groupings:
        return False
    for _, e in op.groupings:
        if not _device_key(e, child_schema):
            return False
    try:
        fns = op._make_fns(child_schema)
    except Exception:
        return False
    pos = len(op.groupings)
    for a, fn in zip(op.aggs, fns):
        if a.agg.fn not in _DEVICE_AGG_FNS or fn.host:
            return False
        for _name, dt in fn.state_fields():
            if not is_device_dtype(dt):
                return False
            if pos >= len(child_schema) or \
                    not is_device_dtype(child_schema[pos].dtype):
                return False
            pos += 1
    return True


class DeviceMergeAgger:
    """Merges partial-state batches on device: concat all input (states are
    small relative to raw rows), run the merge kernel once, emit merged
    state columns (PARTIAL_MERGE) or finalized values (FINAL) via the agg
    functions' own device column builders."""

    _KINDS = {E.AggFunction.SUM: "sum", E.AggFunction.COUNT: "count",
              E.AggFunction.AVG: "avg", E.AggFunction.MIN: "min",
              E.AggFunction.MAX: "max", E.AggFunction.STDDEV_SAMP: "moment"}

    def __init__(self, op, child_schema: T.Schema, conf=None, metrics=None):
        from blaze_tpu.config import get_config

        self.op = op
        self.child_schema = child_schema
        self.conf = conf or get_config()
        self.metrics = metrics
        self.fns = op._make_fns(child_schema)
        from blaze_tpu.ops.agg import _adopt_moments

        _adopt_moments(self.fns, metrics)

        def kind_of(a, fn):
            lm = getattr(fn, "limbs", False)
            if lm == "2":
                return "sum2" if a.agg.fn == E.AggFunction.SUM else "avg2"
            if lm == "3":
                return "sum3" if a.agg.fn == E.AggFunction.SUM else "avg3"
            if lm == "w":
                return "minw" if a.agg.fn == E.AggFunction.MIN else "maxw"
            return self._KINDS[a.agg.fn]

        self.kinds = tuple(kind_of(a, fn)
                           for a, fn in zip(op.aggs, self.fns))

    def run(self, batches: List[ColumnarBatch]):
        op = self.op
        batches = [b for b in batches if b.num_rows]
        if not batches:
            return []
        if "moment" in self.kinds:
            _note_moment_batches(self.metrics, len(batches))
        big = ColumnarBatch.concat(batches, self.child_schema, self.metrics)
        ev = ExprEvaluator([e for _, e in op.groupings], big.schema,
                           self.metrics)
        ev._reset_cse(big)
        exists = big.row_exists_mask()
        flat = []
        key_data, key_valid, key_dicts = _key_planes(ev, op.groupings, big,
                                                     exists)
        for d, v in zip(key_data, key_valid):
            flat += [d, v]
        key_dtypes = [str(d.dtype) for d in key_data]
        if self.metrics is not None and any(
                d is not None for d in key_dicts):
            self.metrics.add("coded_key_batches", 1)
        state_dtypes = []
        pos = len(op.groupings)
        for fn in self.fns:
            dts = []
            for _name, _dt in fn.state_fields():
                col = big.columns[pos]
                flat += [col.data, col.validity]
                dts.append(str(col.data.dtype))
                pos += 1
            state_dtypes.append(tuple(dts))
        outs, num_groups, capacity = self._merge(
            exists, flat, tuple(key_dtypes), tuple(state_dtypes),
            big.capacity)
        if num_groups == 0:
            return []
        out_valid = outs[1]
        cols: List[DeviceColumn] = []
        p = 2
        out_schema = op.schema
        for gi, _ in enumerate(op.groupings):
            cols.append(_key_column(out_schema[gi].dtype, key_dicts[gi],
                                    outs[p], outs[p + 1] & out_valid))
            p += 2
        final = not op.is_partial_output
        for a, fn, kind in zip(op.aggs, self.fns, self.kinds):
            nstate = {"sum": 2, "sum2": 3, "count": 1, "avg": 2, "avg2": 3,
                      "sum3": 4, "avg3": 4, "minw": 4, "maxw": 4,
                      "min": 2, "max": 2, "moment": _MOMENT_PLANES}[kind]
            state = list(outs[p:p + nstate])
            p += nstate
            if final:
                cols.append(fn.final_column(state, num_groups, capacity))
            else:
                cols.extend(fn.state_columns(state, num_groups, capacity))
        return [ColumnarBatch(out_schema, cols, num_groups)]

    def _merge(self, exists, flat, key_dtypes, state_dtypes, capacity):
        """The merge kernel's outputs over the concatenated input's planes,
        its group count and the outputs' capacity: ONE sort of the packed
        key id where the keys' observed ranges give one (:meth:`_slot_plan`,
        ``jit(agg_merge_sorted)``), else the sort path's
        ``lex_order_traced`` (``jit(agg_merge)``)."""
        table = self._slot_plan(flat, exists, key_dtypes, capacity)
        if table is not None:
            outs = _slot_merge_kernel(key_dtypes, self.kinds, state_dtypes,
                                      capacity)(exists, table, *flat)
            num_groups = wait_int(outs[0], "agg_merge")
            if num_groups >= 0:
                if self.metrics is not None:
                    self.metrics.add("merge_slot_sorted_batches", 1)
                DEVICE_STATS.add_merge_slot_sorted()
                return outs, num_groups, capacity
            # probe/pack disagreement (the plan comes from a probe over this
            # very data): the sort path
        kernel = _merge_kernel(key_dtypes, self.kinds, state_dtypes, capacity)
        outs = kernel(exists, *flat)
        return outs, wait_int(outs[0], "agg_merge"), capacity

    def _slot_plan(self, flat, exists, key_dtypes, capacity):
        """The keys' observed ranges as a wide slot table
        (:func:`_wide_table`), probed over the concatenated input (one small
        sync), where every key is a signed integer plane (dictionary codes
        too) and the packed id stays within ``_SLOT_ID_MAX_SLOTS``; else
        None."""
        if not key_dtypes or not all(
                np.issubdtype(np.dtype(dt), np.signedinteger)
                for dt in key_dtypes):
            return None
        probe = self._probe(flat, exists, len(key_dtypes), "agg_merge_probe")
        # a merge has no later batch to anchor a key with no valid row: its
        # null's slot and one beside it
        probe[probe[:, 0] == 0] = (1, 0, 0)
        plan = _plan_slot_table(probe, capacity, None, _SLOT_ID_MAX_SLOTS,
                                self.conf)
        if plan is None:
            return None
        bases, sizes, _out_cap = plan
        return _wide_table(bases, sizes)

    @staticmethod
    def _probe(flat, exists, nk: int, what: str) -> np.ndarray:
        """The keys' (any_valid, min, max) rows over the concatenated input:
        one small sync, ``sync:<what>``."""
        keys = list(zip(flat[0:2 * nk:2], flat[1:2 * nk:2]))
        return np.array(wait_array(_key_ranges_jit(exists, keys), what))


@functools.lru_cache(maxsize=256)
def _passthrough_kernel(key_dtypes: Tuple[str, ...],
                        specs: Tuple[Tuple[str, int, str], ...],
                        arg_dtypes: Tuple[str, ...], capacity: int):
    """Singleton-state kernel for skipped partials: every existing row is
    its own group (seg = iota), so _reduce_aggs degenerates to elementwise
    state construction — keys and states stay in place, no sort, no
    scatter contention, no group-count sync."""
    nk = len(key_dtypes)

    def agg_passthrough(exists, *flat):
        key_data = [flat[2 * i] for i in range(nk)]
        key_valid = [flat[2 * i + 1] for i in range(nk)]
        args = []
        pos = 2 * nk
        for (kind, _r, _d) in specs:
            if kind in _WIDE_KINDS:
                args.append(((flat[pos], flat[pos + 1], flat[pos + 2]),
                             flat[pos + 3] & exists))
                pos += 4
            else:
                args.append((flat[pos], flat[pos + 1] & exists))
                pos += 2
        iota = jnp.arange(capacity, dtype=jnp.int32)
        seg = jnp.where(exists, iota, jnp.int32(capacity))
        outs = _reduce_aggs(specs, args, seg, capacity)
        num_groups = jnp.sum(exists)
        results = [num_groups, exists]
        for d, v in zip(key_data, key_valid):
            results.append(jnp.where(v, d, jnp.zeros((), d.dtype)))
            results.append(v)
        for entry in outs:
            for a in entry[1:]:
                results.append(a)
        return tuple(results)

    return jax.jit(agg_passthrough)


@functools.lru_cache(maxsize=256)
def _partial_kernel(key_dtypes: Tuple[str, ...], specs: Tuple[Tuple[str, int], ...],
                    arg_dtypes: Tuple[str, ...], capacity: int):
    """Build + jit the per-batch sort-path partial kernel for one (schema,
    capacity): :func:`_aggregate_sorted` over :func:`_partial_requests`."""
    nk = len(key_dtypes)

    def agg_partial(exists, *flat):
        key_data = [flat[2 * i] for i in range(nk)]
        key_valid = [flat[2 * i + 1] for i in range(nk)]
        args = []
        pos = 2 * nk
        for (kind, _r, _d) in specs:
            if kind in _WIDE_KINDS:
                args.append(((flat[pos], flat[pos + 1], flat[pos + 2]),
                             flat[pos + 3]))
                pos += 4
            else:
                args.append((flat[pos], flat[pos + 1]))
                pos += 2
        return _aggregate_sorted(
            exists, key_data, key_valid, [kind for kind, _r, _d in specs],
            [_partial_requests(spec, arg, exists)
             for spec, arg in zip(specs, args)])

    return jax.jit(agg_partial)
