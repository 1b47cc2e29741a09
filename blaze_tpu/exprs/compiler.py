"""Expression evaluator: IR expressions -> columnar values over a batch.

The reference evaluates DataFusion ``PhysicalExpr`` trees with a
common-subexpression-caching wrapper (``CachedExprsEvaluator``,
``datafusion-ext-plans/src/common/cached_exprs_evaluator.rs``). Here the
evaluator walks the expression IR per batch:

- subtrees over fixed-width (device) columns evaluate as vectorized jax ops —
  eager XLA dispatch per op, whole-expression ``jax.jit`` fusion for the
  common all-device case via :class:`ExprEvaluator`'s compiled cache;
- subtrees needing var-width (host) columns evaluate with pyarrow compute;
- values move between the two worlds only at explicit boundaries.

Null semantics are Spark's: validity propagates through arithmetic,
comparisons use two-valued logic with null poisoning, AND/OR use Kleene
logic, division/modulo by zero yield NULL (non-ANSI), CASE picks the first
branch whose condition is definitively true.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any, List, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

from blaze_tpu.core.batch import (CodedColumn, Column, ColumnarBatch,
                                  DeviceColumn, HostColumn)
from blaze_tpu.exprs import decimal as dec
from blaze_tpu.ir import exprs as E
from blaze_tpu.ir import types as T


@dataclasses.dataclass
class DevVal:
    """Device value: padded data + validity, plus its logical type."""

    dtype: T.DataType
    data: jax.Array
    validity: jax.Array


@dataclasses.dataclass
class HostVal:
    dtype: T.DataType
    arr: pa.Array


class CodedVal(HostVal):
    """A reference to a coded column (core/batch.CodedColumn): the column
    itself where the expression is only that reference (a projection, a
    ROLLUP's Expand, a grouping key: no pull, no value touched), predicates
    over its dictionary through `_dict_fast`, and for everything else a host
    value over the same dictionary, made when first asked for (one pull of
    the codes, counted as ``host_key_batches`` on ``metrics``, the node the
    evaluator's operator gave it)."""

    def __init__(self, dtype: T.DataType, col: CodedColumn, num_rows: int,
                 metrics=None):
        self.dtype = dtype
        self.col = col
        self.num_rows = num_rows
        self.metrics = metrics
        self._arr = None

    @property
    def arr(self) -> pa.Array:
        if self._arr is None:
            if self.metrics is not None:
                self.metrics.add("host_key_batches", 1)
            self._arr = self.col.to_host(self.num_rows).array
        return self._arr

    def __repr__(self):
        return f"CodedVal({self.dtype}, {self.num_rows} rows)"


Val = Union[DevVal, HostVal]


def reference_index(expr: E.Expr, schema: T.Schema) -> Optional[int]:
    """The position of the column a BARE reference names, else None (an
    expression over columns, a name the schema does not have): where an
    operator asks what kind of column a key or an argument arrives as."""
    if isinstance(expr, E.BoundReference):
        return expr.index
    if isinstance(expr, E.Column) and expr.name in schema.names:
        return schema.index_of(expr.name)
    return None


class ExprError(Exception):
    pass


def _is_device_type(dt: T.DataType) -> bool:
    from blaze_tpu.utils.device import is_device_dtype

    return is_device_dtype(dt)


def _is_float(dt: T.DataType) -> bool:
    return isinstance(dt, (T.Float32Type, T.Float64Type))


class ExprEvaluator:
    """Evaluates a fixed list of expressions against batches of one schema.

    Holds per-partition state (RowNum counter) and caches compiled device
    subgraphs keyed by batch capacity.
    """

    def __init__(self, exprs: List[E.Expr], input_schema: T.Schema,
                 metrics=None):
        self.exprs = exprs
        self.input_schema = input_schema
        # the operator's metric node: where an expression reads a coded
        # column's VALUES the pull is counted there (`CodedVal.arr`)
        self.metrics = metrics
        self.row_num_offset = 0
        # common-subexpression cache, valid for ONE batch only (reference:
        # CachedExprsEvaluator's cached_exprs — shared subtrees evaluate once)
        self._cse: dict = {}
        self._cse_ref = None  # weakref to the batch the cache belongs to
        self._cse_keys: dict = {}
        # device int32 code columns for dictionary-encoded host arrays,
        # valid for ONE batch (shared across this batch's predicates)
        self._dict_codes: dict = {}

    def _reset_cse(self, batch: ColumnarBatch):
        import weakref

        if self._cse_ref is None or self._cse_ref() is not batch:
            self._cse.clear()
            self._dict_codes: dict = {}
            self._cse_ref = weakref.ref(batch)

    # -- dictionary-code predicates -------------------------------------------

    def _dict_fast(self, hv, batch: ColumnarBatch, value_fn):
        """String predicates on dictionary CODES (round-2 verdict item 5,
        reference: the dictionary fast paths of ``spark_strings.rs``): when
        a host value wraps a dictionary-encoded arrow array spanning the
        batch (or the column is coded: its codes are on the device
        already), evaluate the predicate over the K dictionary VALUES once
        (tiny host compute), then map per-row results through the device
        int32 codes — the O(rows) work becomes a device gather instead of a
        host string scan. Returns a BOOL DevVal, or None when not
        applicable. ``value_fn(dictionary) -> arrow bool array`` computes
        the per-dictionary-entry result; its nulls propagate as invalid."""
        if isinstance(hv, CodedVal):
            codes, dictionary = hv.col, hv.col.dictionary
            if batch.num_rows == 0:
                return None
        else:
            orig = getattr(hv, "arr", None)
            if not isinstance(hv, HostVal) or orig is None:
                return None
            arr = orig.combine_chunks() if isinstance(orig, pa.ChunkedArray) \
                else orig
            if not pa.types.is_dictionary(arr.type) or \
                    len(arr) != batch.num_rows or batch.num_rows == 0:
                return None
            dictionary = arr.dictionary
            # keyed by the ORIGINAL array object and identity-checked: id()
            # of a freshly combined temporary could be recycled within the
            # batch and hand back another column's codes. The cached entry
            # holds the array reference, pinning the id.
            entry = self._dict_codes.get(id(orig))
            if entry is not None and entry[0] is orig:
                codes = entry[1]
            else:
                codes = CodedColumn.from_arrow(arr, hv.dtype, batch.capacity)
                self._dict_codes[id(orig)] = (orig, codes)
        K = len(dictionary)
        if K == 0:
            # every row is null: invalid everywhere
            z = jnp.zeros(batch.capacity, bool)
            return DevVal(T.BOOL, z, z)
        res = value_fn(dictionary)
        rd = np.asarray(pc.fill_null(res, False)
                        .to_numpy(zero_copy_only=False)).astype(bool)
        rv = ~np.asarray(pc.is_null(res).to_numpy(zero_copy_only=False))
        cidx = jnp.clip(codes.data, 0, K - 1)
        lk_d = jnp.asarray(rd)
        lk_v = jnp.asarray(rv)
        return DevVal(T.BOOL, lk_d[cidx] & codes.validity,
                      codes.validity & lk_v[cidx])

    # -- public API -----------------------------------------------------------

    def evaluate(self, batch: ColumnarBatch) -> List[Column]:
        self._reset_cse(batch)
        out = []
        for expr in self.exprs:
            val = self._eval(expr, batch)
            out.append(self._to_column(val, batch))
        self.row_num_offset += batch.num_rows
        return out

    def evaluate_predicate(self, batch: ColumnarBatch) -> jax.Array:
        """Conjunction of all exprs as a device keep-mask (null -> drop)."""
        self._reset_cse(batch)
        mask = None
        for expr in self.exprs:
            val = self._eval(expr, batch)
            dv = self._to_dev(val, batch)
            keep = dv.data.astype(bool) & dv.validity
            mask = keep if mask is None else (mask & keep)
        return mask & batch.row_exists_mask()

    def evaluate_traced(self, batch) -> List[DeviceColumn]:
        """``evaluate`` for use inside a fused-stage jit trace: every value
        must stay on the device path (a HostVal is a bug — the whitelist in
        :func:`fusable_expr` admitted something it shouldn't have), and
        nothing may read ``batch.num_rows`` (a traced TraceBatch raises)."""
        self._reset_cse(batch)
        out = []
        for expr in self.exprs:
            val = self._eval(expr, batch)
            if not isinstance(val, DevVal):
                raise ExprError(
                    f"host value escaped into fused trace: {type(expr).__name__}")
            out.append(self._to_column(val, batch))
        return out

    # -- value conversions ----------------------------------------------------

    def _to_column(self, val: Val, batch: ColumnarBatch) -> Column:
        if isinstance(val, DevVal):
            data = val.data
            if data.ndim == 0:  # broadcast scalar literal
                data = jnp.full(batch.capacity, data)
                validity = jnp.broadcast_to(val.validity, (batch.capacity,)) & batch.row_exists_mask()
            else:
                validity = val.validity & batch.row_exists_mask()
            return DeviceColumn(val.dtype, data, validity)
        if isinstance(val, CodedVal):
            return val.col  # the reference itself: codes and dictionary
        arr = val.arr
        if len(arr) != batch.num_rows:  # scalar host literal
            assert len(arr) == 1
            arr = pa.concat_arrays([arr] * batch.num_rows) if batch.num_rows else arr.slice(0, 0)
        return HostColumn(val.dtype, arr)

    def _to_dev(self, val: Val, batch: ColumnarBatch) -> DevVal:
        if isinstance(val, DevVal):
            return val
        if isinstance(val, CodedVal):
            # a coded column's device value is its code plane (a grouping
            # key to the device aggregation: equal codes, equal values)
            return DevVal(T.I32, val.col.data, val.col.validity)
        col = _arrow_to_devcol(val.arr, val.dtype, batch.capacity)
        return DevVal(val.dtype, col.data, col.validity)

    def _to_host(self, val: Val, batch: ColumnarBatch) -> HostVal:
        if isinstance(val, HostVal):
            arr = val.arr
            if pa.types.is_dictionary(arr.type):
                # host kernels (pc.utf8_*, concat, ...) have no dictionary
                # variants: decode at THIS boundary. Fast paths that work
                # on codes (_dict_fast) read val.arr before coming here.
                from blaze_tpu.core.batch import decode_dictionary

                arr = decode_dictionary(arr, val.dtype)
                val = HostVal(val.dtype, arr)
            if len(arr) == 1 and batch.num_rows != 1:  # broadcast host literal
                if arr[0].as_py() is None:
                    arr = pa.nulls(batch.num_rows, arr.type)
                else:
                    arr = pa.array([arr[0].as_py()] * batch.num_rows, arr.type)
                return HostVal(val.dtype, arr)
            return val
        col = DeviceColumn(val.dtype, *_broadcast(val, batch))
        return HostVal(val.dtype, col.to_arrow(batch.num_rows))

    # -- core recursion -------------------------------------------------------

    def _eval(self, expr: E.Expr, batch: ColumnarBatch) -> Val:
        key = self._expr_key(expr)
        if key is not None:
            cached = self._cse.get(key)
            if cached is not None:
                return cached
        method = getattr(self, "_eval_" + type(expr).__name__, None)
        if method is None:
            raise ExprError(f"unsupported expression {type(expr).__name__}")
        out = method(expr, batch)
        if key is not None:
            self._cse[key] = out
        return out

    def _expr_key(self, expr: E.Expr):
        """Structural identity for CSE; trees containing stateful or
        callable-bearing nodes opt out entirely (two distinct lambdas share a
        qualname, and RowNum advances state per evaluation). Cached per expr
        object (id) since IR trees are immutable."""
        if isinstance(expr, (E.Column, E.BoundReference, E.Literal)):
            return None  # trivial — not worth caching
        key = self._cse_keys.get(id(expr))
        if key is None:
            if _contains_stateful(expr):
                key = False
            else:
                try:
                    from blaze_tpu.ir.serde import expr_to_json

                    key = expr_to_json(expr)
                except Exception:
                    key = False
            self._cse_keys[id(expr)] = key
        return key or None

    def _eval_Column(self, expr: E.Column, batch: ColumnarBatch) -> Val:
        idx = batch.schema.index_of(expr.name)
        return self._eval_BoundReference(E.BoundReference(idx), batch)

    def _eval_BoundReference(self, expr: E.BoundReference, batch: ColumnarBatch) -> Val:
        col = batch.columns[expr.index]
        dt = batch.schema[expr.index].dtype
        if isinstance(col, DeviceColumn):
            if T.is_wide_decimal(dt):
                # a wide decimal that a window left as a proved int64 plane
                # (ops/window_device.py): expressions read the type's form,
                # decimal128 on the host, exact at every digit
                return HostVal(dt, col.to_arrow(batch.num_rows))
            return DevVal(dt, col.data, col.validity)
        if isinstance(col, CodedColumn):
            return CodedVal(dt, col, batch.num_rows, self.metrics)
        return HostVal(dt, col.array)

    def _eval_Literal(self, expr: E.Literal, batch: ColumnarBatch) -> Val:
        return make_literal(expr.value, expr.dtype)

    def _eval_ScalarSubquery(self, expr: E.ScalarSubquery, batch) -> Val:
        return make_literal(expr.value, expr.dtype)

    def _eval_BinaryExpr(self, expr: E.BinaryExpr, batch: ColumnarBatch) -> Val:
        op = expr.op
        lval = self._eval(expr.left, batch)
        rval = self._eval(expr.right, batch)
        if isinstance(lval, HostVal) or isinstance(rval, HostVal):
            if _is_device_type(lval.dtype) and _is_device_type(rval.dtype):
                lval, rval = self._to_dev(lval, batch), self._to_dev(rval, batch)
            else:
                out = self._binary_dict_fast(op, lval, rval, batch)
                if out is not None:
                    return out
                return self._binary_host(op, lval, rval, batch, expr)
        return self._binary_dev(op, expr, lval, rval)

    def _binary_dict_fast(self, op: E.BinaryOp, lval, rval,
                          batch: ColumnarBatch) -> Optional["DevVal"]:
        """column-vs-literal comparison where the column is dictionary
        encoded: compare the K dictionary values once, gather by code."""
        B = E.BinaryOp
        fns = {
            B.EQ: pc.equal, B.NEQ: pc.not_equal, B.LT: pc.less,
            B.LTEQ: pc.less_equal, B.GT: pc.greater, B.GTEQ: pc.greater_equal,
        }
        if op not in fns:
            return None
        flipped = {B.EQ: B.EQ, B.NEQ: B.NEQ, B.LT: B.GT, B.LTEQ: B.GTEQ,
                   B.GT: B.LT, B.GTEQ: B.LTEQ}

        def scalar_of(v):
            if isinstance(v, CodedVal):
                return None  # a column, and asking for `.arr` would pull it
            if isinstance(v, HostVal) and len(v.arr) == 1:
                return v.arr[0]
            if isinstance(v, DevVal) and v.data.ndim == 0:
                return pa.scalar(v.data.item() if bool(v.validity) else None)
            return None

        for col, lit, use_op in ((lval, rval, op),
                                 (rval, lval, flipped[op])):
            s = scalar_of(lit)
            if s is None:
                continue
            out = self._dict_fast(col, batch,
                                  lambda d, _f=fns[use_op], _s=s: _f(d, _s))
            if out is not None:
                return out
        return None

    def _binary_dev(self, op: E.BinaryOp, expr: E.BinaryExpr, l: DevVal, r: DevVal) -> DevVal:
        B = E.BinaryOp
        if op in (B.AND, B.OR):
            lv, ld = l.validity, l.data.astype(bool)
            rv, rd = r.validity, r.data.astype(bool)
            if op == B.AND:
                dfalse = (lv & ~ld) | (rv & ~rd)
                dtrue = lv & ld & rv & rd
            else:
                dtrue = (lv & ld) | (rv & rd)
                dfalse = lv & ~ld & rv & ~rd
            return DevVal(T.BOOL, dtrue, dtrue | dfalse)

        ldt, rdt = l.dtype, r.dtype
        if op in (B.EQ, B.NEQ, B.LT, B.LTEQ, B.GT, B.GTEQ):
            ld, rd = self._numeric_align(l, r)
            fn = {
                B.EQ: jnp.equal, B.NEQ: jnp.not_equal, B.LT: jnp.less,
                B.LTEQ: jnp.less_equal, B.GT: jnp.greater, B.GTEQ: jnp.greater_equal,
            }[op]
            return DevVal(T.BOOL, fn(ld, rd), l.validity & r.validity)

        # arithmetic
        res_t = expr.result_type or E.infer_type(
            E.BinaryExpr(op, E.Literal(None, ldt), E.Literal(None, rdt)), T.Schema(())
        )
        validity = l.validity & r.validity
        if isinstance(res_t, T.DecimalType):
            if _is_float(ldt) or _is_float(rdt):
                # float operand: compute in f64, rescale into the result type
                out = _float_op(op, self._decimal_to_f64(l), self._decimal_to_f64(r))
                scaled = out * float(10**res_t.scale)
                rounded = jnp.where(scaled >= 0, jnp.floor(scaled + 0.5), jnp.ceil(scaled - 0.5))
                ok = jnp.isfinite(scaled) & (jnp.abs(rounded) < float(2**62))
                data = jnp.where(ok, rounded, 0.0).astype(jnp.int64)
                data, validity = dec.check_overflow(data, validity & ok, res_t.precision)
                return DevVal(res_t, data, validity)
            l = self._coerce_decimal(l)
            r = self._coerce_decimal(r)
            return self._decimal_arith(op, l, r, res_t)
        ld, rd = self._numeric_align(l, r, res_t)
        if op == B.ADD:
            out = ld + rd
        elif op == B.SUB:
            out = ld - rd
        elif op == B.MUL:
            out = ld * rd
        elif op == B.DIV:
            zero = rd == 0
            validity = validity & ~zero
            den = jnp.where(zero, jnp.ones((), rd.dtype), rd)
            if jnp.issubdtype(ld.dtype, jnp.integer):
                out = _java_int_div(ld, den)
            else:
                out = ld / den
        elif op == B.MOD:
            zero = rd == 0
            validity = validity & ~zero
            den = jnp.where(zero, jnp.ones((), rd.dtype), rd)
            if jnp.issubdtype(ld.dtype, jnp.integer):
                q = _java_int_div(ld, den)
                out = ld - q * den
            else:
                out = jnp.where(den != 0, ld - jnp.trunc(ld / den) * den, jnp.zeros((), ld.dtype))
        elif op == B.BIT_AND:
            out = ld & rd
        elif op == B.BIT_OR:
            out = ld | rd
        elif op == B.BIT_XOR:
            out = ld ^ rd
        elif op == B.SHIFT_LEFT:
            out = ld << (rd % jnp.array(ld.dtype.itemsize * 8, rd.dtype))
        elif op == B.SHIFT_RIGHT:
            out = ld >> (rd % jnp.array(ld.dtype.itemsize * 8, rd.dtype))
        else:
            raise ExprError(f"unsupported device binary op {op}")
        return DevVal(res_t, out, validity)

    def _decimal_arith(self, op: E.BinaryOp, l: DevVal, r: DevVal, res_t: T.DecimalType) -> DevVal:
        B = E.BinaryOp
        ls, rs = l.dtype.scale, r.dtype.scale
        if op in (B.ADD, B.SUB):
            s = max(ls, rs)
            ld, lv = dec.rescale(l.data, l.validity, ls, s, 19)
            rd, rv = dec.rescale(r.data, r.validity, rs, s, 19)
            fn = dec.add if op == B.ADD else dec.sub
            out, validity = fn(ld, lv, rd, rv)
            out, validity = dec.rescale(out, validity, s, res_t.scale, res_t.precision)
        elif op == B.MUL:
            rescale_down = ls + rs - res_t.scale
            out, validity = dec.mul(l.data, l.validity, r.data, r.validity,
                                    rescale_down=max(rescale_down, 0))
            out, validity = dec.check_overflow(out, validity, res_t.precision)
        elif op == B.DIV:
            scale_adjust = res_t.scale - ls + rs
            out, validity = dec.div(l.data, l.validity, r.data, r.validity, scale_adjust)
            out, validity = dec.check_overflow(out, validity, res_t.precision)
        elif op == B.MOD:
            s = max(ls, rs)
            ld, lv = dec.rescale(l.data, l.validity, ls, s, 19)
            rd, rv = dec.rescale(r.data, r.validity, rs, s, 19)
            zero = rd == 0
            den = jnp.where(zero, 1, rd)
            q = _java_int_div(ld, den)
            out = ld - q * den
            validity = lv & rv & ~zero
            out, validity = dec.rescale(out, validity, s, res_t.scale, res_t.precision)
        else:
            raise ExprError(f"unsupported decimal op {op}")
        return DevVal(res_t, out, validity)

    @staticmethod
    def _coerce_decimal(v: DevVal) -> DevVal:
        """Treat an integer operand as decimal(_,0) for decimal arithmetic."""
        if isinstance(v.dtype, T.DecimalType):
            return v
        return DevVal(T.DecimalType(18, 0), v.data.astype(jnp.int64), v.validity)

    def _numeric_align(self, l: DevVal, r: DevVal, res_t: Optional[T.DataType] = None):
        """Promote both sides to a common jnp dtype (decimals: align scales)."""
        if isinstance(l.dtype, T.DecimalType) and isinstance(r.dtype, T.DecimalType):
            s = max(l.dtype.scale, r.dtype.scale)
            ld, _ = dec.rescale(l.data, l.validity, l.dtype.scale, s, 19)
            rd, _ = dec.rescale(r.data, r.validity, r.dtype.scale, s, 19)
            return ld, rd
        if isinstance(l.dtype, T.DecimalType) or isinstance(r.dtype, T.DecimalType):
            # decimal vs float/int comparison: go through float64
            ld = self._decimal_to_f64(l)
            rd = self._decimal_to_f64(r)
            return ld, rd
        target = None
        if res_t is not None and res_t.np_dtype is not None:
            target = jnp.dtype(res_t.np_dtype)
        else:
            target = jnp.promote_types(l.data.dtype, r.data.dtype)
        return l.data.astype(target), r.data.astype(target)

    @staticmethod
    def _decimal_to_f64(v: DevVal):
        if isinstance(v.dtype, T.DecimalType):
            return v.data.astype(jnp.float64) / float(10 ** v.dtype.scale)
        return v.data.astype(jnp.float64)

    def _binary_host(self, op: E.BinaryOp, l: Val, r: Val,
                 batch: ColumnarBatch,
                 expr: Optional[E.BinaryExpr] = None) -> Val:
        B = E.BinaryOp
        la = self._to_host(l, batch).arr
        ra = self._to_host(r, batch).arr
        fns = {
            B.EQ: pc.equal, B.NEQ: pc.not_equal, B.LT: pc.less, B.LTEQ: pc.less_equal,
            B.GT: pc.greater, B.GTEQ: pc.greater_equal,
        }
        if op in fns:
            return HostVal(T.BOOL, fns[op](la, ra))
        if op == B.AND:
            return HostVal(T.BOOL, pc.and_kleene(la, ra))
        if op == B.OR:
            return HostVal(T.BOOL, pc.or_kleene(la, ra))
        if op == B.ADD and pa.types.is_large_string(la.type):
            return HostVal(T.STRING, pc.binary_join_element_wise(la, ra, pa.scalar("", type=pa.large_utf8())))
        if pa.types.is_floating(la.type) or pa.types.is_floating(ra.type):
            # exact f64 arithmetic on host (TPU demotes device f64 to f32)
            lv = la.fill_null(0).to_numpy(zero_copy_only=False).astype(np.float64)
            rv = ra.fill_null(0).to_numpy(zero_copy_only=False).astype(np.float64)
            valid = (~np.asarray(pc.is_null(la))) & (~np.asarray(pc.is_null(ra)))
            with np.errstate(all="ignore"):
                if op == B.ADD:
                    out = lv + rv
                elif op == B.SUB:
                    out = lv - rv
                elif op == B.MUL:
                    out = lv * rv
                elif op == B.DIV:
                    valid = valid & (rv != 0)
                    out = lv / np.where(rv == 0, 1.0, rv)
                elif op == B.MOD:
                    valid = valid & (rv != 0)
                    den = np.where(rv == 0, 1.0, rv)
                    out = lv - np.trunc(lv / den) * den
                else:
                    raise ExprError(f"unsupported host float op {op}")
            res_t = T.F64
            return HostVal(res_t, pa.Array.from_pandas(out, mask=~valid,
                                                       type=pa.float64()))
        if pa.types.is_decimal(la.type) or pa.types.is_decimal(ra.type):
            return self._decimal_host_arith(op, l, r, la, ra, expr)
        raise ExprError(f"unsupported host binary op {op} on {la.type}")

    def _decimal_host_arith(self, op: E.BinaryOp, l: Val, r: Val,
                            la: pa.Array, ra: pa.Array,
                            expr: Optional[E.BinaryExpr] = None) -> HostVal:
        """Exact python-Decimal arithmetic for WIDE decimal operands (a
        wide window/agg output dividing a device decimal lands here, e.g.
        TPC-DS q98's revenue ratio). Result type follows the engine's
        decimal promotion rules (E.infer_type); division rounds HALF_UP at
        the result scale and overflow nulls (Spark non-ANSI)."""
        import decimal as _d

        B = E.BinaryOp
        if op not in (B.ADD, B.SUB, B.MUL, B.DIV, B.MOD):
            raise ExprError(f"unsupported host decimal op {op}")
        # the PLAN's declared result type is authoritative (exact Spark
        # promotion comes from the converter); inference is the fallback
        # for hand-built plans — mirroring _binary_dev
        res_t = (expr.result_type if expr is not None and
                 expr.result_type is not None else None) or E.infer_type(
            E.BinaryExpr(op, E.Literal(None, l.dtype), E.Literal(None, r.dtype)),
            T.Schema(()))
        if not isinstance(res_t, T.DecimalType):
            raise ExprError(f"host decimal op {op} inferred {res_t}")
        lv = la.to_pylist()
        rv = ra.to_pylist()
        q = _d.Decimal(1).scaleb(-res_t.scale)
        bound = _d.Decimal(10) ** (res_t.precision - res_t.scale)
        out = []
        with _d.localcontext() as ctx:
            ctx.prec = 80
            for x, y in zip(lv, rv):
                if x is None or y is None:
                    out.append(None)
                    continue
                x, y = _d.Decimal(x), _d.Decimal(y)
                if op == B.ADD:
                    v = x + y
                elif op == B.SUB:
                    v = x - y
                elif op == B.MUL:
                    v = x * y
                elif op == B.DIV:
                    if y == 0:
                        out.append(None)
                        continue
                    v = x / y
                else:  # MOD (Java truncating-division remainder)
                    if y == 0:
                        out.append(None)
                        continue
                    v = x - (x / y).to_integral_value(
                        rounding=_d.ROUND_DOWN) * y
                v = v.quantize(q, rounding=_d.ROUND_HALF_UP)
                out.append(v if abs(v) < bound else None)
        return HostVal(res_t, pa.array(out, type=T.to_arrow_type(res_t)))

    # -- unary / predicates ---------------------------------------------------

    def _eval_IsNull(self, expr: E.IsNull, batch) -> Val:
        v = self._eval(expr.child, batch)
        if isinstance(v, DevVal):
            validity = _broadcast(v, batch)[1]
            return DevVal(T.BOOL, ~validity, jnp.ones(batch.capacity, bool))
        return HostVal(T.BOOL, pc.is_null(v.arr))

    def _eval_IsNotNull(self, expr: E.IsNotNull, batch) -> Val:
        v = self._eval(expr.child, batch)
        if isinstance(v, DevVal):
            validity = _broadcast(v, batch)[1]
            return DevVal(T.BOOL, validity, jnp.ones(batch.capacity, bool))
        return HostVal(T.BOOL, pc.is_valid(v.arr))

    def _eval_Not(self, expr: E.Not, batch) -> Val:
        v = self._eval(expr.child, batch)
        if isinstance(v, DevVal):
            return DevVal(T.BOOL, ~v.data.astype(bool), v.validity)
        return HostVal(T.BOOL, pc.invert(v.arr))

    def _eval_Case(self, expr: E.Case, batch) -> Val:
        # evaluate all branches, select first definitively-true condition
        taken = jnp.zeros(batch.capacity, dtype=bool)
        out_data = None
        out_valid = None
        res_dtype = None
        host_mode = False
        vals = []
        conds = []
        for cond_e, val_e in expr.branches:
            conds.append(self._eval(cond_e, batch))
            vals.append(self._eval(val_e, batch))
        else_v = self._eval(expr.else_expr, batch) if expr.else_expr is not None else None
        host_mode = any(isinstance(v, HostVal) and not _is_device_type(v.dtype) for v in vals) or (
            else_v is not None and isinstance(else_v, HostVal) and not _is_device_type(else_v.dtype)
        )
        if host_mode:
            return self._case_host(conds, vals, else_v, batch)
        for cv, vv in zip(conds, vals):
            cdev = self._to_dev(cv, batch)
            vdev = self._to_dev(vv, batch)
            cmask = cdev.data.astype(bool) & cdev.validity & ~taken
            vdata, vvalid = _broadcast(vdev, batch)
            if out_data is None:
                res_dtype = vdev.dtype
                out_data = jnp.where(cmask, vdata, jnp.zeros((), vdata.dtype))
                out_valid = cmask & vvalid
            else:
                out_data = jnp.where(cmask, vdata.astype(out_data.dtype), out_data)
                out_valid = jnp.where(cmask, vvalid, out_valid)
            taken = taken | cmask
        if else_v is not None:
            edev = self._to_dev(else_v, batch)
            edata, evalid = _broadcast(edev, batch)
            out_data = jnp.where(taken, out_data, edata.astype(out_data.dtype))
            out_valid = jnp.where(taken, out_valid, evalid)
        else:
            out_valid = out_valid & taken
        return DevVal(res_dtype, out_data, out_valid)

    def _case_host(self, conds, vals, else_v, batch) -> HostVal:
        n = batch.num_rows
        taken = np.zeros(n, dtype=bool)
        res_dtype = vals[0].dtype
        out = [None] * n
        for cv, vv in zip(conds, vals):
            ca = self._to_host(cv, batch).arr
            va = self._to_host(vv, batch).arr
            cnp = np.asarray(ca.fill_null(False).to_numpy(zero_copy_only=False)).astype(bool)
            sel = cnp & ~taken
            va_py = va.to_pylist()
            for i in np.nonzero(sel)[0]:
                out[i] = va_py[i]
            taken |= sel
        if else_v is not None:
            ea = self._to_host(else_v, batch).arr.to_pylist()
            for i in np.nonzero(~taken)[0]:
                out[i] = ea[i]
        return HostVal(res_dtype, pa.array(out, type=T.to_arrow_type(res_dtype)))

    def _eval_InList(self, expr: E.InList, batch) -> Val:
        v = self._eval(expr.child, batch)
        values = [self._eval(x, batch) for x in expr.values]
        if isinstance(v, DevVal) and all(isinstance(x, DevVal) for x in values):
            eq_any = jnp.zeros(batch.capacity, dtype=bool)
            # a NULL scalar item makes every non-match NULL; kept as a device
            # scalar so the same code traces inside a fused closure (where a
            # literal's validity is a tracer, not a python bool)
            null_item = jnp.zeros((), dtype=bool)
            for x in values:
                if x.data.ndim == 0:
                    null_item = null_item | ~x.validity
                xd, xv = _broadcast(x, batch)
                ld, rd = self._numeric_align(v, DevVal(x.dtype, xd, xv))
                eq_any = eq_any | (jnp.equal(ld, rd) & xv)
            data = eq_any
            validity = v.validity & (eq_any | ~null_item)
            if expr.negated:
                data = ~data
            return DevVal(T.BOOL, data, validity)
        has_null_item = any(
            (isinstance(x, DevVal) and x.data.ndim == 0 and not bool(x.validity)) or
            (isinstance(x, HostVal) and len(x.arr) == 1 and x.arr[0].as_py() is None)
            for x in values
        )
        # dictionary-code path: is_in over the K dictionary values, gathered
        # by device code (null-item semantics folded into the value result)
        if isinstance(v, HostVal):
            pylist0 = [self._host_scalar(x) for x in values]

            def in_values(d, _vals=pylist0, _neg=expr.negated,
                          _hn=has_null_item):
                vset = pa.array([p for p in _vals if p is not None],
                                type=d.type if not pa.types.is_dictionary(
                                    d.type) else d.type.value_type)
                data = pc.is_in(d, value_set=vset)
                dn = np.asarray(data.to_numpy(zero_copy_only=False)).astype(bool)
                # null list item: misses become NULL, hits stay true
                validity = dn | (not _hn)
                out = np.where(validity, dn ^ _neg, False)
                return pa.array(out, type=pa.bool_(),
                                mask=~np.asarray(validity, bool))

            out = self._dict_fast(v, batch, in_values)
            if out is not None:
                return out
        # host path
        va = self._to_host(v, batch).arr
        pylist = [self._host_scalar(x) for x in values]
        vset = pa.array([p for p in pylist if p is not None], type=va.type)
        isin = pc.is_in(va, value_set=vset)
        data = np.asarray(isin.to_numpy(zero_copy_only=False)).astype(bool)
        valid = ~np.asarray(pc.is_null(va).to_numpy(zero_copy_only=False)).astype(bool)
        validity = valid & (data | (not has_null_item))
        if expr.negated:
            data = ~data
        return HostVal(T.BOOL, pa.Array.from_pandas(
            np.where(validity, data, False), mask=np.asarray(~validity), type=pa.bool_()))

    def _host_scalar(self, v: Val):
        if isinstance(v, HostVal):
            assert len(v.arr) == 1
            return v.arr[0].as_py()
        assert v.data.ndim == 0
        return v.data.item() if bool(v.validity) else None

    # -- casts ----------------------------------------------------------------

    def _eval_Cast(self, expr: E.Cast, batch) -> Val:
        v = self._eval(expr.child, batch)
        return self._cast(v, expr.dtype, batch, try_mode=False)

    def _eval_TryCast(self, expr: E.TryCast, batch) -> Val:
        v = self._eval(expr.child, batch)
        return self._cast(v, expr.dtype, batch, try_mode=True)

    def _cast(self, v: Val, to: T.DataType, batch: ColumnarBatch, try_mode: bool) -> Val:
        from blaze_tpu.exprs.cast import cast_dev, cast_host

        if v.dtype == to:
            return v
        if isinstance(v, DevVal) and _is_device_type(to) and _is_device_type(v.dtype):
            data, validity = cast_dev(v.data, v.validity, v.dtype, to)
            return DevVal(to, data, validity)
        hv = self._to_host(v, batch)
        return HostVal(to, cast_host(hv.arr, hv.dtype, to, try_mode))

    # -- strings (host fast paths) --------------------------------------------

    def _string_match(self, expr_child, batch, match_fn) -> Val:
        """Shared by startswith/endswith/contains/like: dictionary-code
        gather when the child is dictionary encoded, host scan otherwise."""
        v = self._eval(expr_child, batch)
        out = self._dict_fast(v, batch, match_fn)
        if out is not None:
            return out
        return HostVal(T.BOOL, match_fn(self._to_host(v, batch).arr))

    def _eval_StringStartsWith(self, expr, batch) -> Val:
        return self._string_match(
            expr.child, batch,
            lambda a, _p=expr.prefix: pc.starts_with(a, pattern=_p))

    def _eval_StringEndsWith(self, expr, batch) -> Val:
        return self._string_match(
            expr.child, batch,
            lambda a, _s=expr.suffix: pc.ends_with(a, pattern=_s))

    def _eval_StringContains(self, expr, batch) -> Val:
        return self._string_match(
            expr.child, batch,
            lambda a, _i=expr.infix: pc.match_substring(a, pattern=_i))

    def _eval_Like(self, expr: E.Like, batch) -> Val:
        if expr.escape_char not in ("\\", ""):
            # translate custom escape to \ for arrow's SQL LIKE
            pat = re.sub(re.escape(expr.escape_char) + r"(.)", r"\\\1", expr.pattern)
        else:
            pat = expr.pattern

        def like(a, _p=pat, _i=expr.case_insensitive, _n=expr.negated):
            out = pc.match_like(a, pattern=_p, ignore_case=_i)
            return pc.invert(out) if _n else out

        return self._string_match(expr.child, batch, like)

    # -- misc -----------------------------------------------------------------

    def _eval_RowNum(self, expr, batch) -> Val:
        data = jnp.arange(batch.capacity, dtype=jnp.int64) + self.row_num_offset
        return DevVal(T.I64, data, batch.row_exists_mask())

    def _eval_NamedStruct(self, expr: E.NamedStruct, batch) -> Val:
        dtype = expr.dtype or E.infer_type(expr, batch.schema)
        arrays = []
        for name, e in zip(expr.names, expr.exprs):
            col = self._to_column(self._eval(e, batch), batch)
            arrays.append(col.to_arrow(batch.num_rows))
        st = pa.StructArray.from_arrays(arrays, names=list(expr.names))
        return HostVal(dtype, st)

    def _eval_GetIndexedField(self, expr: E.GetIndexedField, batch) -> Val:
        child = self._to_host(self._eval(expr.child, batch), batch)
        assert isinstance(expr.ordinal, E.Literal)
        ord_v = expr.ordinal.value
        if isinstance(child.dtype, T.StructType):
            field = child.dtype.fields[ord_v]
            return HostVal(field.dtype, pc.struct_field(child.arr, indices=[ord_v]))
        # array element (spark 1-based converted to 0-based by the frontend)
        out = pc.list_element(child.arr, ord_v)
        return HostVal(child.dtype.element_type, out)

    def _eval_GetMapValue(self, expr: E.GetMapValue, batch) -> Val:
        child = self._to_host(self._eval(expr.child, batch), batch)
        key = self._host_scalar(self._eval(expr.key, batch))
        vt = child.dtype.value_type
        out = []
        for row in child.arr.to_pylist():
            if row is None:
                out.append(None)
            else:
                d = dict(row) if not isinstance(row, dict) else row
                out.append(d.get(key))
        return HostVal(vt, pa.array(out, type=T.to_arrow_type(vt)))

    def _eval_ScalarFunction(self, expr: E.ScalarFunction, batch) -> Val:
        from blaze_tpu.exprs.functions import dispatch_function

        args = [self._eval(a, batch) for a in expr.args]
        return dispatch_function(expr.name, args, self, batch)

    def _eval_PyUDF(self, expr: E.PyUDF, batch) -> Val:
        args = [self._to_host(self._eval(a, batch), batch).arr for a in expr.args]
        out = expr.fn(*args)
        if not isinstance(out, pa.Array):
            out = pa.array(out, type=T.to_arrow_type(expr.return_type))
        return HostVal(expr.return_type, out)

    def _eval_BloomFilterMightContain(self, expr, batch) -> Val:
        from blaze_tpu.ops.bloom import SparkBloomFilter

        blob = self._host_scalar(self._eval(expr.bloom_filter, batch))
        if blob is None:
            return make_literal(None, T.BOOL)
        bf = SparkBloomFilter.deserialize(blob)
        v = self._eval(expr.value, batch)
        dv = self._to_dev(v, batch)
        hit = bf.might_contain_long(dv.data)
        return DevVal(T.BOOL, hit, dv.validity)

    def _eval_SortOrder(self, expr: E.SortOrder, batch) -> Val:
        return self._eval(expr.child, batch)


def _contains_stateful(expr: E.Expr) -> bool:
    if isinstance(expr, (E.RowNum, E.PyUDF)):
        return True
    return any(_contains_stateful(c) for c in expr.children())


def _broadcast(v: DevVal, batch: ColumnarBatch):
    """Broadcast scalar DevVals to batch capacity."""
    data, validity = v.data, v.validity
    if data.ndim == 0:
        data = jnp.full(batch.capacity, data)
    if validity.ndim == 0:
        validity = jnp.broadcast_to(validity, (batch.capacity,))
    return data, validity


def _float_op(op: E.BinaryOp, ld, rd):
    B = E.BinaryOp
    if op == B.ADD:
        return ld + rd
    if op == B.SUB:
        return ld - rd
    if op == B.MUL:
        return ld * rd
    if op == B.DIV:
        return jnp.where(rd == 0, jnp.nan, ld / jnp.where(rd == 0, 1.0, rd))
    if op == B.MOD:
        return jnp.where(rd == 0, jnp.nan, ld - jnp.trunc(ld / jnp.where(rd == 0, 1.0, rd)) * rd)
    raise ExprError(f"unsupported float/decimal op {op}")


def _java_int_div(a, b):
    """Java-style truncating integer division (jnp // floors)."""
    q = a // b
    r = a - q * b
    adjust = (r != 0) & ((a < 0) != (b < 0))
    return jnp.where(adjust, q + 1, q)


def _arrow_to_devcol(arr: pa.Array, dt: T.DataType, capacity: int) -> DeviceColumn:
    from blaze_tpu.core.batch import _arrow_to_column

    col = _arrow_to_column(arr, dt, capacity)
    assert isinstance(col, DeviceColumn)
    return col


# Device scalars for literals, keyed by (value, dtype repr, default device).
# Without this every evaluation of every literal re-staged a fresh host
# scalar onto the device per batch: a host->device hop per constant per
# batch. DevVals are immutable so sharing one array across expressions and
# batches is safe.
_LITERAL_CACHE: dict = {}
_LITERAL_CACHE_MAX = 4096


def make_literal(value: Any, dtype: T.DataType) -> Val:
    """Build a scalar Val for a python literal value."""
    if _is_device_type(dtype):
        try:
            key = (value, repr(dtype), jax.config.jax_default_device)
            cached = _LITERAL_CACHE.get(key)
        except TypeError:  # unhashable literal value
            key = cached = None
        if cached is not None:
            return cached
        npdt = dtype.np_dtype
        if value is None:
            out = DevVal(dtype, jnp.zeros((), npdt), jnp.zeros((), bool))
        else:
            v = value
            if isinstance(dtype, T.DecimalType):
                from decimal import Decimal

                v = int(Decimal(str(value)).scaleb(dtype.scale).to_integral_value())
            elif isinstance(dtype, T.TimestampType) and not isinstance(value, (int, np.integer)):
                v = int(pa.scalar(value, type=pa.timestamp("us")).value)
            elif isinstance(dtype, T.DateType) and not isinstance(value, (int, np.integer)):
                v = int(pa.scalar(value, type=pa.date32()).value)
            out = DevVal(dtype, jnp.array(v, npdt), jnp.ones((), bool))
        # never cache a value built while some enclosing jit is tracing
        # (device-agg probes, fused closures): jnp "constants" are staged as
        # tracers there, and a tracer in a global cache poisons every later
        # eager evaluation (UnexpectedTracerError)
        if key is not None and len(_LITERAL_CACHE) < _LITERAL_CACHE_MAX \
                and not isinstance(out.data, jax.core.Tracer):
            _LITERAL_CACHE[key] = out
        return out
    at = T.to_arrow_type(dtype)
    return HostVal(dtype, pa.array([value], type=at))


# -- whole-stage fusion: traceable closures over operator chains --------------
#
# The fused-stage operator (ops/fused.py) evaluates a project/filter/rename/
# expand chain inside ONE jax.jit trace. The evaluator above already keeps
# the all-fixed-width path in pure jnp (DevVal in, DevVal out), so tracing is
# a matter of (a) admitting only expressions that provably stay on that path
# (fusable_expr), and (b) feeding _eval a batch stand-in whose columns hold
# tracers and whose row-exists mask is the chain's running live mask
# (TraceBatch). Filters do NOT compact mid-chain: they narrow the live mask,
# and each output group compacts once at the end with the same stable
# argsort-gather as kernels._compact — elementwise expressions commute with
# stable compaction, so results are identical to the unfused operators.


class TraceBatch:
    """Duck-typed ColumnarBatch stand-in used inside a fused jit trace:
    static schema + capacity, DeviceColumns holding tracers, and a traced
    row-exists mask. ``num_rows`` raises so any host-path leak surfaces as a
    loud fallback instead of a silent wrong answer."""

    def __init__(self, schema: T.Schema, columns: List[DeviceColumn],
                 capacity: int, exists: jax.Array):
        self.schema = schema
        self.columns = columns
        self.capacity = capacity
        self._exists = exists

    def row_exists_mask(self) -> jax.Array:
        return self._exists

    @property
    def num_rows(self):
        raise ExprError("num_rows is not defined inside a fused trace")


def fusable_expr(expr: E.Expr, schema: T.Schema) -> bool:
    """True when ``expr`` evaluates entirely on the device (pure-jnp) path
    for batches of ``schema``, i.e. it is safe to trace inside a fused
    stage. Host-path expressions (strings, structs, UDFs, stateful RowNum,
    bloom probes, scalar functions) are rejected; so is anything whose
    result type cannot live on device."""
    try:
        return _fusable(expr, schema) and _is_device_type(E.infer_type(expr, schema))
    except Exception:
        return False


def _fusable(expr: E.Expr, schema: T.Schema) -> bool:
    if isinstance(expr, E.BoundReference):
        return _is_device_type(schema[expr.index].dtype)
    if isinstance(expr, E.Column):
        return _is_device_type(schema[schema.index_of(expr.name)].dtype)
    if isinstance(expr, (E.Literal, E.ScalarSubquery)):
        return _is_device_type(expr.dtype)
    if isinstance(expr, E.BinaryExpr):
        return _fusable(expr.left, schema) and _fusable(expr.right, schema)
    if isinstance(expr, (E.Not, E.IsNull, E.IsNotNull)):
        return _fusable(expr.child, schema)
    if isinstance(expr, E.Case):
        parts = [p for branch in expr.branches for p in branch]
        if expr.else_expr is not None:
            parts.append(expr.else_expr)
        return all(_fusable(p, schema) for p in parts)
    if isinstance(expr, E.InList):
        return _fusable(expr.child, schema) and \
            all(_fusable(v, schema) for v in expr.values)
    if isinstance(expr, (E.Cast, E.TryCast)):
        # cast_dev needs device source AND target dtypes
        return _fusable(expr.child, schema) and _is_device_type(expr.dtype) \
            and _is_device_type(E.infer_type(expr.child, schema))
    if isinstance(expr, E.SortOrder):
        return _fusable(expr.child, schema)
    return False


def fused_chain_schemas(input_schema: T.Schema, steps) -> List[T.Schema]:
    """Per-step input schemas of a fused chain (index i = schema seen by
    steps[i]; the final entry is the chain's output schema). Expand emits a
    single declared schema for all its projections, so the schema stays
    uniform across groups at every step."""
    schemas = [input_schema]
    s = input_schema
    for st in steps:
        kind = st[0]
        if kind == "project":
            s = T.Schema(tuple(
                T.StructField(n, E.infer_type(e, s))
                for n, e in zip(st[2], st[1])))
        elif kind == "rename":
            s = s.rename(list(st[1]))
        elif kind == "expand":
            s = st[2]
        schemas.append(s)
    return schemas


def fused_group_flags(steps) -> List[bool]:
    """Static per-output-group "was filtered" flags: a group whose live mask
    was never narrowed by a filter step passes ``num_rows`` through and its
    compaction is skipped inside the trace (and the count sync skipped by
    the operator)."""
    flags = [False]
    for st in steps:
        if st[0] == "filter":
            flags = [True] * len(flags)
        elif st[0] == "expand":
            flags = [f for f in flags for _ in range(len(st[1]))]
    return flags


def build_fused_closure(input_schema: T.Schema, steps):
    """Compose a fused chain into one jax-traceable function.

    ``steps`` is a tuple of ("project", exprs, names) | ("filter", preds) |
    ("rename", names) | ("expand", projections, schema). Returns a function
    ``(datas, valids, num_rows) -> (groups, counts)`` over one batch's
    device planes, where ``groups[g]`` is that output group's
    ``(datas, valids)`` tuples at input capacity and ``counts[g]`` its live
    row count (traced; equal to ``num_rows`` for never-filtered groups).
    Callers jit it; the jit cache keys on (capacity, dtypes), which the
    capacity-bucket discipline makes recur."""
    schemas = fused_chain_schemas(input_schema, steps)

    def fused_chain(datas, valids, num_rows):
        cap = datas[0].shape[0]
        exists = jnp.arange(cap) < num_rows
        cols = [DeviceColumn(f.dtype, d, v)
                for f, d, v in zip(input_schema, datas, valids)]
        groups = [(cols, exists, False)]
        for si, st in enumerate(steps):
            kind = st[0]
            schema = schemas[si]
            out_groups = []
            for cols, live, filtered in groups:
                tb = TraceBatch(schema, cols, cap, live)
                if kind == "project":
                    ev = ExprEvaluator(list(st[1]), schema)
                    out_groups.append((ev.evaluate_traced(tb), live, filtered))
                elif kind == "filter":
                    ev = ExprEvaluator(list(st[1]), schema)
                    out_groups.append((cols, ev.evaluate_predicate(tb), True))
                elif kind == "rename":
                    out_groups.append((cols, live, filtered))
                elif kind == "expand":
                    for proj in st[1]:
                        ev = ExprEvaluator(list(proj), schema)
                        out_groups.append(
                            (ev.evaluate_traced(tb), live, filtered))
                else:
                    raise ExprError(f"unknown fused step {kind!r}")
            groups = out_groups
        outs = []
        counts = []
        for cols, live, filtered in groups:
            ds = tuple(c.data for c in cols)
            vs = tuple(c.validity for c in cols)
            if filtered:
                # end-of-chain compaction, same stable order + dead-lane
                # zeroing as kernels._compact
                count = jnp.sum(live)
                order = jnp.argsort(~live, stable=True)
                out_live = jnp.arange(cap) < count
                ds = tuple(
                    jnp.where(out_live, d[jnp.clip(order, 0, d.shape[0] - 1)],
                              jnp.zeros((), d.dtype))
                    for d in ds)
                vs = tuple(
                    v[jnp.clip(order, 0, v.shape[0] - 1)] & out_live
                    for v in vs)
            else:
                count = num_rows
            outs.append((ds, vs))
            counts.append(count)
        return tuple(outs), tuple(counts)

    return fused_chain


def trace_fused_steps(input_schema: T.Schema, steps, cols, live, cap: int):
    """Trace a SINGLE-GROUP fused chain (project/filter/rename steps — no
    expand) over already-traced columns, for absorbing the chain into a
    downstream kernel (the partial agg): the same step semantics as
    build_fused_closure, but the caller owns compaction — filters only
    narrow the live mask and rows stay in place. Returns (columns, live)
    over the chain's output schema."""
    schemas = fused_chain_schemas(input_schema, steps)
    for si, st in enumerate(steps):
        kind = st[0]
        schema = schemas[si]
        tb = TraceBatch(schema, cols, cap, live)
        if kind == "project":
            cols = ExprEvaluator(list(st[1]), schema).evaluate_traced(tb)
        elif kind == "filter":
            live = ExprEvaluator(list(st[1]), schema).evaluate_predicate(tb)
        elif kind == "rename":
            pass
        else:
            raise ExprError(f"fused step {kind!r} cannot be absorbed")
    return cols, live
