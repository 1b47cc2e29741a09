"""Aggregate functions over slot-indexed accumulators, dual-mode.

Reference: ``datafusion-ext-plans/src/agg/`` — typed accumulator columns
(``acc.rs:43-730``) updated vectorized per IdxSelection, with
freeze/unfreeze for spill.

Two accumulation modes, chosen per function by where its values can live
with exact semantics (see blaze_tpu/utils/device.py):

- **device**: accumulators are jax arrays; updates are XLA scatter ops
  (``array.at[slots].add/min/max``) — ints, decimals(<=18), dates,
  timestamps, f32, and f64 on backends with real float64;
- **host**: accumulators are numpy arrays updated via ``np.ufunc.at``
  (still vectorized) — f64 on TPU (which silently demotes f64 to f32),
  strings/binary via per-slot python objects (collect/min/max/first).

Partial-state representation: unlike the reference (which packs all
accumulators into one opaque binary column ``#9223372036854775807`` because
state must traverse *Spark's* row-oriented shuffle), partial output here uses
**typed columnar state fields** (e.g. sum -> [sum, has]) — our own shuffle
moves columns natively, so keeping state columnar avoids a pack/unpack pass
and lets the exchange compress per-plane. The opaque-binary contract can be
restored at a Spark boundary by serializing these fields.

NaN caveat: device scatter min/max follows XLA semantics (NaN propagates);
Spark orders NaN as largest. Plans aggregating floats should normalize NaNs
first (the converter inserts normalize_nan_and_zero, as Spark does).
"""

from __future__ import annotations

from typing import Any, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa

from blaze_tpu.core.batch import Column, DeviceColumn, HostColumn
from blaze_tpu.exprs import decimal as dec
from blaze_tpu.ir import exprs as E
from blaze_tpu.ir import types as T
from blaze_tpu.utils.device import is_device_dtype

_I64_MAX = np.iinfo(np.int64).max


def _grow(arr, capacity, fill=0):
    if arr.shape[0] >= capacity:
        return arr
    if isinstance(arr, np.ndarray):
        out = np.full(capacity, fill, dtype=arr.dtype)
        out[: arr.shape[0]] = arr
        return out
    if fill == 0:
        return jnp.pad(arr, (0, capacity - arr.shape[0]))
    return jnp.concatenate([arr, jnp.full(capacity - arr.shape[0], fill, arr.dtype)])


def _sentinel_np(np_dtype, which: str):
    if np.issubdtype(np_dtype, np.floating):
        return np.array(np.inf if which == "min" else -np.inf, np_dtype)
    if np_dtype == np.bool_:
        return np.array(which == "min", np_dtype)
    info = np.iinfo(np_dtype)
    return np.array(info.max if which == "min" else info.min, np_dtype)


def _arr_np(arr: pa.Array, np_dtype) -> Tuple[np.ndarray, np.ndarray]:
    """pa.Array -> (values, validity) numpy pair."""
    valid = ~np.asarray(arr.is_null()) if arr.null_count else np.ones(len(arr), bool)
    fill = False if pa.types.is_boolean(arr.type) else 0
    vals = arr.fill_null(fill).to_numpy(zero_copy_only=False).astype(np_dtype, copy=False)
    return vals, valid


def _col_np(col: Column, n: int, np_dtype) -> Tuple[np.ndarray, np.ndarray]:
    if isinstance(col, DeviceColumn):
        return (np.asarray(col.data[:n]).astype(np_dtype, copy=False),
                np.asarray(col.validity[:n]))
    return _arr_np(col.array, np_dtype)


def _host_col_out(dtype: T.DataType, vals: np.ndarray, valid: np.ndarray) -> HostColumn:
    at = T.to_arrow_type(dtype)
    if isinstance(dtype, T.DecimalType):
        # vals carry unscaled python ints (object array, exact for p > 18);
        # overflow beyond the precision becomes NULL (Spark non-ANSI)
        from decimal import Decimal

        bound = 10 ** dtype.precision
        out = [
            Decimal(int(v)).scaleb(-dtype.scale)
            if ok and -bound < int(v) < bound else None
            for v, ok in zip(vals, valid)
        ]
        return HostColumn(dtype, pa.array(out, type=at))
    return HostColumn(dtype, pa.Array.from_pandas(vals, mask=~valid, type=at))


def _decimal_unscaled_np(arr: pa.Array, scale: int):
    """(object array of unscaled python ints, validity) — exact for any
    precision (Spark hashes/aggregates wide decimals as BigIntegers)."""
    valid = ~np.asarray(arr.is_null()) if arr.null_count else np.ones(len(arr), bool)
    vals = np.empty(len(arr), dtype=object)
    for i, d in enumerate(arr.to_pylist()):
        vals[i] = 0 if d is None else int(d.scaleb(scale))
    return vals, valid


def _limb_renorm(lo, hi):
    """Re-establish the limb invariant lo in [0, 2^32): move accumulated
    carries into hi. Run after every accumulation round so lo never
    approaches int64 overflow (per round it grows by <= batch_rows * 2^32,
    well under 2^63 at any real capacity)."""
    carry = lo >> 32
    return lo & jnp.int64(0xFFFFFFFF), hi + carry


def _limb3_renorm(l0, l1, l2):
    """Re-establish l0/l1 in [0, 2^32) after an accumulation round; l2
    absorbs the carries (wrapping mod 2^64 — exact for totals within
    decimal(38), the same i128-wrapping semantics the reference uses)."""
    c0 = l0 >> 32
    l0 = l0 & jnp.int64(0xFFFFFFFF)
    l1 = l1 + c0
    c1 = l1 >> 32
    l1 = l1 & jnp.int64(0xFFFFFFFF)
    return l0, l1, l2 + c1


def _chunks_renorm(*limbs):
    """:func:`_limb3_renorm` for any number of limbs: every limb but the
    last back in [0, 2^32), the carries moved up into the last."""
    out, carry = [], None
    for limb in limbs[:-1]:
        if carry is not None:
            limb = limb + carry
        carry = limb >> 32
        out.append(limb & jnp.int64(0xFFFFFFFF))
    return out + [limbs[-1] + carry]


def moment_row_planes(x):
    """A long's seven planes of the exact moment state (ir/aggstate
    ``moment_state_fields``) before any reduction: x's limbs (two 32-bit
    chunks and the sign) and x²'s (three chunks and the rest). |x| as a
    uint64 holds every long's magnitude, 2^63 too, and its halves a < 2^32,
    b <= 2^31 multiply exactly in uint64: x² = a² + 2ab·2^32 + b²·2^64, so
    every plane is exact, none is negative but the sign, and none passes
    2^34 — a batch's segment sums stay far inside int64."""
    x = x.astype(jnp.int64)
    chunk = jnp.int64(0xFFFFFFFF)
    u = jnp.abs(x).astype(jnp.uint64)
    a, b = u & jnp.uint64(0xFFFFFFFF), u >> jnp.uint64(32)
    aa, ab, bb = ((p * q).astype(jnp.int64) for p, q in ((a, a), (a, b), (b, b)))
    logical = jax.lax.shift_right_logical
    return [x & chunk, (x >> 32) & chunk, x >> 63,
            aa & chunk, logical(aa, jnp.int64(32)) + ((ab & chunk) << 1),
            ((ab >> 32) << 1) + (bb & chunk), bb >> 32]


def moment_renorm(n, s0, s1, s2, q0, q1, q2, q3):
    """The moment state's limb invariant again after a reduction: the count
    as it is, Σx's and Σx²'s chunks back in [0, 2^32)."""
    return [n, *_limb3_renorm(s0, s1, s2), *_chunks_renorm(q0, q1, q2, q3)]


_EXACT_DOUBLE = 1 << 53   # every integer below it is a double
_SMALL_MOMENT = 1 << 26   # n, |Σx|, Σx² under it: N and D stay under 2^53


def moment_stddev(packed: np.ndarray):
    """STDDEV_SAMP of each group from its exact moments (``packed``: the
    eight state planes as int64 rows, one column a group) -> (values,
    valid, groups finished as a Fraction). With N = n·Σx² - (Σx)² and
    D = n·(n - 1) the variance is N / D rounded once: where both are under
    2^53 one IEEE division of two exact doubles, which is that rounding;
    elsewhere ``float(Fraction(N, D))`` by Python integers, and those groups
    are counted. The result is the square root of it, correctly rounded, so
    it may differ from Spark's running double in the last bits. NULL under
    two rows (Spark 3.1+: ``spark.sql.legacy.statisticalAggregate`` false).
    Groups whose n, |Σx| and Σx² are under 2^26 go as int64 arrays."""
    import math
    from fractions import Fraction

    n, s0, s1, s2, q0, q1, q2, q3 = packed
    valid = n > 1
    chunk = (1 << 32) - 1
    small_s = ((s2 == 0) & (s1 == 0) & (s0 < _SMALL_MOMENT)) | (
        (s2 == -1) & (s1 == chunk) & (s0 >= (1 << 32) - _SMALL_MOMENT))
    small = valid & small_s & (q3 == 0) & (q2 == 0) & (q1 == 0) & \
        (q0 < _SMALL_MOMENT) & (n < _SMALL_MOMENT)
    s = np.where(s2 == 0, s0, s0 - (1 << 32))
    num = np.where(small, n * q0 - s * s, 0)
    den = np.where(small, n * (n - 1), 1)
    out = np.sqrt(num.astype(np.float64) / den.astype(np.float64))
    exact = 0
    for i in np.flatnonzero(valid & ~small).tolist():
        total = int(s0[i]) + (int(s1[i]) << 32) + (int(s2[i]) << 64)
        squares = int(q0[i]) + (int(q1[i]) << 32) + (int(q2[i]) << 64) + \
            (int(q3[i]) << 96)
        count = int(n[i])
        num_i, den_i = count * squares - total * total, count * (count - 1)
        if num_i < _EXACT_DOUBLE and den_i < _EXACT_DOUBLE:
            var = float(num_i) / float(den_i)
        else:
            var = float(Fraction(num_i, den_i))
            exact += 1
        out[i] = math.sqrt(var)
    return np.where(valid, out, 0.0), valid, exact


def _wide_value_limbs(arr: pa.Array):
    """decimal128 array -> (l0, l1, l2, validity) numpy planes: l0/l1 the
    low/high 32-bit chunks of the unsigned low word (nonnegative int64),
    l2 the signed high word. value == (l2 << 64) + (l1 << 32) + l0."""
    from blaze_tpu.core.batch import decimal128_limbs

    lo_raw, hi, valid = decimal128_limbs(arr)
    l0 = lo_raw & 0xFFFFFFFF
    l1 = (lo_raw >> 32) & 0xFFFFFFFF  # arithmetic shift + mask = chunk
    return l0, l1, hi, valid


def _limb3_totals(l0, l1, l2, num_slots, extra=None):
    """Pull the limb planes (and the optional has/count plane) in ONE sync
    and combine to exact object ints."""
    arrs = [l0[:num_slots], l1[:num_slots], l2[:num_slots]]
    if extra is not None:
        arrs.append(extra[:num_slots].astype(jnp.int64))
    packed = np.asarray(jnp.stack(arrs))
    totals = ((packed[2].astype(object) << 64)
              + (packed[1].astype(object) << 32) + packed[0].astype(object))
    if extra is not None:
        return totals, packed[3]
    return totals





def _lex_scatter_minmax(state, slots, l0, l1, l2, m, is_max: bool):
    """Per-slot lexicographic min/max of (l2, l1, l0) value triples into
    ``state`` [s0, s1, s2, has] — the device path for wide-decimal MIN/MAX.
    Scatter cannot express a lex comparator, so rows group by slot (sort +
    segment reduce, the module's standard shape) and each slot's batch-best
    conditionally replaces the running state."""
    s0, s1, s2, has = state
    cap = s0.shape[0]
    n = slots.shape[0]
    dead = jnp.int64(cap)
    sl = jnp.where(m, slots.astype(jnp.int64), dead)
    order = jnp.argsort(sl)
    sl_s = sl[order]
    l0s, l1s, l2s, ms = l0[order], l1[order], l2[order], m[order]
    new = jnp.concatenate([jnp.ones(1, bool), sl_s[1:] != sl_s[:-1]])
    seg = jnp.cumsum(new) - 1
    from blaze_tpu.ops.agg_device import _segment_lex3

    b0, b1, b2, seg_any = _segment_lex3(l0s, l1s, l2s, ms, seg, n, is_max)
    seg_slot = jax.ops.segment_min(jnp.where(ms, sl_s, dead), seg, n)
    idx = jnp.clip(seg_slot, 0, cap - 1)
    c0, c1, c2, chas = s0[idx], s1[idx], s2[idx], has[idx]
    if is_max:
        better = ((b2 > c2) | ((b2 == c2) & (b1 > c1))
                  | ((b2 == c2) & (b1 == c1) & (b0 > c0)))
    else:
        better = ((b2 < c2) | ((b2 == c2) & (b1 < c1))
                  | ((b2 == c2) & (b1 == c1) & (b0 < c0)))
    take = seg_any & (seg_slot < dead) & (better | ~chas)
    # scatter ONLY the winners (dropped index for the rest): a plain
    # conditional .set would race stale values across duplicate indices
    idx_w = jnp.where(take, idx, dead)
    s0 = s0.at[idx_w].set(b0, mode="drop")
    s1 = s1.at[idx_w].set(b1, mode="drop")
    s2 = s2.at[idx_w].set(b2, mode="drop")
    has = has.at[idx_w].set(True, mode="drop")
    return [s0, s1, s2, has]


def _limb_final_column(state, num_slots, result_type: T.DecimalType):
    """Combine (lo, hi, has) limb state into an exact decimal host column,
    nulling values that overflow the result precision (Spark
    check_overflow semantics)."""
    lo, hi, has = state
    # ONE device->host pull: stack the three planes as int64 on device first
    packed = np.asarray(jnp.stack(
        [lo[:num_slots], hi[:num_slots], has[:num_slots].astype(jnp.int64)]))
    lo_np = packed[0].astype(object)
    hi_np = packed[1].astype(object)
    has_np = packed[2].astype(bool)
    totals = (hi_np << 32) + lo_np  # object ints: exact beyond int64
    # _host_col_out nulls totals beyond the precision (check_overflow)
    return _host_col_out(result_type, totals, has_np)


class AggFunction:
    """One aggregate over one arg expression; stateless descriptor, state is
    passed explicitly."""

    def __init__(self, agg: E.AggExpr, arg_type: T.DataType, result_type: T.DataType):
        self.agg = agg
        self.arg_type = arg_type
        self.result_type = result_type
        self.host = False  # overridden per function

    def state_fields(self) -> List[Tuple[str, T.DataType]]:
        raise NotImplementedError

    def init_state(self, capacity: int) -> List[Any]:
        raise NotImplementedError

    def grow(self, state: List[Any], capacity: int) -> List[Any]:
        return [_grow(s, capacity) if hasattr(s, "shape") else s for s in state]

    def update(self, state, slots, value, validity, mask, order=None):
        """Accumulate raw values (PARTIAL). Device mode: slots/value/validity
        are device arrays, mask is the row-exists device mask. Host mode:
        slots/mask are numpy, value is a pa.Array."""
        raise NotImplementedError

    def merge(self, state, slots, partial_cols: List[Column], mask, n: int):
        raise NotImplementedError

    def state_columns(self, state, num_slots: int, capacity: int) -> List[Column]:
        raise NotImplementedError

    def final_column(self, state, num_slots: int, capacity: int) -> Column:
        raise NotImplementedError

    def mem_used(self, state) -> int:
        return sum(s.nbytes for s in state if hasattr(s, "nbytes"))


class SumAgg(AggFunction):
    def __init__(self, agg, arg_type, result_type, limbs=None):
        super().__init__(agg, arg_type, result_type)
        from blaze_tpu.ir.aggstate import limb3_tag, limb_tag, state_mode

        # decimal(19..28) sums stay on device as two int64 limbs ('2');
        # sums over WIDE args (19..38 digits) as three ('3'). Eligibility
        # lives in ir/aggstate.state_mode (shared with the wire-schema
        # derivation). ``limbs``: None derives it; merge-mode callers pass
        # the decision read from the wire schema, and AvgAgg passes False
        # (its embedded sum keeps [sum, count])."""
        if limbs is None:
            self.limbs = state_mode(E.AggFunction.SUM, arg_type, result_type)
        else:
            self.limbs = "2" if limbs is True else (limbs or False)
        self.host = (not self.limbs) and not is_device_dtype(result_type)
        self._decimal_obj = self.host and isinstance(result_type, T.DecimalType)
        if self.limbs == "2":
            self._limb_tag = limb_tag(result_type)
            self._npdt = np.dtype(np.int64)
        elif self.limbs == "3":
            self._limb_tag = limb3_tag(result_type, arg_type)
            self._npdt = np.dtype(np.int64)
        elif self._decimal_obj:
            self._npdt = np.dtype(object)  # unscaled python ints, exact
        elif isinstance(result_type, T.DecimalType):
            self._npdt = np.dtype(np.int64)
        else:
            self._npdt = result_type.np_dtype

    def state_fields(self):
        if self.limbs == "2":
            return [(self._limb_tag, T.I64), ("sum_hi", T.I64), ("has", T.BOOL)]
        if self.limbs == "3":
            return [(self._limb_tag, T.I64), ("sum_l1", T.I64),
                    ("sum_l2", T.I64), ("has", T.BOOL)]
        return [("sum", self.result_type), ("has", T.BOOL)]

    def init_state(self, capacity):
        if self.limbs:
            nlimb = 2 if self.limbs == "2" else 3
            return [jnp.zeros(capacity, jnp.int64) for _ in range(nlimb)] \
                + [jnp.zeros(capacity, bool)]
        if self.host:
            return [np.zeros(capacity, self._npdt), np.zeros(capacity, bool)]
        return [jnp.zeros(capacity, self._npdt), jnp.zeros(capacity, bool)]

    def _rescale_arg(self, v, m):
        if isinstance(self.arg_type, T.DecimalType) and isinstance(self.result_type, T.DecimalType):
            if self.result_type.scale != self.arg_type.scale:
                v, _ = dec.rescale(v, m, self.arg_type.scale, self.result_type.scale, 19)
        return v

    def extract_host(self, value: pa.Array, in_scale: Optional[int] = None):
        """(values, validity) numpy pair for host accumulation; decimals as
        exact unscaled python ints rescaled to the result scale."""
        if self._decimal_obj:
            scale = self.result_type.scale if in_scale is None else in_scale
            vals, valid = _decimal_unscaled_np(value, scale)
            if in_scale is not None and in_scale != self.result_type.scale:
                m = 10 ** (self.result_type.scale - in_scale)
                vals = np.array([v * m for v in vals], dtype=object)
            return vals, valid
        return _arr_np(value, self._npdt)

    def update(self, state, slots, value, validity, mask, order=None):
        if self.limbs == "3":
            # wide arg arrives as a host decimal128 array (no int64 plane
            # exists); limb extraction is a buffer view, accumulation runs
            # on device
            l0a, l1a, l2a, has = state
            v0, v1, v2, valid = _wide_value_limbs(value)
            m = np.asarray(valid & mask)
            sl = jnp.asarray(np.asarray(slots, np.int64))
            jm = jnp.asarray(m)
            l0a = l0a.at[sl].add(jnp.asarray(np.where(m, v0, 0)), mode="drop")
            l1a = l1a.at[sl].add(jnp.asarray(np.where(m, v1, 0)), mode="drop")
            l2a = l2a.at[sl].add(jnp.asarray(np.where(m, v2, 0)), mode="drop")
            has = has.at[sl].max(jm, mode="drop")
            return list(_limb3_renorm(l0a, l1a, l2a)) + [has]
        if self.limbs:
            lo, hi, has = state
            m = validity & mask
            assert not (isinstance(self.arg_type, T.DecimalType)
                        and self.arg_type.scale != self.result_type.scale), \
                "SUM keeps the arg scale (Spark rule); limb path assumes it"
            v = value.astype(jnp.int64)
            vlo = jnp.where(m, v & jnp.int64(0xFFFFFFFF), jnp.int64(0))
            vhi = jnp.where(m, v >> 32, jnp.int64(0))
            lo = lo.at[slots].add(vlo, mode="drop")
            hi = hi.at[slots].add(vhi, mode="drop")
            has = has.at[slots].max(m, mode="drop")
            return list(_limb_renorm(lo, hi)) + [has]
        acc, has = state
        if self.host:
            in_scale = self.arg_type.scale if isinstance(self.arg_type, T.DecimalType) else None
            vals, valid = self.extract_host(value, in_scale)
            m = valid & mask
            np.add.at(acc, slots[m], vals[m])
            has[slots[m]] = True
            return [acc, has]
        m = validity & mask
        v = self._rescale_arg(value.astype(acc.dtype), m)
        acc = acc.at[slots].add(jnp.where(m, v, jnp.zeros((), acc.dtype)), mode="drop")
        has = has.at[slots].max(m, mode="drop")
        return [acc, has]

    def merge(self, state, slots, partial_cols, mask, n):
        if self.limbs == "3":
            l0a, l1a, l2a, has = state
            p0, p1, p2, phas = partial_cols
            m = phas.data.astype(bool) & phas.validity & mask
            for i, (acc, p) in enumerate(((l0a, p0), (l1a, p1), (l2a, p2))):
                upd = acc.at[slots].add(
                    jnp.where(m, p.data, jnp.int64(0)), mode="drop")
                if i == 0:
                    l0a = upd
                elif i == 1:
                    l1a = upd
                else:
                    l2a = upd
            has = has.at[slots].max(m, mode="drop")
            return list(_limb3_renorm(l0a, l1a, l2a)) + [has]
        if self.limbs:
            lo, hi, has = state
            plo, phi, phas = partial_cols
            m = phas.data.astype(bool) & phas.validity & mask
            lo = lo.at[slots].add(jnp.where(m, plo.data, jnp.int64(0)),
                                  mode="drop")
            hi = hi.at[slots].add(jnp.where(m, phi.data, jnp.int64(0)),
                                  mode="drop")
            has = has.at[slots].max(m, mode="drop")
            return list(_limb_renorm(lo, hi)) + [has]
        acc, has = state
        psum, phas = partial_cols
        if self.host:
            if self._decimal_obj:
                assert isinstance(psum, HostColumn)
                vals, valid = _decimal_unscaled_np(psum.array, self.result_type.scale)
            else:
                vals, valid = _col_np(psum, n, self._npdt)
            hvals, _ = _col_np(phas, n, np.bool_)
            m = valid & hvals & mask
            np.add.at(acc, slots[m], vals[m])
            has[slots[m]] = True
            return [acc, has]
        m = phas.data.astype(bool) & phas.validity & mask
        acc = acc.at[slots].add(jnp.where(m, psum.data.astype(acc.dtype), 0), mode="drop")
        has = has.at[slots].max(m, mode="drop")
        return [acc, has]

    def state_columns(self, state, num_slots, capacity):
        if self.limbs:
            grown = self.grow(state, capacity)
            ones = jnp.ones(capacity, bool)
            return [DeviceColumn(T.I64, g, ones) for g in grown[:-1]] \
                + [DeviceColumn(T.BOOL, grown[-1], ones)]
        acc, has = self.grow(state, capacity)
        if self.host:
            return [_host_col_out(self.result_type, acc[:num_slots], has[:num_slots]),
                    _host_col_out(T.BOOL, has[:num_slots], np.ones(num_slots, bool))]
        return [DeviceColumn(self.result_type, acc, has),
                DeviceColumn(T.BOOL, has, jnp.ones(capacity, bool))]

    def final_column(self, state, num_slots, capacity):
        if self.limbs == "3":
            l0a, l1a, l2a, has = state
            totals, has_i = _limb3_totals(l0a, l1a, l2a, num_slots, has)
            return _host_col_out(self.result_type, totals,
                                 has_i.astype(bool))
        if self.limbs:
            return _limb_final_column(state, num_slots, self.result_type)
        acc, has = self.grow(state, capacity)
        if self.host:
            return _host_col_out(self.result_type, acc[:num_slots], has[:num_slots])
        if isinstance(self.result_type, T.DecimalType):
            acc, has = dec.check_overflow(acc, has, self.result_type.precision)
        return DeviceColumn(self.result_type, acc, has)


class CountAgg(AggFunction):
    def state_fields(self):
        return [("count", T.I64)]

    def init_state(self, capacity):
        return [jnp.zeros(capacity, jnp.int64)]

    def update(self, state, slots, value, validity, mask, order=None):
        (acc,) = state
        if isinstance(value, pa.Array):  # host-resident arg: count on host mask
            valid = ~np.asarray(value.is_null()) if value.null_count else \
                np.ones(len(value), bool)
            m = valid & mask
            accn = np.asarray(acc)
            np.add.at(accn, slots[m], 1)
            return [jnp.asarray(accn)]
        m = mask if value is None else (validity & mask)
        acc = acc.at[slots].add(m.astype(jnp.int64), mode="drop")
        return [acc]

    def merge(self, state, slots, partial_cols, mask, n):
        (pcol,) = partial_cols
        (acc,) = state
        if isinstance(pcol, HostColumn) or isinstance(slots, np.ndarray):
            vals, valid = _col_np(pcol, n, np.int64)
            accn = np.asarray(acc)
            m = valid & (np.asarray(mask)[:n] if hasattr(mask, "shape") else mask)
            np.add.at(accn, slots[:n][m] if len(slots) > n else slots[m], vals[m])
            return [jnp.asarray(accn)]
        v = jnp.where(pcol.validity & mask, pcol.data, 0)
        acc = acc.at[slots].add(v, mode="drop")
        return [acc]

    def state_columns(self, state, num_slots, capacity):
        (acc,) = self.grow(state, capacity)
        return [DeviceColumn(T.I64, acc, jnp.ones(capacity, bool))]

    def final_column(self, state, num_slots, capacity):
        (acc,) = self.grow(state, capacity)
        return DeviceColumn(T.I64, acc, jnp.ones(capacity, bool))


class AvgAgg(AggFunction):
    """State: [sum (sum-type), count i64]; final divides with Spark scale
    rules (decimal avg result scale via converter result_type). A
    decimal(9..18) arg's sum type is decimal(19..28): the sum then rides
    the same two-int64-limb device layout as SUM (state [lo, hi, count])
    with an exact host combine+divide at finalization."""

    def __init__(self, agg, arg_type, result_type, limbs=None):
        super().__init__(agg, arg_type, result_type)
        from blaze_tpu.ir.aggstate import (avg_sum_type, limb3_tag, limb_tag,
                                           state_mode)

        self.sum_type = avg_sum_type(arg_type)
        if limbs is None:
            self.limbs = state_mode(E.AggFunction.AVG, arg_type,
                                    self.result_type)
        else:
            self.limbs = "2" if limbs is True else (limbs or False)
        self._sum = SumAgg(agg, arg_type, self.sum_type, limbs=False)
        self._cnt = CountAgg(agg, arg_type, T.I64)
        self.host = (not self.limbs) and self._sum.host
        if self.limbs == "2":
            self._limb_tag = limb_tag(self.sum_type)
        elif self.limbs == "3":
            self._limb_tag = limb3_tag(self.sum_type, arg_type)

    def state_fields(self):
        if self.limbs == "2":
            return [(self._limb_tag, T.I64), ("sum_hi", T.I64), ("count", T.I64)]
        if self.limbs == "3":
            return [(self._limb_tag, T.I64), ("sum_l1", T.I64),
                    ("sum_l2", T.I64), ("count", T.I64)]
        return [("sum", self.sum_type), ("count", T.I64)]

    def init_state(self, capacity):
        if self.limbs:
            nlimb = 2 if self.limbs == "2" else 3
            return [jnp.zeros(capacity, jnp.int64) for _ in range(nlimb + 1)]
        if self.host:
            return [np.zeros(capacity, self._sum._npdt), np.zeros(capacity, np.int64)]
        return [self._sum.init_state(capacity)[0], self._cnt.init_state(capacity)[0]]

    def grow(self, state, capacity):
        return [_grow(s, capacity) for s in state]

    def update(self, state, slots, value, validity, mask, order=None):
        if self.limbs == "3":
            l0a, l1a, l2a, c = state
            v0, v1, v2, valid = _wide_value_limbs(value)
            m = np.asarray(valid & mask)
            sl = jnp.asarray(np.asarray(slots, np.int64))
            jm = jnp.asarray(m)
            l0a = l0a.at[sl].add(jnp.asarray(np.where(m, v0, 0)), mode="drop")
            l1a = l1a.at[sl].add(jnp.asarray(np.where(m, v1, 0)), mode="drop")
            l2a = l2a.at[sl].add(jnp.asarray(np.where(m, v2, 0)), mode="drop")
            c = c.at[sl].add(jm.astype(jnp.int64), mode="drop")
            return list(_limb3_renorm(l0a, l1a, l2a)) + [c]
        if self.limbs:
            lo, hi, c = state
            m = validity & mask
            v = value.astype(jnp.int64)
            lo = lo.at[slots].add(
                jnp.where(m, v & jnp.int64(0xFFFFFFFF), jnp.int64(0)), mode="drop")
            hi = hi.at[slots].add(jnp.where(m, v >> 32, jnp.int64(0)), mode="drop")
            c = c.at[slots].add(m.astype(jnp.int64), mode="drop")
            return list(_limb_renorm(lo, hi)) + [c]
        s, c = state
        if self.host:
            in_scale = self.arg_type.scale if isinstance(self.arg_type, T.DecimalType) else None
            vals, valid = self._sum.extract_host(value, in_scale)
            m = valid & mask
            np.add.at(s, slots[m], vals[m])
            np.add.at(c, slots[m], 1)
            return [s, c]
        s = self._sum.update([s, jnp.zeros_like(mask)], slots, value, validity, mask)[0]
        c = self._cnt.update([c], slots, value, validity, mask)[0]
        return [s, c]

    def merge(self, state, slots, partial_cols, mask, n):
        if self.limbs == "3":
            l0a, l1a, l2a, c = state
            p0, p1, p2, pcnt = partial_cols
            m = pcnt.data.astype(bool) & pcnt.validity & mask
            l0a = l0a.at[slots].add(jnp.where(m, p0.data, jnp.int64(0)),
                                    mode="drop")
            l1a = l1a.at[slots].add(jnp.where(m, p1.data, jnp.int64(0)),
                                    mode="drop")
            l2a = l2a.at[slots].add(jnp.where(m, p2.data, jnp.int64(0)),
                                    mode="drop")
            c = c.at[slots].add(jnp.where(m, pcnt.data, jnp.int64(0)),
                                mode="drop")
            return list(_limb3_renorm(l0a, l1a, l2a)) + [c]
        if self.limbs:
            lo, hi, c = state
            plo, phi, pcnt = partial_cols
            m = pcnt.data.astype(bool) & pcnt.validity & mask
            lo = lo.at[slots].add(jnp.where(m, plo.data, jnp.int64(0)),
                                  mode="drop")
            hi = hi.at[slots].add(jnp.where(m, phi.data, jnp.int64(0)),
                                  mode="drop")
            c = c.at[slots].add(jnp.where(m, pcnt.data, jnp.int64(0)),
                                mode="drop")
            return list(_limb_renorm(lo, hi)) + [c]
        psum, pcnt = partial_cols
        s, c = state
        if self.host:
            if self._sum._decimal_obj:
                vals, valid = _decimal_unscaled_np(psum.array, self.sum_type.scale)
            else:
                vals, valid = _col_np(psum, n, self._sum._npdt)
            m = valid & mask
            np.add.at(s, slots[m], vals[m])
            cvals, cvalid = _col_np(pcnt, n, np.int64)
            mc = cvalid & mask
            np.add.at(c, slots[mc], cvals[mc])
            return [s, c]
        m = psum.validity & mask
        s = s.at[slots].add(jnp.where(m, psum.data.astype(s.dtype), 0), mode="drop")
        c = c.at[slots].add(jnp.where(pcnt.validity & mask, pcnt.data, 0), mode="drop")
        return [s, c]

    def state_columns(self, state, num_slots, capacity):
        if self.limbs:
            grown = self.grow(state, capacity)
            ones = jnp.ones(capacity, bool)
            return [DeviceColumn(T.I64, g, ones) for g in grown]
        s, c = self.grow(state, capacity)
        if self.host:
            cn = c
            return [_host_col_out(self.sum_type, s[:num_slots], cn[:num_slots] > 0),
                    DeviceColumn(T.I64, jnp.asarray(cn.astype(np.int64)),
                                 jnp.ones(capacity, bool))]
        return [DeviceColumn(self.sum_type, s, c > 0),
                DeviceColumn(T.I64, c, jnp.ones(capacity, bool))]

    def _decimal_divide(self, totals, counts, num_slots, has):
        """Exact Decimal sum/count with Spark HALF_UP rounding and
        check_overflow nulling. ``totals`` unscaled object ints. Runs under
        a widened context: wide-arg sums reach ~10^38 and the default
        28-significant-digit context raises InvalidOperation on
        quantize."""
        import decimal as _d
        from decimal import ROUND_HALF_UP, Decimal

        q = Decimal(1).scaleb(-self.result_type.scale)
        bound = Decimal(10) ** (self.result_type.precision - self.result_type.scale)
        out = []
        with _d.localcontext() as ctx:
            ctx.prec = 80
            for i in range(num_slots):
                if not has[i]:
                    out.append(None)
                    continue
                v = (Decimal(int(totals[i])).scaleb(-self.sum_type.scale)
                     / Decimal(int(counts[i]))).quantize(
                         q, rounding=ROUND_HALF_UP)
                out.append(v if abs(v) < bound else None)
        return HostColumn(self.result_type,
                          pa.array(out, type=T.to_arrow_type(self.result_type)))

    def final_column(self, state, num_slots, capacity):
        if self.limbs == "3":
            l0a, l1a, l2a, c = state
            totals, counts = _limb3_totals(l0a, l1a, l2a, num_slots, c)
            return self._decimal_divide(totals, counts, num_slots, counts > 0)
        if self.limbs:
            lo, hi, c = state
            packed = np.asarray(jnp.stack(
                [lo[:num_slots], hi[:num_slots], c[:num_slots]]))
            totals = (packed[1].astype(object) << 32) + packed[0].astype(object)
            counts = packed[2]
            return self._decimal_divide(totals, counts, num_slots, counts > 0)
        s, c = self.grow(state, capacity)
        if self.host:
            has = c > 0
            if self._sum._decimal_obj:
                return self._decimal_divide(s, c, num_slots, has)
            out = s.astype(np.float64) / np.where(has, c, 1)
            return _host_col_out(T.F64, out[:num_slots], has[:num_slots])
        has = c > 0
        cnz = jnp.where(has, c, 1)
        if isinstance(self.result_type, T.DecimalType):
            scale_adjust = self.result_type.scale - self.sum_type.scale
            out, validity = dec.div(s, has, cnz, has, scale_adjust)
            out, validity = dec.check_overflow(out, validity, self.result_type.precision)
            return DeviceColumn(self.result_type, out, validity)
        if not is_device_dtype(T.F64):
            # no float64 arithmetic on this device: the sum and the count
            # stayed int64 on it, and the one division happens here, on the
            # host, in IEEE double (exact operands under 2^53)
            packed = np.asarray(jnp.stack([s[:num_slots], c[:num_slots]]))
            counted = packed[1] > 0
            out = packed[0].astype(np.float64) / \
                np.where(counted, packed[1], 1).astype(np.float64)
            return _host_col_out(T.F64, out, counted)
        out = s.astype(jnp.float64) / cnz.astype(jnp.float64)
        return DeviceColumn(T.F64, out, has)


class MinMaxAgg(AggFunction):
    def __init__(self, agg, arg_type, result_type, which: str, limbs=None):
        super().__init__(agg, arg_type, result_type)
        from blaze_tpu.ir.aggstate import state_mode, wide_val_tag

        self.which = which
        # numerics stay vectorized (numpy ufunc.at when host); wide
        # decimals (19..38) as three int64 value limbs compared
        # lexicographically on DEVICE; other var-width values per-slot
        # python objects
        if limbs is None:
            fn = E.AggFunction.MIN if which == "min" else E.AggFunction.MAX
            self.limbs = state_mode(fn, arg_type, result_type)
        else:
            self.limbs = limbs or False
        if isinstance(arg_type, T.DecimalType):
            self.numeric = arg_type.fits_int64
        else:
            self.numeric = arg_type.np_dtype is not None
        self.host = (not self.limbs) and not is_device_dtype(arg_type)
        self._npdt = np.dtype(np.int64) if isinstance(arg_type, T.DecimalType) else (
            arg_type.np_dtype if self.numeric else None)
        if self.limbs == "w":
            self._limb_tag = wide_val_tag(result_type)

    def state_fields(self):
        if self.limbs == "w":
            return [(self._limb_tag, T.I64), ("val_l1", T.I64),
                    ("val_l2", T.I64), ("has", T.BOOL)]
        return [("val", self.result_type), ("has", T.BOOL)]

    def init_state(self, capacity):
        if self.limbs == "w":
            return [jnp.zeros(capacity, jnp.int64) for _ in range(3)] \
                + [jnp.zeros(capacity, bool)]
        if self.host and not self.numeric:
            return [dict(), None]
        if self.host:
            return [np.full(capacity, _sentinel_np(self._npdt, self.which)),
                    np.zeros(capacity, bool)]
        return [jnp.full(capacity, _sentinel_np(self._npdt, self.which).item(),
                         self._npdt),
                jnp.zeros(capacity, bool)]

    def grow(self, state, capacity):
        if self.limbs == "w":
            return [_grow(s, capacity) for s in state]
        if self.host and not self.numeric:
            return state
        val, has = state
        if val.shape[0] >= capacity:
            return state
        return [_grow(val, capacity, fill=_sentinel_np(val.dtype, self.which).item()),
                _grow(has, capacity)]

    def update(self, state, slots, value, validity, mask, order=None):
        if self.limbs == "w":
            v0, v1, v2, valid = _wide_value_limbs(value)
            m = np.asarray(valid & mask)
            return _lex_scatter_minmax(
                state, jnp.asarray(np.asarray(slots, np.int64)),
                jnp.asarray(v0), jnp.asarray(v1), jnp.asarray(v2),
                jnp.asarray(m), self.which == "max")
        if self.host and not self.numeric:
            return self._update_obj(state, slots, value.to_pylist(), mask)
        if self.host:
            val, has = state
            vals, valid = _arr_np(value, self._npdt)
            m = valid & mask
            ufn = np.minimum if self.which == "min" else np.maximum
            ufn.at(val, slots[m], vals[m])
            has[slots[m]] = True
            return [val, has]
        acc, has = state
        m = validity & mask
        sent = jnp.array(_sentinel_np(acc.dtype, self.which).item(), acc.dtype)
        v = jnp.where(m, value.astype(acc.dtype), sent)
        acc = acc.at[slots].min(v, mode="drop") if self.which == "min" else \
            acc.at[slots].max(v, mode="drop")
        has = has.at[slots].max(m, mode="drop")
        return [acc, has]

    def _update_obj(self, state, slots, vals, mask):
        d, _ = state
        better = (lambda a, b: a < b) if self.which == "min" else (lambda a, b: a > b)
        for i, v in enumerate(vals):
            if not mask[i] or v is None:
                continue
            s = int(slots[i])
            cur = d.get(s)
            if cur is None or better(v, cur):
                d[s] = v
        return [d, None]

    def merge(self, state, slots, partial_cols, mask, n):
        if self.limbs == "w":
            p0, p1, p2, phas = partial_cols
            m = phas.data.astype(bool) & phas.validity & mask
            return _lex_scatter_minmax(state, slots, p0.data, p1.data,
                                       p2.data, m, self.which == "max")
        pval, phas = partial_cols
        if self.host and not self.numeric:
            return self._update_obj(state, slots, pval.array.to_pylist(), mask)
        if self.host:
            val, has = state
            vals, valid = _col_np(pval, n, self._npdt)
            hvals, _ = _col_np(phas, n, np.bool_)
            m = valid & hvals & mask
            ufn = np.minimum if self.which == "min" else np.maximum
            ufn.at(val, slots[m], vals[m])
            has[slots[m]] = True
            return [val, has]
        m = phas.data.astype(bool) & phas.validity & mask
        acc, has = state
        sent = jnp.array(_sentinel_np(acc.dtype, self.which).item(), acc.dtype)
        v = jnp.where(m, pval.data.astype(acc.dtype), sent)
        acc = acc.at[slots].min(v, mode="drop") if self.which == "min" else \
            acc.at[slots].max(v, mode="drop")
        has = has.at[slots].max(m, mode="drop")
        return [acc, has]

    def state_columns(self, state, num_slots, capacity):
        if self.limbs == "w":
            grown = self.grow(state, capacity)
            ones = jnp.ones(capacity, bool)
            return [DeviceColumn(T.I64, g, ones) for g in grown[:-1]] \
                + [DeviceColumn(T.BOOL, grown[-1], ones)]
        if self.host and not self.numeric:
            d = state[0]
            vals = [d.get(i) for i in range(num_slots)]
            has = [i in d for i in range(num_slots)]
            return [
                HostColumn(self.result_type, pa.array(vals, type=T.to_arrow_type(self.result_type))),
                HostColumn(T.BOOL, pa.array(has, type=pa.bool_())),
            ]
        val, has = self.grow(state, capacity)
        if self.host:
            return [_host_col_out(self.result_type, np.where(has, val, 0)[:num_slots], has[:num_slots]),
                    _host_col_out(T.BOOL, has[:num_slots], np.ones(num_slots, bool))]
        return [DeviceColumn(self.result_type, jnp.where(has, val, 0), has),
                DeviceColumn(T.BOOL, has, jnp.ones(capacity, bool))]

    def final_column(self, state, num_slots, capacity):
        if self.limbs == "w":
            l0a, l1a, l2a, has = state
            totals, has_i = _limb3_totals(l0a, l1a, l2a, num_slots, has)
            return _host_col_out(self.result_type, totals,
                                 has_i.astype(bool))
        return self.state_columns(state, num_slots, capacity)[0]

    def mem_used(self, state):
        if self.host and not self.numeric:
            d = state[0]
            return 64 * len(d)
        return super().mem_used(state)


class MomentAgg(AggFunction):
    """STDDEV_SAMP. Over an INTEGRAL argument the state is the exact moments
    (ir/aggstate ``moment_state_fields``): device arrays here, and the same
    planes in the device kernels' ``moment`` kind (ops/agg_device), so the
    two stages and the exchange between them see one layout wherever each
    ran; the group finishes once, on the host (:func:`moment_stddev`). Any
    other argument keeps Spark's (n, mean, m2) in double on the host: a
    batch's per-group moments by two passes, merged as Spark merges two
    states (CentralMomentAgg), so it can differ from Spark's row-by-row
    update in the last bits."""

    def __init__(self, agg, arg_type, result_type):
        super().__init__(agg, arg_type, result_type)
        from blaze_tpu.ir.aggstate import _INTEGRAL, moment_state_fields

        self.exact = isinstance(arg_type, _INTEGRAL)
        self.host = not self.exact
        self._fields = moment_state_fields(arg_type)
        self.metrics = None  # the operator's node, set by the table using it

    def state_fields(self):
        return list(self._fields)

    def init_state(self, capacity):
        if self.exact:
            return [jnp.zeros(capacity, jnp.int64) for _ in self._fields]
        return [np.zeros(capacity, np.float64) for _ in self._fields]

    def update(self, state, slots, value, validity, mask, order=None):
        if self.exact:
            m = validity & mask
            z = jnp.int64(0)
            planes = [m.astype(jnp.int64)] + [
                jnp.where(m, p, z) for p in moment_row_planes(value)]
            return moment_renorm(*(acc.at[slots].add(p, mode="drop")
                                   for acc, p in zip(state, planes)))
        x, valid = _arr_np(value.cast(pa.float64(), safe=False), np.float64)
        m = valid & mask
        return _moment_merge(state, *_moment_parts(slots[m], x[m],
                                                   len(state[0])))

    def merge(self, state, slots, partial_cols, mask, n):
        if self.exact:
            m = partial_cols[0].validity & mask
            z = jnp.int64(0)
            return moment_renorm(*(
                acc.at[slots].add(jnp.where(m, p.data, z), mode="drop")
                for acc, p in zip(state, partial_cols)))
        (cn, _), (mean, _), (m2, _) = (_col_np(c, n, np.float64)
                                       for c in partial_cols)
        m = np.asarray(mask)[:n] & (cn > 0)
        sl, cn, mean, m2 = slots[:n][m], cn[m], mean[m], m2[m]
        cap = len(state[0])
        # the parts of one slot first, then the slot's state and their sum
        tot = np.bincount(sl, weights=cn, minlength=cap)
        avg = np.divide(np.bincount(sl, weights=cn * mean, minlength=cap),
                        tot, out=np.zeros(cap), where=tot > 0)
        dev = np.bincount(sl, weights=m2 + cn * (mean - avg[sl]) ** 2,
                          minlength=cap)
        return _moment_merge(state, tot, avg, dev)

    def state_columns(self, state, num_slots, capacity):
        grown = self.grow(state, capacity)
        if self.exact:
            ones = jnp.ones(capacity, bool)
            return [DeviceColumn(T.I64, g, ones) for g in grown]
        ones = np.ones(num_slots, bool)
        return [_host_col_out(T.F64, g[:num_slots], ones) for g in grown]

    def final_column(self, state, num_slots, capacity):
        if not self.exact:
            n, _mean, m2 = (s[:num_slots] for s in state)
            valid = n > 1
            out = np.sqrt(np.where(valid, m2, 0.0) / np.where(valid, n - 1, 1))
            return _host_col_out(T.F64, out, valid)
        from blaze_tpu.obs.tracer import TRACER
        from blaze_tpu.utils.device import DEVICE_STATS, wait_array

        packed = wait_array(jnp.stack([p[:num_slots] for p in state]),
                            "agg_moment_state")
        with TRACER.detail("moment_finish", "agg", {"groups": num_slots}):
            out, valid, exact = moment_stddev(packed)
        DEVICE_STATS.add_moment(exact_groups=exact)
        if exact and self.metrics is not None:
            self.metrics.add("moment_exact_groups", exact)
        return _host_col_out(T.F64, out, valid)


def _moment_parts(slots, x, capacity):
    """(n, mean, m2) a slot of one batch's valid values, by two passes."""
    n = np.bincount(slots, minlength=capacity).astype(np.float64)
    mean = np.divide(np.bincount(slots, weights=x, minlength=capacity), n,
                     out=np.zeros(capacity), where=n > 0)
    m2 = np.bincount(slots, weights=(x - mean[slots]) ** 2, minlength=capacity)
    return n, mean, m2


def _moment_merge(state, n2, avg2, m2_2):
    """Spark's CentralMomentAgg merge of two (n, mean, m2) states."""
    n1, avg1, m2_1 = state
    n = n1 + n2
    delta = avg2 - avg1
    delta_n = np.divide(delta, n, out=np.zeros_like(n), where=n > 0)
    return [n, avg1 + delta_n * n2, m2_1 + m2_2 + delta * delta_n * n1 * n2]


class FirstAgg(AggFunction):
    """FIRST / FIRST_IGNORES_NULL: winner = smallest global row order; two
    scatter passes (order min, then conditional value write)."""

    def __init__(self, agg, arg_type, result_type, ignores_null: bool):
        super().__init__(agg, arg_type, result_type)
        self.ignores_null = ignores_null
        self.host = not is_device_dtype(arg_type)

    def state_fields(self):
        return [("val", self.result_type), ("valid", T.BOOL), ("order", T.I64)]

    def init_state(self, capacity):
        if self.host:
            return [dict(), None, None]  # slot -> (order, value)
        return [
            jnp.zeros(capacity, self.result_type.np_dtype if not isinstance(
                self.result_type, T.DecimalType) else np.int64),
            jnp.zeros(capacity, bool),
            jnp.full(capacity, _I64_MAX, jnp.int64),
        ]

    def grow(self, state, capacity):
        if self.host:
            return state
        val, valid, order = state
        if val.shape[0] >= capacity:
            return state
        return [_grow(val, capacity), _grow(valid, capacity),
                _grow(order, capacity, fill=_I64_MAX)]

    def update(self, state, slots, value, validity, mask, order=None):
        if self.host:
            vals = value.to_pylist()
            d = state[0]
            order_np = np.asarray(order)
            for i, v in enumerate(vals):
                if not mask[i]:
                    continue
                if self.ignores_null and v is None:
                    continue
                s = int(slots[i])
                o = int(order_np[i])
                cur = d.get(s)
                if cur is None or o < cur[0]:
                    d[s] = (o, v)
            return [d, None, None]
        val, valid, best = state
        m = (validity & mask) if self.ignores_null else mask
        o = jnp.where(m, order, _I64_MAX)
        best = best.at[slots].min(o, mode="drop")
        win = m & (o == best.at[slots].get(mode="fill", fill_value=_I64_MAX))
        val = _scatter_where(val, slots, value.astype(val.dtype), win)
        valid = _scatter_where(valid, slots, validity & m, win)
        return [val, valid, best]

    def merge(self, state, slots, partial_cols, mask, n):
        pval, pvalid, porder = partial_cols
        if self.host:
            d = state[0]
            vals = pval.array.to_pylist() if isinstance(pval, HostColumn) else \
                np.asarray(pval.data[:n]).tolist()
            orders, _ = _col_np(porder, n, np.int64)
            pv, _ = _col_np(pvalid, n, np.bool_)
            for i in range(n):
                if not mask[i] or orders[i] == _I64_MAX:
                    continue
                s = int(slots[i])
                o = int(orders[i])
                v = vals[i] if pv[i] else None
                cur = d.get(s)
                if cur is None or o < cur[0]:
                    d[s] = (o, v)
            return [d, None, None]
        val, valid, best = state
        m = mask & (porder.data != _I64_MAX)
        o = jnp.where(m, porder.data, _I64_MAX)
        best = best.at[slots].min(o, mode="drop")
        win = m & (o == best.at[slots].get(mode="fill", fill_value=_I64_MAX))
        val = _scatter_where(val, slots, pval.data.astype(val.dtype), win)
        valid = _scatter_where(valid, slots, pval.validity & phas_true(pvalid) & win, win)
        return [val, valid, best]

    def state_columns(self, state, num_slots, capacity):
        if self.host:
            d = state[0]
            vals = [d[i][1] if i in d else None for i in range(num_slots)]
            has = [i in d for i in range(num_slots)]
            orders = [d[i][0] if i in d else _I64_MAX for i in range(num_slots)]
            return [
                HostColumn(self.result_type, pa.array(vals, type=T.to_arrow_type(self.result_type))),
                HostColumn(T.BOOL, pa.array(has, type=pa.bool_())),
                HostColumn(T.I64, pa.array(orders, type=pa.int64())),
            ]
        val, valid, best = self.grow(state, capacity)
        ones = jnp.ones(capacity, bool)
        return [
            DeviceColumn(self.result_type, val, valid),
            DeviceColumn(T.BOOL, valid, ones),
            DeviceColumn(T.I64, best, ones),
        ]

    def final_column(self, state, num_slots, capacity):
        return self.state_columns(state, num_slots, capacity)[0]

    def mem_used(self, state):
        if self.host:
            return 96 * len(state[0])
        return super().mem_used(state)


def phas_true(pvalid):
    return pvalid.data.astype(bool) & pvalid.validity


def _scatter_where(arr, slots, values, cond):
    """arr[slots[i]] = values[i] where cond[i] (losers write out of range and
    are dropped)."""
    n = arr.shape[0]
    safe_slots = jnp.where(cond, slots, n)
    return arr.at[safe_slots].set(values, mode="drop")


class CollectAgg(AggFunction):
    """collect_list / collect_set — per-slot python lists (reference:
    agg/collect.rs)."""

    def __init__(self, agg, arg_type, result_type, distinct: bool):
        super().__init__(agg, arg_type, result_type)
        self.distinct = distinct
        self.host = True

    def state_fields(self):
        return [("items", T.ArrayType(self.arg_type))]

    def init_state(self, capacity):
        return [dict()]

    def grow(self, state, capacity):
        return state

    def update(self, state, slots, value, validity, mask, order=None):
        (d,) = state
        vals = value.to_pylist()
        for i, v in enumerate(vals):
            if not mask[i] or v is None:
                continue
            s = int(slots[i])
            lst = d.setdefault(s, [])
            if not self.distinct or v not in lst:
                lst.append(v)
        return [d]

    def merge(self, state, slots, partial_cols, mask, n):
        (plist,) = partial_cols
        return self._union_rows(state, slots, plist.array.to_pylist(), mask)

    def _union_rows(self, state, slots, rows, mask):
        (d,) = state
        for i, items in enumerate(rows):
            if not mask[i] or items is None:
                continue
            s = int(slots[i])
            lst = d.setdefault(s, [])
            for v in items:
                if v is None:
                    continue
                if not self.distinct or v not in lst:
                    lst.append(v)
        return [d]

    def state_columns(self, state, num_slots, capacity):
        (d,) = state
        vals = [d.get(i, []) for i in range(num_slots)]
        at = pa.large_list(T.to_arrow_type(self.arg_type))
        return [HostColumn(T.ArrayType(self.arg_type), pa.array(vals, type=at))]

    def final_column(self, state, num_slots, capacity):
        return self.state_columns(state, num_slots, capacity)[0]

    def mem_used(self, state):
        (d,) = state
        return sum(64 + 16 * len(v) for v in d.values())


class CombineUniqueAgg(CollectAgg):
    """brickhouse combine_unique: the argument column holds ARRAYS; the
    aggregate unions their elements per group, deduped (reference:
    agg/brickhouse.rs combine_unique over UserDefinedArray states)."""

    def __init__(self, agg, arg_type, result_type):
        elem = arg_type.element_type if isinstance(arg_type, T.ArrayType) else arg_type
        super().__init__(agg, elem, T.ArrayType(elem), distinct=True)

    def update(self, state, slots, value, validity, mask, order=None):
        return self._union_rows(state, slots, value.to_pylist(), mask)


class BloomFilterAgg(AggFunction):
    """bloom_filter aggregate building a Spark-compatible bloom filter over
    int64 values (reference: agg/bloom_filter.rs + spark_bloom_filter.rs)."""

    def __init__(self, agg, arg_type, result_type, expected_items: int = 1_000_000,
                 num_bits: int = 8_388_608):
        super().__init__(agg, arg_type, T.BINARY)
        self.expected_items = expected_items
        self.num_bits = num_bits
        self.host = True

    def state_fields(self):
        return [("bloom", T.BINARY)]

    def init_state(self, capacity):
        from blaze_tpu.ops.bloom import SparkBloomFilter

        return [{0: SparkBloomFilter.create(self.expected_items, self.num_bits)}]

    def grow(self, state, capacity):
        return state

    def update(self, state, slots, value, validity, mask, order=None):
        (d,) = state
        vals, valid = _arr_np(value, np.int64) if isinstance(value, pa.Array) else (
            np.asarray(value), np.asarray(validity))
        m = valid & np.asarray(mask)[: len(vals)]
        d[0].put_longs(vals[m])
        return [d]

    def merge(self, state, slots, partial_cols, mask, n):
        from blaze_tpu.ops.bloom import SparkBloomFilter

        (pcol,) = partial_cols
        (d,) = state
        for blob in pcol.array.to_pylist():
            if blob is not None:
                d[0].merge(SparkBloomFilter.deserialize(blob))
        return [d]

    def state_columns(self, state, num_slots, capacity):
        (d,) = state
        blob = d[0].serialize()
        return [HostColumn(T.BINARY, pa.array([blob] * num_slots, type=pa.large_binary()))]

    def final_column(self, state, num_slots, capacity):
        return self.state_columns(state, num_slots, capacity)[0]

    def mem_used(self, state):
        (d,) = state
        return d[0].words.nbytes


class UDAFAgg(AggFunction):
    """Python UDAF: object with initialize()/update(acc, value)/merge(a, b)/
    evaluate(acc) — the host-callback analogue of the reference's
    SparkUDAFWrapperContext JNI round-trip."""

    def __init__(self, agg, arg_type, result_type):
        super().__init__(agg, arg_type, result_type)
        self.udaf = agg.udaf
        self.host = True

    def state_fields(self):
        return [("acc", T.BINARY)]

    def init_state(self, capacity):
        return [dict()]

    def grow(self, state, capacity):
        return state

    def update(self, state, slots, value, validity, mask, order=None):
        (d,) = state
        vals = value.to_pylist()
        for i, v in enumerate(vals):
            if not mask[i]:
                continue
            s = int(slots[i])
            if s not in d:
                d[s] = self.udaf.initialize()
            d[s] = self.udaf.update(d[s], v)
        return [d]

    def merge(self, state, slots, partial_cols, mask, n):
        import pickle

        (pcol,) = partial_cols
        (d,) = state
        for i, blob in enumerate(pcol.array.to_pylist()):
            if not mask[i] or blob is None:
                continue
            s = int(slots[i])
            other = pickle.loads(blob)
            if s not in d:
                d[s] = self.udaf.initialize()
            d[s] = self.udaf.merge(d[s], other)
        return [d]

    def state_columns(self, state, num_slots, capacity):
        import pickle

        (d,) = state
        vals = [pickle.dumps(d[i]) if i in d else None for i in range(num_slots)]
        return [HostColumn(T.BINARY, pa.array(vals, type=pa.large_binary()))]

    def final_column(self, state, num_slots, capacity):
        (d,) = state
        vals = [self.udaf.evaluate(d[i]) if i in d else None for i in range(num_slots)]
        return HostColumn(self.result_type,
                          pa.array(vals, type=T.to_arrow_type(self.result_type)))


def create_agg_function(agg: E.AggExpr, input_schema: T.Schema,
                        limbs=None) -> AggFunction:
    """``limbs``: wide-decimal SUM layout override for merge-mode callers
    that read the partial producer's decision off the wire schema
    (aggstate.parse_limb_tag); None derives it from the types."""
    arg_t = E.infer_type(agg.args[0], input_schema) if agg.args else T.NULL
    result_t = agg.return_type or E.agg_result_type(agg.fn, arg_t)
    F = E.AggFunction
    if agg.fn == F.SUM:
        return SumAgg(agg, arg_t, result_t, limbs=limbs)
    if agg.fn == F.COUNT:
        return CountAgg(agg, arg_t, T.I64)
    if agg.fn == F.AVG:
        return AvgAgg(agg, arg_t, result_t, limbs=limbs)
    if agg.fn == F.MIN:
        return MinMaxAgg(agg, arg_t, result_t, "min", limbs=limbs)
    if agg.fn == F.MAX:
        return MinMaxAgg(agg, arg_t, result_t, "max", limbs=limbs)
    if agg.fn == F.FIRST:
        return FirstAgg(agg, arg_t, result_t, ignores_null=False)
    if agg.fn == F.FIRST_IGNORES_NULL:
        return FirstAgg(agg, arg_t, result_t, ignores_null=True)
    if agg.fn == F.COLLECT_LIST:
        return CollectAgg(agg, arg_t, result_t, distinct=False)
    if agg.fn == F.COLLECT_SET:
        return CollectAgg(agg, arg_t, result_t, distinct=True)
    if agg.fn == F.BRICKHOUSE_COLLECT:
        return CollectAgg(agg, arg_t, result_t, distinct=False)
    if agg.fn == F.BRICKHOUSE_COMBINE_UNIQUE:
        return CombineUniqueAgg(agg, arg_t, result_t)
    if agg.fn == F.BLOOM_FILTER:
        return BloomFilterAgg(agg, arg_t, result_t)
    if agg.fn == F.UDAF:
        return UDAFAgg(agg, arg_t, result_t)
    if agg.fn == F.STDDEV_SAMP:
        return MomentAgg(agg, arg_t, result_t)
    raise NotImplementedError(f"agg function {agg.fn}")
