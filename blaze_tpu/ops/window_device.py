"""The window operator's device programs.

What runs here: row_number / rank / dense_rank, and SUM / COUNT / MIN / MAX
over the running frame (ROWS BETWEEN UNBOUNDED PRECEDING AND CURRENT ROW),
partitioned and ordered by fixed-width keys, over integer, date and decimal
arguments. Every value of such a window is known at its own row, so nothing
is withheld: one program a batch, ``jit(window_scan)``, reads the sorted
batch's key and argument planes and writes the window columns —

* ``bounds``: partition and peer starts from the key planes, row against
  the row before it, row 0 against the last key row the carry holds;
* ``scan``: the restart-at-segment prefix scans of ``core/kernels``
  (``restarting_counters_traced``, ``segment_cumsum_traced``,
  ``segment_running_reduce_traced``), each seeded by the carry;
* ``emit``: the typed result planes under the padding contract, and the next
  carry (last key row, counters, running sum / count / extremum), which
  stays on the device from batch to batch.

A wide decimal (Spark types ``SUM(decimal(17,2))`` as decimal(27,2)) is by
type a host column of decimal128. Inside the program such a value is always
exact: two int64 words, ``hi * 2^64 + uint64(lo)``, summed by
``segment_cumsum_wide_traced`` and ordered by both words, in the carry too.
What differs is only how a batch's result LEAVES the program. The values of a
window over money are small, so where every result of the batch fits int64
(``hi == lo >> 63``, one flag the operator waits for, ``sync:window_carry``)
the low word rides on as ONE int64 plane in a ``DeviceColumn`` of the wide
type; where one does not, both words are pulled and laid side by side, which
is decimal128's buffer (:func:`wide_host_column`, ``wide_host_batches``).
A wide argument takes either form back in (:func:`wide_words` uploads a
host column's words)."""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa

from blaze_tpu.core import kernels as K
from blaze_tpu.core.batch import (DeviceColumn, HostColumn, decimal128_limbs,
                                  pack_bitmap)
from blaze_tpu.ir import exprs as E
from blaze_tpu.ir import types as T
from blaze_tpu.utils.device import (DEVICE_STATS, is_device_dtype,
                                    pull_columns)

F = E.AggFunction
RANK_KINDS = ("row_number", "rank", "dense_rank")
RUNNING_FRAME = ("rows", None, 0)


is_wide_decimal = T.is_wide_decimal


def _integral(dt: T.DataType) -> bool:
    """A type whose device plane orders and adds as integers: the integer
    types, date and timestamp, and a decimal of any precision (a wide one
    where its column arrives as a proved int64 plane)."""
    if isinstance(dt, T.DecimalType):
        return True
    return is_device_dtype(dt) and dt.np_dtype is not None and \
        np.issubdtype(np.dtype(dt.np_dtype), np.integer)


class Spec(NamedTuple):
    """What ``jit(window_scan)`` is compiled for (hashable: the jit's static
    argument). ``exprs``: one entry a window expression, ``(kind,)`` for the
    rank family and ``("count",)``, ``("sum", wide)`` where ``wide`` says the
    RESULT is typed wider than int64, ``("min" | "max", wide)`` where it says
    the ARGUMENT is (the result has the argument's type)."""

    n_part: int
    n_order: int
    exprs: Tuple[tuple, ...]


def plan(window_exprs, partition_spec, order_spec, child_schema,
         result_types) -> Optional[Spec]:
    """The program's spec, or None where the window is not one the device
    programs compute (it then takes the host paths, counted)."""
    for e in list(partition_spec) + [so.child for so in order_spec]:
        dt = E.infer_type(e, child_schema)
        if T.is_var_width(dt) and isinstance(e, (E.Column, E.BoundReference)):
            # a name that arrives CODED (core/batch.CodedColumn) is an int32
            # plane here: the program only asks whether a row's key differs
            # from the row's before it, and within one dictionary equal
            # codes are equal values (ops/window.py keeps a stream to one)
            continue
        if not (_integral(dt) or isinstance(dt, T.BooleanType)) or \
                is_wide_decimal(dt):
            return None
    exprs = []
    for w, result_t in zip(window_exprs, result_types):
        if w.kind in RANK_KINDS:
            exprs.append((w.kind,))
            continue
        if w.kind != "agg" or w.frame is None or \
                tuple(w.frame) != RUNNING_FRAME or \
                w.agg.fn not in (F.SUM, F.COUNT, F.MIN, F.MAX):
            return None
        arg_t = E.infer_type(w.agg.args[0], child_schema) if w.agg.args \
            else None
        if w.agg.fn == F.COUNT:
            if arg_t is not None and not (is_device_dtype(arg_t)
                                          or is_wide_decimal(arg_t)):
                return None
            exprs.append(("count",))
        elif arg_t is None or not _integral(arg_t):
            return None
        elif w.agg.fn == F.SUM:
            if not _integral(result_t):
                return None
            exprs.append(("sum", is_wide_decimal(result_t)))
        else:
            exprs.append((w.agg.fn.value, is_wide_decimal(arg_t)))
    return Spec(len(partition_spec), len(order_spec), tuple(exprs))


def is_wide_result(e: tuple) -> bool:
    """Does this entry of ``Spec.exprs`` leave the program as two words?"""
    return e[0] in ("sum", "min", "max") and e[1]


def _differs(data, valid, prev_data, prev_valid):
    """Row against the row before it, NULLs equal to one another."""
    return (valid != prev_valid) | (valid & (data != prev_data))


def _shift(plane, first):
    """The plane moved down a row, ``first`` at row 0."""
    return jnp.concatenate([first[None].astype(plane.dtype), plane[:-1]])


def _words(arg):
    """An argument as (lo, hi, validity): a wide one comes as that, an int64
    plane is its own low word under its sign."""
    if len(arg) == 3:
        return arg
    data = arg[0].astype(jnp.int64)
    return data, data >> 63, arg[1]


@functools.partial(jax.jit, static_argnames=("spec", "cap"))
def window_scan(num_rows, keys, args, carry, spec: Spec, cap: int):
    """One sorted batch of ``num_rows`` > 0 rows at capacity ``cap`` through
    the window: ``keys`` the partition keys' then the order keys' (data,
    validity) planes, ``args`` one entry a window expression ((data,
    validity), a wide argument's (lo, hi, validity), None for the rank family
    and COUNT(*)), ``carry`` as :func:`initial_carry` shapes it. Returns the
    window columns' planes ((data, validity), a wide result's (lo, hi,
    validity)), the next carry and whether every wide result fits int64."""
    idx = jnp.arange(cap, dtype=jnp.int32)
    exists = idx < num_rows
    last = num_rows - 1

    with jax.named_scope("bounds"):
        first = idx == 0
        changed = []  # per key: this row's key differs from the row before
        for (data, valid), (cdata, cvalid) in zip(keys, carry["keys"]):
            changed.append(_differs(data, valid, _shift(data, cdata),
                                    _shift(valid, cvalid)))
        fresh = first & ~carry["started"]
        part_start = fresh
        for c in changed[:spec.n_part]:
            part_start = part_start | c
        new_peer = part_start
        for c in changed[spec.n_part:]:
            new_peer = new_peer | c
        part_start = part_start & exists
        new_peer = new_peer & exists

    with jax.named_scope("scan"):
        rn = rank = dense = None
        if any(e[0] in RANK_KINDS for e in spec.exprs):
            rn, rank, dense = K.restarting_counters_traced(
                part_start, new_peer, carry["rn"], carry["rank"],
                carry["dense"])
        # per aggregate: (value planes, count or has plane)
        scans = []
        for e, arg, acc in zip(spec.exprs, args, carry["aggs"]):
            if e[0] in RANK_KINDS:
                scans.append(None)
            elif e[0] == "count":
                valid = exists if arg is None else arg[-1] & exists
                scans.append(((), K.segment_cumsum_traced(
                    jnp.zeros(cap, jnp.int64), valid, part_start,
                    jnp.int64(0), acc[-1])[1]))
            elif e[0] == "sum" and e[1]:
                lo, hi, valid = _words(arg)
                *words, cnt = K.segment_cumsum_wide_traced(
                    lo, hi, valid & exists, part_start, *acc)
                scans.append((tuple(words), cnt))
            elif e[0] == "sum":
                total, cnt = K.segment_cumsum_traced(
                    arg[0], arg[1] & exists, part_start, *acc)
                scans.append(((total,), cnt))
            else:
                if e[1]:
                    lo, hi, valid = _words(arg)
                    planes = (hi, lo)
                else:
                    planes, valid = (arg[0],), arg[1]
                ext, has = K.segment_running_reduce_traced(
                    planes, valid & exists, part_start, e[0] == "min",
                    acc[:-1][::-1] if e[1] else acc[:-1], acc[-1])
                scans.append((ext[::-1] if e[1] else ext, has))

    with jax.named_scope("emit"):
        cols, aggs, fits = [], [], jnp.bool_(True)
        for e, scan, acc in zip(spec.exprs, scans, carry["aggs"]):
            if e[0] in RANK_KINDS:
                plane = {"row_number": rn, "rank": rank, "dense_rank": dense}[
                    e[0]]
                if e[0] != "row_number":
                    plane = plane.astype(jnp.int32)
                cols.append((jnp.where(exists, plane,
                                       jnp.zeros((), plane.dtype)), exists))
                aggs.append(acc)
                continue
            values, tail = scan
            if e[0] == "count":
                values, has = (tail,), exists
            else:
                has = exists & (tail > 0 if e[0] == "sum" else tail)
            cols.append((*(jnp.where(has, v, jnp.zeros((), v.dtype))
                           for v in values), has))
            if is_wide_result(e):
                fits = fits & jnp.all(~has | (values[1] == values[0] >> 63))
            aggs.append((*(v[last] for v in scan[0]), tail[last]))

        def roll(plane, old):
            return old if plane is None else plane[last]

        nxt = {
            "started": jnp.bool_(True),
            "keys": tuple((d[last], v[last]) for d, v in keys),
            "rn": roll(rn, carry["rn"]),
            "rank": roll(rank, carry["rank"]),
            "dense": roll(dense, carry["dense"]),
            "segments": carry["segments"] + jnp.sum(part_start, dtype=jnp.int64),
            "aggs": tuple(aggs),
        }
    return tuple(cols), nxt, fits


def initial_carry(spec: Spec, key_dtypes, arg_dtypes):
    """The carry before a partition's first batch (host values; the jit
    uploads them once). An aggregate's entry is its value (no word for a
    count, two for a wide value, else one) and then its count or ``has``;
    the rank family's entries are placeholders."""
    aggs = []
    for e, dt in zip(spec.exprs, arg_dtypes):
        if e[0] in RANK_KINDS:
            aggs.append(())
        elif e[0] == "count":
            aggs.append((np.int64(0),))
        elif e[0] == "sum":
            aggs.append((np.int64(0),) * (3 if e[1] else 2))
        elif e[1]:
            aggs.append((np.int64(0), np.int64(0), np.bool_(False)))
        else:
            aggs.append((np.zeros((), dt), np.bool_(False)))
    return {
        "started": np.bool_(False),
        "keys": tuple((np.zeros((), dt), np.bool_(False)) for dt in key_dtypes),
        "rn": np.int64(0), "rank": np.int64(1), "dense": np.int64(0),
        "segments": np.int64(0),
        "aggs": tuple(aggs),
    }


# -- a wide decimal's two words in and out --------------------------------------


def wide_words(col: HostColumn, capacity: int):
    """A wide decimal's host column as the program's (lo, hi, validity)
    planes at ``capacity``: decimal128's own words, uploaded."""
    n = len(col.array)
    planes = []
    for plane in decimal128_limbs(col.array):
        buf = np.zeros(capacity, plane.dtype)
        buf[:n] = plane
        planes.append(buf)
    DEVICE_STATS.add_to_device(sum(p.nbytes for p in planes))
    return tuple(jnp.asarray(p) for p in planes)


def wide_host_column(dt: T.DecimalType, lo, hi, valid, n: int) -> HostColumn:
    """The (lo, hi, validity) planes of a result that does not fit int64 ->
    the type's host column: the two words side by side are decimal128."""
    (lo, valid), (hi, _v) = pull_columns(
        [DeviceColumn(T.I64, lo, valid), DeviceColumn(T.I64, hi, valid)], n)
    return HostColumn(dt, pa.Array.from_buffers(
        pa.decimal128(dt.precision, dt.scale), n,
        [pack_bitmap(valid), pa.py_buffer(np.stack([lo, hi], axis=1))]))
