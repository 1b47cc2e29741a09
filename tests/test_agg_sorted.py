"""The sort-path aggregation kernels (ops/agg_device.py: ``jit(agg_partial)``
and ``jit(agg_merge)``, one body, ``_aggregate_sorted``) against a plain
numpy group-by whose arithmetic is Python's own integers.

Every aggregate kind, over what the body's pieces could get wrong: null
keys and null arguments, garbage in the padding rows, one segment, a segment
a row, one row, no row, a single int key inside ``[0, capacity - 1)`` (the
range the deleted direct-indexing branch took), negative keys (the groups
leave nulls first, then ascending), ``-0.0`` / NaN float keys, int64 sums that
wrap, and capacities 128, 1,024 and 131,072 with a row count that is no power
of two. Integer results are compared bit for bit.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from blaze_tpu.ops import agg_device as A
from tests.test_dense_agg import (KINDS, NARROW, NSTATE, WIDE,
                                   _state_dtypes)

I64 = np.iinfo(np.int64)
M32 = 0xFFFFFFFF
def _wrap(x: int) -> int:
    """A Python integer as int64 arithmetic leaves it (mod 2^64)."""
    x &= (1 << 64) - 1
    return x - (1 << 64) if x >> 63 else x


# -- inputs ---------------------------------------------------------------------

# name -> (capacity, rows, key dtypes, aggregate names, how the keys are drawn,
# how the values are drawn)
CASES = {
    "nulls_and_padding": (128, 100, ("int64", "int32"), NARROW, "few", "random"),
    "wide_nulls_and_padding": (128, 100, ("int64",), WIDE, "few", "random"),
    "one_segment": (128, 128, ("int64", "int32"), NARROW, "one", "random"),
    "wide_one_segment": (128, 128, ("int64",), WIDE, "one", "extremes"),
    "a_segment_a_row": (128, 100, ("int64", "int32"), NARROW, "distinct",
                        "random"),
    "wide_a_segment_a_row": (128, 100, ("int64",), WIDE, "distinct", "random"),
    "one_row": (128, 1, ("int64", "int32"), NARROW, "few", "random"),
    "no_row": (128, 0, ("int64", "int32"), NARROW, "few", "random"),
    "wide_no_row": (128, 0, ("int64",), WIDE, "few", "random"),
    "one_key_inside_the_capacity": (128, 100, ("int64",), ["sum", "count"],
                                    "in_range", "random"),
    "negative_keys": (1024, 777, ("int64", "int32"), NARROW, "signed",
                      "random"),
    "float_keys": (1024, 777, ("float32", "int64"), ["sum", "count"],
                   "few", "random"),
    "sums_that_wrap": (1024, 777, ("int64", "int32"), NARROW, "few",
                       "extremes"),
    "wide_extremes": (1024, 777, ("int64",), WIDE, "few", "extremes"),
    "a_scan_batch": (131072, 100_000, ("int64", "int64"), ["sum", "count"],
                     "many", "random"),
}
FLOAT_KEYS = [0.0, -0.0, np.nan, -np.nan, 1.5, -2.5, np.inf, -np.inf]


def _key_plane(rng, dtype, cap, draw):
    if dtype.startswith("float"):
        return rng.choice(np.array(FLOAT_KEYS, dtype), cap)
    if draw == "one":
        return np.full(cap, -7, dtype)
    if draw == "distinct":
        return (rng.permutation(cap) - cap // 2).astype(dtype)
    if draw == "in_range":
        return rng.integers(0, cap - 1, cap).astype(dtype)
    if draw == "signed":
        info = np.iinfo(dtype)
        return rng.choice(np.array([info.min, info.min + 1, -3, -1, 0, 1, 2,
                                    info.max - 1, info.max], dtype), cap)
    if draw == "many":
        return rng.integers(-20_000, 20_000, cap).astype(dtype)
    return rng.integers(-3, 4, cap).astype(dtype)  # "few"


def _value_planes(rng, dtype, cap, draw):
    """One argument's data planes: one, or a decimal(38)'s three limbs."""
    if dtype == "wide3":
        # l0, l1: nonnegative 32-bit chunks; l2: the signed high word
        if draw == "extremes":
            return [rng.choice(np.array(c, np.int64), cap)
                    for c in ([0, M32], [0, M32], [I64.min, I64.max, 0, -1])]
        return [rng.integers(0, 2**32, cap), rng.integers(0, 2**32, cap),
                rng.integers(-3, 3, cap)]  # ties in the high word
    if dtype == "float32":
        return [rng.normal(0, 1e3, cap).astype(np.float32)]
    if dtype == "bool":
        return [rng.random(cap) > 0.3]
    info = np.iinfo(dtype)
    if draw == "extremes":
        return [rng.choice(np.array([info.min, info.max, info.min + 1,
                                     info.max - 1, 0, -1], dtype), cap)]
    return [rng.integers(-10**6, 10**6, cap).astype(dtype)]


def _valid(rng, cap, nulls=0.1):
    """A validity plane; like every plane here it holds garbage past the row
    count, which only ``exists`` may cut off."""
    return rng.random(cap) > nulls


def partial_inputs(case, seed=0):
    """(exists, keys, args): numpy planes of ``jit(agg_partial)``'s arguments;
    keys are (data, valid), args (data planes, valid)."""
    cap, rows, key_dtypes, names, key_draw, value_draw = CASES[case]
    rng = np.random.default_rng(seed)
    exists = np.arange(cap) < rows
    nulls = 0.0 if key_draw in ("one", "distinct") else 0.1
    keys = [(_key_plane(rng, kd, cap, key_draw), _valid(rng, cap, nulls))
            for kd in key_dtypes]
    args = [(_value_planes(rng, KINDS[n][1], cap, value_draw), _valid(rng, cap))
            for n in names]
    return exists, keys, args


def merge_inputs(case, seed=0):
    """(exists, keys, states): ``jit(agg_merge)``'s arguments; a state is a
    list of (data, valid) columns. Counts are small and sometimes 0."""
    cap, rows, key_dtypes, names, key_draw, value_draw = CASES[case]
    rng = np.random.default_rng(seed + 1)
    exists = np.arange(cap) < rows
    nulls = 0.0 if key_draw in ("one", "distinct") else 0.1
    keys = [(_key_plane(rng, kd, cap, key_draw), _valid(rng, cap, nulls))
            for kd in key_dtypes]
    states = []
    for n in names:
        cols = []
        for i, dt in enumerate(_state_dtypes(n)):
            kind = KINDS[n][0][0]
            if dt == "int64" and (kind == "count" or (
                    kind.startswith("avg") and i == NSTATE[kind] - 1)):
                data = rng.integers(0, 4, cap)
            elif kind in ("sum3", "avg3", "minw", "maxw") and i < 3:
                data = _value_planes(rng, "wide3", cap, value_draw)[i]
            else:
                (data,) = _value_planes(rng, dt, cap, value_draw)
            cols.append((data, _valid(rng, cap, 0.05)))
        states.append(cols)
    return exists, keys, states


# -- the reference: a group-by in numpy, its arithmetic in Python integers ------


def _canonical(d, v):
    """(class, value) a key row: nulls (class 0) before values (1) before NaN
    (2); -0.0 is 0.0; the value of a null or a NaN is 0."""
    cls = v.astype(np.int8)
    if d.dtype.kind == "f":
        cls = np.where(v & np.isnan(d), 2, cls)
        d = d + np.zeros((), d.dtype)  # -0.0 + 0.0 = 0.0
    return cls, np.where(cls == 1, d, np.zeros((), d.dtype))


def group_rows(exists, keys):
    """The rows of every group, each in row order, the groups in key order."""
    rows = np.flatnonzero(exists)
    cols = []
    for d, v in keys:
        cls, val = _canonical(d[rows], v[rows])
        cols += [cls, val]
    order = np.lexsort(tuple(reversed(cols)))  # stable; the first key leads
    new = np.zeros(len(rows), bool)
    new[:1] = True
    for c in cols:
        c = c[order]
        new[1:] |= c[1:] != c[:-1]
    return np.split(rows[order], np.flatnonzero(new)[1:]) if len(rows) else []


def _limbs_renorm(l0, l1, l2):
    """aggfns._limb3_renorm on int64 sums that may have wrapped."""
    l0, l1 = _wrap(l0), _wrap(l1)
    l1 = _wrap(l1 + (l0 >> 32))
    return l0 & M32, l1 & M32, _wrap(l2 + (l1 >> 32))


def _lex_extreme(values, is_max):
    """(b0, b1, b2, has) of (l0, l1, l2) triples, compared high word first."""
    if not values:
        return 0, 0, 0, False
    pick = max if is_max else min
    l0, l1, l2 = pick(values, key=lambda t: (t[2], t[1], t[0]))
    return l0, l1, l2, True


def _finish(kind, sums, tail, acc="int64"):
    """State of a summing kind from the exact sums of its planes and its last
    plane (`has` or the count)."""
    if kind in ("sum2", "avg2"):
        lo, hi = map(_wrap, sums)
        return lo & M32, _wrap(hi + (lo >> 32)), tail
    if kind in ("sum3", "avg3"):
        return (*_limbs_renorm(*sums), tail)
    (s,) = sums
    return (s if acc.startswith("float") else _wrap(s)), tail


def partial_state(name, planes, valid, rows):
    """One aggregate's state over one group's rows, from the raw argument."""
    (kind, rescale, acc), _adt = KINDS[name]
    rows = [r for r in rows if valid[r]]
    tail = len(rows) if kind.startswith("avg") else bool(rows)
    if kind == "count":
        return (len(rows),)
    if kind in ("minw", "maxw"):
        return _lex_extreme([tuple(int(p[r]) for p in planes) for r in rows],
                            kind == "maxw")
    if kind in ("min", "max"):
        vals = [planes[0][r] for r in rows]
        return ((max if kind == "max" else min)(vals) if vals else 0, tail)
    if kind in ("sum3", "avg3"):
        return _finish(kind, [sum(int(p[r]) for r in rows) for p in planes],
                       tail)
    if kind in ("sum2", "avg2"):
        xs = [int(planes[0][r]) for r in rows]
        return _finish(kind, [sum(x & M32 for x in xs),
                              sum(x >> 32 for x in xs)], tail)
    if acc.startswith("float"):
        return _finish(kind, [float(np.sum(planes[0][rows], dtype=np.float64))],
                       tail, acc)
    return _finish(kind, [sum(int(planes[0][r]) for r in rows)
                          * 10 ** rescale], tail)


def merge_state(name, cols, rows):
    """One aggregate's merged state over one group's partial-state rows."""
    (kind, _rescale, acc), _adt = KINDS[name]

    def total(col, keep):
        if col[0].dtype.kind == "f":
            return float(np.sum(col[0][keep], dtype=np.float64))
        return sum(int(col[0][r]) for r in keep)

    if kind in ("count", "avg"):  # every column under its own validity
        sums = [total(c, [r for r in rows if c[1][r]]) for c in cols]
        if kind == "count":
            return (_wrap(sums[0]),)
        return (sums[0] if acc.startswith("float") else _wrap(sums[0]),
                _wrap(sums[1]))
    *vals, (last, last_valid) = cols
    keep = [r for r in rows if vals[0][1][r] and last[r] and last_valid[r]]
    tail = _wrap(sum(int(last[r]) for r in keep)) \
        if kind.startswith("avg") else bool(keep)
    if kind in ("minw", "maxw"):
        return _lex_extreme([tuple(int(d[r]) for d, _ in vals) for r in keep],
                            kind == "maxw")
    if kind in ("min", "max"):
        picked = [vals[0][0][r] for r in keep]
        return ((max if kind == "max" else min)(picked) if picked else 0, tail)
    return _finish(kind, [total(c, keep) for c in vals], tail, acc)


# -- the comparison --------------------------------------------------------------


def check_outputs(outs, exists, keys, names, states_of, out_len=None):
    """``outs`` (a kernel's outputs, numpy) against the reference: the group
    count, the keys in order, every state plane, and zeros past the groups.
    ``states_of(name_index, rows)`` gives the reference's state of a group.
    The planes are ``out_len`` long (a batch's capacity, or the slot table's
    ``out_cap``)."""
    outs = [np.asarray(o) for o in outs]
    groups = group_rows(exists, keys)
    g = len(groups)
    out_len = len(exists) if out_len is None else out_len
    assert int(outs[0]) == g
    assert np.array_equal(outs[1], np.arange(out_len) < g)
    pos = 2
    for d, v in keys:
        cls, val = _canonical(d, v & exists)
        first = np.array([rows[0] for rows in groups], np.int64)
        want_valid = np.zeros(out_len, bool)
        want_valid[:g] = cls[first] > 0
        want = np.zeros(out_len, d.dtype)
        want[:g] = np.where(cls[first] == 2, np.nan, val[first]) \
            if d.dtype.kind == "f" else val[first]
        assert outs[pos].dtype == d.dtype
        # bit for bit: a float key leaves as its canonical value, +0.0
        assert np.array_equal(outs[pos].view(f"u{d.dtype.itemsize}"),
                              want.view(f"u{d.dtype.itemsize}")), "key data"
        assert np.array_equal(outs[pos + 1], want_valid), "key validity"
        pos += 2
    for i, name in enumerate(names):
        kind = KINDS[name][0][0]
        want = [states_of(i, rows) for rows in groups]
        for j in range(NSTATE[kind]):
            got = outs[pos]
            assert not got[g:].any(), (name, j, "zeros past the groups")
            col = [w[j] for w in want]
            if got.dtype.kind == "f":
                np.testing.assert_allclose(got[:g], np.array(col, got.dtype),
                                           rtol=1e-5, atol=1e-2)
            else:
                assert np.array_equal(got[:g], np.array(col, got.dtype)), \
                    (name, j)
            pos += 1
    assert pos == len(outs)


def run_partial(case):
    _cap, _rows, key_dtypes, names, _k, _v = CASES[case]
    exists, keys, args = partial_inputs(case)
    flat = [p for d, v in keys for p in (d, v)]
    flat += [p for planes, v in args for p in (*planes, v)]
    kernel = A._partial_kernel(
        tuple(key_dtypes), tuple(KINDS[n][0] for n in names),
        tuple(KINDS[n][1] for n in names), len(exists))
    outs = kernel(jnp.asarray(exists), *map(jnp.asarray, flat))
    check_outputs(outs, exists, keys, names,
                  lambda i, rows: partial_state(names[i], *args[i], rows))


def run_merge(case):
    _cap, _rows, key_dtypes, names, _k, _v = CASES[case]
    exists, keys, states = merge_inputs(case)
    flat = [p for d, v in keys for p in (d, v)]
    flat += [p for cols in states for d, v in cols for p in (d, v)]
    kernel = A._merge_kernel(
        tuple(key_dtypes), tuple(KINDS[n][0][0] for n in names),
        tuple(_state_dtypes(n) for n in names), len(exists))
    outs = kernel(jnp.asarray(exists), *map(jnp.asarray, flat))
    check_outputs(outs, exists, keys, names,
                  lambda i, rows: merge_state(names[i], states[i], rows))


@pytest.mark.parametrize("case", sorted(CASES))
def test_partial_kernel_equals_the_numpy_group_by(case):
    run_partial(case)


@pytest.mark.parametrize("case", sorted(CASES))
def test_merge_kernel_equals_the_numpy_group_by(case):
    run_merge(case)


def test_order_word_keeps_the_keys_order():
    """The uint64 word sorts as the canonical key does: signed integers
    ascending, floats ascending with NaN last, a bool False first."""
    ints = np.array([I64.min, -2, -1, 0, 1, I64.max], np.int64)
    words = np.asarray(A._order_word(jnp.asarray(ints)))
    assert words.dtype == np.uint64 and (np.diff(words.astype(object)) > 0).all()
    for dt in ("float32", "float64"):
        floats = np.array([-np.inf, -2.5, -1e-30, 0.0, 1e-30, 1.5, np.inf,
                           np.nan], dt)
        words = np.asarray(A._order_word(jnp.asarray(floats)))
        assert (np.diff(words.astype(object)) > 0).all(), dt
    small = np.asarray(A._order_word(jnp.asarray(np.array([-128, 0, 127],
                                                           np.int8))))
    assert (np.diff(small.astype(object)) > 0).all()
    flags = np.asarray(A._order_word(jnp.asarray(np.array([False, True]))))
    assert flags[0] < flags[1]
