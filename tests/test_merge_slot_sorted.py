"""The merge by ONE sort of a packed key id (ops/agg_device.py:
``DeviceMergeAgger._merge`` and ``jit(agg_merge_sorted)``).

Where no radix table is planned (the chip; ``radix_agg=False`` here), a
FINAL / PARTIAL_MERGE merge whose keys are signed integer planes probes
their ranges and reduces by one sort of the packed int64 id in place of
``lex_order_traced``'s ranking of every key word. Its outputs must be the
sort-path merge's (``jit(agg_merge)``) bit for bit: the same groups in the
same order, float sums added in the same order, the same capacity. A float
key, or a key space past ``_SLOT_ID_MAX_SLOTS``, keeps the sort path, and
``merge_slot_sorted_batches`` says which ran.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from blaze_tpu.config import Config
from blaze_tpu.ops import agg_device as A
from blaze_tpu.utils.device import DEVICE_STATS
from tests.test_agg_sorted import FLOAT_KEYS, _valid, _value_planes
from tests.test_dense_agg import (CAPACITY, KINDS, NARROW, NSTATE, WIDE,
                                  _state_dtypes)
from tests.util import jaxpr_eqns

I32, I64 = np.iinfo(np.int32), np.iinfo(np.int64)


def _key_plane(rng, dtype, draw, cap):
    """(data, valid) of one key; every draw has nulls but ``all_null``."""
    if draw == "floats":
        return rng.choice(np.array(FLOAT_KEYS, dtype), cap), _valid(rng, cap)
    data = {
        "few": lambda: rng.integers(-3, 4, cap),
        "negative": lambda: rng.integers(-10**6 - 40, -10**6, cap),
        "items": lambda: rng.integers(1, 18_001, cap),  # ss_item_sk
        "dates": lambda: rng.integers(2_450_816, 2_452_642, cap),
        "codes": lambda: rng.integers(0, 40, cap),  # a dictionary's codes
        "all_null": lambda: rng.integers(-5, 5, cap),
        "full_span": lambda: rng.choice(
            np.array([I64.min, -1, 0, I64.max], np.int64), cap),
        "int32_span": lambda: rng.choice(
            np.array([I32.min, 0, I32.max], np.int64), cap),
    }[draw]().astype(dtype)
    valid = np.zeros(cap, bool) if draw == "all_null" else _valid(rng, cap)
    return data, valid


def _state_planes(rng, name, cap):
    """A partial state's (data, valid) columns, as ``merge_inputs`` draws
    them; STDDEV_SAMP's moment state is eight int64 planes, a limb or the
    count each, every plane under its own validity."""
    if name == "moment":
        return [(rng.integers(0, 1 << 32, cap), _valid(rng, cap, 0.05))
                for _ in range(A._MOMENT_PLANES)]
    kind = KINDS[name][0][0]
    cols = []
    for i, dt in enumerate(_state_dtypes(name)):
        if dt == "int64" and (kind == "count" or (
                kind.startswith("avg") and i == NSTATE[kind] - 1)):
            data = rng.integers(0, 4, cap)
        elif kind in ("sum3", "avg3", "minw", "maxw") and i < 3:
            data = _value_planes(rng, "wide3", cap, "random")[i]
        else:
            (data,) = _value_planes(rng, dt, cap, "random")
        cols.append((data, _valid(rng, cap, 0.05)))
    return cols


# name -> (capacity, rows, (key dtype, draw) a key, aggregates, whether the
# packed id merges them)
CASES = {
    "one_int64_key": (128, 100, [("int64", "few")], NARROW, True),
    "one_int32_key": (128, 100, [("int32", "negative")], NARROW, True),
    "two_keys_negative": (1024, 777, [("int64", "negative"),
                                      ("int32", "few")], NARROW, True),
    "wide_kinds": (128, 100, [("int64", "few")], WIDE, True),
    "wide_kinds_two_keys": (1024, 777, [("int32", "few"),
                                        ("int64", "negative")], WIDE, True),
    "moments": (1024, 777, [("int64", "items")],
                ["moment", "count"], True),
    "dictionary_codes": (1024, 777, [("int32", "codes"), ("int32", "codes")],
                         ["sum", "count", "avg", "sum3"], True),
    "q51_keys_and_a_float_sum": (4096, 3000, [("int64", "items"),
                                              ("int64", "dates")],
                                 ["sum_f32", "sum", "count"], True),
    "int32_keys_across_their_span": (1024, 777, [("int32", "int32_span")],
                                     NARROW, True),
    "a_key_all_null": (1024, 777, [("int32", "codes"), ("int64", "all_null")],
                       ["sum", "count", "max_f32"], True),
    "every_key_all_null": (128, 100, [("int64", "all_null"),
                                      ("int32", "all_null")], NARROW, True),
    "no_row": (128, 0, [("int64", "few")], ["sum", "count"], True),
    "past_the_widest_id": (1024, 777, [("int64", "full_span"),
                                       ("int64", "few")],
                           ["sum", "count"], False),
    "a_float_key": (1024, 777, [("float32", "floats"), ("int64", "few")],
                    ["sum", "count"], False),
}


class _Metrics:
    def __init__(self):
        self.counts = {}

    def add(self, name, n):
        self.counts[name] = self.counts.get(name, 0) + n


def _merger(kinds, metrics):
    """The merge of a ``DeviceMergeAgger`` over these kinds, as the chip
    plans it (no radix table), without the operator around it."""
    agger = object.__new__(A.DeviceMergeAgger)
    agger.conf, agger.kinds, agger.metrics = Config(radix_agg=False), kinds, \
        metrics
    return agger


def _inputs(case, seed):
    cap, rows, keys, names, _engaged = CASES[case]
    rng = np.random.default_rng(seed)
    exists = np.arange(cap) < rows
    flat = []
    for dtype, draw in keys:
        d, v = _key_plane(rng, dtype, draw, cap)
        flat += [d, v & exists]  # run() hands the keys' validity masked
    states = [_state_planes(rng, n, cap) for n in names]
    for cols in states:
        flat += [p for d, v in cols for p in (d, v)]
    kinds = tuple("moment" if n == "moment" else KINDS[n][0][0]
                  for n in names)
    state_dtypes = tuple(tuple(str(d.dtype) for d, _ in cols)
                         for cols in states)
    key_dtypes = tuple(dtype for dtype, _ in keys)
    return (jnp.asarray(exists), [jnp.asarray(p) for p in flat], key_dtypes,
            kinds, state_dtypes)


def _bits(x):
    x = np.asarray(x)
    return x.view(f"u{x.dtype.itemsize}") if x.dtype.kind == "f" else x


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_packed_id_merges_as_the_sort_path_bit_for_bit(case):
    """Over nulls in every key, negative keys, int32 and int64 keys and
    dictionary codes, every merge kind (the wide kinds and STDDEV_SAMP's
    moments among them), a batch whose key is all null and one with no row:
    the merge's outputs are ``jit(agg_merge)``'s plane for plane, bit for bit
    (group order and float sums included), and the counter moved. A float
    key or a key space past the widest id keeps ``jit(agg_merge)`` itself,
    and the counter stays."""
    cap, rows, _keys, _names, engaged = CASES[case]
    exists, flat, key_dtypes, kinds, state_dtypes = _inputs(
        case, sum(map(ord, case)))
    metrics = _Metrics()
    s0 = DEVICE_STATS.snapshot()
    got, num_groups, out_cap = _merger(kinds, metrics)._merge(
        exists, flat, key_dtypes, state_dtypes, cap)
    s1 = DEVICE_STATS.snapshot()
    want = A._merge_kernel(key_dtypes, kinds, state_dtypes, cap)(exists, *flat)
    assert out_cap == cap
    assert num_groups == int(want[0])
    assert (num_groups > 0) == (rows > 0)
    assert len(got) == len(want)
    for i, (g, w) in enumerate(zip(got, want)):
        assert g.dtype == w.dtype and g.shape == w.shape, i
        assert np.array_equal(_bits(g), _bits(w)), i
    moved = s1["merge_slot_sorted_batches"] - s0["merge_slot_sorted_batches"]
    assert moved == int(engaged)
    assert metrics.counts == ({"merge_slot_sorted_batches": 1}
                              if engaged else {})
    # the probe's sync and the merge's own, or the merge's alone where a key
    # is no integer plane
    assert s1["sync_calls"] - s0["sync_calls"] == \
        (1 if case == "a_float_key" else 2)


def test_the_slot_plan_numbers_the_keys_from_their_ranges():
    """Per key the null's code 0, then value - base + 1, key 0 most
    significant; a key with no valid row takes the null's slot and one
    beside it."""
    exists, flat, key_dtypes, kinds, _dts = _inputs("a_key_all_null", 3)
    table = _merger(kinds, None)._slot_plan(flat, exists, key_dtypes, 1024)
    codes = np.asarray(flat[0])[np.asarray(flat[1])]
    bases, sizes, shifts = table
    assert bases[0] == codes.min() and sizes[0] >= codes.max() - codes.min() + 2
    assert (bases[1], sizes[1], shifts[1]) == (0, 2, 0)
    assert shifts[0] == 1


@pytest.mark.parametrize("names", [["sum", "count"], NARROW, WIDE,
                                   ["moment"]],
                         ids=["q51", "narrow", "wide", "moment"])
@pytest.mark.parametrize("key_dtypes", [("int64", "int64"), ("int32",)])
def test_the_packed_id_merge_sorts_once_and_scatters_nothing(key_dtypes,
                                                            names):
    """``jit(agg_merge_sorted)`` at a scan batch's capacity: no scatter, no
    ``cond`` or ``while``; of its keys ONE sort, of the id's two uint32
    halves and the row iota (the other sort is the shared emit's, of the
    groups' last-row flags); and its planes move by two gathers with
    batch-sized indices, one in and one out."""
    kinds = tuple("moment" if n == "moment" else KINDS[n][0][0]
                  for n in names)
    state_dtypes = tuple(("int64",) * A._MOMENT_PLANES if n == "moment"
                         else _state_dtypes(n) for n in names)
    kernel = A._slot_merge_kernel(key_dtypes, kinds, state_dtypes, CAPACITY)

    def plane(dt):
        return jax.ShapeDtypeStruct((CAPACITY,), jnp.dtype(dt))

    avals = [plane(bool), jax.ShapeDtypeStruct((3, len(key_dtypes)),
                                               jnp.int64)]
    for kd in key_dtypes:
        avals += [plane(kd), plane(bool)]
    avals += [plane(dt) for dts in state_dtypes for d in dts
              for dt in (d, bool)]
    eqns = list(jaxpr_eqns(jax.make_jaxpr(kernel)(*avals).jaxpr))
    names_seen = {e.primitive.name for e in eqns}
    assert not [n for n in names_seen if n.startswith("scatter")]
    assert "cond" not in names_seen and "while" not in names_seen
    sorts = [[str(v.aval.dtype) for v in e.invars] for e in eqns
             if e.primitive.name == "sort"]
    assert sorts == [["uint32", "uint32", "int32"], ["uint8", "int32"]]
    gathers = [e.invars[1].aval.shape[0] for e in eqns
               if e.primitive.name == "gather"]
    assert gathers == [CAPACITY, CAPACITY]
