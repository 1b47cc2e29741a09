"""The sort-merge join's device path against a plain reference written here
(a dict of key tuples, no engine code): every join type over one, two and
three fixed-width keys, with null keys on either side, many-to-many runs, an
empty side and a partition of several batches. Each case asserts the answer,
which path ran (`smj_device_joins` / `smj_host_joins`), and that the device
path pulled no key column. A string key and a `condition` pin the host path.
The slot map (``smj._slots``: which joint position fills each output slot)
is held to ``np.searchsorted`` on its own, and the programs that use it to
one scatter and no loop."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pytest

from blaze_tpu.config import get_config
from blaze_tpu.core import kernels as K
from blaze_tpu.ir import exprs as E
from blaze_tpu.ir.nodes import JoinType
from blaze_tpu.ops.base import ExecContext
from blaze_tpu.ops.joins import smj
from blaze_tpu.ops.joins.smj import SortMergeJoinExec
from blaze_tpu.ops.sort import SortExec
from blaze_tpu.utils.device import DEVICE_STATS
from tests.util import jaxpr_eqns, mem_scan

KEY_RANGES = (7, 3, 2)  # few distinct values a key: runs on both sides
CASES = ("nulls", "many_to_many", "empty_left", "empty_right",
         "several_batches")


def _side(rng, prefix, rows, nkeys, nulls, batches):
    """(pydict of arrow arrays, key tuples with None for a null, payloads)."""
    data, keys = {}, []
    for k in range(nkeys):
        values = rng.integers(0, KEY_RANGES[k], rows)
        mask = rng.random(rows) < 0.2 if nulls else np.zeros(rows, bool)
        data[f"{prefix}k{k}"] = pa.array(values, type=pa.int64(), mask=mask)
        keys.append([None if m else int(v) for v, m in zip(values, mask)])
    payload = list(range(rows)) if prefix == "l" else list(range(1000, 1000 + rows))
    data[f"{prefix}v"] = pa.array(payload, type=pa.int64())
    return data, list(zip(*keys)) if rows else [], payload


def _reference(jt, lkeys, lvals, rkeys, rvals):
    """Rows the join must give, as a sorted list of tuples (key columns of
    both sides, payloads; None where a side is missing)."""
    nk = len(lkeys[0]) if lkeys else len(rkeys[0]) if rkeys else 1
    by_key = {}
    for j, key in enumerate(rkeys):
        if None not in key:
            by_key.setdefault(key, []).append(j)
    lrow = lambda i: lkeys[i] + (lvals[i],)
    rrow = lambda j: rkeys[j] + (rvals[j],)
    lnull, rnull = (None,) * (nk + 1), (None,) * (nk + 1)
    pairs = [(i, j) for i, key in enumerate(lkeys) if None not in key
             for j in by_key.get(key, ())]
    lhit = {i for i, _ in pairs}
    rhit = {j for _, j in pairs}
    lmiss = [i for i in range(len(lkeys)) if i not in lhit]
    rmiss = [j for j in range(len(rkeys)) if j not in rhit]
    out = []
    if jt in (JoinType.INNER, JoinType.LEFT, JoinType.RIGHT, JoinType.FULL):
        out += [lrow(i) + rrow(j) for i, j in pairs]
    if jt in (JoinType.LEFT, JoinType.FULL):
        out += [lrow(i) + rnull for i in lmiss]
    if jt in (JoinType.RIGHT, JoinType.FULL):
        out += [lnull + rrow(j) for j in rmiss]
    if jt == JoinType.LEFT_SEMI:
        out = [lrow(i) for i in sorted(lhit)]
    if jt == JoinType.LEFT_ANTI:
        out = [lrow(i) for i in lmiss]
    if jt == JoinType.RIGHT_SEMI:
        out = [rrow(j) for j in sorted(rhit)]
    if jt == JoinType.RIGHT_ANTI:
        out = [rrow(j) for j in rmiss]
    if jt == JoinType.EXISTENCE:
        out = [lrow(i) + (i in lhit,) for i in range(len(lkeys))]
    return _sorted(out)


def _sorted(rows):
    return sorted(rows, key=lambda t: tuple((v is None, v) for v in t))


def _run(op, ctx):
    """The operator's batches of partition 0, the bytes pulled to the host
    while it ran, and the rows as sorted tuples."""
    pulled0 = DEVICE_STATS.snapshot()["to_host_bytes"]
    batches = list(op.execute(0, ctx))
    pulled = DEVICE_STATS.snapshot()["to_host_bytes"] - pulled0
    rows = []
    for b in batches:
        table = b.to_arrow().to_pydict()
        rows += list(zip(*(table[name] for name in op.schema.names)))
    return batches, pulled, _sorted(rows)


def _join(ldata, rdata, nkeys, jt, batches=1, **kw):
    on = [(E.Column(f"lk{k}"), E.Column(f"rk{k}")) for k in range(nkeys)]
    left = SortExec(mem_scan(ldata, num_batches=batches),
                    [E.SortOrder(l) for l, _ in on])
    right = SortExec(mem_scan(rdata, num_batches=batches),
                     [E.SortOrder(r) for _, r in on])
    return SortMergeJoinExec(left, right, on, jt, **kw)


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("nkeys", [1, 2, 3])
@pytest.mark.parametrize("jt", list(JoinType), ids=lambda jt: jt.name)
def test_device_merge_join_equals_the_reference(jt, nkeys, case):
    rng = np.random.default_rng(1000 * nkeys + CASES.index(case))
    nl = 0 if case == "empty_left" else 120
    nr = 0 if case == "empty_right" else 90
    batches = 4 if case == "several_batches" else 1
    nulls = case in ("nulls", "several_batches")
    ldata, lkeys, lvals = _side(rng, "l", nl, nkeys, nulls, batches)
    rdata, rkeys, rvals = _side(rng, "r", nr, nkeys, nulls, batches)
    conf = get_config()
    if case == "several_batches":  # more output rows than a batch holds
        conf = dataclasses.replace(conf, batch_size=64)
    ctx = ExecContext(conf=conf)
    out, pulled, got = _run(_join(ldata, rdata, nkeys, jt, batches), ctx)

    assert got == _reference(jt, lkeys, lvals, rkeys, rvals)
    assert ctx.metrics.totals(["smj_device_joins", "smj_host_joins"]) == {
        "smj_device_joins": 1, "smj_host_joins": 0}
    # no key column crossed to the host: nothing did, here
    assert pulled < max(1, sum(b.nbytes() for b in out))
    assert pulled == 0
    assert all(b.num_rows <= conf.batch_size for b in out)
    if case == "several_batches" and len(got) > conf.batch_size:
        assert len(out) > 1
    if case == "many_to_many" and nkeys == 1 and jt == JoinType.INNER:
        assert len(got) > nl  # runs of several rows met runs of several rows


def test_pairs_come_left_major_in_key_order():
    ldata = {"lk0": pa.array([2, 1, 2], type=pa.int64()),
             "lv": pa.array([0, 1, 2], type=pa.int64())}
    rdata = {"rk0": pa.array([2, 2, 1], type=pa.int64()),
             "rv": pa.array([10, 11, 12], type=pa.int64())}
    ctx = ExecContext()
    (batch,) = list(_join(ldata, rdata, 1, JoinType.INNER).execute(0, ctx))
    got = batch.to_arrow().to_pydict()
    assert list(zip(got["lv"], got["rv"])) == [
        (1, 12), (0, 10), (0, 11), (2, 10), (2, 11)]


def test_a_host_payload_column_rides_the_device_path():
    ldata = {"lk0": pa.array([1, 2, 2, None], type=pa.int64()),
             "lv": pa.array(["a", "b", "c", "d"])}
    rdata = {"rk0": pa.array([2, 3, None], type=pa.int64()),
             "rv": pa.array(["x", "y", "z"])}
    ctx = ExecContext()
    _out, _pulled, got = _run(_join(ldata, rdata, 1, JoinType.FULL), ctx)
    assert got == _sorted([
        (2, "b", 2, "x"), (2, "c", 2, "x"), (1, "a", None, None),
        (None, "d", None, None), (None, None, 3, "y"), (None, None, None, "z")])
    assert ctx.metrics.total("smj_device_joins") == 1


@pytest.mark.parametrize("why", ["string_key", "condition"])
def test_the_host_path_keeps_var_width_keys_and_conditions(why):
    ldata = {"lk0": pa.array([1, 2, 2, 3], type=pa.int64()),
             "lv": pa.array([5, 6, 7, 8], type=pa.int64())}
    rdata = {"rk0": pa.array([2, 3, 4], type=pa.int64()),
             "rv": pa.array([6, 9, 1], type=pa.int64())}
    kw = {}
    want = [(2, 6, 2, 6), (2, 7, 2, 6), (3, 8, 3, 9)]
    if why == "string_key":
        for data, key in ((ldata, "lk0"), (rdata, "rk0")):
            data[key] = pa.array([str(v) for v in data[key].to_pylist()])
        want = [(str(a), b, str(c), d) for a, b, c, d in want]
    else:
        kw["condition"] = E.BinaryExpr(E.BinaryOp.GT, E.Column("lv"),
                                       E.Column("rv"))
        want = [row for row in want if row[1] > row[3]]
    ctx = ExecContext()
    _out, _pulled, got = _run(_join(ldata, rdata, 1, JoinType.INNER, **kw), ctx)
    assert got == _sorted(want)
    assert ctx.metrics.totals(["smj_device_joins", "smj_host_joins"]) == {
        "smj_device_joins": 0, "smj_host_joins": 1}


def _prefix_sums(rng, case):
    """(inclusive prefix sums of a selection over the joint order, count):
    most positions wide 0, runs of 1 to 40 rows."""
    n = 300
    w = np.where(rng.random(n) < 0.7, 0, rng.integers(1, 41, n))
    if case == "no_live_row":
        w[:] = 0
    elif case == "straddle":  # one long run holds slots 60 .. 99
        w[:] = 0
        w[[3, 50, 51, 200]] = [60, 40, 1, 7]
    sums = np.cumsum(w).astype(np.int64)
    return sums, int(sums[-1])


SLOT_CASES = [("zero_widths", 0, 64), ("zero_widths", "middle", 64),
              ("zero_widths", "last", 64), ("zero_widths", 0, 8192),
              ("straddle", 64, 64), ("straddle", 0, 64), ("straddle", 96, 32),
              ("no_live_row", 0, 64)]


@pytest.mark.parametrize("case,offset,cap", SLOT_CASES,
                         ids=[f"{c}-{o}-{k}" for c, o, k in SLOT_CASES])
def test_the_slot_map_finds_what_a_binary_search_finds(case, offset, cap):
    """Every slot of a batch — live or past ``count`` — takes the position
    ``np.searchsorted(sums, slot, side="right")`` names, clipped into the
    joint order, as the search the map replaced gave it: zero-width
    positions skipped, a run straddling the batch's first slot, the first,
    a middle and the last batch, a batch wider than what is left (``cap >
    count``), and a selection with no row (``count`` 0)."""
    sums, count = _prefix_sums(np.random.default_rng(7), case)
    if offset == "middle":
        offset = count // 2
    elif offset == "last":
        offset = (count - 1) // cap * cap
    slot_fn = jax.jit(smj._slots, static_argnums=3)
    slot, live, pos = slot_fn(jnp.asarray(sums), np.int64(offset),
                              np.int64(count), cap)
    want_slot = offset + np.arange(cap)
    want = np.clip(np.searchsorted(sums, want_slot, side="right"), 0,
                   len(sums) - 1)
    np.testing.assert_array_equal(np.asarray(slot), want_slot)
    np.testing.assert_array_equal(np.asarray(live), want_slot < count)
    np.testing.assert_array_equal(np.asarray(pos), want)
    if case == "no_live_row":
        assert count == 0 and not np.asarray(live).any()
    if case == "straddle" and offset == 64:
        assert (np.asarray(pos)[:36] == 50).all()  # the run that began at 60


def test_a_run_straddling_a_batch_keeps_its_pairs_in_order():
    """A 10 x 10 many-to-many run whose 100 pairs begin at slot 30 of a
    64-row batch and run through two more batches: the pairs come left-major
    in key order, each left row's right rows in input order, across the
    batch boundaries."""
    lkeys = [1] * 30 + [5] * 10 + [9] * 4
    rkeys = [1] + [5] * 10 + [7]
    ldata = {"lk0": pa.array(lkeys, type=pa.int64()),
             "lv": pa.array(range(len(lkeys)), type=pa.int64())}
    rdata = {"rk0": pa.array(rkeys, type=pa.int64()),
             "rv": pa.array(range(100, 100 + len(rkeys)), type=pa.int64())}
    ctx = ExecContext(conf=dataclasses.replace(get_config(), batch_size=64))
    batches = list(_join(ldata, rdata, 1, JoinType.INNER).execute(0, ctx))
    assert [b.num_rows for b in batches] == [64, 64, 2]
    got = []
    for b in batches:
        table = b.to_arrow().to_pydict()
        got += list(zip(table["lv"], table["rv"]))
    assert got == [(i, 100) for i in range(30)] + [
        (i, j) for i in range(30, 40) for j in range(101, 111)]


def _plane_avals(cap, n):
    return (tuple(jax.ShapeDtypeStruct((cap,), jnp.int64) for _ in range(n)),
            tuple(jax.ShapeDtypeStruct((cap,), jnp.bool_) for _ in range(n)))


def _primitives(fn, *avals):
    return [e.primitive.name for e in jaxpr_eqns(jax.make_jaxpr(fn)(*avals).jaxpr)]


@pytest.mark.parametrize("program", ["smj_pairs", "smj_rows"])
def test_the_slot_map_is_one_scatter_and_no_search_loop(program):
    """``jit(smj_pairs)`` / ``jit(smj_rows)`` at q29's shape (1,048,576 left
    and 131,072 right rows, a 131,072-slot batch): no ``while`` (the binary
    search's loop), no sort, and exactly ONE scatter beyond what the
    programs' ``take_rows_traced`` gathers emit."""
    cap_l, cap_r, cap = 1 << 20, 1 << 17, 1 << 17
    joint = jax.ShapeDtypeStruct((cap_l + cap_r,), jnp.int32)
    sums = jax.ShapeDtypeStruct((cap_l + cap_r,), jnp.int64)
    at = (jax.ShapeDtypeStruct((), jnp.int64),) * 2
    lplanes, rplanes = _plane_avals(cap_l, 5), _plane_avals(cap_r, 4)
    if program == "smj_pairs":
        seen = _primitives(functools.partial(smj.smj_pairs, cap=cap,
                                             cap_r=cap_r),
                           joint, joint, joint, sums, lplanes, rplanes, *at)
        movers = [lplanes, rplanes]
    else:
        seen = _primitives(functools.partial(smj.smj_rows, cap=cap,
                                             base=cap_r),
                           joint, joint, sums, lplanes, *at)
        movers = [lplanes]
    idx = jax.ShapeDtypeStruct((cap,), jnp.int32)
    live = jax.ShapeDtypeStruct((cap,), jnp.bool_)
    moved = [n for planes in movers
             for n in _primitives(K.take_rows_traced, *planes, idx, live)]
    scatters = lambda names: [n for n in names if n.startswith("scatter")]
    assert "while" not in seen
    assert seen.count("sort") == moved.count("sort")
    assert len(scatters(seen)) == len(scatters(moved)) + 1
