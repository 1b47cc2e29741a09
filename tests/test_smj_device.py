"""The sort-merge join's device path against a plain reference written here
(a dict of key tuples, no engine code): every join type over one, two and
three fixed-width keys, with null keys on either side, many-to-many runs, an
empty side and a partition of several batches. Each case asserts the answer,
which path ran (`smj_device_joins` / `smj_host_joins`), and that the device
path pulled no key column. A string key and a `condition` pin the host path."""

import dataclasses

import numpy as np
import pyarrow as pa
import pytest

from blaze_tpu.config import get_config
from blaze_tpu.ir import exprs as E
from blaze_tpu.ir.nodes import JoinType
from blaze_tpu.ops.base import ExecContext
from blaze_tpu.ops.joins.smj import SortMergeJoinExec
from blaze_tpu.ops.sort import SortExec
from blaze_tpu.utils.device import DEVICE_STATS
from tests.util import mem_scan

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
