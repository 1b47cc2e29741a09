"""STDDEV_SAMP: exact integer moments (n, Σx, Σx²) on the device, finished
once a group on the host. Every form that reduces them — the slot table (a
masked table, a slot-sorted one, a wide one), the sort path, the radix
kernels of the CPU backend, the host table — against Python's ``Fraction``
exactly and against Acero's ``stddev`` (ddof=1) to 1e-12 relative; FINAL
across four partitions; NULL arguments, groups of no row and of one; a long
argument whose Σx² passes 2^63 (the limbs) and a group whose N passes 2^53
(the counted finish by Python integers)."""

import math
from fractions import Fraction

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pytest

from blaze_tpu.config import Config
from blaze_tpu.ir import exprs as E
from blaze_tpu.ir import nodes as N
from blaze_tpu.ir import types as T
from blaze_tpu.ops import aggfns
from blaze_tpu.runtime.session import Session
from blaze_tpu.utils.device import DEVICE_STATS

SCHEMA = T.Schema.of(("k", T.I64), ("x", T.I64))
PARTS = 4


def _exact_stddev(values):
    """The correctly rounded sample standard deviation of integers, or None
    below two of them."""
    n = len(values)
    if n < 2:
        return None
    s, q = sum(values), sum(v * v for v in values)
    return math.sqrt(float(Fraction(n * q - s * s, n * (n - 1))))


def _expected(keys, xs):
    groups = {}
    for k, x in zip(keys, xs):
        vals = groups.setdefault(k, [])
        if x is not None:
            vals.append(x)
    return {k: (len(v), _exact_stddev(v)) for k, v in groups.items()}


def _plan(schema=SCHEMA, agg_fn=E.AggFunction.STDDEV_SAMP):
    scan = N.FFIReader(schema=schema, resource_id="src", num_partitions=PARTS)
    aggs = [("sd", E.AggExpr(agg_fn, [E.Column("x")])),
            ("n", E.AggExpr(E.AggFunction.COUNT, [E.Column("x")]))]
    partial = N.Agg(scan, E.AggExecMode.HASH_AGG, [("k", E.Column("k"))], [
        N.AggColumn(a, E.AggMode.PARTIAL, name) for name, a in aggs])
    exchange = N.ShuffleExchange(partial, N.HashPartitioning(
        [E.Column("k")], PARTS))
    return N.Agg(exchange, E.AggExecMode.HASH_AGG, [("k", E.Column("k"))], [
        N.AggColumn(a, E.AggMode.FINAL, name) for name, a in aggs])


def _run(conf, keys, xs, x_type=pa.int64(), batch_rows=None):
    table = pa.table({"k": pa.array(keys, pa.int64()),
                      "x": pa.array(xs, x_type)})
    rows = batch_rows or max(1, -(-len(keys) // PARTS))
    parts = [table.slice(i, rows).to_batches()
             for i in range(0, len(keys), rows)]
    parts += [[] for _ in range(PARTS - len(parts))]
    session = Session(conf=conf)
    try:
        session.resources["src"] = lambda p: parts[p]
        schema = SCHEMA if x_type == pa.int64() else T.Schema.of(
            ("k", T.I64), ("x", T.F64))
        before = DEVICE_STATS.snapshot()
        got = session.execute_to_table(_plan(schema))
        after = DEVICE_STATS.snapshot()
    finally:
        session.close()
    counted = {k: after[k] - before[k] for k in (
        "moment_device_batches", "moment_host_batches", "moment_exact_groups")}
    return got.to_pydict(), counted


# the plans the forms come from: the chip's (no radix table), the CPU's
# (radix tables), the sort path alone (no slot table)
FORMS = {
    "chip": dict(radix_agg=False, fused_filter_agg=False),
    "cpu": dict(),
    "sorted": dict(radix_agg=False, dense_agg=False),
}


def _conf(form):
    return Config(**FORMS[form])


def _draw(rng, rows, groups, lo, hi, nulls=0.1):
    keys = rng.integers(0, groups, rows).tolist()
    xs = rng.integers(lo, hi, rows).tolist()
    return keys, [None if rng.random() < nulls else x for x in xs]


@pytest.mark.parametrize("form", ["chip", "cpu", "sorted"])
@pytest.mark.parametrize("groups", [7, 3000, 40_000])
def test_device_forms_equal_the_fraction(form, groups):
    """Few groups: a masked slot table; thousands: a slot-sorted one; tens of
    thousands: past `dense_agg_max_buckets`, wide on the chip's plan."""
    rng = np.random.default_rng(groups)
    keys, xs = _draw(rng, 60_000, groups, -1000, 1000)
    # a group of one row and a group of only NULLs: both NULL
    keys += [10**6, 10**6 + 1, 10**6 + 1]
    xs += [5, None, None]
    got, counted = _run(_conf(form), keys, xs)
    want = _expected(keys, xs)
    assert len(got["k"]) == len(want)
    for k, sd, n in zip(got["k"], got["sd"], got["n"]):
        assert (n, sd) == want[k], k
    assert counted["moment_device_batches"] >= PARTS
    assert counted["moment_host_batches"] == 0


def test_host_table_keeps_the_same_state(monkeypatch):
    """The generic table (here: the device path declined) accumulates the
    same exact planes, and counts its batches as the host's."""
    from blaze_tpu.ops import agg_device

    monkeypatch.setattr(agg_device, "supports_device_partial",
                        lambda op, schema: False)
    monkeypatch.setattr(agg_device, "supports_device_merge",
                        lambda op, schema: False)
    rng = np.random.default_rng(5)
    keys, xs = _draw(rng, 20_000, 50, 0, 10**6)
    got, counted = _run(_conf("sorted"), keys, xs)
    want = _expected(keys, xs)
    for k, sd, n in zip(got["k"], got["sd"], got["n"]):
        assert (n, sd) == want[k], k
    assert counted["moment_device_batches"] == 0
    assert counted["moment_host_batches"] >= PARTS


@pytest.mark.parametrize("form", ["chip", "cpu"])
def test_long_arguments_past_two_to_the_63(form):
    """Σx² passes 2^63 within a group (the limbs), the largest longs and
    their negatives among them; N passes 2^53, so the finish goes by Python
    integers and counts the groups."""
    big = [2**63 - 1, -(2**63), 2**62 + 12345, -(2**40) - 7, 3 * 2**31, 17]
    keys = [i % 3 for i in range(len(big) * 40)]
    xs = [big[(i * 7) % len(big)] for i in range(len(big) * 40)]
    got, counted = _run(_conf(form), keys, xs)
    want = _expected(keys, xs)
    for k, sd, n in zip(got["k"], got["sd"], got["n"]):
        assert (n, sd) == want[k], k
    assert counted["moment_exact_groups"] == 3


def test_acero_agrees_to_1e_12_on_dense_groups():
    """Groups of thousands of rows: the exact finish against Acero's running
    double (ddof=1), which the 1e-12 covers at up to 10^4 rows a group."""
    rng = np.random.default_rng(11)
    keys, xs = _draw(rng, 80_000, 12, 1, 101, nulls=0.02)
    got, _ = _run(_conf("chip"), keys, xs)
    acero = pa.table({"k": pa.array(keys, pa.int64()),
                      "x": pa.array(xs, pa.int64())}).group_by("k").aggregate(
        [("x", "stddev", pc.VarianceOptions(ddof=1))])
    ref = dict(zip(acero["k"].to_pylist(), acero["x_stddev"].to_pylist()))
    for k, sd in zip(got["k"], got["sd"]):
        assert sd == pytest.approx(ref[k], rel=1e-12, abs=0)


def test_double_argument_stays_on_the_host_table():
    """A DOUBLE argument keeps Spark's (n, mean, m2) in double, counted as
    the host's; it agrees with the two-pass double to 1e-12."""
    rng = np.random.default_rng(3)
    keys = rng.integers(0, 20, 30_000).tolist()
    xs = (rng.random(30_000) * 1e3).tolist()
    xs[::97] = [None] * len(xs[::97])
    keys += [99]
    xs += [1.5]
    got, counted = _run(_conf("chip"), keys, xs, x_type=pa.float64())
    groups = {}
    for k, x in zip(keys, xs):
        if x is not None:
            groups.setdefault(k, []).append(x)
    for k, sd in zip(got["k"], got["sd"]):
        vals = np.array(groups[k])
        if len(vals) < 2:
            assert sd is None
            continue
        assert sd == pytest.approx(np.std(vals, ddof=1), rel=1e-12, abs=0)
    assert counted["moment_host_batches"] >= PARTS
    assert counted["moment_device_batches"] == 0


@pytest.mark.parametrize("x", [0, 1, -1, 2**31, 2**32 + 5, 2**62 - 3, 2**63 - 1,
                               -(2**63), -(2**35) + 9])
def test_row_planes_hold_x_and_its_square(x):
    import jax.numpy as jnp

    planes = [int(p[0]) for p in aggfns.moment_row_planes(
        jnp.asarray([x], jnp.int64))]
    s0, s1, s2, q0, q1, q2, q3 = planes
    assert s0 + (s1 << 32) + (s2 << 64) == x
    assert q0 + (q1 << 32) + (q2 << 64) + (q3 << 96) == x * x
    assert all(0 <= p < 2**34 for p in (s0, s1, q0, q1, q2, q3))


def test_finish_counts_the_groups_past_two_to_the_53():
    # n = 3 rows of 0, 2^27, 2^28 in limbs: N = 3·(2^54 + 2^56) - (3·2^27)²
    xs = [0, 2**27, 2**28]
    n, s, q = len(xs), sum(xs), sum(x * x for x in xs)
    packed = np.array([[n], [s & 0xFFFFFFFF], [s >> 32], [0],
                       [q & 0xFFFFFFFF], [(q >> 32) & 0xFFFFFFFF],
                       [(q >> 64) & 0xFFFFFFFF], [q >> 96]], np.int64)
    out, valid, exact = aggfns.moment_stddev(packed)
    assert valid.tolist() == [True] and exact == 1
    assert out[0] == _exact_stddev(xs)
