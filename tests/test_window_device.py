"""The window's device programs (``ops/window_device``, ``jit(window_scan)``)
against a plain numpy / Python reference: the running frame's SUM / MAX / MIN
/ COUNT and the rank family over partitions that cross batches, NULLs, the
wide decimal that rides as one int64 plane from one window to the next and
into a PARTIAL aggregation, the batch whose sums pass int64, and everyone
else reading such a column as its type."""

from decimal import Decimal

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pytest

from blaze_tpu.core import kernels as K
from blaze_tpu.core.batch import ColumnarBatch, DeviceColumn, HostColumn
from blaze_tpu.ir import exprs as E
from blaze_tpu.ir import types as T
from blaze_tpu.ir.nodes import AggColumn, WindowExpr
from blaze_tpu.ops.base import ExecContext, Operator
from blaze_tpu.ops.window import WindowExec
from blaze_tpu.runtime.metrics import TRIPWIRE_METRICS, MetricNode
from blaze_tpu.utils.device import DEVICE_STATS
from tests.util import mem_scan

RUNNING = ("rows", None, 0)
F = E.AggFunction


def _agg(fn, name, arg="v", frame=RUNNING):
    return WindowExpr("agg", name, agg=E.AggExpr(
        fn, [E.Column(arg)] if arg else []), frame=frame)


def _run(op):
    """(pydict of the output, metrics, the output batches)."""
    m = MetricNode("root")
    out, batches = {}, []
    for b in op.execute(0, ExecContext(), m):
        batches.append(b)
        for k, v in b.to_pydict().items():
            out.setdefault(k, []).extend(v)
    return out, m, batches


def _reference(g, o, v):
    """Row by row, in Python: row_number, rank, dense_rank and the running
    sum / count / max / min of ``v`` (None = NULL) within each run of ``g``."""
    n = len(g)
    out = {k: [None] * n for k in ("rn", "rk", "dr", "sum", "cnt", "max", "min")}
    for i in range(n):
        if i == 0 or g[i] != g[i - 1]:
            rn = rk = dr = 1
            s = c = 0
            mx = mn = None
        else:
            rn += 1
            if o[i] != o[i - 1]:
                rk, dr = rn, dr + 1
        if v[i] is not None:
            s, c = s + v[i], c + 1
            mx = v[i] if mx is None else max(mx, v[i])
            mn = v[i] if mn is None else min(mn, v[i])
        out["rn"][i], out["rk"][i], out["dr"][i] = rn, rk, dr
        out["sum"][i] = s if c else None
        out["cnt"][i] = c
        out["max"][i], out["min"][i] = mx, mn
    return out


# -- the traced twins against their numpy originals ---------------------------


def _masks(rng, n, p_part=0.2, p_peer=0.5, head=True):
    part = rng.random(n) < p_part
    part[0] = not head
    peer = part | (rng.random(n) < p_peer)
    return part, peer


@pytest.mark.parametrize("seed", range(4))
def test_restarting_counters_traced_equals_numpy(seed):
    rng = np.random.default_rng(seed)
    part, peer = _masks(rng, 300, head=bool(seed % 2))
    want = K.restarting_counters(part, peer, 5, 4, 2)
    got = jax.jit(K.restarting_counters_traced)(
        jnp.asarray(part), jnp.asarray(peer), jnp.int64(5), jnp.int64(4),
        jnp.int64(2))
    for w, g in zip(want, got):
        assert np.array_equal(w, np.asarray(g))


def _exact(lo, hi):
    """Python integers of (lo, hi) word planes."""
    return [(int(h) << 64) + (int(l) & (2**64 - 1))
            for l, h in zip(np.asarray(lo), np.asarray(hi))]


@pytest.mark.parametrize("wide", [False, True])
@pytest.mark.parametrize("seed", range(3))
def test_segment_cumsum_traced_equals_numpy(seed, wide):
    rng = np.random.default_rng(seed)
    part, _ = _masks(rng, 257, head=bool(seed % 2))
    vals = rng.integers(-10**15, 10**15, 257)
    valid = rng.random(257) < 0.8
    carry = -7 * 10**15
    want_s, want_c = K.segment_cumsum(vals, valid, part, carry, 3)
    if wide:
        lo, hi, cnt = jax.jit(K.segment_cumsum_wide_traced)(
            jnp.asarray(vals), jnp.asarray(vals) >> 63, jnp.asarray(valid),
            jnp.asarray(part), jnp.int64(carry), jnp.int64(-1), jnp.int64(3))
        assert _exact(lo, hi) == want_s.tolist()
    else:
        got, cnt = jax.jit(K.segment_cumsum_traced)(
            jnp.asarray(vals), jnp.asarray(valid), jnp.asarray(part),
            jnp.int64(carry), jnp.int64(3))
        assert np.array_equal(want_s, np.asarray(got))
    assert np.array_equal(want_c, np.asarray(cnt))


def test_segment_cumsum_wide_traced_is_exact_past_int64():
    """Two words hold every sum, to the last digit either side of 2^63 and
    of -2^63, and a carry of two words seeds the open segment."""
    big = 2**62
    vals = np.array([big, big - 1, 1, -5, 7, big, -big, -big, -big, -1],
                    dtype=np.int64)
    part = np.array([1, 0, 0, 0, 1, 0, 1, 0, 0, 0], dtype=bool)
    run = jax.jit(K.segment_cumsum_wide_traced)
    lo, hi, _c = run(jnp.asarray(vals), jnp.asarray(vals) >> 63,
                     jnp.ones(10, bool), jnp.asarray(part),
                     jnp.int64(0), jnp.int64(0), jnp.int64(0))
    exact = [big, 2 * big - 1, 2 * big, 2 * big - 5, 7, big + 7,
             -big, -2 * big, -3 * big, -3 * big - 1]
    assert _exact(lo, hi) == exact
    fits = np.asarray(hi) == np.asarray(lo) >> 63
    assert fits.tolist() == [-2**63 <= x < 2**63 for x in exact]
    # a carry past int64 (3 * 2^63 + 5) continues its segment
    carry = 3 * 2**63 + 5
    lo, hi, cnt = run(jnp.asarray(vals[:3]), jnp.asarray(vals[:3]) >> 63,
                      jnp.ones(3, bool), jnp.zeros(3, bool),
                      jnp.int64((carry & (2**64 - 1)) - 2**64),
                      jnp.int64(carry >> 64), jnp.int64(2))
    assert _exact(lo, hi) == [carry + big, carry + 2 * big - 1,
                              carry + 2 * big]
    assert np.asarray(cnt).tolist() == [3, 4, 5]


@pytest.mark.parametrize("wide", [False, True])
@pytest.mark.parametrize("is_min", [False, True])
@pytest.mark.parametrize("seed", range(3))
def test_segment_running_reduce_traced_equals_numpy(seed, is_min, wide):
    """One plane against numpy's; two words (values around +-2^64, where the
    low word alone orders wrongly) against Python integers' numpy scan."""
    rng = np.random.default_rng(seed)
    part, _ = _masks(rng, 200, head=True)
    valid = rng.random(200) < 0.7
    carry = None if seed == 0 else 17
    run = jax.jit(K.segment_running_reduce_traced, static_argnames="is_min")
    if wide:
        vals = np.array([int(x) * 2**62 + int(y) for x, y in zip(
            rng.integers(-9, 9, 200), rng.integers(-5, 5, 200))], object)
        lo = np.array([(x & (2**64 - 1)) - (2**64 if x & 2**63 else 0)
                       for x in vals], np.int64)
        hi = np.array([x >> 64 for x in vals], np.int64)
        (ehi, elo), has = run(
            (jnp.asarray(hi), jnp.asarray(lo)), jnp.asarray(valid),
            jnp.asarray(part), is_min=is_min,
            carry_vals=(jnp.int64(0), jnp.int64(carry or 0)),
            carry_has=jnp.bool_(carry is not None))
        got = np.array(_exact(elo, ehi), object)
    else:
        vals = rng.integers(-1000, 1000, 200)
        (ext,), has = run(
            (jnp.asarray(vals),), jnp.asarray(valid), jnp.asarray(part),
            is_min=is_min, carry_vals=(jnp.int64(carry or 0),),
            carry_has=jnp.bool_(carry is not None))
        got = np.asarray(ext)
    want = K.segment_running_reduce(
        np.where(valid, vals, None) if wide else vals, valid, part, is_min,
        carry)
    _s, cnt = K.segment_cumsum(np.zeros(200, np.int64), valid, part, 0,
                               int(carry is not None))
    has = np.asarray(has)
    assert np.array_equal(has, cnt > 0)
    assert list(want[has]) == list(got[has])


# -- the operator --------------------------------------------------------------


def _sorted_data(seed, n, groups, nulls=0.2, vmax=1000):
    rng = np.random.default_rng(seed)
    g = np.sort(rng.integers(0, groups, n))
    o = np.concatenate([np.sort(rng.integers(0, 6, c))
                        for c in np.bincount(g, minlength=groups) if c])
    v = [None if rng.random() < nulls else int(x)
         for x in rng.integers(-vmax, vmax, n)]
    return g.tolist(), o.tolist(), v


@pytest.mark.parametrize("n,groups,batches", [
    (500, 40, 7),     # partitions cross batch boundaries
    (400, 3, 9),      # a partition spans three batches and more; a batch
                      # that is one partition's middle
    (64, 64, 5),      # near one-row partitions
    (1, 1, 1),        # one row
])
def test_device_window_running_frame_and_ranks(n, groups, batches):
    g, o, v = _sorted_data(n + groups, n, groups)
    data = {"g": pa.array(g, pa.int64()), "o": pa.array(o, pa.int64()),
            "v": pa.array(v, pa.int64())}
    op = WindowExec(
        mem_scan(data, num_batches=batches),
        [WindowExpr("row_number", "rn"), WindowExpr("rank", "rk"),
         WindowExpr("dense_rank", "dr"), _agg(F.SUM, "sum"),
         _agg(F.COUNT, "cnt"), _agg(F.MAX, "max"), _agg(F.MIN, "min"),
         _agg(F.COUNT, "all", arg=None)],
        [E.Column("g")], [E.SortOrder(E.Column("o"))])
    out, m, outs = _run(op)
    want = _reference(g, o, v)
    for k in want:
        assert out[k] == want[k], k
    assert out["all"] == want["rn"]
    assert m.total("window_device_batches") == len(outs)
    assert m.total("window_host_batches") == 0
    assert m.total("window_group_loops") == 0
    assert m.total("window_segments") == len(set(g))
    assert all(isinstance(c, DeviceColumn) for b in outs for c in b.columns)


def test_device_window_null_keys_and_no_partition():
    data = {"a": pa.array([1, 1, None, None, 2], pa.int64()),
            "o": pa.array([1, None, None, 3, 3], pa.int64()),
            "v": pa.array([5, 6, 7, None, 9], pa.int32())}
    op = WindowExec(mem_scan(data, num_batches=2),
                    [WindowExpr("rank", "rk"), _agg(F.SUM, "s"),
                     _agg(F.MAX, "mx")],
                    [E.Column("a")], [E.SortOrder(E.Column("o"))])
    out, m, _ = _run(op)
    assert out["rk"] == [1, 2, 1, 2, 1]
    assert out["s"] == [5, 11, 7, 7, 9]
    assert out["mx"] == [5, 6, 7, 7, 9]
    assert m.total("window_segments") == 3
    # no PARTITION BY: one partition over every batch
    op = WindowExec(mem_scan(data, num_batches=3),
                    [WindowExpr("row_number", "rn"), _agg(F.SUM, "s")],
                    [], [E.SortOrder(E.Column("o"))])
    out, m, _ = _run(op)
    assert out["rn"] == [1, 2, 3, 4, 5]
    assert out["s"] == [5, 11, 18, 18, 27]
    assert m.total("window_segments") == 1
    assert m.total("window_host_batches") == 0


@pytest.mark.parametrize("kinds,limit", [
    (("rank",), 2), (("dense_rank",), 2), (("row_number",), 3),
    (("row_number", "rank"), 1)])
def test_device_window_group_limit(kinds, limit):
    g, o, _v = _sorted_data(3, 300, 25)
    data = {"g": pa.array(g, pa.int64()), "o": pa.array(o, pa.int64())}
    names = {"row_number": "rn", "rank": "rk", "dense_rank": "dr"}
    op = WindowExec(mem_scan(data, num_batches=4),
                    [WindowExpr(k, names[k]) for k in kinds],
                    [E.Column("g")], [E.SortOrder(E.Column("o"))],
                    group_limit=limit)
    out, m, _ = _run(op)
    want = _reference(g, o, [None] * len(g))
    by = want[names[kinds[0] if len(kinds) == 1 else "row_number"]]
    keep = [i for i in range(len(g)) if by[i] <= limit]
    assert out["g"] == [g[i] for i in keep]
    for k in kinds:
        assert out[names[k]] == [want[names[k]][i] for i in keep]
    assert m.total("window_host_batches") == 0
    assert m.total("window_group_loops") == 0


def _money(values, precision=17):
    return pa.array([None if v is None else Decimal(v).scaleb(-2)
                     for v in values], pa.decimal128(precision, 2))


def _cents(column):
    return [None if v is None else int(v.scaleb(2)) for v in column]


def _q51_windows(child):
    first = WindowExec(child, [_agg(F.SUM, "cume", "sales")],
                       [E.Column("item")], [E.SortOrder(E.Column("day"))])
    return WindowExec(first, [_agg(F.MAX, "top", "cume")],
                      [E.Column("item")], [E.SortOrder(E.Column("day"))])


def test_wide_decimal_rides_on_the_device_into_a_partial_sum():
    """decimal(17,2) -> SUM decimal(27,2) -> MAX decimal(27,2) -> PARTIAL
    SUM / MAX: the wide columns are device planes all the way, and nothing
    is pulled between the first window and the aggregation's output."""
    from blaze_tpu.ops.agg import AggExec

    g, o, v = _sorted_data(11, 600, 30, nulls=0.1, vmax=10**9)
    data = {"item": pa.array(g, pa.int64()), "day": pa.array(o, pa.int64()),
            "sales": _money(v)}
    windows = _q51_windows(mem_scan(data, num_batches=5))
    assert windows.schema[-1].dtype == T.DecimalType(27, 2)
    stream = windows.execute(0, ExecContext(), MetricNode("w"))
    first = next(stream)  # past the programs' first compile and upload
    before = DEVICE_STATS.snapshot()
    batches = [first] + list(stream)
    assert all(isinstance(c, DeviceColumn) for b in batches for c in b.columns)
    agg = AggExec(
        _Replay(windows.schema, batches), E.AggExecMode.HASH_AGG,
        [("day", E.Column("day"))],
        [AggColumn(E.AggExpr(F.SUM, [E.Column("cume")]), E.AggMode.PARTIAL, "s"),
         AggColumn(E.AggExpr(F.MAX, [E.Column("top")]), E.AggMode.PARTIAL, "m"),
         AggColumn(E.AggExpr(F.COUNT, []), E.AggMode.PARTIAL, "c")])
    m = MetricNode("a")
    partial = list(agg.execute(0, ExecContext(), m))
    after = DEVICE_STATS.snapshot()
    assert after["to_host_bytes"] == before["to_host_bytes"]
    # and the answer is the reference's
    want = _reference(g, o, v)
    tops, run = [], None
    for i in range(len(g)):
        if i == 0 or g[i] != g[i - 1]:
            run = None
        if want["sum"][i] is not None:
            run = want["sum"][i] if run is None else max(run, want["sum"][i])
        tops.append(run)
    out = {}
    for b in batches:
        for k, col in b.to_pydict().items():
            out.setdefault(k, []).extend(col)
    assert _cents(out["cume"]) == want["sum"]
    assert _cents(out["top"]) == tops
    final = _final_by_day(agg, partial)
    for d in sorted(set(o)):
        sums = [want["sum"][i] for i in range(len(g))
                if o[i] == d and want["sum"][i] is not None]
        top = [tops[i] for i in range(len(g))
               if o[i] == d and tops[i] is not None]
        assert final[d] == (sum(sums) if sums else None,
                            max(top, default=None), o.count(d))


class _Replay(Operator):
    """An operator that replays batches already computed (device columns
    kept as they are)."""

    def __init__(self, schema, batches):
        super().__init__(schema, [])
        self._batches = batches

    def num_partitions(self):
        return 1

    def _execute(self, partition, ctx, metrics):
        yield from self._batches


def _final_by_day(partial_op, partial_batches):
    """day -> (sum cents, max cents, count) through the FINAL aggregation."""
    from blaze_tpu.ops.agg import AggExec

    final = AggExec(
        _Replay(partial_op.schema, partial_batches), E.AggExecMode.HASH_AGG,
        [("day", E.Column("day"))],
        [AggColumn(a.agg, E.AggMode.FINAL, a.name) for a in partial_op.aggs])
    out = {}
    for b in final.execute(0, ExecContext(), MetricNode("f")):
        d = b.to_pydict()
        for day, s, mx, c in zip(d["day"], _cents(d["s"]), _cents(d["m"]),
                                 d["c"]):
            out[day] = (s, mx, c)
    return out


@pytest.mark.parametrize("batches", [1, 4])
def test_running_sum_past_int64_is_exact_on_the_host(batches):
    """Values of 9 * 10^16 cents: a partition's running sum passes 2^63 at
    its 103rd row. Every batch runs on the device, exactly (two words, in the
    carry too); a batch with a sum past int64 leaves as the type's host
    column (``wide_host_batches``), the batches whose sums fit, before AND
    after it, as the int64 plane, and the second window takes either."""
    n = 240
    g = [0] * 120 + [1] * 120
    o = list(range(120)) * 2
    v = [9 * 10**16] * n
    data = {"item": pa.array(g, pa.int64()), "day": pa.array(o, pa.int64()),
            "sales": _money(v)}
    op = _q51_windows(mem_scan(data, num_batches=batches))
    out, m, outs = _run(op)
    want = _reference(g, o, v)
    assert max(want["sum"]) > 2**63
    assert _cents(out["cume"]) == want["sum"]
    assert _cents(out["top"]) == want["sum"]  # sums of positives only rise
    assert m.total("window_host_batches") == 0
    assert m.total("window_device_batches") == 2 * batches
    kinds = [type(b.columns[-1]) for b in outs]
    if batches == 1:
        assert kinds == [HostColumn]
        assert m.total("wide_host_batches") == 2
    else:  # rows 0-59 fit, 60-119 pass, 120-179 (a new item) fit, 180- pass
        assert kinds == [DeviceColumn, HostColumn, DeviceColumn, HostColumn]
        assert m.total("wide_host_batches") == 4
    assert m.total("window_group_loops") == 0


@pytest.mark.parametrize("fn", ["sum", "min", "max", "count"])
def test_a_wide_argument_in_a_host_column_runs_on_the_device(fn):
    """decimal(27,2) rows from a scan (a host column of decimal128) with
    values either side of +-2^63, NULLs among them, over three batches: the
    words are uploaded and the same program runs; MIN / MAX order by both
    words, SUM (typed decimal(37,2)) adds them."""
    rng = np.random.default_rng(5)
    g, o, _v = _sorted_data(13, 90, 7)
    v = [None if rng.random() < 0.2 else
         int(rng.integers(-4, 5)) * 2**62 + int(rng.integers(-9, 9))
         for _ in range(90)]
    data = {"item": pa.array(g, pa.int64()), "day": pa.array(o, pa.int64()),
            "sales": _money(v, 27)}
    op = WindowExec(mem_scan(data, num_batches=3),
                    [_agg(getattr(F, fn.upper()), "w", "sales")],
                    [E.Column("item")], [E.SortOrder(E.Column("day"))])
    out, m, _ = _run(op)
    want = _reference(g, o, v)[{"count": "cnt"}.get(fn, fn)]
    got = out["w"] if fn == "count" else _cents(out["w"])
    assert got == want
    assert m.total("window_device_batches") == 3
    assert m.total("window_host_batches") == 0


def _cume_batches(v, batches=2):
    """The first q51 window over one item's ``v`` cents a day."""
    n = len(v)
    data = {"item": pa.array([1] * n, pa.int64()),
            "day": pa.array(list(range(n)), pa.int64()),
            "name": pa.array([f"s{i % 3}" for i in range(n)], pa.string()),
            "sales": _money(v)}
    return WindowExec(mem_scan(data, num_batches=batches),
                      [_agg(F.SUM, "cume", "sales")],
                      [E.Column("item")], [E.SortOrder(E.Column("day"))])


def _running(v):
    out, s = [], 0
    for x in v:
        s += x
        out.append(s)
    return out


@pytest.mark.parametrize("expr", ["divide", "multiply", "bound_reference"])
def test_an_expression_over_the_proved_plane_reads_decimal128(expr):
    """A running total that fits int64 rides as the plane; a Project over it
    computes as the type's host column does, to the last digit: `cume / 3.00`
    at $1.8e15 (whose int64 form passes 2^63 at the rescale and would read
    NULL) and `cume * cume` at $80 billion (past 2^63), against Python's
    Decimal."""
    from blaze_tpu.ops.basic import ProjectExec

    big = 4 * 10**12 if expr == "multiply" else 9 * 10**16
    v = [big, big, -5, 7]
    cume = [Decimal(x).scaleb(-2) for x in _running(v)]
    child = _cume_batches(v)
    ref = E.BoundReference(child.schema.index_of("cume")) \
        if expr == "bound_reference" else E.Column("cume")
    three = E.Literal(Decimal("3.00"), T.DecimalType(3, 2))
    tree = {"divide": E.BinaryExpr(E.BinaryOp.DIV, ref, three),
            "multiply": E.BinaryExpr(E.BinaryOp.MUL, ref, ref),
            "bound_reference": E.BinaryExpr(E.BinaryOp.ADD, ref, ref)}[expr]
    assert all(isinstance(b.columns[-1], DeviceColumn)
               for b in child.execute(0, ExecContext(), MetricNode("c")))
    out, _m, _ = _run(ProjectExec(child, [tree], ["x"]))
    dt = E.infer_type(tree, child.schema)
    import decimal
    with decimal.localcontext() as ctx:
        ctx.prec = 60
        unit = Decimal(1).scaleb(-dt.scale)
        exact = {"divide": lambda c: c / Decimal("3.00"),
                 "multiply": lambda c: c * c,
                 "bound_reference": lambda c: c + c}[expr]
        want = [exact(c).quantize(unit, rounding=decimal.ROUND_HALF_UP)
                for c in cume]
    assert abs(int(want[0].scaleb(dt.scale))) > 2**63 or expr != "multiply"
    assert out["x"] == want


@pytest.mark.parametrize("keyed_by,arg", [
    ("name", "column"), ("name", "bound_reference"),
    ("day", "bound_reference"), ("day", "expression")])
def test_an_aggregation_over_the_proved_plane_is_exact(keyed_by, arg):
    """SUM / MAX of the plane grouped by a string key (the host table's
    path) and by an integer key (the device aggers), the argument a column,
    a BoundReference and an expression over one."""
    from blaze_tpu.ops.agg import AggExec

    v = [9 * 10**16 - i for i in range(42)]
    child = _cume_batches(v, batches=3)
    at = child.schema.index_of("cume")
    ref = {"column": E.Column("cume"),
           "bound_reference": E.BoundReference(at),
           "expression": E.BinaryExpr(E.BinaryOp.ADD, E.BoundReference(at),
                                      E.Column("cume"))}[arg]
    times = 2 if arg == "expression" else 1
    partial = AggExec(
        child, E.AggExecMode.HASH_AGG, [(keyed_by, E.Column(keyed_by))],
        [AggColumn(E.AggExpr(F.SUM, [ref]), E.AggMode.PARTIAL, "s"),
         AggColumn(E.AggExpr(F.MAX, [ref]), E.AggMode.PARTIAL, "m")])
    final = AggExec(
        partial, E.AggExecMode.HASH_AGG, [(keyed_by, E.Column(keyed_by))],
        [AggColumn(a.agg, E.AggMode.FINAL, a.name) for a in partial.aggs])
    out, _m, _ = _run(final)
    keys = [f"s{i % 3}" for i in range(42)] if keyed_by == "name" \
        else list(range(42))
    want = {}
    for k, c in zip(keys, _running(v)):
        s, mx = want.get(k, (0, None))
        want[k] = (s + times * c,
                   times * c if mx is None else max(mx, times * c))
    got = dict(zip(out[keyed_by], zip(_cents(out["s"]), _cents(out["m"]))))
    assert got == want
    assert max(s for s, _ in want.values()) > 2**63 or keyed_by == "day"


def test_the_proved_plane_crosses_an_exchange_as_its_type():
    """Window -> hash exchange -> `cume * cume`: the shuffle writer is handed
    the type's host column, and the product past 2^63 is exact."""
    from blaze_tpu.ir import nodes as N
    from blaze_tpu.runtime.session import Session

    v = [4 * 10**12, 4 * 10**12, -5, 7]
    table = pa.table({"item": pa.array([1] * 4, pa.int64()),
                      "day": pa.array(list(range(4)), pa.int64()),
                      "sales": _money(v)})
    schema = T.schema_from_arrow(table.schema)
    session = Session()
    try:
        session.resources["sales"] = lambda p: table.to_batches()
        cume = N.Window(
            N.FFIReader(schema, "sales", 1),
            [WindowExpr("agg", "cume", agg=E.AggExpr(
                F.SUM, [E.Column("sales")]), frame=RUNNING)],
            [E.Column("item")], [E.SortOrder(E.Column("day"))])
        plan = N.Projection(
            N.ShuffleExchange(cume, N.HashPartitioning([E.Column("day")], 2)),
            [E.Column("day"), E.BinaryExpr(E.BinaryOp.MUL, E.Column("cume"),
                                           E.Column("cume"))], ["day", "x"])
        got = session.execute_to_table(plan).sort_by("day")
    finally:
        session.close()
    unit = Decimal(1).scaleb(-got.schema.field("x").type.scale)
    assert got["x"].to_pylist() == [
        (Decimal(c * c).scaleb(-4)).quantize(unit, rounding="ROUND_HALF_UP")
        for c in _running(v)]
    assert (4 * 10**12) ** 2 > 2**63


def test_wide_device_column_reads_as_its_type():
    """Whoever does not know the proved plane sees the column's type: Arrow,
    a filter's comparison, a slice and a concat give decimal(27,2) values."""
    from blaze_tpu.ops.basic import FilterExec

    data = {"item": pa.array([1, 1, 1, 2], pa.int64()),
            "day": pa.array([1, 2, 3, 1], pa.int64()),
            "sales": _money([150, 250, -100, 7])}
    op = _q51_windows(mem_scan(data))
    flt = FilterExec(op, [E.BinaryExpr(
        E.BinaryOp.GT, E.Column("cume"),
        E.Literal(Decimal("2.00"), T.DecimalType(27, 2)))])
    out, _m, _ = _run(flt)
    assert out["cume"] == [Decimal("4.00"), Decimal("3.00")]
    assert out["top"] == [Decimal("4.00"), Decimal("4.00")]
    (batch,) = list(op.execute(0, ExecContext(), MetricNode("r")))
    both = ColumnarBatch.concat([batch.slice(1, 2), batch.slice(0, 1)],
                                batch.schema)
    assert both.to_arrow().column("cume").to_pylist() == [
        Decimal("4.00"), Decimal("3.00"), Decimal("1.50")]
    assert both.to_arrow().schema.field("cume").type == pa.decimal128(27, 2)


def test_what_stays_on_the_host_is_counted():
    """A default-frame aggregate, a var-width key and a RANGE offset frame
    keep today's host paths; each batch counts as ``window_host_batches``."""
    data = {"g": pa.array(["a", "a", "b"], pa.string()),
            "o": pa.array([1, 2, 1], pa.int64()),
            "v": pa.array([1, 2, 3], pa.int64())}
    for exprs, part in (
            ([_agg(F.SUM, "s")], [E.Column("g")]),           # var-width key
            ([_agg(F.SUM, "s", frame=None)], [E.Column("o")]),  # default frame
            ([_agg(F.SUM, "s", frame=("range", -1, 0))], [E.Column("g")])):
        op = WindowExec(mem_scan(data), exprs, part,
                        [E.SortOrder(E.Column("o"))])
        _out, m, _ = _run(op)
        assert m.total("window_host_batches") == 1
        assert m.total("window_device_batches") == 0
    assert {"window_device_batches", "window_host_batches",
            "wide_host_batches"} <= set(TRIPWIRE_METRICS)


def test_window_scan_has_no_scatter_and_no_row_sized_gather():
    """The program moves nothing by index: prefix scans only (PERF.md
    section 6: 9.2 ms a row-sized int64 scatter at 131,072 rows)."""
    from blaze_tpu.ops import window_device as WD
    from tests.util import jaxpr_eqns

    spec = WD.Spec(1, 1, (("rank",), ("sum", True), ("max", True)))
    cap = 1024
    plane = (jnp.zeros(cap, jnp.int64), jnp.ones(cap, bool))
    carry = WD.initial_carry(spec, [np.int64, np.int64],
                             [np.int64, np.int64, np.int64])
    jaxpr = jax.make_jaxpr(
        lambda n, k, a, c: WD.window_scan(n, k, a, c, spec=spec, cap=cap))(
        np.int32(5), (plane, plane), (None, plane, plane), carry)
    for eqn in jaxpr_eqns(jaxpr.jaxpr):
        name = eqn.primitive.name
        assert not name.startswith("scatter"), name
        assert name != "sort", name
        if name == "gather":
            assert all(np.prod(v.aval.shape) <= 1 for v in eqn.outvars), eqn
