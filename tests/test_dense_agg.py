"""Dense-bucket partial aggregation (ops/agg_device.py dense path).

The TPU-friendly analogue of the reference's one-pass hash table
(``agg/agg_hash_map.rs``): integer keys whose observed range fits a small
static table scatter straight into range-sized segment slots — no sort, no
capacity-sized tables. These tests pin the orchestration edges: probe +
plan, range-overflow widening, all-null-key batches keeping the anchor,
fallback beyond the bucket cap, and end-to-end equality with the sort
kernel on nullable multi-key input.
"""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pytest

from blaze_tpu.core.batch import ColumnarBatch
from blaze_tpu.ir import exprs as E
from blaze_tpu.ir import nodes as N
from blaze_tpu.ir import types as T
from blaze_tpu.ops import agg_device as A
from blaze_tpu.ops.agg_device import DevicePartialAgger
from blaze_tpu.runtime.executor import build_operator
from blaze_tpu.runtime.session import Session
from blaze_tpu.utils.device import DEVICE_STATS
from tests.util import jaxpr_eqns

SCHEMA = pa.schema([("k1", pa.int64()), ("k2", pa.int64()), ("v", pa.int64())])


def _scan_stub():
    import tempfile

    import pyarrow.parquet as pq

    from blaze_tpu.ops.parquet import scan_node_for_files

    td = tempfile.mkdtemp(prefix="dense_agg_")
    pq.write_table(pa.table({"k1": [1], "k2": [0], "v": [1]},
                            schema=SCHEMA), td + "/t.parquet")
    return scan_node_for_files([td + "/t.parquet"], num_partitions=1)


def _agger(groupings=("k1",)):
    schema = T.schema_from_arrow(SCHEMA)
    node = N.Agg(_scan_stub(), E.AggExecMode.HASH_AGG,
                 [(g, E.Column(g)) for g in groupings], [
        N.AggColumn(E.AggExpr(E.AggFunction.SUM, [E.Column("v")]),
                    E.AggMode.PARTIAL, "s")])
    return DevicePartialAgger(build_operator(node), schema)


def _batch(ks, vs):
    return ColumnarBatch.from_arrow(pa.table(
        {"k1": pa.array(ks, type=pa.int64()),
         "k2": pa.array([0] * len(ks), type=pa.int64()),
         "v": pa.array(vs, type=pa.int64())}))


def test_dense_engages_and_anchors_far_from_zero():
    agger = _agger()
    out = agger.process(_batch([9_000_001, 9_000_002] * 50, [1] * 100))
    assert agger._bucket_state is not None, "dense plan expected"
    kind, bases, sizes, out_cap = agger._bucket_state
    assert kind == "dense"
    assert bases == (9_000_001,) and sizes[0] <= 4
    got = out.to_arrow().to_pydict()
    assert sorted(got["k1"]) == [9_000_001, 9_000_002]
    assert got["s#sum"] == [50, 50]


def test_range_overflow_widens_within_budget():
    agger = _agger()
    o1 = agger.process(_batch([5, 6, 7] * 100, [1] * 300))
    o2 = agger.process(_batch([50, 51] * 100, [2] * 200))
    assert o1.num_rows == 3 and o2.num_rows == 2
    assert agger._bucket_state is not None, "union 5..51 fits: dense stays"
    assert agger._bucket_state[0] == "dense"
    assert sorted(o2.to_arrow().to_pydict()["s#sum"]) == [200, 200]


def test_range_overflow_beyond_dense_cap_goes_radix():
    agger = _agger()
    o1 = agger.process(_batch([5, 6, 7] * 100, [1] * 300))
    assert agger._bucket_state[0] == "dense"
    # union with 10005.. needs 16k slots > batch capacity: the dense plan
    # overflows and the re-plan lands on the radix table, results stay exact
    o2 = agger.process(_batch([10005, 10006] * 100, [2] * 200))
    assert agger._bucket_state is not None
    assert agger._bucket_state[0] == "radix"
    assert sorted(o2.to_arrow().to_pydict()["s#sum"]) == [200, 200]
    assert o1.num_rows == 3


def test_range_overflow_beyond_radix_cap_falls_back_correctly():
    agger = _agger()
    o1 = agger.process(_batch([5, 6, 7] * 100, [1] * 300))
    # union with 9_000_005.. would need ~9M slots > radix_agg_max_slots
    # (4M): every scatter table disables, the sort kernel takes over,
    # results stay exact
    o2 = agger.process(_batch([9_000_005, 9_000_006] * 100, [2] * 200))
    assert agger._dense_ok is False and agger._radix_ok is False
    assert agger._bucket_state is None
    assert sorted(o2.to_arrow().to_pydict()["s#sum"]) == [200, 200]
    assert o1.num_rows == 3


def test_all_null_key_batch_keeps_anchor():
    agger = _agger()
    agger.process(_batch([9_000_001, 9_000_002] * 50, [1] * 100))
    st = agger._bucket_state
    onull = agger.process(_batch([None] * 64, [3] * 64))
    assert onull.num_rows == 1  # the null-key group
    assert onull.to_arrow().to_pydict()["s#sum"] == [192]
    assert agger._bucket_state == st, "all-null probe must not move the anchor"


def test_non_integer_keys_decline_dense(tmp_path):
    import pyarrow.parquet as pq

    from blaze_tpu.ops.parquet import scan_node_for_files

    path = str(tmp_path / "f.parquet")
    pq.write_table(pa.table({"k": pa.array([1.5, 2.5], type=pa.float64()),
                             "v": pa.array([1, 2], type=pa.int64())}), path)
    scan = scan_node_for_files([path], num_partitions=1)
    node = N.Agg(scan, E.AggExecMode.HASH_AGG,
                 [("k", E.Column("k"))], [
        N.AggColumn(E.AggExpr(E.AggFunction.SUM, [E.Column("v")]),
                    E.AggMode.PARTIAL, "s")])
    op = build_operator(node)
    agger = DevicePartialAgger(op, op.children[0].schema)
    assert agger._dense_enabled() is False


def test_dense_matches_oracle_multikey_nulls(tmp_path):
    import pyarrow.parquet as pq

    from blaze_tpu.ops.parquet import scan_node_for_files

    rng = np.random.default_rng(3)
    n = 50_000
    k1 = rng.integers(1_000_000, 1_000_050, n).astype(object)
    k2 = rng.integers(0, 7, n).astype(object)
    for i in rng.choice(n, 500, replace=False):
        k1[i] = None
    for i in rng.choice(n, 300, replace=False):
        k2[i] = None
    v = rng.integers(-1000, 1000, n)
    tbl = pa.table({"k1": pa.array(list(k1), type=pa.int64()),
                    "k2": pa.array(list(k2), type=pa.int64()),
                    "v": pa.array(v, type=pa.int64())})
    path = str(tmp_path / "t.parquet")
    pq.write_table(tbl, path)
    scan = scan_node_for_files([path], num_partitions=1)

    def aggs(mode):
        return [
            N.AggColumn(E.AggExpr(E.AggFunction.SUM, [E.Column("v")]), mode, "s"),
            N.AggColumn(E.AggExpr(E.AggFunction.MIN, [E.Column("v")]), mode, "mn"),
            N.AggColumn(E.AggExpr(E.AggFunction.MAX, [E.Column("v")]), mode, "mx"),
            N.AggColumn(E.AggExpr(E.AggFunction.COUNT, []), mode, "c"),
            N.AggColumn(E.AggExpr(E.AggFunction.AVG, [E.Column("v")]), mode, "a"),
        ]

    keys = [("k1", E.Column("k1")), ("k2", E.Column("k2"))]
    partial = N.Agg(scan, E.AggExecMode.HASH_AGG, keys, aggs(E.AggMode.PARTIAL))
    ex = N.ShuffleExchange(partial, N.HashPartitioning([E.Column("k1")], 3))
    final = N.Agg(ex, E.AggExecMode.HASH_AGG, keys, aggs(E.AggMode.FINAL))
    plan = N.Sort(N.ShuffleExchange(final, N.SinglePartitioning(1)),
                  [E.SortOrder(E.Column("k1")), E.SortOrder(E.Column("k2"))])
    od = Session().execute_to_table(plan).to_pandas()

    df = tbl.to_pandas()
    g = df.groupby(["k1", "k2"], dropna=False).agg(
        s=("v", "sum"), mn=("v", "min"), mx=("v", "max"),
        c=("v", "size"), a=("v", "mean")).reset_index()
    g = g.sort_values(["k1", "k2"], na_position="first").reset_index(drop=True)
    assert len(od) == len(g)
    assert (od.s.values == g.s.values).all()
    assert (od.mn.values == g.mn.values).all()
    assert (od.mx.values == g.mx.values).all()
    assert (od.c.values == g.c.values).all()
    assert np.allclose(od.a.astype(float).values, g.a.values)


def test_first_batch_no_valid_keys_defers_plan():
    """Round-3 advisor: an all-null (or fully filtered) first batch must not
    pin an artificial [0, 0] anchor — it defers, and the next batch with
    real keys plans from its own range."""
    agger = _agger()
    o1 = agger.process(_batch([None] * 64, [3] * 64))
    assert o1.num_rows == 1  # null-key group, via the sort fallback
    assert o1.to_arrow().to_pydict()["s#sum"] == [192]
    assert agger._bucket_state is None, "no plan should be pinned"
    assert agger._dense_ok is not False, "dense path must stay available"
    o2 = agger.process(_batch([9_000_001, 9_000_002] * 50, [1] * 100))
    assert agger._bucket_state is not None, "dense plan expected on real keys"
    _, bases, sizes, _ = agger._bucket_state
    assert bases == (9_000_001,), "anchor must come from the real keys"
    assert sorted(o2.to_arrow().to_pydict()["s#sum"]) == [50, 50]


def test_key_just_below_anchor_does_not_merge_into_null_group():
    """key == base-1 encodes to bucket 0 (the null bucket) under the naive
    range test; it must instead flip the fits flag and re-plan."""
    agger = _agger()
    agger.process(_batch([10, 11] * 50, [1] * 100))
    assert agger._bucket_state is not None
    o2 = agger.process(_batch([9] * 100, [2] * 100))
    got = o2.to_arrow().to_pydict()
    assert got["k1"] == [9], "key 9 must survive as a real (non-null) group"
    assert got["s#sum"] == [200]


def test_int64_extreme_ranges_stay_exact():
    """Round-3 advisor: keys near opposite int64 extremes make the
    bucket-code subtraction wrap; the overflow-safe range test must force
    fallback/re-plan instead of silently mis-bucketing."""
    hi = 2**63 - 2
    lo = -(2**63)
    agger = _agger()
    o1 = agger.process(_batch([hi, hi + 1] * 50, [1] * 100))
    assert sorted(o1.to_arrow().to_pydict()["k1"]) == [hi, hi + 1]
    o2 = agger.process(_batch([lo] * 100, [2] * 100))
    got = o2.to_arrow().to_pydict()
    assert got["k1"] == [lo]
    assert got["s#sum"] == [200]


# -- the reduction's two forms (_seg_reduce) -----------------------------------

CUT = A._MASKED_REDUCE_MAX_SLOTS
ROWS = 384  # a batch of 300 rows and 84 padding rows
# (kind, rescale, accumulator dtype), argument dtype
KINDS = {
    "sum": (("sum", 0, "int64"), "int64"),
    "sum_rescaled": (("sum", 2, "int64"), "int64"),
    "sum_widened": (("sum", 0, "int64"), "int32"),
    "sum_f32": (("sum", 0, "float32"), "float32"),
    "avg": (("avg", 0, "int64"), "int64"),
    "count": (("count", 0, ""), "int64"),
    "min": (("min", 0, ""), "int64"),
    "max": (("max", 0, ""), "int64"),
    "min_i32": (("min", 0, ""), "int32"),
    "max_f32": (("max", 0, ""), "float32"),
    "sum2": (("sum2", 0, ""), "int64"),
    "avg2": (("avg2", 0, ""), "int64"),
    "sum3": (("sum3", 0, ""), "wide3"),
    "avg3": (("avg3", 0, ""), "wide3"),
    "minw": (("minw", 0, ""), "wide3"),
    "maxw": (("maxw", 0, ""), "wide3"),
}
I64 = np.iinfo(np.int64)


def _values(rng, dtype, data):
    """One argument plane (or the three limb planes of a decimal(38))."""
    if dtype == "wide3":
        # l0, l1: nonnegative 32-bit chunks; l2: the signed high word
        if data == "extremes":
            return tuple(jnp.asarray(rng.choice(np.array(c, np.int64), ROWS))
                         for c in ([0, 2**32 - 1], [0, 2**32 - 1],
                                   [I64.min, I64.max, 0, -1]))
        return (jnp.asarray(rng.integers(0, 2**32, ROWS)),
                jnp.asarray(rng.integers(0, 2**32, ROWS)),
                jnp.asarray(rng.integers(-3, 3, ROWS)))  # ties in the high word
    if dtype == "float32":
        return jnp.asarray(rng.normal(0, 1e3, ROWS).astype(np.float32))
    info = np.iinfo(dtype)
    if data == "extremes":
        return jnp.asarray(rng.choice(
            np.array([info.min, info.max, info.min + 1, info.max - 1, 0, -1],
                     dtype), ROWS))
    return jnp.asarray(rng.integers(-10**6, 10**6, ROWS).astype(dtype))


def _rows(nseg, data, seed):
    """(seg, valid): 300 rows routed to slots below ``nseg`` and 84 padding
    rows at the sentinel ``nseg``, which every reduction must drop."""
    rng = np.random.default_rng(seed)
    exists = np.arange(ROWS) < 300
    if data == "one_segment":  # every row of the batch in one slot
        slot = np.full(ROWS, nseg - 1)
        exists[:] = True
    else:
        slot = rng.integers(0, min(nseg, 40), ROWS)  # crowded slots
        slot[::7] = rng.integers(0, nseg, len(slot[::7]))  # and far ones
    valid = exists & (rng.random(ROWS) > (1.0 if data == "all_null" else 0.1))
    seg = jnp.asarray(np.where(exists, slot, nseg).astype(np.int32))
    return rng, seg, jnp.asarray(valid)


def _reduced(monkeypatch, masked, spec, arg, seg, nseg):
    monkeypatch.setattr(A, "_masked_form", lambda nseg, rows: masked)
    (out,) = A._reduce_aggs((spec,), [arg], seg, nseg)
    monkeypatch.undo()
    return [np.asarray(a) for a in out[1:]]


@pytest.mark.parametrize("nseg", [16, 1024, CUT, 2 * CUT])
@pytest.mark.parametrize("name", sorted(KINDS))
def test_masked_reduction_equals_the_scatter(monkeypatch, name, nseg):
    """Every aggregate kind, at slot counts on both sides of the cut: the
    masked vector reduction gives the scatter's bits on random keys with
    nulls and padding rows, an all-null batch, int64 extremes (sums wrap
    alike, an extreme equals the sentinel) and a batch that is one segment."""
    spec, dtype = KINDS[name]
    for seed, data in enumerate(("random", "all_null", "extremes",
                                 "one_segment")):
        rng, seg, valid = _rows(nseg, data, 1000 * nseg + seed)
        arg = (_values(rng, dtype, data), valid)
        masked = _reduced(monkeypatch, True, spec, arg, seg, nseg)
        scatter = _reduced(monkeypatch, False, spec, arg, seg, nseg)
        for m, s in zip(masked, scatter):
            assert m.dtype == s.dtype and m.shape == s.shape == (nseg,)
            if name == "sum_f32":  # a float sum's order is the form's own
                np.testing.assert_allclose(m, s, rtol=1e-5, atol=1e-2)
            else:
                assert np.array_equal(m, s), (name, nseg, data)


@pytest.mark.parametrize("nseg,rows,masked", [
    (16, 131072, True), (CUT, 131072, True), (2 * CUT, 131072, False),
    (128, 128, False),  # a segment a row: the passthrough kernel
    (128, 256, True)])
def test_form_follows_the_static_shapes(nseg, rows, masked):
    closed = jax.make_jaxpr(
        lambda seg, x: A._seg_reduce("add", seg, x, nseg))(
        jax.ShapeDtypeStruct((rows,), jnp.int32),
        jax.ShapeDtypeStruct((rows,), jnp.int64))
    scatters = [e for e in jaxpr_eqns(closed.jaxpr)
                if e.primitive.name.startswith("scatter")]
    assert bool(scatters) != masked


def _avals(key_dtypes, arg_dtypes, cap, bases=False):
    """A partial kernel's arguments: exists, (the slot table's bases,) then a
    (data, valid) pair a key and an aggregate argument."""
    def plane(dt):
        return jax.ShapeDtypeStruct((cap,), jnp.dtype(dt))

    flat = [jax.ShapeDtypeStruct((len(key_dtypes),), jnp.int64)] * bases
    for kd in key_dtypes:
        flat += [plane(kd), plane(bool)]
    for ad in arg_dtypes:
        flat += [plane("int64")] * 3 if ad == "wide3" else [plane(ad)]
        flat.append(plane(bool))
    return [plane(bool)] + flat


NARROW = [k for k, (_s, d) in KINDS.items() if d != "wide3"]
WIDE = [k for k, (_s, d) in KINDS.items() if d == "wide3"]
CAPACITY = 131072  # a scan batch of the benchmark's cells


@pytest.mark.parametrize("names", [["sum", "count"], NARROW, WIDE],
                         ids=["q01", "narrow", "wide"])
def test_dense_kernel_at_16_slots_has_no_row_sized_scatter(names):
    """At 16 slots nothing in the slot-table kernel touches a row at a time:
    no scatter, gather or sort with a batch-sized operand or update."""
    specs = tuple(KINDS[n][0] for n in names)
    adt = tuple(KINDS[n][1] for n in names)
    kernel = A._dense_partial_kernel(("int64",), specs, adt, CAPACITY,
                                     (16,), 128)
    avals = _avals(("int64",), adt, CAPACITY, bases=True)
    closed = jax.make_jaxpr(kernel)(*avals)
    serial = [e for e in jaxpr_eqns(closed.jaxpr)
              if e.primitive.name.startswith(("scatter", "gather", "sort"))]
    assert serial, "the emit step still compacts 16 slots by scatter"
    for eqn in serial:
        sizes = [v.aval.size for v in list(eqn.invars) + list(eqn.outvars)]
        assert max(sizes) < CAPACITY, eqn
    # and above the cut the same kernel keeps its row-sized scatter-adds
    big = A._dense_partial_kernel(("int64",), specs, adt, CAPACITY,
                                  (2 * CUT,), 2 * CUT)
    closed = jax.make_jaxpr(big)(*avals)
    assert any(e.primitive.name.startswith("scatter")
               and max(v.aval.size for v in e.invars) >= CAPACITY
               for e in jaxpr_eqns(closed.jaxpr))


# how many state planes a kind's merge takes and every kernel gives
NSTATE = {"sum": 2, "count": 1, "avg": 2, "min": 2, "max": 2, "sum2": 3,
          "avg2": 3, "sum3": 4, "avg3": 4, "minw": 4, "maxw": 4}


def _state_dtypes(name):
    """The dtypes of the partial-state columns ``jit(agg_merge)`` takes."""
    (kind, _rescale, acc), adt = KINDS[name]
    last = "int64" if kind.startswith("avg") else "bool"
    if kind == "count":
        return ("int64",)
    if kind in ("sum", "avg"):
        return (acc, last)
    if kind in ("min", "max"):
        return (adt, "bool")
    return ("int64",) * (NSTATE[kind] - 1) + (last,)


# key dtypes, aggregates, and sha256 of str(make_jaxpr(_dense_partial_kernel))
# at 16 and at 2 x CUT slots at PR 28's commit (03958eb, jax 0.9.0): PR 29
# rewrote the sort path beside it, and the slot-table kernel, its program and
# the compile-cache entries q01, q06 and q47 hit, must not move.
SCHEMAS = {
    "sum_count_1key": (
        ("int64",), ["sum", "count"],
        "e93110f768a05d8b5ee57927301a266c2baa358f3998343c0315047c38931927",
        "9eeb4b89a0917718a8eaffaead9f9d02c57edf9321b1e6d1bf81c078fb8e2666"),
    "narrow_2key": (
        ("int64", "int32"), ["sum", "avg", "min", "max", "count", "sum_f32"],
        "3ae10d49a207373a94025b56d1d04d0faad1fe64fc7ad1b84a12d05b7b159f09",
        "c1df3eb7eac6195bf0f2cb062948361387bda4e5a6bbefe5413b95771d220806"),
    "wide_1key": (
        ("int64",), ["sum2", "avg2", "sum3", "avg3", "minw", "maxw"],
        "4257a9db6e65ad6b05887834f3b593892b8538d1e359de3751b579c50a68dcf8",
        "59f9404fba7e47a35e0753cb3b7ce6fe288c6e3066b8031c1075916a1a92f5d6"),
}


@pytest.mark.parametrize("case", sorted(SCHEMAS))
def test_dense_kernel_jaxpr_is_the_parents(case):
    key_dtypes, names, *digests = SCHEMAS[case]
    specs = tuple(KINDS[n][0] for n in names)
    adt = tuple(KINDS[n][1] for n in names)
    avals = _avals(key_dtypes, adt, CAPACITY, bases=True)
    for slots, digest in zip((16, 2 * CUT), digests):
        sizes = (slots,) if len(key_dtypes) == 1 else (slots // 4, 4)
        kernel = A._dense_partial_kernel(key_dtypes, specs, adt, CAPACITY,
                                         sizes, max(128, slots))
        text = str(jax.make_jaxpr(kernel)(*avals))
        assert hashlib.sha256(text.encode()).hexdigest() == digest, slots


@pytest.mark.parametrize("which", ["partial", "merge"])
@pytest.mark.parametrize("case", sorted(SCHEMAS))
def test_sort_path_kernels_touch_no_row_at_a_time(case, which):
    """``jit(agg_partial)`` and ``jit(agg_merge)`` order their rows by
    two-operand sorts, reduce by scans and move their planes by one gather in
    and one out: no scatter with a batch-sized operand or update, at most two
    gathers with batch-sized indices, no sort of more than two operands, and
    no ``cond`` (one path, whatever the keys)."""
    key_dtypes, names, *_ = SCHEMAS[case]
    if which == "partial":
        adt = tuple(KINDS[n][1] for n in names)
        kernel = A._partial_kernel(
            key_dtypes, tuple(KINDS[n][0] for n in names), adt, CAPACITY)
        avals = _avals(key_dtypes, adt, CAPACITY)
    else:
        states = tuple(_state_dtypes(n) for n in names)
        kernel = A._merge_kernel(
            key_dtypes, tuple(KINDS[n][0][0] for n in names), states, CAPACITY)
        avals = _avals(key_dtypes, [dt for dts in states for dt in dts],
                       CAPACITY)
    eqns = list(jaxpr_eqns(jax.make_jaxpr(kernel)(*avals).jaxpr))
    names_seen = {e.primitive.name for e in eqns}
    assert "cond" not in names_seen and "while" not in names_seen
    gathers = 0
    for eqn in eqns:
        prim = eqn.primitive.name
        if prim.startswith("scatter"):
            assert max(v.aval.size for v in eqn.invars) < CAPACITY, eqn
        elif prim == "sort":
            assert len(eqn.invars) <= 2, eqn
        elif prim == "gather":
            gathers += eqn.invars[1].aval.shape[0] >= CAPACITY
    assert 1 <= gathers <= 2
    assert "sort" in names_seen and "cumsum" in names_seen


# -- selection and counters ----------------------------------------------------


@pytest.mark.parametrize("backend_is_cpu", [False, True])
def test_auto_engages_whatever_the_backend_and_counts_what_ran(
        monkeypatch, backend_is_cpu):
    """``dense_agg=None`` selects from the probed key range alone; the radix
    table keeps its backend gate, so off the CPU a wide range goes to the
    sort kernel. Each answered batch is counted as what ran."""
    from blaze_tpu.runtime import placement

    monkeypatch.setattr(placement, "backend_is_cpu_hint",
                        lambda: backend_is_cpu)
    agger = _agger()
    assert agger.conf.dense_agg is None
    s0 = DEVICE_STATS.snapshot()
    out = agger.process(_batch([3, 4, 5] * 100, [1] * 300))
    s1 = DEVICE_STATS.snapshot()
    assert agger._bucket_state[0] == "dense" and out.num_rows == 3
    assert s1["agg_dense_batches"] - s0["agg_dense_batches"] == 1
    assert s1["agg_sort_batches"] == s0["agg_sort_batches"]
    # the probe and the kernel's group count: two named syncs
    assert s1["sync_calls"] - s0["sync_calls"] == 2
    out = agger.process(_batch([3, 4] * 100, [1] * 200))
    s2 = DEVICE_STATS.snapshot()
    assert s2["agg_dense_batches"] - s1["agg_dense_batches"] == 1
    assert s2["sync_calls"] - s1["sync_calls"] == 1, "one probe a stream"
    # a range past dense_agg_max_buckets: radix where its gate allows, else
    # the sort kernel for the rest of the stream
    wide = _agger()
    out = wide.process(_batch([5, 900_005] * 100, [2] * 200))
    s3 = DEVICE_STATS.snapshot()
    assert sorted(out.to_arrow().to_pydict()["s#sum"]) == [200, 200]
    if backend_is_cpu:
        assert wide._bucket_state[0] == "radix"
        assert s3["agg_dense_batches"] - s2["agg_dense_batches"] == 1
    else:
        assert wide._bucket_state is None and wide._dense_ok is False
        assert s3["agg_sort_batches"] - s2["agg_sort_batches"] == 1
        assert s3["agg_dense_batches"] == s2["agg_dense_batches"]


def test_dense_agg_false_forces_the_sort_kernel():
    from blaze_tpu.config import Config

    schema = T.schema_from_arrow(SCHEMA)
    node = N.Agg(_scan_stub(), E.AggExecMode.HASH_AGG,
                 [("k1", E.Column("k1"))], [
        N.AggColumn(E.AggExpr(E.AggFunction.SUM, [E.Column("v")]),
                    E.AggMode.PARTIAL, "s")])
    agger = DevicePartialAgger(build_operator(node), schema,
                               conf=Config(dense_agg=False, radix_agg=False))
    s0 = DEVICE_STATS.snapshot()
    out = agger.process(_batch([3, 4, 5] * 100, [1] * 300))
    s1 = DEVICE_STATS.snapshot()
    assert out.num_rows == 3 and agger._bucket_state is None
    assert s1["agg_sort_batches"] - s0["agg_sort_batches"] == 1
    assert s1["agg_dense_batches"] == s0["agg_dense_batches"]
    assert s1["sync_calls"] - s0["sync_calls"] == 1, "no probe"
