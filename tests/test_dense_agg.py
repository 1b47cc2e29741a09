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
import math

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


def _scan_stub(schema=SCHEMA):
    import tempfile

    import pyarrow.parquet as pq

    from blaze_tpu.ops.parquet import scan_node_for_files

    td = tempfile.mkdtemp(prefix="dense_agg_")
    pq.write_table(pa.table({f.name: [1] for f in schema}, schema=schema),
                   td + "/t.parquet")
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


# -- the table's forms (_table_form): masked, slot-sorted, scatter ---------------

CUT = A._MASKED_REDUCE_MAX_SLOTS
RULE = A._SLOT_SORT_MIN_SLOTS  # the first table that reduces by a sort
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
    monkeypatch.setattr(A, "_table_form", lambda nseg, rows, sortable=False:
                        "masked" if masked else "scatter")
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
    """``_seg_reduce`` gives the table itself, so it never sorts: masked up
    to the cut where the table is smaller than the batch, else a scatter."""
    assert A._table_form(nseg, rows) == ("masked" if masked else "scatter")
    closed = jax.make_jaxpr(
        lambda seg, x: A._seg_reduce("add", seg, x, nseg))(
        jax.ShapeDtypeStruct((rows,), jnp.int32),
        jax.ShapeDtypeStruct((rows,), jnp.int64))
    scatters = [e for e in jaxpr_eqns(closed.jaxpr)
                if e.primitive.name.startswith("scatter")]
    assert bool(scatters) != masked


@pytest.mark.parametrize("nseg,rows,nbuck,form", [
    (16, 131072, 0, "masked"), (RULE // 2, 131072, 0, "masked"),
    (RULE, 131072, 0, "sorted"), (CUT, 131072, 0, "sorted"),
    (4 * CUT, 131072, 0, "sorted"),
    (4 * CUT, 4 * CUT, 0, "sorted"),  # as many slots as rows: no scatter left
    (RULE, 131072, 256, "masked"),  # the radix variant keeps the table
    (4 * CUT, 131072, 256, "scatter")])
def test_the_slot_table_sorts_from_the_rule_on(nseg, rows, nbuck, form):
    """A rule on the static shapes alone: ``jit(agg_dense_partial)`` of
    ``RULE`` slots or more reduces by one sort of the slot id, whatever the
    capacity, unless it is the radix variant."""
    assert A._table_form(nseg, rows, sortable=not nbuck) == form
    assert A._is_slot_sorted((nseg // 4, 4), rows, nbuck) == (form == "sorted")


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
# at 16 and at 2 x CUT slots (jax 0.9.0). The 16-slot digests are those of
# PR 28's commit (03958eb) and no PR since has had any business moving them:
# PR 29 rewrote the sort path beside the slot table and PR 38 gave a table of
# RULE slots or more the slot-sorted form, and either way the masked program
# of a small table, and the compile-cache entries q01, q06 and q22's grand
# total hit, stay byte for byte. The 2 x CUT digests are PR 38's: the
# slot-sorted program that replaced the scatter form there (q47's, q51's).
# Then the sort path's own two kernels at PR 37's commit (034ba47): PR 38
# factored their body (_aggregate_sorted -> _reduce_ordered) to share it with
# the slot-sorted form, and their programs (q67, q29, q51, q22) must not move.
SCHEMAS = {
    "sum_count_1key": (
        ("int64",), ["sum", "count"],
        "e93110f768a05d8b5ee57927301a266c2baa358f3998343c0315047c38931927",
        "c5b1a2ea941171acfabdc537a5e0e83ad201a7fdd719d2ea68bbc9d44888f3c4"),
    "narrow_2key": (
        ("int64", "int32"), ["sum", "avg", "min", "max", "count", "sum_f32"],
        "3ae10d49a207373a94025b56d1d04d0faad1fe64fc7ad1b84a12d05b7b159f09",
        "942c22a608a9d0dca9475a1fa4e36a990a07598166bea9a1e77e43251596729a"),
    "wide_1key": (
        ("int64",), ["sum2", "avg2", "sum3", "avg3", "minw", "maxw"],
        "4257a9db6e65ad6b05887834f3b593892b8538d1e359de3751b579c50a68dcf8",
        "ce762c969b8d6264d9da273e9006a685ab208f0858cb2ebf4a9ecc872a5506ed"),
}
SORT_PATH_DIGESTS = {
    ("sum_count_1key", "partial"):
        "7008a2c7e01bd20fd784a98fee28157ed888dcfc51c28d3d48be409f88d7c28d",
    ("sum_count_1key", "merge"):
        "5feaff504f37522db4ae4c42629d65815d8b762c87c8b3b12e175390344c5dd3",
    ("narrow_2key", "partial"):
        "ee7b108e761082c9128c9b836bc87153bcc2ce9ff41ce14bb32db50555bb3160",
    ("narrow_2key", "merge"):
        "0adcc66aa412a928c9944c904caee4a52d3712125cd1431238447a660fd49861",
    ("wide_1key", "partial"):
        "ee7b3fa7168e931d7ad7ba4c719967b75739031b942afc9508e6277c009b8bc7",
    ("wide_1key", "merge"):
        "eb73d73d1f742f281c2682951801a4743d79e2ae875c78e7f37edd58237fb729",
}


def _dense_kernel(case, slots, capacity=CAPACITY, form=None):
    """``_dense_partial_kernel`` of a schema at ``slots`` slots with its
    abstract arguments; ``form`` forces the table's form (the kernel is then
    built past the cache, so no other test meets a forced program)."""
    key_dtypes, names, *_ = SCHEMAS[case]
    specs = tuple(KINDS[n][0] for n in names)
    adt = tuple(KINDS[n][1] for n in names)
    sizes = (slots,) if len(key_dtypes) == 1 else (slots // 4, 4)
    build = A._dense_partial_kernel
    if form is not None:
        build = build.__wrapped__
        rule, A._table_form = A._table_form, lambda *_a, **_k: form
    try:
        kernel = build(key_dtypes, specs, adt, capacity, sizes,
                       max(128, min(slots, capacity)))
    finally:
        if form is not None:
            A._table_form = rule
    return kernel, sizes, _avals(key_dtypes, adt, capacity, bases=True)


def _digest(kernel, avals):
    text = str(jax.make_jaxpr(kernel)(*avals))
    return hashlib.sha256(text.encode()).hexdigest()


@pytest.mark.parametrize("case", sorted(SCHEMAS))
def test_dense_kernel_jaxpr_is_the_parents(case):
    *_, small, large = SCHEMAS[case]
    for slots, digest in ((16, small), (2 * CUT, large)):
        kernel, _sizes, avals = _dense_kernel(case, slots)
        assert _digest(kernel, avals) == digest, slots


def _sort_path_kernel(case, which):
    key_dtypes, names, *_ = SCHEMAS[case]
    if which == "partial":
        adt = tuple(KINDS[n][1] for n in names)
        kernel = A._partial_kernel(
            key_dtypes, tuple(KINDS[n][0] for n in names), adt, CAPACITY)
        return kernel, _avals(key_dtypes, adt, CAPACITY)
    states = tuple(_state_dtypes(n) for n in names)
    kernel = A._merge_kernel(
        key_dtypes, tuple(KINDS[n][0][0] for n in names), states, CAPACITY)
    return kernel, _avals(key_dtypes, [dt for dts in states for dt in dts],
                          CAPACITY)


@pytest.mark.parametrize("case,which", sorted(SORT_PATH_DIGESTS))
def test_sort_path_jaxprs_are_the_parents(case, which):
    assert _digest(*_sort_path_kernel(case, which)) == \
        SORT_PATH_DIGESTS[case, which]


# The merge by one sort of the packed key id (``jit(agg_merge_sorted)``),
# beside ``jit(agg_merge)``, which keeps its digest above: its program as it
# entered, so that a change to it is a decision.
SLOT_MERGE_DIGESTS = {
    "narrow_2key":
        "7240edf06504422246fa8b155582f2739a2d2a283f3f1282ad87ebfc4aba7ca0",
    "sum_count_1key":
        "0a129764824d48d0ae4aeb4cfc6f336cc35a9534452a23d8776d068ef374e427",
    "wide_1key":
        "9d49911bb64b34a7467f8aa155c9d8ef0f62748522fee78e4c0b4d588c1778d6",
}


@pytest.mark.parametrize("case", sorted(SLOT_MERGE_DIGESTS))
def test_slot_merge_jaxprs_are_pinned(case):
    key_dtypes, names, *_ = SCHEMAS[case]
    states = tuple(_state_dtypes(n) for n in names)
    kernel = A._slot_merge_kernel(
        key_dtypes, tuple(KINDS[n][0][0] for n in names), states, CAPACITY)
    avals = _avals(key_dtypes, [dt for dts in states for dt in dts], CAPACITY)
    avals.insert(1, jax.ShapeDtypeStruct((3, len(key_dtypes)), jnp.int64))
    assert _digest(kernel, avals) == SLOT_MERGE_DIGESTS[case]


@pytest.mark.parametrize("which", ["partial", "merge"])
@pytest.mark.parametrize("case", sorted(SCHEMAS))
def test_sort_path_kernels_touch_no_row_at_a_time(case, which):
    """``jit(agg_partial)`` and ``jit(agg_merge)`` order their rows by
    two-operand sorts, reduce by scans and move their planes by one gather in
    and one out: no scatter with a batch-sized operand or update, at most two
    gathers with batch-sized indices, no sort of more than two operands, and
    no ``cond`` (one path, whatever the keys)."""
    kernel, avals = _sort_path_kernel(case, which)
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


@pytest.mark.parametrize("slots", [RULE, CUT, 4 * CUT])
@pytest.mark.parametrize("case", sorted(SCHEMAS))
def test_slot_sorted_kernel_touches_no_row_at_a_time(case, slots):
    """From the rule on ``jit(agg_dense_partial)`` is the sort path's body
    behind ONE sort of the slot id: no scatter of any size, nothing of
    ``slots x rows`` elements (nor of more than a row of words a batch row),
    no sort of more than two operands and none of a 64-bit operand, one
    gather with batch-sized indices in and one of ``out_cap`` rows out, no
    ``cond`` or ``while``."""
    kernel, _sizes, avals = _dense_kernel(case, slots)
    eqns = list(jaxpr_eqns(jax.make_jaxpr(kernel)(*avals).jaxpr))
    names_seen = {e.primitive.name for e in eqns}
    assert not [n for n in names_seen if n.startswith("scatter")]
    assert "cond" not in names_seen and "while" not in names_seen
    gathers = []
    for eqn in eqns:
        for v in list(eqn.invars) + list(eqn.outvars):
            # the widest thing is the word matrix: a few words a row
            assert v.aval.size <= 64 * CAPACITY, eqn
        if eqn.primitive.name == "sort":
            assert len(eqn.invars) <= 2, eqn
            assert all(v.aval.dtype.itemsize <= 4 for v in eqn.invars), eqn
        elif eqn.primitive.name == "gather":
            gathers.append(eqn.invars[1].aval.shape[0])
    assert sorted(gathers) == [min(slots, CAPACITY), CAPACITY]
    assert "sort" in names_seen and "cumsum" in names_seen


@pytest.mark.parametrize("case", sorted(SCHEMAS))
def test_the_wide_kernel_sorts_the_ids_halves_once_and_scatters_nothing(case):
    """A wide table (traced sizes, int64 id) is the slot-sorted body behind
    ONE sort of the id's two uint32 halves and the row iota: no scatter, no
    ``cond`` or ``while``, one gather with batch-sized indices in and one
    out, and the same program whatever the table's size."""
    key_dtypes, names, *_ = SCHEMAS[case]
    adt = tuple(KINDS[n][1] for n in names)
    kernel = A._dense_partial_kernel.__wrapped__(
        key_dtypes, tuple(KINDS[n][0] for n in names), adt, CAPACITY, None,
        CAPACITY)
    avals = _avals(key_dtypes, adt, CAPACITY)
    avals.insert(1, jax.ShapeDtypeStruct((3, len(key_dtypes)), jnp.int64))
    eqns = list(jaxpr_eqns(jax.make_jaxpr(kernel)(*avals).jaxpr))
    names_seen = {e.primitive.name for e in eqns}
    assert not [n for n in names_seen if n.startswith("scatter")]
    assert "cond" not in names_seen and "while" not in names_seen
    sorts = [e for e in eqns if e.primitive.name == "sort"]
    # the id's halves and the iota; the emit's flags and the iota
    assert [[str(v.aval.dtype) for v in e.invars] for e in sorts] == [
        ["uint32", "uint32", "int32"], ["uint8", "int32"]]
    gathers = [e.invars[1].aval.shape[0] for e in eqns
               if e.primitive.name == "gather"]
    assert sorted(gathers) == [CAPACITY, CAPACITY]


SMALL = 256  # rows a batch of the equality tests: 200 and 56 padding rows


def _dense_inputs(case, sizes, data, seed):
    """(exists, keys, args, bases) in numpy for a kernel of ``SMALL`` rows:
    keys inside the slot table's ranges but for ``out_of_range``."""
    from tests.test_agg_sorted import _valid, _value_planes

    key_dtypes, names, *_ = SCHEMAS[case]
    rng = np.random.default_rng(seed)
    exists = np.arange(SMALL) < (SMALL if data == "one_slot" else 200)
    bases = [-5, 3][:len(sizes)]
    keys = []
    for kd, base, size in zip(key_dtypes, bases, sizes):
        if data == "one_slot":  # every row in the table's last slot
            k, v = np.full(SMALL, base + size - 2), np.ones(SMALL, bool)
        else:
            # crowded low codes, some far ones: more slots than groups
            k = base + rng.integers(0, min(size - 1, 12), SMALL)
            k[::7] = base + rng.integers(0, size - 1, len(k[::7]))
            v = _valid(rng, SMALL)
        keys.append((k.astype(kd), v))
    if data == "out_of_range":
        keys[0][0][17], keys[0][1][17] = bases[0] + sizes[0] - 1, True
    draw = "extremes" if data == "one_slot" else "random"
    args = [(_value_planes(rng, KINDS[n][1], SMALL, draw), _valid(rng, SMALL))
            for n in names]
    return exists, keys, args, np.array(bases, np.int64)


@pytest.mark.parametrize("slots", [RULE, CUT, 4 * CUT])
@pytest.mark.parametrize("case", sorted(SCHEMAS))
def test_slot_sorted_form_equals_the_masked_form_and_numpy(case, slots):
    """Every aggregate kind at the first size that sorts, at 16,384 and at
    65,536 slots: the slot-sorted form gives the masked form's outputs plane
    for plane, row for row and in row order (a float sum by the suite's
    tolerance), and both give a numpy group-by's (``test_agg_sorted``'s
    reference; the groups in ascending slot order, which is key order with
    nulls first), over null keys, null arguments, garbage in the padding
    rows, far more slots than groups, every row in one slot with sums that
    wrap, and a key outside the table (``num_groups`` -1 from both)."""
    from tests.test_agg_sorted import check_outputs, partial_state

    _key_dtypes, names, *_ = SCHEMAS[case]
    sorted_kernel, sizes, _ = _dense_kernel(case, slots, SMALL)
    masked_kernel, _, _ = _dense_kernel(case, slots, SMALL, form="masked")
    assert A._is_slot_sorted(sizes, SMALL, 0)
    for seed, data in enumerate(("random", "one_slot", "out_of_range")):
        exists, keys, args, bases = _dense_inputs(case, sizes, data,
                                                  100 * slots + seed)
        flat = [p for d, v in keys for p in (d, v)]
        flat += [p for planes, v in args for p in (*planes, v)]
        flat = [jnp.asarray(exists), jnp.asarray(bases),
                *map(jnp.asarray, flat)]
        got = [np.asarray(o) for o in sorted_kernel(*flat)]
        want = [np.asarray(o) for o in masked_kernel(*flat)]
        assert len(got) == len(want)
        if data == "out_of_range":
            assert int(got[0]) == int(want[0]) == -1
            continue
        for i, (g, w) in enumerate(zip(got, want)):
            assert g.dtype == w.dtype and g.shape == w.shape, i
            if g.dtype.kind == "f":  # a float sum's order is the form's own
                np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-2)
            else:
                assert np.array_equal(g, w), (data, i)
        # under a null key the slot table's data plane reads base - 1, in
        # either form (only the validity is contract); the reference reads 0
        for at in range(2, 2 + 2 * len(keys), 2):
            got[at] = np.where(got[at + 1], got[at], 0).astype(got[at].dtype)
        check_outputs(got, exists, keys, names,
                      lambda i, rows: partial_state(names[i], *args[i], rows),
                      out_len=SMALL)


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
    # one slot-sorted table as wide as the range
    wide = _agger()
    out = wide.process(_batch([5, 900_005] * 100, [2] * 200))
    s3 = DEVICE_STATS.snapshot()
    assert sorted(out.to_arrow().to_pydict()["s#sum"]) == [200, 200]
    assert s3["agg_dense_batches"] - s2["agg_dense_batches"] == 1
    assert s3["agg_sort_batches"] == s2["agg_sort_batches"]
    if backend_is_cpu:
        assert wide._bucket_state[0] == "radix"
    else:
        assert wide._bucket_state == ("wide", (5,), (1 << 20,), 256)
        assert s3["agg_slot_sorted_batches"] - \
            s2["agg_slot_sorted_batches"] == 1


def test_slot_sorted_batches_are_counted_and_a_key_outside_widens():
    """``agg_slot_sorted_batches`` counts the slot-table batches whose table
    was large enough to reduce by a sort, and no other; a key outside such a
    table reads -1, and ``_try_dense`` probes again and runs the widened
    table, still slot-sorted, with exact results."""
    def delta(after, before):
        return {k: after[k] - before[k] for k in (
            "agg_dense_batches", "agg_slot_sorted_batches",
            "agg_sort_batches")}

    s0 = DEVICE_STATS.snapshot()
    _agger().process(_batch([3, 4, 5] * 100, [1] * 300))
    s1 = DEVICE_STATS.snapshot()
    assert delta(s1, s0) == {"agg_dense_batches": 1, "agg_sort_batches": 0,
                             "agg_slot_sorted_batches": 0}
    agger = _agger()
    n = 4 * RULE
    ks = 5 + np.arange(n) % (RULE - 2)  # RULE - 2 values and the null: RULE
    out = agger.process(_batch(ks.tolist(), [1] * n))
    s2 = DEVICE_STATS.snapshot()
    _, bases, sizes, _ = agger._bucket_state
    assert sizes == (RULE,) and out.num_rows == RULE - 2
    assert delta(s2, s1) == {"agg_dense_batches": 1, "agg_sort_batches": 0,
                             "agg_slot_sorted_batches": 1}
    ks[-1] = 5 + RULE + 10
    out = agger.process(_batch(ks.tolist(), [2] * n))
    s3 = DEVICE_STATS.snapshot()
    assert agger._bucket_state[0] == "dense"
    assert agger._bucket_state[2] == (2 * RULE,), "the union of both ranges"
    assert delta(s3, s2) == {"agg_dense_batches": 1, "agg_sort_batches": 0,
                             "agg_slot_sorted_batches": 1}
    # the -1 run, the second probe and the widened run: three syncs
    assert s3["sync_calls"] - s2["sync_calls"] == 3
    got = out.to_arrow().to_pydict()
    want = {}
    for k in ks.tolist():
        want[k] = want.get(k, 0) + 2
    assert got["k1"] == sorted(want), "groups leave in key order"
    assert got["s#sum"] == [want[k] for k in sorted(want)]


# -- a table past dense_agg_max_buckets, where no radix table is planned --------

WIDE_KEYS = ("k1", "k2", "k3", "k4", "k5")
# name -> the key columns' (anchor, span of values) on the first batch; the
# second batch adds one key a span past the first key's, which widens it
WIDE_TABLES = {
    # 32,768 x 16 slots: q67's item x store
    "2key_under_2e31": ((10**12, 20_000), (-7, 12)),
    # 1,048,576 x 4,096 slots
    "2key_past_2e31": ((-(10**15), 600_000), (5, 3_000)),
    # 1,024 x 32 x 8 x 8 x 4 slots
    "5key_under_2e31": ((10**12, 600), (-40, 20), (0, 5), (3, 5), (9, 2)),
    # 32,768 x 1,024 x 64 x 16 x 2 slots: q22's finest set
    "5key_past_2e31": ((10**12, 18_000), (-500, 1_000), (0, 50), (3, 10),
                       (7, 1)),
}
# batches of capacity 4,096, 8,192 (more groups than the first one holds)
# and 4,096
WIDE_ROWS = (3_000, 5_000, 3_000)


def _wide_agger(nkeys: int, conf):
    from blaze_tpu.config import Config

    schema = pa.schema([(k, pa.int64()) for k in WIDE_KEYS[:nkeys]] +
                       [("v", pa.int64())])
    scan = _scan_stub(schema)
    mode = E.AggMode.PARTIAL
    node = N.Agg(scan, E.AggExecMode.HASH_AGG,
                 [(k, E.Column(k)) for k in WIDE_KEYS[:nkeys]], [
        N.AggColumn(E.AggExpr(E.AggFunction.SUM, [E.Column("v")]), mode, "s"),
        N.AggColumn(E.AggExpr(E.AggFunction.COUNT, []), mode, "c"),
        N.AggColumn(E.AggExpr(E.AggFunction.MIN, [E.Column("v")]), mode, "mn"),
        N.AggColumn(E.AggExpr(E.AggFunction.MAX, [E.Column("v")]), mode, "mx")])
    return DevicePartialAgger(build_operator(node), T.schema_from_arrow(schema),
                              conf=Config(**{"radix_agg": False, **conf}))


def _wide_batches(ranges, seed):
    """``WIDE_ROWS`` batches over ``ranges`` with 2% null keys and null
    values; the last holds one key past the first key's span."""
    rng = np.random.default_rng(seed)
    tables = []
    for b, rows in enumerate(WIDE_ROWS):
        cols = {}
        for i, (anchor, span) in enumerate(ranges):
            k = anchor + rng.integers(0, span, rows)
            k[::50] = anchor + rng.integers(0, 3, len(k[::50]))  # groups > 1 row
            if b == len(WIDE_ROWS) - 1 and i == 0:
                k[17] = anchor + 2 * span
            ks = k.astype(object)
            ks[rng.random(rows) < 0.02] = None
            cols[WIDE_KEYS[i]] = pa.array(list(ks), type=pa.int64())
        v = rng.integers(-10**6, 10**6, rows).astype(object)
        v[rng.random(rows) < 0.02] = None
        cols["v"] = pa.array(list(v), type=pa.int64())
        tables.append(pa.table(cols))
    return tables


def _oracle(table, nkeys):
    """Numpy's partial state of one batch: groups nulls first, ascending."""
    keys = [table[k].to_pylist() for k in WIDE_KEYS[:nkeys]]
    vals = table["v"].to_pylist()
    groups = {}
    for row, v in zip(zip(*keys), vals):
        g = groups.setdefault(row, [0, 0, None, None])
        g[1] += 1
        if v is not None:
            g[0] += v
            g[2] = v if g[2] is None else min(g[2], v)
            g[3] = v if g[3] is None else max(g[3], v)
    order = sorted(groups, key=lambda r: tuple((k is not None, k or 0)
                                               for k in r))
    return order, [groups[r] for r in order]


@pytest.mark.parametrize("case", sorted(WIDE_TABLES))
def test_a_table_past_the_cap_is_slot_sorted_and_equals_the_sort_kernel(case):
    """Without a radix table (the chip) a key space past
    ``dense_agg_max_buckets`` is ONE wide slot table, slot-sorted, its id
    int64 and its sizes traced, below 2^31 slots and past. Over nulls,
    anchors far from zero
    a batch with more groups than the one the table was planned on, and a
    key outside the table that widens it once, each batch's partial state
    is the sort kernel's row for row and numpy's."""
    ranges = WIDE_TABLES[case]
    nkeys = len(ranges)
    agger = _wide_agger(nkeys, {})
    sort_agger = _wide_agger(nkeys, {"dense_agg": False})
    plans = []
    for b, table in enumerate(_wide_batches(ranges, sum(map(ord, case)))):
        batch = ColumnarBatch.from_arrow(table)
        s0 = DEVICE_STATS.snapshot()
        got = agger.process(batch).to_arrow()
        s1 = DEVICE_STATS.snapshot()
        assert s1["agg_slot_sorted_batches"] - s0["agg_slot_sorted_batches"] \
            == s1["agg_dense_batches"] - s0["agg_dense_batches"] == 1
        assert s1["agg_sort_batches"] == s0["agg_sort_batches"]
        plans.append(agger._bucket_state)
        want = sort_agger.process(batch).to_arrow()
        assert got.to_pydict() == want.to_pydict(), b
        order, states = _oracle(table, nkeys)
        got = got.to_pydict()
        assert list(zip(*(got[k] for k in WIDE_KEYS[:nkeys]))) == order
        assert got["c#count"] == [s[1] for s in states]
        assert [s if h else 0 for s, h in zip(got["s#sum"], got["s#has"])] \
            == [s[0] for s in states]
        assert [m if h else None for m, h in zip(got["mn#val"],
                                                 got["mn#has"])] == \
            [s[2] for s in states]
        assert [m if h else None for m, h in zip(got["mx#val"],
                                                 got["mx#has"])] == \
            [s[3] for s in states]
    (kind0, _, sizes0, _), second, (kind2, _, sizes2, _) = plans
    assert second == plans[0] and kind0 == kind2 == "wide"
    assert sizes2[0] > sizes0[0] and sizes2[1:] == sizes0[1:], "widened once"
    slots = math.prod(sizes0)
    assert slots > agger.conf.dense_agg_max_buckets
    assert (slots >= 1 << 31) == ("past" in case)


def test_an_id_past_62_bits_takes_the_sort_path():
    """A table whose packed id would pass ``_SLOT_ID_MAX_SLOTS`` is refused:
    the stream takes the sort kernel, exactly."""
    from blaze_tpu.config import Config

    conf = Config(radix_agg=False)
    probe = np.array([(1, 0, (1 << 31) - 3), (1, 0, (1 << 30) - 3)])
    bases, sizes, out_cap = A._plan_slot_table(
        probe, 4096, None, A._SLOT_ID_MAX_SLOTS, conf)
    assert sizes == (1 << 31, 1 << 30) and out_cap == 4096
    probe[1, 2] = (1 << 31) - 3
    assert A._plan_slot_table(probe, 4096, None, A._SLOT_ID_MAX_SLOTS,
                              conf) is None
    agger = _wide_agger(2, {})
    s0 = DEVICE_STATS.snapshot()
    out = agger.process(ColumnarBatch.from_arrow(pa.table({
        "k1": pa.array([0, 1 << 40, 0], type=pa.int64()),
        "k2": pa.array([0, 1 << 30, 0], type=pa.int64()),
        "v": pa.array([1, 2, 3], type=pa.int64())})))
    s1 = DEVICE_STATS.snapshot()
    assert agger._bucket_state is None and agger._dense_ok is False
    assert s1["agg_sort_batches"] - s0["agg_sort_batches"] == 1
    assert s1["agg_dense_batches"] == s0["agg_dense_batches"]
    got = out.to_arrow().to_pydict()
    assert got["k1"] == [0, 1 << 40] and got["s#sum"] == [4, 2]


def test_with_the_radix_gate_on_a_table_past_the_cap_plans_radix():
    """Where ``radix_agg`` is on (the CPU backend's default) the planning is
    the parent's: dense within the cap, radix up to ``radix_agg_max_slots``,
    the sort kernel past it."""
    agger = _wide_agger(2, {"radix_agg": True})
    batch = _wide_batches(WIDE_TABLES["2key_under_2e31"], 5)[0]
    out = agger.process(ColumnarBatch.from_arrow(batch))
    assert agger._bucket_state[0] == "radix"
    assert math.prod(agger._bucket_state[2]) == 32_768 * 16
    order, _ = _oracle(batch, 2)
    assert list(zip(*(out.to_arrow()[k].to_pylist() for k in ("k1", "k2")))) \
        == order
    wide = _wide_agger(2, {"radix_agg": True})
    wide.process(ColumnarBatch.from_arrow(
        _wide_batches(WIDE_TABLES["2key_past_2e31"], 5)[0]))
    assert wide._bucket_state is None and wide._radix_ok is False


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
