"""The fused inner-join kernel (``jit(bhj_inner_fast)``, ops/joins/bhj.py)
against the generic host probe path (``probe_codes`` + ``probe`` +
``_emit_probe_batch``): the same rows in the same order, the same count, and
the padding contract past it. The benchmark's cells only send it batches in
which every row hits; the selective, null-keyed and ragged cases live here."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from blaze_tpu.core.batch import ColumnarBatch, DeviceColumn
from blaze_tpu.ir import exprs as E
from blaze_tpu.ir import types as T
from blaze_tpu.ir.nodes import JoinSide, JoinType
from blaze_tpu.ops.basic import MemoryScanExec
from blaze_tpu.ops.joins.bhj import BroadcastJoinExec, _inner_fast_kernel
from blaze_tpu.ops.joins.keymap import JoinHashMap
from blaze_tpu.runtime.metrics import MetricNode
from tests.util import jaxpr_eqns


def _column(rng, n, cap, data=None, null_share=0.0, garbage=False):
    """An int64 device column of ``n`` rows in ``cap`` slots. ``garbage``
    breaks the padding contract on purpose: a kernel must mask by the row
    count, not trust what lies past it."""
    d = np.zeros(cap, np.int64)
    v = np.zeros(cap, bool)
    d[:n] = rng.integers(-10**12, 10**12, n) if data is None else data
    v[:n] = rng.random(n) >= null_share
    d[:n] = np.where(v[:n], d[:n], 0)
    if garbage:
        d[n:] = rng.integers(1, 50, cap - n)
        v[n:] = True
    return DeviceColumn(T.I64, jnp.asarray(d), jnp.asarray(v))


def _batch(prefix, cols, n):
    schema = T.Schema.of(*[(f"{prefix}{i}", T.I64, True)
                           for i in range(len(cols))])
    return ColumnarBatch(schema, cols, n)


# each: (rng, rows) -> (probe keys, build keys)
def _every_row_hits(rng, n):
    bk = rng.permutation(np.arange(100, 100 + 300))
    return rng.choice(bk, n), bk


def _no_row_hits(rng, n):
    return rng.integers(1000, 2000, n), np.arange(300)


def _alternating_hits(rng, n):
    bk = rng.permutation(np.arange(0, 600, 2))
    return np.arange(n) % 600, bk


def _selective_build(rng, n):
    # a filtered dimension: a tenth of the key domain is on the build side
    bk = rng.permutation(np.arange(0, 3000, 10))
    return rng.integers(0, 3000, n), bk


CASES = {
    "every_row_hits": dict(keys=_every_row_hits),
    "no_row_hits": dict(keys=_no_row_hits, expect_none=True),
    "alternating_hits": dict(keys=_alternating_hits),
    "selective_build": dict(keys=_selective_build),
    "null_probe_keys": dict(keys=_every_row_hits, key_nulls=0.3),
    "garbage_past_num_rows": dict(keys=_selective_build, n=700, cap=1024,
                                  garbage=True),
    "capacities_differ": dict(keys=_selective_build, n=900, cap=1024,
                              payload_cap=2048, build_cap=512),
    "two_validity_words": dict(keys=_selective_build, n_payload=34,
                               payload_nulls=0.2),
    "probe_on_right": dict(keys=_selective_build, probe_on_left=False,
                           payload_nulls=0.2),
    "probe_on_left_nulls": dict(keys=_alternating_hits, key_nulls=0.1,
                                payload_nulls=0.2, garbage=True, n=1000,
                                cap=1024),
}


def _run_case(keys, n=1000, cap=1024, build_cap=None, payload_cap=None,
              n_payload=2, key_nulls=0.0, payload_nulls=0.0, garbage=False,
              probe_on_left=True, expect_none=False):
    rng = np.random.default_rng(27)
    pk, bk = keys(rng, n)
    # build side: one null key (it matches nothing) and nulls in the payload
    bk = np.concatenate([bk, [0]])
    bcap = build_cap or 1 << int(np.ceil(np.log2(len(bk))))
    bcols = [_column(rng, len(bk), bcap, data=bk)]
    kv = np.asarray(bcols[0].validity).copy()
    kv[len(bk) - 1] = False
    bcols[0] = DeviceColumn(T.I64, bcols[0].data, jnp.asarray(kv))
    bcols += [_column(rng, len(bk), bcap, null_share=payload_nulls)
              for _ in range(n_payload)]
    build = _batch("b", bcols, len(bk))
    pcols = [_column(rng, n, cap, data=pk, null_share=key_nulls,
                     garbage=garbage)]
    pcols += [_column(rng, n, payload_cap or cap, null_share=payload_nulls,
                      garbage=garbage) for _ in range(n_payload)]
    probe = _batch("p", pcols, n)

    left, right = (probe, build) if probe_on_left else (build, probe)
    op = BroadcastJoinExec(
        MemoryScanExec(left.schema, [[left]]),
        MemoryScanExec(right.schema, [[right]]),
        [(E.Column(left.schema.fields[0].name),
          E.Column(right.schema.fields[0].name))],
        JoinType.INNER, JoinSide.RIGHT if probe_on_left else JoinSide.LEFT)
    bmap = JoinHashMap.build([build], [E.Column("b0")], build.schema)
    assert bmap.unique_single_key
    key_cols = [probe.columns[0]]

    metrics = MetricNode("bhj")
    got = op._inner_fast(probe, bmap, key_cols, probe_on_left, metrics)
    assert got is not NotImplemented
    assert metrics.total("device_inner_batches") == 1

    codes, on_device = bmap.probe_codes(probe, key_cols)
    assert on_device
    probe_idx, build_idx, counts = bmap.probe(codes)
    want = op._emit_probe_batch(probe, bmap, probe_idx, build_idx, counts,
                                False, probe_on_left, JoinType.INNER)
    if expect_none:
        assert got is None and want is None
        return
    assert want.num_rows > 0
    assert got.num_rows == want.num_rows
    assert got.schema == op.schema
    # rows and their order
    assert got.to_arrow().equals(want.to_arrow())
    # padding contract past the count: data 0, validity False
    for c in got.columns:
        assert isinstance(c, DeviceColumn) and c.capacity == cap
        assert not np.asarray(c.data)[got.num_rows:].any()
        assert not np.asarray(c.validity)[got.num_rows:].any()


@pytest.mark.parametrize("case", list(CASES))
def test_fused_kernel_matches_generic_probe(case):
    _run_case(**CASES[case])


def test_lowered_kernel_moves_planes_by_gather():
    """What the chip charges for (PERF.md §6, PR 27): no scatter at all (the
    parent had a row-sized one for every data and validity plane), no binary
    search made of gathers (the scan form of ``searchsorted`` is a ``while``
    loop), no sort with more than two operands (a 64-bit operand is a minute
    of cold compile) and only the probe's with a 64-bit one, and one gather
    a side, however many planes it has."""
    cap, nk, ncols = 1024, 300, 3
    i64 = jax.ShapeDtypeStruct((cap,), jnp.int64)
    mask = jax.ShapeDtypeStruct((cap,), jnp.bool_)
    b64 = jax.ShapeDtypeStruct((512,), jnp.int64)
    bmask = jax.ShapeDtypeStruct((512,), jnp.bool_)
    kernel = _inner_fast_kernel(ncols, nk)
    jaxpr = jax.make_jaxpr(kernel)(
        jax.ShapeDtypeStruct((nk,), jnp.int64),
        jax.ShapeDtypeStruct((), jnp.int64), i64, mask,
        *([i64, mask] * ncols), *([b64, bmask] * 2))
    prims = {}
    for eqn in jaxpr_eqns(jaxpr.jaxpr):
        prims.setdefault(eqn.primitive.name, []).append(eqn)
    assert not [name for name in prims if name.startswith("scatter")]
    assert "while" not in prims and "scan" not in prims
    wide = 0
    for e in prims["sort"]:
        assert len(e.invars) <= 2, e
        wide += sum(v.aval.dtype.itemsize == 8 for v in e.invars)
    assert len(prims["sort"]) == 3 and wide == 1
    assert len(prims["gather"]) == 2


@pytest.mark.parametrize("dtype", ["bool", "int8", "int16", "int32", "int64",
                                   "float32", "float64"])
def test_take_rows_matches_take_planes(dtype):
    """``take_rows_traced`` (one gather for all planes, as a matrix of
    32-bit words) against a gather a plane in numpy: bit for bit, NaN
    payloads and -0.0 included, over 40 validity planes (two packed words),
    planes of two capacities and rows that are not live."""
    from blaze_tpu.core.kernels import take_rows_traced

    rng = np.random.default_rng(5)
    cap, n_out, n_rows = 512, 256, 300
    raw = rng.integers(0, 256, (3, 2 * cap * 8), dtype=np.uint8)
    datas = []
    for i, c in enumerate((cap, 2 * cap, cap)):
        d = raw[i, :c * np.dtype(dtype).itemsize].view(dtype) \
            if dtype != "bool" else raw[i, :c] > 127
        datas.append(d)
    datas.append(rng.integers(-2**62, 2**62, cap))  # mixed widths
    valids = [rng.random(cap if i % 2 else 2 * cap) < 0.7 for i in range(40)]
    idx = rng.integers(0, n_rows, n_out).astype(np.int32)
    live = np.arange(n_out) < 200
    want_d = [np.where(live, d[idx], np.zeros((), d.dtype)) for d in datas]
    want_v = [v[idx] & live for v in valids]
    got_d, got_v = jax.jit(take_rows_traced)(
        [jnp.asarray(d) for d in datas], [jnp.asarray(v) for v in valids],
        jnp.asarray(idx), jnp.asarray(live))
    assert len(got_d) == len(want_d) and len(got_v) == len(want_v)
    for g, w in zip(got_d + got_v, want_d + want_v):
        assert g.dtype == w.dtype and g.shape == w.shape
        assert np.asarray(g).tobytes() == w.tobytes()
    assert take_rows_traced((), (), idx, live) == ((), ())
