"""The row movers of ``core/kernels.py`` (and ``ops/sort.sort_take``) against
numpy, bit for bit: contiguous moves are slice copies (``concat_planes``,
``slice_planes``), permutation moves one gather of a matrix of words
(``gather_planes``, ``compact_planes``, ``sort_take``). Every mover keeps the
padding contract: data 0 and validity False beyond the live rows."""

import datetime

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pytest

from blaze_tpu.core import kernels as K
from blaze_tpu.core.batch import ColumnarBatch
from blaze_tpu.ops.sort import sort_take

DTYPES = ["bool", "int8", "int16", "int32", "int64", "float32", "float64",
          "date"]


def _plane(rng, dtype, cap):
    """``cap`` values of ``dtype`` from random bytes (so floats hold NaNs of
    every payload, infinities and denormals), -0.0 and a NaN put in by hand.
    A date plane is what the engine holds: int32 days."""
    if dtype == "bool":
        return rng.random(cap) < 0.5
    np_dtype = np.dtype("int32" if dtype == "date" else dtype)
    plane = rng.integers(0, 256, cap * np_dtype.itemsize, dtype=np.uint8) \
        .view(np_dtype).copy()
    if np_dtype.kind == "f" and cap >= 2:
        plane[0], plane[1] = -0.0, np.nan
    return plane


def _planes(rng, dtype, caps):
    return ([_plane(rng, dtype, c) for c in caps],
            [rng.random(c) < 0.7 for c in caps])


def _device(planes):
    return [jnp.asarray(p) for p in planes]


def _want(datas, valids, idx, live):
    """numpy's answer: rows ``idx`` where ``live``, else the padding."""
    idx = np.asarray(idx, dtype=np.int64)
    want_d = [np.where(live, d[np.clip(idx, 0, len(d) - 1)],
                       np.zeros((), d.dtype)) for d in datas]
    want_v = [v[np.clip(idx, 0, len(v) - 1)] & live for v in valids]
    return want_d, want_v


def _same(got, want):
    got_d, got_v = got
    want_d, want_v = want
    assert len(got_d) == len(want_d) and len(got_v) == len(want_v)
    for g, w in zip((*got_d, *got_v), (*want_d, *want_v)):
        g = np.asarray(g)
        assert g.dtype == w.dtype and g.shape == w.shape
        assert g.tobytes() == w.tobytes()


# rows out of a 64-row plane: none, one, all of the capacity, and some
@pytest.mark.parametrize("n_out", [0, 1, 64, 23])
@pytest.mark.parametrize("dtype", DTYPES)
def test_gather_planes(dtype, n_out):
    rng = np.random.default_rng(n_out)
    cap, num_rows = 64, 64 if n_out == 64 else 40
    datas, valids = _planes(rng, dtype, [cap, cap])
    idx = rng.integers(0, num_rows, n_out)
    out_cap = max(8, 1 << max(n_out - 1, 0).bit_length())
    live = np.arange(out_cap) < n_out
    padded = np.zeros(out_cap, np.int64)
    padded[:n_out] = idx
    got = K.gather_planes(_device(datas), _device(valids), idx, out_cap, n_out)
    _same(got, _want(datas, valids, padded, live))
    # the outer join's extension: rows of the null mask come out null
    null_mask = rng.random(n_out) < 0.3
    live_n = live.copy()
    live_n[:n_out] &= ~null_mask
    got = K.gather_planes(_device(datas), _device(valids),
                          np.where(null_mask, 0, idx), out_cap, n_out,
                          null_mask=null_mask)
    _same(got, _want(datas, valids, np.where(live_n, padded, 0), live_n))


@pytest.mark.parametrize("mask_kind", ["none", "all", "some", "one"])
@pytest.mark.parametrize("dtype", DTYPES)
def test_compact_planes(dtype, mask_kind):
    rng = np.random.default_rng(7)
    cap = 128
    datas, valids = _planes(rng, dtype, [cap, cap, cap])
    mask = {"none": np.zeros(cap, bool), "all": np.ones(cap, bool),
            "some": rng.random(cap) < 0.4,
            "one": np.arange(cap) == 77}[mask_kind]
    count, out_d, out_v = K.compact_planes(_device(datas), _device(valids),
                                           jnp.asarray(mask))
    kept = np.flatnonzero(mask)
    assert count == len(kept)
    idx = np.zeros(cap, np.int64)
    idx[:len(kept)] = kept
    _same((out_d, out_v), _want(datas, valids, idx, np.arange(cap) < count))


# (capacity, num_rows, offset, length, out_cap)
SLICES = {
    "head": (256, 200, 0, 64, 64),
    "clamp": (256, 250, 224, 26, 64),       # offset + out_cap > capacity
    "to_the_end": (256, 200, 136, 64, 64),  # offset + length = num_rows
    "last_row": (256, 256, 255, 1, 8),      # one row, the plane's last
    "empty": (256, 200, 200, 0, 8),
    "whole": (256, 256, 0, 256, 256),       # rows = capacity
    "past_capacity": (64, 64, 32, 32, 128),  # out_cap over the capacity
}


@pytest.mark.parametrize("case", list(SLICES))
@pytest.mark.parametrize("dtype", DTYPES)
def test_slice_planes(dtype, case):
    cap, _num_rows, offset, length, out_cap = SLICES[case]
    rng = np.random.default_rng(11)
    datas, valids = _planes(rng, dtype, [cap, cap])
    got = K.slice_planes(_device(datas), _device(valids), offset, length,
                         out_cap)
    live = np.arange(out_cap) < length
    _same(got, _want(datas, valids, offset + np.arange(out_cap), live))


def _concat_case(k):
    """Row counts and capacities of k parts: empty parts, a part at full
    capacity, the rest in between."""
    rng = np.random.default_rng(k)
    caps = [int(c) for c in rng.choice([8, 16, 32], k)]
    rows = [int(rng.integers(0, c + 1)) for c in caps]
    rows[0] = caps[0]                      # a part at full capacity
    if k > 1:
        rows[1] = 0                        # an empty part
    if k > 2:
        rows[-1] = 0                       # and one at the end
    return caps, rows


def _want_concat(fields, rows, out_cap):
    """numpy's answer for ``concat_planes``: per field, every part's live
    rows one after the other, then the padding."""
    want_d, want_v = [], []
    for datas, valids in fields:
        for planes, want in ((datas, want_d), (valids, want_v)):
            out = np.zeros(out_cap, planes[0].dtype)
            out[:sum(rows)] = np.concatenate(
                [p[:n] for p, n in zip(planes, rows)])
            want.append(out)
    return want_d, want_v


@pytest.mark.parametrize("k", [1, 2, 7, 64])
@pytest.mark.parametrize("dtype", DTYPES)
def test_concat_planes(dtype, k):
    caps, rows = _concat_case(k)
    rng = np.random.default_rng(100 + k)
    fields = [_planes(rng, dtype, caps) for _ in range(2)]
    total = sum(rows)
    out_cap = max(8, 1 << max(total - 1, 0).bit_length())
    got = K.concat_planes(
        [tuple(_device(d)) for d, _ in fields],
        [tuple(_device(v)) for _, v in fields], rows, out_cap)
    _same(got, _want_concat(fields, rows, out_cap))


def test_concat_planes_all_parts_empty():
    rng = np.random.default_rng(3)
    datas, valids = _planes(rng, "int64", [16, 32])
    got = K.concat_planes([tuple(_device(datas))], [tuple(_device(valids))],
                          [0, 0], 8)
    _same(got, ([np.zeros(8, np.int64)], [np.zeros(8, bool)]))


@pytest.mark.parametrize("n_out", [0, 1, 100, 128])
@pytest.mark.parametrize("dtype", DTYPES)
def test_sort_take(dtype, n_out):
    rng = np.random.default_rng(n_out + 1)
    cap = 128
    datas, valids = _planes(rng, dtype, [cap, cap])
    order = rng.permutation(cap).astype(np.int32)
    out_cap = max(8, 1 << max(n_out - 1, 0).bit_length())
    got = sort_take(jnp.asarray(order), tuple(_device(datas)),
                    tuple(_device(valids)), np.int32(n_out), out_cap=out_cap)
    _same(got, _want(datas, valids, order[:out_cap],
                     np.arange(out_cap) < n_out))


def _mover_results(mover, datas, valids, num_rows):
    """One call of ``mover`` over the planes' first ``num_rows`` rows, and
    numpy's answer for it."""
    rng = np.random.default_rng(17)
    dd, dv = _device(datas), _device(valids)
    if mover == "gather":
        idx = rng.integers(0, num_rows, 48)
        padded = np.zeros(64, np.int64)
        padded[:48] = idx
        return (K.gather_planes(dd, dv, idx, 64, 48),
                _want(datas, valids, padded, np.arange(64) < 48))
    if mover == "compact":
        cap = min(len(p) for p in (*datas, *valids))
        mask = (rng.random(cap) < 0.5) & (np.arange(cap) < num_rows)
        count, out_d, out_v = K.compact_planes(dd, dv, jnp.asarray(mask))
        kept = np.flatnonzero(mask)
        idx = np.zeros(cap, np.int64)
        idx[:len(kept)] = kept
        assert count == len(kept)
        return ((out_d, out_v),
                _want(datas, valids, idx, np.arange(cap) < count))
    if mover == "slice":
        offset, length = num_rows - 20, 20
        return (K.slice_planes(dd, dv, offset, length, 32),
                _want(datas, valids, offset + np.arange(32),
                      np.arange(32) < length))
    if mover == "sort_take":
        cap = min(len(p) for p in (*datas, *valids))
        order = rng.permutation(cap).astype(np.int32)
        return (sort_take(jnp.asarray(order), tuple(dd), tuple(dv),
                          np.int32(30), out_cap=32),
                _want(datas, valids, order[:32], np.arange(32) < 30))
    raise AssertionError(mover)


MOVERS = ["gather", "compact", "slice", "sort_take"]


@pytest.mark.parametrize("mover", MOVERS)
def test_planes_of_unequal_capacity(mover):
    """A batch's columns may differ in capacity; the live rows lie below the
    shortest, and every mover's output is cut from there."""
    rng = np.random.default_rng(23)
    num_rows = 60
    datas = [_plane(rng, "int64", 64), _plane(rng, "float32", 128),
             _plane(rng, "int8", 256), _plane(rng, "bool", 64)]
    valids = [rng.random(c) < 0.7 for c in (128, 64, 64, 256)]
    got, want = _mover_results(mover, datas, valids, num_rows)
    _same(got, want)


@pytest.mark.parametrize("mover", MOVERS)
def test_more_than_32_validity_planes_and_128_words(mover):
    """70 int64 planes are 140 words of a row, past the 128 lanes a row of
    the word matrix is laid to; 70 validity planes are three packed words."""
    rng = np.random.default_rng(29)
    cap = 64
    datas = [_plane(rng, "int64", cap) for _ in range(70)]
    valids = [rng.random(cap) < 0.7 for _ in range(70)]
    got, want = _mover_results(mover, datas, valids, num_rows=50)
    _same(got, want)


@pytest.mark.parametrize("words", [8, 16, 24])
def test_take_rows_in_several_matrices(words, monkeypatch):
    """Past ``_WORD_MATRIX_BYTES`` the words travel in several matrices, a
    gather each: the same answer whatever the groups (here 8, 16 or 24 words
    a matrix for 11 int64, 3 narrow and 40 validity planes: 27 words)."""
    rng = np.random.default_rng(words)
    cap, n_out = 96, 64
    monkeypatch.setattr(K, "_WORD_MATRIX_BYTES", 4 * cap * words)
    datas = [_plane(rng, "int64", cap) for _ in range(11)] + [
        _plane(rng, "float32", cap), _plane(rng, "int8", cap),
        _plane(rng, "bool", cap)]
    valids = [rng.random(cap) < 0.7 for _ in range(40)]
    idx = rng.integers(0, cap, n_out).astype(np.int32)
    live = np.arange(n_out) < 50
    # a function of this test's own: jax caches a trace by the function, and
    # the limit is read while tracing
    def take(*args):
        return K.take_rows_traced(*args)

    args = (_device(datas), _device(valids), jnp.asarray(idx),
            jnp.asarray(live))
    _same(jax.jit(take)(*args), _want(datas, valids, idx, live))
    jaxpr = jax.make_jaxpr(take)(*args)
    gathers = sum(e.primitive.name == "gather" for e in jaxpr.jaxpr.eqns)
    assert gathers == {8: 4, 16: 2, 24: 2}[words]


def test_concat_of_wide_batches():
    rng = np.random.default_rng(31)
    caps, rows = [16, 32, 16], [16, 5, 9]
    fields = [_planes(rng, "int64", caps) for _ in range(70)]
    got = K.concat_planes([tuple(_device(d)) for d, _ in fields],
                          [tuple(_device(v)) for _, v in fields], rows, 32)
    _same(got, _want_concat(fields, rows, 32))


def test_concat_of_parts_whose_planes_differ_in_capacity():
    """Each plane is laid at its own capacity: a part's columns need not
    share one."""
    rng = np.random.default_rng(43)
    rows = [10, 0, 16]
    fields = [_planes(rng, "int64", [16, 8, 16]),
              _planes(rng, "float32", [32, 16, 64])]
    fields[1] = (fields[1][0], [rng.random(c) < 0.7 for c in (16, 32, 16)])
    got = K.concat_planes([tuple(_device(d)) for d, _ in fields],
                          [tuple(_device(v)) for _, v in fields], rows, 32)
    _same(got, _want_concat(fields, rows, 32))


def test_movers_issue_no_gather_a_plane():
    """What the issue asks of the programs themselves: a contiguous move
    gathers nothing, a permutation move gathers once, whatever the number
    of planes."""
    rng = np.random.default_rng(37)
    cap = 64
    datas, valids = _planes(rng, "int64", [cap] * 5)
    dd, dv = tuple(_device(datas)), tuple(_device(valids))

    def gathers(fn, *args, **kw):
        jaxpr = jax.make_jaxpr(fn, static_argnums=kw.pop("static", ()))(*args)
        found = []

        def walk(j):
            for e in j.eqns:
                found.append(e.primitive.name)
                for v in e.params.values():
                    for sub in (v if isinstance(v, (list, tuple)) else [v]):
                        if hasattr(sub, "jaxpr"):
                            walk(sub.jaxpr)
                        elif hasattr(sub, "eqns"):
                            walk(sub)
        walk(jaxpr.jaxpr)
        return found.count("gather"), found.count("scatter")

    idx = jnp.arange(cap, dtype=jnp.int32)
    assert gathers(K._gather_n, dd, dv, idx, jnp.int32(9)) == (1, 0)
    assert gathers(K._gather, dd, dv, idx, idx < 9) == (1, 0)
    assert gathers(K._compact, dd, dv, idx < 9) == (1, 0)
    assert gathers(sort_take, idx, dd, dv, jnp.int32(9), 32,
                   static=(4,)) == (1, 0)
    assert gathers(K._dyn_slice, dd, dv, jnp.int32(3), jnp.int32(9), 32,
                   static=(4,)) == (0, 0)
    parts = (tuple((d, d) for d in dd), tuple((v, v) for v in dv))
    assert gathers(K._concat_gather, *parts,
                   jnp.asarray([0, 5, 9], jnp.int32), 32,
                   static=(3,)) == (0, 0)


def _dated_table(n, seed):
    rng = np.random.default_rng(seed)
    day0 = datetime.date(1998, 1, 1)
    days = [None if rng.random() < 0.2 else
            day0 + datetime.timedelta(days=int(d))
            for d in rng.integers(0, 1823, n)]
    keys = pa.array(rng.integers(-2**62, 2**62, n), pa.int64())
    flags = pa.array([None if rng.random() < 0.2 else bool(b)
                      for b in rng.integers(0, 2, n)], pa.bool_())
    return pa.table({"d": pa.array(days, pa.date32()), "k": keys,
                     "f": flags})


def test_batch_take_slice_concat_keep_their_answers():
    """``ColumnarBatch.take`` / ``take_nullable`` / ``slice`` / ``concat``
    over a date, an int64 and a nullable bool column, against Arrow."""
    a, b, c = (_dated_table(n, n) for n in (37, 1, 64))
    batch = ColumnarBatch.from_arrow(c)
    idx = np.random.default_rng(41).integers(0, 64, 20)
    assert batch.take(idx).to_arrow().to_pydict() == c.take(idx).to_pydict()
    with_null = np.where(np.arange(20) % 5 == 0, -1, idx)
    want = c.take(pa.array([None if i < 0 else i for i in with_null]))
    assert batch.take_nullable(with_null).to_arrow().to_pydict() == \
        want.to_pydict()
    for offset, length in ((0, 64), (60, 10), (63, 1), (64, 0), (5, 20)):
        assert batch.slice(offset, length).to_arrow().to_pydict() == \
            c.slice(offset, length).to_pydict()
    joined = ColumnarBatch.concat(
        [ColumnarBatch.from_arrow(a), ColumnarBatch.from_arrow(b), batch])
    assert joined.num_rows == 102
    assert joined.to_arrow().to_pydict() == \
        pa.concat_tables([a, b, c]).to_pydict()
    # the padding contract, on the planes themselves
    for col in joined.columns:
        assert not np.asarray(col.validity)[102:].any()
        assert not np.asarray(col.data)[102:].any()
