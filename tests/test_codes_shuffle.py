"""Code-carrying shuffle: dictionary columns cross the exchange as index
codes plus once-per-stream dictionary definitions (``dict_ref`` frames).

Unit coverage of the frame protocol (FRAME_DICT_DEF sequencing, shared
dictionary identity on the decode side, oversized-dictionary pruning, the
legacy non-ref stream), plus a worker-pool roundtrip asserting the final
agg over string keys is bit-identical with codes_shuffle on and off."""

import collections
import io

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from blaze_tpu.config import config_override
from blaze_tpu.core import ColumnarBatch
from blaze_tpu.io.batch_serde import (
    FRAME_DICT_DEF,
    BatchReader,
    BatchWriter,
    dict_identity,
    read_frames,
)
from blaze_tpu.ir import exprs as E
from blaze_tpu.ir import nodes as N
from blaze_tpu.runtime.session import Session

F = E.AggFunction
M = E.AggMode


def _dict_batches(n=1000, card=37):
    """Two batches sliced off one dictionary-encoded column — the shape a
    partial agg emits (one dictionary shared across every slice)."""
    arr = pa.array([f"key-{i % card}" for i in range(n)]).dictionary_encode()
    big = ColumnarBatch.from_pydict({"k": arr, "v": list(range(n))})
    half = n // 2
    return big, [big.slice(0, half), big.slice(half, half)]


@pytest.mark.quick
def test_dict_def_frame_sequencing():
    """First frame defines the dictionary (FRAME_DICT_DEF), later frames
    ship codes only; the decode side rebuilds every batch dict-encoded over
    one shared dictionary."""
    big, batches = _dict_batches()
    buf = io.BytesIO()
    w = BatchWriter(buf, codec="none", dict_refs=True)
    for b in batches:
        w.write_batch(b)
    assert w.codes_bytes > 0

    buf.seek(0)
    flag_seq = [flags & FRAME_DICT_DEF for flags, _, _ in read_frames(buf)]
    assert flag_seq == [FRAME_DICT_DEF, 0]

    buf.seek(0)
    got = list(BatchReader(buf))
    tbl = pa.Table.from_batches([b.to_arrow() for b in got])
    assert tbl.to_pydict() == big.to_arrow().to_pydict()
    # the wire columns (before to_arrow() decodes to the schema type) come
    # back CODED over one shared dictionary
    from blaze_tpu.core.batch import CodedColumn

    cols = [b.column(0) for b in got]
    assert all(isinstance(c, CodedColumn) for c in cols)
    assert dict_identity(cols[0].dictionary) == dict_identity(cols[1].dictionary)


@pytest.mark.quick
def test_oversized_dictionary_pruned():
    """A huge shared dictionary behind a tiny batch is re-encoded compactly
    per frame instead of being shipped as a ref."""
    big_dict = pa.array([f"val-{i}" for i in range(5000)])
    idx = pa.array(np.arange(10, dtype=np.int32))
    arr = pa.DictionaryArray.from_arrays(idx, big_dict)
    batch = ColumnarBatch.from_pydict({"k": arr})
    buf = io.BytesIO()
    w = BatchWriter(buf, codec="none", dict_refs=True)
    w.write_batch(batch)
    assert w.codes_bytes == 0  # pruned: no ref, no codes accounting
    buf.seek(0)
    (flags, _, _), = list(read_frames(buf))
    assert not flags & FRAME_DICT_DEF
    buf.seek(0)
    (got,) = list(BatchReader(buf))
    assert got.to_arrow().column("k").to_pylist() == arr.to_pylist()


def test_legacy_stream_roundtrips_dicts():
    """dict_refs=False keeps the old wire shape: dictionaries travel inside
    each frame's arrow IPC, no dict-def flags, no codes accounting."""
    big, batches = _dict_batches(n=600)
    buf = io.BytesIO()
    w = BatchWriter(buf, codec="none", dict_refs=False)
    for b in batches:
        w.write_batch(b)
    assert w.codes_bytes == 0
    buf.seek(0)
    assert all(not flags & FRAME_DICT_DEF for flags, _, _ in read_frames(buf))
    buf.seek(0)
    tbl = pa.Table.from_batches([b.to_arrow() for b in BatchReader(buf)])
    assert tbl.to_pydict() == big.to_arrow().to_pydict()


def test_redefined_ref_decodes_in_order():
    """Spilled stream segments restart ref numbering: a second definition of
    ref 0 must replace the first for frames that follow it."""
    a1 = pa.array(["a", "b", "a"]).dictionary_encode()
    a2 = pa.array(["x", "y", "x"]).dictionary_encode()
    buf = io.BytesIO()
    for arr in (a1, a2):
        # separate writers emulate two stream segments concatenated by the
        # spill merge (each restarts at ref 0)
        w = BatchWriter(buf, codec="none", dict_refs=True)
        w.write_batch(ColumnarBatch.from_pydict({"k": arr}))
    buf.seek(0)
    got = [b.to_arrow().column("k").to_pylist() for b in BatchReader(buf)]
    assert got == [a1.to_pylist(), a2.to_pylist()]


def _string_agg_plan(paths, reducers=3):
    from blaze_tpu.ops.parquet import scan_node_for_files

    scan = scan_node_for_files(paths, num_partitions=2)
    partial = N.Agg(scan, E.AggExecMode.HASH_AGG, [("k", E.Column("k"))], [
        N.AggColumn(E.AggExpr(F.SUM, [E.Column("v")]), E.AggMode.PARTIAL, "s"),
        N.AggColumn(E.AggExpr(F.COUNT, []), E.AggMode.PARTIAL, "c"),
    ], supports_partial_skipping=True)
    ex = N.ShuffleExchange(partial, N.HashPartitioning([E.Column("k")], reducers))
    final = N.Agg(ex, E.AggExecMode.HASH_AGG, [("k", E.Column("k"))], [
        N.AggColumn(E.AggExpr(F.SUM, [E.Column("v")]), E.AggMode.FINAL, "s"),
        N.AggColumn(E.AggExpr(F.COUNT, []), E.AggMode.FINAL, "c"),
    ])
    single = N.ShuffleExchange(final, N.SinglePartitioning(1))
    return N.Sort(single, [E.SortOrder(E.Column("k"))])


@pytest.fixture(scope="module")
def string_key_files(tmp_path_factory):
    td = tmp_path_factory.mktemp("codesdata")
    rng = np.random.default_rng(31)
    paths = []
    for p in range(2):
        n = 12000
        tbl = pa.table({
            "k": pa.array([f"user-{i:05d}" for i in rng.integers(0, 4000, n)]),
            "v": pa.array(rng.integers(0, 1000, n), type=pa.int64()),
        })
        path = str(td / f"f{p}.parquet")
        pq.write_table(tbl, path)
        paths.append(path)
    return paths


@pytest.mark.slow
def test_codes_shuffle_bit_identical_on_worker_pool(string_key_files):
    """Dict-encoded partial-agg batches cross a real worker-pool shuffle;
    the final agg is bit-identical to the decoded-values path, codes bytes
    were actually shipped, and no rows were re-interned at merge tables."""
    plan = _string_agg_plan(string_key_files)
    with config_override(codes_shuffle=False):
        with Session(num_worker_processes=2) as s:
            decoded = s.execute_to_table(plan)
    with config_override(codes_shuffle=True):
        with Session(num_worker_processes=2) as s:
            coded = s.execute_to_table(plan)
            codes_bytes = s.metrics.total("codes_shuffle_bytes")
            reintern = s.metrics.total("agg_reintern_rows")
    assert coded.to_pydict() == decoded.to_pydict()
    assert codes_bytes > 0
    assert reintern == 0
    # sanity against an independent oracle
    exp_s = collections.defaultdict(int)
    exp_c = collections.defaultdict(int)
    for path in string_key_files:
        t = pq.read_table(path)
        for k, v in zip(t.column("k").to_pylist(), t.column("v").to_pylist()):
            exp_s[k] += v
            exp_c[k] += 1
    out = coded.to_pydict()
    assert out["k"] == sorted(exp_s)
    assert out["s"] == [exp_s[k] for k in out["k"]]
    assert out["c"] == [exp_c[k] for k in out["k"]]
