"""The coded column (core/batch.CodedColumn): a var-width column as int32
codes and validity on the device over one host dictionary. Every mover
against the host column it replaces; two dictionaries through the remap; the
broadcast join's fused kernel with a coded payload against the generic
probe; Expand's projections; aggregation by coded keys against the host
table; the exchange's partition ids against spark_hash of the decoded
strings; ordering by value where the code order is another."""

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from blaze_tpu.core import dictionary as D
from blaze_tpu.core.batch import (CodedColumn, ColumnarBatch, DeviceColumn,
                                  HostBatch, HostColumn)
from blaze_tpu.ir import exprs as E
from blaze_tpu.ir import nodes as N
from blaze_tpu.ir import types as T
from blaze_tpu.ops.base import ExecContext
from blaze_tpu.ops.basic import (CoalesceBatchesExec, ExpandExec, FilterExec,
                                 MemoryScanExec, ProjectExec)
from tests.util import collect

NAMES = ["pear", "apple", None, "fig", "apple", "kiwi", None, "pear", "date"]
SCHEMA = T.Schema.of(("s", T.STRING), ("v", T.I64))


def _table(names=NAMES, encode=True):
    s = pa.array(names, type=pa.string())
    return pa.table({"s": s.dictionary_encode() if encode else s,
                     "v": pa.array(range(len(names)), type=pa.int64())})


def _pair(names=NAMES):
    """The same rows with the string column coded, and as a host column."""
    coded = ColumnarBatch.from_arrow(_table(names), SCHEMA)
    host = ColumnarBatch.from_arrow(_table(names, encode=False), SCHEMA)
    assert isinstance(coded.columns[0], CodedColumn)
    assert isinstance(host.columns[0], HostColumn)
    return coded, host


def _rows(batch):
    return batch.to_arrow().to_pydict()


MOVERS = {
    "take": lambda b: b.take(np.array([8, 0, 2, 2, 5])),
    "take_none": lambda b: b.take(np.array([], dtype=np.int64)),
    "take_nullable": lambda b: b.take_nullable(np.array([3, -1, 0, -1])),
    "slice": lambda b: b.slice(2, 5),
    "slice_past_end": lambda b: b.slice(7, 50),
    "concat": lambda b: ColumnarBatch.concat([b.slice(0, 4), b.slice(4, 5)]),
    "concat_three": lambda b: ColumnarBatch.concat([b, b.slice(1, 3), b]),
    "with_capacity": lambda b: b.with_capacity(2 * b.capacity),
    "select": lambda b: b.select([0]),
}


@pytest.mark.parametrize("mover", sorted(MOVERS))
def test_mover_equals_the_host_columns(mover):
    coded, host = _pair()
    got, want = MOVERS[mover](coded), MOVERS[mover](host)
    assert _rows(got) == _rows(want)
    col = got.columns[0]
    assert isinstance(col, CodedColumn) and col.data.dtype == np.int32
    assert col.dictionary is coded.columns[0].dictionary  # by reference
    # the padding contract: code 0 and validity False past the rows
    n = got.num_rows
    assert not np.asarray(col.validity)[n:].any()
    assert not np.asarray(col.data)[n:].any()


def test_compact_moves_code_planes_with_the_filter():
    coded, host = _pair()
    pred = [E.BinaryExpr(E.BinaryOp.GT, E.Column("v"), E.Literal(3, T.I64))]
    got = collect(FilterExec(MemoryScanExec(SCHEMA, [[coded]]), pred))
    want = collect(FilterExec(MemoryScanExec(SCHEMA, [[host]]), pred))
    assert got.to_pydict() == want.to_pydict()
    (out,) = list(FilterExec(MemoryScanExec(SCHEMA, [[coded]]), pred)
                  .execute(0, ExecContext()))
    assert isinstance(out.columns[0], CodedColumn)


@pytest.mark.parametrize("predicate,want", [
    (E.BinaryExpr(E.BinaryOp.EQ, E.Column("s"), E.Literal("apple", T.STRING)),
     [1, 4]),
    (E.StringStartsWith(E.Column("s"), "p"), [0, 7]),
    (E.IsNull(E.Column("s")), [2, 6]),
])
def test_predicates_run_on_the_codes(predicate, want):
    coded, _ = _pair()
    ctx = ExecContext()
    got = collect(FilterExec(MemoryScanExec(SCHEMA, [[coded]]), [predicate]),
                  ctx)
    assert got["v"].to_pylist() == want
    assert ctx.metrics.total("host_key_batches") == (
        1 if isinstance(predicate, E.IsNull) else 0)


def test_nulls_and_to_arrow_decode():
    coded, _ = _pair()
    arr = coded.to_arrow().column(0)
    assert arr.type == pa.large_utf8() and arr.to_pylist() == NAMES
    every = ColumnarBatch.from_arrow(_table([None, None, None]), SCHEMA)
    assert len(every.columns[0].dictionary) == 0  # an empty dictionary
    assert _rows(every)["s"] == [None, None, None]
    assert _rows(every.take(np.array([2, 0])))["s"] == [None, None]
    both = ColumnarBatch.concat([every, coded])
    assert _rows(both)["s"] == [None, None, None] + NAMES


def test_two_dictionaries_meet_through_one_remap_table():
    a = ColumnarBatch.from_arrow(_table(["x", "y", None, "x"]), SCHEMA)
    b = ColumnarBatch.from_arrow(_table(["z", "y", "w"]), SCHEMA)
    assert not D.same_dictionary(a.columns[0].dictionary,
                                 b.columns[0].dictionary)
    both = ColumnarBatch.concat([a, b])
    assert _rows(both)["s"] == ["x", "y", None, "x", "z", "y", "w"]
    assert both.columns[0].dictionary.to_pylist() == ["x", "y", "z", "w"]
    # built once a pair of dictionaries: the second meeting reuses the table
    unified, tables = D.unify([a.columns[0].dictionary,
                               b.columns[0].dictionary])
    again, tables2 = D.unify([a.columns[0].dictionary,
                              b.columns[0].dictionary])
    assert again is unified and tables2[1] is tables[1]
    assert tables[0] is None and tables[1].tolist() == [2, 1, 3]
    # counted on the operator that made them meet
    ctx = ExecContext()
    out = collect(CoalesceBatchesExec(MemoryScanExec(SCHEMA, [[a, b]]), 100),
                  ctx)
    assert out["s"].to_pylist() == ["x", "y", None, "x", "z", "y", "w"]
    assert ctx.metrics.total("dict_remap_rows") == 3


def test_a_batch_without_a_dictionary_concats_as_host_columns():
    coded, host = _pair()
    empty = ColumnarBatch.empty(SCHEMA)
    assert _rows(ColumnarBatch.concat([coded, host]))["s"] == NAMES + NAMES
    assert ColumnarBatch.concat([empty, coded]) is coded


def test_staged_form_and_serde_ship_codes_and_one_dictionary():
    import io

    from blaze_tpu.io.batch_serde import BatchReader, BatchWriter

    coded, _ = _pair()
    staged = HostBatch.from_batch(coded)
    assert isinstance(staged.items[0], pa.DictionaryArray)
    assert D.same_dictionary(staged.items[0].dictionary,
                             coded.columns[0].dictionary)
    back = staged.take(np.array([1, 0, 2])).to_columnar()
    assert isinstance(back.columns[0], CodedColumn)
    assert D.same_dictionary(back.columns[0].dictionary,
                             coded.columns[0].dictionary)
    assert _rows(back)["s"] == ["apple", "pear", None]
    buf = io.BytesIO()
    BatchWriter(buf, dict_refs=True).write_batch(coded)
    buf.seek(0)
    (read,) = list(BatchReader(buf))
    assert isinstance(read.columns[0], CodedColumn) and _rows(read) == _rows(coded)


def test_operators_that_do_not_take_coded_columns_get_host_columns():
    from blaze_tpu.ops.window import WindowExec

    coded, host = _pair()

    def window(batch):
        # a default-frame SUM is a host window: not among the device programs
        return WindowExec(MemoryScanExec(SCHEMA, [[batch]]), [N.WindowExpr(
            "agg", "t", agg=E.AggExpr(E.AggFunction.SUM, [E.Column("v")]))],
            [E.Column("s")], [])

    op = window(coded)
    assert not op.takes_coded
    (seen,) = list(op.execute_child(0, 0, ExecContext(), ExecContext().metrics))
    assert isinstance(seen.columns[0], HostColumn)
    assert pa.types.is_dictionary(seen.columns[0].array.type)  # not decoded
    # counted on the operator that asked, while it runs
    ctx = ExecContext()
    assert collect(op, ctx).to_pydict() == collect(window(host)).to_pydict()
    assert ctx.metrics.total("host_key_batches") == 1


@pytest.mark.parametrize("kind", ["row_number", "rank"])
def test_window_over_a_coded_partition_key_runs_the_device_program(kind):
    """The rank family partitioned by a name: int32 code equality to
    `jit(window_scan)`, the stream kept to one dictionary across batches
    whose dictionaries differ."""
    from blaze_tpu.ops.window import WindowExec

    first = ColumnarBatch.from_arrow(_table(["a", "a", "b", "b", "b"]), SCHEMA)
    second = ColumnarBatch.from_arrow(_table(["b", "c", "c", None, None]), SCHEMA)
    plain = [ColumnarBatch.from_arrow(_table(n, encode=False), SCHEMA)
             for n in (["a", "a", "b", "b", "b"], ["b", "c", "c", None, None])]
    order = [E.SortOrder(E.Column("v"))]

    def run(batches, ctx):
        op = WindowExec(MemoryScanExec(SCHEMA, [batches]),
                        [N.WindowExpr(kind, "w")], [E.Column("s")], order)
        return collect(op, ctx).to_pydict()

    coded_ctx, host_ctx = ExecContext(), ExecContext()
    got, want = run([first, second], coded_ctx), run(plain, host_ctx)
    assert got == want
    assert got["w"] == [1, 2, 1, 2, 3, 4, 1, 2, 1, 2]
    assert coded_ctx.metrics.total("window_device_batches") == 2
    assert coded_ctx.metrics.total("window_host_batches") == 0
    assert coded_ctx.metrics.total("host_key_batches") == 0
    assert coded_ctx.metrics.total("dict_remap_rows") == 5
    assert host_ctx.metrics.total("window_host_batches") == 2


# -- the scan ------------------------------------------------------------------


def test_scan_reads_strings_coded_with_one_dictionary_a_column(tmp_path):
    from blaze_tpu.ops.parquet import scan_node_for_files
    from blaze_tpu.runtime.executor import build_operator

    names = ["b", "a", "b", None] * 3 + ["d", "c", "a", "d"] * 3
    path = str(tmp_path / "t.parquet")
    pq.write_table(pa.table({"s": names, "v": list(range(len(names)))}),
                   path, row_group_size=12)  # two row groups, two dictionaries
    ctx = ExecContext()
    op = build_operator(scan_node_for_files([path]))
    batches = [b for b in op.execute(0, ctx) if b.num_rows]
    assert len(batches) == 2
    assert all(isinstance(b.columns[0], CodedColumn) for b in batches)
    assert [v for b in batches for v in _rows(b)["s"]] == names
    # unified once: the first group's entries stay a prefix of the second's
    first, second = (b.columns[0].dictionary for b in batches)
    assert second.to_pylist()[:len(first)] == first.to_pylist()
    assert sorted(second.to_pylist()) == ["a", "b", "c", "d"]
    assert ctx.metrics.total("dict_entries") == 4
    assert ctx.metrics.total("dict_remap_rows") == 12
    assert _rows(ColumnarBatch.concat(batches))["s"] == names


# -- the join ------------------------------------------------------------------


def _join(probe_batches, build_batch, ctx):
    from blaze_tpu.ir.nodes import JoinSide, JoinType
    from blaze_tpu.ops.joins.bhj import BroadcastJoinExec

    probe_schema = T.Schema.of(("k", T.I64), ("p", T.STRING))
    build_schema = T.Schema.of(("bk", T.I64), ("name", T.STRING),
                               ("brand", T.STRING))
    op = BroadcastJoinExec(
        MemoryScanExec(probe_schema, [probe_batches]),
        MemoryScanExec(build_schema, [[build_batch]]),
        [(E.Column("k"), E.Column("bk"))], JoinType.INNER, JoinSide.RIGHT)
    return collect(op, ctx)


@pytest.mark.parametrize("encode", [True, False])
def test_bhj_coded_payload_takes_the_fused_kernel_and_equals_the_generic_probe(
        encode):
    rng = np.random.default_rng(5)
    keys = rng.integers(0, 40, 500)
    tags = np.array(["u", "v", None], dtype=object)[rng.integers(0, 3, 500)]

    def column(values):
        arr = pa.array(list(values), type=pa.string())
        return arr.dictionary_encode() if encode else arr

    probe = ColumnarBatch.from_arrow(pa.table({
        "k": pa.array(keys, type=pa.int64()), "p": column(tags)}))
    build = ColumnarBatch.from_arrow(pa.table({
        "bk": pa.array(range(30), type=pa.int64()),
        "name": column(f"n{i % 7}" if i % 5 else None for i in range(30)),
        "brand": column(f"b{i % 3}" for i in range(30))}))
    ctx = ExecContext()
    got = _join([probe.slice(0, 300), probe.slice(300, 200)], build, ctx)
    fast = ctx.metrics.total("device_inner_batches")
    generic = ctx.metrics.total("join_generic_batches")
    assert (fast, generic) == ((2, 0) if encode else (0, 2))
    assert ctx.metrics.total("coded_key_batches") == (2 if encode else 0)
    want = [(int(k), t, int(k), f"n{k % 7}" if k % 5 else None, f"b{k % 3}")
            for k, t in zip(keys, tags) if k < 30]
    rows = list(zip(*(got[c].to_pylist() for c in got.column_names)))
    assert rows == want  # the generic probe's answer too (the other case)


# -- Expand --------------------------------------------------------------------


def test_expand_five_projections_keep_codes_and_one_dictionary():
    keys = ["a", "b", "c", "d"]
    schema = T.Schema.of(*[(k, T.STRING) for k in keys], ("m", T.I64))
    table = pa.table({
        **{k: pa.array([f"{k}{i % 3}" if (i + j) % 4 else None
                        for i in range(10)]).dictionary_encode()
           for j, k in enumerate(keys)},
        "m": pa.array(range(10), type=pa.int64())})
    batch = ColumnarBatch.from_arrow(table, schema)
    projections = [
        [E.Column(k) if i < 4 - lvl else E.Literal(None, T.STRING)
         for i, k in enumerate(keys)]
        + [E.Literal((1 << lvl) - 1, T.I64), E.Column("m")]
        for lvl in range(5)]
    out_schema = T.Schema.of(*[(k, T.STRING) for k in keys],
                             ("spark_grouping_id", T.I64), ("m", T.I64))
    ctx = ExecContext()
    outs = list(ExpandExec(MemoryScanExec(schema, [[batch]]), projections,
                           out_schema).execute(0, ctx))
    assert len(outs) == 5
    plain = table.to_pydict()
    for lvl, out in enumerate(outs):
        rows = _rows(out)
        for i, k in enumerate(keys):
            col = out.columns[i]
            assert isinstance(col, CodedColumn)
            assert col.dictionary is batch.columns[i].dictionary
            assert rows[k] == (plain[k] if i < 4 - lvl else [None] * 10)
        assert rows["spark_grouping_id"] == [(1 << lvl) - 1] * 10
        assert isinstance(out.columns[4], DeviceColumn)  # a device constant
        assert rows["m"] == list(range(10))
    assert ctx.metrics.total("rollup_rows") == 50
    assert ctx.metrics.total("coded_key_batches") == 5
    assert ctx.metrics.total("host_key_batches") == 0


def test_expand_over_host_columns_is_unchanged():
    schema = T.Schema.of(("a", T.STRING), ("m", T.I64))
    batch = ColumnarBatch.from_arrow(
        pa.table({"a": ["x", None, "y"],
                  "m": pa.array([1, 2, 3], type=pa.int64())}), schema)
    outs = list(ExpandExec(
        MemoryScanExec(schema, [[batch]]),
        [[E.Column("a"), E.Column("m")], [E.Literal(None, T.STRING), E.Column("m")]],
        schema).execute(0, ExecContext()))
    assert [_rows(o)["a"] for o in outs] == [["x", None, "y"], [None] * 3]


# -- the aggregation ---------------------------------------------------------------


def _two_stage(scan_schema, batches, keys, ctx, aggs=None):
    from blaze_tpu.ops.agg import AggExec

    aggs = aggs or [("total", E.AggExpr(E.AggFunction.SUM, [E.Column("v")])),
                    ("mean", E.AggExpr(E.AggFunction.AVG, [E.Column("v")])),
                    ("n", E.AggExpr(E.AggFunction.COUNT, []))]
    groups = [(k, E.Column(k)) for k in keys]
    partial = AggExec(MemoryScanExec(scan_schema, [batches]),
                      E.AggExecMode.HASH_AGG, groups,
                      [N.AggColumn(a, E.AggMode.PARTIAL, n) for n, a in aggs])
    final = AggExec(partial, E.AggExecMode.HASH_AGG, groups,
                    [N.AggColumn(a, E.AggMode.FINAL, n) for n, a in aggs])
    table = collect(final, ctx)
    names = table.column_names
    return sorted(zip(*(table[c].to_pylist() for c in names)),
                  key=lambda r: tuple((v is None, v) for v in r[:len(keys)]))


@pytest.mark.parametrize("keys", [("s",), ("s", "t"), ("s", "g", "t")])
def test_aggregation_by_coded_keys_equals_the_host_tables(keys):
    rng = np.random.default_rng(11)
    n = 400
    pool = np.array(["ash", "birch", None, "cedar", "elm"], dtype=object)
    s, t = pool[rng.integers(0, 5, n)], pool[rng.integers(0, 5, n)]
    data = {"s": s, "t": t, "g": rng.integers(0, 3, n), "v": rng.integers(-50, 50, n)}
    schema = T.Schema.of(("s", T.STRING), ("t", T.STRING), ("g", T.I64),
                         ("v", T.I64))

    def batches(encode):
        def column(values):
            arr = pa.array(list(values), type=pa.string())
            return arr.dictionary_encode() if encode else arr

        big = ColumnarBatch.from_arrow(pa.table({
            "s": column(s), "t": column(t),
            "g": pa.array(data["g"], type=pa.int64()),
            "v": pa.array(data["v"], type=pa.int64())}), schema)
        return [big.slice(0, 150), big.slice(150, 250)]

    coded_ctx, host_ctx = ExecContext(), ExecContext()
    got = _two_stage(schema, batches(True), keys, coded_ctx)
    want = _two_stage(schema, batches(False), keys, host_ctx)
    assert got == want
    # the coded stream took the device aggers, the other the host table
    assert coded_ctx.metrics.total("coded_key_batches") >= 3
    assert coded_ctx.metrics.total("host_key_batches") == 0
    assert coded_ctx.metrics.total("agg_reintern_rows") == 0
    assert host_ctx.metrics.total("coded_key_batches") == 0


def test_avg_of_a_long_keeps_an_int64_sum_and_divides_once():
    from blaze_tpu.ir.aggstate import agg_state_fields, avg_sum_type

    assert avg_sum_type(T.I64) == T.I64 and avg_sum_type(T.I32) == T.I64
    assert avg_sum_type(T.F64) == T.F64
    assert agg_state_fields(E.AggFunction.AVG, T.I64, T.F64) == \
        [("sum", T.I64), ("count", T.I64)]
    schema = T.Schema.of(("s", T.STRING), ("v", T.I64))
    big = 2 ** 52  # the sum 2^52 + 2^52 + 1 needs all 53 bits
    batch = ColumnarBatch.from_arrow(pa.table({
        "s": pa.array(["a", "a", "a", "b", None]).dictionary_encode(),
        "v": pa.array([big, big, 1, None, 7], type=pa.int64())}), schema)
    rows = _two_stage(schema, [batch], ("s",), ExecContext(), aggs=[
        ("mean", E.AggExpr(E.AggFunction.AVG, [E.Column("v")]))])
    assert rows == [("a", float(2 * big + 1) / 3.0), ("b", None), (None, 7.0)]


def test_avg_divides_on_the_host_where_the_device_has_no_float64(monkeypatch):
    from blaze_tpu.ops import aggfns

    monkeypatch.setattr(aggfns, "is_device_dtype",
                        lambda dt: not isinstance(dt, T.Float64Type)
                        and dt.is_fixed_width)
    fn = aggfns.create_agg_function(
        E.AggExpr(E.AggFunction.AVG, [E.Column("v")]),
        T.Schema.of(("v", T.I64)))
    import jax.numpy as jnp

    state = [jnp.asarray([10, 0, 7], dtype=jnp.int64),
             jnp.asarray([4, 0, 2], dtype=jnp.int64)]
    col = fn.final_column(state, 3, 3)
    assert isinstance(col, HostColumn)
    assert col.array.to_pylist() == [2.5, None, 3.5]


# -- the exchange --------------------------------------------------------------------


@pytest.mark.parametrize("keys", [("s",), ("g", "s"), ("s", "t", "g")])
def test_exchange_partition_ids_are_spark_hash_of_the_decoded_strings(keys):
    from blaze_tpu.exprs.spark_hash import hash_batch
    from blaze_tpu.ops.shuffle.repartitioner import HashPartitioner

    rng = np.random.default_rng(3)
    n = 300
    pool = np.array(["", "a", "abcd", "abcde", None, "ünïcode", "x" * 50],
                    dtype=object)
    s, t = pool[rng.integers(0, 7, n)], pool[rng.integers(0, 7, n)]
    g = rng.integers(-5, 5, n)
    schema = T.Schema.of(("s", T.STRING), ("t", T.STRING), ("g", T.I64))

    def batch(encode):
        def column(values):
            arr = pa.array(list(values), type=pa.string())
            return arr.dictionary_encode() if encode else arr

        return ColumnarBatch.from_arrow(pa.table({
            "s": column(s), "t": column(t),
            "g": pa.array(g, type=pa.int64())}), schema)

    coded, host = batch(True), batch(False)
    exprs = [E.Column(k) for k in keys]
    hashes = hash_batch([host.columns[schema.index_of(k)] for k in keys],
                        n, host.capacity, seed=42)
    want = ((hashes.astype(np.int64) % 7) + 7) % 7
    part = HashPartitioner(exprs, 7, schema)
    staged = part.partition_ids_host(HostBatch.from_batch(coded))
    assert staged is not None and staged.tolist() == want.tolist()
    assert part.partition_ids(coded).tolist() == want.tolist()
    # and the rows arrive where their hash says, still coded
    for pid, sub in part.bucketize_host(coded):
        rows = sub.to_columnar()
        assert isinstance(rows.columns[0], CodedColumn)
        assert D.same_dictionary(rows.columns[0].dictionary,
                                 coded.columns[0].dictionary)


# -- ordering ------------------------------------------------------------------------


def _sorted(batch, orders, ctx=None, limit=None):
    from blaze_tpu.ops.sort import SortExec

    return collect(SortExec(MemoryScanExec(batch.schema, [[batch]]), orders,
                            fetch_limit=limit), ctx or ExecContext())


@pytest.mark.parametrize("ascending,nulls_first", [
    (True, True), (True, False), (False, True), (False, False)])
def test_ordering_a_coded_column_is_by_value_not_by_code(ascending, nulls_first):
    names = ["pear", "Zed", None, "apple", "äpfel", "app", "", "pear", None, "b"]
    coded, host = _pair(names)
    # the dictionary is in order of first appearance: not the value order
    assert coded.columns[0].dictionary.to_pylist()[:2] == ["pear", "Zed"]
    orders = [E.SortOrder(E.Column("s"), ascending, nulls_first),
              E.SortOrder(E.Column("v"))]
    got, want = _sorted(coded, orders), _sorted(host, orders)
    assert got.to_pydict() == want.to_pydict()
    live = sorted((n.encode() for n in names if n is not None),
                  reverse=not ascending)
    values = [v.encode() for v in got["s"].to_pylist() if v is not None]
    assert values == live  # bytes order, as Spark's UTF8String
    nulls = [i for i, v in enumerate(got["s"].to_pylist()) if v is None]
    assert nulls == ([0, 1] if nulls_first else [8, 9])


def test_top_k_beside_a_host_key_orders_the_ranks_on_the_host():
    names = ["pear", "fig", None, "apple", "fig", "kiwi"]
    schema = T.Schema.of(("q", T.STRING), ("s", T.STRING), ("v", T.I64))
    table = pa.table({
        "q": pa.array(["b", "a", "a", "b", "a", None]),  # no dictionary
        "s": pa.array(names).dictionary_encode(),
        "v": pa.array(range(6), type=pa.int64())})
    batch = ColumnarBatch.from_arrow(table, schema)
    ctx = ExecContext()
    got = _sorted(batch, [E.SortOrder(E.Column("q")), E.SortOrder(E.Column("s"))],
                  ctx, limit=4)
    assert got["v"].to_pylist() == [5, 2, 1, 4]
    assert ctx.metrics.total("host_key_batches") == 0  # ranks, not values


def test_rank_is_one_sort_of_the_dictionary():
    d = pa.array(["b", "a", "", "ab", "B"], type=pa.large_utf8())
    assert D.rank(d).tolist() == [4, 2, 0, 3, 1]
    assert D.rank(d) is D.rank(d)
    assert D.rank(pa.array([], type=pa.large_utf8())).tolist() == []


def test_a_rollups_sets_through_the_partial_aggregation_equal_pandas():
    """An Expand's typed NULLs and the data's own group apart by the grouping
    id, every set through the device aggregation on its code planes."""
    from blaze_tpu.ops.agg import AggExec

    n = 3000
    rng = np.random.default_rng(2)
    schema = T.Schema.of(("a", T.STRING), ("b", T.STRING), ("v", T.I64))
    names = [f"name-{i:03d}" for i in range(300)] + [None]
    table = pa.table({
        "a": pa.array([names[i] for i in rng.integers(0, 301, n)]).dictionary_encode(),
        "b": pa.array([names[i] for i in rng.integers(0, 301, n)]).dictionary_encode(),
        "v": pa.array(rng.integers(0, 9, n), type=pa.int64())})
    batch = ColumnarBatch.from_arrow(table, schema)
    projections = [
        [E.Column("a"), E.Column("b"), E.Literal(0, T.I64), E.Column("v")],
        [E.Column("a"), E.Literal(None, T.STRING), E.Literal(1, T.I64), E.Column("v")],
        [E.Literal(None, T.STRING), E.Literal(None, T.STRING), E.Literal(3, T.I64),
         E.Column("v")]]
    out_schema = T.Schema.of(("a", T.STRING), ("b", T.STRING), ("g", T.I64),
                             ("v", T.I64))
    expand = ExpandExec(MemoryScanExec(schema, [[batch, batch]]), projections,
                        out_schema)
    partial = AggExec(expand, E.AggExecMode.HASH_AGG,
                      [(k, E.Column(k)) for k in ("a", "b", "g")],
                      [N.AggColumn(E.AggExpr(E.AggFunction.SUM, [E.Column("v")]),
                                   E.AggMode.PARTIAL, "s")])
    ctx = ExecContext()
    got = collect(partial, ctx).to_pandas()
    assert ctx.metrics.total("host_key_batches") == 0
    assert ctx.metrics.total("coded_key_batches") >= 6  # 2 batches x 3 sets
    rows = table.to_pandas().astype({"a": object, "b": object})
    for g, keys in ((0, ["a", "b"]), (1, ["a"]), (3, [])):
        want = (rows.groupby(keys, dropna=False)["v"].sum() * 2) if keys \
            else [2 * rows["v"].sum()]
        have = got[got["g"] == g].groupby(keys or ["g"], dropna=False)["s#sum"].sum()
        assert sorted(have.tolist()) == sorted(list(want))
        assert len(have) == len(want)
        rolled_up = [k for k in ("a", "b") if k not in keys]
        assert got[got["g"] == g][rolled_up].isna().all().all()


def test_each_grouping_set_plans_its_slot_table_for_itself():
    """A ROLLUP's grand total is one slot, its finest set every name: the
    partial aggregation keeps a slot-table state a null signature of its
    keys, so each set plans the table of its own key space, at one probe a
    signature — the finest past ``dense_agg_max_buckets`` and slot-sorted,
    the grand total eight slots and masked."""
    import dataclasses

    from blaze_tpu.config import get_config
    from blaze_tpu.ops.agg import AggExec
    from blaze_tpu.utils.device import DEVICE_STATS

    n = 3000
    rng = np.random.default_rng(2)
    schema = T.Schema.of(("a", T.STRING), ("b", T.STRING), ("v", T.I64))
    names = [f"name-{i:03d}" for i in range(300)]
    batch = ColumnarBatch.from_arrow(pa.table({
        "a": pa.array([names[i] for i in rng.integers(0, 300, n)]).dictionary_encode(),
        "b": pa.array([names[i] for i in rng.integers(0, 300, n)]).dictionary_encode(),
        "v": pa.array(rng.integers(0, 9, n), type=pa.int64())}), schema)
    projections = [
        [E.Column("a"), E.Column("b"), E.Literal(0, T.I64), E.Column("v")],
        [E.Column("a"), E.Literal(None, T.STRING), E.Literal(1, T.I64), E.Column("v")],
        [E.Literal(None, T.STRING), E.Literal(None, T.STRING), E.Literal(3, T.I64),
         E.Column("v")]]
    out_schema = T.Schema.of(("a", T.STRING), ("b", T.STRING), ("g", T.I64),
                             ("v", T.I64))
    expand = ExpandExec(MemoryScanExec(schema, [[batch, batch]]), projections,
                        out_schema)
    partial = AggExec(expand, E.AggExecMode.HASH_AGG,
                      [(k, E.Column(k)) for k in ("a", "b", "g")],
                      [N.AggColumn(E.AggExpr(E.AggFunction.SUM, [E.Column("v")]),
                                   E.AggMode.PARTIAL, "s")])
    # as on the chip: no radix table; a slot table of at most 4,096 slots
    ctx = ExecContext(conf=dataclasses.replace(
        get_config(), radix_agg=False, dense_agg_max_buckets=4096))
    before = DEVICE_STATS.snapshot()
    got = collect(partial, ctx)
    after = DEVICE_STATS.snapshot()
    # 512 x 512 x 2 slots passes the cap and is slot-sorted, 512 x 2 x 2 is
    # inside it and slot-sorted, 2 x 2 x 2 masked: in both batches
    assert after["agg_dense_batches"] - before["agg_dense_batches"] == 6
    assert after["agg_slot_sorted_batches"] - \
        before["agg_slot_sorted_batches"] == 4
    assert after["agg_sort_batches"] == before["agg_sort_batches"]
    total = got.filter(pa.compute.equal(got["g"], 3))
    assert total["s#sum"].to_pylist() == [2 * int(batch.to_arrow()["v"].to_numpy().sum())]


def test_a_q22_rollups_product_sets_are_slot_sorted_and_equal_the_sort_kernel():
    """q22's shape without a radix table (the chip): a five-set ROLLUP over
    four coded names, the product's dictionary 18,000 entries. Every set
    that keeps the product is one slot table past ``dense_agg_max_buckets``
    (2^19 to 2^36 slots), slot-sorted, and all four run ONE program; the
    grand total is masked; the partial rows equal the sort kernel's row for
    row."""
    import dataclasses

    from blaze_tpu.config import get_config
    from blaze_tpu.ops import agg_device as A
    from blaze_tpu.ops.agg import AggExec
    from blaze_tpu.utils.device import DEVICE_STATS

    rng = np.random.default_rng(22)
    n = 3000
    keys = ("product", "brand", "class", "category")
    vocab = {"product": 18_000, "brand": 1_000, "class": 50, "category": 10}

    def coded(name):
        words = pa.array([f"{name}-{i:05d}" for i in range(vocab[name])])
        codes = rng.integers(0, vocab[name], n).astype(np.int32)
        return pa.DictionaryArray.from_arrays(
            pa.array(codes, mask=rng.random(n) < 0.005), words)

    schema = T.Schema.of(*((k, T.STRING) for k in keys), ("q", T.I64))
    table = pa.table({**{k: coded(k) for k in keys},
                      "q": pa.array(rng.integers(0, 1000, n), type=pa.int64(),
                                    mask=rng.random(n) < 0.01)})
    batch = ColumnarBatch.from_arrow(table, schema)
    projections = [
        [*(E.Column(k) if i < kept else E.Literal(None, T.STRING)
           for i, k in enumerate(keys)),
         E.Literal(gid, T.I64), E.Column("q")]
        for kept, gid in ((4, 0), (3, 1), (2, 3), (1, 7), (0, 15))]
    out_schema = T.Schema.of(*((k, T.STRING) for k in keys), ("gid", T.I64),
                             ("q", T.I64))

    def partial(dense_agg):
        expand = ExpandExec(MemoryScanExec(schema, [[batch, batch]]),
                            projections, out_schema)
        agg = AggExec(expand, E.AggExecMode.HASH_AGG,
                      [(k, E.Column(k)) for k in (*keys, "gid")],
                      [N.AggColumn(E.AggExpr(E.AggFunction.AVG,
                                             [E.Column("q")]),
                                   E.AggMode.PARTIAL, "qoh")])
        ctx = ExecContext(conf=dataclasses.replace(
            get_config(), radix_agg=False, dense_agg=dense_agg))
        before = DEVICE_STATS.snapshot()
        got = collect(agg, ctx)
        after = DEVICE_STATS.snapshot()
        assert ctx.metrics.total("host_key_batches") == 0
        return got, {k: after[k] - before[k] for k in (
            "agg_dense_batches", "agg_slot_sorted_batches",
            "agg_sort_batches")}

    built = A._dense_partial_kernel.cache_info().misses
    got, counts = partial(None)
    # the wide tables' program, and the grand total's unless built before
    assert A._dense_partial_kernel.cache_info().misses - built in (1, 2)
    want, sort_counts = partial(False)
    # two batches: four product sets slot-sorted, the grand total masked
    assert counts == {"agg_dense_batches": 10, "agg_slot_sorted_batches": 8,
                      "agg_sort_batches": 0}
    assert sort_counts["agg_sort_batches"] == 10
    assert got.to_pydict() == want.to_pydict()
    assert got.num_rows > 3 * n  # a product set: a group nearly a row


def test_a_typed_null_is_marked_and_a_mover_drops_the_mark():
    coded, _ = _pair()
    nulls = coded.columns[0].nulls_like()
    assert nulls.null_literal and not coded.columns[0].null_literal
    assert D.same_dictionary(nulls.dictionary, coded.columns[0].dictionary)
    batch = ColumnarBatch(coded.schema, [nulls, coded.columns[1]], coded.num_rows)
    assert _rows(batch)["s"] == [None] * len(NAMES)
    moved = batch.take(np.array([1, 0])).columns[0]
    assert not moved.null_literal  # unmarked: treated as any column
    assert _rows(batch.take(np.array([1, 0])))["s"] == [None, None]
