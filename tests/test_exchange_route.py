"""The exchange on the chip (ISSUE 36): ``Repartitioner.bucketize`` routes a
batch in ONE device program (``exchange_route``: partition ids, a stable
order, one matrix gather, the offsets) and one small wait, and its
sub-batches equal ``bucketize_host``'s row for row and in order — so the
device shuffle tier, which a pool-less session negotiates on an accelerator,
gives every reducer the rows the process tier gives it, in the same order.
The degrade paths (a host-backed batch, the ``device.put`` failpoint, the
byte budget) give the same answers, and nothing staged outlives its query."""

import gc
import weakref

import numpy as np
import pyarrow as pa
import pytest

from blaze_tpu.config import config_override
from blaze_tpu.core.batch import (CodedColumn, ColumnarBatch, RowWindow,
                                  has_planes)
from blaze_tpu.ir import exprs as E
from blaze_tpu.ir import nodes as N
from blaze_tpu.ir import types as T
from blaze_tpu.ops.shuffle import repartitioner as R
from blaze_tpu.ops.shuffle import writer as W
from blaze_tpu.runtime.session import Session
from blaze_tpu.utils.device import DEVICE_STATS


def _col(n):
    return E.Column(n)


ROWS = 5000


def _nullable(rng, values, share=0.1):
    return pa.array(values, mask=rng.random(len(values)) < share)


def _batch(case: str) -> ColumnarBatch:
    """A batch whose planes are longer than its rows (an all-padding tail
    of more than half the capacity), with the columns a case routes by."""
    rng = np.random.default_rng(36)
    names = pa.array([f"name-{i % 37}" for i in rng.integers(0, 1000, ROWS)]
                     ).dictionary_encode()
    table = pa.table({
        # few distinct values: at 7 partitions some partition stays empty
        "a": pa.array(rng.integers(0, 3, ROWS), type=pa.int64()),
        "b": _nullable(rng, rng.integers(-2**40, 2**40, ROWS)),
        "c": pa.array(rng.integers(0, 1000, ROWS), type=pa.int64()),
        "i": _nullable(rng, rng.integers(-2**31, 2**31 - 1, ROWS)
                       .astype(np.int32)),
        "d": pa.array(rng.integers(-10**9, 10**9, ROWS), type=pa.int64())
        .cast(pa.decimal128(21, 2)).cast(pa.decimal128(17, 2)),
        "f": _nullable(rng, rng.random(ROWS).astype(np.float32)),
        "s": names,
    })
    if case != "host_column":
        batch = ColumnarBatch.from_arrow(table, capacity=16384)
        assert isinstance(batch.columns[-1], CodedColumn)
        assert all(has_planes(c) for c in batch.columns)
        return batch
    plain = table.set_column(6, "s", names.cast(pa.string()))
    return ColumnarBatch.from_arrow(plain, capacity=16384)


def _partitioner(case: str, n: int, schema) -> R.Repartitioner:
    if case == "round_robin":
        return R.RoundRobinPartitioner(n, start=5)
    if case == "range":
        bounds = [(int(v), 0) for v in np.linspace(900, 100, max(n - 1, 0))]
        return R.RangePartitioner(
            [E.SortOrder(_col("c"), False, False),
             E.SortOrder(_col("i"), True, True)], n, bounds, schema)
    keys = {
        "hash_one": [_col("c")],
        "hash_three": [_col("a"), _col("b"), _col("c")],
        "hash_few_values": [_col("a")],
        "hash_nulls": [_col("b"), _col("i")],
        "hash_decimal_int32": [_col("d"), _col("i")],
        "hash_float": [_col("f"), _col("c")],
        "hash_coded_key": [_col("s"), _col("c")],
        "hash_expression": [E.BinaryExpr(E.BinaryOp.ADD, _col("a"), _col("c"))],
        "host_column": [_col("c")],
    }[case]
    return R.HashPartitioner(keys, n, schema)


CASES = ["hash_one", "hash_three", "hash_few_values", "hash_nulls",
         "hash_decimal_int32", "hash_float", "hash_coded_key",
         "hash_expression", "host_column", "range", "round_robin"]


def _rows(sub):
    batch = sub.to_columnar() if hasattr(sub, "to_columnar") else sub
    return batch.to_arrow().to_pydict()


@pytest.mark.parametrize("n", [1, 4, 7])
@pytest.mark.parametrize("case", CASES)
def test_device_route_equals_host_route(case, n):
    """Row for row and in order: what the device tier stages for each
    partition is what ``bucketize_host`` stages for it."""
    batch = _batch(case)
    dev = _partitioner(case, n, batch.schema).bucketize(batch)
    host = _partitioner(case, n, batch.schema).bucketize_host(batch)
    assert [p for p, _ in dev] == [p for p, _ in host]
    assert sum(sub.num_rows for _, sub in dev) == ROWS
    for (pid, d), (_, h) in zip(dev, host):
        # a window of the one moved batch; a slice where the batch has
        # host columns; the batch itself where one partition took it all
        assert isinstance(d, ColumnarBatch if case == "host_column" or n == 1
                          else RowWindow)
        assert d.num_rows == h.num_rows > 0
        got, want = _rows(d), _rows(h)
        for name in want:
            g, w = got[name], want[name]
            assert all(a == b or (a != a and b != b) for a, b in zip(g, w)), \
                f"{case}/{n}: partition {pid}, column {name}"
        if case != "host_column":
            # the logical index is the same on both tiers for the same
            # rows: a code plane's five bytes a row apart
            coded = d.num_rows * 5
            host_fixed = sum(it[0].nbytes + it[1].nbytes for it in h.items
                             if isinstance(it, tuple))
            assert W._logical_batch_nbytes(d) == host_fixed + coded
    if case != "host_column" and n > 1:
        # the windows share the moved batch's planes: so do their bytes
        (moved,) = {id(d.batch): d.batch for _, d in dev}.values()
        held = sum(W._staged_batch_nbytes(d) for _, d in dev)
        assert moved.nbytes() - len(dev) < held <= moved.nbytes()
    if case == "hash_few_values" and n == 7:
        assert len(dev) < n, "three key values cannot fill seven partitions"


@pytest.mark.parametrize("case", ["hash_three", "hash_coded_key"])
def test_windows_concatenate_as_their_slices_do(case):
    """The reduce side copies the windows of several routed batches into
    one batch in ONE program that gathers nothing, and gets what the
    concat of their slices gives, under the padding contract."""
    batch = _batch(case)
    part = _partitioner(case, 4, batch.schema)
    routed = [dict(part.bucketize(batch)) for _ in range(3)]
    for pid in range(4):
        windows = [r[pid] for r in routed]
        before = DEVICE_STATS.snapshot()["kernel_calls"]
        got = ColumnarBatch.concat(windows)
        assert DEVICE_STATS.snapshot()["kernel_calls"] - before == 1
        want = ColumnarBatch.concat([w.to_columnar() for w in windows])
        assert got.num_rows == want.num_rows == 3 * windows[0].num_rows
        assert got.to_arrow().equals(want.to_arrow())
        for c in got.columns:
            assert not np.asarray(c.validity)[got.num_rows:].any()
            assert not np.asarray(c.data)[got.num_rows:].any()
    # a window among whole batches is cut first; one alone is its slice
    mixed = ColumnarBatch.concat([routed[0][0], routed[1][0].to_columnar()])
    assert mixed.to_arrow().equals(ColumnarBatch.concat(
        [routed[0][0].to_columnar(), routed[1][0].to_columnar()]).to_arrow())
    assert ColumnarBatch.concat([routed[0][2]]).to_arrow().equals(
        routed[0][2].to_columnar().to_arrow())


def test_round_robin_continues_across_batches():
    batch = _batch("round_robin")
    dev, host = R.RoundRobinPartitioner(4, 3), R.RoundRobinPartitioner(4, 3)
    for _ in range(2):
        d, h = dev.bucketize(batch), host.bucketize_host(batch)
        assert [(p, _rows(s)) for p, s in d] == [(p, _rows(s)) for p, s in h]
    assert dev.next_pid == host.next_pid


@pytest.mark.parametrize("case", ["hash_three", "range", "round_robin"])
def test_route_is_one_program_and_one_wait(case, monkeypatch):
    """A batch costs ONE jitted ``exchange_route`` call and ONE blocking
    wait (the offsets), and nothing else: a partition is a window."""
    from blaze_tpu.obs.tracer import TRACER

    batch = _batch(case)
    part = _partitioner(case, 4, batch.schema)
    part.bucketize(batch)  # compiled, bounds resident
    calls = []
    routed = R.exchange_route

    def counting(*args, **kw):
        calls.append(kw["how"][0])
        return routed(*args, **kw)

    monkeypatch.setattr(R, "exchange_route", counting)
    before = DEVICE_STATS.snapshot()
    seen = len(TRACER.snapshot())
    TRACER.enable()
    try:
        out = part.bucketize(batch)
        spans = [e for e in TRACER.snapshot()[seen:] if e.get("cat") == "sync"]
    finally:
        TRACER.disable()
    after = DEVICE_STATS.snapshot()
    assert len(calls) == 1
    assert after["sync_calls"] - before["sync_calls"] == 1
    assert after["to_host_calls"] == before["to_host_calls"]
    assert [e["name"] for e in spans] == ["exchange_route"]
    assert after["kernel_calls"] - before["kernel_calls"] == 1
    assert len(out) == 4 and after["to_device_calls"] == before["to_device_calls"]
    assert part.split_gathers == part.split_batches == 2


# -- the device tier end to end ----------------------------------------------


def _two_stage_plan(schema, nparts, reducers=4):
    scan = N.FFIReader(schema=schema, resource_id="src",
                       num_partitions=nparts)
    s = E.AggExpr(E.AggFunction.SUM, [_col("v")], T.I64)
    partial = N.Agg(scan, E.AggExecMode.HASH_AGG, [("k", _col("k"))],
                    [N.AggColumn(s, E.AggMode.PARTIAL, "s")])
    ex = N.ShuffleExchange(partial, N.HashPartitioning([_col("k")], reducers))
    final = N.Agg(ex, E.AggExecMode.HASH_AGG, [("k", _col("k"))],
                  [N.AggColumn(s, E.AggMode.FINAL, "s")])
    return N.Sort(N.ShuffleExchange(final, N.SinglePartitioning(1)),
                  [E.SortOrder(_col("k"))])


def _parts(nparts=4, n=20_000, seed=36):
    rng = np.random.default_rng(seed)
    b = ColumnarBatch.from_pydict({
        "k": rng.integers(0, 3000, n).tolist(),
        "v": rng.integers(0, 1000, n).tolist()})
    per = n // nparts
    return [[b.slice(i * per, per)] for i in range(nparts)]


TRACKED = ("shuffle_tier_degraded", "device_shuffle_bytes",
           "serde_elided_batches", "shuffle_bytes_serialized",
           "collective_bytes", "mesh_host_resident_exchanges")


def _run(parts, watch=None, **conf):
    with config_override(**conf):
        with Session() as sess:
            sess.resources["src"] = lambda p: [x.to_arrow() for x in parts[p]]
            if watch is not None:
                watch(sess)
            out = sess.execute_to_table(
                _two_stage_plan(parts[0][0].schema, len(parts)))
            left = len(sess.mem_segments)
            metrics = sess.metrics.totals(TRACKED)
    return out, metrics, left


@pytest.mark.parametrize("how,conf", [
    ("device.put", {"failpoints": "device.put=enospc"}),
    ("budget", {"mesh_device_resident_max_bytes": 4096}),
])
def test_device_tier_degrades_to_the_same_answer(how, conf):
    parts = _parts()
    want, base, _ = _run(parts, zero_copy_tier="device")
    assert base["shuffle_tier_degraded"] == 0
    assert base["device_shuffle_bytes"] > 0
    assert base["shuffle_bytes_serialized"] == 0
    got, m, _ = _run(parts, zero_copy_tier="device", **conf)
    assert got.equals(want)
    assert m["shuffle_tier_degraded"] > 0, how
    ref, _, _ = _run(parts, zero_copy_tier="process")
    assert ref.equals(want)


def test_device_tier_leaves_nothing_staged():
    """After the query returns the registry is empty and no batch the
    device tier staged is reachable: an exchange that kept its sub-batches
    would fill the chip."""
    staged = []

    def watch(sess):
        commit = sess.mem_segments.commit

        def recording(stage, map_id, parts, nbytes):
            staged.extend(weakref.ref(b) for subs in parts.values()
                          for b in subs)
            return commit(stage, map_id, parts, nbytes)

        sess.mem_segments.commit = recording

    out, m, left = _run(_parts(), watch=watch, zero_copy_tier="device")
    assert out.num_rows > 0 and m["device_shuffle_bytes"] > 0
    assert left == 0
    assert staged, "the device tier must have committed sub-batches"
    gc.collect()
    assert not [r for r in staged if r() is not None]


# -- every benchmark cell, rehearsed as the chip runs it -----------------------


def _cells():
    import json

    from tests.benchmark import helpers

    with open(helpers.MANIFEST) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


@pytest.mark.parametrize("cell", _cells())
def test_cell_rehearsal_on_the_device_tier(cell, tmp_path, capsys, monkeypatch):
    """A tiny rehearsal of each cell with the session told its stages run on
    an accelerator: it negotiates the device tier by itself, every answer
    equals the reference's, every ``counters_must`` holds, nothing degrades
    and the reducers read device-resident sub-batches (on a mesh, the
    reducer batches the collective left on their chips)."""
    import json

    from tests.benchmark import helpers

    seen = {}
    close = Session.close

    def closing(self):
        seen.update(self.metrics.totals(TRACKED), tier=self._shuffle_tier(),
                    left=len(self.mem_segments))
        return close(self)

    monkeypatch.setattr(Session, "_stage_platform", lambda self: "tpu")
    monkeypatch.setattr(Session, "close", closing)
    path = helpers.tiny_manifest(tmp_path)
    with open(path) as f:
        manifest = json.load(f)
    (config,) = [c for c in manifest["configs"] if c["name"] == next(
        w["config"] for w in manifest["workloads"] if w["name"] == cell)]
    with open(tmp_path / config["file"]) as f:
        mesh = json.load(f)["session"]["conf"].get("multichip_enabled", False)
    rc, lines = helpers.run_cell(capsys, path, cell)
    assert rc == 0, lines
    result = json.loads(lines[-1])
    assert result["correct"] and result["failed"] == 0, lines[-1]
    assert seen["tier"] == "device"
    assert seen["shuffle_tier_degraded"] == 0
    if mesh:
        assert seen["collective_bytes"] > 0
        assert seen["mesh_host_resident_exchanges"] == 0
    else:
        assert seen["device_shuffle_bytes"] > 0
    assert seen["shuffle_bytes_serialized"] == 0
    assert seen["left"] == 0
