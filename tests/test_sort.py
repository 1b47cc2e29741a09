import numpy as np
import pyarrow as pa
import pytest

from blaze_tpu.config import config_override
from blaze_tpu.ir import exprs as E
from blaze_tpu.ir import types as T
from blaze_tpu.ops.sort import SortExec
from blaze_tpu.runtime.memmgr import MemManager
from tests.util import collect_pydict, mem_scan


def so(name, asc=True, nulls_first=True):
    return E.SortOrder(E.Column(name), asc, nulls_first)


def test_sort_ints_asc_desc():
    data = {"a": pa.array([3, 1, None, 2], type=pa.int64()), "b": pa.array(list("wxyz"))}
    out = collect_pydict(SortExec(mem_scan(data), [so("a")]))
    assert out["a"] == [None, 1, 2, 3]
    assert out["b"] == ["y", "x", "z", "w"]
    out = collect_pydict(SortExec(mem_scan(data), [so("a", asc=False, nulls_first=False)]))
    assert out["a"] == [3, 2, 1, None]


@pytest.mark.quick
def test_sort_multi_key():
    data = {
        "a": pa.array([1, 2, 1, 2], type=pa.int64()),
        "b": pa.array([9.0, 1.0, 3.0, None], type=pa.float64()),
    }
    out = collect_pydict(SortExec(mem_scan(data, num_batches=2),
                                  [so("a"), so("b", asc=False, nulls_first=False)]))
    assert out["a"] == [1, 1, 2, 2]
    assert out["b"] == [9.0, 3.0, 1.0, None]


def test_sort_floats_nan_largest():
    data = {"a": pa.array([1.5, float("nan"), -0.0, None, 1e308], type=pa.float64())}
    out = collect_pydict(SortExec(mem_scan(data), [so("a", nulls_first=False)]))
    assert out["a"][:3] == [-0.0, 1.5, 1e308]
    assert out["a"][3] != out["a"][3]  # NaN before nulls-last
    assert out["a"][4] is None


def test_sort_strings_host_path():
    data = {"s": pa.array(["pear", "apple", None, "fig"])}
    out = collect_pydict(SortExec(mem_scan(data), [so("s")]))
    assert out["s"] == [None, "apple", "fig", "pear"]


def test_sort_dates_and_decimals():
    import datetime
    from decimal import Decimal

    data = {
        "d": pa.array([datetime.date(2020, 5, 1), datetime.date(1999, 1, 1), None],
                      type=pa.date32()),
        "m": pa.array([Decimal("1.10"), Decimal("-2.50"), Decimal("0.00")],
                      type=pa.decimal128(9, 2)),
    }
    out = collect_pydict(SortExec(mem_scan(data), [so("d", nulls_first=False)]))
    assert out["d"] == [datetime.date(1999, 1, 1), datetime.date(2020, 5, 1), None]
    out = collect_pydict(SortExec(mem_scan(data), [so("m")]))
    assert out["m"] == [Decimal("-2.50"), Decimal("0.00"), Decimal("1.10")]


def test_topk():
    rng = np.random.default_rng(0)
    vals = rng.integers(0, 10_000, size=5000).tolist()
    out = collect_pydict(SortExec(mem_scan({"a": vals}, num_batches=7),
                                  [so("a")], fetch_limit=10))
    assert out["a"] == sorted(vals)[:10]


def test_external_sort_with_spill():
    rng = np.random.default_rng(1)
    vals = rng.integers(-(10**9), 10**9, size=20_000).tolist()
    MemManager.reset()
    with config_override(memory_total=2_000_000, memory_fraction=1.0):
        out = collect_pydict(
            SortExec(mem_scan({"a": vals}, num_batches=10), [so("a")]))
    MemManager.reset()
    assert out["a"] == sorted(vals)
    assert len(out["a"]) == 20_000


def test_external_sort_strings_with_spill():
    rng = np.random.default_rng(2)
    vals = ["s" + str(rng.integers(0, 10**6)) for _ in range(5000)]
    MemManager.reset()
    with config_override(memory_total=300_000, memory_fraction=1.0):
        out = collect_pydict(
            SortExec(mem_scan({"s": vals}, num_batches=8), [so("s")]))
    MemManager.reset()
    assert out["s"] == sorted(vals)


def _batch_for_bucketize(n=4000, seed=3):
    rng = np.random.default_rng(seed)
    price = rng.random(n) * 100
    price[rng.random(n) < 0.05] = np.nan
    price_arr = price.astype(object)
    price_arr[rng.random(n) < 0.05] = None
    item = rng.integers(0, 1000, n)
    return {
        "price": pa.array([None if p is None else float(p) for p in price_arr],
                          type=pa.float64()),
        "item": pa.array(item, type=pa.int64()),
    }


def _pydict_of(sub):
    """HostBatch | RowWindow | ColumnarBatch -> pydict with NaN made
    comparable."""
    b = sub.to_columnar() if hasattr(sub, "to_columnar") else sub
    return {k: ["<nan>" if isinstance(v, float) and v != v else v
                for v in vs] for k, vs in b.to_pydict().items()}


@pytest.mark.quick
def test_bucketize_matches_mask_reference_all_partitioners():
    """The fused one-gather split must produce identical partition CONTENTS
    to the old per-partition boolean-mask take, for every partitioner
    type (device batches and staged host batches alike)."""
    from blaze_tpu.core.batch import ColumnarBatch, HostBatch
    from blaze_tpu.ir import exprs as E
    from blaze_tpu.ir import types as T
    from blaze_tpu.ops.shuffle.repartitioner import (
        HashPartitioner, RangePartitioner, RoundRobinPartitioner,
        SinglePartitioner)

    data = _batch_for_bucketize()
    schema = T.Schema.of(("price", T.F64), ("item", T.I64))
    batch = ColumnarBatch.from_pydict(data, schema)
    orders = [E.SortOrder(E.Column("price"), False, False),
              E.SortOrder(E.Column("item"), True, True)]
    prices = sorted(p for p in data["price"].to_pylist() if p is not None
                    and p == p)
    bounds = [(prices[len(prices) * (7 - i) // 8], int(i * 100))
              for i in range(7)]

    def mk_range():
        return RangePartitioner(orders, 8, bounds, schema)

    partitioners = [
        ("single", lambda: SinglePartitioner()),
        ("hash", lambda: HashPartitioner([E.Column("item")], 8, schema)),
        ("roundrobin", lambda: RoundRobinPartitioner(8)),
        ("range", mk_range),
    ]
    for name, mk in partitioners:
        # reference: per-partition boolean-mask takes over partition_ids
        pids = mk().partition_ids(batch)
        ref = {}
        for pid in sorted(set(pids.tolist())):
            idx = np.nonzero(pids == pid)[0].astype(np.int64)
            ref[pid] = _pydict_of(batch.take(idx))
        got_dev = {pid: _pydict_of(sub) for pid, sub in mk().bucketize(batch)}
        assert got_dev == ref, f"device bucketize mismatch ({name})"
        got_host = {pid: _pydict_of(sub)
                    for pid, sub in mk().bucketize_host(batch)}
        assert got_host == ref, f"host bucketize mismatch ({name})"

    # range device kernel and host searchsorted must agree row-by-row
    rp = mk_range()
    host = HostBatch.from_batch(batch)
    assert np.array_equal(rp.partition_ids(batch), rp.partition_ids_host(host))
    # routing is ordered: every row of partition p sorts <= rows of p+1
    parts = mk_range().bucketize(batch)
    from blaze_tpu.ops import sort_keys as SK

    last = None
    for pid, sub in parts:
        keys = SK.merge_keys_matrix(sub.to_columnar(), orders)
        rows = [tuple(r) for r in keys]
        if last is not None and rows:
            assert last <= min(rows)
        if rows:
            last = max(rows)


def test_bucketize_one_gather_per_batch_counter():
    """Hot-path invariant: splitting B batches costs exactly B gathers (no
    per-partition take loop), observable via the repartitioner counters the
    shuffle writers surface as metrics."""
    from blaze_tpu.core.batch import ColumnarBatch
    from blaze_tpu.ir import exprs as E
    from blaze_tpu.ir import types as T
    from blaze_tpu.ops.shuffle.repartitioner import RangePartitioner

    schema = T.Schema.of(("price", T.F64), ("item", T.I64))
    orders = [E.SortOrder(E.Column("price"), True, True)]
    rp = RangePartitioner(orders, 4, [(25.0, 0), (50.0, 0), (75.0, 0)], schema)
    for seed in range(3):
        batch = ColumnarBatch.from_pydict(_batch_for_bucketize(seed=seed), schema)
        rp.bucketize_host(batch)
        rp.bucketize(batch)
    assert rp.split_batches == 6
    assert rp.split_gathers == 6


@pytest.mark.quick
def test_spill_merge_rides_packed_keys_only(monkeypatch):
    """Device-key spill merge must consume the squeezed #sortkey columns —
    never re-derive keys from data columns (merge_keys_matrix /
    host_keys_matrix stay un-called for the whole spilled query)."""
    from blaze_tpu.ops import sort_keys as SK

    def boom(*a, **k):  # pragma: no cover - only fires on regression
        raise AssertionError("merge re-derived sort keys from data columns")

    rng = np.random.default_rng(11)
    n = 30_000
    vals = (rng.random(n) * 1e6).astype(object)
    vals[rng.random(n) < 0.03] = None
    b = rng.integers(-(10**6), 10**6, n).tolist()
    data = {"a": vals.tolist(), "b": b}
    orders = [so("a", asc=False, nulls_first=False), so("b")]
    expect = collect_pydict(SortExec(mem_scan(data, num_batches=12), orders))
    MemManager.reset()
    monkeypatch.setattr(SK, "merge_keys_matrix", boom)
    monkeypatch.setattr(SK, "host_keys_matrix", boom)
    with config_override(memory_total=300_000, memory_fraction=1.0):
        out = collect_pydict(SortExec(mem_scan(data, num_batches=12), orders))
    mgr_spills = MemManager._instance.spill_count if MemManager._instance else 0
    MemManager.reset()
    assert mgr_spills > 0, "test must engage the spill path"
    assert out == expect


def test_external_sort_multikey_desc_nulls_with_spill():
    """Vectorized spilled-run merge (device-key path): multi-column keys,
    mixed directions, and NULL ordering must match the in-memory sort."""
    rng = np.random.default_rng(7)
    n = 30_000
    a = rng.integers(0, 50, n).astype(object)
    a[rng.random(n) < 0.05] = None
    b = rng.integers(-(10**6), 10**6, n).tolist()
    data = {"a": a.tolist(), "b": b}
    orders = [so("a", asc=False), so("b")]
    out_mem = collect_pydict(
        SortExec(mem_scan(data, num_batches=12), orders))
    MemManager.reset()
    with config_override(memory_total=1_500_000, memory_fraction=1.0):
        out_spill = collect_pydict(
            SortExec(mem_scan(data, num_batches=12), orders))
    MemManager.reset()
    assert out_spill == out_mem
