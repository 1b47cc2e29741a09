"""High-cardinality device aggregation: the wide slot table.

A partial aggregation whose integer key space passes
``dense_agg_max_buckets`` plans ONE "wide" slot table (slot-sorted, its
packed id int64), and the merge of its partials reduces by one sort of the
same packed id (``jit(agg_merge_sorted)``), on every backend. Covers both
against a host oracle and the sort path, the quick-tier guards (a 100k-group
smoke and ``agg_reintern_rows == 0`` on the q67 bench shape: int keys never
round-trip through host interning), and the partial skipper, whose one
signal is the host table's whole-table ratio."""

import collections

import numpy as np
import pyarrow as pa
import pytest

from blaze_tpu.config import config_override
from blaze_tpu.ir import exprs as E
from blaze_tpu.ir import nodes as N
from blaze_tpu.ops import agg_device
from blaze_tpu.ops.agg import AggExec, _PartialSkipper
from blaze_tpu.ops.base import ExecContext
from blaze_tpu.runtime.metrics import MetricNode, tripwire_totals
from blaze_tpu.utils.device import DEVICE_STATS
from tests.util import mem_scan

F = E.AggFunction
M = E.AggMode
HASH = E.AggExecMode.HASH_AGG


def col(n):
    return E.Column(n)


def _two_stage(scan, keys, skipping=False):
    partial = AggExec(scan, HASH, [(k, col(k)) for k in keys], [
        N.AggColumn(E.AggExpr(F.SUM, [col("v")]), M.PARTIAL, "s"),
        N.AggColumn(E.AggExpr(F.COUNT, [col("v")]), M.PARTIAL, "c"),
    ], supports_partial_skipping=skipping)
    return AggExec(partial, HASH, [(k, col(k)) for k in keys], [
        N.AggColumn(E.AggExpr(F.SUM, [col("v")]), M.FINAL, "s"),
        N.AggColumn(E.AggExpr(F.COUNT, [col("v")]), M.FINAL, "c"),
    ])


def _collect(op, metrics=None):
    ctx = ExecContext()
    out = collections.defaultdict(list)
    for b in op.execute(0, ctx, metrics):
        for k, v in b.to_arrow().to_pydict().items():
            out[k].extend(v)
    return out


def _oracle(a, b, v):
    s = collections.defaultdict(int)
    c = collections.defaultdict(int)
    for ka, kb, kv in zip(a, b, v):
        s[(ka, kb)] += kv
        c[(ka, kb)] += 1
    return s, c


def _check(out, es, ec):
    got_s = dict(zip(zip(out["a"], out["b"]), out["s"]))
    got_c = dict(zip(zip(out["a"], out["b"]), out["c"]))
    assert got_s == dict(es)
    assert got_c == dict(ec)


def _hicard_scan(n=300_000, ka=2000, kb=100, num_batches=12, seed=5):
    # slot space ka.pow2 * kb.pow2 = 2048 * 128 > dense_agg_max_buckets,
    # so the bucketed planner must take the wide table
    rng = np.random.default_rng(seed)
    a = rng.integers(0, ka, n)
    b = rng.integers(0, kb, n)
    v = rng.integers(0, 100, n)
    scan = mem_scan({
        "a": pa.array(a, type=pa.int64()),
        "b": pa.array(b, type=pa.int64()),
        "v": pa.array(v, type=pa.int64()),
    }, num_batches=num_batches)
    return scan, a.tolist(), b.tolist(), v.tolist()


@pytest.fixture
def plans(monkeypatch):
    """The table kind of every plan the partial aggers make."""
    seen = []
    real = agg_device.DevicePartialAgger._plan_bucketed

    def spy(self, probe, capacity, prev):
        st = real(self, probe, capacity, prev)
        seen.append(st[0] if isinstance(st, tuple) else st)
        return st

    monkeypatch.setattr(agg_device.DevicePartialAgger, "_plan_bucketed", spy)
    return seen


def _wide_ran(plans, root, s0):
    """Every partial batch ran the wide table, and the merge sorted its
    packed id."""
    s1 = DEVICE_STATS.snapshot()
    assert plans and set(plans) == {"wide"}, plans
    assert s1["agg_sort_batches"] == s0["agg_sort_batches"]
    assert s1["agg_slot_sorted_batches"] - s0["agg_slot_sorted_batches"] \
        == s1["agg_dense_batches"] - s0["agg_dense_batches"] > 0
    assert root.total("merge_slot_sorted_batches") >= 1


@pytest.mark.quick
def test_radix_100k_group_smoke(plans):
    """~190k groups through the wide partial table and the slot-sorted
    merge with the default configuration, exact vs a host oracle."""
    scan, a, b, v = _hicard_scan()
    es, ec = _oracle(a, b, v)
    assert len(es) > 100_000
    root = MetricNode("root")
    s0 = DEVICE_STATS.snapshot()
    out = _collect(_two_stage(scan, ["a", "b"]), root)
    _check(out, es, ec)
    _wide_ran(plans, root, s0)


def test_radix_matches_sort_path(plans):
    scan, a, b, v = _hicard_scan(n=60_000, seed=9)
    es, ec = _oracle(a, b, v)
    root = MetricNode("root")
    s0 = DEVICE_STATS.snapshot()
    wide = _collect(_two_stage(scan, ["a", "b"]), root)
    _wide_ran(plans, root, s0)
    with config_override(dense_agg=False):
        host = _collect(_two_stage(scan, ["a", "b"]))
    _check(wide, es, ec)
    _check(host, es, ec)


@pytest.mark.quick
def test_q67_shape_no_reintern(plans):
    """q67 bench shape (int composite keys, near-unique): keys stay device
    codes end to end — zero rows re-interned at the merge table."""
    scan, a, b, v = _hicard_scan(n=100_000, ka=2000, kb=400, num_batches=8,
                                 seed=67)
    es, ec = _oracle(a, b, v)
    root = MetricNode("root")
    s0 = DEVICE_STATS.snapshot()
    out = _collect(_two_stage(scan, ["a", "b"], skipping=True), root)
    _check(out, es, ec)
    assert tripwire_totals(root)["agg_reintern_rows"] == 0
    _wide_ran(plans, root, s0)


def test_partial_skipping_near_unique_keys(monkeypatch):
    """Near-unique keys on the host table flip the skipper; passthrough
    batches still merge to the exact answer."""
    monkeypatch.setattr(agg_device, "supports_device_partial",
                        lambda op, schema: False)
    scan, a, b, v = _hicard_scan(n=120_000, ka=2000, kb=400, num_batches=10,
                                 seed=3)
    es, ec = _oracle(a, b, v)
    root = MetricNode("root")
    with config_override(partial_agg_skipping_min_rows=20_000):
        out = _collect(_two_stage(scan, ["a", "b"], skipping=True), root)
    _check(out, es, ec)
    assert root.total("partial_skipped_batches") > 0


class _Table:
    def __init__(self, rows, slots):
        self.rows_processed, self.num_slots = rows, slots


@pytest.mark.parametrize("table,skip", [
    (None, False),                     # the device path: no signal
    (_Table(9_999, 9_999), False),     # under min_rows
    (_Table(10_000, 9_500), True),     # near-unique: 0.95 > 0.9
    (_Table(10_000, 9_000), False),    # 0.9 is not past the ratio
    (_Table(50_000, 5), False)])       # few groups
def test_partial_skipper_whole_table_ratio(table, skip):
    """The skipper skips once the table has seen ``min_rows`` rows and its
    ``num_slots / rows_processed`` passes the ratio; without a table, never."""
    with config_override(partial_agg_skipping_min_rows=10_000,
                         partial_agg_skipping_ratio=0.9):
        sk = _PartialSkipper(None, ExecContext())
        assert sk.should_skip(table) is skip
