"""The cells `q29_smj_facts` and `q47_sort_rank` and the per-layer metrics
that came with them: the new readers on synthetic launches and counters, the
byte count of `smj_roofline_share` against a case worked by hand, and a traced
rehearsal of both cells on the plan the chip runs (see
`test_host_span_metrics`). A rehearsal has no device trace, so the metrics
read from one (`smj_device_s`, `smj_roofline_share`, `sort_device_s`) have
nothing to read there and stay out of the line; the synthetic trace pins
them."""

import json
import types

import pytest

from tests.benchmark import helpers
from tests.benchmark.test_host_span_metrics import _as_on_the_chip, _reader

helpers.load_run()  # puts the benchmark's directory on sys.path
from benchlib import xplane  # noqa: E402
from benchlib.registry import Registry  # noqa: E402

MS = 1e6  # ns


def _trace(launches, queries):
    """One chip; ``launches`` are (start_ms, dur_ms, name), ``queries``
    (start_ms, dur_ms) of the `bench_query` annotations."""
    return xplane.Trace(
        {0: [(s * MS, d * MS, n) for s, d, n in launches]}, {0: []},
        sorted([(0.0, 1.0, xplane.ANCHOR)]
               + [(s * MS, d * MS, "bench_query") for s, d in queries]))


def _ctx(trace, records, classes=None, rows=None):
    run = helpers.load_run()
    ctx = types.SimpleNamespace(
        trace=trace, classes=classes or {},
        records=[types.SimpleNamespace(index=i, name=name, counters=counters)
                 for i, (name, counters) in enumerate(records)],
        system=types.SimpleNamespace(
            data=types.SimpleNamespace(rows=lambda table: rows[table]),
            devices=[types.SimpleNamespace(device_kind="TPU v5 lite")]))
    ctx.per_query = lambda value: run.ReadContext.per_query(ctx, value)
    return ctx


LAUNCHES = [
    # query 0: the join's three programs, two sorts, and programs of others
    (10, 40, "jit_smj_probe(111)"), (60, 4, "jit_smj_pairs(222)"),
    (70, 6, "jit_smj_rows(333)"), (80, 20, "jit_sort_take(444)"),
    (110, 1, "jit_sort(555)"), (120, 500, "jit_agg_partial(666)"),
    (112, 0.5, "jit_sort_order(888)"),
    (700, 9, "jit_sorted_something_else(777)"),
    # between the queries: nobody's
    (1500, 100, "jit_smj_probe(111)"),
    # query 1
    (2010, 60, "jit_smj_probe(111)"), (2100, 30, "jit_sort_take(444)"),
]
QUERIES = [(0, 1000), (2000, 1000)]


def test_program_device_s_sums_the_named_programs_inside_each_query():
    ctx = _ctx(_trace(LAUNCHES, QUERIES), [("q29", {}), ("q29", {})])
    # medians of (0.050, 0.060) and (0.0215, 0.030)
    assert _reader("smj_device_s")(ctx) == pytest.approx(0.055)
    assert _reader("sort_device_s")(ctx) == pytest.approx(0.02575)
    # the parent's program runs no such launch: nothing to read, no error
    parent = _ctx(_trace([(10, 500, "jit_agg_partial(666)")], QUERIES),
                  [("q29", {}), ("q29", {})])
    assert _reader("smj_device_s")(parent) is None
    assert _reader("smj_roofline_share")(parent) is None
    # the parent sorts with the eager `jit(sort)` alone
    old = _ctx(_trace([(10, 7, "jit_sort(555)")], QUERIES[:1]), [("q47", {})])
    assert _reader("sort_device_s")(old) == pytest.approx(0.007)


def test_join_bytes_against_a_case_worked_by_hand():
    module = Registry([helpers.BENCH_DIR]).module("readers", "smj_roofline_share")
    # 1,000 x 100 rows on 3 keys, 100 pairs, 5 and 4 columns a side; a plane
    # is 8 + 1 bytes a row. Keys read: 1,100 rows x 3 x 9 = 29,700. Pairs:
    # 100 x 9 columns x 9 bytes = 8,100, read once and written once.
    assert module.join_bytes(1000, 100, 100, 3, 5, 4) == 29_700 + 2 * 8_100
    assert module.join_bytes(10, 10, 0, 1, 2, 2) == 20 * 9


def test_smj_roofline_share_is_bytes_over_bandwidth_over_device_time():
    q29 = Registry([helpers.BENCH_DIR]).module("queries", "q29")
    rows = {"store_sales": 2_880_404, "store_returns": 287_514}
    counters = {"smj_matched_pairs": 287_514, "smj_device_joins": 4}
    ctx = _ctx(_trace(LAUNCHES, QUERIES), [("q29", counters)] * 2,
               {"q29": q29}, rows)
    nbytes = (2_880_404 + 287_514) * 3 * 9 + 2 * 287_514 * 9 * 9
    want = [100 * nbytes / 819e9 / s for s in (0.050, 0.060)]
    assert _reader("smj_roofline_share")(ctx) == pytest.approx(sum(want) / 2)
    assert 0 < _reader("smj_roofline_share")(ctx) < 100
    # a class without a merge join, or a program without the counter
    assert _reader("smj_roofline_share")(_ctx(
        _trace(LAUNCHES, QUERIES), [("q29", counters)] * 2,
        {"q29": types.SimpleNamespace()}, rows)) is None
    assert _reader("smj_roofline_share")(_ctx(
        _trace(LAUNCHES, QUERIES), [("q29", {})] * 2, {"q29": q29}, rows)) is None


def test_smj_device_joins_reads_the_counter_or_nothing():
    trace = _trace([], QUERIES)
    counted = _ctx(trace, [("q29", {"smj_device_joins": 4, "smj_host_joins": 0})] * 3)
    assert _reader("smj_device_joins")(counted) == 4
    assert _reader("smj_device_joins")(_ctx(trace, [("q01", {})])) is None


def test_q29_ends_a_program_without_the_counters_before_anything_compiles(monkeypatch):
    from blaze_tpu.runtime import metrics

    q29 = Registry([helpers.BENCH_DIR]).module("queries", "q29")
    monkeypatch.setattr(metrics, "TRIPWIRE_METRICS", tuple(
        m for m in metrics.TRIPWIRE_METRICS if not m.startswith("smj_")))
    # `run.main` turns an ImportError into its FAIL line and exit code 1
    with pytest.raises(ImportError, match="smj_device_joins.*smj_host_joins"):
        q29.plan(None)


def _traced_rehearsal(cell, tmp_path, capsys):
    path = helpers.tiny_manifest(tmp_path, _as_on_the_chip)
    rc, lines = helpers.run_cell(capsys, path, cell, trace=1)
    assert rc == 0, lines
    result = json.loads(lines[-1])
    assert result["correct"] and result["failed"] == 0
    (readings,) = [line for line in lines if line.startswith("readings: ")]
    return result["metrics"], json.loads(readings[len("readings: "):])


def test_traced_rehearsal_of_q29_joins_on_the_device_path(tmp_path, capsys):
    metrics, readings = _traced_rehearsal("q29_smj_facts", tmp_path, capsys)
    counters = readings["counters_last_query"]
    assert counters["smj_device_joins"] >= 1 and counters["smj_host_joins"] == 0
    # every return names one sale: as many pairs as the tiny store_returns
    assert counters["smj_matched_pairs"] == helpers.TINY_ROWS["store_returns"]
    assert metrics["smj_device_joins"]["value"] == counters["smj_device_joins"]
    assert 0 <= metrics["smj_host_s"]["value"] <= metrics["smj_self_s"]["value"]
    assert metrics["sortwin_self_s"]["value"] > 0
    # read from a device trace: none on the CPU
    assert not {"smj_device_s", "smj_roofline_share", "sort_device_s"} & set(metrics)
    assert "join_self_s" not in metrics  # lists the two cells with a hash join


def test_traced_rehearsal_of_q47_ranks_after_the_slot_table(tmp_path, capsys):
    metrics, _readings = _traced_rehearsal("q47_sort_rank", tmp_path, capsys)
    assert metrics["agg_dense_batches"]["value"] >= 4
    assert metrics["sortwin_self_s"]["value"] > 0
    assert "sort_device_s" not in metrics and "smj_device_joins" not in metrics
