"""The cell `q51_cume_window` and what came with it: the dated generator
against `tpcds_star` (every other column byte-equal for the same seed), the
q51 class against its plain reference on a cut-down generator, the new
readers on synthetic launches and counters, `window_roofline_share`'s byte
count against a case worked by hand, and a traced rehearsal of the cell on
the plan the chip runs. A rehearsal has no device trace, so the metrics read
from one (`window_device_s`, `window_roofline_share`) have nothing to read
there and stay out of the line; the synthetic trace pins them."""

import json
import os
import types

import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from tests.benchmark import helpers
from tests.benchmark.test_host_span_metrics import _reader
from tests.benchmark.test_smj_cell import (QUERIES, _ctx, _trace,
                                           _traced_rehearsal)

helpers.load_run()  # puts the benchmark's directory on sys.path
from benchlib import plans  # noqa: E402
from benchlib.registry import Registry  # noqa: E402

REGISTRY = Registry([helpers.BENCH_DIR])
with open(os.path.join(helpers.BENCH_DIR, "configs",
                       "tpcds_sf1_window_chip1.json")) as _f:
    CONFIG = json.load(_f)


def _tiny(**rows):
    config = json.loads(json.dumps(CONFIG))
    config["generator_params"]["table_rows"] = dict(helpers.TINY_ROWS, **rows)
    return config


def _read(paths):
    return pa.concat_tables([pq.read_table(p) for p in paths])


# -- the generator -------------------------------------------------------------


@pytest.mark.parametrize("seed", [3, 2**31 + 11])
def test_dated_generator_adds_one_column_and_moves_nothing(tmp_path, seed):
    star = REGISTRY.module("generators", "tpcds_star")
    dated = REGISTRY.module("generators", "tpcds_star_dated")
    (tmp_path / "a").mkdir(), (tmp_path / "b").mkdir()
    plain = star.generate(str(tmp_path / "a"), seed, _tiny())
    with_dates = dated.generate(str(tmp_path / "b"), seed, _tiny())
    assert set(plain) == set(with_dates) == set(star.TABLES)
    for table in star.TABLES:
        a, b = _read(plain[table]), _read(with_dates[table])
        if table == "store_sales":
            assert b.column_names == a.column_names + ["ss_sold_date_sk"]
            b = b.drop_columns(["ss_sold_date_sk"])
        assert b.schema == a.schema
        for name in a.column_names:  # byte for byte
            assert [c.buffers()[1].to_pybytes() for c in a[name].chunks] == \
                [c.buffers()[1].to_pybytes() for c in b[name].chunks], name
        assert [pq.ParquetFile(p).metadata.num_rows for p in plain[table]] == \
            [pq.ParquetFile(p).metadata.num_rows for p in with_dates[table]]


def test_dated_generator_gives_a_ticket_one_date_of_the_sale_days(tmp_path):
    dated = REGISTRY.module("generators", "tpcds_star_dated")
    sales = _read(dated.generate(str(tmp_path), 5, _tiny(),
                                 ("store_sales",))["store_sales"])
    assert sales.schema.field("ss_sold_date_sk").type == pa.int64()
    by_ticket = sales.group_by("ss_ticket_number").aggregate(
        [("ss_sold_date_sk", "min"), ("ss_sold_date_sk", "max")])
    assert by_ticket["ss_sold_date_sk_min"] == by_ticket["ss_sold_date_sk_max"]
    dates = sales["ss_sold_date_sk"].to_numpy()
    assert dates.min() >= dated.FIRST_SALE_DATE_SK
    assert dates.max() < dated.FIRST_SALE_DATE_SK + dated.SALE_DAYS
    assert len(set(dates.tolist())) > 300  # 750 tickets over 1,823 days
    # the same seed, the same dates; another seed, others
    again = _read(dated.generate(str(tmp_path), 5, _tiny(),
                                 ("store_sales",))["store_sales"])
    assert again["ss_sold_date_sk"] == sales["ss_sold_date_sk"]
    other = _read(dated.generate(str(tmp_path), 6, _tiny(),
                                 ("store_sales",))["store_sales"])
    assert other["ss_sold_date_sk"] != sales["ss_sold_date_sk"]


# -- the class and its reference ----------------------------------------------


def test_q51_reference_against_a_case_worked_by_hand():
    q51 = REGISTRY.module("queries", "q51")
    from decimal import Decimal as D

    price = pa.array([D("1.50"), D("2.00"), D("0.25"), D("4.00"), D("1.00"),
                      D("3.00")], pa.decimal128(7, 2))
    sales = pa.table({
        "ss_item_sk": pa.array([7, 7, 7, 9, 9, 7], pa.int64()),
        "ss_sold_date_sk": pa.array([2, 1, 2, 1, 3, 3], pa.int64()),
        "ss_sales_price": price})
    # item 7: day 1 2.00, day 2 1.75, day 3 3.00 -> cume 2.00 3.75 6.75,
    # running max the same; item 9: day 1 4.00, day 3 1.00 -> 4.00 5.00
    got = q51.reference({"store_sales": sales}).to_pydict()
    assert got == {
        "ss_sold_date_sk": [1, 2, 3], "items": [2, 1, 2],
        "cume_sales": [D("6.00"), D("3.75"), D("11.75")],
        "store_cumulative": [D("4.00"), D("3.75"), D("6.75")]}
    answer = q51.reference({"store_sales": sales})
    assert answer.schema.field("cume_sales").type == pa.decimal128(37, 2)
    assert answer.schema.field("store_cumulative").type == pa.decimal128(27, 2)


@pytest.mark.parametrize("rows,items,batch_size", [
    (6000, 300, 2048),   # ~20 rows a partition: many close in a batch
    (20000, 12, 1024),   # ~1,600 rows a partition: each spans batches
])
def test_q51_class_equals_its_reference_on_the_chips_plan(
        tmp_path, rows, items, batch_size):
    """The plan the chip runs (no radix table, no fused filter), at batch
    sizes that cut every partition stream into many batches."""
    import dataclasses

    from blaze_tpu.config import get_config
    from blaze_tpu.runtime.session import Session

    q51 = REGISTRY.module("queries", "q51")
    dated = REGISTRY.module("generators", "tpcds_star_dated")
    paths = dated.generate(str(tmp_path), 2**31 + 7,
                           _tiny(store_sales=rows, item=items), q51.TABLES)
    data = plans.Dataset(paths, CONFIG["scan_partitions"],
                         CONFIG["shuffle_partitions"])
    want = q51.reference({"store_sales": data.table("store_sales")})
    session = Session(conf=dataclasses.replace(
        get_config(), radix_agg=False, fused_filter_agg=False,
        batch_size=batch_size, **CONFIG["session"]["conf"]))
    try:
        got = session.execute_to_table(q51.plan(data))
        counters = session.metrics.totals(tuple(CONFIG["counters_must"]))
    finally:
        session.close()
    assert plans.rows_of(got, q51.ENGINE_COLUMNS, True) == \
        plans.rows_of(want, q51.REFERENCE_COLUMNS, True)
    assert got.schema.field("cume_sales").type == pa.decimal128(37, 2)
    for name, (lo, hi) in CONFIG["counters_must"].items():
        assert counters[name] >= (lo or 0), name
        assert hi is None or counters[name] <= hi, name
    # both windows saw every (item, date) group once
    assert counters["window_rows"] == 2 * sum(want["items"].to_pylist())


def test_q51_ends_a_program_without_the_counters_before_anything_compiles(
        monkeypatch):
    from blaze_tpu.runtime import metrics

    q51 = REGISTRY.module("queries", "q51")
    monkeypatch.setattr(metrics, "TRIPWIRE_METRICS", tuple(
        m for m in metrics.TRIPWIRE_METRICS if not m.startswith("window_")
        or m in ("window_segments", "window_group_loops")))
    # `run.main` turns an ImportError into its FAIL line and exit code 1
    with pytest.raises(ImportError,
                       match="window_device_batches.*window_host_batches"):
        q51.plan(None)


# -- the readers ---------------------------------------------------------------

LAUNCHES = [
    (10, 3, "jit_window_scan(111)"), (20, 5, "jit_window_scan(111)"),
    (30, 400, "jit_agg_partial(666)"), (500, 2, "jit_window_scan(222)"),
    (600, 9, "jit_windowless(777)"), (1500, 50, "jit_window_scan(111)"),
    (2010, 12, "jit_window_scan(111)"),
]


def test_window_device_s_sums_the_window_programs_inside_each_query():
    ctx = _ctx(_trace(LAUNCHES, QUERIES), [("q51", {}), ("q51", {})])
    # medians of (0.010, 0.012)
    assert _reader("window_device_s")(ctx) == pytest.approx(0.011)
    parent = _ctx(_trace([(10, 500, "jit_agg_partial(666)")], QUERIES),
                  [("q51", {}), ("q51", {})])
    assert _reader("window_device_s")(parent) is None
    assert _reader("window_roofline_share")(parent) is None


def test_window_bytes_against_a_case_worked_by_hand():
    module = REGISTRY.module("readers", "window_roofline_share")
    windows = ({"keys": 2, "arguments": 1, "results": 1},) * 2
    # 1,000 rows through each of two windows (2,000 counted): each reads
    # two key planes and an argument plane and writes a result plane, 9
    # bytes a plane and row: 2 x 1,000 x 4 x 9
    assert module.window_bytes(2000, windows) == 72_000
    assert module.window_bytes(500, windows[:1]) == 500 * 4 * 9


def test_window_roofline_share_is_bytes_over_bandwidth_over_device_time():
    q51 = REGISTRY.module("queries", "q51")
    counters = {"window_rows": 5_520_000, "window_device_batches": 44}
    ctx = _ctx(_trace(LAUNCHES, QUERIES), [("q51", counters)] * 2,
               {"q51": q51})
    nbytes = 5_520_000 * 4 * 9
    want = [100 * nbytes / 819e9 / s for s in (0.010, 0.012)]
    assert _reader("window_roofline_share")(ctx) == pytest.approx(sum(want) / 2)
    assert 0 < _reader("window_roofline_share")(ctx) < 100
    # a class without windows, or a program without the counter
    assert _reader("window_roofline_share")(_ctx(
        _trace(LAUNCHES, QUERIES), [("q51", counters)] * 2,
        {"q51": types.SimpleNamespace()})) is None
    assert _reader("window_roofline_share")(_ctx(
        _trace(LAUNCHES, QUERIES), [("q51", {})] * 2, {"q51": q51})) is None


def test_window_device_batches_reads_the_counter_or_nothing():
    trace = _trace([], QUERIES)
    counted = _ctx(trace, [("q51", {"window_device_batches": 44})] * 3)
    assert _reader("window_device_batches")(counted) == 44
    assert _reader("window_device_batches")(_ctx(trace, [("q01", {})])) is None


# -- the cell ------------------------------------------------------------------


def test_traced_rehearsal_of_q51_windows_on_the_device_path(tmp_path, capsys):
    metrics, readings = _traced_rehearsal("q51_cume_window", tmp_path, capsys)
    counters = readings["counters_last_query"]
    assert counters["window_device_batches"] >= 2
    assert counters["window_host_batches"] == 0
    assert counters["wide_host_batches"] == 0
    assert counters["window_group_loops"] == 0
    assert metrics["window_device_batches"]["value"] == \
        counters["window_device_batches"]
    assert 0 <= metrics["window_host_s"]["value"] <= \
        metrics["window_self_s"]["value"]
    assert metrics["sortwin_self_s"]["value"] >= metrics["window_self_s"]["value"]
    # read from a device trace: none on the CPU (`sort_device_s` lists the
    # cell since PR 34; `test_gained_cells.py` reads it over stand-ins)
    assert not {"window_device_s", "window_roofline_share",
                "sort_device_s"} & set(metrics)
    # the other cells' listed metrics stay theirs
    assert not {"smj_device_joins", "join_self_s"} & set(metrics)


def test_the_other_cells_do_not_report_the_windows_metrics(tmp_path, capsys):
    metrics, _readings = _traced_rehearsal("q47_sort_rank", tmp_path, capsys)
    assert not {m for m in metrics if m.startswith("window_")}
    assert metrics["sortwin_self_s"]["value"] > 0
