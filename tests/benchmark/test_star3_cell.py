"""The cell `q17_star_wide` (configuration `tpcds_sf1_star3_chip1`, generator
`tpcds_star3`, query class `q17`) and its two per-layer metrics: the
generator's tables, the manifest's new entries, the class's up-front check,
and traced rehearsals on the plan the chip runs (see `test_host_span_metrics`)
— the cell as the manifest has it, and a dense-key star whose groups hold
tens of triples, so that the moments meet groups of many rows."""

import json

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pyarrow.parquet as pq
import pytest

from tests.benchmark import helpers
from tests.benchmark.test_host_span_metrics import _as_on_the_chip, _ctx, _reader

helpers.load_run()  # puts the benchmark's directory on sys.path
from benchlib import plans  # noqa: E402
from benchlib.registry import Registry  # noqa: E402

CELL = "q17_star_wide"
CONFIG = "tpcds_sf1_star3_chip1"
METRICS = ("moment_device_batches", "moment_finish_host_s")
REGISTRY = Registry([helpers.BENCH_DIR])
# a star whose (item id, description, state) groups hold tens of triples:
# twenty item revisions, two hundred customers
DENSE_ROWS = dict(helpers.TINY_ROWS, store_sales=20_000, store_returns=3_000,
                  item=20, customer=200)


def _config(table_rows=None):
    with open(f"{helpers.BENCH_DIR}/configs/{CONFIG}.json") as f:
        config = json.load(f)
    if table_rows is not None:
        config["generator_params"]["table_rows"] = table_rows
    return config


def _tables(tmp_path, config, seed=7):
    generator = REGISTRY.module("generators", "tpcds_star3")
    tmp_path.mkdir(parents=True, exist_ok=True)
    paths = generator.generate(str(tmp_path), seed, config)
    return {t: pa.concat_tables([pq.read_table(p) for p in files])
            for t, files in paths.items()}


# -- the generator ----------------------------------------------------------------


def test_shape_is_table_3_2_and_a_cut_derives_what_it_leaves_out():
    generator = REGISTRY.module("generators", "tpcds_star3")
    full = generator.shape(_config())
    assert (full["store_sales"], full["store_returns"], full["catalog_sales"],
            full["date_dim"], full["item"]) == (
        2_880_404, 287_514, 1_441_548, 73_049, 18_000)
    assert full["sale_days"] == 1823
    cut = generator.shape(_config(helpers.TINY_ROWS))
    assert cut["catalog_sales"] == round(6000 * 1_441_548 / 2_880_404)
    assert cut["date_dim"] == 73_049
    # under a cut every sale falls in 2001Q1
    assert (cut["first_sale_date_sk"], cut["sale_days"]) == (2451911, 90)


def test_tables_keep_the_store_channel_and_plant_the_repurchases(tmp_path):
    tables = _tables(tmp_path, _config(helpers.TINY_ROWS))
    assert {t: tables[t].num_rows for t in tables} == {
        "store_sales": 6000, "store_returns": 900, "catalog_sales": 3003,
        "date_dim": 73_049, "store": 12, "item": 300}
    dates = dict(zip(tables["date_dim"]["d_date_sk"].to_pylist(),
                     tables["date_dim"]["d_quarter_name"].to_pylist()))
    assert dates[2451911] == "2001Q1" and dates[2451910] == "2000Q4"
    assert {dates[d] for d in tables["store_sales"]["ss_sold_date_sk"]
            .to_pylist()} == {"2001Q1"}
    # a return comes 1..60 days after its ticket's sale
    sales = tables["store_sales"].group_by("ss_ticket_number").aggregate(
        [("ss_sold_date_sk", "min")])
    sold = dict(zip(sales["ss_ticket_number"].to_pylist(),
                    sales["ss_sold_date_sk_min"].to_pylist()))
    returns = tables["store_returns"]
    lag = np.array(returns["sr_returned_date_sk"].to_pylist()) - np.array(
        [sold[t] for t in returns["sr_ticket_number"].to_pylist()])
    assert lag.min() >= 1 and lag.max() <= 60
    # one return in four has a catalog line of its customer and item
    catalog = tables["catalog_sales"]
    bought = set(zip(catalog["cs_bill_customer_sk"].to_pylist(),
                     catalog["cs_item_sk"].to_pylist()))
    again = sum((c, i) in bought for c, i in zip(
        returns["sr_customer_sk"].to_pylist(), returns["sr_item_sk"].to_pylist()))
    assert 900 // 4 <= again < 900 // 4 + 30
    items = tables["item"]
    assert {len(s) for s in items["i_item_id"].to_pylist()} == {16}
    assert pc.max(pc.utf8_length(items["i_item_desc"])).as_py() <= 200
    # 150 item ids over 300 revisions, as tpcds_inventory keeps history
    assert len(set(items["i_item_id"].to_pylist())) == 150
    assert {len(s) for s in tables["store"]["s_state"].to_pylist()} == {2}


def test_the_same_seed_gives_the_same_tables(tmp_path):
    a = _tables(tmp_path / "a", _config(helpers.TINY_ROWS), seed=2**31 + 5)
    b = _tables(tmp_path / "b", _config(helpers.TINY_ROWS), seed=2**31 + 5)
    assert all(a[t].equals(b[t]) for t in a)


# -- the manifest and the class -----------------------------------------------------


def test_the_manifest_gained_one_configuration_one_cell_and_two_metrics():
    with open(helpers.MANIFEST) as f:
        manifest = json.load(f)
    configs = {c["name"]: c for c in manifest["configs"]}
    cells = {w["name"]: w for w in manifest["workloads"]}
    metrics = {m["name"]: m for m in manifest["per_layer"]}
    assert configs[CONFIG]["reduced"] == ["scale_factor"]
    assert cells[CELL] == {"name": CELL, "config": CONFIG, "traffic": "q17_repeat",
                           "chips": 1, "why": cells[CELL]["why"]}
    for name in METRICS:
        assert metrics[name]["workloads"] == [CELL]
        assert metrics[name]["moves"] == "query_s"
        assert metrics[name]["layer"] == "operators"
        assert callable(REGISTRY.reader(name))
    config = _config()
    assert config["chips"] == 1 and "query_text" in config
    assert config["counters_must"]["moment_host_batches"] == [0, 0]
    assert config["counters_must"]["smj_device_joins"] == [2, None]
    assert set(config["session"]["conf"]) == {"advisory_partition_bytes"}
    traffic = REGISTRY.data("traffic", "q17_repeat")
    assert traffic["classes"] == [{"query": "q17", "weight": 1,
                                   "params": {"year": 2001}}]


def test_q17_ends_a_program_without_the_moments_before_anything_compiles(
        monkeypatch):
    from blaze_tpu.runtime import metrics

    q17 = REGISTRY.module("queries", "q17")
    monkeypatch.setattr(metrics, "TRIPWIRE_METRICS", tuple(
        m for m in metrics.TRIPWIRE_METRICS if not m.startswith("moment_")))
    # `run.main` turns an ImportError into its FAIL line and exit code 1
    with pytest.raises(ImportError, match="moment_device_batches"):
        q17.plan(None)
    with pytest.raises(ImportError, match="STDDEV_SAMP"):
        q17.reference({})


def test_moment_device_batches_reads_the_counter_or_nothing():
    counted = _ctx((10.0, 5.0, {"moment_device_batches": 9}),
                   (20.0, 5.0, {"moment_device_batches": 9}))
    assert _reader("moment_device_batches")(counted) == 9
    # the parent counts no moments: nothing to read, and no error
    assert _reader("moment_device_batches")(
        _ctx((10.0, 5.0, {"sync_calls": 7}))) is None


# -- the cell, rehearsed ------------------------------------------------------------


def _dense(manifest, tmp_path):
    _as_on_the_chip(manifest, tmp_path)
    (entry,) = [c for c in manifest["configs"] if c["name"] == CONFIG]
    path = tmp_path / entry["file"]
    config = json.loads(path.read_text())
    config["generator_params"]["table_rows"] = DENSE_ROWS
    path.write_text(json.dumps(config))


def _traced_rehearsal(tmp_path, capsys, edit):
    path = helpers.tiny_manifest(tmp_path, edit)
    rc, lines = helpers.run_cell(capsys, path, CELL, trace=1)
    assert rc == 0, lines
    result = json.loads(lines[-1])
    assert result["correct"] and result["failed"] == 0
    (readings,) = [line for line in lines if line.startswith("readings: ")]
    return result["metrics"], json.loads(readings[len("readings: "):])


def test_traced_rehearsal_of_q17_chains_the_joins_and_the_moments(
        tmp_path, capsys):
    metrics, readings = _traced_rehearsal(tmp_path, capsys, _as_on_the_chip)
    counters = readings["counters_last_query"]
    # both merge joins in each of the four partitions, on the device
    assert counters["smj_device_joins"] == 8 and counters["smj_host_joins"] == 0
    assert counters["moment_device_batches"] >= 4
    assert counters["moment_host_batches"] == 0
    assert counters["coded_key_batches"] >= 1 and counters["host_key_batches"] == 0
    assert metrics["moment_device_batches"]["value"] >= 4
    assert metrics["moment_finish_host_s"]["value"] >= 0
    assert metrics["agg_dense_batches"]["value"] >= 4
    # the hash joins' and merge joins' lists do not name this cell yet
    assert not {"join_self_s", "smj_device_joins"} & set(metrics)


def test_dense_key_rehearsal_meets_groups_of_tens_of_triples(tmp_path, capsys):
    config = _config(DENSE_ROWS)
    q17 = REGISTRY.module("queries", "q17")
    answer = q17.reference(_tables(tmp_path / "data", config), limit=None)
    counts = answer["store_sales_quantitycount"].to_pylist()
    assert len(counts) <= 20 * 12 and np.median(counts) >= 20
    assert pc.count(answer["catalog_sales_quantitystdev"]).as_py() >= 50
    # the program's answer equals that reference row for row (`correct`),
    # the deviations of groups of tens of rows among it
    metrics, readings = _traced_rehearsal(tmp_path, capsys, _dense)
    assert readings["counters_last_query"]["moment_host_batches"] == 0
    assert metrics["moment_device_batches"]["value"] >= 4


def test_reference_rows_compare_as_the_harness_compares_them(tmp_path):
    q17 = REGISTRY.module("queries", "q17")
    answer = q17.reference(_tables(tmp_path, _config(DENSE_ROWS)))
    rows = plans.rows_of(answer, q17.REFERENCE_COLUMNS, q17.ORDERED)
    assert len(rows) == min(100, answer.num_rows)
    assert [r[:3] for r in rows] == sorted(r[:3] for r in rows)
    for row in rows:
        for (_m, _p), i in zip(q17.MEASURES, range(3, len(row), 4)):
            count, ave, stdev, cov = row[i:i + 4]
            assert count >= 1 and (stdev is None) == (count < 2)
            assert cov == (None if stdev is None else stdev / ave)
