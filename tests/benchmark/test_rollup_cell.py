"""The cell `q22_inv_rollup` and what came with it: the inventory generator's
row counts and determinism by seed, the q22 class against its plain reference
on a cut-down generator (whole rollup and first 100 rows, NULLs in the
names), the new readers on synthetic launches, spans and counters,
`rollup_roofline_share`'s byte count against a case worked by hand, the
manifest's new entries, and a traced rehearsal of the cell on the plan the
chip runs. A rehearsal has no device trace, so the metrics read from one
(`rollup_device_s`, `rollup_roofline_share`) have nothing to read there and
stay out of the line; the synthetic trace pins them."""

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
from benchlib import manifest as M  # noqa: E402
from benchlib import plans  # noqa: E402
from benchlib import spans as sp  # noqa: E402
from benchlib.registry import Registry  # noqa: E402

REGISTRY = Registry([helpers.BENCH_DIR])
CELL = "q22_inv_rollup"
CONFIG_NAME = "tpcds_sf1_inv_rollup_chip1"
with open(os.path.join(helpers.BENCH_DIR, "configs", CONFIG_NAME + ".json")) as _f:
    CONFIG = json.load(_f)
NEW_METRICS = ("rollup_device_s", "rollup_roofline_share", "expand_self_s",
               "expand_host_s", "coded_key_batches", "dict_host_s")


def _tiny(**rows):
    config = json.loads(json.dumps(CONFIG))
    config["generator_params"]["table_rows"] = {
        "item": 300, "inventory_weeks": 261, **rows}
    return config


def _read(paths):
    return pa.concat_tables([pq.read_table(p) for p in paths])


# -- the generator -------------------------------------------------------------


def test_table_3_2_rows_are_the_product_that_forms_inventory():
    gen = REGISTRY.module("generators", "tpcds_inventory")
    sizes = gen.shape(CONFIG)
    assert sizes == {"weeks": 261, "item_ids": 9000, "warehouses": 5,
                     "inventory": 11_745_000, "item": 18_000,
                     "date_dim": 73_049}
    assert gen.table_rows(1)["inventory"] == 261 * 9000 * 5
    wrong = json.loads(json.dumps(CONFIG))
    wrong["scale_factor"] = 7
    with pytest.raises(gen.GeneratorError, match="no row counts"):
        gen.shape(wrong)


@pytest.mark.parametrize("items,ids", [(18_000, 9_000), (300, 150), (7, 4),
                                       (1, 1), (2, 2), (3, 2), (4, 3)])
def test_item_ids_have_one_two_and_three_revisions_in_turn(items, ids):
    gen = REGISTRY.module("generators", "tpcds_inventory")
    first, count = gen.revisions(items)
    assert len(first) == ids == gen._item_ids(items)
    assert first[0] == 1 and (first + count)[-1] == items + 1
    assert (first[1:] == (first + count)[:-1]).all()  # no key left out
    assert count[:3].tolist() == [1, 2, 3][:ids] or items < 6


@pytest.mark.parametrize("seed", [3, 2**31 + 11])
def test_generator_row_counts_and_determinism_by_seed(tmp_path, seed):
    gen = REGISTRY.module("generators", "tpcds_inventory")
    (tmp_path / "a").mkdir(), (tmp_path / "b").mkdir(), (tmp_path / "c").mkdir()
    config = _tiny(inventory_weeks=20)
    one = gen.generate(str(tmp_path / "a"), seed, config)
    two = gen.generate(str(tmp_path / "b"), seed, config, ("inventory", "item"))
    other = gen.generate(str(tmp_path / "c"), seed + 1, config)
    assert set(one) == set(gen.TABLES) and set(two) == {"inventory", "item"}
    inv, item, dates = (_read(one[t]) for t in gen.TABLES)
    assert (inv.num_rows, item.num_rows, dates.num_rows) == \
        (20 * 150 * 5, 300, 73_049)
    assert len(one["inventory"]) == config["generator_params"]["fact_files"]
    assert inv.column_names == ["inv_date_sk", "inv_item_sk",
                                "inv_quantity_on_hand"]
    assert {f.type for f in inv.schema} == {pa.int64()}
    for table in two:  # the same seed, the same tables, whichever are written
        assert _read(two[table]).equals(_read(one[table])), table
    assert not _read(other["inventory"]).equals(inv)
    assert not _read(other["item"]).equals(item)
    # every surrogate key of item is referenced once its weeks have passed
    full = _read(gen.generate(str(tmp_path / "c"), seed, _tiny(),
                              ("inventory",))["inventory"])
    assert full.num_rows == 261 * 150 * 5
    assert set(full["inv_item_sk"].to_pylist()) == set(range(1, 301))


def test_item_names_are_formed_as_the_configuration_says(tmp_path):
    gen = REGISTRY.module("generators", "tpcds_inventory")
    item = _read(gen.generate(str(tmp_path), 9, _tiny(item=6000),
                              ("item",))["item"])
    assert gen.product_names(pa.array([1, 10, 123]).to_numpy()) == \
        ["ought", "barought", "priableought"]
    names = item["i_product_name"].drop_null().to_pylist()
    assert len(set(names)) == len(names)  # unique an item
    assert set(item["i_category"].drop_null().to_pylist()) == set(gen.CATEGORIES)
    assert max(len(v) for c in item.column_names[1:]
               for v in item[c].drop_null().to_pylist()) <= 50  # char(50)
    for c in item.column_names[1:]:  # NULLs at the small stated rate
        assert 0 < item[c].null_count < 0.02 * item.num_rows, c
    # classes within categories, brands within classes
    by_class = item.group_by(["i_category", "i_class"]).aggregate([])
    assert by_class.num_rows <= len(gen.CATEGORIES) * \
        (gen.CLASSES_PER_CATEGORY + 1) + len(gen.CLASS_WORDS) + 1
    brands = item.group_by(["i_category", "i_class", "i_brand"]).aggregate([])
    assert brands.num_rows > by_class.num_rows


def test_one_year_of_the_five_passes_the_month_filter(tmp_path):
    gen = REGISTRY.module("generators", "tpcds_inventory")
    paths = gen.generate(str(tmp_path), 4, _tiny(item=30))
    dates, inv = _read(paths["date_dim"]), _read(paths["inventory"])
    import pyarrow.compute as pc

    year = dates.filter(pc.and_(pc.greater_equal(dates["d_month_seq"], 1200),
                                pc.less_equal(dates["d_month_seq"], 1211)))
    assert year.num_rows == 366  # the year 2000
    assert year["d_date_sk"][0].as_py() == 2415022 + 36523  # 2000-01-01
    snapshots = set(inv["inv_date_sk"].to_pylist())
    assert len(snapshots) == 261
    assert len(snapshots & set(year["d_date_sk"].to_pylist())) == 52


# -- the class and its reference ----------------------------------------------


def test_q22_reference_against_a_case_worked_by_hand():
    q22 = REGISTRY.module("queries", "q22")
    item = pa.table({
        "i_item_sk": pa.array([1, 2, 3], pa.int64()),
        "i_product_name": ["p1", "p2", None],
        "i_brand": ["b", "b", "b"], "i_class": ["c", None, "c"],
        "i_category": ["k", "k", "k"]})
    dates = pa.table({"d_date_sk": pa.array([10, 11, 12], pa.int64()),
                      "d_month_seq": pa.array([1200, 1211, 1212], pa.int64())})
    inv = pa.table({
        "inv_date_sk": pa.array([10, 10, 11, 11, 12, 10], pa.int64()),
        "inv_item_sk": pa.array([1, 2, 1, 3, 1, 2], pa.int64()),
        "inv_quantity_on_hand": pa.array([4, 10, 7, None, 1000, 5], pa.int64())})
    got = q22.reference({"inventory": inv, "date_dim": dates, "item": item},
                        limit=None)
    rows = list(zip(*(got[c].to_pylist() for c in q22.ENGINE_COLUMNS)))
    # item 1: 4, 7 -> 5.5; item 2: 10, 5 -> 7.5; item 3: one NULL quantity ->
    # NULL (first); day 12 is outside the year; the grand total 26 / 4
    none = (None, None, None, None)
    assert rows[:4] == [none + (None,), (None, "b", None, None, None),
                        (None, "b", "c", None, None), (None, "b", "c", "k", None)]
    assert rows[4:8] == [("p1", None, None, None, 5.5), ("p1", "b", None, None, 5.5),
                         ("p1", "b", "c", None, 5.5), ("p1", "b", "c", "k", 5.5)]
    assert rows[8] == none + (6.5,)
    # item 2's NULL class in the data and the rolled-up NULLs: equal rows
    assert rows[9:] == [("p2", None, None, None, 7.5), ("p2", "b", None, None, 7.5),
                        ("p2", "b", None, None, 7.5), ("p2", "b", None, "k", 7.5)]
    assert q22.reference({"inventory": inv, "date_dim": dates, "item": item},
                         limit=3).num_rows == 3


@pytest.mark.parametrize("items,weeks,batch_size,limit,float64", [
    (300, 261, 8192, None, True),   # the whole rollup, no LIMIT
    (300, 261, 8192, 100, True),    # the first 100 rows
    (60, 120, 1024, None, True),    # small batches: every stream in many
    (300, 261, 8192, 100, False),   # as on the v5e: DOUBLE is the host's
])
def test_q22_class_equals_its_reference_on_the_chips_plan(
        tmp_path, monkeypatch, items, weeks, batch_size, limit, float64):
    """The plan the chip runs (no radix table, no fused filter), exactly:
    every row of the rollup in order, NULLs in the names, and the counters
    the configuration holds a run to. Without float64 arithmetic on the
    device (`supports_f64()` False, as on the v5e) AVG's sum and count stay
    int64 there, the division happens on the host and the top 100 orders a
    host DOUBLE beside the names' ranks."""
    import dataclasses

    from blaze_tpu.utils import device

    if not float64:
        monkeypatch.setattr(device, "_supports_f64_on", lambda platform: False)

    from blaze_tpu.config import get_config
    from blaze_tpu.ops.joins.bhj import clear_build_cache
    from blaze_tpu.runtime.session import Session

    q22 = REGISTRY.module("queries", "q22")
    gen = REGISTRY.module("generators", "tpcds_inventory")
    paths = gen.generate(str(tmp_path), 2**31 + 7,
                         _tiny(item=items, inventory_weeks=weeks), q22.TABLES)
    data = plans.Dataset(paths, CONFIG["scan_partitions"],
                         CONFIG["shuffle_partitions"])
    want = q22.reference({t: data.table(t) for t in q22.TABLES}, limit=limit)
    assert want["i_product_name"].null_count > 0
    session = Session(conf=dataclasses.replace(
        get_config(), radix_agg=False, fused_filter_agg=False,
        batch_size=batch_size, **CONFIG["session"]["conf"]))
    try:
        got = session.execute_to_table(q22.plan(data, limit=limit))
        counters = session.metrics.totals(tuple(CONFIG["counters_must"]))
    finally:
        session.close()
        clear_build_cache()
    assert plans.rows_of(got, q22.ENGINE_COLUMNS, True) == \
        plans.rows_of(want, q22.REFERENCE_COLUMNS, True)
    assert got.schema.field("qoh").type == pa.float64()
    for name, (lo, hi) in CONFIG["counters_must"].items():
        assert counters[name] >= (lo or 0), name
        assert hi is None or counters[name] <= hi, name
    if limit is None:  # four sets keep the product name, and the grand total
        assert got.num_rows == want.num_rows > 100
        assert got.num_rows % 4 == 1


def test_q22_ends_a_program_without_the_counters_before_anything_compiles(
        monkeypatch):
    from blaze_tpu.runtime import metrics

    q22 = REGISTRY.module("queries", "q22")
    monkeypatch.setattr(metrics, "TRIPWIRE_METRICS", tuple(
        m for m in metrics.TRIPWIRE_METRICS if m not in q22.COUNTERS))
    # `run.main` turns an ImportError into its FAIL line and exit code 1;
    # the reference is asked first, the plan after it
    with pytest.raises(ImportError, match="coded_key_batches.*host_key_batches"):
        q22.reference(None)
    with pytest.raises(ImportError, match="rollup_rows"):
        q22.plan(None)


# -- the readers ---------------------------------------------------------------

LAUNCHES = [
    (10, 3, "jit_expand_literal(111)"), (20, 5, "jit_agg_partial(222)"),
    (30, 400, "jit_bhj_inner_fast(666)"), (500, 2, "jit_agg_merge(333)"),
    (600, 9, "jit__concat_gather(777)"), (1500, 50, "jit_agg_partial(222)"),
    (2010, 12, "jit_agg_dense_partial(444)"),
]


def test_rollup_device_s_sums_expands_and_the_aggregations_programs():
    ctx = _ctx(_trace(LAUNCHES, QUERIES), [("q22", {}), ("q22", {})])
    # medians of (0.010, 0.012): the join and the movers are not the rollup's
    assert _reader("rollup_device_s")(ctx) == pytest.approx(0.011)
    parent = _ctx(_trace([(10, 500, "jit_bhj_inner_fast(666)")], QUERIES),
                  [("q22", {}), ("q22", {})])
    assert _reader("rollup_device_s")(parent) is None
    assert _reader("rollup_roofline_share")(parent) is None


def test_rollup_programs_name_jitted_functions_that_exist():
    import fnmatch

    from blaze_tpu.ops import agg_device, basic

    module = REGISTRY.module("readers", "rollup_roofline_share")
    source = open(agg_device.__file__).read()
    names = {"expand_literal": basic.expand_literal}
    names.update({n: None for n in ("agg_partial", "agg_merge",
                                    "agg_dense_partial", "agg_passthrough")
                  if f"def {n}(" in source})
    assert len(names) == 5
    for pattern in module.programs():
        assert any(fnmatch.fnmatchcase(f"jit({n})", pattern) for n in names), \
            pattern


def test_rollup_bytes_against_a_case_worked_by_hand():
    module = REGISTRY.module("readers", "rollup_roofline_share")
    rollup = {"keys": 4, "sets": 5, "measures": 1}
    # 1,000 joined rows, 5,000 out. Read once: four code planes (4 + 1 bytes
    # a row) and the measure (8 + 1): 1,000 x 29. Written once a row out:
    # four coded keys, the grouping id and the measure: 5,000 x (20 + 18)
    assert module.rollup_bytes(5000, rollup) == 29_000 + 190_000
    assert module.rollup_bytes(10, {"keys": 1, "sets": 2, "measures": 1}) == \
        5 * 14 + 10 * 23


def test_rollup_roofline_share_is_bytes_over_bandwidth_over_device_time():
    q22 = REGISTRY.module("queries", "q22")
    counters = {"rollup_rows": 11_700_000, "coded_key_batches": 101}
    ctx = _ctx(_trace(LAUNCHES, QUERIES), [("q22", counters)] * 2,
               {"q22": q22})
    nbytes = 2_340_000 * 29 + 11_700_000 * 38
    want = [100 * nbytes / 819e9 / s for s in (0.010, 0.012)]
    assert _reader("rollup_roofline_share")(ctx) == pytest.approx(sum(want) / 2)
    assert 0 < _reader("rollup_roofline_share")(ctx) < 100
    # a class without a rollup, or a program without the counter
    assert _reader("rollup_roofline_share")(_ctx(
        _trace(LAUNCHES, QUERIES), [("q22", counters)] * 2,
        {"q22": types.SimpleNamespace()})) is None
    assert _reader("rollup_roofline_share")(_ctx(
        _trace(LAUNCHES, QUERIES), [("q22", {})] * 2, {"q22": q22})) is None


def test_row_move_device_s_reads_q22s_movers_and_nothing_else():
    """The launches of a q22 query: the movers `row_move_device_s` lists,
    beside the join's and the rollup's programs it must not count."""
    launches = LAUNCHES + [(2100, 4, "jit__dyn_slice(778)"),
                           (2200, 6, "jit_sort_take(779)")]
    ctx = _ctx(_trace(launches, QUERIES), [("q22", {}), ("q22", {})])
    # the medians of 0.009 (`_concat_gather`) and 0.010 (`_dyn_slice`,
    # `sort_take`)
    assert _reader("row_move_device_s")(ctx) == pytest.approx(0.0095)


def test_coded_key_batches_reads_the_counter_or_nothing():
    trace = _trace([], QUERIES)
    counted = _ctx(trace, [("q22", {"coded_key_batches": 101})] * 3)
    assert _reader("coded_key_batches")(counted) == 101
    assert _reader("coded_key_batches")(_ctx(trace, [("q01", {})])) is None


def test_dict_host_s_sums_the_dictionary_spans(monkeypatch):
    spans = [sp.Span(100.0, 100.25, "dict:rank", 1, {}),
             sp.Span(100.5, 101.0, "dict:hash", 2, {}),
             sp.Span(100.6, 100.7, "op:ExpandExec", 2, {}),
             sp.Span(300.0, 301.0, "dict:unify", 1, {})]  # another query's
    monkeypatch.setattr(sp, "load", lambda: spans)
    run = helpers.load_run()
    ctx = types.SimpleNamespace(records=[
        types.SimpleNamespace(t0=99.0, seconds=5.0)])
    ctx.per_query = lambda value: run.ReadContext.per_query(ctx, value)
    assert _reader("dict_host_s")(ctx) == pytest.approx(0.75)
    monkeypatch.setattr(sp, "load", lambda: spans[2:3])
    assert _reader("dict_host_s")(ctx) is None  # a program without them


# -- the manifest ----------------------------------------------------------------

ACCEPTED_CONFIGS = ["tpcds_sf1_chip1", "tpcds_sf1_smj_chip1",
                    "tpcds_sf1_window_chip1"]
ACCEPTED_CELLS = ["q01_scan_topk", "q06_bhj_agg", "q67_agg_rank",
                  "q47_sort_rank", "q29_smj_facts", "q51_cume_window"]
ACCEPTED_METRICS = [
    "device_idle_share", "peak_hbm_gb", "device_busy_s", "hbm_roofline_share",
    "device_launches", "h2d_mb", "d2h_mb", "scan_self_s", "agg_self_s",
    "join_self_s", "sortwin_self_s", "exchange_self_s", "compiles_in_window",
    "uncached_compiles", "decode_wait_s", "decode_mrows_s", "stage_h2d_s",
    "d2h_s", "device_wait_s", "sync_points", "agg_host_s", "join_host_s",
    "exchange_host_s", "agg_dense_batches", "smj_self_s", "smj_host_s",
    "smj_device_s", "smj_roofline_share", "sort_device_s", "smj_device_joins",
    "window_device_s", "window_roofline_share", "window_self_s",
    "window_host_s", "window_device_batches", "row_move_device_s"]
# the accepted metrics' lists as PR 35 found them (a metric not here has none:
# every cell), and the cell that PR 39 appended to three of them
ACCEPTED_LISTS = {
    "join_self_s": ["q06_bhj_agg", "q47_sort_rank"],
    "join_host_s": ["q06_bhj_agg", "q47_sort_rank"],
    "sort_device_s": ["q29_smj_facts", "q47_sort_rank", "q51_cume_window"],
    "row_move_device_s": ["q01_scan_topk", "q47_sort_rank", "q67_agg_rank",
                          "q29_smj_facts", "q51_cume_window"],
    **{name: ["q29_smj_facts"] for name in (
        "smj_self_s", "smj_host_s", "smj_device_s", "smj_roofline_share",
        "smj_device_joins")},
    **{name: ["q51_cume_window"] for name in (
        "window_device_s", "window_roofline_share", "window_self_s",
        "window_host_s", "window_device_batches")}}
GAINED_BY_PR_39 = ("join_self_s", "join_host_s", "row_move_device_s")


def _kept_in_order(names, accepted):
    """The accepted names, all there and in the order they had (whatever
    later PRs appended around this one's)."""
    return [n for n in names if n in accepted] == accepted


def test_the_manifest_gained_entries_and_files_beside_the_others():
    with open(helpers.MANIFEST) as f:
        manifest = json.load(f)
    configs = {c["name"]: c for c in manifest["configs"]}
    cells = {w["name"]: w for w in manifest["workloads"]}
    metrics = {m["name"]: m for m in manifest["per_layer"]}
    assert _kept_in_order(list(configs), ACCEPTED_CONFIGS)
    assert _kept_in_order(list(cells), ACCEPTED_CELLS)
    assert _kept_in_order(list(metrics), ACCEPTED_METRICS)
    assert manifest["run_seconds"] == 51
    bounds = {m["name"]: m["bound"] for m in manifest["end_to_end"]}
    assert (bounds["query_s"], bounds["setup_s"]) == (0.08, 0.25)
    config, cell = configs[CONFIG_NAME], cells[CELL]
    assert config["reduced"] == ["scale_factor"] and config["source"] == CONFIG["source"]
    assert cell == {"name": CELL, "config": CONFIG_NAME, "traffic": "q22_repeat",
                    "chips": 1, "why": cell["why"]}
    assert "48,000 groups" in cell["why"]  # what the generator yields
    assert CONFIG["chips"] == 1  # the cell and its configuration: one chip
    for name in NEW_METRICS:
        assert metrics[name]["workloads"] == [CELL]
        assert metrics[name]["moves"] == "query_s"
    # the lists the accepted metrics keep: those PR 35 found, whatever later
    # PRs appended to them
    for name in ACCEPTED_METRICS:
        listed = metrics[name].get("workloads")
        if name in ACCEPTED_LISTS:
            assert _kept_in_order(listed, ACCEPTED_LISTS[name]), name
        else:
            assert listed is None, name
    for name in GAINED_BY_PR_39:
        assert CELL in metrics[name]["workloads"], name
    loaded = M.Manifest(helpers.MANIFEST)
    registry = Registry(loaded.paths)
    assert M.problems(loaded, registry.find) == []
    for name in (*NEW_METRICS, "row_move_device_s"):
        assert callable(registry.reader(name))
    traffic = REGISTRY.data("traffic", "q22_repeat")
    assert traffic == {"loop": "closed", "warmup_queries": 1, "traced_queries": 3,
                       "classes": [{"query": "q22", "weight": 1,
                                    "params": {"dms": 1200}}]}
    assert CONFIG["counters_must"]["host_key_batches"] == [0, 0]
    assert CONFIG["counters_must"]["coded_key_batches"] == [1, None]
    assert set(CONFIG["session"]["conf"]) == {"advisory_partition_bytes"}


# -- the cell ------------------------------------------------------------------


def test_traced_rehearsal_of_q22_keeps_the_names_as_codes(tmp_path, capsys):
    metrics, readings = _traced_rehearsal(CELL, tmp_path, capsys)
    counters = readings["counters_last_query"]
    assert counters["coded_key_batches"] >= 1
    assert counters["host_key_batches"] == 0
    assert counters["agg_reintern_rows"] == 0
    assert counters["join_generic_batches"] == 0
    assert counters["device_inner_batches"] >= 2
    # five rows out of Expand a joined row: 52 of the 261 snapshots pass
    assert counters["rollup_rows"] == 5 * 52 * 150 * 5
    assert metrics["coded_key_batches"]["value"] == counters["coded_key_batches"]
    # Expand waits for nothing: its host time is its self time (two clocks)
    assert 0 <= metrics["expand_host_s"]["value"] <= \
        metrics["expand_self_s"]["value"] * 1.001
    assert metrics["dict_host_s"]["value"] > 0
    assert metrics["agg_self_s"]["value"] > 0
    # read from a device trace: none on the CPU (the synthetic trace above
    # pins them, and `row_move_device_s` below)
    assert not {"rollup_device_s", "rollup_roofline_share",
                "row_move_device_s"} & set(metrics)
    # the two broadcast joins, in the lists PR 39 appended q22 to
    for name in ("join_self_s", "join_host_s"):
        assert isinstance(metrics[name]["value"], (int, float)), name
    assert 0 <= metrics["join_host_s"]["value"] <= \
        metrics["join_self_s"]["value"] * 1.001


def test_the_other_cells_do_not_report_the_rollups_metrics(tmp_path, capsys):
    metrics, _readings = _traced_rehearsal("q06_bhj_agg", tmp_path, capsys)
    assert not set(NEW_METRICS) & set(metrics)
    assert metrics["join_self_s"]["value"] > 0
