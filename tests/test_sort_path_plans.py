"""q67's and q29's plans as the chip runs them, end to end on the CPU.

On the CPU ``radix_agg`` defaults on and the aggregation goes through the
radix slot table, so tier-1 otherwise never runs the chip's aggregation
under a whole query (ROADMAP.md D12). Here it is off, as on the chip, over a
cut-down ``tpcds_star`` data set whose (item, store) range passes
``dense_agg_max_buckets`` as SF1's does: every PARTIAL batch reduces by one
sort of its packed slot id (``jit(agg_dense_partial)``, slot-sorted), or with
``dense_agg`` off by the sort kernel (``jit(agg_partial)``); the FINAL side
merges on the device by one sort of its packed key id
(``jit(agg_merge_sorted)``), and the answer is Acero's."""

import dataclasses

import pytest

from tests.benchmark import helpers

helpers.load_run()  # puts the benchmark's directory on sys.path
from benchlib import plans  # noqa: E402
from benchlib.registry import Registry  # noqa: E402

# item x store -> 2,048 x 16 slots, more than a batch's capacity holds
ROWS = {"store_sales": 20_000, "store_returns": 3_000, "item": 2_000,
        "store": 12, "customer": 1_000}
# the configuration each cell runs under (benchmark/configs/)
CONFIGS = {"q67": "tpcds_sf1_chip1", "q29": "tpcds_sf1_smj_chip1"}


@pytest.fixture(scope="module")
def registry():
    return Registry([helpers.BENCH_DIR])


@pytest.fixture(scope="module")
def star(registry, tmp_path_factory):
    config = registry.data("configs", "tpcds_sf1_chip1")
    config["generator_params"]["table_rows"] = ROWS
    generator = registry.module("generators", config["generator"])
    paths = generator.generate(str(tmp_path_factory.mktemp("star")), 7, config)
    return plans.Dataset(paths, config["scan_partitions"],
                         config["shuffle_partitions"])


@pytest.mark.parametrize("dense_agg", [None, False])
@pytest.mark.parametrize("query", sorted(CONFIGS))
def test_the_chips_plan_takes_the_sort_path_and_answers_as_acero(
        query, dense_agg, registry, star):
    from blaze_tpu.config import get_config
    from blaze_tpu.ops.joins.bhj import clear_build_cache
    from blaze_tpu.runtime.session import Session
    from blaze_tpu.utils.device import DEVICE_STATS

    cls = registry.module("queries", query)
    overrides = registry.data("configs", CONFIGS[query])["session"]["conf"]
    conf = dataclasses.replace(get_config(), radix_agg=False,
                               fused_filter_agg=False, dense_agg=dense_agg,
                               **overrides)
    want = plans.rows_of(cls.reference({t: star.table(t) for t in cls.TABLES}),
                         cls.REFERENCE_COLUMNS, cls.ORDERED)
    session = Session(conf=conf)
    try:
        before = DEVICE_STATS.snapshot()
        got = session.execute_to_table(cls.plan(star))
        after = DEVICE_STATS.snapshot()
        merged = session.metrics.totals(("device_merge_batches",))
    finally:
        session.close()
        clear_build_cache()
    assert plans.rows_of(got, cls.ENGINE_COLUMNS, cls.ORDERED) == want
    assert len(want) > 100 or query == "q29"  # q29 keeps the first 100 groups
    delta = {k: after[k] - before[k] for k in (
        "agg_sort_batches", "agg_dense_batches", "agg_slot_sorted_batches",
        "merge_slot_sorted_batches")}
    if dense_agg is None:
        assert delta["agg_slot_sorted_batches"] == \
            delta["agg_dense_batches"] > 0
        assert delta["agg_sort_batches"] == 0
    else:
        assert delta["agg_sort_batches"] > 0
        assert delta["agg_dense_batches"] == 0
    assert merged["device_merge_batches"] >= 1
    # the FINAL merge groups by integers: one sort of their packed id
    assert delta["merge_slot_sorted_batches"] >= 1
