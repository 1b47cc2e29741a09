"""`row_move_device_s` (PR 32): the device seconds a query spends in the
programs that only move rows between operators. The reader is data over
`program_device_s`; each of its patterns has to name a jitted function that
exists, so that a rename cannot silence the metric."""

import json
import os
import re

import pytest

from tests.benchmark import helpers
from tests.benchmark.test_host_span_metrics import _reader
from tests.benchmark.test_smj_cell import _ctx, _trace

helpers.load_run()  # puts the benchmark's directory on sys.path
from benchlib import manifest as M  # noqa: E402
from benchlib.registry import Registry  # noqa: E402

READER_FILE = os.path.join(helpers.BENCH_DIR, "readers",
                           "row_move_device_s.json")


def _programs():
    with open(READER_FILE) as f:
        spec = json.load(f)
    assert spec["reader"] == "program_device_s"
    assert set(spec["params"]) == {"programs"}
    return spec["params"]["programs"]


def test_the_manifest_lists_the_metric_for_the_cells_that_move_rows():
    m = M.Manifest(helpers.MANIFEST)
    assert M.problems(m, Registry(m.paths).find) == []
    (entry,) = [e for e in m.data["per_layer"]
                if e["name"] == "row_move_device_s"]
    assert entry == {
        "name": "row_move_device_s", "unit": "s", "better": "lower",
        "source": "device_trace", "layer": "kernels", "moves": "query_s",
        "workloads": ["q01_scan_topk", "q47_sort_rank", "q67_agg_rank",
                      "q29_smj_facts", "q51_cume_window", "q22_inv_rollup"]}
    assert callable(Registry(m.paths).reader("row_move_device_s"))


@pytest.mark.parametrize("pattern", _programs())
def test_every_pattern_names_a_jitted_function_that_exists(pattern):
    import jax

    from blaze_tpu.core import kernels
    from blaze_tpu.ops import sort

    name = re.fullmatch(r"jit\((\w+)\)", pattern).group(1)
    owners = [mod for mod in (kernels, sort) if hasattr(mod, name)]
    assert owners, f"{pattern}: no such function in core/kernels.py or ops/sort.py"
    fn = getattr(owners[0], name)
    # a jitted function: it lowers, and the trace names its launch after it
    assert isinstance(fn, type(jax.jit(lambda: 0))) and fn.__name__ == name


def test_row_move_device_s_sums_the_movers_inside_each_query():
    launches = [
        # query 0: five movers and two programs that compute
        (10, 40, "jit__concat_gather(1)"), (60, 4, "jit__dyn_slice(2)"),
        (70, 6, "jit_sort_take(3)"), (80, 2, "jit__compact(4)"),
        (90, 1, "jit__gather_n(5)"), (100, 500, "jit_agg_merge(6)"),
        (650, 9, "jit_sort_order(7)"),
        # between the queries: nobody's
        (1500, 100, "jit__concat_gather(1)"),
        # query 1
        (2010, 20, "jit__concat_gather(1)"), (2100, 3, "jit__gather(8)"),
    ]
    queries = [(0, 1000), (2000, 1000)]
    ctx = _ctx(_trace(launches, queries), [("q51", {}), ("q51", {})])
    # the median of 0.053 and 0.023
    assert _reader("row_move_device_s")(ctx) == pytest.approx(0.038)
    # a query that moves no rows through these programs: nothing to read
    none = _ctx(_trace([(10, 500, "jit_bhj_inner_fast(9)")], queries[:1]),
                [("q06", {})])
    assert _reader("row_move_device_s")(none) is None
