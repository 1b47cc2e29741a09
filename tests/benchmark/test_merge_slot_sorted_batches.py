"""`merge_slot_sorted_batches`, the per-layer metric that says how many of a
traced query's FINAL / PARTIAL_MERGE merges reduced by one sort of the
packed key id (`jit(agg_merge_sorted)`): the reader on synthetic counters,
the cells the metric lists, and a traced rehearsal of each on the plan the
chip runs (see `test_host_span_metrics`), with no radix table, as there."""

import json

import pytest

from tests.benchmark import helpers
from tests.benchmark.test_host_span_metrics import (
    _MANIFEST, _as_on_the_chip, _ctx, _reader)

(METRIC,) = [m for m in _MANIFEST["per_layer"]
             if m["name"] == "merge_slot_sorted_batches"]


def test_merge_slot_sorted_batches_reads_the_counter_or_nothing():
    read = _reader("merge_slot_sorted_batches")
    counted = _ctx(
        (10.0, 5.0, {"sync_calls": 40, "merge_slot_sorted_batches": 6}),
        (20.0, 5.0, {"sync_calls": 40, "merge_slot_sorted_batches": 6}),
        (30.0, 5.0, {"sync_calls": 40, "merge_slot_sorted_batches": 0}))
    assert read(counted) == 6
    # merges that all kept the sort path are a reading, of 0
    assert read(_ctx((10.0, 5.0, {"merge_slot_sorted_batches": 0}))) == 0
    # a program without the counter: nothing to read, and no error
    assert read(_ctx((10.0, 5.0, {"agg_dense_batches": 24,
                                  "agg_slot_sorted_batches": 24}))) is None


def test_the_metric_lists_the_cells_whose_merges_group_by_integers():
    assert METRIC["workloads"] == ["q51_cume_window", "q67_agg_rank",
                                   "q22_inv_rollup", "q67_agg_rank_mesh4"]
    assert (METRIC["layer"], METRIC["moves"], METRIC["better"]) == \
        ("operators", "query_s", "higher")


def _as_on_the_chip_without_radix(manifest, tmp_path):
    _as_on_the_chip(manifest, tmp_path)
    for entry in manifest["configs"]:
        path = tmp_path / entry["file"]
        config = json.loads(path.read_text())
        config["session"]["conf"]["radix_agg"] = False
        path.write_text(json.dumps(config))


@pytest.mark.parametrize("cell", METRIC["workloads"])
def test_traced_rehearsal_reports_merge_slot_sorted_batches(cell, tmp_path,
                                                            capsys):
    path = helpers.tiny_manifest(tmp_path, _as_on_the_chip_without_radix)
    rc, lines = helpers.run_cell(capsys, path, cell, trace=1)
    assert rc == 0, lines
    metrics = json.loads(lines[-1])["metrics"]
    assert metrics["merge_slot_sorted_batches"]["value"] >= 1
