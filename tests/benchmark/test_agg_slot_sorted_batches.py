"""`agg_slot_sorted_batches`, the per-layer metric that says how many of a
traced query's slot-table batches reduced by one sort of the packed slot id
(`jit(agg_dense_partial)` past its crossover): the reader on synthetic
counters, and a traced rehearsal of the two cells that list it, on the plan
the chip runs (see `test_host_span_metrics`)."""

import json

import pytest

from tests.benchmark import helpers
from tests.benchmark.test_host_span_metrics import (
    _MANIFEST, _as_on_the_chip, _ctx, _reader)

(CELLS,) = [m["workloads"] for m in _MANIFEST["per_layer"]
            if m["name"] == "agg_slot_sorted_batches"]


def test_agg_slot_sorted_batches_reads_the_counter_or_nothing():
    read = _reader("agg_slot_sorted_batches")
    counted = _ctx(
        (10.0, 5.0, {"agg_dense_batches": 24, "agg_slot_sorted_batches": 24}),
        (20.0, 5.0, {"agg_dense_batches": 24, "agg_slot_sorted_batches": 24}),
        (30.0, 5.0, {"agg_dense_batches": 24, "agg_slot_sorted_batches": 0}))
    assert read(counted) == 24
    # every table under the crossover is a reading, of 0
    assert read(_ctx((10.0, 5.0, {"agg_dense_batches": 4,
                                  "agg_slot_sorted_batches": 0}))) == 0
    # the parent counts slot-table batches and not their form: nothing to
    # read, and no error
    assert read(_ctx((10.0, 5.0, {"agg_dense_batches": 24,
                                  "agg_sort_batches": 0}))) is None


@pytest.mark.parametrize("cell", CELLS)
def test_traced_rehearsal_reports_agg_slot_sorted_batches(cell, tmp_path,
                                                          capsys):
    path = helpers.tiny_manifest(tmp_path, _as_on_the_chip)
    rc, lines = helpers.run_cell(capsys, path, cell, trace=1)
    assert rc == 0, lines
    metrics = json.loads(lines[-1])["metrics"]
    sorted_batches = metrics["agg_slot_sorted_batches"]["value"]
    assert 0 <= sorted_batches <= metrics["agg_dense_batches"]["value"]
    if cell == "q51_cume_window":
        # its closing aggregation groups by an item key of 2,048 slots at
        # any size; q47's 16,384 slots pass these tiny batches' capacity, so
        # the CPU's radix plan takes them and keeps its table
        assert sorted_batches >= 1
