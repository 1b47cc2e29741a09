"""`agg_dense_batches`, the per-layer metric that says how many PARTIAL
aggregation batches of a traced query the slot-table kernel answered: the
reader on synthetic counters, and a traced rehearsal of each cell on the plan
the chip runs (see `test_host_span_metrics`)."""

import json

import pytest

from tests.benchmark import helpers
from tests.benchmark.test_host_span_metrics import (
    CELLS, _as_on_the_chip, _ctx, _reader)


def test_agg_dense_batches_reads_the_counter_or_nothing():
    counted = _ctx((10.0, 5.0, {"agg_dense_batches": 4, "agg_sort_batches": 0}),
                   (20.0, 5.0, {"agg_dense_batches": 4, "agg_sort_batches": 0}),
                   (30.0, 5.0, {"agg_dense_batches": 0, "agg_sort_batches": 4}))
    assert _reader("agg_dense_batches")(counted) == 4
    # every batch through the sort kernel is a reading, of 0
    assert _reader("agg_dense_batches")(
        _ctx((10.0, 5.0, {"agg_dense_batches": 0, "agg_sort_batches": 24}))) == 0
    # the parent counts syncs and no batches: nothing to read, and no error
    assert _reader("agg_dense_batches")(_ctx((10.0, 5.0, {"sync_calls": 7}))) is None


@pytest.mark.parametrize("cell", CELLS)
def test_traced_rehearsal_reports_agg_dense_batches(cell, tmp_path, capsys):
    path = helpers.tiny_manifest(tmp_path, _as_on_the_chip)
    rc, lines = helpers.run_cell(capsys, path, cell, trace=1)
    assert rc == 0, lines
    metrics = json.loads(lines[-1])["metrics"]
    # q01 and q06 group by a handful of integers: every task's PARTIAL
    # batches go through the slot table (q67's too at these rows, by the
    # radix plan the CPU allows itself)
    assert metrics["agg_dense_batches"]["value"] >= 4
