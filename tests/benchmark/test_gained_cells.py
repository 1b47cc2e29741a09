"""The cells that per-layer metrics' lists gained (PR 34: `sort_device_s`
reads `q51_cume_window`, `join_self_s` and `join_host_s` read
`q47_sort_rank`; PR 39: those two and `row_move_device_s` read
`q22_inv_rollup`): each reader gives a number for its new cell in the
traced rehearsal of the chip's plan. A CPU trace has no `XLA Modules` line,
so for the metric that reads launches the rehearsal lays the program's own
`kernel:<fn>` spans (the enqueue of `jit(<fn>)`) in the launches' place:
what the reader then sums is a stand-in, and that it finds the cell's sorts
inside its queries is the point."""

import json
import os

import pytest

from tests.benchmark import helpers
from tests.benchmark.test_host_span_metrics import _as_on_the_chip

run = helpers.load_run()
from benchlib import manifest as M  # noqa: E402

GAINED = [("sort_device_s", "q51_cume_window"),
          ("join_self_s", "q47_sort_rank"),
          ("join_host_s", "q47_sort_rank"),
          ("join_self_s", "q22_inv_rollup"),
          ("join_host_s", "q22_inv_rollup"),
          ("row_move_device_s", "q22_inv_rollup")]


def _enqueues_as_launches(monkeypatch, metric):
    """Only the programs ``metric``'s reader names get a stand-in (another
    device metric of the cell would go on to ask for the CPU's peaks)."""
    with open(os.path.join(helpers.BENCH_DIR, "readers", metric + ".json")) as f:
        programs = json.load(f)["params"].get("programs", ())
    real = run.xplane.reduce

    def reduce(trace, queries, spans=(), top=10):
        trace.launches[0] = sorted(
            (start, end - start, f"jit_{name[len('kernel:'):]}(1)")
            for start, end, name in spans
            if f"jit({name[len('kernel:'):]})" in programs)
        return real(trace, queries, spans, top)

    monkeypatch.setattr(run.xplane, "reduce", reduce)


@pytest.mark.parametrize("metric,cell", GAINED)
def test_the_reader_gives_a_number_for_the_cell_it_gained(
        metric, cell, tmp_path, capsys, monkeypatch):
    (entry,) = [e for e in M.Manifest(helpers.MANIFEST).data["per_layer"]
                if e["name"] == metric]
    assert cell in entry["workloads"]
    _enqueues_as_launches(monkeypatch, metric)
    path = helpers.tiny_manifest(tmp_path, _as_on_the_chip)
    rc, lines = helpers.run_cell(capsys, path, cell, trace=1)
    assert rc == 0, lines
    result = json.loads(lines[-1])
    assert result["correct"] and result["failed"] == 0
    assert result["metrics"][metric]["value"] > 0
    assert result["metrics"][metric]["unit"] == entry["unit"]
