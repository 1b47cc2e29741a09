"""`benchmark/sets.py`: the seeds of a set, what a shorter window would have
read from a run's queries, and the table's grouping of runs into sets. The
runs themselves need the chip and are not made here."""

import importlib.util
import os

import pytest

from tests.benchmark import helpers

helpers.load_run()  # puts benchmark/ on sys.path
_spec = importlib.util.spec_from_file_location(
    "_benchmark_sets", os.path.join(helpers.BENCH_DIR, "sets.py"))
sets = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(sets)


def _run(set_, run, query_s, seconds=(), starts=(), label="base", trace=0):
    return {"label": label, "cell": "q51_cume_window", "set": set_, "run": run,
            "trace": trace,
            "result": {"metrics": {"query_s": {"value": query_s},
                                   "setup_s": {"value": 40.0 + run}}},
            "readings": {"query_seconds": list(seconds),
                         "query_starts": list(starts)}}


def test_every_set_of_a_cell_has_the_same_large_seeds():
    a = sets.seeds_for("q51_cume_window", 2_000_000_000, 6)
    assert a == sets.seeds_for("q51_cume_window", 2_000_000_000, 6)
    assert len(set(a)) == 6 and min(a) > 2 ** 30 and max(a) < 2 ** 31 + 1000
    assert not set(a) & set(sets.seeds_for("q29_smj_facts", 2_000_000_000, 6))


def test_runs_are_grouped_by_set_in_order_and_traced_runs_left_out():
    records = [_run(1, 0, 1.9), _run(0, 0, 1.7), _run(0, 1, 1.8),
               _run(0, 2, 9.9, trace=1), _run(0, 0, 5.0, label="trial")]
    assert sets.metric_sets(records, "base", "q51_cume_window", "query_s") \
        == [[1.7, 1.8], [1.9]]
    assert sets.metric_sets(records, "base", "q51_cume_window", "setup_s") \
        == [[40.0, 41.0], [40.0]]
    assert sets.metric_sets(records, "trial", "q51_cume_window", "query_s") == [[5.0]]


def test_a_shorter_window_is_the_median_of_the_queries_started_inside_it():
    # five queries of 10 s each: a 30 s window starts the first three, and
    # the one in flight at its end counts whole
    run = _run(0, 0, 3.0, seconds=[5.0, 1.0, 2.0, 3.0, 4.0],
               starts=[0.0, 10.0, 20.0, 30.0, 40.0])
    assert sets.metric_sets([run], "base", "q51_cume_window", "query_s", 30) \
        == [[2.0]]
    assert sets.metric_sets([run], "base", "q51_cume_window", "query_s", 31) \
        == [[2.5]]


def test_the_report_names_the_bounds_window(capsys):
    records = [_run(k, i, v) for k, row in enumerate(
        ([1.00, 1.01, 1.02, 1.03, 1.04, 1.10], [1.00, 1.00, 1.02, 1.02, 1.04, 1.04]))
        for i, v in enumerate(row)]
    sets.report(records)
    out = capsys.readouterr().out
    assert "q51_cume_window" in out and "tight" in out and "loose" in out
    assert "query_s's bound may lie from" in out
    # a set of fewer than three runs has no quartiles: the row is left out
    sets.report(records[:2])
    assert "query_s's bound" not in capsys.readouterr().out
