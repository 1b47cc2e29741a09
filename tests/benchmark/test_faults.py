"""`correct` comes out false when the timed path is broken underneath: the
rest of a run driven as it is (the CPU let through), with
`Session.execute_to_table` altered after the warm-up has passed. The faults a
query cell can have: an answer altered where it is produced, and part of the
rows left out of it."""

import json

import pyarrow as pa
import pyarrow.compute as pc
import pytest

from tests.benchmark import helpers


def _one_value_altered(table):
    """The first column's first value (a key of the answer), one more."""
    keys = table.column(0)
    first = pc.add(keys.slice(0, 1), pa.scalar(1, pa.int64())).cast(keys.type)
    column = pa.chunked_array([first.combine_chunks(),
                               keys.slice(1).combine_chunks()], type=keys.type)
    return table.set_column(0, table.schema.field(0), column)


def _half_left_out(table):
    return table.slice(0, table.num_rows // 2)


def _break(monkeypatch, fault, is_hit):
    """`Session.execute_to_table` answers through ``fault`` on the calls
    (counted from 1) that ``is_hit`` picks; returns the list of calls."""
    from blaze_tpu.runtime.session import Session

    real, calls = Session.execute_to_table, []

    def broken(self, plan, *args, **kwargs):
        calls.append(plan)
        table = real(self, plan, *args, **kwargs)
        return fault(table) if is_hit(len(calls)) else table

    monkeypatch.setattr(Session, "execute_to_table", broken)
    return calls


@pytest.mark.parametrize("fault", [_one_value_altered, _half_left_out],
                         ids=lambda f: f.__name__.strip("_"))
def test_a_fault_in_the_timed_path_makes_the_run_incorrect(
        fault, tmp_path, capsys, monkeypatch):
    # the first call is the warm-up, which has to pass
    calls = _break(monkeypatch, fault, lambda n: n > 1)
    path = helpers.tiny_manifest(tmp_path)
    rc, lines = helpers.run_cell(capsys, path, "q67_agg_rank")
    # no query of the window answered rightly: the run ends without a result
    # line, which the driver reads as a failure too
    assert rc != 0 and len(calls) >= 2
    assert not any(line.startswith("{") for line in lines)


def test_one_wrong_answer_among_right_ones_is_counted(tmp_path, capsys, monkeypatch):
    _break(monkeypatch, _one_value_altered, lambda n: n == 3)
    path = helpers.tiny_manifest(tmp_path)
    rc, lines = helpers.run_cell(capsys, path, "q01_scan_topk")
    assert rc == 0, lines
    result = json.loads(lines[-1])
    assert result["correct"] is False and result["failed"] == 1
    assert list(result)[-1] == "compared"
    assert result["compared"]["answers_wrong"] == {"value": 1, "limit": 0}
    assert result["compared"]["counters_outside"] == {"value": 0, "limit": 0}
    assert result["compared"]["queries_compared"]["value"] == result["attempted"]
