"""The seven per-layer metrics that read what PR 37 records: the thread's CPU
clock beside the wall clock on a task's run and on a transfer (`op_cpu_s`,
`op_offcpu_s`, `stage_cpu_s`), the enqueue spans summed and what they
enqueued (`enqueue_s`, `enqueue_operands`), the launches no enqueue span
timed (`unnamed_launches`) and the end of a query (`finish_s`). Synthetic
spans pin the new readers; a traced rehearsal of the chip's plan reports in
every cell the span metrics the manifest lists for it. `unnamed_launches`
needs the trace's `XLA Modules` line, which a CPU trace has not: its reader
is pinned on a synthetic reduction, and in a rehearsal it has nothing to
read."""

import json
import types

import pytest

from tests.benchmark import helpers
from tests.benchmark.test_host_span_metrics import (_as_on_the_chip, _ctx,
                                                    _reader, _span)

helpers.load_run()  # puts the benchmark's directory on sys.path
from benchlib import manifest as M  # noqa: E402
from benchlib import spans as sp  # noqa: E402
from benchlib.registry import Registry  # noqa: E402

with open(helpers.MANIFEST) as _f:
    CELLS = [w["name"] for w in json.load(_f)["workloads"]]
# a CPU sum is a count of the clock's ticks, 10 ms on the chip tool's host:
# registered where a query gives about thirty (PERF.md 5)
TICKS_ENOUGH = ["q06_bhj_agg", "q67_agg_rank", "q29_smj_facts",
                "q51_cume_window", "q22_inv_rollup"]
ADDED = {  # name: unit, source, layer, the cells it lists (None: every cell)
    "op_cpu_s": ("s", "program_span", "operators", TICKS_ENOUGH),
    "op_offcpu_s": ("s", "program_span", "operators", TICKS_ENOUGH),
    "enqueue_s": ("s", "program_span", "driver", None),
    "stage_cpu_s": ("s", "program_span", "driver", ["q22_inv_rollup"]),
    "unnamed_launches": ("count", "device_trace", "planner", None),
    "finish_s": ("s", "program_span", "driver", None),
    "enqueue_operands": ("count", "program_span", "driver", None)}


def test_the_manifest_has_the_seven_entries_and_each_has_a_reader():
    m = M.Manifest(helpers.MANIFEST)
    assert M.problems(m, Registry(m.paths).find) == []
    entries = {e["name"]: e for e in m.data["per_layer"]}
    for name, (unit, source, layer, cells) in ADDED.items():
        entry = entries[name]
        assert (entry["unit"], entry["source"], entry["layer"]) == (
            unit, source, layer), name
        assert entry["moves"] == "query_s" and entry["better"] == "lower"
        assert entry.get("workloads") == cells, name
        assert callable(Registry(m.paths).reader(name))


@pytest.mark.parametrize("cell", CELLS)
def test_traced_rehearsal_reports_the_cpu_span_metrics(cell, tmp_path, capsys):
    path = helpers.tiny_manifest(tmp_path, _as_on_the_chip)
    rc, lines = helpers.run_cell(capsys, path, cell, trace=1)
    assert rc == 0, lines
    result = json.loads(lines[-1])
    assert result["correct"] and result["failed"] == 0
    metrics = result["metrics"]
    listed = {name for name, (*_x, cells) in ADDED.items()
              if cells is None or cell in cells}
    # no `XLA Modules` line in a CPU trace: nothing to read, and no error
    assert set(ADDED) & set(metrics) == listed - {"unnamed_launches"}
    for name in set(ADDED) & set(metrics):
        assert isinstance(metrics[name]["value"], (int, float)), name
        assert metrics[name]["unit"] == ADDED[name][0]
        assert metrics[name]["value"] > 0 or name == "op_offcpu_s", name
    # a thread cannot use more CPU than the time it had: the operators' CPU
    # is inside their host seconds and the upload's inside its wall seconds
    # (a task's few steps outside any operator, and a stamp's own cost, are
    # the slack)
    slack = 5e-3
    if "op_offcpu_s" in metrics:
        assert metrics["op_offcpu_s"]["value"] >= -slack
    if "stage_cpu_s" in metrics:
        assert metrics["stage_cpu_s"]["value"] \
            <= metrics["stage_h2d_s"]["value"] + slack


# -- the readers, on synthetic spans -------------------------------------------


def _cpu(start, end, key, cpu_s, tid=1, **args):
    return _span(start, end, key, tid, cpu_us=cpu_s * 1e6, **args)


def test_operator_cpu_takes_the_copying_out_once_and_splits_host_seconds(
        monkeypatch):
    spans = [
        # thread 1: a task of 13 s, 8 s of them on the CPU: ten seconds of
        # AggExec and three of a join. Inside the first a 4 s sync (the
        # program does not stamp a wait: what CPU it used stays the
        # operators') with a 1 s pull nested in it that used 0.2 s, and a
        # 2 s upload that used 1.5 s with a second one nested in it (its
        # 0.3 s are in the 1.5 already): 6 s blocked and 4 + 3 s host,
        # 1.7 s of CPU in copies and 6.3 s the operators'
        _cpu(100.0, 113.0, "task:task", 8.0),
        _span(100.0, 110.0, "op:AggExec"),
        _span(101.0, 105.0, "sync:agg_partial"),
        _cpu(102.0, 103.0, "transfer:to_host", 0.2),
        _cpu(106.0, 108.0, "transfer:stage", 1.5),
        _cpu(106.5, 107.0, "transfer:stage", 0.3),
        _span(110.0, 113.0, "op:BroadcastJoinExec"),
        # thread 2: another task's copy over the same seconds is not taken
        # from thread 1's; its own 2 s task (1.2 s of CPU) waits for 0.5 s
        _cpu(100.0, 110.0, "transfer:stage", 3.0, tid=2),
        _cpu(110.0, 112.0, "task:task", 1.2, tid=2),
        _span(110.0, 112.0, "op:AggExec", tid=2),
        _span(110.5, 111.0, "scan:decode_wait", tid=2),
        # thread 3 runs no task: its upload's CPU is nobody's
        _cpu(100.0, 104.0, "transfer:stage", 4.0, tid=3),
        # the operator's lifetime span and an enqueue are not waits
        _span(100.0, 113.0, "operator:AggExec"),
        _span(100.5, 100.6, "kernel:agg_partial", operands=5),
        # the next query's
        _cpu(120.0, 121.0, "task:task", 0.25),
        _span(120.0, 121.0, "op:AggExec"),
    ]
    monkeypatch.setattr(sp, "load", lambda: spans)
    ctx = _ctx((100.0, 15.0, {}))
    cpu = (8.0 - 0.2 - 1.5) + 1.2
    host = (10.0 - 6.0) + 3.0 + (2.0 - 0.5)
    assert _reader("op_cpu_s")(ctx) == pytest.approx(cpu)
    assert _reader("op_offcpu_s")(ctx) == pytest.approx(host - cpu)
    # the two add up to the host seconds `operator_host_s` reads over the
    # classes that ran
    assert _reader("agg_host_s")(ctx) + 3.0 == pytest.approx(host)
    # the median over the traced queries
    both = _ctx((100.0, 15.0, {}), (120.0, 5.0, {}))
    assert _reader("op_cpu_s")(both) == pytest.approx((cpu + 0.25) / 2)
    assert _reader("op_offcpu_s")(both) == pytest.approx(
        ((host - cpu) + 0.75) / 2)


def test_a_copy_that_reaches_past_its_task_gives_up_its_share(monkeypatch):
    # a copy the thread's tasks cover half of gives up half of its CPU
    spans = [_cpu(10.0, 12.0, "task:task", 1.0),
             _cpu(11.0, 13.0, "transfer:to_host", 0.4)]
    monkeypatch.setattr(sp, "load", lambda: spans)
    assert _reader("op_cpu_s")(_ctx((10.0, 5.0, {}))) == pytest.approx(0.8)


def test_a_program_without_the_stamp_gives_the_new_readers_nothing(monkeypatch):
    # the parent's spans: tasks, segments, transfers and enqueues with no
    # `cpu_us`, no `operands`, and no span at a query's end
    spans = [_span(10.0, 12.0, "task:task"),
             _span(10.0, 12.0, "op:AggExec"),
             _span(10.5, 11.0, "transfer:stage"),
             _span(11.0, 11.2, "kernel:agg_partial", compiled=False)]
    monkeypatch.setattr(sp, "load", lambda: spans)
    ctx = _ctx((10.0, 5.0, {}))
    for name in ("op_cpu_s", "op_offcpu_s", "stage_cpu_s", "finish_s",
                 "enqueue_operands"):
        assert _reader(name)(ctx) is None, name
    # the enqueue spans are older than this PR
    assert _reader("enqueue_s")(ctx) == pytest.approx(0.2)
    assert _reader("agg_host_s")(ctx) == pytest.approx(1.5)


def test_span_cpu_adds_threads_and_counts_the_outermost_span(monkeypatch):
    spans = [
        _cpu(10.0, 12.0, "transfer:stage", 1.0),
        _cpu(10.5, 11.0, "transfer:stage", 0.3),         # nested: in the 1.0
        _cpu(10.0, 13.0, "transfer:stage", 0.5, tid=2),  # side by side: added
        _span(13.0, 14.0, "transfer:stage", tid=2),      # not stamped
        _cpu(10.0, 11.0, "transfer:to_host", 0.9),       # another span
        _cpu(30.0, 31.0, "transfer:stage", 0.7),         # after the query
        _span(14.0, 14.5, "obs:finish"),
        _span(10.1, 10.3, "kernel:_concat_gather", operands=44),
        _span(10.4, 10.5, "kernel:jit_compile:sort_take", operands=3),
        _span(30.1, 30.2, "kernel:_concat_gather", operands=7),
    ]
    monkeypatch.setattr(sp, "load", lambda: spans)
    ctx = _ctx((10.0, 5.0, {}))
    assert _reader("stage_cpu_s")(ctx) == pytest.approx(1.0 + 0.5)
    assert _reader("stage_h2d_s")(ctx) == pytest.approx(2.0 + 4.0)
    assert _reader("finish_s")(ctx) == pytest.approx(0.5)
    assert _reader("enqueue_s")(ctx) == pytest.approx(0.2 + 0.1)
    assert _reader("enqueue_operands")(ctx) == 47


def test_unnamed_launches_is_the_trace_less_the_programs_own_count():
    run = helpers.load_run()

    def ctx(launches, stats):
        out = types.SimpleNamespace(
            records=[types.SimpleNamespace(device_stats=s) for s in stats],
            reduction=types.SimpleNamespace(launches_per_query=launches))
        out.per_query = lambda value: run.ReadContext.per_query(out, value)
        return out

    read = _reader("unnamed_launches")
    three = [{0: 102}, {0: 110}, {0: 104}]
    counted = [{"kernel_calls": 40}, {"kernel_calls": 41}, {"kernel_calls": 40}]
    assert read(ctx(three, counted)) == 64  # the median of 62, 69, 64
    # the mean over chips, as `device_launches`
    assert read(ctx([{0: 100, 1: 50}], counted[:1])) == 35
    # a rehearsal's trace has no launches; an older program no such count
    assert read(ctx([{0: 0}] * 3, counted)) is None
    assert read(ctx(three, [{}] * 3)) is None
