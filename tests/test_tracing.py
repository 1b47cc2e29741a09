"""Span tracer + EXPLAIN ANALYZE tests: event recording, worker re-basing,
per-operator self-time attribution, the disabled-path overhead guard, and
the /debug/trace + /debug/queries endpoints."""

import json
import time
import urllib.request

import pytest

from blaze_tpu.config import Config
from blaze_tpu.core import ColumnarBatch
from blaze_tpu.ir import exprs as E
from blaze_tpu.ir import nodes as N
from blaze_tpu.ir import types as T
from blaze_tpu.obs.tracer import TRACER, Tracer
from blaze_tpu.runtime.session import Session

F = E.AggFunction
M = E.AggMode
HASH = E.AggExecMode.HASH_AGG


@pytest.fixture(autouse=True)
def _reset_tracer():
    """Each test starts from a disabled, empty process tracer."""
    TRACER.disable()
    TRACER.reset()
    yield
    TRACER.disable()
    TRACER.reset()


def _two_stage_agg_plan(sess, n=10_000, groups=7, reducers=4):
    b = ColumnarBatch.from_pydict({"k": [i % groups for i in range(n)],
                                   "v": list(range(n))})
    sess.resources["src"] = lambda p: [b.to_arrow()]
    scan = N.FFIReader(schema=b.schema, resource_id="src", num_partitions=1)
    groupings = [("k", E.Column("k"))]
    partial = N.Agg(scan, HASH, groupings,
                    [N.AggColumn(E.AggExpr(F.SUM, [E.Column("v")], T.I64),
                                 M.PARTIAL, "total")])
    ex = N.ShuffleExchange(partial, N.HashPartitioning([E.Column("k")],
                                                       reducers))
    return N.Agg(ex, HASH, groupings,
                 [N.AggColumn(E.AggExpr(F.SUM, [E.Column("v")], T.I64),
                              M.FINAL, "total")])


# -- tracer unit behaviour ----------------------------------------------------


@pytest.mark.quick
def test_span_records_complete_events_and_nesting():
    tr = Tracer()
    tr.enable()
    with tr.span("outer", "engine", {"q": 1}):
        with tr.span("inner", "engine"):
            time.sleep(0.002)
    events = tr.snapshot()
    assert [e["name"] for e in events] == ["inner", "outer"]
    inner, outer = events
    assert inner["ph"] == outer["ph"] == "X"
    # the inner span lies within the outer one on the timeline
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-3
    assert outer["args"] == {"q": 1}


def test_disabled_tracer_records_nothing_and_reuses_noop():
    # with BOTH full tracing and the flight-recorder ring off, span() must
    # return the shared allocation-free no-op
    tr = Tracer()
    tr.set_ring(0)
    s1, s2 = tr.span("a"), tr.span("b")
    assert s1 is s2, "disabled span() must return the shared no-op"
    with s1:
        pass
    tr.instant("x")
    tr.complete("y", "engine", 0, 10)
    assert tr.snapshot() == []


def test_ring_records_while_trace_buffer_stays_empty():
    # default posture: tracing off, flight recorder on — events land in the
    # ring (for incident bundles) but never in the Chrome-trace buffer
    tr = Tracer()
    assert not tr.enabled and tr.active
    with tr.span("a", "engine"):
        pass
    tr.complete("b", "engine", 0, 10)
    assert tr.snapshot() == []
    assert [e["name"] for e in tr.ring_snapshot()] == ["a", "b"]
    # bounded: oldest events fall off
    tr.set_ring(2)
    tr.complete("c", "engine", 0, 10)
    assert [e["name"] for e in tr.ring_snapshot()] == ["b", "c"]


def test_buffer_cap_counts_drops():
    tr = Tracer()
    tr.enable()
    tr.max_events = 3
    for i in range(5):
        tr.complete(f"e{i}", "engine", 0, 1)
    assert len(tr.snapshot()) == 3
    assert tr.dropped == 2
    assert tr.to_chrome_trace()["otherData"]["dropped_events"] == 2


@pytest.mark.quick
def test_absorb_rebases_worker_events_onto_driver_timeline():
    driver, worker = Tracer(), Tracer()
    driver.enable()
    worker.enable()
    # simulate a worker whose epoch is 5ms later than the driver's
    worker.wall_epoch_ns = driver.wall_epoch_ns + 5_000_000
    worker.pid = driver.pid + 1
    worker.complete("task", "task", worker.perf_epoch_ns, 2_000_000)
    events = worker.drain()
    assert worker.snapshot() == [], "drain must clear the worker buffer"
    assert events[0]["ts"] == 0.0
    driver.absorb(events, worker.wall_epoch_ns)
    absorbed = driver.snapshot()[0]
    assert absorbed["ts"] == pytest.approx(5_000.0)  # µs
    assert absorbed["pid"] == worker.pid, "worker keeps its own pid track"
    trace = driver.to_chrome_trace("driver")
    names = {e["args"]["name"] for e in trace["traceEvents"]
             if e.get("ph") == "M"}
    assert any("worker" in n for n in names)


# -- engine integration -------------------------------------------------------


@pytest.mark.quick
def test_explain_analyze_two_stage_agg():
    with Session(conf=Config(trace_enable=True, batch_size=4096)) as sess:
        text = sess.explain_analyze(_two_stage_agg_plan(sess))
    lines = text.splitlines()
    assert lines[0].startswith("== Query 0:")
    assert "-- Stage 0 [shuffle_map]" in text
    assert "ShuffleWriterExec" in text and "IpcReaderExec" in text
    # every EXECUTED operator node carries non-zero self-time
    for line in lines:
        if "rows=" not in line or "[not executed]" in line:
            continue
        rows = int(line.split("rows=")[1].split()[0])
        batches = int(line.split("batches=")[1].split()[0])
        elapsed = line.split("elapsed_compute=")[1].split()[0]
        if rows or batches:
            assert elapsed != "0ns", f"executed node without self-time: {line}"
    # spans of every category landed in the trace buffer
    cats = {e.get("cat") for e in TRACER.snapshot()}
    assert {"query", "stage", "task", "operator", "shuffle"} <= cats


@pytest.mark.quick
def test_self_time_excludes_children():
    """The parent's clock pauses while a child's generator runs: a pipeline
    of pass-through operators must not multiply-count the scan time."""
    from blaze_tpu.ops.base import ExecContext
    from blaze_tpu.ops.basic import MemoryScanExec, RenameColumnsExec
    from blaze_tpu.runtime.metrics import MetricNode

    b = ColumnarBatch.from_pydict({"a": list(range(50_000))})
    scan = MemoryScanExec(b.schema, [[b.slice(i * 5000, 5000)
                                      for i in range(10)]])
    op = RenameColumnsExec(RenameColumnsExec(scan, ["b"]), ["c"])
    ctx = ExecContext()
    root = MetricNode("root")
    total_ns = -time.perf_counter_ns()
    for _ in op.execute(0, ctx, root):
        time.sleep(0.001)  # consumer time: must land on NO node
    total_ns += time.perf_counter_ns()
    self_sum = root.total("elapsed_compute_time_ns")
    # sum of self-times <= wall (each ns attributed to at most one node);
    # consumer sleeps (>=10ms) are excluded
    assert 0 < self_sum < total_ns - 5_000_000


def test_query_log_and_stage_meta():
    with Session(conf=Config(batch_size=4096)) as sess:
        list(sess.execute(_two_stage_agg_plan(sess)))
        list(sess.execute(_two_stage_agg_plan(sess)))
        assert len(sess.query_log) == 2
        q0, q1 = sess.query_log
        assert (q0["id"], q1["id"]) == (0, 1)
        assert q0["rows"] == 7 and q0["wall_s"] > 0
        assert q0["stages"][0]["kind"] == "shuffle_map"
        assert q1["stages"][0]["id"] != q0["stages"][0]["id"]


@pytest.mark.quick
def test_debug_trace_and_queries_endpoints():
    from blaze_tpu.runtime.http import ProfilingService

    with Session(conf=Config(trace_enable=True, batch_size=4096)) as sess:
        list(sess.execute(_two_stage_agg_plan(sess)))
        svc = ProfilingService.start(sess)
        try:
            def get(path):
                url = f"http://127.0.0.1:{svc.port}{path}"
                with urllib.request.urlopen(url, timeout=10) as r:
                    return r.read().decode()

            trace = json.loads(get("/debug/trace"))
            events = trace["traceEvents"]
            assert trace["displayTimeUnit"] == "ms"
            assert any(e.get("ph") == "M" and e["name"] == "process_name"
                       for e in events)
            xs = [e for e in events if e.get("ph") == "X"]
            assert xs and all(
                {"name", "cat", "ts", "dur", "pid", "tid"} <= set(e)
                for e in xs), "events must be Perfetto-loadable complete spans"
            assert any(e["cat"] == "task" for e in xs)

            queries = json.loads(get("/debug/queries"))
            assert queries and queries[-1]["rows"] == 7

            metrics = json.loads(get("/debug/metrics"))

            def has_durations(node):
                return bool(node.get("durations")) or any(
                    has_durations(c) for c in node.get("children") or [])

            assert has_durations(metrics), \
                "*_time_ns metrics must render human durations"
        finally:
            ProfilingService.stop()


@pytest.mark.slow
def test_worker_spans_ship_back_and_rebase(tmp_path):
    """Pool-run map tasks record spans in the worker PROCESS; they must come
    back with task replies and land in the driver's buffer with worker pids.
    Needs a parquet-backed plan — resource lambdas aren't pool-shippable."""
    import os

    import pyarrow as pa
    import pyarrow.parquet as pq

    from blaze_tpu.ops.parquet import scan_node_for_files

    path = str(tmp_path / "t.parquet")
    pq.write_table(pa.table({"k": [i % 7 for i in range(10_000)],
                             "v": list(range(10_000))}), path)
    scan = scan_node_for_files([path], num_partitions=2)
    groupings = [("k", E.Column("k"))]
    partial = N.Agg(scan, HASH, groupings,
                    [N.AggColumn(E.AggExpr(F.SUM, [E.Column("v")], T.I64),
                                 M.PARTIAL, "total")])
    ex = N.ShuffleExchange(partial, N.HashPartitioning([E.Column("k")], 3))
    plan = N.Agg(ex, HASH, groupings,
                 [N.AggColumn(E.AggExpr(F.SUM, [E.Column("v")], T.I64),
                              M.FINAL, "total")])

    with Session(conf=Config(trace_enable=True, batch_size=4096),
                 num_worker_processes=1) as sess:
        list(sess.execute(plan))
    events = TRACER.snapshot()
    pids = {e["pid"] for e in events}
    assert os.getpid() in pids
    assert pids - {os.getpid()}, "no worker-process spans came back"
    worker_tasks = [e for e in events
                    if e["pid"] != os.getpid() and e["cat"] == "task"]
    assert worker_tasks
    driver_span = max(events, key=lambda e: e.get("dur", 0))
    for ev in worker_tasks:
        # re-based into the driver timeline: inside the driver's query span
        assert driver_span["ts"] - 1e6 <= ev["ts"] <= \
            driver_span["ts"] + driver_span["dur"] + 1e6


@pytest.mark.quick
def test_tracing_disabled_overhead_under_5_percent():
    """The tracing-disabled path must stay near-free. Measured analytically
    (robust to CI noise): per-instrumentation-event cost is microbenched,
    multiplied by the observed event count of a real 1M-row query, and
    compared against that query's wall-clock."""
    from blaze_tpu.ops.base import ExecContext
    from blaze_tpu.ops.basic import MemoryScanExec, RenameColumnsExec
    from blaze_tpu.runtime.metrics import MetricNode

    n = 1_000_000
    batch = 65_536
    b = ColumnarBatch.from_pydict({"k": [i % 97 for i in range(n)],
                                   "v": list(range(n))})
    with Session(conf=Config(batch_size=batch)) as sess:
        assert not TRACER.enabled
        sess.resources["src"] = lambda p: [b.to_arrow()]
        scan = N.FFIReader(schema=b.schema, resource_id="src",
                           num_partitions=1)
        groupings = [("k", E.Column("k"))]
        plan = N.Agg(scan, HASH, groupings,
                     [N.AggColumn(E.AggExpr(F.SUM, [E.Column("v")], T.I64),
                                  M.COMPLETE, "total")])
        t0 = time.perf_counter_ns()
        out = sess.execute_to_pydict(plan)
        wall_ns = time.perf_counter_ns() - t0
        assert len(out["k"]) == 97
        events = sess.metrics.total("output_batches")

    # microbench the per-batch instrumentation: the generator wrapper's
    # stack push/pop + 2 metric adds + TRACER.enabled check + span() no-op,
    # and per batch one pass through each site that records only under
    # full tracing: the op-segment gate (inside execute), a counted sync,
    # a decode wait, a staging span and a decoded batch
    from blaze_tpu.ops.parquet import _decoded
    from blaze_tpu.utils.device import stage_span, wait_int

    bsmall = ColumnarBatch.from_pydict({"a": list(range(64))})
    scan = MemoryScanExec(bsmall.schema, [[bsmall] * 256])
    op = RenameColumnsExec(RenameColumnsExec(scan, ["b"]), ["c"])
    ctx = ExecContext()
    decoded = _decoded(iter(range(256)))
    t0 = time.perf_counter_ns()
    for _ in op.execute(0, ctx, MetricNode("root")):
        TRACER.span("x")
        wait_int(7, "agg_partial")
        with TRACER.detail("decode_wait", "scan"):
            pass
        with stage_span(64):
            pass
        next(decoded)
    bench_ns = time.perf_counter_ns() - t0
    assert TRACER.snapshot() == []  # nothing was recorded on the way
    per_event_ns = bench_ns / (256 * 3)  # 3 operator levels x 256 batches

    overhead_ns = per_event_ns * max(events, 32)
    assert overhead_ns < 0.05 * wall_ns, (
        f"instrumentation {overhead_ns / 1e6:.2f}ms vs query "
        f"{wall_ns / 1e6:.1f}ms: disabled-path overhead exceeds 5%")
