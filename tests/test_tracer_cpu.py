"""CPU beside wall on the tracer's spans (PR 37): under full tracing a span
of a stamped category (``tracer.CPU_STAMPED``: a task's whole run, a
transfer) carries ``args.cpu_us``, the recording thread's own CPU clock
across it, so ``dur - cpu_us`` is the time the thread did not run. No other
span is stamped (a read of that clock is a system call), nothing is with the
tracer disabled, and the flight-recorder ring's spans carry none."""

import threading
import time

import pytest

from blaze_tpu.core import ColumnarBatch
from blaze_tpu.ir import exprs as E
from blaze_tpu.ir import nodes as N
from blaze_tpu.ir import types as T
from blaze_tpu.obs.tracer import CPU_STAMPED, TRACER, Tracer
from blaze_tpu.runtime.session import Session

F = E.AggFunction
M = E.AggMode
HASH = E.AggExecMode.HASH_AGG
# a stamp's own cost and the clock's step: what cpu_us may pass dur by (us)
SLACK_US = 50


@pytest.fixture(autouse=True)
def _reset_tracer():
    """Each test starts from a disabled, empty process tracer."""
    TRACER.disable()
    TRACER.reset()
    yield
    TRACER.disable()
    TRACER.reset()


def _busy(seconds: float):
    end = time.perf_counter() + seconds
    while time.perf_counter() < end:
        pass


def test_a_span_around_a_busy_loop_is_nearly_all_cpu():
    """The stamp is the recording thread's own CPU clock across the span:
    at least the ``time.thread_time`` delta read inside it, and within
    ``SLACK_US`` of it, however often the machine deschedules the thread. A
    sleep in the span and a second thread busy throughout tell it from wall
    and process time."""
    tr = Tracer()
    tr.enable()
    stop = threading.Event()

    def other():
        while not stop.is_set():
            pass

    spinner = threading.Thread(target=other, daemon=True)
    spinner.start()
    over = []
    try:
        # the least of a few: an interrupt the thread is charged for between
        # the stamp and the read beside it is not what is tested here
        for _ in range(3):
            with tr.span("task", "task"):
                t0 = time.thread_time_ns()
                _busy(0.02)
                time.sleep(0.01)
                own_us = (time.thread_time_ns() - t0) / 1e3
            ev = tr.snapshot()[-1]
            assert own_us <= ev["args"]["cpu_us"] <= ev["dur"] + SLACK_US
            over.append(ev["args"]["cpu_us"] - own_us)
    finally:
        stop.set()
        spinner.join()
    assert min(over) <= SLACK_US, over


def test_a_span_around_a_sleep_is_nearly_no_cpu():
    tr = Tracer()
    tr.enable()
    with tr.detail("stage", "transfer"):
        time.sleep(0.05)
    (ev,) = tr.snapshot()
    assert ev["dur"] >= 50_000
    assert 0 <= ev["args"]["cpu_us"] < 0.1 * ev["dur"]


def test_only_the_stamped_categories_reach_the_cpu_clock(monkeypatch):
    assert CPU_STAMPED == {"task", "transfer"}
    tr = Tracer()
    tr.enable()
    reads = []
    real = time.thread_time_ns
    monkeypatch.setattr(time, "thread_time_ns",
                        lambda: reads.append(1) or real())
    with tr.span("task", "task"):
        # a per-batch wait, an operator's span, the end of a query: no read
        with tr.detail("agg_partial", "sync"), tr.span("x", "operator"):
            pass
        with tr.span("finish", "obs"):
            pass
    assert len(reads) == 2
    stamped = {e["cat"] for e in tr.snapshot()
               if "cpu_us" in (e.get("args") or {})}
    assert stamped == {"task"}


def test_complete_records_the_cpu_it_is_handed_and_none_otherwise():
    tr = Tracer()
    tr.enable()
    tr.complete("plain", "engine", 0, 10_000)
    tr.complete("stamped", "engine", 0, 10_000, {"rows": 3}, 4_000)
    tr.complete("idle", "engine", 0, 10_000, None, 0)
    plain, stamped, idle = tr.snapshot()
    assert "args" not in plain
    assert stamped["args"] == {"rows": 3, "cpu_us": 4.0}
    assert idle["args"] == {"cpu_us": 0.0}


def test_nothing_is_stamped_with_the_tracer_disabled(monkeypatch):
    """With full tracing off no site reaches the CPU clock or counts
    operands: the ring's spans are what they were."""
    from blaze_tpu.core import kernels
    from blaze_tpu.ops.base import ExecContext
    from blaze_tpu.ops.basic import MemoryScanExec, RenameColumnsExec
    from blaze_tpu.runtime.metrics import MetricNode
    from blaze_tpu.utils.device import pull_columns

    def forbidden(*_a, **_k):
        raise AssertionError("reached with the tracer disabled")

    monkeypatch.setattr(time, "thread_time_ns", forbidden)
    monkeypatch.setattr(kernels, "_operands", forbidden)
    assert not TRACER.enabled and TRACER.active  # the ring alone
    batch = ColumnarBatch.from_pydict({"a": list(range(64))})
    scan = MemoryScanExec(batch.schema, [[batch] * 4])
    op = RenameColumnsExec(RenameColumnsExec(scan, ["b"]), ["c"])
    for out in op.execute(0, ExecContext(), MetricNode("root")):
        with TRACER.span("task", "task"), TRACER.detail("stage", "transfer"):
            pulled = pull_columns(out.columns, out.num_rows)
        kernels.slice_planes([out.columns[0].data],
                             [out.columns[0].validity], 1, 3, 8)
    assert pulled[0][0].tolist() == list(range(64))
    assert TRACER.snapshot() == []
    ring = TRACER.ring_snapshot()
    assert {"task", "to_host"} <= {e["name"] for e in ring}
    assert not [e for e in ring if "cpu_us" in (e.get("args") or {})]


def _query(sess, n=20_000, groups=7):
    b = ColumnarBatch.from_pydict({"k": [i % groups for i in range(n)],
                                   "v": list(range(n))})
    sess.resources["src"] = lambda p: [b.to_arrow()]
    scan = N.FFIReader(schema=b.schema, resource_id="src", num_partitions=1)
    groupings = [("k", E.Column("k"))]
    partial = N.Agg(scan, HASH, groupings,
                    [N.AggColumn(E.AggExpr(F.SUM, [E.Column("v")], T.I64),
                                 M.PARTIAL, "total")])
    ex = N.ShuffleExchange(partial, N.HashPartitioning([E.Column("k")], 2))
    return N.Agg(ex, HASH, groupings,
                 [N.AggColumn(E.AggExpr(F.SUM, [E.Column("v")], T.I64),
                              M.FINAL, "total")])


def test_a_traced_query_stamps_its_tasks_and_transfers_and_spans_its_end():
    with Session() as sess:
        plan = _query(sess)
        sess.execute_to_table(plan)  # compile outside the traced one
        TRACER.enable()
        TRACER.reset()
        table = sess.execute_to_table(plan)
        events = [e for e in TRACER.snapshot() if e["ph"] == "X"]
        TRACER.disable()
    assert table.num_rows == 7

    def of(cat):
        return [e for e in events if e["cat"] == cat]

    def cpu(e):
        return (e.get("args") or {}).get("cpu_us")

    # a task's whole run is stamped, and a thread cannot use more CPU than
    # the time it had; the segments and waits inside it are not
    tasks = of("task")
    assert tasks and all(0 <= cpu(e) <= e["dur"] + SLACK_US for e in tasks)
    assert sum(cpu(e) for e in tasks) > 0
    assert of("op") and of("sync")
    assert {e["cat"] for e in events if cpu(e) is not None} <= CPU_STAMPED
    # a task thread is in one operator's segment nearly all of its run
    for task in tasks:
        inside = sum(e["dur"] for e in of("op") if e["tid"] == task["tid"]
                     and task["ts"] <= e["ts"] <= task["ts"] + task["dur"])
        assert inside <= task["dur"] + 1.0
    # the enqueue says what was enqueued
    enqueues = of("kernel")
    assert enqueues
    for e in enqueues:
        assert e["args"]["operands"] >= 1 and e["args"]["compiled"] is False, e
    # the pull of the result and the end of the query, once each
    pulls = [e for e in of("transfer") if e["name"] == "to_host"]
    assert pulls and all(0 <= cpu(e) <= e["dur"] + SLACK_US for e in pulls)
    (finish,) = [e for e in of("obs") if e["name"] == "finish"]
    (query,) = of("query")
    assert finish["ts"] >= query["ts"] + query["dur"] - 1.0  # at its end


def test_the_ring_alone_sees_the_end_of_a_query_without_a_stamp():
    with Session() as sess:
        TRACER.reset()
        sess.execute_to_table(_query(sess, n=2_000))
        ring = TRACER.ring_snapshot()
    assert TRACER.snapshot() == []
    (finish,) = [e for e in ring if e["cat"] == "obs"]
    assert finish["name"] == "finish"
    assert "cpu_us" not in (finish.get("args") or {})
    assert not [e for e in ring if e["cat"] in ("op", "kernel")]


def test_save_profile_lists_the_store_only_for_a_new_fingerprint(
        tmp_path, monkeypatch):
    import os

    from blaze_tpu.config import Config
    from blaze_tpu.obs import stats

    conf = Config(profile_store_dir=str(tmp_path), profile_store_max=2)
    listed = []  # the times the store's directory was listed
    real = os.listdir
    monkeypatch.setattr(
        stats.os, "listdir", lambda d: real(d) if str(d) != str(tmp_path)
        else listed.append(d) or real(d))
    assert stats.save_profile({"fingerprint": "a", "wall_s": 1}, conf) == "a"
    assert len(listed) == 1
    # the same plan again: the file is rewritten, the directory not listed
    assert stats.save_profile({"fingerprint": "a", "wall_s": 2}, conf) == "a"
    assert len(listed) == 1
    assert stats.load_profile("a", conf)["wall_s"] == 2
    # new fingerprints are listed, and past the cap the oldest goes
    for i, fp in enumerate("bc"):
        os.utime(tmp_path / "a.json", (1, 1))
        assert stats.save_profile({"fingerprint": fp, "wall_s": i}, conf) == fp
    assert len(listed) == 3
    assert sorted(real(tmp_path)) == ["b.json", "c.json"]
