"""The per-layer metrics read from the program's own host spans: a traced
rehearsal of each cell reports them, and synthetic spans pin what the two
shared readers compute. The rehearsal runs the plan the chip runs: there the
filter and the join stay operators of their own (`fused_filter_agg` is
opt-in on an accelerator and on by default on the CPU), so the test's tiny
configuration switches the CPU's default off."""

import json
import types

import pytest

from tests.benchmark import helpers

helpers.load_run()  # puts the benchmark's directory on sys.path
from benchlib import spans as sp  # noqa: E402
from benchlib.registry import Registry  # noqa: E402

with open(helpers.MANIFEST) as _f:
    _MANIFEST = json.load(_f)
CELLS = [w["name"] for w in _MANIFEST["workloads"]]
# the cells whose plan has a hash join: `join_host_s`'s own list
(JOIN_CELLS,) = [m["workloads"] for m in _MANIFEST["per_layer"]
                 if m["name"] == "join_host_s"]
NEW = {"decode_wait_s", "decode_mrows_s", "stage_h2d_s", "d2h_s",
       "device_wait_s", "sync_points", "agg_host_s", "exchange_host_s"}


def _as_on_the_chip(manifest, tmp_path):
    for entry in manifest["configs"]:
        path = tmp_path / entry["file"]
        config = json.loads(path.read_text())
        config["session"]["conf"]["fused_filter_agg"] = False
        path.write_text(json.dumps(config))


@pytest.mark.parametrize("cell", CELLS)
def test_traced_rehearsal_reports_the_span_metrics(cell, tmp_path, capsys):
    path = helpers.tiny_manifest(tmp_path, _as_on_the_chip)
    rc, lines = helpers.run_cell(capsys, path, cell, trace=1)
    assert rc == 0, lines
    result = json.loads(lines[-1])
    metrics = result["metrics"]
    want = NEW | ({"join_host_s"} if cell in JOIN_CELLS else set())
    assert want <= set(metrics), sorted(want - set(metrics))
    assert ("join_host_s" in metrics) == (cell in JOIN_CELLS)
    for name in want:
        assert metrics[name]["value"] >= 0, name
    assert metrics["decode_mrows_s"]["value"] > 0
    assert metrics["sync_points"]["value"] >= 4
    # what the spans split is never more than the whole they split
    eps = 1e-9
    assert metrics["decode_wait_s"]["value"] <= metrics["scan_self_s"]["value"] + eps
    assert metrics["agg_host_s"]["value"] <= metrics["agg_self_s"]["value"] + eps
    assert metrics["exchange_host_s"]["value"] <= \
        metrics["exchange_self_s"]["value"] + eps
    if cell in JOIN_CELLS:
        assert metrics["join_host_s"]["value"] <= \
            metrics["join_self_s"]["value"] + eps
    # an idle gap is named by what a thread was doing in it (the shortest
    # span open), never by the span of an operator's whole life: inside one
    # a thread is always in a shorter `op` segment
    names = [name for name, _s in result["breakdown"]["idle_gaps"]]
    assert names and not [n for n in names if n.startswith("operator:")], names


# -- the two shared readers, on synthetic spans --------------------------------


def _span(start, end, key, tid=1, **args):
    return sp.Span(start, end, key, tid, args)


def _ctx(*records):
    run = helpers.load_run()
    ctx = types.SimpleNamespace(records=[
        types.SimpleNamespace(t0=t0, seconds=seconds, device_stats=stats)
        for t0, seconds, stats in records])
    ctx.per_query = lambda value: run.ReadContext.per_query(ctx, value)
    return ctx


def _reader(name):
    return Registry([helpers.BENCH_DIR]).reader(name)


def test_operator_host_s_takes_out_the_same_threads_waits_once(monkeypatch):
    spans = [
        # thread 1: ten seconds of AggExec holding a 4 s sync with a 1 s
        # pull nested in it and a 2 s stage beside it: 6 s blocked, 4 s host
        _span(100.0, 110.0, "op:AggExec"),
        _span(101.0, 105.0, "sync:agg_partial"),
        _span(102.0, 103.0, "transfer:to_host"),
        _span(106.0, 108.0, "transfer:stage"),
        # a wait of thread 1 outside the segment is another operator's
        _span(111.0, 112.0, "sync:agg_merge"),
        _span(110.0, 113.0, "op:SortExec"),
        # thread 2: another task's wait over the same seconds is not taken
        # from thread 1's operator; its own 2 s segment waits for 0.5 s
        _span(100.0, 110.0, "sync:agg_partial", tid=2),
        _span(110.0, 112.0, "op:AggExec", tid=2),
        _span(110.5, 111.0, "scan:decode_wait", tid=2),
        # the operator's lifetime span and the kernel span are not waits
        _span(100.0, 113.0, "operator:AggExec"),
        _span(100.5, 100.6, "kernel:agg_partial"),
        # the next query's
        _span(120.0, 121.0, "op:AggExec"),
    ]
    monkeypatch.setattr(sp, "load", lambda: spans)
    ctx = _ctx((100.0, 15.0, {}))
    assert _reader("agg_host_s")(ctx) == pytest.approx(4.0 + 1.5)
    # no operator of the join's classes ran: nothing to read
    assert _reader("join_host_s")(ctx) is None
    # the median over the traced queries
    both = _ctx((100.0, 15.0, {}), (120.0, 5.0, {}))
    assert _reader("agg_host_s")(both) == pytest.approx((5.5 + 1.0) / 2)


def test_span_sum_adds_threads_counts_nesting_once_and_divides_rates(monkeypatch):
    spans = [
        _span(10.0, 12.0, "sync:agg_partial"),
        _span(11.0, 11.5, "sync:compact"),            # nested: counted once
        _span(10.0, 13.0, "sync:bhj_probe", tid=2),    # side by side: added
        _span(10.0, 10.5, "scan:decode", tid=3, rows=1_000_000, bytes=8),
        _span(12.0, 13.5, "scan:decode", tid=3, rows=3_000_000, bytes=8),
        _span(30.0, 31.0, "sync:agg_partial"),        # after the query
    ]
    monkeypatch.setattr(sp, "load", lambda: spans)
    ctx = _ctx((10.0, 5.0, {"sync_calls": 7}))
    assert _reader("device_wait_s")(ctx) == pytest.approx(2.0 + 3.0)
    assert _reader("decode_mrows_s")(ctx) == pytest.approx(4.0 / 2.0)
    assert _reader("sync_points")(ctx) == 7
    # a program that records none of it gives nothing to read, and no error
    assert _reader("stage_h2d_s")(ctx) is None
    assert _reader("decode_wait_s")(ctx) is None
    assert _reader("sync_points")(_ctx((10.0, 5.0, {}))) is None


def test_spans_come_from_the_tracer_on_the_query_records_clock():
    import time

    from blaze_tpu.obs.tracer import TRACER

    TRACER.enable()
    TRACER.reset()
    try:
        t0 = time.perf_counter()
        with TRACER.detail("agg_partial", "sync"):
            time.sleep(0.002)
        seconds = time.perf_counter() - t0
        TRACER.instant("not_a_span", "agg")
        loaded = sp.load()
    finally:
        TRACER.disable()
        TRACER.reset()
    assert [s.key for s in loaded] == ["sync:agg_partial"]
    (span,) = loaded
    assert t0 <= span.start and span.end <= t0 + seconds
    assert span.end - span.start >= 0.002
    record = types.SimpleNamespace(t0=t0, seconds=seconds)
    assert sp.of_query(loaded, record) == loaded
    assert sp.of_query(loaded, types.SimpleNamespace(t0=t0 + seconds,
                                                      seconds=1.0)) == []
