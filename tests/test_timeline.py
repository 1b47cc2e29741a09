"""Live health plane tests (ISSUE 20, blaze_tpu/obs/timeline.py): ring
wrap, the slo_specs grammar, counter-rate and histogram-quantile math
against hand-computed values, ``Histogram.snapshot_delta`` under
concurrent observers, burn-rate window goldens driving the full
healthy -> degraded -> critical -> healthy transition arc (exactly one
incident bundle per edge), sampler thread start/stop hygiene across
sessions (no leak), the /debug/health + /debug/timeseries endpoints,
the disabled-path <5% overhead guard, and a quick-tier e2e on a real
2-worker pool where the ingest-lag series rises on append and returns
to zero after the cached refresh."""

import json
import os
import threading
import time
import urllib.error
import urllib.request

import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from blaze_tpu.config import Config
from blaze_tpu.ir import exprs as E
from blaze_tpu.ir import nodes as N
from blaze_tpu.ir import types as T
from blaze_tpu.obs.telemetry import (bucket_upper_bound, delta_snapshot,
                                     get_registry, quantile_from_snapshot)
from blaze_tpu.obs.timeline import (SUBSYSTEMS, TIMELINE, Ring, Timeline,
                                    get_timeline, parse_slo_specs)
from blaze_tpu.runtime.memmgr import MemManager
from blaze_tpu.runtime.session import Session

F = E.AggFunction
M = E.AggMode
HASH = E.AggExecMode.HASH_AGG


@pytest.fixture(autouse=True)
def _fresh():
    MemManager.reset()
    TIMELINE.stop()
    TIMELINE.reset()
    yield
    TIMELINE.stop()
    TIMELINE.reset()
    MemManager.reset()


def _batch(ks, vs):
    return pa.RecordBatch.from_pydict({"k": ks, "v": vs})


def _agg_plan(child, reducers=3):
    g = [("k", E.Column("k"))]
    partial = N.Agg(child, HASH, g, [N.AggColumn(
        E.AggExpr(F.SUM, [E.Column("v")], T.I64), M.PARTIAL, "s")])
    ex = N.ShuffleExchange(partial,
                           N.HashPartitioning([E.Column("k")], reducers))
    return N.Agg(ex, HASH, g, [N.AggColumn(
        E.AggExpr(F.SUM, [E.Column("v")], T.I64), M.FINAL, "s")])


def _tl_threads():
    return [t for t in threading.enumerate() if t.name == "blaze-timeline"]


# -- ring ----------------------------------------------------------------------


def test_ring_wrap_keeps_newest():
    r = Ring(5)
    for i in range(12):
        r.append(float(i), float(i * 10))
    assert len(r) == 5
    assert r.items() == [(float(i), float(i * 10)) for i in range(7, 12)]
    assert r.last() == (11.0, 110.0)
    assert r.since(9.0) == [(9.0, 90.0), (10.0, 100.0), (11.0, 110.0)]


def test_ring_partial_fill_in_order():
    r = Ring(8)
    r.append(1.0, 1.0)
    r.append(2.0, 2.0)
    assert r.items() == [(1.0, 1.0), (2.0, 2.0)]
    assert len(r) == 2


# -- slo_specs grammar ---------------------------------------------------------


def test_parse_slo_specs_grammar():
    specs = parse_slo_specs(
        "serve:serve_deadline_miss_ratio<=0.05;"
        "ingest:ingest_lag_versions<=2; cache:cache_stale_served_rate==0")
    assert [s.subsystem for s in specs] == ["serve", "ingest", "cache"]
    assert specs[0].check(0.05) and not specs[0].check(0.06)
    assert specs[2].check(0.0) and not specs[2].check(0.1)
    assert specs[1].key == "ingest:ingest_lag_versions<=2"


def test_parse_slo_specs_rejects_malformed():
    with pytest.raises(ValueError):
        parse_slo_specs("serve:deadline_miss 0.05")  # no operator
    with pytest.raises(ValueError):
        parse_slo_specs("nosuchsub:x_ratio<=0.1")  # unknown subsystem
    assert parse_slo_specs("") == []
    assert parse_slo_specs(" ; ") == []


def test_configure_from_keeps_objectives_on_malformed_specs():
    tl = Timeline()
    tl.configure(Config(slo_specs="serve:serve_deadline_miss_ratio<=0.05"))
    assert len(tl._slos) == 1
    # a typo'd reconfigure must not silently drop the objectives
    try:
        tl.configure(Config(slo_specs="serve:broken"))
    except ValueError:
        pass
    assert [s.key for s in tl._slos] == \
        ["serve:serve_deadline_miss_ratio<=0.05"]


# -- sampler math: rates and quantiles ----------------------------------------


def test_counter_rate_hand_computed():
    tl = Timeline()
    tl.configure(Config(slo_specs=""))
    c = get_registry().counter("blaze_testtl_ticks_total", "test counter")
    tl.sample_once(now=100.0)  # establishes prev; no rate yet (no dt)
    assert tl.latest("blaze_testtl_ticks_total:rate") is None
    c.inc(30)
    tl.sample_once(now=110.0)
    assert tl.latest("blaze_testtl_ticks_total:rate") == \
        pytest.approx(30.0 / 10.0)
    # flat interval -> zero rate
    tl.sample_once(now=120.0)
    assert tl.latest("blaze_testtl_ticks_total:rate") == 0.0
    # a shrunk total (reset_values between samples) clamps to 0 rate,
    # never negative
    with c._mu:
        c._series.clear()
    tl.sample_once(now=130.0)
    assert tl.latest("blaze_testtl_ticks_total:rate") == 0.0
    assert c is get_registry().counter("blaze_testtl_ticks_total", "")


def test_histogram_quantiles_hand_computed():
    tl = Timeline()
    tl.configure(Config(slo_specs=""))
    h = get_registry().histogram("blaze_testtl_lat_seconds", "test hist")
    tl.sample_once(now=10.0)
    for _ in range(100):
        h.observe(2.0)
    for _ in range(100):
        h.observe(32.0)
    tl.sample_once(now=11.0)
    # log buckets, 4/octave: 2.0 -> idx 4 (le 2^(5/4)), 32.0 -> idx 20
    # (le 2^(21/4)); p50 = target rank 100 lands exactly on the first
    # bucket, p99 interpolates log-linearly inside the second
    p50 = tl.latest("blaze_testtl_lat_seconds:p50")
    p99 = tl.latest("blaze_testtl_lat_seconds:p99")
    le_lo, le_hi = 2.0 ** (5 / 4), 2.0 ** (21 / 4)
    assert p50 == pytest.approx(le_lo)
    frac = (198 - 100) / 100  # rank 198 of 200, 98 into the second bucket
    assert p99 == pytest.approx(le_lo * (le_hi / le_lo) ** frac)
    # the NEXT interval has no new observations -> no quantile sample
    tl.sample_once(now=12.0)
    s = tl.series_since("blaze_testtl_lat_seconds:p99", 0.0)
    assert [t for t, _ in s] == [11.0]


def test_snapshot_delta_concurrent_observers():
    h = get_registry().histogram("blaze_testtl_conc_seconds", "test hist")
    stop = threading.Event()
    observed = [0] * 4

    def worker(i):
        while not stop.is_set():
            h.observe(0.5 + i)
            observed[i] += 1

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(4)]
    prev = h.snapshot() or {"buckets": {}, "sum": 0.0, "count": 0}
    for t in threads:
        t.start()
    seen = 0
    for _ in range(50):
        d = h.snapshot_delta(prev)
        assert d["count"] >= 0
        assert all(c >= 0 for c in d["buckets"].values())
        assert sum(d["buckets"].values()) == d["count"]
        # the chain takes ONE read an interval, as the sampler does: what
        # lands between two reads would be counted in both their intervals
        cur = h.snapshot()
        seen += delta_snapshot(cur, prev)["count"]
        prev = cur
    stop.set()
    for t in threads:
        t.join()
    cur = h.snapshot()
    seen += h.snapshot_delta(prev)["count"]
    assert seen == sum(observed)  # chained deltas tile the total exactly
    assert quantile_from_snapshot(cur, 0.5) is not None


# -- burn-rate goldens + health transitions + incident bundles -----------------


def _drive(tl, t, miss):
    """One tick at time ``t``: 10 outcomes, all deadline misses when
    ``miss`` else all served."""
    for _ in range(10):
        tl.note_outcome("dash", "deadline" if miss else "done")
    tl.sample_once(now=float(t))


def test_burn_rate_windows_and_health_arc(tmp_path):
    """Golden arc at 1s cadence: 60 healthy ticks, 60 breaching, 31
    recovering. Fast window catches onset (degraded at the 2nd breach:
    2/11 samples breaching -> burn 1.82 >= 1.0), critical waits for the
    slow window to confirm (multiwindow rule), recovery unwinds through
    degraded back to healthy — and every edge writes exactly one
    incident bundle."""
    tl = Timeline()
    tl.configure(Config(
        slo_specs="serve:serve_deadline_miss_ratio<=0.05;"
                  "cache:cache_hit_ratio>=0.5",
        slo_fast_window_s=10.0, slo_slow_window_s=60.0,
        slo_error_budget_ratio=0.1, slo_degraded_burn=1.0,
        slo_critical_burn=2.0,
        incident_dir=str(tmp_path), incident_max_bundles=32))
    tl.enabled = True  # hot-path hook on, without the thread
    for t in range(60):
        _drive(tl, t, miss=False)
    assert tl._sub_state["serve"] == "healthy"
    serve = tl._slos[0]
    assert serve.burn_fast == 0.0 and serve.burn_slow == 0.0
    for t in range(60, 120):
        _drive(tl, t, miss=True)
    assert tl._sub_state["serve"] == "critical"
    assert serve.burn_fast == pytest.approx(10.0)  # all-breach fast window
    for t in range(120, 151):
        _drive(tl, t, miss=False)
    assert tl._sub_state["serve"] == "healthy"

    rep = tl.health_report(now=151.0)
    arc = [(tr["from"], tr["to"]) for tr in rep["transitions"]
           if tr["subsystem"] == "serve"]
    assert arc == [("healthy", "degraded"), ("degraded", "critical"),
                   ("critical", "degraded"), ("degraded", "healthy")]
    assert rep["critical_intervals"] == 1
    assert rep["degraded_s"] > 0 and rep["critical_s"] > 0
    assert 0.0 < rep["degraded_ratio"] < 1.0
    assert rep["samples"] == 151
    # cache_hit_ratio never produced data: no budget spent, stays healthy
    cache_slo = rep["slo"]["cache:cache_hit_ratio>=0.5"]
    assert cache_slo["state"] == "healthy"
    assert cache_slo["last_value"] is None
    assert rep["subsystems"]["cache"]["state"] == "healthy"
    # exactly one incident bundle per transition edge
    bundles = [f for f in os.listdir(tmp_path) if "_health_" in f]
    assert len(bundles) == 4
    kinds = sorted(json.load(open(os.path.join(tmp_path, f)))["label"]
                   for f in bundles)
    assert kinds == sorted(["serve:healthy-degraded",
                            "serve:degraded-critical",
                            "serve:critical-degraded",
                            "serve:degraded-healthy"])


def test_single_hiccup_never_goes_critical():
    """One breaching sample after healthy history degrades at worst — the
    slow window refuses to confirm, so it cannot page."""
    tl = Timeline()
    tl.configure(Config(
        slo_specs="serve:serve_deadline_miss_ratio<=0.05",
        slo_fast_window_s=10.0, slo_slow_window_s=60.0,
        slo_error_budget_ratio=0.1, slo_degraded_burn=1.0,
        slo_critical_burn=2.0, incident_dir=""))
    tl.enabled = True
    for t in range(60):
        _drive(tl, t, miss=False)
    _drive(tl, 60, miss=True)
    assert tl._sub_state["serve"] != "critical"
    for t in range(61, 75):
        _drive(tl, t, miss=False)
    assert tl._sub_state["serve"] == "healthy"
    assert tl.health_report(now=75.0)["critical_intervals"] == 0


# -- lifecycle: thread hygiene across sessions ---------------------------------


def test_sampler_thread_hygiene_no_leak():
    assert _tl_threads() == []
    for _ in range(3):
        with Session(conf=Config(timeline_interval_s=0.05)):
            assert len(_tl_threads()) == 1
        assert _tl_threads() == []  # session close joins the sampler
    # a second session rebinds the one process-global thread
    s1 = Session(conf=Config(timeline_interval_s=0.05))
    s2 = Session(conf=Config(timeline_interval_s=0.05))
    try:
        assert len(_tl_threads()) == 1
    finally:
        s2.close()
        s1.close()
    assert _tl_threads() == []


def test_timeline_disabled_starts_nothing():
    with Session(conf=Config(timeline_enabled=False)):
        assert _tl_threads() == []
        assert not TIMELINE.enabled
        TIMELINE.note_outcome("t", "done")  # cheap no-op, drops the note
        assert TIMELINE._outcomes == {}


def test_env_force_disable_overrides_config(monkeypatch):
    monkeypatch.setenv("BLAZE_TPU_TIMELINE", "0")
    with Session(conf=Config(timeline_enabled=True)):
        assert _tl_threads() == []
        assert not TIMELINE.enabled


# -- disabled-path overhead guard ----------------------------------------------


@pytest.mark.quick
def test_timeline_disabled_overhead_under_5_percent(tmp_path):
    """With the plane off the only per-outcome cost in the scheduler is
    one attribute check in ``note_outcome``; scaled by a generous outcome
    count it stays under 5% of a real query's wall (same bar as the
    tracer/stats/attribution planes)."""
    from blaze_tpu.ops.parquet import scan_node_for_files

    path = str(tmp_path / "t.parquet")
    pq.write_table(pa.table({"k": [i % 97 for i in range(200_000)],
                             "v": list(range(200_000))}), path)
    plan = _agg_plan(scan_node_for_files([path], num_partitions=2))
    with Session(conf=Config(timeline_enabled=False)) as sess:
        t0 = time.perf_counter_ns()
        out = sess.execute_to_pydict(plan)
        wall_ns = time.perf_counter_ns() - t0
        assert len(out["k"]) == 97

        ITER = 100_000
        t0 = time.perf_counter_ns()
        for _ in range(ITER):
            TIMELINE.note_outcome("dash", "done")
        per_call_ns = (time.perf_counter_ns() - t0) / ITER
    overhead_ns = per_call_ns * 10_000  # far more outcomes than any query
    assert overhead_ns < 0.05 * wall_ns, (
        f"disabled timeline {overhead_ns / 1e6:.2f}ms vs query "
        f"{wall_ns / 1e6:.1f}ms: disabled-path overhead exceeds 5%")
    assert per_call_ns < 2_000, f"note_outcome {per_call_ns:.0f}ns"


# -- HTTP endpoints ------------------------------------------------------------


def test_debug_health_and_timeseries_endpoints():
    from blaze_tpu.runtime.http import ProfilingService

    def _get(port, path):
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}", timeout=10) as r:
            return r.read().decode()

    with Session(conf=Config(timeline_interval_s=0.05)) as sess:
        get_timeline().sample_once()
        svc = ProfilingService.start(sess)
        try:
            health = json.loads(_get(svc.port, "/debug/health"))
            assert health["enabled"] is True
            assert set(health["subsystems"]) == set(SUBSYSTEMS)
            listing = json.loads(_get(svc.port, "/debug/timeseries"))
            assert "serve_deadline_miss_ratio" in listing["series"]
            one = json.loads(_get(
                svc.port,
                "/debug/timeseries?name=serve_deadline_miss_ratio&since=0"))
            assert one["name"] == "serve_deadline_miss_ratio"
            assert one["samples"] and len(one["samples"][0]) == 2
            try:
                _get(svc.port, "/debug/timeseries?name=no_such_series")
                assert False, "expected 404"
            except urllib.error.HTTPError as exc:
                assert exc.code == 404
        finally:
            ProfilingService.stop()


# -- e2e: real 2-worker pool, lag rises then returns to zero -------------------


@pytest.mark.quick
def test_timeline_e2e_ingest_lag_round_trip():
    tl = get_timeline()
    with Session(conf=Config(timeline_interval_s=0.2),
                 num_worker_processes=2) as sess:
        tl.reset()
        sess.append("t", [_batch([0, 1, 0], [1, 2, 3])], num_partitions=2)
        plan = _agg_plan(sess.table_scan("t"))
        filled = sess.execute_cached(plan)
        tl.sample_once()
        assert tl.latest("ingest_lag_versions") == 0.0
        appends0 = get_registry().counter(
            "blaze_ingest_appends_total", "").total()
        sess.append("t", [_batch([1], [10])])
        tl.sample_once()
        assert tl.latest("ingest_lag_versions") >= 1.0
        assert tl.latest("ingest_lag_versions.t") >= 1.0
        refreshed = sess.execute_cached(plan)  # refresh folds the tail
        tl.sample_once()
        assert tl.latest("ingest_lag_versions") == 0.0
        vals = [v for _, v in tl.series_since("ingest_lag_versions", 0.0)]
        assert max(vals) >= 1.0 and vals[-1] == 0.0
        d = dict(zip(refreshed.to_pydict()["k"], refreshed.to_pydict()["s"]))
        assert d == {0: 4, 1: 12}
        assert get_registry().counter(
            "blaze_ingest_appends_total", "").total() - appends0 >= 1
        rep = tl.health_report()
        assert rep["samples"] >= 3
        assert rep["critical_intervals"] == 0
    assert _tl_threads() == []
