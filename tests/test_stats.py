"""Query stats plane (ISSUE 11): per-stage runtime statistics and
fingerprint-keyed query profiles.

Covers the acceptance surface: a QueryProfile with per-stage partition
sizes/rows and est-vs-actual cardinalities; fingerprint
stability across runs (and across data directories — paths are normalized
out); the capped/GC'd profile store and its HTTP surface
(``/debug/profiles[/<fp>]``, ``stage_stats`` lines in ``/debug/queries``);
the stats-disabled overhead guard; and the real 2-worker pool across shuffle
tiers (slow tier)."""

import json
import os
import time
import urllib.request

import numpy as np
import pyarrow as pa
import pytest

from blaze_tpu.config import Config, config_override
from blaze_tpu.core import ColumnarBatch
from blaze_tpu.ir import exprs as E
from blaze_tpu.ir import nodes as N
from blaze_tpu.ir import types as T
from blaze_tpu.obs.stats import (list_profiles, load_profile,
                                 plan_fingerprint, save_profile,
                                 stage_summary_line)
from blaze_tpu.runtime.session import Session

F = E.AggFunction
M = E.AggMode
HASH = E.AggExecMode.HASH_AGG


def _col(n):
    return E.Column(n)


def _two_stage_plan(schema, nparts, reducers=3):
    scan = N.FFIReader(schema=schema, resource_id="src", num_partitions=nparts)
    partial = N.Agg(scan, HASH, [("k", _col("k"))],
                    [N.AggColumn(E.AggExpr(F.SUM, [_col("v")], T.I64),
                                 M.PARTIAL, "s")])
    ex = N.ShuffleExchange(partial, N.HashPartitioning([_col("k")], reducers))
    return N.Agg(ex, HASH, [("k", _col("k"))],
                 [N.AggColumn(E.AggExpr(F.SUM, [_col("v")], T.I64),
                              M.FINAL, "s")])


def _make_parts(seed=7, n=20_000, nparts=2, keys=300):
    rng = np.random.default_rng(seed)
    b = ColumnarBatch.from_pydict({
        "k": rng.integers(0, keys, n).tolist(),
        "v": rng.integers(0, 1000, n).tolist()})
    per = n // nparts
    return [[b.slice(i * per, per)] for i in range(nparts)]


def _run_profiled(tmp_path, parts, **conf_kw):
    """Run the two-stage agg in a fresh session with the profile store
    pointed at tmp; returns (pydict result, profile, session query record)."""
    store = str(tmp_path / "profiles")
    with config_override(profile_store_dir=store, **conf_kw):
        with Session() as sess:
            sess.resources["src"] = lambda p: [x.to_arrow() for x in parts[p]]
            out = sess.execute_to_pydict(
                _two_stage_plan(parts[0][0].schema, len(parts)))
            profile = sess.profile()
            record = sess.query_log[-1]
    return out, profile, record


def _pq_plan(tmp_path, fname="t.parquet", rows=10_000, keys=7):
    """Parquet-backed two-stage agg (pool-shippable: no resource lambdas)."""
    import pyarrow.parquet as pq

    from blaze_tpu.ops.parquet import scan_node_for_files

    path = str(tmp_path / fname)
    pq.write_table(pa.table({"k": [i % keys for i in range(rows)],
                             "v": list(range(rows))}), path)
    scan = scan_node_for_files([path], num_partitions=2)
    partial = N.Agg(scan, HASH, [("k", _col("k"))],
                    [N.AggColumn(E.AggExpr(F.SUM, [_col("v")], T.I64),
                                 M.PARTIAL, "s")])
    ex = N.ShuffleExchange(partial, N.HashPartitioning([_col("k")], 3))
    return N.Agg(ex, HASH, [("k", _col("k"))],
                 [N.AggColumn(E.AggExpr(F.SUM, [_col("v")], T.I64),
                              M.FINAL, "s")])


# -- stage records ---------------------------------------------------------------


@pytest.mark.quick
def test_stage_summary_line_unit():
    line = stage_summary_line({"stage": 0, "kind": "shuffle_map/shm",
                               "partitions": 4, "total_bytes": 2048,
                               "total_rows": 10, "partition_skew_ratio": 2.5,
                               "recovered_tasks": 1})
    assert line.startswith("stage 0 [shuffle_map/shm] partitions=4")
    assert "row_count=10" in line and "max/med=2.5" in line
    assert "recovered=1" in line and "rows=" not in line.replace(
        "row_count=", "")


@pytest.mark.quick
@pytest.mark.parametrize("enabled", [True, False])
def test_partition_rows_follow_stats_enabled(tmp_path, enabled):
    """The shuffle writer counts a map's rows a partition only where the
    session's conf has stats on."""
    parts = _make_parts(n=4_000)
    with config_override(profile_store_dir=str(tmp_path / "profiles"),
                         stats_enabled=enabled):
        with Session() as sess:
            sess.resources["src"] = lambda p: [x.to_arrow() for x in parts[p]]
            out = sess.execute_to_pydict(
                _two_stage_plan(parts[0][0].schema, len(parts)))
            profile = sess.profile()
            rows = sum(sess.metrics.total(f"part_rows_{r}") for r in range(3))
    assert len(out["k"]) == 300
    assert (profile is not None) is enabled
    assert (rows > 0) is enabled


# -- fingerprints --------------------------------------------------------------


@pytest.mark.quick
def test_fingerprint_stable_and_path_normalized(tmp_path):
    import pyarrow.parquet as pq

    from blaze_tpu.ops.parquet import scan_node_for_files

    t = pa.table({"k": [1, 2], "v": [3, 4]})
    d1, d2 = tmp_path / "a", tmp_path / "b"
    d1.mkdir(), d2.mkdir()
    pq.write_table(t, str(d1 / "t.parquet"))
    pq.write_table(t, str(d2 / "t.parquet"))
    p1 = scan_node_for_files([str(d1 / "t.parquet")], num_partitions=1)
    p2 = scan_node_for_files([str(d2 / "t.parquet")], num_partitions=1)
    # same plan shape from different data directories -> same fingerprint
    assert plan_fingerprint(p1) == plan_fingerprint(p2)
    # built twice -> deterministic
    assert plan_fingerprint(p1) == plan_fingerprint(
        scan_node_for_files([str(d1 / "t.parquet")], num_partitions=1))
    # a different plan -> different fingerprint
    assert plan_fingerprint(N.Filter(p1, [E.BinaryExpr(
        E.BinaryOp.GT, _col("k"), E.Literal(1, T.I64))])) \
        != plan_fingerprint(p1)


@pytest.mark.quick
def test_fingerprint_stable_across_runs(tmp_path):
    parts = _make_parts()
    _, prof1, _ = _run_profiled(tmp_path, parts)
    _, prof2, _ = _run_profiled(tmp_path, parts)
    assert prof1["fingerprint"] == prof2["fingerprint"]


# -- the end-to-end profile ----------------------------------------------------


@pytest.mark.quick
def test_profile_process_tier_end_to_end(tmp_path):
    parts = _make_parts()
    out, profile, record = _run_profiled(tmp_path, parts)
    assert len(out["k"]) == 300
    assert profile is not None and record["stats"] is profile
    assert profile["state"] == "done"
    assert profile["rows"] == 300

    # one map stage with per-reducer partition sizes + row counts
    stages = [s for s in profile["stages"] if s["stage"] >= 0]
    assert stages and stages[0]["kind"].startswith("shuffle_map/")
    s0 = stages[0]
    assert s0["partitions"] == 3 and len(s0["partition_bytes"]) == 3
    assert s0["total_bytes"] == sum(s0["partition_bytes"])
    # map-OUTPUT rows: each of 2 maps partial-aggs to <=300 groups, so the
    # shuffle carries between 300 (disjoint) and 600 (full overlap) rows
    assert sum(s0["partition_rows"]) == s0["total_rows"]
    assert 300 <= s0["total_rows"] <= 600
    assert s0["partition_skew_ratio"] >= 1.0

    # operators: est-vs-actual pairing (scan + both aggs have estimates,
    # exchange plumbing pairs to None)
    ops = {o["op"]: o for o in profile["operators"]}
    assert ops["FFIReaderExec"]["actual_rows"] == 20_000
    agg_recs = [o for o in profile["operators"] if o["op"] == "AggExec"]
    assert len(agg_recs) == 2
    assert all(o["est_rows"] is not None for o in agg_recs)
    assert any(o["est_rows"] is None for o in profile["operators"])

    # residency tripwires: process tier elides all serde
    assert profile["residency"]["shuffle_bytes_serialized"] == 0
    assert profile["residency"]["serde_elided_batches"] > 0
    assert profile["recovery"] == []


@pytest.mark.quick
def test_session_profile_lookup_forms(tmp_path):
    parts = _make_parts(seed=11)
    store = str(tmp_path / "profiles")
    with config_override(profile_store_dir=store):
        with Session() as sess:
            sess.resources["src"] = lambda p: [x.to_arrow() for x in parts[p]]
            plan = _two_stage_plan(parts[0][0].schema, len(parts))
            sess.execute_to_pydict(plan)
            prof = sess.profile()  # None -> last finished query
            assert prof is not None
            fp = prof["fingerprint"]
            assert sess.profile(fp)["fingerprint"] == fp  # by fingerprint
            assert sess.profile(plan)["fingerprint"] == fp  # by plan
            assert sess.profile(sess.query_log[-1]) is prof  # by record
        # store outlives the session: a NEW session reads it back
        with Session() as sess2:
            assert sess2.profile(fp)["fingerprint"] == fp
    assert os.path.exists(os.path.join(store, fp + ".json"))


@pytest.mark.quick
def test_explain_analyze_includes_stats(tmp_path):
    parts = _make_parts(seed=13)
    with config_override(profile_store_dir=str(tmp_path / "p")):
        with Session() as sess:
            sess.resources["src"] = lambda p: [x.to_arrow() for x in parts[p]]
            text = sess.explain_analyze(
                _two_stage_plan(parts[0][0].schema, len(parts)))
    assert "stage 0" in text and "partitions=3" in text
    assert "Cardinality (estimated vs actual)" in text
    assert "part_rows[" in text  # writer per-reducer rows summarized


# -- the profile store ---------------------------------------------------------


@pytest.mark.quick
def test_profile_store_cap_and_gc(tmp_path):
    store = str(tmp_path / "profiles")
    conf = Config(profile_store_dir=store, profile_store_max=3)
    for i in range(5):
        save_profile({"fingerprint": f"fp{i:02d}", "wall_s": i}, conf)
        time.sleep(0.01)  # distinct mtimes for deterministic GC order
    names = sorted(os.listdir(store))
    assert len(names) == 3
    assert names == ["fp02.json", "fp03.json", "fp04.json"]  # newest kept
    # listing is newest-first
    listed = [p["fingerprint"] for p in list_profiles(conf)]
    assert listed == ["fp04", "fp03", "fp02"]
    assert load_profile("fp04", conf)["wall_s"] == 4
    assert load_profile("fp00", conf) is None  # GC'd
    assert load_profile("../../etc/passwd", conf) is None  # sanitized
    # disabled store: no writes, no raise
    assert save_profile({"fingerprint": "x"},
                        Config(profile_store_dir="", profile_store_max=3)) \
        is None


# -- HTTP surface --------------------------------------------------------------


def _get(port, path):
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                timeout=10) as r:
        return r.read().decode()


@pytest.mark.quick
def test_http_profiles_and_query_stage_stats(tmp_path):
    from blaze_tpu.runtime.http import ProfilingService

    parts = _make_parts(seed=17)
    store = str(tmp_path / "profiles")
    with config_override(profile_store_dir=store):
        with Session() as sess:
            sess.resources["src"] = lambda p: [x.to_arrow() for x in parts[p]]
            sess.execute_to_pydict(
                _two_stage_plan(parts[0][0].schema, len(parts)))
            fp = sess.profile()["fingerprint"]
            svc = ProfilingService.start(sess)
            try:
                idx = json.loads(_get(svc.port, "/debug/profiles"))
                assert any(p["fingerprint"] == fp for p in idx)
                full = json.loads(_get(svc.port, f"/debug/profiles/{fp}"))
                assert full["fingerprint"] == fp and full["stages"]
                try:
                    _get(svc.port, "/debug/profiles/nope")
                    assert False, "unknown fingerprint must 404"
                except urllib.error.HTTPError as exc:
                    assert exc.code == 404
                queries = json.loads(_get(svc.port, "/debug/queries"))
                done = [q for q in queries if q.get("state") == "done"]
                assert done and any("stage 0" in line
                                    for line in done[-1]["stage_stats"])
                assert done[-1]["fingerprint"] == fp
            finally:
                ProfilingService.stop()


# -- disabled-path overhead guard ----------------------------------------------


@pytest.mark.quick
def test_stats_disabled_overhead_under_5_percent(tmp_path):
    """Mirror of the telemetry guard: with stats_enabled=False no plane is
    built, and the cost of the check that remains (the shuffle writers'
    ``conf.stats_enabled`` test before a partition's row count) scaled by a
    generous event count stays under 5% of the query wall."""
    n = 500_000
    b = ColumnarBatch.from_pydict({"k": [i % 97 for i in range(n)],
                                   "v": list(range(n))})
    with Session(conf=Config(batch_size=65_536, stats_enabled=False)) as sess:
        assert not sess.conf.stats_enabled
        sess.resources["src"] = lambda p: [b.to_arrow()]
        scan = N.FFIReader(schema=b.schema, resource_id="src",
                           num_partitions=1)
        plan = N.Agg(scan, HASH, [("k", _col("k"))],
                     [N.AggColumn(E.AggExpr(F.SUM, [_col("v")], T.I64),
                                  M.COMPLETE, "total")])
        t0 = time.perf_counter_ns()
        out = sess.execute_to_pydict(plan)
        wall_ns = time.perf_counter_ns() - t0
        assert len(out["k"]) == 97
        assert sess.profile() is None  # no plane, no profile
        events = sess.metrics.total("output_batches")

        ITER = 100_000
        conf, noted = sess.conf, 0
        t0 = time.perf_counter_ns()
        for _ in range(ITER):
            if conf.stats_enabled:
                noted += 1
        bench_ns = time.perf_counter_ns() - t0
        assert noted == 0
    per_note_ns = bench_ns / ITER
    overhead_ns = per_note_ns * 4 * max(events, 32)
    assert overhead_ns < 0.05 * wall_ns, (
        f"disabled stats {overhead_ns / 1e6:.2f}ms vs query "
        f"{wall_ns / 1e6:.1f}ms: disabled-path overhead exceeds 5%")
    assert per_note_ns < 2_000, f"disabled note {per_note_ns:.0f}ns"


# -- real 2-worker pool across tiers (slow) ------------------------------------


@pytest.mark.slow
@pytest.mark.parametrize("tier_conf,expect_kind", [
    ({}, "shuffle_map/shm"),  # pool forces shm
    ({"zero_copy_shuffle": False}, "shuffle_map/ipc"),
])
def test_pool_two_workers_stats(tmp_path, tier_conf, expect_kind):
    """StatsPlane over a real 2-worker pool: partition rows recorded from
    worker-side writers, stage kind labels the negotiated tier, and the
    profile reaches the store."""
    plan = _pq_plan(tmp_path)
    store = str(tmp_path / "profiles")
    with config_override(profile_store_dir=store, **tier_conf):
        with Session(num_worker_processes=2) as sess:
            out = sess.execute_to_pydict(plan)
            profile = sess.profile()
    assert len(out["k"]) == 7
    assert profile is not None
    stages = [s for s in profile["stages"] if s.get("kind", "").startswith(
        "shuffle_map/")]
    assert stages and stages[0]["kind"] == expect_kind
    assert stages[0]["total_rows"] == 7
    assert sum(stages[0]["partition_rows"]) == 7
    assert os.path.exists(os.path.join(
        store, profile["fingerprint"] + ".json"))
