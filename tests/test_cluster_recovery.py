"""Worker-death survival (ISSUE 9): lineage-based stage recovery, worker
supervision + exclusion + circuit breaker, atomic shuffle commits, and the
serve layer's typed retryable error (reference: Spark's DAGScheduler
resubmitting stages on FetchFailedException + executor blacklisting,
SURVEY.md §5.3/§5.4)."""

import glob
import os
import threading
import time

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from blaze_tpu.config import Config, config_override
from blaze_tpu.ir import exprs as E
from blaze_tpu.ir import nodes as N
from blaze_tpu.ir import types as T
from blaze_tpu.runtime.session import Session, _QueryRun
from tests.util import CrashAlways, CrashOnce


@pytest.fixture(scope="module")
def data_files(tmp_path_factory):
    td = tmp_path_factory.mktemp("recoverydata")
    rng = np.random.default_rng(31)
    paths = []
    for p in range(2):
        n = 4000
        tbl = pa.table({
            "store": pa.array(rng.integers(1, 40, n), type=pa.int64()),
            "amt": pa.array(rng.integers(0, 1000, n), type=pa.int64()),
        })
        path = str(td / f"f{p}.parquet")
        pq.write_table(tbl, path)
        paths.append(path)
    return paths


def _agg_plan(paths, parts=2, reducers=3):
    from blaze_tpu.ops.parquet import scan_node_for_files

    scan = scan_node_for_files(paths, num_partitions=parts)
    ex = N.ShuffleExchange(scan,
                           N.HashPartitioning([E.Column("store")], reducers))
    return N.Agg(ex, E.AggExecMode.HASH_AGG, [("store", E.Column("store"))], [
        N.AggColumn(E.AggExpr(E.AggFunction.SUM, [E.Column("amt")], T.I64),
                    E.AggMode.COMPLETE, "total")])


def _sorted_rows(pydict):
    return sorted(zip(pydict["store"], pydict["total"]))


# -- atomic commit footer -----------------------------------------------------


def test_map_output_footer_verifies(data_files, tmp_path):
    """Committed map outputs end in a valid footer; truncation (a torn
    write surviving a crash) and garbage tails read as invalid."""
    import shutil

    from blaze_tpu.runtime.recovery import (FOOTER_LEN, check_map_output,
                                            ShuffleOutputMissing,
                                            verify_map_output)

    # force the shm tier: this test pokes committed map FILES, and the
    # pool-less default (zero-copy process tier) commits in-memory segments
    # with footer-only marker files instead
    with Session(conf=Config(zero_copy_tier="shm")) as sess:
        qrun = _QueryRun(0)
        sess._tls.qrun = qrun
        sess._lower(_agg_plan(data_files))
        sess._tls.qrun = None
        datafiles = sorted(glob.glob(
            os.path.join(sess.shuffle_root, "shuffle_*", "map_*.data")))
        assert datafiles, "map stage must have committed outputs"
        for f in datafiles:
            assert verify_map_output(f) is None
            assert verify_map_output(f, full=True) is None
            assert os.path.getsize(f) > FOOTER_LEN

        # torn file: footer gone -> invalid
        torn = str(tmp_path / "torn.data")
        shutil.copy(datafiles[0], torn)
        with open(torn, "r+b") as fh:
            fh.truncate(os.path.getsize(torn) - 5)
        assert verify_map_output(torn) is not None
        with pytest.raises(ShuffleOutputMissing):
            check_map_output(torn)

        # bit flip inside the payload: only the full crc check sees it
        flipped = str(tmp_path / "flip.data")
        shutil.copy(datafiles[0], flipped)
        with open(flipped, "r+b") as fh:
            fh.seek(3)
            b = fh.read(1)
            fh.seek(3)
            fh.write(bytes([b[0] ^ 0xFF]))
        assert verify_map_output(flipped, full=True) is not None

    assert verify_map_output(datafiles[0]) == "missing"  # session closed


# -- lineage recompute (in-driver reduce side) --------------------------------


def test_missing_and_torn_map_recompute(data_files):
    """A reduce task hitting a missing or torn upstream map output triggers
    lineage recompute of exactly those maps instead of failing the query."""
    from blaze_tpu.obs.telemetry import get_registry

    # shm tier for the same reason as above: deleting/truncating committed
    # map files is the scenario under test, so the maps must write real
    # data files, not process-tier markers
    with Session(conf=Config(zero_copy_tier="shm")) as sess:
        oracle = _sorted_rows(sess.execute_to_table(
            _agg_plan(data_files)).to_pydict())

        def lower_and_files(plan):
            before = set(glob.glob(
                os.path.join(sess.shuffle_root, "shuffle_*", "map_*.data")))
            qrun = _QueryRun(0)
            sess._tls.qrun = qrun
            lowered = sess._lower(plan)
            sess._tls.qrun = None
            after = sorted(glob.glob(
                os.path.join(sess.shuffle_root, "shuffle_*", "map_*.data")))
            return lowered, [f for f in after if f not in before]

        def recovered_count():
            snap = get_registry().to_raw()
            series = snap["blaze_cluster_maps_recomputed_total"]["series"]
            return series[0]["value"] if series else 0

        # missing: the file is deleted outright
        lowered, files = lower_and_files(_agg_plan(data_files, reducers=4))
        n0 = recovered_count()
        os.remove(files[0])
        got = _sorted_rows(sess.execute_to_table(lowered).to_pydict())
        assert got == oracle
        assert recovered_count() == n0 + 1

        # torn: the footer is cut off mid-file
        lowered, files = lower_and_files(_agg_plan(data_files, reducers=5))
        with open(files[1], "r+b") as fh:
            fh.truncate(max(0, os.path.getsize(files[1]) - 7))
        got = _sorted_rows(sess.execute_to_table(lowered).to_pydict())
        assert got == oracle
        assert recovered_count() == n0 + 2


# -- worker supervision / exclusion / breaker ---------------------------------


def test_exclusion_list_and_death_dedup():
    """_note_death counts one death per worker generation, excludes the
    slot (TTL'd), and the liveness guarantee keeps an all-excluded pool
    serving."""
    from blaze_tpu.runtime.cluster import WorkerPool

    pool = WorkerPool(2)
    try:
        w0, w1 = pool.workers
        assert pool._note_death(w0, "test") is True
        assert pool._note_death(w0, "test") is False  # same generation
        assert pool.deaths_total == 1
        assert 0 in pool.excluded_workers()
        assert pool._sit_out(w0) is True  # w1 is eligible
        assert pool._note_death(w1, "test") is True
        assert pool._sit_out(w0) is False  # everyone excluded: keep serving
        # TTL expiry clears the exclusion on the next check
        with pool._mu:
            pool._excluded[0] = time.monotonic() - 1.0
        assert pool._sit_out(w0) is False
        assert 0 not in pool.excluded_workers()
    finally:
        pool.close()


@pytest.mark.slow
def test_circuit_breaker_aborts_stage(data_files, tmp_path):
    """More worker deaths than fault_max_worker_deaths within one stage
    aborts with the typed WorkerPoolBroken instead of retrying forever."""
    from blaze_tpu.ops.parquet import scan_node_for_files
    from blaze_tpu.runtime.cluster import WorkerPoolBroken

    scan = scan_node_for_files(data_files, num_partitions=2)
    proj = N.Projection(scan, [
        E.Column("store"),
        E.PyUDF(CrashOnce(str(tmp_path / "breaker.marker")),
                [E.Column("store")], T.I64, "crash1"),
    ], ["store", "crashed"])
    plan = N.ShuffleExchange(proj,
                             N.HashPartitioning([E.Column("store")], 2))
    conf = Config(fault_max_worker_deaths=0)
    with Session(conf=conf, num_worker_processes=2) as s:
        with pytest.raises(WorkerPoolBroken):
            s.execute_to_table(plan)


# -- chaos: kill a real worker mid-stage --------------------------------------


@pytest.mark.quick
def test_chaos_smoke_one_kill(data_files, tmp_path):
    """Quick-tier chaos smoke: one deterministic worker death mid-map-stage
    (CrashOnce hard-kills its host on first call); the query's result is
    bit-identical to the unkilled in-driver run, the death is counted, and
    the lost worker has a retrievable incident bundle."""
    from blaze_tpu.obs.dump import list_incidents, load_incident
    from blaze_tpu.obs.telemetry import get_registry
    from blaze_tpu.ops.parquet import scan_node_for_files

    def plan(crash_marker=None):
        scan = scan_node_for_files(data_files, num_partitions=2)
        # "crashed" is store passed through the crash UDF (identity after
        # the kill) — and the agg CONSUMES it, so pruning can't drop it
        crashed = E.Column("store") if crash_marker is None else \
            E.PyUDF(CrashOnce(crash_marker), [E.Column("store")], T.I64,
                    "crash1")
        proj = N.Projection(scan,
                            [E.Column("store"), E.Column("amt"), crashed],
                            ["store", "amt", "crashed"])
        ex = N.ShuffleExchange(
            proj, N.HashPartitioning([E.Column("store")], 2))
        return N.Agg(ex, E.AggExecMode.HASH_AGG,
                     [("store", E.Column("store"))], [
            N.AggColumn(E.AggExpr(E.AggFunction.SUM, [E.Column("amt")],
                                  T.I64), E.AggMode.COMPLETE, "total"),
            N.AggColumn(E.AggExpr(E.AggFunction.SUM, [E.Column("crashed")],
                                  T.I64), E.AggMode.COMPLETE, "chk")])

    with Session() as s_local:
        expect = _sorted_rows(s_local.execute_to_table(
            plan()).to_pydict())

    marker = str(tmp_path / "chaos.marker")
    incident_dir = str(tmp_path / "incidents")
    conf = Config(incident_dir=incident_dir)

    def deaths():
        snap = get_registry().to_raw()
        series = snap["blaze_cluster_worker_deaths_total"]["series"]
        return series[0]["value"] if series else 0

    d0 = deaths()
    with Session(conf=conf, num_worker_processes=2) as s:
        got = _sorted_rows(s.execute_to_table(
            plan(crash_marker=marker)).to_pydict())
    assert os.path.exists(marker), "the chaos kill must actually have fired"
    assert got == expect, "result after worker death differs from clean run"
    assert deaths() > d0
    lost = [i for i in list_incidents(conf) if i["kind"] == "worker_lost"]
    assert lost, "every killed worker writes an incident bundle"
    bundle = load_incident(lost[0]["id"], conf)
    assert bundle["extra"]["context"] in ("mid_task", "heartbeat",
                                          "push_shared")
    assert "wid" in bundle["extra"]


@pytest.mark.slow
def test_kill_worker_mid_stage_bit_identical(data_files):
    """An asynchronous hard kill (the chaos-soak primitive) mid-query: the
    task retries elsewhere, the worker is excluded + respawned, and the
    result matches the unkilled run exactly."""
    plan = _agg_plan(data_files, parts=6, reducers=4)
    with Session() as s_local:
        expect = _sorted_rows(s_local.execute_to_table(plan).to_pydict())
    with Session(num_worker_processes=2) as s:
        killer = threading.Timer(0.4, lambda: s.pool.kill_worker(0))
        killer.start()
        try:
            got = _sorted_rows(s.execute_to_table(plan).to_pydict())
        finally:
            killer.cancel()
        deaths = s.pool.deaths_total
    assert got == expect
    # the timer may fire before, during, or (rarely, tiny stage) after the
    # stage window — but the kill itself always lands and is always noticed
    assert deaths >= 1


# -- RSS: attempt-id dedup on re-commit ---------------------------------------


@pytest.mark.quick
def test_celeborn_recommit_attempt_dedup():
    """A re-committed map (retry after a worker death) must not double-serve:
    MapperEnd's first-wins commit pins the winning attempt id, and fetches
    serve only that attempt's pushed blocks (runtime/rss.py
    CelebornShuffleClient.writer_for_map)."""
    from blaze_tpu.runtime.rss import (CelebornShuffleClient, RssClient,
                                       RssServer)

    srv = RssServer()
    try:
        c = RssClient(srv.sock_path, app="recommit-test", shuffle_id=9)
        sc = CelebornShuffleClient(c, num_mappers=1, num_partitions=1)
        sc.register()
        w1 = sc.writer_for_map(0, attempt_id=1)
        w1.write(0, b"attempt1-payload")
        w1.flush()
        sc.commit_files()
        first = sc.fetch(0)
        assert first, "committed attempt must serve"
        # the retry re-commits the same map under a fresh attempt id
        w2 = sc.writer_for_map(0, attempt_id=2)
        w2.write(0, b"attempt2-payload")
        w2.flush()
        sc.commit_files()
        assert sc.fetch(0) == first, "re-commit must not replace or add"
        # distinct writers drew distinct attempt ids by default too
        wa, wb = sc.writer_for_map(0), sc.writer_for_map(0)
        assert wa.attempt_id != wb.attempt_id
    finally:
        srv.close()


# -- serve: typed retryable error after retry exhaustion ----------------------


@pytest.mark.slow
def test_serve_worker_loss_is_typed_retryable(data_files, tmp_path):
    """A query whose workers keep dying exhausts the retry budget and fails
    with QueryRetryable (retryable=True, incident bundle id attached); the
    scheduler releases its memory exactly once and keeps serving."""
    from blaze_tpu.obs.dump import load_incident
    from blaze_tpu.ops.parquet import scan_node_for_files
    from blaze_tpu.runtime.memmgr import MemManager
    from blaze_tpu.serve import QueryRetryable, QueryScheduler

    scan = scan_node_for_files(data_files, num_partitions=2)
    proj = N.Projection(scan, [
        E.Column("store"),
        E.PyUDF(CrashAlways(), [E.Column("store")], T.I64, "crashN"),
    ], ["store", "crashed"])
    doomed = N.ShuffleExchange(proj,
                               N.HashPartitioning([E.Column("store")], 2))
    conf = Config(incident_dir=str(tmp_path / "incidents"))
    with Session(conf=conf, num_worker_processes=2) as sess:
        with QueryScheduler(sess, max_concurrent=1) as sched:
            h = sched.submit(doomed, label="doomed")
            with pytest.raises(QueryRetryable) as ei:
                h.result(timeout=120)
            err = ei.value
            assert err.retryable is True
            assert err.incident_id, "the retryable error carries forensics"
            bundle = load_incident(err.incident_id, conf)
            assert bundle is not None
            assert bundle["label"] == "doomed"
            # memory group released exactly once, nothing leaked
            assert h._released is True
            mm = MemManager._instance
            assert h.mem_group not in mm.stats()["reservations"]
            # the pool still serves: a clean query right after succeeds
            h2 = sched.submit(_agg_plan(data_files), label="after")
            table = h2.result(timeout=120)
            assert table.num_rows > 0


# -- failpoint-driven degradation (ISSUE 12) ----------------------------------
#
# Paranoid-mode corruption, resource-exhaustion fallbacks, hard task
# timeouts, and the serve layer's transparent auto-retry — each proven
# bit-identical against an uninjected oracle on a real 2-worker pool.


@pytest.fixture(autouse=True)
def _disarm_failpoints():
    """No failpoint armed in one test may leak into the next (the registry
    is process-global; Session.__init__ arms from conf)."""
    from blaze_tpu.runtime import failpoints

    failpoints.disarm()
    yield
    failpoints.disarm()
    failpoints.unhang()


@pytest.mark.parametrize("tier", ["shm", "ipc"])
def test_corrupt_frame_recovers_bit_identical(data_files, tier):
    """Paranoid mode (full crc verification) + the frame.decode failpoint
    flipping committed payload bytes on disk: corruption is detected as a
    crc mismatch, routed into lineage recompute like a lost output, and the
    result matches the clean run exactly — on both the shm and ipc tiers,
    over a real 2-worker pool."""
    from blaze_tpu.obs.telemetry import get_registry

    with Session() as s_clean:
        oracle = _sorted_rows(s_clean.execute_to_table(
            _agg_plan(data_files, parts=2, reducers=3)).to_pydict())

    def recomputed():
        snap = get_registry().to_raw()
        series = snap["blaze_cluster_maps_recomputed_total"]["series"]
        return series[0]["value"] if series else 0

    n0 = recomputed()
    # triggers count per PROCESS: every2:x1 makes each worker corrupt the
    # 2nd output it verifies, exactly once, wherever the schedule lands it.
    # config_override (not just Session(conf=...)) because the paranoia
    # level must also reach the DRIVER's global-config readers (providers,
    # lineage recompute), not only the conf shipped to workers.
    with config_override(zero_copy_tier=tier, shuffle_verify_checksum=True,
                         failpoints="frame.decode=corrupt:every2:x1",
                         failpoint_seed=12):
        with Session(num_worker_processes=2) as sess:
            got = _sorted_rows(sess.execute_to_table(
                _agg_plan(data_files, parts=2, reducers=3)).to_pydict())
    assert got == oracle, "corrupted frames must recompute, not change rows"
    assert recomputed() > n0, "corruption must route through lineage"


def test_shm_enospc_degrades_to_spill_tier(data_files):
    """A shm-tier commit hitting ENOSPC mid-query degrades that map output
    to the spill dir behind a redirect marker: same rows, the
    shuffle_tier_degraded tripwire fires, and the degraded copies are
    reclaimed with the query (no leaks outlive the session)."""
    with Session() as s_clean:
        oracle = _sorted_rows(s_clean.execute_to_table(
            _agg_plan(data_files, parts=2, reducers=3)).to_pydict())

    # every1: triggers count per PROCESS, and each pool worker only commits
    # a couple of maps — firing on every commit keeps this deterministic
    conf = Config(zero_copy_tier="shm",
                  failpoints="shm.commit=enospc:every1", failpoint_seed=12)
    with Session(conf=conf, num_worker_processes=2) as sess:
        got = _sorted_rows(sess.execute_to_table(
            _agg_plan(data_files, parts=2, reducers=3)).to_pydict())
        degraded = sess.metrics.total("shuffle_tier_degraded")
        spill_dir = sess.conf.spill_dir
    assert got == oracle, "degraded outputs must serve identical rows"
    assert degraded > 0, "the enospc failpoint must exercise the degrade"
    leaks = glob.glob(os.path.join(spill_dir, "degraded_shuffle", "*"))
    assert not leaks, f"degraded copies leaked: {leaks}"


@pytest.mark.slow
def test_hung_task_times_out_and_reroutes(data_files):
    """task_timeout_s on top of speculation: a task hung past the hard
    timeout is cancelled at the process level, charged to the retry budget,
    rerouted, and the hung worker is marked suspect — the query still
    returns the exact clean-run rows."""
    from blaze_tpu.obs.telemetry import get_registry

    with Session() as s_clean:
        oracle = _sorted_rows(s_clean.execute_to_table(
            _agg_plan(data_files, parts=4, reducers=3)).to_pydict())

    def timed_out():
        # the counter has no series until its first inc — tolerate absence
        snap = get_registry().to_raw()
        series = snap.get("blaze_cluster_tasks_timed_out_total", {}).get(
            "series", [])
        return series[0]["value"] if series else 0

    n0 = timed_out()
    conf = Config(task_timeout_s=1.5, fault_exclusion_ttl_s=2.0,
                  failpoints="worker.task=hang:every2:600",
                  failpoint_seed=12)
    t0 = time.monotonic()
    with Session(conf=conf, num_worker_processes=2) as sess:
        got = _sorted_rows(sess.execute_to_table(
            _agg_plan(data_files, parts=4, reducers=3)).to_pydict())
        deaths = sess.pool.deaths_total
    wall = time.monotonic() - t0
    assert got == oracle
    assert timed_out() > n0, "the hard timeout must have fired"
    assert deaths >= 1, "a timed-out attempt kills its worker"
    assert wall < 120, "hung attempts must not stall the query"


class CrashFirstNTasks:
    """Crash fixture UDF: hard-kills the hosting WORKER on each call until
    ``n`` crash markers exist, then passes through. Lets a test exhaust the
    pool's per-task retry budget on the FIRST query attempt and succeed on
    the serve layer's transparent re-execution."""

    def __init__(self, marker_dir, n):
        self.marker_dir = marker_dir
        self.n = n

    def __call__(self, x):
        import os

        if os.environ.get("BLAZE_TPU_FAILPOINT_SALT") is None:
            return x  # in-driver recompute paths survive
        os.makedirs(self.marker_dir, exist_ok=True)
        done = len(os.listdir(self.marker_dir))
        if done < self.n:
            with open(os.path.join(self.marker_dir, f"crash_{done}"), "w"):
                pass
            os._exit(9)
        return x


@pytest.mark.slow
def test_serve_auto_retry_hides_worker_loss(data_files, tmp_path):
    """A query whose first execution exhausts the pool retry budget is
    transparently re-executed by the scheduler (backoff + jitter inside the
    deadline): the CLIENT sees a clean result, never QueryRetryable, and
    the handle records the retry history."""
    from blaze_tpu.obs.telemetry import get_registry
    from blaze_tpu.ops.parquet import scan_node_for_files
    from blaze_tpu.serve import QueryScheduler

    scan = scan_node_for_files(data_files, num_partitions=2)
    proj = N.Projection(scan, [
        E.Column("store"),
        # n=5 over 2 tasks: one task is guaranteed 3 crashing attempts,
        # exhausting the pool's max_task_retries=2 budget on the FIRST
        # execution — which is what forces a serve-layer retry
        E.PyUDF(CrashFirstNTasks(str(tmp_path / "crashes"), 5),
                [E.Column("store")], T.I64, "crashN"),
    ], ["store", "crashed"])
    plan = N.ShuffleExchange(proj,
                             N.HashPartitioning([E.Column("store")], 2))

    def retries():
        # the counter has no series until its first inc — tolerate absence
        snap = get_registry().to_raw()
        series = snap.get("blaze_serve_retries_total", {}).get("series", [])
        return series[0]["value"] if series else 0

    n0 = retries()
    conf = Config(incident_dir=str(tmp_path / "incidents"),
                  fault_max_worker_deaths=8, fault_exclusion_ttl_s=1.0)
    with Session(conf=conf, num_worker_processes=2) as sess:
        with QueryScheduler(sess, max_concurrent=1) as sched:
            h = sched.submit(plan, label="flaky")
            table = h.result(timeout=180)  # no QueryRetryable raised
    assert table.num_rows > 0
    assert h.retries, "the handle must record its transparent retries"
    assert retries() > n0
    assert h.snapshot().get("retries") == len(h.retries)
