"""Scale soak (round-4 verdict item 4): ~1 GB of fact data through the
four bench shapes + five real TPC-DS queries under a CONSTRAINED memory
budget, so spill/merge/window-stream paths genuinely engage at volume.

Defaults: 10M bench fact rows over 32 partitions (~0.95 GB parquet across
the star tables) with a 512 MB engine budget, plus the real-query gate's
dataset scaled ~40x (2.4M store_sales). Records wall-clock, spill
count/bytes, window-stream counts, and peak RSS — the numbers BASELINE.md
cites. Reference analogue: the 1 GB TPC-DS dataset gate
(``tpcds-reusable.yml:168-260``).

Run: python scripts/scale_soak.py   (CPU; ~15-30 min)
Env: SOAK_ROWS (10_000_000), SOAK_PARTS (32), SOAK_BUDGET_MB (512),
SOAK_TPCDS_SCALE (40). SOAK_PROFILE_DIR=<dir> additionally enables span
tracing and dumps per-query trace/metrics artifacts there
(obs/dump.dump_profile; load the *_trace.json files in Perfetto).
"""

import json
import os
import resource
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROWS = int(os.environ.get("SOAK_ROWS", 10_000_000))
PARTS = int(os.environ.get("SOAK_PARTS", 32))
BUDGET_MB = int(os.environ.get("SOAK_BUDGET_MB", 128))
TPCDS_SCALE = int(os.environ.get("SOAK_TPCDS_SCALE", 40))
PROFILE_DIR = os.environ.get("SOAK_PROFILE_DIR", "")

os.environ["BENCH_ROWS"] = str(ROWS)
os.environ["BENCH_PARTITIONS"] = str(PARTS)

# ``--devices N`` (the multichip round) needs the forced host-device count
# in place BEFORE jax initializes its backends, so honor the flag here at
# import time — one command, no manual XLA_FLAGS incantation:
#   python scripts/scale_soak.py --devices 8
if "--devices" in sys.argv[1:]:
    try:
        _n_dev = int(sys.argv[sys.argv.index("--devices") + 1])
    except (IndexError, ValueError):
        _n_dev = 0
    if _n_dev > 1 and "xla_force_host_platform_device_count" \
            not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={_n_dev}").strip()
        os.environ["JAX_PLATFORMS"] = "cpu"

import jax

jax.config.update("jax_platforms", "cpu")


def peak_rss_mb() -> int:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss // 1024


def shm_roots(baseline=()) -> list:
    """blaze_tpu_shm_* roots in /dev/shm beyond ``baseline`` — the
    zero-copy plane's leak surface (segment files are unlink-safe while
    mapped, so directory entries are what a leak looks like)."""
    import glob

    return sorted(set(glob.glob("/dev/shm/blaze_tpu_shm_*")) - set(baseline))


def main():
    import bench  # repo-root bench.py (shapes, generators, oracles)
    from blaze_tpu.config import Config, set_config
    from blaze_tpu.runtime.session import Session
    from blaze_tpu.runtime.memmgr import MemManager

    set_config(Config(memory_total=BUDGET_MB << 20, memory_fraction=1.0,
                      mem_wait_timeout_s=5.0))
    out = {"rows": ROWS, "partitions": PARTS, "budget_mb": BUDGET_MB,
           "shapes": {}, "tpcds": {}}
    shm0 = shm_roots()  # roots that predate this run are not ours to gate
    with tempfile.TemporaryDirectory(prefix="blaze_soak_") as tmpdir:
        t0 = time.perf_counter()
        paths = bench.make_data(tmpdir)
        out["datagen_s"] = round(time.perf_counter() - t0, 1)
        out["data_bytes"] = sum(os.path.getsize(p)
                                for ps in paths.values() for p in ps)
        _, oracles = bench.run_baseline(paths)

        # a full-fact global sort: the one shape whose buffers CANNOT fit
        # the constrained budget — 32 concurrent range-partition sorts over
        # ~30 MB each force the sort spill/merge machinery to churn real
        # files (the round-4 verdict's "merge width, spill-file churn"
        # evidence; the agg shapes stream and never hold rows)
        def plan_big_sort(paths):
            from blaze_tpu.ir import exprs as E
            from blaze_tpu.ir import nodes as N
            from blaze_tpu.ops.parquet import scan_node_for_files

            scan = scan_node_for_files(paths["store_sales"],
                                       num_partitions=PARTS)
            orders = [E.SortOrder(E.Column("ss_sales_price"),
                                  ascending=False),
                      E.SortOrder(E.Column("ss_item_sk"))]
            ex = N.ShuffleExchange(scan, N.RangePartitioning(
                orders, PARTS, []))
            return N.Sort(ex, orders)

        def check_big_sort(table, _oracle):
            import pyarrow.compute as pc

            assert table.num_rows == ROWS, table.num_rows
            prices = table["ss_sales_price"].combine_chunks()
            # global descending order across ALL partitions
            assert pc.min(pc.subtract(
                prices.cast("float64").slice(0, len(prices) - 1),
                prices.cast("float64").slice(1))).as_py() >= 0

        shapes = list(bench.SHAPES) + [
            ("sort10M", plan_big_sort, None, None, check_big_sort, ())]
        oracles["sort10M"] = None
        for name, plan_fn, _o, _a, check_fn, _t in shapes:
            MemManager.reset()
            t0 = time.perf_counter()
            conf = Config(memory_total=BUDGET_MB << 20, memory_fraction=1.0,
                          mem_wait_timeout_s=5.0,
                          trace_enable=bool(PROFILE_DIR))
            with Session(conf=conf) as sess:
                table = sess.execute_to_table(plan_fn(paths))
                spills = sess.metrics.total("spill_count")
                spill_bytes = sess.metrics.total("spilled_bytes")
                # invariant tripwires (runtime/metrics.TRIPWIRE_METRICS):
                # split_gathers == split_batches, window_group_loops == 0,
                # window_segments > 0 on window-bearing shapes — a degraded
                # fast path shows up as a counter diff in the artifact
                from blaze_tpu.runtime.metrics import tripwire_totals

                trips = tripwire_totals(sess.metrics)
                profile = sess.profile()
                if PROFILE_DIR:
                    from blaze_tpu.obs import TRACER, dump_profile

                    dump_profile(sess, PROFILE_DIR, name)
                    TRACER.reset()
            mgr = MemManager._instance
            peak_used = int(mgr.peak_used) if mgr is not None else 0
            wall = time.perf_counter() - t0
            check_fn(table, oracles[name])  # correctness AT SCALE
            out["shapes"][name] = {
                "wall_s": round(wall, 1), "spill_count": int(spills),
                "spilled_bytes": int(spill_bytes),
                "streamed_window_partitions": trips["streamed_partitions"],
                "split_batches": trips["split_batches"],
                "split_gathers": trips["split_gathers"],
                "window_segments": trips["window_segments"],
                "window_group_loops": trips["window_group_loops"],
                "ipc_decode_in_prefetch": trips["ipc_decode_in_prefetch"],
                "fused_stages": trips["fused_stages"],
                "fused_ops": trips["fused_ops"],
                "jit_cache_hits": trips["jit_cache_hits"],
                "jit_cache_misses": trips["jit_cache_misses"],
                "fused_fallback_batches": trips["fused_fallback_batches"],
                "agg_reintern_rows": trips["agg_reintern_rows"],
                "agg_radix_buckets": trips["agg_radix_buckets"],
                "codes_shuffle_bytes": trips["codes_shuffle_bytes"],
                "shuffle_bytes_serialized": trips["shuffle_bytes_serialized"],
                "shm_bytes_mapped": trips["shm_bytes_mapped"],
                "serde_elided_batches": trips["serde_elided_batches"],
                "sharded_stages": trips["sharded_stages"],
                "device_shuffle_bytes": trips["device_shuffle_bytes"],
                "collective_bytes": trips["collective_bytes"],
                "peak_mem_used": peak_used,
                "peak_rss_mb": peak_rss_mb(),
            }
            if profile is not None:
                # stats-plane summary: the skew + partition-shape numbers a
                # soak diff (scripts/bench_diff.py) compares across runs
                out["shapes"][name]["stats"] = {
                    "fingerprint": profile["fingerprint"],
                    "device_time_fraction": profile["device_time_fraction"],
                    "stages": [{k: s.get(k) for k in (
                        "stage", "kind", "partitions", "total_bytes",
                        "total_rows", "partition_skew_ratio", "skew")}
                        for s in profile["stages"]],
                }
            print(json.dumps({name: out["shapes"][name]}), flush=True)

    soak_path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "SOAK_r10.json")
    if "tpcds" not in os.environ.get("SOAK_PHASES", "shapes,tpcds"):
        from blaze_tpu.obs.attribution import artifact_section
        from blaze_tpu.obs.timeline import timeline_artifact_section

        out.update(artifact_section())
        out.update(timeline_artifact_section())
        out["peak_rss_mb"] = peak_rss_mb()
        leaked = shm_roots(shm0)
        out["shm_segments_leaked"] = len(leaked)
        assert not leaked, f"/dev/shm leak: {leaked}"
        assert out["health"]["critical_intervals"] == 0, out["health"]
        assert out["health"]["degraded_ratio"] <= 0.5, out["health"]
        # keep a previous run's tpcds section (phase-scoped reruns merge)
        try:
            with open(soak_path) as f:
                prev = json.load(f)
            if prev.get("tpcds") and not out.get("tpcds"):
                out["tpcds"] = prev["tpcds"]
        except (OSError, ValueError):
            pass
        print(json.dumps(out))
        with open(soak_path, "w") as f:
            json.dump(out, f, indent=1)
        return

    # real-query gate at ~40x its CI size
    import tests.tpcds.data as D

    D.N_SS *= TPCDS_SCALE
    D.N_CS *= TPCDS_SCALE
    D.N_WS *= TPCDS_SCALE
    D.N_INV *= TPCDS_SCALE
    D.N_CUSTOMERS *= 4
    D.N_ADDRS *= 4
    from tests.tpcds.queries import QUERIES
    from tests.test_tpcds_queries import (_rows_equal, _sorted_if_tied)

    with tempfile.TemporaryDirectory(prefix="blaze_soak_tpcds_") as td:
        t0 = time.perf_counter()
        tables = D.generate(td)
        dfs = D.load_dfs(tables)
        out["tpcds"]["datagen_s"] = round(time.perf_counter() - t0, 1)
        out["tpcds"]["data_bytes"] = sum(os.path.getsize(p)
                                         for ps in tables.values()
                                         for p in ps)
        from blaze_tpu.frontend.converter import SparkPlanConverter

        for name in ("q3", "q7", "q53", "q67", "q96"):
            plan_json, oracle, extract, flags = QUERIES[name]()
            conv = SparkPlanConverter(tables=tables)
            res = conv.convert(json.dumps(plan_json))
            assert not [t for t in res.tags if "fallback" in t[1]]
            MemManager.reset()
            t0 = time.perf_counter()
            conf = Config(memory_total=BUDGET_MB << 20, memory_fraction=1.0,
                          mem_wait_timeout_s=5.0,
                          trace_enable=bool(PROFILE_DIR))
            with Session(conf=conf) as sess:
                table = sess.execute_to_table(res.plan)
                spills = sess.metrics.total("spill_count")
                spill_bytes = sess.metrics.total("spilled_bytes")
                from blaze_tpu.runtime.metrics import tripwire_totals

                trips = tripwire_totals(sess.metrics)
                profile = sess.profile()
                if PROFILE_DIR:
                    from blaze_tpu.obs import TRACER, dump_profile

                    dump_profile(sess, PROFILE_DIR, name)
                    TRACER.reset()
            wall = time.perf_counter() - t0
            if extract is None:
                d = table.to_pydict()
                rows = list(zip(*d.values())) if d else []
            else:
                rows = extract(table)
            got = _sorted_if_tied(rows, flags)
            want = _sorted_if_tied(oracle(dfs), flags)
            assert _rows_equal(got, want, flags), f"{name} wrong at scale"
            out["tpcds"][name] = {
                "wall_s": round(wall, 1), "rows_out": len(got),
                "spill_count": int(spills),
                "spilled_bytes": int(spill_bytes),
                "agg_reintern_rows": trips["agg_reintern_rows"],
                "agg_radix_buckets": trips["agg_radix_buckets"],
                "codes_shuffle_bytes": trips["codes_shuffle_bytes"],
                "shuffle_bytes_serialized": trips["shuffle_bytes_serialized"],
                "shm_bytes_mapped": trips["shm_bytes_mapped"],
                "serde_elided_batches": trips["serde_elided_batches"],
                "sharded_stages": trips["sharded_stages"],
                "device_shuffle_bytes": trips["device_shuffle_bytes"],
                "collective_bytes": trips["collective_bytes"],
                "peak_rss_mb": peak_rss_mb(),
            }
            if profile is not None:
                out["tpcds"][name]["stats"] = {
                    "fingerprint": profile["fingerprint"],
                    "device_time_fraction": profile["device_time_fraction"],
                    "stages": [{k: s.get(k) for k in (
                        "stage", "kind", "partitions", "total_bytes",
                        "total_rows", "partition_skew_ratio", "skew")}
                        for s in profile["stages"]],
                }
            print(json.dumps({name: out["tpcds"][name]}), flush=True)
    from blaze_tpu.obs.attribution import artifact_section
    from blaze_tpu.obs.timeline import timeline_artifact_section

    out.update(artifact_section())
    out.update(timeline_artifact_section())
    out["peak_rss_mb"] = peak_rss_mb()
    leaked = shm_roots(shm0)
    out["shm_segments_leaked"] = len(leaked)
    print(json.dumps(out))
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "SOAK_r10.json"), "w") as f:
        json.dump(out, f, indent=1)
    assert not leaked, f"/dev/shm leak: {leaked}"
    # health-state history over the whole soak: never critical, bounded
    # non-healthy time (obs/timeline.py)
    assert out["health"]["critical_intervals"] == 0, out["health"]
    assert out["health"]["degraded_ratio"] <= 0.5, out["health"]


def _result_digest(table) -> str:
    """Stable content hash of an arrow result table, for the multichip
    round's bit-identity gate. ``repr`` of python scalars is exact
    (shortest-roundtrip floats), so two tables hash equal iff every cell —
    including null positions and -0.0 vs 0.0 — is identical, independent
    of chunking."""
    import hashlib

    h = hashlib.sha256()
    h.update(repr(table.schema).encode())
    for name in table.column_names:
        h.update(repr(table[name].to_pylist()).encode())
    return h.hexdigest()[:16]


def multichip_main(n_devices: int):
    """Multichip round: the five bench shapes + the full-fact global sort,
    each run over 1/2/N-device meshes with device-primary execution on
    (``multichip_enabled``), gated on bit-identical results across mesh
    sizes and on the oracle checks at mesh size 1. Writes the structured
    MULTICHIP_r06.json artifact — per-shape wall, n_devices,
    device_time_fraction (stats plane), sharded_stages, collective/device
    shuffle bytes — replacing the raw-stderr-tail format of earlier
    rounds (``scripts/bench_diff.py --multichip`` diffs two of these).

    Dev boxes emulate the mesh: the ``--devices N`` preamble above forces
    ``--xla_force_host_platform_device_count=N`` before jax initializes.
    Env: MULTICHIP_ROWS (2_000_000), MULTICHIP_PARTS (8),
    MULTICHIP_WARMUP (1 — per-(shape, mesh) compile warmup run).
    """
    mc_rows = int(os.environ.get("MULTICHIP_ROWS", 2_000_000))
    mc_parts = int(os.environ.get("MULTICHIP_PARTS", 8))
    warmup = int(os.environ.get("MULTICHIP_WARMUP", 1))
    os.environ["BENCH_ROWS"] = str(mc_rows)
    os.environ["BENCH_PARTITIONS"] = str(mc_parts)

    import bench  # repo-root bench.py (shapes, generators, oracles)
    from blaze_tpu.config import Config
    from blaze_tpu.runtime.memmgr import MemManager
    from blaze_tpu.runtime.metrics import tripwire_totals
    from blaze_tpu.runtime.session import Session

    avail = len(jax.devices())
    assert avail >= n_devices, \
        f"{n_devices} devices requested, jax sees {avail} " \
        f"(XLA_FLAGS={os.environ.get('XLA_FLAGS')!r})"
    mesh_sizes = sorted({k for k in (1, 2, n_devices) if k <= avail})
    emulated = "xla_force_host_platform_device_count" in \
        os.environ.get("XLA_FLAGS", "")

    def plan_big_sort(paths):
        from blaze_tpu.ir import exprs as E
        from blaze_tpu.ir import nodes as N
        from blaze_tpu.ops.parquet import scan_node_for_files

        scan = scan_node_for_files(paths["store_sales"],
                                   num_partitions=mc_parts)
        orders = [E.SortOrder(E.Column("ss_sales_price"), ascending=False),
                  E.SortOrder(E.Column("ss_item_sk"))]
        ex = N.ShuffleExchange(scan, N.RangePartitioning(
            orders, mc_parts, []))
        return N.Sort(ex, orders)

    def check_big_sort(table, _oracle):
        import pyarrow.compute as pc

        assert table.num_rows == mc_rows, table.num_rows
        prices = table["ss_sales_price"].combine_chunks()
        assert pc.min(pc.subtract(
            prices.cast("float64").slice(0, len(prices) - 1),
            prices.cast("float64").slice(1))).as_py() >= 0

    out = {"metric": "multichip_device_primary",
           "forced_devices": n_devices, "emulated": emulated,
           "rows": mc_rows, "partitions": mc_parts,
           "mesh_sizes": mesh_sizes, "shapes": {}}
    with tempfile.TemporaryDirectory(prefix="blaze_mchip_") as tmpdir:
        t0 = time.perf_counter()
        paths = bench.make_data(tmpdir)
        out["datagen_s"] = round(time.perf_counter() - t0, 1)
        _, oracles = bench.run_baseline(paths)
        oracles["sort10M"] = None

        shapes = list(bench.SHAPES) + [
            ("sort10M", plan_big_sort, None, None, check_big_sort, ())]
        for name, plan_fn, _o, _a, check_fn, _t in shapes:
            per_mesh = {}
            for k in mesh_sizes:
                MemManager.reset()
                conf = Config(multichip_enabled=True, multichip_devices=k)
                for _ in range(warmup):  # compile outside the timed run
                    with Session(conf=conf) as sess:
                        sess.execute_to_table(plan_fn(paths))
                MemManager.reset()
                t0 = time.perf_counter()
                with Session(conf=conf) as sess:
                    table = sess.execute_to_table(plan_fn(paths))
                    wall = time.perf_counter() - t0
                    trips = tripwire_totals(sess.metrics)
                    profile = sess.profile()
                if k == mesh_sizes[0]:
                    check_fn(table, oracles[name])  # absolute correctness
                per_mesh[str(k)] = {
                    "wall_s": round(wall, 3), "n_devices": k,
                    "device_time_fraction":
                        (profile or {}).get("device_time_fraction", 0.0),
                    "sharded_stages": trips["sharded_stages"],
                    "collective_bytes": trips["collective_bytes"],
                    "device_shuffle_bytes": trips["device_shuffle_bytes"],
                    "shuffle_bytes_serialized":
                        trips["shuffle_bytes_serialized"],
                    "serde_elided_batches": trips["serde_elided_batches"],
                    "digest": _result_digest(table),
                }
            digests = {r["digest"] for r in per_mesh.values()}
            top = per_mesh[str(mesh_sizes[-1])]
            out["shapes"][name] = dict(top, per_mesh=per_mesh,
                                       bit_identical=len(digests) == 1)
            print(json.dumps({name: out["shapes"][name]}), flush=True)

    sort_rec = out["shapes"].get("sort10M", {}).get("per_mesh", {})
    w1 = (sort_rec.get(str(mesh_sizes[0])) or {}).get("wall_s")
    wn = (sort_rec.get(str(mesh_sizes[-1])) or {}).get("wall_s")
    out["gates"] = {
        "bit_identical": all(s["bit_identical"]
                             for s in out["shapes"].values()),
        "sort_wall_1dev_s": w1,
        f"sort_wall_{mesh_sizes[-1]}dev_s": wn,
        "sort_speedup": round(w1 / wn, 2) if w1 and wn else None,
    }
    from blaze_tpu.obs.attribution import artifact_section

    out.update(artifact_section())
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "MULTICHIP_r06.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({"gates": out["gates"], "artifact": path}), flush=True)
    # the hard gates: every shape must agree across mesh sizes, and the
    # device tiers must not re-serialize shuffle traffic
    for name, rec in out["shapes"].items():
        assert rec["bit_identical"], (name, rec)
    if out["gates"]["sort_speedup"] is not None \
            and out["gates"]["sort_speedup"] < 1.0:
        print(f"WARNING: {mesh_sizes[-1]}-way sort did not beat 1-device "
              f"({wn}s vs {w1}s) — emulated meshes share host cores",
              flush=True)
    print("MULTICHIP ROUND PASSED", flush=True)


def _pctl(vals, q):
    if not vals:
        return 0.0
    s = sorted(vals)
    return s[min(len(s) - 1, int(q * len(s)))]


def _write_chaos_section(section: str, data: dict,
                         fname: str = "CHAOS_r01.json") -> str:
    """Merge one section into a chaos artifact at the repo root (the scale
    and serve chaos runs each own a section; reruns replace only their own)."""
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), fname)
    try:
        with open(path) as f:
            out = json.load(f)
    except (OSError, ValueError):
        out = {}
    out[section] = data
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    return path


def chaos_main(kill_every_s: float):
    """Chaos soak (--chaos-kill-every): run the three shuffle-bearing shapes
    repeatedly against a 2-worker pool while a ChaosMonkey hard-kills a
    random worker every ``kill_every_s`` seconds, then gate on

      * zero wrong results (every query bit-identical to the in-driver oracle),
      * zero leaked memory-manager bytes,
      * worker deaths observed and every kill with an incident bundle,
      * >= 1 stage recovered from persisted shuffle outputs (a map output is
        deleted mid-query on a fixed cadence in BOTH phases, so the latency
        populations stay comparable),
      * chaos-phase p99 <= 3x the no-chaos baseline p99.

    The full evidence lands in CHAOS_r01.json (section "scale") BEFORE the
    gates are asserted, so a failing run still leaves its forensics behind.
    Env: CHAOS_ROWS (200_000), CHAOS_ITERS (12).
    """
    import glob

    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq

    from blaze_tpu.config import Config
    from blaze_tpu.ir import exprs as E
    from blaze_tpu.ir import nodes as N
    from blaze_tpu.ir import types as T
    from blaze_tpu.obs.dump import list_incidents
    from blaze_tpu.obs.telemetry import get_registry
    from blaze_tpu.ops.parquet import scan_node_for_files
    from blaze_tpu.runtime.cluster import ChaosMonkey
    from blaze_tpu.runtime.memmgr import MemManager
    from blaze_tpu.runtime.session import Session, _QueryRun

    rows = int(os.environ.get("CHAOS_ROWS", 200_000))
    iters = int(os.environ.get("CHAOS_ITERS", 12))

    COUNTERS = ("blaze_cluster_worker_deaths_total",
                "blaze_cluster_tasks_retried_total",
                "blaze_cluster_stages_recovered_total",
                "blaze_cluster_maps_recomputed_total",
                "blaze_chaos_kills_total")

    def counters() -> dict:
        snap = get_registry().to_raw()
        out = {}
        for name in COUNTERS:
            series = snap.get(name, {}).get("series", [])
            out[name] = series[0]["value"] if series else 0
        return out

    def agg_by(col, reducers):
        def mk(paths):
            scan = scan_node_for_files(paths, num_partitions=4)
            ex = N.ShuffleExchange(
                scan, N.HashPartitioning([E.Column(col)], reducers))
            return N.Agg(ex, E.AggExecMode.HASH_AGG, [(col, E.Column(col))], [
                N.AggColumn(E.AggExpr(E.AggFunction.SUM, [E.Column("paid")],
                                      T.I64), E.AggMode.COMPLETE, "total")])
        return mk

    def sort_top(paths):
        scan = scan_node_for_files(paths, num_partitions=4)
        orders = [E.SortOrder(E.Column("paid"), ascending=False),
                  E.SortOrder(E.Column("item"))]
        ex = N.ShuffleExchange(scan, N.SinglePartitioning(1))
        return N.Limit(N.Sort(ex, orders), 500)

    shapes = [("agg_store", agg_by("store", 4)),
              ("agg_item", agg_by("item", 8)),
              ("sort_top", sort_top)]

    def canon(table):
        d = table.to_pydict()
        return sorted(zip(*d.values())) if d else []

    import tempfile

    section = {"kill_every_s": kill_every_s, "rows": rows, "iters": iters,
               "phases": {}}
    with tempfile.TemporaryDirectory(prefix="blaze_chaos_") as tmpdir:
        rng = np.random.default_rng(11)
        paths = []
        for p in range(2):
            n = rows // 2
            tbl = pa.table({
                "store": pa.array(rng.integers(1, 41, n), type=pa.int64()),
                "item": pa.array(rng.integers(1, 201, n), type=pa.int64()),
                "paid": pa.array(rng.integers(0, 10_000, n), type=pa.int64()),
            })
            path = os.path.join(tmpdir, f"chaos_{p}.parquet")
            pq.write_table(tbl, path)
            paths.append(path)

        # in-driver oracle: the answers every clustered run must reproduce
        # bit-identically, worker deaths or not
        with Session() as s_local:
            oracle = {name: canon(s_local.execute_to_table(mk(paths)))
                      for name, mk in shapes}

        def run_phase(with_chaos: bool) -> dict:
            MemManager.reset()
            conf = Config(incident_dir=os.path.join(
                tmpdir, "incidents_chaos" if with_chaos else "incidents_base"))
            lats, wrong, injected = [], [], 0
            c0 = counters()
            shm0 = shm_roots()
            with Session(conf=conf, num_worker_processes=2) as sess:
                monkey = None
                if with_chaos:
                    monkey = ChaosMonkey(sess.pool, kill_every_s,
                                         seed=11).start()
                try:
                    for it in range(iters):
                        for name, mk in shapes:
                            t0 = time.perf_counter()
                            if name == "agg_store" and it % 3 == 2:
                                # deterministic lineage exercise: lower (runs
                                # the map stage), delete one committed map
                                # output, then execute — the reduce MUST
                                # recover via lineage recompute
                                before = set(glob.glob(os.path.join(
                                    sess.shuffle_root, "shuffle_*",
                                    "map_*.data")))
                                qrun = _QueryRun(0)
                                sess._tls.qrun = qrun
                                lowered = sess._lower(mk(paths))
                                sess._tls.qrun = None
                                fresh = sorted(
                                    f for f in glob.glob(os.path.join(
                                        sess.shuffle_root, "shuffle_*",
                                        "map_*.data")) if f not in before)
                                if fresh:
                                    # the largest output: an empty map (a
                                    # scan range with no rows writes just
                                    # the footer) wouldn't exercise anything
                                    os.remove(max(fresh,
                                                  key=os.path.getsize))
                                    injected += 1
                                got = canon(sess.execute_to_table(lowered))
                            else:
                                got = canon(sess.execute_to_table(mk(paths)))
                            lats.append(time.perf_counter() - t0)
                            if got != oracle[name]:
                                wrong.append({"iter": it, "shape": name})
                        print(json.dumps({
                            "phase": "chaos" if with_chaos else "baseline",
                            "iter": it, "p99_s": round(_pctl(lats, 0.99), 3),
                            "wrong": len(wrong)}), flush=True)
                finally:
                    if monkey is not None:
                        monkey.stop()
                        # grace: the heartbeat supervisor notices a kill that
                        # landed between the last query and stop()
                        time.sleep(2.0)
                kills = list(monkey.kills) if monkey else []
                from blaze_tpu.runtime.metrics import tripwire_totals

                trips = tripwire_totals(sess.metrics)
                leaked_metric = int(sess.metrics.total(
                    "query_leaked_mem_reclaimed"))
                mm = MemManager._instance
                stats = mm.stats() if mm is not None else {"used": 0,
                                                           "reservations": {}}
                incidents = [i for i in list_incidents(conf)
                             if i["kind"] == "worker_lost"]
            c1 = counters()
            return {
                "lat_s": [round(v, 4) for v in lats],
                "p50_s": round(_pctl(lats, 0.50), 4),
                "p99_s": round(_pctl(lats, 0.99), 4),
                "queries": len(lats),
                "wrong_results": wrong,
                "injected_missing_maps": injected,
                "kills_injected": len(kills),
                "kills": kills,
                "incident_bundles_worker_lost": len(incidents),
                "leaked_mem_reclaimed": leaked_metric,
                "mem_used_after": int(stats["used"]),
                "mem_reservations_after": list(stats["reservations"]),
                "counters_delta": {k: c1[k] - c0[k] for k in COUNTERS},
                # zero-copy tripwires: pool mode negotiates the shm tier, so
                # mapped bytes must flow and shm roots must not outlive the
                # session even with workers dying mid-query
                "shuffle_bytes_serialized": trips["shuffle_bytes_serialized"],
                "shm_bytes_mapped": trips["shm_bytes_mapped"],
                "serde_elided_batches": trips["serde_elided_batches"],
                "shm_segments_leaked": len(shm_roots(shm0)),
            }

        section["phases"]["baseline"] = base = run_phase(with_chaos=False)
        section["phases"]["chaos"] = chaos = run_phase(with_chaos=True)

    d = chaos["counters_delta"]
    section["gates"] = gates = {
        "wrong_results": len(base["wrong_results"])
        + len(chaos["wrong_results"]),
        "leaked_bytes": base["leaked_mem_reclaimed"] + base["mem_used_after"]
        + chaos["leaked_mem_reclaimed"] + chaos["mem_used_after"],
        "shm_segments_leaked": base["shm_segments_leaked"]
        + chaos["shm_segments_leaked"],
        "worker_deaths_total": d["blaze_cluster_worker_deaths_total"],
        "stages_recovered_total": d["blaze_cluster_stages_recovered_total"],
        "maps_recomputed_total": d["blaze_cluster_maps_recomputed_total"],
        "kills_injected": chaos["kills_injected"],
        "incident_bundles": chaos["incident_bundles_worker_lost"],
        "p99_no_chaos_s": base["p99_s"],
        "p99_chaos_s": chaos["p99_s"],
        "p99_inflation": round(chaos["p99_s"] / max(base["p99_s"], 1e-9), 2),
    }
    from blaze_tpu.obs.attribution import artifact_section

    section.update(artifact_section())
    path = _write_chaos_section("scale", section)
    print(json.dumps({"gates": gates, "artifact": path}), flush=True)

    # evidence is on disk; now enforce the gates
    assert gates["wrong_results"] == 0, gates
    assert gates["leaked_bytes"] == 0, gates
    assert gates["shm_segments_leaked"] == 0, gates
    assert gates["worker_deaths_total"] > 0, gates
    assert gates["stages_recovered_total"] >= 1, gates
    assert gates["maps_recomputed_total"] >= 1, gates
    assert gates["kills_injected"] > 0, gates
    assert gates["incident_bundles"] >= gates["kills_injected"], gates
    assert gates["p99_chaos_s"] <= 3.0 * gates["p99_no_chaos_s"], gates
    print("CHAOS SOAK (scale) PASSED", flush=True)


# mid_ingest_kill is a serve-matrix-only mode (serve_soak.py): it needs the
# streaming ingest path and the result cache, which the scale soak doesn't
# exercise — chaos_mode_conf_kwargs contributes nothing for it
CHAOS_MODES = ("kill", "hang", "enospc", "corrupt", "preempt",
               "mid_ingest_kill")


def parse_chaos_spec(spec: str) -> dict:
    """``kill:N,hang:N,enospc:N,corrupt:N,preempt:N`` -> ordered {mode: N}.
    N means seconds-between-kills for ``kill`` and a failpoint every-N
    trigger for the others. Any subset of modes is allowed; unknown modes
    fail. ``preempt`` is scheduler-driven and only meaningful under the
    serve soak's matrix (the scale matrix runs sessions directly)."""
    modes = {}
    for entry in (spec or "").split(","):
        entry = entry.strip()
        if not entry:
            continue
        mode, _, val = entry.partition(":")
        if mode not in CHAOS_MODES:
            raise SystemExit(
                f"--chaos-spec: unknown mode {mode!r} "
                f"(one of {', '.join(CHAOS_MODES)})")
        try:
            modes[mode] = float(val) if val else 1.0
        except ValueError:
            raise SystemExit(f"--chaos-spec: bad value in {entry!r}")
    if not modes:
        raise SystemExit("--chaos-spec: empty spec")
    return modes


def chaos_mode_conf_kwargs(mode: str, n: float, seed: int = 3044) -> dict:
    """Config field overrides that arm one injection mode (``kill`` uses a
    ChaosMonkey, not a failpoint, so it contributes none). Shared by the
    scale and serve soaks so both matrices inject identically."""
    if mode == "hang":
        # hang far past the hard timeout: every firing MUST be cancelled by
        # the task_timeout_s monitor, never by the hang expiring. N means
        # "one in N task entries hangs": a probability trigger (an every-N
        # counter would tick in near-lockstep on symmetric workers), drawn
        # from the slot-salted streams so only one worker of the pair
        # hangs and the retry lands on a WARM survivor. The default seed's
        # slot-1 stream fires once at draw ~26 — inside both soaks'
        # per-worker armed call windows (~36 scale, ~51 serve) but past
        # what any respawned worker has left, so one firing cannot cascade
        return {"failpoints": f"worker.task=hang:p{1.0 / max(n, 1):.5f}:600",
                "failpoint_seed": seed, "task_timeout_s": 1.0,
                "fault_exclusion_ttl_s": 2.0}
    if mode == "enospc":
        # shm tier armed so the per-commit headroom/ENOSPC path is the one
        # that fires; the degrade target is the spill-dir tier
        return {"zero_copy_tier": "shm", "failpoint_seed": seed,
                "failpoints": f"shm.commit=enospc:every{int(n)}"}
    if mode == "corrupt":
        # paranoid verification ON: a flipped payload byte must be caught as
        # a crc mismatch and routed into lineage recompute
        return {"shuffle_verify_checksum": True, "failpoint_seed": seed,
                "failpoints": f"frame.decode=corrupt:every{int(n)}"}
    if mode == "preempt":
        # preemption storm: the scheduler preempts on ANY contention (no
        # priority/vtime test), the pause window opens instantly, and a
        # delay at every Nth stage-boundary commit stretches the window the
        # dispatcher needs to land a pause request mid-plan
        return {"serve_preempt_aggressive": True,
                "serve_preempt_after_s": 0.05,
                "serve_preempt_min_run_s": 0.0,
                "failpoint_seed": seed,
                "failpoints":
                    f"serve.preempt=delay:every{max(int(n), 1)}:0.02"}
    return {}


def chaos_matrix_main(spec: str):
    """Chaos matrix (--chaos-spec kill:N,hang:N,enospc:N,corrupt:N): run the
    shuffle-bearing shapes against a 2-worker pool once uninjected, then once
    per requested injection mode, and gate EVERY mode on

      * zero wrong results (bit-identical to the in-driver oracle),
      * zero leaked memory-manager bytes and zero leaked /dev/shm roots,
      * p99 <= 2x the uninjected phase,

    plus per-mode evidence: kill -> worker deaths observed; hang -> hard
    task timeouts fired; enospc -> ``shuffle_tier_degraded`` > 0 (the query
    degraded tiers instead of failing); corrupt -> lineage recomputes > 0.
    Evidence lands in CHAOS_r02.json (section "scale") BEFORE gates are
    asserted. Env: CHAOS_ROWS (200_000), CHAOS_ITERS (6).
    """
    import tempfile

    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq

    from blaze_tpu.config import Config, set_config
    from blaze_tpu.ir import exprs as E
    from blaze_tpu.ir import nodes as N
    from blaze_tpu.ir import types as T
    from blaze_tpu.obs.telemetry import get_registry
    from blaze_tpu.ops.parquet import scan_node_for_files
    from blaze_tpu.runtime import failpoints
    from blaze_tpu.runtime.cluster import ChaosMonkey
    from blaze_tpu.runtime.memmgr import MemManager
    from blaze_tpu.runtime.session import Session

    modes = parse_chaos_spec(spec)
    if "preempt" in modes:
        # stage-boundary preemption lives in the serve scheduler; the scale
        # matrix calls Session.execute_to_table directly so nothing would
        # ever pause — refuse rather than green-light a vacuous phase
        raise SystemExit("--chaos-spec: mode 'preempt' is scheduler-driven; "
                         "run it under scripts/serve_soak.py --chaos-spec")
    rows = int(os.environ.get("CHAOS_ROWS", 200_000))
    iters = int(os.environ.get("CHAOS_ITERS", 6))

    COUNTERS = ("blaze_cluster_worker_deaths_total",
                "blaze_cluster_tasks_retried_total",
                "blaze_cluster_tasks_timed_out_total",
                "blaze_cluster_stages_recovered_total",
                "blaze_cluster_maps_recomputed_total",
                "blaze_chaos_kills_total")

    def counters() -> dict:
        snap = get_registry().to_raw()
        out = {}
        for name in COUNTERS:
            series = snap.get(name, {}).get("series", [])
            out[name] = series[0]["value"] if series else 0
        return out

    def agg_by(col, reducers):
        def mk(paths):
            scan = scan_node_for_files(paths, num_partitions=4)
            ex = N.ShuffleExchange(
                scan, N.HashPartitioning([E.Column(col)], reducers))
            return N.Agg(ex, E.AggExecMode.HASH_AGG, [(col, E.Column(col))], [
                N.AggColumn(E.AggExpr(E.AggFunction.SUM, [E.Column("paid")],
                                      T.I64), E.AggMode.COMPLETE, "total")])
        return mk

    def sort_top(paths):
        scan = scan_node_for_files(paths, num_partitions=4)
        orders = [E.SortOrder(E.Column("paid"), ascending=False),
                  E.SortOrder(E.Column("item"))]
        ex = N.ShuffleExchange(scan, N.SinglePartitioning(1))
        return N.Limit(N.Sort(ex, orders), 500)

    shapes = [("agg_store", agg_by("store", 4)),
              ("agg_item", agg_by("item", 8)),
              ("sort_top", sort_top)]

    def canon(table):
        d = table.to_pydict()
        return sorted(zip(*d.values())) if d else []

    section = {"spec": spec, "rows": rows, "iters": iters, "phases": {}}
    with tempfile.TemporaryDirectory(prefix="blaze_chaosm_") as tmpdir:
        rng = np.random.default_rng(11)
        paths = []
        for p in range(2):
            n = rows // 2
            tbl = pa.table({
                "store": pa.array(rng.integers(1, 41, n), type=pa.int64()),
                "item": pa.array(rng.integers(1, 201, n), type=pa.int64()),
                "paid": pa.array(rng.integers(0, 10_000, n), type=pa.int64()),
            })
            path = os.path.join(tmpdir, f"chaos_{p}.parquet")
            pq.write_table(tbl, path)
            paths.append(path)

        with Session() as s_local:
            oracle = {name: canon(s_local.execute_to_table(mk(paths)))
                      for name, mk in shapes}

        def run_phase(mode, n) -> dict:
            MemManager.reset()
            kwargs = dict(chaos_mode_conf_kwargs(mode, n)) if mode else {}
            # injection starts AFTER a one-pass JIT warmup (identically in
            # every phase, warmup latencies recorded in every phase): a
            # failpoint landing inside worker compilation would measure the
            # compiler, not the recovery path
            arm_spec = kwargs.pop("failpoints", "")
            arm_timeout = kwargs.pop("task_timeout_s", 0.0)
            conf = Config(incident_dir=os.path.join(
                tmpdir, f"incidents_{mode or 'baseline'}"), **kwargs)
            # the GLOBAL config must match the session conf: driver-side
            # readers (recompute pre-checks, tier selection outside a query)
            # consult get_config(), and the corrupt mode's paranoia level
            # must be coherent between them or recompute pre-checks would
            # pass a crc-corrupt file as healthy
            set_config(conf)
            lats, wrong = [], []
            c0 = counters()
            shm0 = shm_roots()
            with Session(conf=conf, num_worker_processes=2) as sess:
                for name, mk in shapes:  # warmup pass, uninjected
                    t0 = time.perf_counter()
                    if canon(sess.execute_to_table(mk(paths))) != oracle[name]:
                        wrong.append({"iter": "warmup", "shape": name})
                    lats.append(time.perf_counter() - t0)
                if arm_spec:
                    # conf is shared by reference with the pool, so workers
                    # pick the spec up from the next task's shipped conf and
                    # the timeout monitor reads it per stage
                    conf.failpoints = arm_spec
                    conf.task_timeout_s = arm_timeout
                    failpoints.arm_from(conf)
                monkey = ChaosMonkey(sess.pool, n, seed=11).start() \
                    if mode == "kill" else None
                try:
                    for it in range(iters):
                        for name, mk in shapes:
                            t0 = time.perf_counter()
                            got = canon(sess.execute_to_table(mk(paths)))
                            lats.append(time.perf_counter() - t0)
                            if got != oracle[name]:
                                wrong.append({"iter": it, "shape": name})
                        print(json.dumps({
                            "phase": mode or "baseline", "iter": it,
                            "p99_s": round(_pctl(lats, 0.99), 3),
                            "wrong": len(wrong)}), flush=True)
                finally:
                    if monkey is not None:
                        monkey.stop()
                        time.sleep(2.0)  # heartbeat grace for the last kill
                    failpoints.unhang()
                kills = list(monkey.kills) if monkey else []
                tier_degraded = int(sess.metrics.total(
                    "shuffle_tier_degraded"))
                leaked_metric = int(sess.metrics.total(
                    "query_leaked_mem_reclaimed"))
                mm = MemManager._instance
                used_after = int(mm.used) if mm is not None else 0
            fired = failpoints.fired()  # driver-process firings (workers
            failpoints.disarm()         # report through session metrics)
            c1 = counters()
            return {
                "p50_s": round(_pctl(lats, 0.50), 4),
                "p99_s": round(_pctl(lats, 0.99), 4),
                "queries": len(lats),
                "wrong_results": wrong,
                "kills_injected": len(kills),
                "failpoints_fired_in_driver": fired,
                "shuffle_tier_degraded": tier_degraded,
                "leaked_mem_reclaimed": leaked_metric,
                "mem_used_after": used_after,
                "shm_segments_leaked": len(shm_roots(shm0)),
                "counters_delta": {k: c1[k] - c0[k] for k in COUNTERS},
            }

        section["phases"]["baseline"] = base = run_phase(None, 0)
        for mode, n in modes.items():
            section["phases"][mode] = run_phase(mode, n)

    gates = {"p99_baseline_s": base["p99_s"], "modes": {}}
    for mode in modes:
        ph = section["phases"][mode]
        d = ph["counters_delta"]
        gates["modes"][mode] = {
            "wrong_results": len(ph["wrong_results"]),
            "leaked_bytes": ph["leaked_mem_reclaimed"]
            + ph["mem_used_after"],
            "shm_segments_leaked": ph["shm_segments_leaked"],
            "p99_s": ph["p99_s"],
            "p99_inflation": round(ph["p99_s"] / max(base["p99_s"], 1e-9),
                                   2),
            "worker_deaths": d["blaze_cluster_worker_deaths_total"],
            "tasks_timed_out": d["blaze_cluster_tasks_timed_out_total"],
            "maps_recomputed": d["blaze_cluster_maps_recomputed_total"],
            "shuffle_tier_degraded": ph["shuffle_tier_degraded"],
            "kills_injected": ph["kills_injected"],
        }
    section["gates"] = gates
    path = _write_chaos_section("scale", section, fname="CHAOS_r02.json")
    print(json.dumps({"gates": gates, "artifact": path}), flush=True)

    # evidence is on disk; now enforce the matrix gates
    for mode in modes:
        g = gates["modes"][mode]
        assert g["wrong_results"] == 0, (mode, g)
        assert g["leaked_bytes"] == 0, (mode, g)
        assert g["shm_segments_leaked"] == 0, (mode, g)
        assert g["p99_s"] <= 2.0 * gates["p99_baseline_s"], (mode, g)
    if "kill" in modes:
        g = gates["modes"]["kill"]
        assert g["kills_injected"] > 0 and g["worker_deaths"] > 0, g
    if "hang" in modes:
        assert gates["modes"]["hang"]["tasks_timed_out"] > 0, gates
    if "enospc" in modes:
        assert gates["modes"]["enospc"]["shuffle_tier_degraded"] > 0, gates
    if "corrupt" in modes:
        assert gates["modes"]["corrupt"]["maps_recomputed"] > 0, gates
    print("CHAOS MATRIX (scale) PASSED", flush=True)


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--devices", type=int, metavar="N",
                    help="multichip round: run the bench shapes + the "
                         "global sort over 1/2/N-device meshes (emulated "
                         "via --xla_force_host_platform_device_count, set "
                         "automatically) and write the structured "
                         "MULTICHIP_r06.json artifact instead of soaking")
    ap.add_argument("--chaos-kill-every", type=float, metavar="N",
                    help="chaos mode: hard-kill a random worker every N "
                         "seconds and gate on recovery (CHAOS_r01.json) "
                         "instead of running the scale soak")
    ap.add_argument("--chaos-spec", metavar="SPEC",
                    help="chaos matrix: comma-separated modes "
                         "kill:N,hang:N,enospc:N,corrupt:N — one injected "
                         "phase per mode plus an uninjected baseline, gated "
                         "per mode (CHAOS_r02.json)")
    args = ap.parse_args()
    if args.devices:
        multichip_main(args.devices)
    elif args.chaos_spec:
        chaos_matrix_main(args.chaos_spec)
    elif args.chaos_kill_every:
        chaos_main(args.chaos_kill_every)
    else:
        main()
