#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that blaze_tpu still starts on the chip.

One process, the only one that touches JAX, drives the system's main path on
one TPU chip at a size a TPC-DS user would call small-but-real, checks every
answer against an independent reference, asserts that the chip did the work,
and prints two JSON lines at the end of stdout: ``report: {...}`` with every
reading, then, last, the verdict ``{"ok": true, "device": {"platform",
"kind", "count"}}`` and nothing else in it. Any failed phase is an exception
and a non-zero exit; nothing is caught to reach the end, and no verdict line
is printed.

Phases, in order:

  device     fail unless jax.devices()[0].platform == "tpu" (--allow-cpu lets
             a CPU run through, for catching typos off the chip)
  native     build/load the host kernel library, synchronously
  semantics  what differs on this chip: the f64 probe, a DOUBLE column through
             from_arrow -> filter -> SUM -> to_arrow exact against numpy,
             device murmur3 == host murmur3
  data       bench.py's generator at --rows fact rows (default: store_sales at
             TPC-DS scale factor 10), parquet on local disk
  reference  pyarrow Acero on the same files (bench.acero_q01..q67)
  batch      the five shapes, each once through Session().execute_to_table,
             pool-less, default Config. Asserted per shape: the answer, every
             stage placed on the device, no fused batch on the eager path and
             the FINAL aggregation merged on the device (KNOWN_EXCEPTIONS
             names the one shape that misses, and why). Then q01 once more
             through Session.execute to look at the result columns' devices.
             The shapes run side by side, each in its own Session: a cold
             XLA:TPU compile of this engine's kernels takes minutes per shape
             (64-bit sorts), and only overlapping them fits a cold run into
             the time limit
  serve      QueryScheduler on one session, default Config: four client
             threads submit the five plans at once, twice over. Round one
             executes them side by side on the chip; the batch leg ran the
             same plans, so a compile request there means a capacity bucket
             moved. Round two meets the result cache that round one filled.
             Neither round may ask the backend for an executable
  mesh       with >= 4 devices (or --mesh): q01 and q67 through a multichip
             Session over all devices, equal to the same reference (and so to
             the one-chip answers). It runs instead of the batch and serve
             legs: cold, either fills the time limit by itself

Run it on the chip through the chip tool: ``python3 chip_smoke.py``. Here on
the CPU: ``JAX_PLATFORMS=cpu python3 chip_smoke.py --rows 100000 --allow-cpu``.
"""

import argparse
import concurrent.futures
import dataclasses
import faulthandler
import json
import os
import sys
import tempfile
import threading
import time

# store_sales rows at scale factor 10 (TPC-DS specification, table of
# database row counts by scale factor)
SF10_STORE_SALES_ROWS = 28_800_991

CLIENT_THREADS = 4
SERVE_ROUNDS = 2
MESH_SHAPES = ("q01", "q67")
# the contract allows 1200 s, compilation included; give up a little earlier
# with every thread's stack on stderr rather than be killed with nothing
DEADLINE_S = 1150
SLOW_COMPILE_S = 5.0  # compiles at least this long are printed as they end

# Shapes that may miss the device-work checks of the batch leg, each with the
# one way it is known to miss. The result carries them under
# "known_exceptions" when they apply; any other shape, or any other way of
# leaving the device, fails the run.
KNOWN_EXCEPTIONS = {
    "q67": "device_merge_batches == 0 at SF10 rows: ~6.6M partial rows per "
           "reducer exceed device_merge_max_bytes (256 MB), so the FINAL "
           "aggregation runs in the host AggTable, its keys come out as "
           "HostColumns and the window stage's batches take the fused "
           "stage's eager path (PERF.md section 6; ROADMAP S4)",
}

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class SmokeFailure(Exception):
    pass


def check(ok, message: str):
    if not ok:
        raise SmokeFailure(message)


def say(message: str):
    print(message, flush=True)


class CompileLog:
    """Every executable JAX asks its backend for, by name and seconds, and how
    many of those the persistent cache answered. The rest are programs XLA
    really compiled in this process."""

    def __init__(self):
        import jax.monitoring

        self._mu = threading.Lock()
        self.requests = []  # (fun_name, seconds)
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **kw):
        if event == _COMPILE_EVENT:
            name = str(kw.get("fun_name", "?"))
            with self._mu:
                self.requests.append((name, seconds))
            if seconds >= SLOW_COMPILE_S:
                say(f"compile: {seconds:.1f}s {name}")

    def _event(self, event, **kw):
        if event == _CACHE_HIT_EVENT:
            with self._mu:
                self.cache_hits += 1

    def counts(self):
        with self._mu:
            return len(self.requests), self.cache_hits

    def slowest(self, n: int):
        with self._mu:
            return sorted(self.requests, key=lambda r: -r[1])[:n]


def cache_files(path) -> int:
    return len(os.listdir(path)) if path and os.path.isdir(path) else 0


def peak_bytes(device):
    stats = device.memory_stats()  # None where the backend reports nothing
    return stats.get("peak_bytes_in_use") if stats else None


# ---------------------------------------------------------------------------
# semantics that differ on the chip
# ---------------------------------------------------------------------------


def phase_semantics(platform: str):
    import numpy as np
    import pyarrow as pa

    from blaze_tpu.core.batch import ColumnarBatch, DeviceColumn
    from blaze_tpu.exprs import spark_hash as H
    from blaze_tpu.ir import exprs as E
    from blaze_tpu.ir import nodes as N
    from blaze_tpu.ir import types as T
    from blaze_tpu.ops.agg import AggExec
    from blaze_tpu.ops.base import ExecContext
    from blaze_tpu.ops.basic import FilterExec, MemoryScanExec
    from blaze_tpu.utils.device import supports_f64

    f64 = supports_f64()
    say(f"semantics: supports_f64() = {f64}")

    # DOUBLE exactness wherever the column lives. Each group's sum has one
    # possible value in IEEE double whatever the order of addition: 1e200
    # swallows its companions, and 2**52+1 plus 2 needs every mantissa bit.
    k = np.array([0, 0, 0, 1, 1, 1] * 500, dtype=np.int64)
    x = np.array([1e200, 1e17 + 1, 0.25,
                  4503599627370497.0, 2.0, 0.125] * 500, dtype=np.float64)
    x[6:] = 0.0625  # below the filter: one live triple per group
    tbl = pa.table({"k": pa.array(k), "x": pa.array(x)})
    batch = ColumnarBatch.from_arrow(tbl)
    xcol = batch.columns[1]
    say(f"semantics: DOUBLE column lives in {type(xcol).__name__}")
    check(f64 or not isinstance(xcol, DeviceColumn),
          "a DOUBLE column is on the device though supports_f64() is False")
    pipeline = AggExec(
        FilterExec(MemoryScanExec(batch.schema, [[batch]]),
                   [E.BinaryExpr(E.BinaryOp.GT, E.Column("x"),
                                 E.Literal(0.5, T.F64))]),
        E.AggExecMode.HASH_AGG, [("k", E.Column("k"))],
        [N.AggColumn(E.AggExpr(E.AggFunction.SUM, [E.Column("x")]),
                     E.AggMode.COMPLETE, "s")])
    got = pa.Table.from_batches(
        [b.to_arrow() for b in pipeline.execute(0, ExecContext())]).to_pydict()
    got = dict(zip(got["k"], got["s"]))
    want = {g: float(np.sum(x[(k == g) & (x > 0.5)])) for g in (0, 1)}
    check(got == want, f"DOUBLE filter+SUM: got {got}, numpy says {want}")
    say(f"semantics: DOUBLE filter+SUM exact ({want})")

    # device murmur3 (partition routing) == host murmur3
    vals = np.random.default_rng(0).integers(-2**62, 2**62, 8192)
    hb = ColumnarBatch.from_arrow(pa.table({"v": pa.array(vals)}))
    col = hb.columns[0]
    check(isinstance(col, DeviceColumn), "an int64 column is not on the device")
    devs = {d.platform for d in col.data.devices()}
    check(devs == {platform},
          f"a DeviceColumn's array lives on {devs}, not {platform!r}")
    h_dev = np.asarray(H.hash_batch([col], hb.num_rows, hb.capacity))
    h_np = H.murmur3_int64_np(
        vals, np.full(len(vals), 42, np.uint32)).view(np.int32)
    check((h_dev == h_np).all(), "device murmur3 != host murmur3")
    say(f"semantics: device murmur3 == host murmur3 on {len(vals)} rows; "
        f"DeviceColumn arrays on {sorted(devs)}")
    return f64


# ---------------------------------------------------------------------------
# main path
# ---------------------------------------------------------------------------

# fused_fallback_batches counts batches a fused stage ran eagerly because they
# carried host columns (a failing closure raises instead);
# device_merge_batches says the FINAL aggregation merged on the device rather
# than in the host table. Placement counters alone cannot see either.
_COUNTERS = ("placement_device_stages", "placement_host_stages",
             "fused_stages", "fused_fallback_batches", "device_merge_batches")


def phase_reference(bench, paths):
    """Acero on the same parquet files; canonical rows per shape."""
    ref = {}
    tables = bench.load_tables(paths, list(paths))
    for name, _plan, _pandas, acero_fn, _check, used in bench.SHAPES:
        t0 = time.perf_counter()
        ref[name] = bench.canon_rows(
            name, acero_fn({n: tables[n] for n in used}), "acero")
        say(f"reference: {name} {len(ref[name])} rows "
            f"in {time.perf_counter() - t0:.1f}s (Acero)")
    return ref


def run_shape(bench, name, plan_fn, paths, ref, device):
    """One shape, once, through its own Session; asserted before returned."""
    from blaze_tpu.runtime.session import Session

    t0 = time.perf_counter()
    with Session() as sess:
        out = sess.execute_to_table(plan_fn(paths))
        totals = sess.metrics.totals(_COUNTERS)
    wall = time.perf_counter() - t0
    check(bench.canon_rows(name, out, "engine") == ref[name],
          f"{name}: engine answer differs from the Acero reference")
    check(totals["placement_device_stages"] > 0,
          f"{name}: no stage was placed on the device")
    check(totals["placement_host_stages"] == 0,
          f"{name}: {totals['placement_host_stages']} stages placed on host")
    report = {"first_run_s": round(wall, 2),
              "device_stages": totals["placement_device_stages"],
              "fused_stages": totals["fused_stages"],
              "fused_fallback_batches": totals["fused_fallback_batches"],
              "device_merge_batches": totals["device_merge_batches"],
              # the process's running maximum when this shape ended, with
              # the other shapes in flight beside it
              "peak_bytes_in_use": peak_bytes(device)}
    if totals["device_merge_batches"] == 0 and name in KNOWN_EXCEPTIONS:
        report["known_exception"] = True
    else:
        check(totals["device_merge_batches"] >= 1,
              f"{name}: the FINAL aggregation never merged on the device")
        check(totals["fused_fallback_batches"] == 0,
              f"{name}: {totals['fused_fallback_batches']} fused batches "
              "took the eager path")
    return out, report


def phase_batch(bench, paths, ref, device):
    """Each shape once through Session().execute_to_table, side by side."""
    tables, report = {}, {}
    with concurrent.futures.ThreadPoolExecutor(
            len(bench.SHAPES), thread_name_prefix="shape") as pool:
        futures = {name: pool.submit(run_shape, bench, name, plan_fn, paths,
                                     ref, device)
                   for name, plan_fn, *_ in bench.SHAPES}
        for name, fut in futures.items():
            tables[name], report[name] = fut.result()  # re-raises a failure
            say(f"batch: {name} ok {report[name]}")
    return tables, report


def phase_residency(bench, sess, paths, platform):
    """The batches a query hands back are device-resident: q01 again through
    Session.execute, looking at the arrays behind its DeviceColumns."""
    from blaze_tpu.core.batch import DeviceColumn

    seen = set()
    t0 = time.perf_counter()
    for batch in sess.execute(bench.plan_q01(paths)):
        for col in batch.columns:
            if isinstance(col, DeviceColumn):
                seen |= {d.platform for d in col.data.devices()}
    check(seen == {platform},
          f"result DeviceColumns live on {sorted(seen)}, not {platform!r}")
    say(f"batch: q01 result columns on {sorted(seen)} "
        f"(warm run {time.perf_counter() - t0:.2f}s)")


def phase_serve(bench, sess, paths, tables, compiles):
    """QueryScheduler on one session, CLIENT_THREADS clients at once."""
    from blaze_tpu.serve.scheduler import QueryScheduler

    plans = [(name, plan_fn(paths)) for name, plan_fn, *_ in bench.SHAPES]
    want = {name: bench.canon_rows(name, t, "engine")
            for name, t in tables.items()}
    sched = QueryScheduler(sess)
    rounds = []
    def cache_hits():
        return sess.cache.stats_fields()["cache_hits"] if sess.cache else 0

    for rnd in range(SERVE_ROUNDS):
        c0, _ = compiles.counts()
        h0 = cache_hits()
        errors = []
        start = threading.Barrier(CLIENT_THREADS)

        def client(i):
            try:
                start.wait(timeout=60)
                handles = [(n, sched.submit(p, label=f"{n}-c{i}-r{rnd}"))
                           for n, p in plans[i::CLIENT_THREADS]]
                for n, h in handles:
                    got = bench.canon_rows(n, h.result(timeout=900), "engine")
                    if got != want[n]:
                        errors.append(f"{n} (client {i}): served answer "
                                      "differs from the batch leg")
            except BaseException as exc:  # relayed to the main thread below
                errors.append(f"client {i}: {type(exc).__name__}: {exc}")
                raise

        t0 = time.perf_counter()
        threads = [threading.Thread(target=client, args=(i,), daemon=True)
                   for i in range(CLIENT_THREADS)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=1000)
            check(not t.is_alive(), "a serve client did not finish")
        wall = time.perf_counter() - t0
        check(not errors, "; ".join(errors))
        rounds.append({"queries": len(plans), "wall_s": round(wall, 2),
                       "compile_requests": compiles.counts()[0] - c0,
                       "cache_hits": cache_hits() - h0})
        say(f"serve: round {rnd + 1} ok {rounds[-1]}")
        check(rounds[-1]["compile_requests"] == 0,
              f"serve round {rnd + 1} asked the backend for "
              f"{rounds[-1]['compile_requests']} new executables after the "
              "batch leg ran the same plans")
    sched.close()
    say("serve: close() returned")
    return rounds


def phase_mesh(bench, conf, paths, ref, devices):
    """q01 and q67 through a multichip Session over all local devices, one
    after the other: two threads launching collectives on the same devices
    may order them differently per device."""
    from blaze_tpu.runtime.session import Session

    report = {}
    with Session(conf=dataclasses.replace(conf, multichip_enabled=True)) as ms:
        check(ms.mesh is not None and ms.mesh.devices.size == len(devices),
              f"mesh has {ms.mesh.devices.size} of {len(devices)} devices")
        for name, plan_fn, *_ in bench.SHAPES:
            if name not in MESH_SHAPES:
                continue
            before = ms.metrics.totals(("sharded_stages", "collective_bytes"))
            t0 = time.perf_counter()
            out = ms.execute_to_table(plan_fn(paths))
            wall = time.perf_counter() - t0
            after = ms.metrics.totals(("sharded_stages", "collective_bytes"))
            check(bench.canon_rows(name, out, "engine") == ref[name],
                  f"mesh {name}: answer differs from the Acero reference")
            delta = {m: after[m] - before[m] for m in after}
            check(delta["sharded_stages"] > 0, f"mesh {name}: no sharded stage")
            check(delta["collective_bytes"] > 0,
                  f"mesh {name}: no bytes crossed the mesh")
            report[name] = dict(delta, first_run_s=round(wall, 2))
            say(f"mesh: {name} ok {report[name]}")
    report["peak_bytes_in_use_per_device"] = [peak_bytes(d) for d in devices]
    say(f"mesh: peak bytes per device {report['peak_bytes_in_use_per_device']}")
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=SF10_STORE_SALES_ROWS,
                    help="fact-table rows (default: store_sales at SF10)")
    ap.add_argument("--seed", type=int, default=42)
    ap.add_argument("--allow-cpu", action="store_true",
                    help="let a non-TPU backend through (tiny sizes, off chip)")
    ap.add_argument("--mesh", action="store_true",
                    help="run the multichip leg, instead of the batch and "
                         "serve legs, on fewer than four devices too")
    args = ap.parse_args(argv)
    t_start = time.perf_counter()
    faulthandler.dump_traceback_later(DEADLINE_S, exit=True)

    import jax

    devices = jax.devices()
    dev0 = devices[0]
    if dev0.platform != "tpu" and not args.allow_cpu:
        print(f"chip_smoke: FAIL: jax.devices()[0].platform is "
              f"{dev0.platform!r}, not 'tpu' (JAX_PLATFORMS="
              f"{os.environ.get('JAX_PLATFORMS')!r})", file=sys.stderr)
        return 1

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import blaze_tpu  # noqa: F401  (x64; places the compile cache)
    import bench
    from blaze_tpu.config import get_config
    from blaze_tpu.runtime.session import Session
    from blaze_tpu.utils import native

    compiles = CompileLog()
    cache_dir = jax.config.jax_compilation_cache_dir
    files_before = cache_files(cache_dir)
    say(f"device: platform={dev0.platform} device_kind={dev0.device_kind} "
        f"count={len(devices)} jax={jax.__version__}")
    say(f"compile cache: {cache_dir} ({files_before} files; from "
        f"{'JAX_COMPILATION_CACHE_DIR' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'the checkout'})")
    say(f"native: {native.ensure_built()}")

    f64 = phase_semantics(dev0.platform)

    run_mesh = args.mesh or len(devices) >= 4
    check(len(devices) > 1 or not args.mesh,
          "--mesh needs more than one device")
    with tempfile.TemporaryDirectory(prefix="blaze_smoke_") as tmpdir:
        t0 = time.perf_counter()
        paths = bench.make_data(tmpdir, rows=args.rows, seed=args.seed)
        data_bytes = sum(os.path.getsize(p) for ps in paths.values() for p in ps)
        say(f"data: {args.rows} fact rows x2 tables in "
            f"{len(paths['store_sales'])} files each, "
            f"{data_bytes / 1e6:.0f} MB parquet, seed {args.seed}, "
            f"{time.perf_counter() - t0:.1f}s")
        ref = phase_reference(bench, paths)

        if run_mesh:
            batch_report = serve_report = (
                f"skipped ({len(devices)} devices: the mesh leg runs instead)")
            say(f"batch, serve: {batch_report}")
            mesh_report = phase_mesh(bench, get_config(), paths, ref, devices)
        else:
            tables, batch_report = phase_batch(bench, paths, ref, dev0)
            with Session() as sess:
                phase_residency(bench, sess, paths, dev0.platform)
                serve_report = phase_serve(bench, sess, paths, tables, compiles)
            mesh_report = f"skipped ({len(devices)} device)"
            say(f"mesh: {mesh_report}")

    requests, hits = compiles.counts()
    files_after = cache_files(cache_dir)
    slowest = [{"name": n, "s": round(s, 2)} for n, s in compiles.slowest(5)]
    say(f"compiles: {requests} executables asked for, {hits} from the "
        f"persistent cache, {requests - hits} compiled; cache files "
        f"{files_before} -> {files_after}")
    say(f"compiles: slowest five {slowest}")
    known = ([] if run_mesh else
             [{"shape": name, "reason": KNOWN_EXCEPTIONS[name]}
              for name, r in batch_report.items() if "known_exception" in r])
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(devices)}
    report = {
        "device": device,
        "jax": jax.__version__,
        "rows": args.rows,
        "seed": args.seed,
        "assumed": ["generator is bench.py's, not dsdgen", "keys are uniform",
                    "store_returns gets the store_sales row count"],
        "reduced": ([] if args.rows == SF10_STORE_SALES_ROWS else
                    [f"rows {SF10_STORE_SALES_ROWS} -> {args.rows}"]),
        "supports_f64": f64,
        "batch": batch_report,
        "serve": serve_report,
        "mesh": mesh_report,
        "known_exceptions": known,
        "compile": {"requests": requests, "persistent_cache_hits": hits,
                    "compiled": requests - hits, "slowest": slowest,
                    "cache_dir": cache_dir,
                    "cache_files": [files_before, files_after]},
        "wall_s": round(time.perf_counter() - t_start, 1),
    }
    faulthandler.cancel_dump_traceback_later()
    say(f"report: {json.dumps(report)}")
    # the verdict, last: exactly these keys, the device as JAX reports it
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
