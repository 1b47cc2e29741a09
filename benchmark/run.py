#!/usr/bin/env python3
"""One run of one cell of `BENCHMARK.json`:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

finds the cell, then its configuration, traffic mix, loop kind, query
classes, generator and per-layer readers by name in the benchmark's
directories; makes the data from the seed; computes the reference answers;
builds the system once; warms up every query class the traffic uses (all of
that is `setup_s`); then measures for `--seconds`. Every answer is compared
with the reference and the program's counters must say the chip did the work.

`--trace 0` reports the cell's end-to-end metrics from the host clock.
`--trace 1` profiles a short steady stretch with `jax.profiler` and reports
the per-layer metrics, each through its reader, plus `breakdown`.

The last line of stdout is the result object (its last key, `compared`, has
every number `correct` was decided by beside its limit, and the last line of
stderr says the same); the line before it, `readings: {...}`, has the
window's order statistics and every query's time in order. A run that finds no
TPU, or fewer chips than the cell asks for, exits non-zero and prints no
result (`--allow-cpu` is for the rehearsal tests and marks the output).
"""

from __future__ import annotations

import time

_T_PROCESS = time.perf_counter()  # before the heavy imports: set-up starts here

import argparse
import contextlib
import dataclasses
import json
import os
import random
import shutil
import sys
import tempfile
from typing import Dict, List, Optional

HERE = os.path.dirname(os.path.realpath(__file__))
ROOT = os.path.dirname(HERE)  # the checkout: the program is imported from here
for _p in (ROOT, HERE):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from benchlib import compilelog, device, plans, stats, xplane  # noqa: E402
from benchlib.manifest import Manifest, ManifestError  # noqa: E402
from benchlib.registry import Registry, UnknownName  # noqa: E402

SELF_TIME = "elapsed_compute_time_ns"


class BenchFailure(Exception):
    pass


def say(message: str):
    print(message, flush=True)


@dataclasses.dataclass
class QueryRecord:
    """One query of a loop: what the loop fills in, and what the probe reads
    from the program's counters around it."""

    index: int
    name: str
    t0: float = 0.0
    seconds: float = 0.0
    table: object = None
    error: Optional[BaseException] = None
    counters: Dict[str, int] = dataclasses.field(default_factory=dict)
    device_stats: Dict[str, int] = dataclasses.field(default_factory=dict)
    self_ns: Dict[str, int] = dataclasses.field(default_factory=dict)
    wrong: Optional[str] = None  # why the answer does not count, if it does not
    wrong_counter: bool = False  # ... because a counter left `counters_must`


@dataclasses.dataclass
class System:
    """The system under test, built once in set-up."""

    session: object
    config: dict
    data: plans.Dataset
    devices: list


@dataclasses.dataclass
class ReadContext:
    """What a per-layer reader may read."""

    system: System
    classes: Dict[str, object]           # query class name -> its module
    records: List[QueryRecord]           # the traced queries
    trace: xplane.Trace
    reduction: xplane.Reduction
    peak_bytes: List[Optional[int]]      # per device, after the window
    setup_compiles: int                  # executables XLA compiled in set-up
    setup_cache_hits: int
    cache_was_warm: bool
    window_compiles: List[tuple]         # (name, seconds) asked for in the window

    def per_query(self, value) -> float:
        """Median over the traced queries of ``value(record, index)``."""
        return stats.median([value(r, i) for i, r in enumerate(self.records)])


def self_time_by_class(node: dict, into: Optional[dict] = None) -> dict:
    """`elapsed_compute_time_ns` of a `MetricNode.to_dict()` tree summed by
    node (operator class) name."""
    into = {} if into is None else into
    ns = node["values"].get(SELF_TIME)
    if ns:
        into[node["name"]] = into.get(node["name"], 0) + ns
    for child in node["children"]:
        self_time_by_class(child, into)
    return into


def _delta(after: dict, before: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in after.items()}


class Probe:
    """Snapshots of the program's counters around each query, outside the
    timed span; in a traced run also a `TraceAnnotation` per query. The
    counters are those the configuration's ``counters_must`` names (a reader
    that needs another has the configuration name it, with the range it
    must keep, `[0, null]` if any)."""

    def __init__(self, session, counters, traced: bool):
        from blaze_tpu.utils.device import DEVICE_STATS

        self.session = session
        self.counters = tuple(counters)  # those the configuration names
        self.traced = traced
        self.device_stats = DEVICE_STATS

    def _snapshot(self):
        snap = {"counters": self.session.metrics.totals(self.counters),
                "device_stats": {k: v for k, v in self.device_stats.snapshot().items()
                                 if isinstance(v, int)}}
        if self.traced:
            snap["self_ns"] = self_time_by_class(self.session.metrics.to_dict())
        return snap

    @contextlib.contextmanager
    def __call__(self, index: int, name: str):
        import jax.profiler

        record = QueryRecord(index, name)
        before = self._snapshot()
        note = (jax.profiler.TraceAnnotation("bench_query", index=index, query=name)
                if self.traced else contextlib.nullcontext())
        with note:
            yield record
        after = self._snapshot()
        for key in before:
            setattr(record, key, _delta(after[key], before[key]))


def check_record(record: QueryRecord, expected: dict, must: dict):
    """Sets ``record.wrong`` unless the answer equals the reference and the
    counters are inside the configuration's ``counters_must`` ranges."""
    if record.error is not None:
        record.wrong = f"raised {type(record.error).__name__}: {record.error}"
        return
    cls, want = expected[record.name]
    got = plans.rows_of(record.table, cls.ENGINE_COLUMNS, cls.ORDERED)
    if got != want:
        record.wrong = (f"answer differs from the reference "
                        f"({len(got)} rows against {len(want)})")
        return
    for counter, (lo, hi) in must.items():
        n = record.counters[counter]
        if (lo is not None and n < lo) or (hi is not None and n > hi):
            record.wrong = f"{counter} = {n}, outside [{lo}, {hi}]"
            record.wrong_counter = True
            return
    record.table = None  # compared; let it go


def reported_names(node: dict, into: Optional[set] = None) -> set:
    """Every metric name of a `MetricNode.to_dict()` tree."""
    into = set() if into is None else into
    into.update(node["values"])
    for child in node["children"]:
        reported_names(child, into)
    return into


def require_counters(session, names):
    """A counter a configuration or reader names has to be one the program
    reports: one its metric tree holds after the warm-up, or one of the
    counters it documents (`runtime.metrics.TRIPWIRE_METRICS`, which stay out
    of the tree while they are 0). Any other name would read 0 for ever."""
    from blaze_tpu.runtime.metrics import TRIPWIRE_METRICS

    known = reported_names(session.metrics.to_dict()) | set(TRIPWIRE_METRICS)
    unknown = sorted(set(names) - known)
    if unknown:
        raise BenchFailure(f"the program reports no counter named {unknown} "
                           f"(`counters_must` of the configuration)")


def build_session(overrides: dict, traced: bool):
    from blaze_tpu.config import get_config
    from blaze_tpu.runtime.session import Session

    conf = get_config()
    known = {f.name for f in dataclasses.fields(conf)}
    unknown = sorted(set(overrides) - known)
    if unknown:
        raise BenchFailure(f"the configuration overrides Config fields the "
                           f"program does not have: {unknown}")
    if traced:
        overrides = dict(overrides, trace_enable=True)
    return Session(conf=dataclasses.replace(conf, **overrides))


def usage() -> dict:
    """This process's CPU seconds and context switches so far: taken around
    the window, they tell a process that worked more from one that waited
    more (involuntary switches: somebody else wanted its cores)."""
    import resource

    ru = resource.getrusage(resource.RUSAGE_SELF)
    return {"user_s": ru.ru_utime, "sys_s": ru.ru_stime,
            "voluntary_switches": ru.ru_nvcsw,
            "involuntary_switches": ru.ru_nivcsw,
            "minor_faults": ru.ru_minflt}


def host_facts(usage_before) -> dict:
    """What differs from one process or machine to the next and is not the
    program's: what the window cost in CPU time and switches, the cores this
    process may use, the hash seed, Arrow's pools, the interpreter's switch
    interval and the collector's runs. (The load average reads 0.00 on the
    chip tool's machine whatever runs there, so it is not among them.)"""
    import gc

    import pyarrow as pa

    return {"window_usage": _delta(usage(), usage_before),
            "cpus": os.cpu_count(), "affinity": len(os.sched_getaffinity(0)),
            "hashseed": os.environ.get("PYTHONHASHSEED"),
            "arrow_cpu_threads": pa.cpu_count(),
            "arrow_io_threads": pa.io_thread_count(),
            "switch_interval": sys.getswitchinterval(),
            "gc_collections": [g["collections"] for g in gc.get_stats()]}


def cache_files(path) -> int:
    return len(os.listdir(path)) if path and os.path.isdir(path) else 0


def tracer_spans(offset_ns: float):
    """The program's tracer spans as (start, end, "cat:name") on the trace's
    clock. Query and stage spans cover everything and name nothing."""
    from blaze_tpu.obs.tracer import TRACER

    out = []
    for ev in TRACER.snapshot():
        if ev.get("ph") != "X" or ev.get("cat") in ("query", "stage"):
            continue
        start = TRACER.perf_epoch_ns + ev["ts"] * 1e3 + offset_ns
        out.append((start, start + ev["dur"] * 1e3, f"{ev['cat']}:{ev['name']}"))
    return out


def run(args, t_start: float) -> int:
    manifest = Manifest(args.manifest or os.path.join(ROOT, "BENCHMARK.json"))
    registry = Registry(manifest.paths)
    cell = manifest.cell(args.workload)
    with open(manifest.config_file(cell["config"])) as f:
        config = json.load(f)
    traffic = registry.data("traffic", cell["traffic"])
    loop = registry.module("loops", traffic["loop"])
    generator = registry.module("generators", config["generator"])
    classes = {c["query"]: registry.module("queries", c["query"])
               for c in traffic["classes"]}
    traced = bool(args.trace)
    layer_metrics = manifest.metrics_for("per_layer", cell["name"])
    readers = ({m["name"]: registry.reader(m["name"]) for m in layer_metrics}
               if traced else {})
    if config.get("chips", cell["chips"]) != cell["chips"]:
        raise BenchFailure(f"cell {cell['name']} asks for {cell['chips']} chips, "
                           f"its configuration for {config['chips']}")

    devices = device.require(cell["chips"], args.allow_cpu)  # opens the backend
    rehearsal = devices[0].platform != "tpu"
    import jax

    import blaze_tpu  # noqa: F401  (x64; places the compile cache)
    from blaze_tpu.utils import native

    compiles = compilelog.get()
    compiles_at_start = compiles.counts()
    cache_dir = jax.config.jax_compilation_cache_dir
    files_before = cache_files(cache_dir)
    say(f"device: {devices[0].platform} {devices[0].device_kind} x{len(devices)} "
        f"jax {jax.__version__}; compile cache {cache_dir} ({files_before} files)")
    native.ensure_built()

    work = tempfile.mkdtemp(prefix="blaze_bench_")
    trace_dir = args.keep_trace or os.path.join(work, "trace")
    session = None
    try:
        t0 = time.perf_counter()
        # the generator reads its parameters from the configuration, and
        # writes only the tables this cell's query classes scan
        needed = sorted({t for cls in classes.values() for t in cls.TABLES})
        paths = generator.generate(work, args.seed, config, needed)
        data = plans.Dataset(paths, config["scan_partitions"],
                             config["shuffle_partitions"])
        say(f"data: generator {config['generator']} seed {args.seed} "
            f"{({t: data.rows(t) for t in needed})} rows "
            f"in {time.perf_counter() - t0:.1f}s")

        t0 = time.perf_counter()
        tables = {t: data.table(t) for cls in classes.values() for t in cls.TABLES}
        expected, query_plans, weights = {}, {}, {}
        for c in traffic["classes"]:
            cls, params = classes[c["query"]], c.get("params", {})
            answer = cls.reference({t: tables[t] for t in cls.TABLES}, **params)
            expected[c["query"]] = (cls, plans.rows_of(
                answer, cls.REFERENCE_COLUMNS, cls.ORDERED))
            # plans are built once, in set-up: construction is not in the span
            query_plans[c["query"]] = cls.plan(data, **params)
            weights[c["query"]] = c["weight"]
        del tables
        say(f"reference: {({n: len(r) for n, (_c, r) in expected.items()})} rows "
            f"(Acero) in {time.perf_counter() - t0:.1f}s")

        session = build_session(config["session"]["conf"], traced)
        system = System(session, config, data, devices)
        must = {k: tuple(v) for k, v in config["counters_must"].items()}
        probe = Probe(session, must, traced)

        # warm-up: every class, `warmup_queries` times each, checked
        cycle = [item for item in query_plans.items()
                 for _ in range(traffic["warmup_queries"])]
        warm = loop.run(system, iter(cycle).__next__, float("inf"), len(cycle), probe)
        require_counters(session, must)
        for record in warm:
            check_record(record, expected, must)
            if record.wrong:
                raise BenchFailure(f"warm-up {record.name}: {record.wrong}")
        say(f"warm-up: {[round(r.seconds, 2) for r in warm]} s")
        rng = random.Random(args.seed)

        def next_query():
            name = rng.choices(list(weights), list(weights.values()))[0]
            return name, query_plans[name]

        requests, hits = compiles.counts()
        setup_compiles = (requests - compiles_at_start[0]) - (hits - compiles_at_start[1])
        setup_hits = hits - compiles_at_start[1]
        mark = requests

        usage_before = usage()
        if traced:
            from blaze_tpu.obs.tracer import TRACER

            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0  # host spans come from the tracer
            TRACER.reset()
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            try:
                anchor_ns = time.perf_counter_ns()
                with jax.profiler.TraceAnnotation(xplane.ANCHOR):
                    pass
                setup_s = time.perf_counter() - t_start
                records = loop.run(system, next_query, args.seconds,
                                   traffic["traced_queries"], probe)
            finally:
                jax.profiler.stop_trace()
        else:
            setup_s = time.perf_counter() - t_start
            records = loop.run(system, next_query, args.seconds, None, probe)

        window_compiles = compiles.since(mark)
        for record in records:
            check_record(record, expected, must)
            if record.wrong:
                say(f"query {record.index} ({record.name}): {record.wrong}")
        good = [r for r in records if not r.wrong]
        peak_bytes = device.peak_bytes_per_device(devices)
        if not good:
            raise BenchFailure("no query of the window completed with the "
                               "reference's answer")
        say("readings: " + json.dumps({
            "query_s": stats.summary([r.seconds for r in good]),
            # every query of the window in order, so that a drift inside it,
            # or what a shorter window would have read, can be told afterwards
            "query_seconds": [r.seconds for r in good],
            "query_starts": [r.t0 - records[0].t0 for r in good],
            "host": host_facts(usage_before),
            "device_stats_last_query": good[-1].device_stats,
            "setup_s": setup_s, "window_compiles": window_compiles,
            "setup_compiled": setup_compiles, "setup_cache_hits": setup_hits,
            "cache_files": [files_before, cache_files(cache_dir)],
            "counters_last_query": good[-1].counters,
            "self_ns_last_query": good[-1].self_ns,
            "peak_bytes_per_device": peak_bytes}))

        result = {"correct": len(good) == len(records),
                  "attempted": len(records),
                  "failed": len(records) - len(good)}
        described = device.describe(devices, rehearsal)
        if not traced:
            # the loop kind says what its users see; set-up is the harness's
            values = dict(loop.end_to_end(good, args.seconds), setup_s=setup_s)
            wanted = manifest.metrics_for("end_to_end", cell["name"])
            missing = [m["name"] for m in wanted if m["name"] not in values]
            if missing:
                raise BenchFailure(
                    f"loop kind {traffic['loop']!r} reports {sorted(values)}, "
                    f"not the cell's end-to-end metrics {missing}")
            result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                             "unit": m["unit"]} for m in wanted}
        else:
            t0 = time.perf_counter()
            trace = xplane.load(xplane.find_xplane(trace_dir), require_tpu=not rehearsal)
            offset = xplane.clock_offset_ns(trace, anchor_ns)
            intervals = [(r.t0 * 1e9 + offset, (r.t0 + r.seconds) * 1e9 + offset)
                         for r in good]
            reduction = xplane.reduce(trace, intervals, tracer_spans(offset))
            say(f"trace: reduced in {time.perf_counter() - t0:.1f}s; "
                f"busy per chip {reduction.busy_s} of {reduction.window_s:.3f}s")
            ctx = ReadContext(system, classes, good, trace, reduction,
                              peak_bytes, setup_compiles, setup_hits, files_before > 0,
                              window_compiles)
            result["metrics"] = {}
            for m in layer_metrics:
                value = readers[m["name"]](ctx)
                if value is not None:
                    result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
            described["busy_s"] = reduction.mean_busy_s
            described["window_s"] = reduction.window_s
            result["breakdown"] = {
                "device_ops": [list(x) for x in reduction.device_ops],
                "idle_gaps": [list(x) for x in reduction.idle_gaps]}
        result["device"] = described
        # every number `correct` was decided by, beside its limit: the rows
        # compare exactly with the reference's, so each limit is 0
        result["compared"] = compared = {
            "queries_compared": {"value": len(records), "limit": ">=1"},
            "answers_wrong": {"value": sum(
                1 for r in records if r.wrong and not r.wrong_counter), "limit": 0},
            "counters_outside": {"value": sum(
                1 for r in records if r.wrong_counter), "limit": 0}}
        print(json.dumps(result), flush=True)
        print("compared: " + json.dumps(compared), file=sys.stderr, flush=True)
        return 0
    finally:
        if session is not None:
            session.close()
            if traced:  # the tracer is the process's: leave it as it was found
                from blaze_tpu.obs.tracer import TRACER

                TRACER.disable()
                TRACER.reset()
        shutil.rmtree(work, ignore_errors=True)


def main(argv=None, t_start: Optional[float] = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--allow-cpu", action="store_true",
                    help="let a non-TPU backend through (rehearsal tests only)")
    ap.add_argument("--manifest", help="another BENCHMARK.json than the checkout's")
    ap.add_argument("--keep-trace", metavar="DIR",
                    help="write the profiler's trace there and keep it")
    args = ap.parse_args(argv)
    try:
        return run(args, t_start)
    except (BenchFailure, ManifestError, UnknownName, device.DeviceError,
            xplane.TraceError, ImportError) as exc:  # ImportError: no program here
        print(f"benchmark: FAIL: {type(exc).__name__}: {exc}", file=sys.stderr)
        return 1


if __name__ == "__main__":
    sys.exit(main(t_start=_T_PROCESS))
