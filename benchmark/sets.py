#!/usr/bin/env python3
"""Sets of runs of some cells, one process after another on the chip, and the
spreads the driver's check would read from them:

    python3 benchmark/sets.py --out chiprun_out/sets --cells q51_cume_window \\
        --sets 2 --runs 6 [--seconds 51] [--seed-base N] [--trace 0|1] \\
        [--env NAME=VALUE ...] [--label base] [--budget-s 3000]

Every set of a cell has the same seeds, as the check's two sets have. Each run
is `BENCHMARK.json`'s command in a process of its own; this one never touches
JAX, so it holds no chip. A run's result line and `readings:` line go to
`<out>/runs.jsonl`, its whole output to `<out>/<label>.<cell>.<set>.<run>.out`
and `.err`. `--seconds` defaults to the manifest's `run_seconds`; `--env`
sets variables for the runs only (a setting on trial). `python3
benchmark/sets.py --report <runs.jsonl> ...` prints the table again from
files."""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.realpath(__file__))
ROOT = os.path.dirname(HERE)
if HERE not in sys.path:
    sys.path.insert(0, HERE)

from benchlib import spread  # noqa: E402


def seeds_for(cell: str, base: int, runs: int):
    """Large seeds, as the driver's are; a cell's differ from another's."""
    salt = sum(ord(ch) * (i + 1) for i, ch in enumerate(cell)) % 9973
    return [(base + 104729 * i + salt) % (2 ** 31 + 1000) for i in range(runs)]


def one_run(command, cell, seed, seconds, trace, env, stem):
    argv = command + ["--workload", cell, "--seed", str(seed),
                      "--seconds", str(seconds), "--trace", str(trace)]
    t0 = time.time()
    with open(stem + ".out", "w") as out, open(stem + ".err", "w") as err:
        rc = subprocess.run(argv, cwd=ROOT, env=env, stdout=out, stderr=err).returncode
    record = {"rc": rc, "wall_s": time.time() - t0}
    with open(stem + ".out") as f:
        lines = f.read().strip().splitlines()
    for line in lines:
        if line.startswith("readings: "):
            record["readings"] = json.loads(line[len("readings: "):])
    if rc == 0 and lines:
        record["result"] = json.loads(lines[-1])
    return record


def metric_sets(records, label, cell, metric, seconds=None):
    """``[[value of run 0, ...] of set 0, ...]``; with ``seconds``, query_s as
    a window of that length would have read it (the median of the queries
    that started inside it)."""
    sets = {}
    for r in records:
        if (r["label"], r["cell"]) != (label, cell) or r["trace"] or "result" not in r:
            continue
        if seconds is None:
            value = r["result"]["metrics"][metric]["value"]
        else:
            rd = r["readings"]
            kept = [s for s, t in zip(rd["query_seconds"], rd["query_starts"])
                    if t < seconds]
            value = statistics.median(kept)
        sets.setdefault(r["set"], []).append(value)
    return [sets[k] for k in sorted(sets)]


def report(records, shorter=None):
    seen = dict.fromkeys((r["label"], r["cell"]) for r in records if not r["trace"])
    rows = [("query_s", "query_s", None), ("setup_s", "setup_s", None)]
    if shorter:
        rows.insert(1, (f"query_s@{shorter:g}s", "query_s", shorter))
    by_label = {}
    for label, cell in seen:
        for name, metric, seconds in rows:
            sets = metric_sets(records, label, cell, metric, seconds)
            if not sets or any(len(s) < 3 for s in sets):
                continue
            got = spread.of_sets(sets)
            print(f"{label:12s} {cell:18s} {name:14s} median {got['median']:.6f} "
                  f"sets {['%.6f' % m for m in got['set_medians']]} "
                  f"tight {100 * got['tight']:.3f}% loose {100 * got['loose']:.3f}% "
                  f"trimmed range {100 * got['trimmed_range']:.3f}% "
                  f"range {100 * got['range']:.3f}%")
            for s in sets:
                print("    " + " ".join(f"{v:.6f}" for v in s))
            if name == "query_s":
                by_label.setdefault(label, {})[cell] = got
    for label, cells in by_label.items():
        window = spread.bound_window(cells)
        print(f"{label}: query_s's bound may lie from {window['lowest']:.4f} "
              f"to {window['highest']:.4f} over {sorted(cells)}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--report", nargs="+", metavar="RUNS_JSONL")
    ap.add_argument("--shorter", type=float,
                    help="also read query_s as a window of this length would")
    ap.add_argument("--out")
    ap.add_argument("--cells", nargs="+")
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--seconds", type=float)
    ap.add_argument("--seed-base", type=int, default=2_000_000_000)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--env", action="append", default=[], metavar="NAME=VALUE")
    ap.add_argument("--label", default="base")
    ap.add_argument("--budget-s", type=float, default=float("inf"),
                    help="start no further run once this long has passed")
    args = ap.parse_args(argv)
    if args.report:
        records = []
        for path in args.report:
            with open(path) as f:
                records += [json.loads(line) for line in f if line.strip()]
        report(records, args.shorter)
        return 0
    if not (args.out and args.cells):
        ap.error("--out and --cells, or --report")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    seconds = args.seconds or manifest["run_seconds"]
    env = dict(os.environ, **dict(e.split("=", 1) for e in args.env))
    os.makedirs(args.out, exist_ok=True)
    t_start, records = time.time(), []
    with open(os.path.join(args.out, "runs.jsonl"), "a") as log:
        for cell in args.cells:
            seeds = seeds_for(cell, args.seed_base, args.runs)
            for k in range(args.sets):
                for i, seed in enumerate(seeds):
                    if time.time() - t_start > args.budget_s:
                        print(f"budget of {args.budget_s:g}s spent before "
                              f"{cell} set {k} run {i}", flush=True)
                        report(records, args.shorter)
                        return 4
                    stem = os.path.join(args.out, f"{args.label}.{cell}.{k}.{i}")
                    record = one_run(manifest["command"], cell, seed, seconds,
                                     args.trace, env, stem)
                    record.update(label=args.label, cell=cell, set=k, run=i,
                                  seed=seed, seconds=seconds, trace=args.trace,
                                  env=args.env)
                    records.append(record)
                    log.write(json.dumps(record) + "\n")
                    log.flush()
                    got = record.get("result", {})
                    values = {} if args.trace else {
                        n: m["value"] for n, m in got.get("metrics", {}).items()}
                    print(f"{args.label} {cell} set {k} run {i} seed {seed} "
                          f"rc {record['rc']} {record['wall_s']:.0f}s "
                          f"correct {got.get('correct')} {values}", flush=True)
    report(records, args.shorter)
    return 0 if all(r["rc"] == 0 and r["result"]["correct"] for r in records) else 1


if __name__ == "__main__":
    sys.exit(main())
