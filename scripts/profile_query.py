"""Profile one TPC-DS-like bench query end to end: run it with span tracing
enabled and write the three observability artifacts to a directory:

- ``<query>_trace.json``    — Chrome trace events (load in
  https://ui.perfetto.dev or chrome://tracing): query/stage/task/operator/
  spill/shuffle-fetch/kernel spans on one timeline
- ``<query>_metrics.json``  — the full session metric tree, ``*_time_ns``
  values rendered as human durations
- ``<query>_explain.txt``   — EXPLAIN ANALYZE text (per-operator rows,
  batches, self-time, spill counters)

Run: ``python scripts/profile_query.py [q01|q06|q17|q47|q67] [-o OUTDIR]``
Env: BENCH_ROWS (default 200_000 here — profiling wants fast iterations),
BENCH_PARTITIONS (4).
"""

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("BENCH_ROWS", "200000")


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("query", nargs="?", default="q01",
                    choices=["q01", "q06", "q17", "q47", "q67"])
    ap.add_argument("-o", "--out-dir", default="profile_out",
                    help="artifact directory (default: ./profile_out)")
    args = ap.parse_args()

    import bench  # repo-root bench.py (data generators + plan builders)
    from blaze_tpu.config import Config
    from blaze_tpu.obs import dump_profile
    from blaze_tpu.runtime.session import Session

    plan_fn = {"q01": bench.plan_q01, "q06": bench.plan_q06,
               "q17": bench.plan_q17, "q47": bench.plan_q47,
               "q67": bench.plan_q67}[args.query]

    with tempfile.TemporaryDirectory(prefix="blaze_profile_") as tmpdir:
        paths = bench.make_data(tmpdir)
        conf = Config(trace_enable=True)
        t0 = time.perf_counter()
        with Session(conf=conf) as sess:
            explain_text = sess.explain_analyze(plan_fn(paths))
            wall = time.perf_counter() - t0
            artifacts = dump_profile(sess, args.out_dir, args.query,
                                     explain_text=explain_text)
    print(explain_text)
    print(json.dumps({"query": args.query, "wall_s": round(wall, 2),
                      "artifacts": artifacts}, indent=2))


if __name__ == "__main__":
    main()
