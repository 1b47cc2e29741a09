"""A later PR adds a cell by adding files and appending entries, editing no
file that is there: here one configuration, one traffic mix, one loop kind,
one query class and one per-layer reader, in a directory of their own beside
the benchmark's, found by name and run."""

import hashlib
import json
import os

from tests.benchmark import helpers

QUERY_CLASS = '''
"""Rows per store: scan store_returns -> two-stage COUNT -> sort by store."""
from benchlib import plans

TABLES = ("store_returns",)
SCANNED = "store_returns"
BYTES_PER_ROW = 8
ORDERED = True
ENGINE_COLUMNS = ("sr_store_sk", "cnt")
REFERENCE_COLUMNS = ("sr_store_sk", "sr_store_sk_count")


def plan(data):
    from blaze_tpu.ir import exprs as E
    from blaze_tpu.ir import nodes as N

    agg = plans.two_stage_agg(
        plans.scan(data, "store_returns"),
        [("sr_store_sk", E.Column("sr_store_sk"))],
        [("cnt", E.AggExpr(E.AggFunction.COUNT, []))], data.shuffle_partitions)
    return N.Sort(N.ShuffleExchange(agg, N.SinglePartitioning(1)),
                  [E.SortOrder(E.Column("sr_store_sk"))])


def reference(tables):
    g = tables["store_returns"].group_by("sr_store_sk").aggregate(
        [("sr_store_sk", "count")])
    return g.sort_by("sr_store_sk")
'''

LOOP_KIND = '''
"""A loop kind of its own: exactly two queries per call, whatever the clock
says (it stands for the open loop a later PR brings)."""
import time


def run(system, next_query, seconds, max_queries, probe):
    records = []
    for _ in range(min(2, max_queries or 2)):
        name, plan = next_query()
        with probe(len(records), name) as record:
            record.t0 = time.perf_counter()
            record.table = system.session.execute_to_table(plan)
            record.seconds = time.perf_counter() - record.t0
        records.append(record)
    return records


def end_to_end(records, seconds):
    """Its own end-to-end metric beside the one every cell has."""
    times = [r.seconds for r in records]
    return {"query_s": sum(times) / len(times), "shots_per_s": len(times) / seconds}
'''

READER = '''
"""Rows the traced queries handed back, from the program's counters."""


def read(ctx):
    return float(len(ctx.records))
'''


def _tree_digest(root):
    digest = hashlib.sha256()
    for directory, dirs, files in sorted(os.walk(root)):
        dirs[:] = sorted(d for d in dirs if d != "__pycache__")
        for name in sorted(files):
            path = os.path.join(directory, name)
            digest.update(path.encode())
            with open(path, "rb") as f:
                digest.update(f.read())
    return digest.hexdigest()


def _add_a_cell(manifest, tmp):
    extra = tmp / "extra"
    for kind in ("configs", "traffic", "loops", "queries", "readers"):
        (extra / kind).mkdir(parents=True)
    with open(tmp / "tiny" / "tpcds_sf1_chip1.json") as f:
        config = json.load(f)
    config.update(name="star_small_stores")
    config["generator_params"].update(
        fact_files=2, table_rows=dict(helpers.TINY_ROWS, store=50))
    config.update(scan_partitions=2, shuffle_partitions=2)
    (extra / "configs" / "star_small_stores.json").write_text(json.dumps(config))
    (extra / "queries" / "rows_per_store.py").write_text(QUERY_CLASS)
    (extra / "loops" / "two_shots.py").write_text(LOOP_KIND)
    (extra / "readers" / "queries_traced.py").write_text(READER)
    (extra / "traffic" / "count_and_q01.json").write_text(json.dumps({
        "loop": "two_shots", "warmup_queries": 1,
        "traced_queries": 2,
        "classes": [{"query": "rows_per_store", "weight": 3, "params": {}},
                    {"query": "q01", "weight": 1, "params": {"limit": 10}}]}))
    manifest["paths"].append("extra")
    manifest["configs"].append({
        "name": "star_small_stores", "source": "ours: the tiny star with 50 stores",
        "file": "extra/configs/star_small_stores.json", "reduced": ["scale_factor"],
        "why": "a configuration a later PR adds as a file of its own"})
    manifest["workloads"].append({
        "name": "rows_per_store_mix", "config": "star_small_stores",
        "traffic": "count_and_q01", "chips": 1,
        "why": "a cell a later PR adds: new class, existing class, new loop kind"})
    manifest["end_to_end"].append({
        "name": "shots_per_s", "unit": "1/s", "better": "higher", "bound": 0.05,
        "source": "host_clock", "workloads": ["rows_per_store_mix"]})
    manifest["per_layer"].append({
        "name": "queries_traced", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "driver", "moves": "query_s",
        "workloads": ["rows_per_store_mix"]})


def test_a_new_cell_is_files_and_entries_only(tmp_path, capsys):
    before = _tree_digest(helpers.BENCH_DIR)
    path = helpers.tiny_manifest(tmp_path, _add_a_cell)

    helpers.load_run()
    from benchlib import manifest as M
    from benchlib.registry import Registry

    m = M.Manifest(path)
    assert M.problems(m, Registry(m.paths).find) == []

    rc, lines = helpers.run_cell(capsys, path, "rows_per_store_mix", trace=0)
    assert rc == 0, lines
    result = json.loads(lines[-1])
    assert result["correct"] and result["attempted"] == 2
    # the new loop kind brings an end-to-end metric of its own
    assert set(result["metrics"]) == {"query_s", "setup_s", "shots_per_s"}
    assert result["metrics"]["shots_per_s"] == {"value": 2.0, "unit": "1/s"}

    rc, lines = helpers.run_cell(capsys, path, "rows_per_store_mix", trace=1)
    assert rc == 0, lines
    result = json.loads(lines[-1])
    assert result["correct"] and result["failed"] == 0
    assert result["metrics"]["queries_traced"] == {"value": 2.0, "unit": "count"}
    # the metrics every cell reports are there too; the mesh's are not
    assert "compiles_in_window" in result["metrics"]
    assert "collective_mb" not in result["metrics"]
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    assert {"busy_s", "window_s"} <= set(result["device"])

    # an existing cell still runs from the same manifest, and nothing that
    # was there has changed
    rc, lines = helpers.run_cell(capsys, path, "q06_bhj_agg")
    assert rc == 0 and json.loads(lines[-1])["correct"]
    assert _tree_digest(helpers.BENCH_DIR) == before
