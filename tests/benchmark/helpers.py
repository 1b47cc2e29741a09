"""Shared by the benchmark's tests: the harness imported from its file, and a
copy of the manifest in a temporary directory whose configurations are tiny."""

import copy
import importlib.util
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH_DIR = os.path.join(ROOT, "benchmark")
MANIFEST = os.path.join(ROOT, "BENCHMARK.json")
# row counts for the rehearsals, in place of Table 3-2's
TINY_ROWS = {"store_sales": 6000, "store_returns": 900, "item": 300,
             "store": 12, "customer": 1000}

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device", "compared"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


def load_run():
    """`benchmark/run.py` as a module (it puts its own directory on
    `sys.path`, so `benchlib` imports after this)."""
    if "_benchmark_run" not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            "_benchmark_run", os.path.join(BENCH_DIR, "run.py"))
        mod = importlib.util.module_from_spec(spec)
        sys.modules["_benchmark_run"] = mod
        spec.loader.exec_module(mod)
    return sys.modules["_benchmark_run"]


def tiny_manifest(tmp_path, edit=None):
    """Writes `<tmp>/BENCHMARK.json`: the real manifest, its configurations
    rewritten by the test into `<tmp>/tiny/` with a few thousand rows, the
    benchmark's own directory linked beside it. ``edit(manifest_dict,
    tmp_path)`` may add entries and files before it is written. Returns its
    path."""
    with open(MANIFEST) as f:
        manifest = json.load(f)
    manifest = copy.deepcopy(manifest)
    os.symlink(BENCH_DIR, tmp_path / "benchmark")
    (tmp_path / "tiny").mkdir()
    for entry in manifest["configs"]:
        with open(os.path.join(ROOT, entry["file"])) as f:
            config = json.load(f)
        config["generator_params"]["table_rows"] = TINY_ROWS
        entry["file"] = f"tiny/{entry['name']}.json"
        (tmp_path / entry["file"]).write_text(json.dumps(config))
    manifest["paths"] = ["benchmark", "tiny"]
    if edit is not None:
        edit(manifest, tmp_path)
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(manifest))
    return str(path)


def run_cell(capsys, manifest_path, workload, trace=0, allow_cpu=True, seconds=1):
    """One in-process run; returns (exit code, stdout lines)."""
    argv = ["--manifest", manifest_path, "--workload", workload, "--seed", "7",
            "--seconds", str(seconds), "--trace", str(trace)]
    if allow_cpu:
        argv.append("--allow-cpu")
    from blaze_tpu.ops.joins.bhj import clear_build_cache

    capsys.readouterr()
    try:
        rc = load_run().main(argv)
    finally:
        # the program keeps built join maps per process under the plan's id;
        # other test files reuse ids over other data, so leave none behind
        clear_build_cache()
    return rc, capsys.readouterr().out.strip().splitlines()


MESH_CONFIG = "tpcds_sf1_mesh4"
MESH_CELL = "q01_scan_topk_mesh4"
# the mesh's per-layer metrics: name, unit, source (their readers are in the
# benchmark already)
MESH_METRICS = (("collective_mb", "MB", "program_counter"),
                ("collective_s", "s", "device_trace"),
                ("device_busy_min_s", "s", "device_trace"),
                ("hbm_peak_skew", "ratio", "program_counter"))


def as_mesh(config):
    """``config`` (a one-chip configuration's file) through the multichip
    Session over four devices, with the counters that say the mesh did the
    work."""
    config.update(name=MESH_CONFIG, chips=4, counters_must={
        "sharded_stages": [1, None], "collective_bytes": [1, None]})
    config["session"]["conf"].update(multichip_enabled=True, multichip_devices=4)
    return config


def add_mesh_cell(manifest, tmp_path):
    """The four-chip cell as the PR that takes up S9 would add it, by files
    and entries only: `tpcds_sf1_chip1` through `Config(multichip_enabled)`
    over four of conftest's virtual devices and the mesh's four per-layer
    metrics. A stand-in: where the manifest has the real configuration, it
    and its cells are rehearsed and nothing is added."""
    if any(c["name"] == MESH_CONFIG for c in manifest["configs"]):
        return
    with open(tmp_path / "tiny" / "tpcds_sf1_chip1.json") as f:
        config = as_mesh(json.load(f))
    (tmp_path / "tiny" / f"{MESH_CONFIG}.json").write_text(json.dumps(config))
    manifest["configs"].append({
        "name": MESH_CONFIG, "source": "the tiny star through the multichip Session",
        "file": f"tiny/{MESH_CONFIG}.json", "reduced": ["scale_factor"],
        "why": "the same data through the multichip Session"})
    manifest["workloads"].append({
        "name": MESH_CELL, "config": MESH_CONFIG, "traffic": "q01_repeat",
        "chips": 4, "why": "the sharded runner and the mesh exchange"})
    for name, unit, source in MESH_METRICS:
        manifest["per_layer"].append({
            "name": name, "unit": unit, "better": "lower", "source": source,
            "layer": "mesh", "moves": "query_s", "workloads": [MESH_CELL]})
