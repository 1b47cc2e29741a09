"""Every cell of `BENCHMARK.json`, and the four-chip cell kept for the PR that
takes up the mesh, end to end on the CPU through a tiny configuration file
the test writes itself: the harness finds the cell's files by name, checks
every answer, and prints the contract's last line. A CPU run is a rehearsal:
it says nothing about speed, and the output says so."""

import json

import pytest

from tests.benchmark import helpers

helpers.load_run()  # puts the benchmark's directory on sys.path
from benchlib import manifest as M  # noqa: E402
from benchlib.registry import Registry  # noqa: E402

with open(helpers.MANIFEST) as _f:
    CELLS = [w["name"] for w in json.load(_f)["workloads"]]
if helpers.MESH_CELL not in CELLS:  # the stand-in, until the real one is there
    CELLS.append(helpers.MESH_CELL)


@pytest.fixture()
def manifest_path(tmp_path):
    return helpers.tiny_manifest(tmp_path, helpers.add_mesh_cell)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_end_to_end_on_cpu(cell, manifest_path, capsys):
    rc, lines = helpers.run_cell(capsys, manifest_path, cell)
    assert rc == 0, lines
    result = json.loads(lines[-1])
    assert set(result) == helpers.RESULT_KEYS
    assert result["correct"] is True
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {"query_s", "setup_s"}
    for metric in result["metrics"].values():
        assert set(metric) == {"value", "unit"} and metric["value"] > 0
    device = result["device"]
    assert set(device) == helpers.DEVICE_KEYS | {"rehearsal_not_a_measurement"}
    assert device["platform"] == "cpu"
    # the line before carries the window's order statistics
    assert lines[-2].startswith("readings: ")
    readings = json.loads(lines[-2][len("readings: "):])
    assert set(readings["query_s"]) == {"n", "p25", "p50", "p75", "min", "max"}
    assert readings["query_s"]["n"] == result["attempted"]
    assert readings["window_compiles"] == []


def test_mesh_cell_used_the_mesh(manifest_path, capsys):
    rc, lines = helpers.run_cell(capsys, manifest_path, helpers.MESH_CELL)
    assert rc == 0, lines
    counters = json.loads(lines[-2][len("readings: "):])["counters_last_query"]
    assert counters["sharded_stages"] > 0 and counters["collective_bytes"] > 0


def test_mesh_cell_traced_reports_the_mesh_metrics(manifest_path, capsys):
    rc, lines = helpers.run_cell(capsys, manifest_path, helpers.MESH_CELL, trace=1)
    assert rc == 0, lines
    result = json.loads(lines[-1])
    assert result["correct"] and result["device"]["count"] >= 4
    assert result["metrics"]["collective_mb"]["value"] > 0
    # the metrics every cell reports are there beside the mesh's
    assert {"device_idle_share", "device_busy_s", "compiles_in_window"} \
        <= set(result["metrics"])


def _with_a_real_mesh_configuration(root):
    """The manifest as the PR that adds `tpcds_sf1_mesh4` leaves it, by files
    and entries only: the configuration (`tpcds_sf1_chip1` through the
    multichip Session), its two cells and the mesh's four metrics appended.
    Returns its path and the configuration; the configuration's file lies
    under ``root``. Once that PR has come, the manifest as it is."""
    with open(helpers.MANIFEST) as f:
        manifest = json.load(f)
    for entry in manifest["configs"]:
        if entry["name"] == helpers.MESH_CONFIG:
            with open(f"{helpers.ROOT}/{entry['file']}") as f:
                return helpers.MANIFEST, json.load(f)
    (chip1,) = [c for c in manifest["configs"] if c["name"] == "tpcds_sf1_chip1"]
    with open(f"{helpers.ROOT}/{chip1['file']}") as f:
        config = helpers.as_mesh(json.load(f))
    config["source"] = "the real one"  # not the stand-in's file
    (root / "mesh4.json").write_text(json.dumps(config))
    manifest["configs"].append(dict(chip1, name=helpers.MESH_CONFIG,
                                    source="the real one",
                                    file=str(root / "mesh4.json")))
    cells = [helpers.MESH_CELL, "q67_agg_rank_mesh4"]
    for cell, traffic in zip(cells, ("q01_repeat", "q67_repeat")):
        manifest["workloads"].append({
            "name": cell, "config": helpers.MESH_CONFIG, "traffic": traffic,
            "chips": 4, "why": "the real mesh cell"})
    for name, unit, source in helpers.MESH_METRICS:
        manifest["per_layer"].append({
            "name": name, "unit": unit, "better": "lower", "source": source,
            "layer": "mesh", "moves": "query_s", "workloads": cells})
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    return str(root / "BENCHMARK.json"), config


def test_the_stand_in_gives_way_to_a_real_mesh_configuration(
        tmp_path, monkeypatch):
    (tmp_path / "real").mkdir(), (tmp_path / "tiny_copy").mkdir()
    real, config = _with_a_real_mesh_configuration(tmp_path / "real")
    monkeypatch.setattr(helpers, "MANIFEST", real)
    seen = {}

    def edit(manifest, tmp):
        seen["tiny_file"] = (tmp / "tiny" / f"{helpers.MESH_CONFIG}.json").read_text()
        seen["manifest"] = json.loads(json.dumps(manifest))
        helpers.add_mesh_cell(manifest, tmp)

    path = helpers.tiny_manifest(tmp_path / "tiny_copy", edit)
    m = M.Manifest(path)
    assert M.problems(m, Registry(m.paths).find) == []
    assert m.data == seen["manifest"]  # nothing appended
    for kind in ("configs", "workloads", "per_layer"):
        names = [e["name"] for e in m.data[kind]]
        assert len(names) == len(set(names)), kind
    # the real configuration's tiny file, as `tiny_manifest` wrote it
    tiny = tmp_path / "tiny_copy" / "tiny" / f"{helpers.MESH_CONFIG}.json"
    assert tiny.read_text() == seen["tiny_file"]
    config["generator_params"]["table_rows"] = helpers.TINY_ROWS
    assert json.loads(tiny.read_text()) == config


def test_cpu_without_allow_cpu_fails_and_prints_no_result(manifest_path, capsys):
    rc, lines = helpers.run_cell(capsys, manifest_path, "q01_scan_topk",
                                 allow_cpu=False)
    assert rc != 0
    assert not any(line.startswith("{") for line in lines)


def test_unknown_cell_is_an_error_that_names_it(manifest_path, capsys):
    rc = helpers.load_run().main(
        ["--manifest", manifest_path, "--seed", "1", "--seconds", "1",
         "--allow-cpu", "--workload", "q99_nothing"])
    captured = capsys.readouterr()
    assert rc != 0 and "q99_nothing" in captured.err
    assert not any(l.startswith("{") for l in captured.out.splitlines())


def test_wrong_answer_makes_the_run_incorrect(tmp_path, capsys):
    """A query class whose reference disagrees with the engine: the warm-up
    stops the run, and no result line is printed."""
    def edit(manifest, tmp):
        (tmp / "tiny" / "queries").mkdir()
        with open(helpers.BENCH_DIR + "/queries/q01.py") as f:
            src = f.read()
        (tmp / "tiny" / "queries" / "q01_off.py").write_text(
            src.replace(".slice(0, limit)", ".slice(1, limit)"))
        (tmp / "tiny" / "traffic").mkdir()
        (tmp / "tiny" / "traffic" / "q01_off_repeat.json").write_text(json.dumps(
            {"loop": "closed", "warmup_queries": 1, "traced_queries": 1,
             "classes": [{"query": "q01_off", "weight": 1, "params": {}}]}))
        manifest["workloads"].append(
            {"name": "q01_off", "config": "tpcds_sf1_chip1",
             "traffic": "q01_off_repeat", "chips": 1, "why": "a wrong reference"})

    path = helpers.tiny_manifest(tmp_path, edit)
    rc, lines = helpers.run_cell(capsys, path, "q01_off")
    assert rc != 0
    assert not any(line.startswith("{") for line in lines)


def test_a_counter_the_program_does_not_report_is_an_error_that_names_it(
        tmp_path, capsys):
    def edit(manifest, tmp):
        path = tmp / "tiny" / "tpcds_sf1_chip1.json"
        config = json.loads(path.read_text())
        config["counters_must"]["batches_on_the_moon"] = [0, 0]
        path.write_text(json.dumps(config))

    path = helpers.tiny_manifest(tmp_path, edit)
    rc = helpers.load_run().main(
        ["--manifest", path, "--seed", "1", "--seconds", "1", "--allow-cpu",
         "--workload", "q01_scan_topk"])
    captured = capsys.readouterr()
    assert rc != 0 and "batches_on_the_moon" in captured.err
    assert not any(l.startswith("{") for l in captured.out.splitlines())


def test_an_end_to_end_metric_the_loop_does_not_report_is_an_error(
        tmp_path, capsys):
    """`serve_p95_ms` is reserved for the serving cells: a cell that lists it
    needs a loop kind that reports it, and the closed loop does not."""
    def edit(manifest, tmp):
        manifest["end_to_end"].append(
            {"name": "serve_p95_ms", "unit": "ms", "better": "lower",
             "bound": 0.05, "source": "host_clock",
             "workloads": ["q01_scan_topk"]})

    path = helpers.tiny_manifest(tmp_path, edit)
    rc = helpers.load_run().main(
        ["--manifest", path, "--seed", "1", "--seconds", "1", "--allow-cpu",
         "--workload", "q01_scan_topk"])
    captured = capsys.readouterr()
    assert rc != 0 and "serve_p95_ms" in captured.err and "closed" in captured.err
    assert not any(l.startswith("{") for l in captured.out.splitlines())
