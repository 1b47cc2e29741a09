"""`BENCHMARK.json` keeps the contract's rules, and the checker that says so
refuses the manifests it should.

The rule for every test of this directory: later PRs append configurations,
cells (on one chip or four) and metrics by files and entries, and may not
edit a test. So a test of a PR's additions names those additions and checks
them. It never asserts the manifest's size, its last entry, the chips of
cells it did not add, or that a cell is absent from a list."""

import copy
import glob
import json
import os
import re

import pytest

from tests.benchmark import helpers

helpers.load_run()  # puts benchmark/ on sys.path
from benchlib import manifest as M  # noqa: E402
from benchlib.registry import Registry  # noqa: E402


def _problems(tmp_path, data):
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(data))
    m = M.Manifest(str(path))
    return M.problems(m)


def test_the_manifest_keeps_every_rule():
    m = M.Manifest(helpers.MANIFEST)
    registry = Registry(m.paths)
    assert M.problems(m, registry.find) == []


def test_every_file_a_cell_names_exists():
    m = M.Manifest(helpers.MANIFEST)
    registry = Registry(m.paths)
    for cell in m.data["workloads"]:
        with open(m.config_file(cell["config"])) as f:
            config = json.load(f)
        assert config["chips"] == cell["chips"]
        assert registry.find("generators", config["generator"], (".py",))
        traffic = registry.data("traffic", cell["traffic"])
        assert registry.find("loops", traffic["loop"], (".py",))
        for cls in traffic["classes"]:
            assert registry.find("queries", cls["query"], (".py",))
        for metric in m.metrics_for("per_layer", cell["name"]):
            assert callable(registry.reader(metric["name"]))


def test_configuration_files_say_what_the_manifest_says():
    m = M.Manifest(helpers.MANIFEST)
    for entry in m.data["configs"]:
        with open(m.config_file(entry["name"])) as f:
            config = json.load(f)
        assert config["name"] == entry["name"]
        assert config["source"] == entry["source"]
        assert sorted(config["reduced_from"]) == sorted(entry["reduced"])
        for key in ("assumed", "guarantees", "counters_must", "deployment"):
            assert config[key], key


def test_no_test_here_holds_a_manifest_list_to_its_last_entry():
    """The rule above, where a pattern can see it: a later PR's entries come
    last, so no test of this directory reads a list of the manifest from its
    end (PR 32's `per_layer[-1]` stood in every cell-adding PR's way)."""
    from_the_end = re.compile(
        r"""\[\s*["'](configs|workloads|end_to_end|per_layer)["']\s*\]\s*\[\s*-""")
    here = os.path.dirname(os.path.abspath(__file__))
    for path in sorted(glob.glob(os.path.join(here, "*.py"))):
        with open(path) as f:
            for number, line in enumerate(f, 1):
                assert not from_the_end.search(line), f"{path}:{number}"


def _break_name(d):
    d["workloads"][0]["name"] = "has space"


def _break_unit(d):
    d["end_to_end"][0]["unit"] = "tokens per second"


def _break_four_chips(d):
    """One cell more than half asks for four chips, whatever their number."""
    for w in d["workloads"][:len(d["workloads"]) // 2 + 1]:
        w["chips"] = 4


def _break_moves(d):
    d["per_layer"][0]["moves"] = "nothing_e2e"


def _break_unused_config(d):
    d["configs"].append(dict(d["configs"][0], name="unused",
                             file="benchmark/traffic/q01_repeat.json"))


def _break_extra_key(d):
    d["per_layer"][0]["why"] = "not allowed here"


def _break_bound(d):
    d["end_to_end"][0]["bound"] = 0.5


def _break_no_setup(d):
    d["end_to_end"] = [e for e in d["end_to_end"] if e["name"] != "setup_s"]


def _break_duplicate_pair(d):
    d["workloads"].append(dict(d["workloads"][0], name="again"))


def _break_source(d):
    d["end_to_end"][0]["source"] = "program_counter"


def _break_run_seconds(d):
    d["run_seconds"] = 52


def _break_command(d):
    d["command"] = ["python3", "../elsewhere/run.py"]


@pytest.mark.parametrize("breaker", [
    _break_name, _break_unit, _break_four_chips, _break_moves,
    _break_unused_config, _break_extra_key, _break_bound, _break_no_setup,
    _break_duplicate_pair, _break_source, _break_run_seconds, _break_command,
], ids=lambda f: f.__name__[len("_break_"):])
def test_a_broken_manifest_is_refused(breaker, tmp_path):
    with open(helpers.MANIFEST) as f:
        data = json.load(f)
    # config files are looked up beside the manifest: point at the real ones
    (tmp_path / "benchmark").symlink_to(helpers.BENCH_DIR)
    assert _problems(tmp_path, copy.deepcopy(data)) == []
    breaker(data)
    assert _problems(tmp_path, data) != []


def test_unknown_file_name_is_an_error_that_names_it():
    from benchlib.registry import UnknownName

    registry = Registry([helpers.BENCH_DIR])
    with pytest.raises(UnknownName, match="open_poisson"):
        registry.module("loops", "open_poisson")
    with pytest.raises(UnknownName, match="no_such_metric"):
        registry.reader("no_such_metric")
