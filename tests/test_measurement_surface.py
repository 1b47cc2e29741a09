"""What the program measures about itself, and what its documents say it
has (ISSUE 28): ``DEVICE_STATS`` is a set of integer counters the benchmark's
readers take deltas of, the driver layer does not reach up into the
operators, no ``Config`` field is dead, the stats plane prints no figure from
the enqueue clock that is gone, and a document names no tool or record that
does not exist."""

import ast
import dataclasses
import json
import os
import re

import pytest

from blaze_tpu.config import Config, config_override
from blaze_tpu.core import ColumnarBatch
from blaze_tpu.ir import exprs as E
from blaze_tpu.ir import nodes as N
from blaze_tpu.ir import types as T
from blaze_tpu.runtime.session import Session
from blaze_tpu.utils.device import DEVICE_STATS

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = os.path.join(REPO, "blaze_tpu")


def _package_sources():
    for root, _dirs, files in os.walk(PACKAGE):
        for name in files:
            if name.endswith(".py"):
                path = os.path.join(root, name)
                with open(path) as f:
                    yield path, f.read()


def _two_stage_agg(parts):
    col = E.Column
    hash_agg = E.AggExecMode.HASH_AGG

    def agg(child, mode):
        return N.Agg(child, hash_agg, [("k", col("k"))],
                     [N.AggColumn(E.AggExpr(E.AggFunction.SUM, [col("v")],
                                            T.I64), mode, "s")])

    scan = N.FFIReader(schema=parts[0][0].schema, resource_id="src",
                       num_partitions=len(parts))
    exchange = N.ShuffleExchange(agg(scan, E.AggMode.PARTIAL),
                                 N.HashPartitioning([col("k")], 3))
    return agg(exchange, E.AggMode.FINAL)


def test_device_stats_snapshot_is_integers_with_the_readers_keys():
    """``benchmark/run.py`` keeps the integers of the snapshot and its
    readers take these keys' deltas a query; ``kernel_calls`` is the count
    ``test_fused_dispatch_count_guard`` compares."""
    batch = ColumnarBatch.from_pydict({"k": [1, 2, 2, 3] * 50,
                                       "v": list(range(200))})
    parts = [[batch.slice(0, 100)], [batch.slice(100, 100)]]
    before = DEVICE_STATS.snapshot()
    with Session() as sess:
        sess.resources["src"] = lambda p: [x.to_arrow() for x in parts[p]]
        sess.execute_to_table(_two_stage_agg(parts))
    after = DEVICE_STATS.snapshot()
    assert all(type(v) is int for v in after.values()), after
    for key in ("to_host_bytes", "to_device_bytes", "sync_calls",
                "agg_dense_batches", "agg_slot_sorted_batches",
                "agg_sort_batches", "kernel_calls"):
        assert after[key] >= before[key]
    assert after["to_device_bytes"] > before["to_device_bytes"]
    assert after["kernel_calls"] > before["kernel_calls"]
    json.dumps(after)  # /debug/device serves it as it stands


def test_driver_layer_imports_nothing_from_the_operators():
    with open(os.path.join(PACKAGE, "utils", "device.py")) as f:
        tree = ast.parse(f.read())
    imported = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported += [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            imported += [f"{node.module}.{a.name}" for a in node.names]
    assert not [m for m in imported if m.startswith("blaze_tpu.ops")], imported


def test_every_config_field_is_read_in_the_package():
    sources = "\n".join(src for path, src in _package_sources()
                        if path != os.path.join(PACKAGE, "config.py"))
    unread = [f.name for f in dataclasses.fields(Config)
              if not re.search(rf"\b{f.name}\b", sources)]
    assert unread == []


def test_profile_and_explain_name_no_enqueue_clock_figure(tmp_path):
    """The enqueue clock is gone: neither the stored profile nor the text an
    operator reads may show a share of "device time" that was the share of
    time spent dispatching."""
    batch = ColumnarBatch.from_pydict({"k": [i % 7 for i in range(2000)],
                                       "v": list(range(2000))})
    parts = [[batch.slice(0, 1000)], [batch.slice(1000, 1000)]]
    with config_override(profile_store_dir=str(tmp_path / "profiles")):
        with Session() as sess:
            sess.resources["src"] = lambda p: [x.to_arrow() for x in parts[p]]
            text = sess.explain_analyze(_two_stage_agg(parts))
            profile = sess.profile()
    assert profile["stages"] and profile["operators"]
    assert "-- Cardinality (estimated vs actual) --" in text
    for shown in (json.dumps(profile), text):
        for gone in ("device_time_fraction", "device_time_ns", "device=",
                     "device_frac", "kernel_time_s"):
            assert gone not in shown


_SCRIPT = re.compile(r"scripts/\w+\.(?:py|sh)")
# a root record is a bare file name in capitals (BASELINE.json, BENCH_r10.json);
# a path in front (/root/TESTS_LAST_RUN.json, benchmark/configs/x.json) or a
# lower-case name (<query>_trace.json) is something else
_ROOT_JSON = re.compile(r"(?<![\w/.>-])[A-Z][A-Za-z0-9_]*\.json\b")


@pytest.mark.parametrize("document", ["README.md",
                                      ".claude/skills/verify/SKILL.md",
                                      ".github/workflows/ci.yml"])
def test_documents_name_only_tools_and_records_that_exist(document):
    with open(os.path.join(REPO, document)) as f:
        text = f.read()
    named = set(_SCRIPT.findall(text)) | set(_ROOT_JSON.findall(text))
    assert named, f"{document} names no script or record: the pattern is off"
    missing = sorted(n for n in named
                     if not os.path.exists(os.path.join(REPO, n)))
    assert missing == []
