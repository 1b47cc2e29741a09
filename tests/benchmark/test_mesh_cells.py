"""The four-chip configuration `tpcds_sf1_mesh4` and its two cells: the
manifest's entries, a traced rehearsal of each cell on four of conftest's
virtual devices (the chip's plan, every task on its own device), and the two
readers the configuration brought, pinned on synthetic spans and a synthetic
reduction (a CPU trace has no device planes, so `collective_roofline_share`
has nothing to read in a rehearsal)."""

import json
import types

import pytest

from tests.benchmark import helpers
from tests.benchmark.test_host_span_metrics import (_as_on_the_chip, _ctx,
                                                    _reader, _span)

run = helpers.load_run()
from benchlib import manifest as M  # noqa: E402
from benchlib import spans as sp  # noqa: E402

CONFIG = "tpcds_sf1_mesh4"
CELLS = ["q67_agg_rank_mesh4", "q01_scan_topk_mesh4"]
TRAFFIC = {"q67_agg_rank_mesh4": "q67_repeat", "q01_scan_topk_mesh4": "q01_repeat"}
ADDED = {  # name: unit, better, source
    "collective_mb": ("MB", "lower", "program_counter"),
    "collective_s": ("s", "lower", "device_trace"),
    "device_busy_min_s": ("s", "lower", "device_trace"),
    "hbm_peak_skew": ("ratio", "lower", "program_counter"),
    "collective_roofline_share": ("%", "higher", "device_trace"),
    "mesh_exchange_host_s": ("s", "lower", "program_span")}


def test_the_manifest_has_the_configuration_its_cells_and_metrics():
    m = M.Manifest(helpers.MANIFEST)
    (entry,) = [c for c in m.data["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == ["scale_factor"]
    with open(m.config_file(CONFIG)) as f:
        config = json.load(f)
    assert config["chips"] == 4 and config["source"] == entry["source"]
    assert config["session"]["conf"] == {"multichip_enabled": True,
                                         "multichip_devices": 4}
    assert config["counters_must"]["mesh_tasks_off_primary"] == [3, None]
    assert config["counters_must"]["mesh_host_resident_exchanges"] == [0, 0]
    with open(m.config_file("tpcds_sf1_chip1")) as f:
        chip1 = json.load(f)
    for key in ("tables", "generator", "generator_params", "scale_factor"):
        assert config[key] == chip1[key], key
    cells = {w["name"]: w for w in m.data["workloads"]}
    for cell in CELLS:
        assert cells[cell] == {"name": cell, "config": CONFIG,
                               "traffic": TRAFFIC[cell], "chips": 4,
                               "why": cells[cell]["why"]}
    metrics = {e["name"]: e for e in m.data["per_layer"]}
    for name, (unit, better, source) in ADDED.items():
        assert metrics[name] == {
            "name": name, "unit": unit, "better": better, "source": source,
            "layer": "mesh", "moves": "query_s", "workloads": CELLS}, name


@pytest.mark.parametrize("cell", CELLS)
def test_traced_rehearsal_reads_the_mesh(cell, tmp_path, capsys):
    path = helpers.tiny_manifest(tmp_path, _as_on_the_chip)
    rc, lines = helpers.run_cell(capsys, path, cell, trace=1)
    assert rc == 0, lines
    result = json.loads(lines[-1])
    assert result["correct"] and result["failed"] == 0
    assert result["device"]["count"] >= 4
    metrics = result["metrics"]
    assert metrics["collective_mb"]["value"] > 0
    assert metrics["mesh_exchange_host_s"]["value"] > 0
    # no device planes in a CPU trace: nothing for the device readers
    assert "collective_roofline_share" not in metrics
    (readings,) = [line for line in lines if line.startswith("readings: ")]
    counters = json.loads(readings[len("readings: "):])["counters_last_query"]
    # two mesh stages of four tasks, three of them off the first chip
    assert counters["mesh_tasks_off_primary"] >= 6
    assert counters["mesh_host_resident_exchanges"] == 0
    assert counters["sharded_stages"] == 2


def test_mesh_exchange_host_s_takes_out_the_threads_waits(monkeypatch):
    spans = [
        # the lowering thread's exchange: 3 s holding a 1 s wait
        _span(100.0, 103.0, "mesh:exchange"),
        _span(101.0, 102.0, "sync:mesh"),
        # a task thread's wait over the same seconds is not the exchange's
        _span(100.0, 103.0, "sync:agg_partial", tid=2),
        # the collective's enqueue is no wait
        _span(102.0, 102.5, "collective:mesh_exchange"),
        # the next query's exchange
        _span(120.0, 120.5, "mesh:exchange"),
    ]
    monkeypatch.setattr(sp, "load", lambda: spans)
    assert _reader("mesh_exchange_host_s")(_ctx((100.0, 5.0, {}))) == \
        pytest.approx(2.0)
    both = _ctx((100.0, 5.0, {}), (120.0, 1.0, {}))
    assert _reader("mesh_exchange_host_s")(both) == pytest.approx(1.25)
    # a program without the span: nothing to read, and no error
    monkeypatch.setattr(sp, "load", lambda: spans[1:4])
    assert _reader("mesh_exchange_host_s")(_ctx((100.0, 5.0, {}))) is None


def _mesh_ctx(collective_s, counters):
    ctx = _ctx(*[(10.0 * i, 5.0, {}) for i in range(len(counters))])
    for record, c in zip(ctx.records, counters):
        record.counters = {"collective_bytes": c}
    ctx.reduction = types.SimpleNamespace(collective_s=collective_s)
    ctx.system = types.SimpleNamespace(devices=[
        types.SimpleNamespace(device_kind="TPU v5 lite")])
    return ctx


def test_collective_roofline_share_is_the_bytes_off_a_chip_over_the_links():
    # 4 chips, 3 queries, 0.009 s of collectives a chip in all: 3 ms a
    # query; 1.6e9 bytes of send buffers, of which a chip sends 3/16 off:
    # 0.3 GB, 1.5 ms at 200 GB/s
    ctx = _mesh_ctx({0: 0.009, 1: 0.009, 2: 0.009, 3: 0.009},
                    [1.6e9, 1.6e9, 1.6e9])
    assert _reader("collective_roofline_share")(ctx) == pytest.approx(50.0)
    # one chip, or no collective operation in the trace: nothing to read
    assert _reader("collective_roofline_share")(
        _mesh_ctx({0: 0.003}, [1.6e9])) is None
    assert _reader("collective_roofline_share")(
        _mesh_ctx({0: 0.0, 1: 0.0}, [1.6e9])) is None
