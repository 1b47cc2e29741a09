"""Sharded device-primary execution over the mesh (ISSUE 14): tier
negotiation for the "device" tier, bit-identical results across 1/2/8
device meshes (both on the two-stage micro plan and on the five bench
shapes), device-resident shuffle hand-off matching the shm tier bit for
bit, lineage recovery over device-tier segments, and the ``device.put``
failpoint degrading device -> host staging with unchanged results.

The suite runs under conftest's forced 8-host-device CPU mesh
(``--xla_force_host_platform_device_count=8``), so every mesh size here
is real: quick-tier inclusion makes the smoke run exercise actual
multi-device sharding on every box."""

import glob
import os

import numpy as np
import pytest

from blaze_tpu.config import Config, config_override
from blaze_tpu.core import ColumnarBatch
from blaze_tpu.ir import exprs as E
from blaze_tpu.ir import nodes as N
from blaze_tpu.ir import types as T
from blaze_tpu.runtime.session import Session


def _col(n):
    return E.Column(n)


def _summed(sess, name: str) -> int:
    """Sum one metric across the session's whole metric tree."""
    total = 0

    def walk(node):
        nonlocal total
        total += node.get("values", {}).get(name, 0)
        for c in node.get("children", []):
            walk(c)

    walk(sess.metrics.to_dict())
    return total


_TRACKED = ("shuffle_bytes_serialized", "serde_elided_batches",
            "sharded_stages", "collective_bytes", "device_shuffle_bytes",
            "shuffle_tier_degraded", "sharded_batches")


def _two_stage_plan(batch_parts, reducers=4):
    """partial agg -> hash exchange -> final agg -> single-collect sort:
    the same micro plan the zero-copy suite gates, now over the mesh."""
    schema = batch_parts[0][0].schema
    scan = N.FFIReader(schema=schema, resource_id="src",
                       num_partitions=len(batch_parts))
    partial = N.Agg(scan, E.AggExecMode.HASH_AGG, [("k", _col("k"))],
                    [N.AggColumn(E.AggExpr(E.AggFunction.SUM, [_col("v")],
                                           T.I64),
                                 E.AggMode.PARTIAL, "s")])
    ex = N.ShuffleExchange(partial, N.HashPartitioning([_col("k")], reducers))
    final = N.Agg(ex, E.AggExecMode.HASH_AGG, [("k", _col("k"))],
                  [N.AggColumn(E.AggExpr(E.AggFunction.SUM, [_col("v")],
                                         T.I64),
                               E.AggMode.FINAL, "s")])
    return N.Sort(N.ShuffleExchange(final, N.SinglePartitioning(1)),
                  [E.SortOrder(_col("k"))])


def _make_parts(seed=7, n=20_000, nparts=4):
    rng = np.random.default_rng(seed)
    b = ColumnarBatch.from_pydict({
        "k": rng.integers(0, 300, n).tolist(),
        "v": rng.integers(0, 1000, n).tolist()})
    per = n // nparts
    return [[b.slice(i * per, per)] for i in range(nparts)]


def _run(parts, **conf_kw):
    with config_override(**conf_kw):
        with Session() as sess:
            sess.resources["src"] = lambda p: [x.to_arrow() for x in parts[p]]
            out = sess.execute_to_table(_two_stage_plan(parts))
            metrics = {m: _summed(sess, m) for m in _TRACKED}
    return out, metrics


# -- tier negotiation ---------------------------------------------------------


@pytest.mark.quick
def test_tier_negotiation_device(eight_devices, monkeypatch):
    with Session() as sess:  # multichip off, CPU backend: process tier
        assert sess.mesh is None
        assert sess._shuffle_tier() == "process"
        # the same session on an accelerator: its stages run on the chip,
        # so the exchange stays there (no mesh, no new option)
        from blaze_tpu.utils import device as _device

        monkeypatch.setattr(_device, "effective_platform", lambda: "tpu")
        assert sess._shuffle_tier() == "device"
        sess.pool = object()  # references cannot cross processes
        assert sess._shuffle_tier() == "shm"
        sess.pool = None
    for pinned in ("process", "shm", "ipc"):  # a pinned tier stays pinned
        with Session(conf=Config(zero_copy_tier=pinned)) as sess:
            assert sess._shuffle_tier() == pinned
    with Session(conf=Config(device_shuffle_tier=False)) as sess:
        assert sess._shuffle_tier() == "process"
    with Session(conf=Config(device_placement="host")) as sess:
        assert sess._shuffle_tier() == "process"  # stages pinned to the CPU
    monkeypatch.undo()
    with Session(conf=Config(zero_copy_tier="device")) as sess:  # pinned
        assert sess._shuffle_tier() == "device"
    with Session(conf=Config(multichip_enabled=True)) as sess:
        assert sess.mesh is not None  # session builds the mesh itself
        assert sess._shuffle_tier() == "device"
        # a worker pool forces shm: device-array references cannot cross
        # process boundaries any more than host batch references can
        sess.pool = object()
        assert sess._shuffle_tier() == "shm"
        sess.pool = None
    with Session(conf=Config(multichip_enabled=True,
                             device_shuffle_tier=False)) as sess:
        assert sess._shuffle_tier() == "process"
    with Session(conf=Config(multichip_enabled=True,
                             multichip_devices=2)) as sess:
        assert sess.mesh.devices.size == 2


# -- bit-identity across mesh sizes -------------------------------------------


@pytest.mark.quick
def test_multichip_bit_identical_across_meshes(eight_devices):
    """The multichip contract: the same plan over 1/2/8-device meshes
    returns byte-for-byte the single-process result, with the mesh
    collective actually engaged and zero shuffle bytes serialized."""
    parts = _make_parts(seed=21)
    ref, _ = _run(parts)
    for k in (1, 2, 8):
        out, m = _run(parts, multichip_enabled=True, multichip_devices=k)
        assert out.equals(ref), f"{k}-device mesh diverged"
        assert m["shuffle_bytes_serialized"] == 0
        assert m["sharded_stages"] > 0, \
            f"{k}-device mesh never lowered an exchange onto the collective"
        assert m["collective_bytes"] > 0


def test_multichip_composes_with_fused_sharding(eight_devices):
    """More map partitions than devices: the fused stage's batch-stacking
    runner and the mesh exchange compose, still bit-identical."""
    parts = _make_parts(seed=24, n=64_000, nparts=8)
    ref, _ = _run(parts)
    out, m = _run(parts, multichip_enabled=True, multichip_devices=8)
    assert out.equals(ref)
    assert m["sharded_stages"] > 0


# -- device-resident shuffle tier ---------------------------------------------


@pytest.mark.quick
def test_device_tier_matches_shm_tier(eight_devices):
    """Device-resident inter-stage hand-off returns exactly what the shm
    tier returns, with zero serialized bytes and the device-resident
    byte tripwire counting the handed-off columns."""
    parts = _make_parts(seed=22)
    dev_out, dev_m = _run(parts, zero_copy_tier="device")
    shm_out, _ = _run(parts, zero_copy_tier="shm")
    assert dev_out.equals(shm_out)
    assert dev_m["shuffle_bytes_serialized"] == 0
    assert dev_m["device_shuffle_bytes"] > 0, \
        "device tier must hand device-resident batches to the reducer"


def test_device_tier_marker_deletion_recovers(eight_devices):
    """PR 9 lineage composes with the device tier: device-resident
    segments publish footer-only markers, and chaos-deleting one
    recomputes the map through ordinary recovery — results unchanged."""
    from blaze_tpu.runtime.recovery import FOOTER_LEN
    from blaze_tpu.runtime.session import _QueryRun

    parts = _make_parts(seed=23)
    with config_override(zero_copy_tier="device"):
        with Session() as sess:
            sess.resources["src"] = lambda p: [x.to_arrow() for x in parts[p]]
            oracle = sess.execute_to_table(_two_stage_plan(parts))

            before = set(glob.glob(os.path.join(
                sess.shuffle_root, "shuffle_*", "map_*.data")))
            qrun = _QueryRun(0)
            sess._tls.qrun = qrun
            lowered = sess._lower(_two_stage_plan(parts))
            sess._tls.qrun = None
            files = [f for f in sorted(glob.glob(os.path.join(
                sess.shuffle_root, "shuffle_*", "map_*.data")))
                if f not in before]
            assert files, "device tier must still publish marker files"
            assert any(os.path.getsize(f) == FOOTER_LEN for f in files), \
                "device-committed maps publish footer-only markers"
            os.remove(files[0])
            assert sess.execute_to_table(lowered).equals(oracle)


def test_mesh_session_recovers_host_staged_stage(eight_devices):
    """A multichip session whose exchange is FORCED onto the host path
    (placement override) still stages through the registry and still
    recovers a deleted marker — the mesh gate and lineage compose."""
    from blaze_tpu.runtime.session import _QueryRun

    parts = _make_parts(seed=25)
    with config_override(multichip_enabled=True, device_placement="host"):
        with Session() as sess:
            sess.resources["src"] = lambda p: [x.to_arrow() for x in parts[p]]
            oracle = sess.execute_to_table(_two_stage_plan(parts))
            assert _summed(sess, "sharded_stages") == 0, \
                "host force must keep exchanges off the collective"

            qrun = _QueryRun(0)
            sess._tls.qrun = qrun
            lowered = sess._lower(_two_stage_plan(parts))
            sess._tls.qrun = None
            files = sorted(glob.glob(os.path.join(
                sess.shuffle_root, "shuffle_*", "map_*.data")))
            assert files
            os.remove(files[0])
            assert sess.execute_to_table(lowered).equals(oracle)


# -- failpoint degrade --------------------------------------------------------


def test_device_put_failpoint_degrades_to_host(eight_devices):
    """PR 12's failpoint plane reaches the new tier: ``device.put=enospc``
    makes on-chip bucketize fail, the writer degrades device -> host
    staging per the tier ladder, and the results are unchanged."""
    parts = _make_parts(seed=26)
    out, m = _run(parts, zero_copy_tier="device",
                  failpoints="device.put=enospc")
    ref, _ = _run(parts, zero_copy_shuffle=False)
    assert out.equals(ref)
    assert m["shuffle_tier_degraded"] > 0, \
        "the failpoint must actually trip the device tier"


# -- the five bench shapes across mesh sizes ----------------------------------


@pytest.fixture(scope="module")
def bench_paths(tmp_path_factory):
    import bench

    bench.ROWS = 60_000
    bench.PARTS = 2
    td = str(tmp_path_factory.mktemp("mcbench"))
    return bench.make_data(td)


@pytest.mark.quick
@pytest.mark.parametrize("shape", ["q01", "q06", "q17", "q47", "q67"])
def test_bench_shapes_identical_across_meshes(bench_paths, shape,
                                              eight_devices):
    """Each bench shape under device-primary execution must return
    byte-for-byte the same table at 1, 2 and 8 mesh devices."""
    import bench

    plan_fn = {s[0]: s[1] for s in bench.SHAPES}[shape]
    tables = []
    for k in (1, 2, 8):
        with config_override(multichip_enabled=True, multichip_devices=k):
            with Session() as sess:
                tables.append(sess.execute_to_table(plan_fn(bench_paths)))
    assert tables[0].equals(tables[1]), f"{shape}: 1 vs 2 devices diverged"
    assert tables[0].equals(tables[2]), f"{shape}: 1 vs 8 devices diverged"
