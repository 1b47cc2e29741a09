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
            "shuffle_tier_degraded")


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


def test_multichip_eight_tasks_on_eight_chips(eight_devices):
    """Eight map tasks, a chip each, through the mesh exchange: still
    bit-identical with the one-chip session."""
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
    bench.PARTS = 4
    td = str(tmp_path_factory.mktemp("mcbench"))
    return bench.make_data(td)


@pytest.mark.quick
@pytest.mark.parametrize("shape", ["q01", "q06", "q17", "q47", "q67"])
def test_bench_shapes_identical_across_meshes(bench_paths, shape,
                                              eight_devices):
    """Each bench shape under device-primary execution must return
    byte-for-byte the same table at 1, 2, 4 and 8 mesh devices, with every
    task on the chip of its partition, and the file-shuffle path's rows (in
    its order where the final Sort makes the order total); q01 and q67 (the
    four-chip cells' classes) equal the Acero reference too."""
    import bench

    (_name, plan_fn, _pandas, acero, _check, tables_of), = [
        s for s in bench.SHAPES if s[0] == shape]
    with config_override(zero_copy_shuffle=False):
        with Session() as sess:
            want = sess.execute_to_table(plan_fn(bench_paths))
    if shape in ("q01", "q67"):
        ref = acero(bench.load_tables(bench_paths, tables_of))
        assert bench.canon_rows(shape, want, "engine") == \
            bench.canon_rows(shape, ref, "acero")
    tables = []
    for k in (1, 2, 4, 8):
        with config_override(multichip_enabled=True, multichip_devices=k):
            with Session() as sess:
                tables.append(sess.execute_to_table(plan_fn(bench_paths)))
                off = _summed(sess, "mesh_tasks_off_primary")
        assert tables[-1].equals(tables[0]), f"{shape}: {k} devices diverged"
        assert (off > 0) == (k > 1), (k, off)
    assert bench.canon_rows(shape, tables[0], "engine") == \
        bench.canon_rows(shape, want, "engine")


# -- a task a chip --------------------------------------------------------------


def _spy_exchanges(monkeypatch):
    """Every MeshBatchExchange.run of the test: what it was handed, what it
    returned, and whether it read or waited on anything meanwhile."""
    from blaze_tpu.parallel.mesh import MeshBatchExchange
    from blaze_tpu.utils.device import DEVICE_STATS

    seen = []
    real = MeshBatchExchange.run

    def run(self, schema, shards, num_reducers, **kw):
        before = DEVICE_STATS.snapshot()
        out = real(self, schema, shards, num_reducers, **kw)
        after = DEVICE_STATS.snapshot()
        seen.append({"exchange": self, "shards": shards, "results": out,
                     "num_reducers": num_reducers,
                     "pulls": after["to_host_calls"] - before["to_host_calls"],
                     "waits": after["sync_calls"] - before["sync_calls"],
                     "resident": self.last_device_resident,
                     "payload": self.last_payload_bytes})
        return out

    monkeypatch.setattr(MeshBatchExchange, "run", run)
    return seen


def _planes_on(batch):
    return {d for c in batch.columns for a in (c.data, c.validity)
            for d in a.devices()}


def test_each_task_runs_on_the_chip_of_its_partition(eight_devices,
                                                     monkeypatch):
    """Four map tasks on four chips: each routes its output on its own
    chip, the exchange reads nothing but the offsets, every reducer's batch
    lands on the chip its reduce task runs on, and the answer is the
    one-chip session's."""
    from blaze_tpu.parallel.mesh import task_chip

    parts = _make_parts(seed=27)
    ref, _ = _run(parts)
    seen = _spy_exchanges(monkeypatch)
    out, m = _run(parts, multichip_enabled=True, multichip_devices=4)
    assert out.equals(ref)
    devs = eight_devices[:4]
    assert len(seen) == 2  # the hash exchange, then the final collect
    for ex in seen:
        for s, pieces in enumerate(ex["shards"]):
            for batch, offsets in pieces:
                assert _planes_on(batch) == {devs[s]}
                assert offsets[-1] == batch.num_rows
        assert ex["pulls"] == 0 and ex["waits"] == 0
        for r, batch in enumerate(ex["results"]):
            if batch is not None:
                assert _planes_on(batch) == \
                    {devs[task_chip(r, ex["num_reducers"], 4)]}
    assert [len(p) for p in seen[0]["shards"]] == [1, 1, 1, 1]


def test_mesh_tasks_off_primary_counts_a_four_task_stage(eight_devices):
    """A four-task stage on a four-chip mesh has three tasks off chip 0; its
    one-partition result stage runs on chip 0."""
    parts = _make_parts(seed=28)
    schema = parts[0][0].schema
    scan = N.FFIReader(schema=schema, resource_id="src",
                       num_partitions=len(parts))
    plan = N.ShuffleExchange(scan, N.HashPartitioning([_col("k")], 1))
    with config_override(multichip_enabled=True, multichip_devices=4):
        with Session() as sess:
            sess.resources["src"] = lambda p: [x.to_arrow() for x in parts[p]]
            out = sess.execute_to_table(plan)
            assert _summed(sess, "mesh_tasks_off_primary") == 3
            assert _summed(sess, "sharded_stages") == 1
    assert out.num_rows == sum(b.num_rows for p in parts for b in p)


def test_twenty_queries_keep_every_exchange_on_the_chips(eight_devices,
                                                         monkeypatch):
    """A query's device-resident exchanges are charged to it until it is
    released: twenty queries of the q67 class in one session, under a budget
    that holds two queries' exchanges, never send an exchange to host RAM
    (when the charge outlived its query, the third query's went there)."""
    parts = _make_parts(seed=29)
    seen = _spy_exchanges(monkeypatch)
    conf = dict(multichip_enabled=True, multichip_devices=4)
    _run(parts, **conf)
    budget = 2 * sum(ex["payload"] for ex in seen)
    seen.clear()
    with config_override(mesh_device_resident_max_bytes=budget, **conf):
        with Session() as sess:
            sess.resources["src"] = lambda p: [x.to_arrow() for x in parts[p]]
            first = sess.execute_to_table(_two_stage_plan(parts))
            for _ in range(19):
                assert sess.execute_to_table(
                    _two_stage_plan(parts)).equals(first)
            assert not sess._mesh_pins
            assert _summed(sess, "mesh_host_resident_exchanges") == 0
    assert len(seen) == 40 and all(ex["resident"] for ex in seen)
