"""Zero-copy data plane (ISSUE 10): tier negotiation, serde elision,
shared-memory segments, and mapped device hand-off.

Covers the acceptance surface end to end: bit-identical results across the
three tiers (including the real 2-worker pool over the five bench shapes),
torn/truncated shm segments recovering through lineage, readers outliving
unlinked segments (POSIX mapping semantics), the tier fallback when
/dev/shm is unusable, mid-write degradation past the mem budget, and the
quick-tier guard pinning ``shuffle_bytes_serialized == 0`` on a
single-process plan."""

import glob
import os

import numpy as np
import pyarrow as pa
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


def _two_stage_plan(batch_parts, reducers=4):
    """partial agg -> hash exchange -> final agg -> single-collect topk:
    exercises both the multi-reducer shuffle and the collect path."""
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


def _make_parts(seed=7, n=20_000, nparts=2):
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
            metrics = {m: _summed(sess, m) for m in (
                "shuffle_bytes_serialized", "serde_elided_batches",
                "shm_bytes_mapped")}
    return out, metrics


# -- tier negotiation ---------------------------------------------------------


def test_tier_negotiation(monkeypatch):
    with Session() as sess:  # pool-less, auto, on the CPU backend
        assert sess._shuffle_tier() == "process"
        from blaze_tpu.utils import device as _device

        with monkeypatch.context() as on_chip:  # the same, on an accelerator
            on_chip.setattr(_device, "effective_platform", lambda: "tpu")
            assert sess._shuffle_tier() == "device"
        # a worker pool forces shm: references cannot cross processes
        sess.pool = object()
        assert sess._shuffle_tier() == "shm"
        sess.pool = None
    with Session(conf=Config(zero_copy_tier="shm")) as sess:
        assert sess._shuffle_tier() == "shm"
    with Session(conf=Config(zero_copy_tier="ipc")) as sess:
        assert sess._shuffle_tier() == "ipc"
    with Session(conf=Config(zero_copy_shuffle=False)) as sess:
        assert sess._shuffle_tier() == "ipc"
        assert sess.shuffle_root == sess.work_dir  # no shm root either


# -- bit-identity + tripwires -------------------------------------------------


@pytest.mark.quick
def test_single_process_plan_elides_all_serde():
    """The quick-tier guard: a single-process plan (auto -> process tier)
    serializes ZERO shuffle bytes; every exchanged batch is counted as a
    serde-elided reference instead."""
    parts = _make_parts()
    out, m = _run(parts)
    assert m["shuffle_bytes_serialized"] == 0
    assert m["serde_elided_batches"] > 0
    # and the result matches the classic serde path bit for bit
    ipc_out, ipc_m = _run(parts, zero_copy_shuffle=False)
    assert ipc_m["serde_elided_batches"] == 0
    assert ipc_m["shuffle_bytes_serialized"] > 0
    assert out.equals(ipc_out)


def test_shm_tier_maps_and_matches():
    parts = _make_parts(seed=8)
    shm_out, shm_m = _run(parts, zero_copy_tier="shm")
    ipc_out, _ = _run(parts, zero_copy_shuffle=False)
    assert shm_out.equals(ipc_out)
    assert shm_m["shm_bytes_mapped"] > 0


def test_mem_budget_degrades_to_files():
    """A process-tier map that outgrows zero_copy_mem_segment_max_bytes
    degrades mid-write to real (raw) shuffle files; results are unchanged
    and the reducer serves the degraded maps transparently."""
    parts = _make_parts(seed=9)
    small, _ = _run(parts, zero_copy_mem_segment_max_bytes=1024)
    ref, _ = _run(parts, zero_copy_shuffle=False)
    assert small.equals(ref)


def test_shm_root_lifecycle():
    """The session's shm root exists while it serves and is removed at
    close; per-query release drops the query's shuffle dirs under it."""
    parts = _make_parts(seed=10)
    with config_override(zero_copy_tier="shm"):
        sess = Session()
        root = sess.shuffle_root
        if root == sess.work_dir:
            pytest.skip("/dev/shm not usable in this environment")
        assert os.path.isdir(root)
        sess.resources["src"] = lambda p: [x.to_arrow() for x in parts[p]]
        for _ in sess.execute(_two_stage_plan(parts),
                              release_on_finish=True):
            pass
        # released with the query: no shuffle dirs linger under the root
        assert glob.glob(os.path.join(root, "shuffle_*")) == []
        sess.close()
        assert not os.path.exists(root)


def test_shm_root_reclaimed_without_close():
    """tmpfs pages are RAM: a session dropped without close() (test code,
    crashed callers) must still give its /dev/shm root back via the GC
    finalizer."""
    import gc

    with config_override(zero_copy_tier="shm"):
        sess = Session()
        root = sess.shuffle_root
        if root == sess.work_dir:
            pytest.skip("/dev/shm not usable in this environment")
        assert os.path.isdir(root)
        del sess
        gc.collect()
        assert not os.path.exists(root)


# -- lineage recovery over shm segments ---------------------------------------


def _lower_and_files(sess, plan):
    from blaze_tpu.runtime.session import _QueryRun

    before = set(glob.glob(
        os.path.join(sess.shuffle_root, "shuffle_*", "map_*.data")))
    qrun = _QueryRun(0)
    sess._tls.qrun = qrun
    lowered = sess._lower(plan)
    sess._tls.qrun = None
    after = sorted(glob.glob(
        os.path.join(sess.shuffle_root, "shuffle_*", "map_*.data")))
    return lowered, [f for f in after if f not in before]


def test_torn_shm_segment_recovers_via_lineage():
    """Truncating a committed shm segment between the map stage and the
    reduce is detected by the footer check and recomputed from lineage —
    the PR 9 recovery semantics survive the raw mappable format."""
    parts = _make_parts(seed=11)
    with config_override(zero_copy_tier="shm"):
        with Session() as sess:
            sess.resources["src"] = lambda p: [x.to_arrow() for x in parts[p]]
            oracle = sess.execute_to_table(_two_stage_plan(parts))

            lowered, files = _lower_and_files(sess, _two_stage_plan(parts))
            assert files, "shm tier must commit real segment files"
            victim = max(files, key=os.path.getsize)
            with open(victim, "r+b") as fh:
                fh.truncate(max(0, os.path.getsize(victim) - 9))
            got = sess.execute_to_table(lowered)
            assert got.equals(oracle)

            # deleted outright: same recovery
            lowered, files = _lower_and_files(sess, _two_stage_plan(parts))
            os.remove(max(files, key=os.path.getsize))
            assert sess.execute_to_table(lowered).equals(oracle)


def test_process_tier_marker_deletion_recovers():
    """The process tier keeps lineage file-shaped with footer-only marker
    files: chaos-deleting a marker recomputes and re-commits the registry
    segment through the ordinary recovery path."""
    parts = _make_parts(seed=12)
    with Session() as sess:  # default: process tier
        sess.resources["src"] = lambda p: [x.to_arrow() for x in parts[p]]
        oracle = sess.execute_to_table(_two_stage_plan(parts))

        lowered, files = _lower_and_files(sess, _two_stage_plan(parts))
        assert files, "process tier must still publish marker files"
        from blaze_tpu.runtime.recovery import FOOTER_LEN

        assert any(os.path.getsize(f) == FOOTER_LEN for f in files), \
            "mem-committed maps publish footer-only markers"
        os.remove(files[0])
        assert sess.execute_to_table(lowered).equals(oracle)


def test_released_registry_entry_is_typed_missing():
    """A registry entry dropped while its marker survives (the
    released-too-early shape) fails the index-size check and surfaces as
    ShuffleOutputMissing -> recovery recomputes it."""
    parts = _make_parts(seed=13)
    with Session() as sess:
        sess.resources["src"] = lambda p: [x.to_arrow() for x in parts[p]]
        lowered, _files = _lower_and_files(sess, _two_stage_plan(parts))
        assert len(sess.mem_segments) > 0
        sess.mem_segments.clear()  # simulate premature release
        out = sess.execute_to_table(lowered)  # recovers, no error
        with config_override(zero_copy_shuffle=False):
            with Session() as ref_sess:
                ref_sess.resources["src"] = \
                    lambda p: [x.to_arrow() for x in parts[p]]
                ref = ref_sess.execute_to_table(_two_stage_plan(parts))
        assert out.equals(ref)


# -- mapped segments & device hand-off ----------------------------------------


def test_reader_outlives_unlinked_segment(tmp_path):
    """POSIX mapping semantics end to end: decode batches from a mapped
    raw segment, unlink the file, and the batches stay intact — the
    mapping (and the pages) live until the last view dies. Mapped plane
    bytes are booked as DEVICE_STATS.mapped, not as host copies."""
    import io as _io

    from blaze_tpu.io.batch_serde import (BatchWriter, decode_frame,
                                          read_frames)
    from blaze_tpu.io.shm_segments import MappedSegmentStream, open_mapped
    from blaze_tpu.utils.device import DEVICE_STATS

    rng = np.random.default_rng(14)
    b = ColumnarBatch.from_pydict({
        "a": rng.integers(0, 10**9, 4096).tolist(),
        "s": [f"x{i}" for i in range(4096)]})
    buf = _io.BytesIO()
    bw = BatchWriter(buf, raw=True)
    bw.write_batch(b)
    path = str(tmp_path / "seg.data")
    with open(path, "wb") as f:
        f.write(buf.getvalue())

    before = DEVICE_STATS.snapshot()
    mf = open_mapped(path)
    stream = MappedSegmentStream(mf.view(0, os.path.getsize(path)))
    frames = list(read_frames(stream))
    assert frames
    batches = [decode_frame(*fr, mapped=True) for fr in frames]
    after = DEVICE_STATS.snapshot()
    assert after["mapped_bytes"] > before["mapped_bytes"]

    os.remove(path)  # unlink while mapped: reader keeps serving
    del mf, stream
    got = pa.Table.from_batches([x.to_arrow() for x in batches])
    assert got.equals(pa.Table.from_batches([b.to_arrow()]))


def test_tier_fallback_without_dev_shm(tmp_path, monkeypatch):
    """When /dev/shm is unusable (here: an impossibly high free-space
    floor) segments fall back to the session work dir — mmap still works,
    results are unchanged, and THIS process makes no shm root (other xdist
    workers make theirs under /dev/shm meanwhile, so no glob of it)."""
    import tempfile

    from blaze_tpu.io.shm_segments import SHM_ROOT_PREFIX, is_shm_path

    made = []
    real_mkdtemp = tempfile.mkdtemp

    def mkdtemp(*a, **kw):
        made.append(real_mkdtemp(*a, **kw))
        return made[-1]

    monkeypatch.setattr(tempfile, "mkdtemp", mkdtemp)
    parts = _make_parts(seed=15)
    with config_override(zero_copy_tier="shm",
                         shm_min_free_bytes=1 << 62):
        with Session() as sess:
            assert sess.shuffle_root == sess.work_dir
            sess.resources["src"] = lambda p: [x.to_arrow() for x in parts[p]]
            out = sess.execute_to_table(_two_stage_plan(parts))
    ref, _ = _run(parts, zero_copy_shuffle=False)
    assert out.equals(ref)
    assert made and not [d for d in made if is_shm_path(d)]

    # explicit shm_dir wins over the probe
    with config_override(zero_copy_tier="shm", shm_dir=str(tmp_path),
                         shm_min_free_bytes=1 << 62):
        with Session() as sess:
            assert sess.shuffle_root.startswith(
                os.path.join(str(tmp_path), SHM_ROOT_PREFIX))
            assert sess.shuffle_root in made


# -- the five bench shapes on a real worker pool ------------------------------


@pytest.fixture(scope="module")
def bench_paths(tmp_path_factory):
    import bench

    bench.ROWS = 60_000
    bench.PARTS = 2
    td = str(tmp_path_factory.mktemp("zcbench"))
    return bench.make_data(td)


@pytest.mark.parametrize("shape", ["q01", "q06", "q17", "q47", "q67"])
def test_bench_shapes_bit_identical_on_pool(bench_paths, shape):
    """Each bench shape runs on a real 2-worker pool (shm tier: workers
    write raw mappable segments, the driver's reducers mmap them) and must
    be bit-identical to the classic-serde run of the same plan."""
    import bench

    plan_fn = {s[0]: s[1] for s in bench.SHAPES}[shape]
    with config_override(zero_copy_shuffle=False):
        with Session(num_worker_processes=2) as sess:
            ref = sess.execute_to_table(plan_fn(bench_paths))
    with Session(num_worker_processes=2) as sess:
        assert sess._shuffle_tier() == "shm"
        got = sess.execute_to_table(plan_fn(bench_paths))
        mapped = _summed(sess, "shm_bytes_mapped")
    assert got.equals(ref)
    assert mapped > 0, "pool shuffle reads must come from mapped segments"
