"""The host spans that name what a task thread is doing batch by batch:
`op` self-time segments, `scan:decode` / `scan:decode_wait`,
`transfer:stage`, `shuffle:fetch_wait` and `sync:<what>` — what they add up
to, whose they are, that they cost nothing with tracing off, and that they
sit on the profiler's clock where the benchmark's anchor puts them."""

import collections
import time

import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from blaze_tpu.config import Config
from blaze_tpu.ir import exprs as E
from blaze_tpu.ir import nodes as N
from blaze_tpu.ir import types as T
from blaze_tpu.obs.tracer import TRACER
from blaze_tpu.runtime.session import Session
from blaze_tpu.utils.device import DEVICE_STATS, wait_int
from tests.benchmark import helpers

F = E.AggFunction
M = E.AggMode
HASH = E.AggExecMode.HASH_AGG
# the span names this file is about: none may reach the flight-recorder ring
DETAIL = {("op", None), ("scan", "decode"), ("scan", "decode_wait"),
          ("transfer", "stage"), ("shuffle", "fetch_wait"), ("sync", None)}
ROWS, FILES = 40_000, 4


@pytest.fixture(autouse=True)
def _reset_tracer():
    TRACER.disable()
    TRACER.reset()
    yield
    TRACER.disable()
    TRACER.reset()


@pytest.fixture(scope="module")
def parquet_files(tmp_path_factory):
    """`ROWS` rows in `FILES` files: a key, a value, a few nulls."""
    d = tmp_path_factory.mktemp("host_spans")
    paths = []
    per = ROWS // FILES
    for i in range(FILES):
        ks = [(i * per + j) % 11 for j in range(per)]
        vs = [None if j % 97 == 0 else j for j in range(per)]
        path = str(d / f"part-{i}.parquet")
        pq.write_table(pa.table({"k": pa.array(ks, pa.int64()),
                                 "v": pa.array(vs, pa.int64())}), path)
        paths.append(path)
    return paths


def _scan_agg_plan(paths, reducers=2):
    """scan -> filter -> PARTIAL agg -> exchange -> FINAL agg: the map side
    syncs per batch, the reduce side reads the exchange."""
    from blaze_tpu.ops.parquet import scan_node_for_files

    scan = scan_node_for_files(paths, num_partitions=len(paths))
    filt = N.Filter(scan, [E.BinaryExpr(E.BinaryOp.GTEQ, E.Column("k"),
                                        E.Literal(0, T.I64))])
    groupings = [("k", E.Column("k"))]
    total = E.AggExpr(F.SUM, [E.Column("v")], T.I64)
    partial = N.Agg(filt, HASH, groupings, [N.AggColumn(total, M.PARTIAL, "t")])
    ex = N.ShuffleExchange(partial, N.HashPartitioning([E.Column("k")], reducers))
    return N.Agg(ex, HASH, groupings, [N.AggColumn(total, M.FINAL, "t")])


def _spans(cat, name=None):
    return [e for e in TRACER.snapshot() if e.get("ph") == "X"
            and e["cat"] == cat and (name is None or e["name"] == name)]


def _is_detail(event):
    return (event.get("cat"), None) in DETAIL or \
        (event.get("cat"), event.get("name")) in DETAIL


def test_op_segments_add_up_to_self_time_and_never_overlap(parquet_files):
    with Session(conf=Config(trace_enable=True, batch_size=4096)) as sess:
        TRACER.reset()
        out = sess.execute_to_pydict(_scan_agg_plan(parquet_files))
        # summed by operator class as the benchmark's `*_self_s` sum it
        self_ns = helpers.load_run().self_time_by_class(sess.metrics.to_dict())
    assert len(out["k"]) == 11
    segments = _spans("op")
    by_class = collections.Counter()
    for ev in segments:
        by_class[ev["name"]] += ev["dur"] * 1e3
    executed = set(self_ns)
    assert {"ParquetScanExec", "AggExec", "ShuffleWriterExec",
            "IpcReaderExec"} <= executed
    assert set(by_class) == executed
    for name in executed:
        assert by_class[name] == pytest.approx(self_ns[name], rel=0.01), name
    # at any instant a thread is in at most one op segment
    by_thread = collections.defaultdict(list)
    for ev in segments:
        by_thread[ev["tid"]].append((ev["ts"], ev["ts"] + ev["dur"]))
    for spans in by_thread.values():
        spans.sort()
        for (_s0, e0), (s1, _e1) in zip(spans, spans[1:]):
            assert s1 >= e0 - 1e-3  # microseconds; float rounding only


def test_scan_query_yields_decode_wait_stage_and_sync_with_their_task(
        parquet_files):
    with Session(conf=Config(trace_enable=True, batch_size=4096)) as sess:
        plan = _scan_agg_plan(parquet_files)
        TRACER.reset()
        before = DEVICE_STATS.snapshot()
        sess.execute_to_pydict(plan)
        after = DEVICE_STATS.snapshot()
    decode = _spans("scan", "decode")
    assert sum(e["args"]["rows"] for e in decode) == ROWS
    assert all(e["args"]["bytes"] > 0 for e in decode)
    waits = _spans("scan", "decode_wait")
    # one wait per batch and one for the end of the stream, in each task
    assert len(waits) == len(decode) + FILES
    stage = _spans("transfer", "stage")
    assert sum(e["args"]["bytes"] for e in stage) == \
        after["to_device_bytes"] - before["to_device_bytes"] > 0
    assert sum(e["args"]["rows"] for e in stage) >= ROWS
    syncs = _spans("sync")
    # the slot table's range probe is a named wait like the others
    assert {e["name"] for e in syncs} >= {"agg_probe", "agg_partial",
                                          "agg_merge"}
    assert after["sync_calls"] - before["sync_calls"] >= len(syncs)
    assert _spans("shuffle", "fetch_wait")
    # whose span it is: stage, partition and query, on the task threads and
    # on the prefetch and decode threads they started
    for ev in decode + waits + stage + syncs + _spans("shuffle", "fetch_wait") \
            + _spans("op"):
        args = ev["args"]
        assert args["stage"] is not None and args["part"] is not None, ev
        assert args["q"] == 0, ev
    # a task's decode spans name its partition from another thread than the
    # one that waits for them (the two are alive together, so their thread
    # ids differ; ids of threads that have ended are used again)
    assert {e["args"]["part"] for e in decode} == set(range(FILES))
    for part in range(FILES):
        decoders = {e["tid"] for e in decode if e["args"]["part"] == part}
        waiters = {e["tid"] for e in waits if e["args"]["part"] == part}
        assert len(decoders) == len(waiters) == 1 and decoders != waiters


def test_tracing_off_counts_syncs_and_keeps_detail_out_of_the_ring(
        parquet_files):
    with Session(conf=Config(batch_size=4096)) as sess:
        assert not TRACER.enabled and TRACER.active  # the ring is on
        before = DEVICE_STATS.snapshot()
        sess.execute_to_pydict(_scan_agg_plan(parquet_files))
        after = DEVICE_STATS.snapshot()
    assert after["sync_calls"] - before["sync_calls"] >= 2 * FILES
    assert after["sync_calls"] - before["sync_calls"] >= \
        after["to_host_calls"] - before["to_host_calls"] > 0
    ring = TRACER.ring_snapshot()
    assert any(e.get("cat") == "operator" for e in ring)  # it did record
    assert not [e for e in ring if _is_detail(e)]
    assert TRACER.snapshot() == []
    # the helper alone: counted, and no span
    n = DEVICE_STATS.snapshot()["sync_calls"]
    assert wait_int(7, "nothing") == 7
    assert DEVICE_STATS.snapshot()["sync_calls"] == n + 1
    assert not [e for e in TRACER.ring_snapshot() if e.get("cat") == "sync"]


def _skews_ns(trace_dir):
    """One profiler session: 80 context-form spans, and for each the distance
    between its `blaze/...` annotation and the span laid through the anchor."""
    import jax.profiler

    helpers.load_run()  # puts the benchmark's directory on sys.path
    from benchlib import xplane

    TRACER.enable()
    TRACER.reset()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        anchor_ns = time.perf_counter_ns()
        with jax.profiler.TraceAnnotation(xplane.ANCHOR):
            pass
        for i in range(40):
            assert wait_int(i, f"probe{i}") == i
            with TRACER.detail(f"probe{i}", "scan"):
                time.sleep(0.0005)
    finally:
        jax.profiler.stop_trace()
        TRACER.disable()
    path = xplane.find_xplane(trace_dir)
    offset = xplane.clock_offset_ns(xplane.load(path, require_tpu=False),
                                    anchor_ns)
    notes = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("blaze/"):
                        notes[e.name] = e.start_ns
    spans = TRACER.snapshot()
    assert len(spans) == 80 and len(notes) == 80
    return [abs(TRACER.perf_epoch_ns + ev["ts"] * 1e3 + offset
                - notes[f"blaze/{ev['cat']}:{ev['name']}"]) for ev in spans]


def test_annotations_and_anchored_spans_share_a_clock(tmp_path):
    """Under `jax.profiler` a context-form span opens the annotation
    `blaze/<cat>:<name>`; the benchmark lays the tracer's spans over the
    trace by one `perf_counter` anchor. The two must agree on when a span
    began: within 100 us here, where both clocks are the host's. A thread
    descheduled between two stamps is the machine's doing, not the clocks':
    such a session is measured again, twice at most."""
    for attempt in range(3):
        skews = _skews_ns(str(tmp_path / f"trace{attempt}"))
        print(f"largest skew {max(skews) / 1e3:.1f} us")
        if max(skews) < 100_000:
            break
    assert max(skews) < 100_000, max(skews)
