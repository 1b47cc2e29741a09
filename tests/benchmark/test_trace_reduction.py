"""The reduction from a device trace to numbers: interval union, idle gaps
and gap naming on synthetic events, the whole reduction on synthetic chips,
and the whole reduction on a trace recorded on the chip: one query of the
traced run of `q01_scan_topk` on a TPU v5 lite in PR 22, trimmed to what the
reduction reads (the TPU plane's `XLA Modules` and `XLA Ops` lines and the
benchmark's annotations; event stats dropped, names cut to 100 characters).
The numbers it must give are written here."""

import os

import pytest

from tests.benchmark import helpers

helpers.load_run()  # puts benchmark/ on sys.path
from benchlib import intervals as iv  # noqa: E402
from benchlib import stats, xplane  # noqa: E402


@pytest.mark.parametrize("given, want", [
    ([], []),
    ([(0, 1)], [(0, 1)]),
    ([(5, 6), (0, 1)], [(0, 1), (5, 6)]),                 # sorted
    ([(0, 4), (1, 2), (3, 6)], [(0, 6)]),                 # nested, overlapping
    ([(0, 1), (1, 2)], [(0, 2)]),                         # touching
    ([(0, 10), (2, 3), (4, 5), (20, 21)], [(0, 10), (20, 21)]),
])
def test_union(given, want):
    assert iv.union(given) == want


def test_covered_clips_to_the_window():
    busy = [(0, 4), (2, 6), (10, 12)]
    assert iv.covered(busy, 0, 20) == 8
    assert iv.covered(busy, 3, 11) == 4
    assert iv.covered(busy, 6, 10) == 0


@pytest.mark.parametrize("busy, lo, hi, want", [
    ([], 0, 10, [(0, 10)]),
    ([(0, 10)], 0, 10, []),
    ([(2, 3), (5, 7)], 0, 10, [(0, 2), (3, 5), (7, 10)]),
    ([(2, 3), (5, 7)], 2, 7, [(3, 5)]),
    ([(0, 4), (6, 20)], 3, 10, [(4, 6)]),
])
def test_gaps(busy, lo, hi, want):
    assert iv.gaps(busy, lo, hi) == want


def test_gap_naming():
    queries = [(0, 100), (110, 200)]
    spans = [(0, 100, "task:task"), (10, 60, "operator:AggExec"),
             (20, 30, "operator:ParquetScanExec"), (120, 130, "transfer:to_host")]
    # the innermost (shortest) span open at the gap's middle
    assert iv.name_gap((22, 28), queries, spans) == "operator:ParquetScanExec"
    assert iv.name_gap((40, 50), queries, spans) == "operator:AggExec"
    assert iv.name_gap((70, 90), queries, spans) == "task:task"
    # outside every query; inside a query that no span covers
    assert iv.name_gap((102, 108), queries, spans) == iv.BETWEEN_QUERIES
    assert iv.name_gap((150, 190), queries, spans) == iv.UNATTRIBUTED


def test_idle_by_name_sums_gaps_of_one_name():
    busy = [(10, 20), (30, 40)]
    # gaps: (0,10) and (20,30) fall in span a, (40,50) in span b
    named = iv.idle_by_name(busy, 0, 50, [(0, 50)], [(0, 32, "a"), (38, 50, "b")])
    assert named == {"a": 20, "b": 10}


def _trace():
    """Two chips. Chip 0 runs two programs in query one and one in query
    two; chip 1 runs one program and a collective."""
    ops0 = [(100, 50, "fusion.1"), (120, 10, "nested.inside"),  # union 100..150
            (200, 100, "sort.2"),                               # 200..300
            (1100, 200, "fusion.1")]                            # query two
    launches0 = [(100, 50, "jit_agg_partial(111)"), (200, 100, "jit_sort(222)"),
                 (1100, 200, "jit_agg_partial(111)")]
    ops1 = [(100, 100, "fusion.9"), (150, 100, "all-to-all.3")]  # union 100..250
    launches1 = [(100, 150, "jit_body(333)")]
    notes = [(5, 1, "bench_anchor"), (0, 1000, "bench_query"),
             (1000, 1000, "bench_query")]
    return xplane.Trace({0: launches0, 1: launches1}, {0: ops0, 1: ops1}, notes)


def test_reduce_on_synthetic_chips():
    queries = [(0, 1000), (1000, 2000)]
    spans = [(0, 1000, "task:task"), (300, 900, "operator:AggExec"),
             (1300, 2000, "operator:SortExec")]
    r = xplane.reduce(_trace(), queries, spans)
    assert r.window_s == pytest.approx(2000e-9)
    assert r.busy_s == {0: pytest.approx(350e-9), 1: pytest.approx(150e-9)}
    assert r.mean_busy_s == pytest.approx(250e-9)
    assert r.idle_share == pytest.approx(1 - 250 / 2000)
    assert r.busy_per_query_s[0] == {0: pytest.approx(150e-9), 1: pytest.approx(150e-9)}
    assert r.busy_per_query_s[1] == {0: pytest.approx(200e-9), 1: 0.0}
    assert r.launches_per_query == [{0: 2, 1: 1}, {0: 1, 1: 0}]
    assert r.collective_s == {0: 0.0, 1: pytest.approx(100e-9)}
    # programs by the compile log's names, seconds averaged over the chips
    assert dict(r.device_ops) == {
        "jit(agg_partial)": pytest.approx(125e-9),
        "jit(sort)": pytest.approx(50e-9), "jit(body)": pytest.approx(75e-9)}
    assert r.device_ops[0][0] == "jit(agg_partial)"
    # idle by what the host was doing, averaged over the chips; all of it
    gaps = dict(r.idle_gaps)
    assert sum(gaps.values()) == pytest.approx((2000 - 250) * 1e-9)
    # chip 0 idles (0,100) and (150,200) under the task span alone, (300,1100)
    # with AggExec innermost at its middle, (1300,2000) under SortExec; chip 1
    # idles (0,100) under the task span and (250,2000), whose middle no span
    # covers
    assert gaps == {"task:task": pytest.approx(250 / 2 * 1e-9),
                    "operator:AggExec": pytest.approx(800 / 2 * 1e-9),
                    "operator:SortExec": pytest.approx(700 / 2 * 1e-9),
                    iv.UNATTRIBUTED: pytest.approx(1750 / 2 * 1e-9)}
    assert r.idle_gaps[0][0] == iv.UNATTRIBUTED


def test_reduce_keeps_at_most_ten_of_each():
    launches = [(i * 10, 5, f"jit_f{i}(1)") for i in range(30)]
    ops = [(s, d, "op") for s, d, _n in launches]
    r = xplane.reduce(xplane.Trace({0: launches}, {0: ops}, []), [(0, 300)])
    assert len(r.device_ops) == 10 and len(r.idle_gaps) <= 10


def test_clock_offset_comes_from_the_anchor():
    assert xplane.clock_offset_ns(_trace(), anchor_perf_ns=1005) == -1000
    with pytest.raises(xplane.TraceError, match="bench_anchor"):
        xplane.clock_offset_ns(xplane.Trace({}, {}, []), 0)


@pytest.mark.parametrize("event, want", [
    ("jit_agg_partial(123456789)", "jit(agg_partial)"),
    ("jit_sort", "jit(sort)"),
    ("pmap_something(4)", "pmap_something"),
])
def test_module_name(event, want):
    assert xplane.module_name(event) == want


def test_reduce_without_a_query_is_an_error():
    with pytest.raises(xplane.TraceError):
        xplane.reduce(_trace(), [])


def test_a_cpu_trace_has_no_tpu_plane(tmp_path):
    """The adapter on a trace this process records: the annotations are
    found by name; without a TPU plane `load` fails unless told it is a
    rehearsal."""
    import time

    import jax
    import jax.numpy as jnp

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        anchor = time.perf_counter_ns()
        with jax.profiler.TraceAnnotation(xplane.ANCHOR):
            pass
        with jax.profiler.TraceAnnotation("bench_query", index=0):
            jnp.arange(8).sum().block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path = xplane.find_xplane(str(tmp_path))
    with pytest.raises(xplane.TraceError, match="no /device:TPU"):
        xplane.load(path)
    trace = xplane.load(path, require_tpu=False)
    assert [n for _s, _d, n in trace.annotations] == ["bench_anchor", "bench_query"]
    assert trace.chips == [0] and trace.ops[0] == []
    # the anchor ties the host clock to the trace's: the query annotation
    # began after the anchor on both
    offset = xplane.clock_offset_ns(trace, anchor)
    assert trace.annotations[1][0] - offset > anchor


@pytest.mark.parametrize("values, q, want", [
    ([1, 2, 3, 4], 0.5, 2.5), ([1, 2, 3], 0.5, 2), ([5], 0.25, 5),
    ([0, 10], 0.25, 2.5), ([1, 2, 3, 4, 5], 0.75, 4),
])
def test_quantile(values, q, want):
    assert stats.quantile(values, q) == want


TRACE = os.path.join(helpers.BENCH_DIR, "testdata",
                     "q01_scan_topk_v5e_one_query.xplane.pb")


@pytest.fixture(scope="module")
def chip_trace():
    return xplane.load(TRACE)


def test_what_the_adapter_finds_in_the_chip_trace(chip_trace):
    assert chip_trace.chips == [0]
    assert len(chip_trace.ops[0]) == 7626 and len(chip_trace.launches[0]) == 520
    assert [(n, s, d) for s, d, n in chip_trace.annotations] == [
        ("bench_anchor", 38656158.0, 1880.0),
        ("bench_query", 38936878.0, 1850055839.0)]
    # the host clock read 1000 ns when the anchor opened
    assert xplane.clock_offset_ns(chip_trace, 1000) == 38655158.0


def test_the_reduction_of_one_q01_query_on_the_chip(chip_trace):
    (start, dur, _name), = [a for a in chip_trace.annotations if a[2] == "bench_query"]
    query = (start, start + dur)
    # one host span over the query's first second, as the tracer would give
    spans = [(start, start + 1e9, "operator:ParquetScanExec")]
    r = xplane.reduce(chip_trace, [query], spans)
    assert r.window_s == pytest.approx(1.850055839, abs=1e-9)
    assert r.busy_s == {0: pytest.approx(1.681479706, abs=1e-9)}
    assert r.busy_per_query_s == [{0: pytest.approx(1.681479706, abs=1e-9)}]
    assert r.launches_per_query == [{0: 520}]
    assert r.idle_share == pytest.approx(0.0911194838, abs=1e-9)
    assert r.collective_s == {0: 0.0}
    # the aggregation's sort-based partial kernel is nine tenths of the
    # device's time in q01
    assert [name for name, _s in r.device_ops[:4]] == [
        "jit(agg_partial)", "jit(_compact)", "jit(agg_merge)", "jit(_concat_gather)"]
    assert dict(r.device_ops)["jit(agg_partial)"] == pytest.approx(1.536490733, abs=1e-9)
    assert dict(r.device_ops)["jit(_compact)"] == pytest.approx(0.132518391, abs=1e-9)
    assert len(r.device_ops) == 10
    assert dict(r.idle_gaps) == {
        "unattributed": pytest.approx(0.087315393, abs=1e-9),
        "operator:ParquetScanExec": pytest.approx(0.081260740, abs=1e-9)}
    # idle and busy make up the window
    assert sum(s for _n, s in r.idle_gaps) + r.mean_busy_s == pytest.approx(r.window_s)
