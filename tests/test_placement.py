"""Stage placement (runtime/placement.py) and the start-up plumbing beside
it: placement is decided in-process from the configuration and the process's
own backend, no child process is ever asked, the compile cache has one place,
and nothing reroutes a failed device kernel."""

import os
import subprocess
import sys

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from blaze_tpu.config import config_override
from blaze_tpu.ir import exprs as E
from blaze_tpu.ir import nodes as N
from blaze_tpu.ir import types as T
from blaze_tpu.runtime import placement

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _scan_plan(tmp_path, rows=5_000):
    tbl = pa.table({"k": np.arange(rows) % 100, "v": np.arange(rows)})
    path = str(tmp_path / "t.parquet")
    pq.write_table(tbl, path)
    from blaze_tpu.ops.parquet import scan_node_for_files

    scan = scan_node_for_files([path], num_partitions=1)
    partial = N.Agg(
        N.Filter(scan, [E.BinaryExpr(E.BinaryOp.GT, E.Column("v"),
                                     E.Literal(10, T.I64))]),
        E.AggExecMode.HASH_AGG,
        [("k", E.Column("k"))],
        [N.AggColumn(E.AggExpr(E.AggFunction.SUM, [E.Column("v")], T.I64),
                     E.AggMode.PARTIAL, "s")])
    ex = N.ShuffleExchange(partial, N.HashPartitioning([E.Column("k")], 2))
    return N.Agg(ex, E.AggExecMode.HASH_AGG, [("k", E.Column("k"))], [
        N.AggColumn(E.AggExpr(E.AggFunction.SUM, [E.Column("v")], T.I64),
                    E.AggMode.FINAL, "s")])


def test_auto_places_on_device_when_backend_is_not_cpu(monkeypatch):
    """``auto`` means the process's backend, whatever it is: with an
    accelerator as the default backend the answer is still "device", and the
    CPU-only kernel switches read the same backend."""
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with config_override(device_placement="auto") as conf:
        assert placement.decide(conf) == "device"
    assert placement.backend_is_cpu_hint() is False


def test_auto_and_device_agree_on_cpu_backend():
    for mode in ("auto", "device"):
        with config_override(device_placement=mode) as conf:
            assert placement.decide(conf) == "device"
    assert placement.backend_is_cpu_hint() is True


def test_forced_host_still_pins(monkeypatch):
    """Forced ``host`` pins the task thread to a CPU device even when the
    process's default backend is an accelerator."""
    import jax

    with config_override(device_placement="host") as conf:
        assert placement.decide(conf) == "host"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert jax.config.jax_default_device is None
    with placement.placed("host"):
        assert jax.config.jax_default_device.platform == "cpu"
    assert jax.config.jax_default_device is None
    with placement.placed("device"):
        assert jax.config.jax_default_device is None


def test_unknown_placement_mode_raises():
    with config_override(device_placement="somewhere") as conf:
        with pytest.raises(ValueError, match="somewhere"):
            placement.decide(conf)


def test_no_device_raises_naming_platforms(monkeypatch):
    """No usable device is an exception from Session that names the platform
    list — never a host placement."""
    import jax

    from blaze_tpu.runtime.session import Session

    def no_devices():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "devices", no_devices)
    with pytest.raises(RuntimeError, match="no usable JAX device.*cpu"):
        Session()


def test_session_spawns_no_subprocess(tmp_path, monkeypatch):
    """A pool-less Session never starts a child: no probe, no background
    build. (WorkerPool.spawn and the synchronous native build — done by
    conftest before this runs — are the only subprocess users.)"""
    from blaze_tpu.runtime.session import Session

    def refuse(*a, **kw):
        raise AssertionError(f"subprocess started: {a[:1]}")

    monkeypatch.setattr(subprocess, "run", refuse)
    monkeypatch.setattr(subprocess, "Popen", refuse)
    monkeypatch.setattr(os, "system", refuse)
    plan = _scan_plan(tmp_path)
    with Session() as sess:
        got = sess.execute_to_table(plan)
        assert sess.metrics.total("placement_device_stages") > 0
        assert sess.metrics.total("placement_host_stages") == 0
    assert got.num_rows == 100


def test_bench_and_placement_name_no_subprocess():
    """bench.py, the placement module and the fused operator have no way to
    start a process at all (the probe-in-a-child they used to share)."""
    for rel in ("bench.py", "blaze_tpu/runtime/placement.py",
                "blaze_tpu/ops/fused.py"):
        with open(os.path.join(REPO, rel)) as f:
            src = f.read()
        assert "subprocess" not in src and "Popen" not in src, rel


def test_session_runs_under_forced_host_placement(tmp_path):
    """End-to-end: forced host placement produces identical results (on the
    CPU test backend the pin is a no-op, but the full decision+context path
    executes for every stage)."""
    from blaze_tpu.runtime.session import Session

    plan = _scan_plan(tmp_path)
    with config_override(device_placement="host"):
        with Session() as sess:
            got = sess.execute_to_table(plan)
            assert sess.metrics.total("placement_host_stages") > 0
    with config_override(device_placement="auto"):
        with Session() as sess:
            want = sess.execute_to_table(plan)
    gd = dict(zip(got["k"].to_pylist(), got["s"].to_pylist()))
    wd = dict(zip(want["k"].to_pylist(), want["s"].to_pylist()))
    assert gd == wd


def test_placed_context_is_noop_on_cpu_backend():
    import jax

    with placement.placed("host"):
        x = jax.numpy.ones(4)
        assert list(x.devices())[0].platform == "cpu"


# -- compile cache ------------------------------------------------------------

_PRINT_CACHE_DIR = ("import jax, blaze_tpu; "
                    "print(jax.config.jax_compilation_cache_dir)")


def _cache_dir_seen_from(cwd, env_dir=None):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = REPO
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    r = subprocess.run([sys.executable, "-c", _PRINT_CACHE_DIR], cwd=cwd,
                       env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    return r.stdout.strip().splitlines()[-1]


def test_compile_cache_env_dir_left_alone(tmp_path):
    placed_dir = str(tmp_path / "some" / "dir")
    assert _cache_dir_seen_from(str(tmp_path), placed_dir) == placed_dir
    assert not os.path.exists(placed_dir)  # not even created: JAX owns it


def test_compile_cache_default_is_in_checkout_from_any_cwd(tmp_path):
    want = os.path.join(REPO, ".jax_cache")
    assert _cache_dir_seen_from(str(tmp_path)) == want
    assert _cache_dir_seen_from(REPO) == want


# -- fused stage failures -----------------------------------------------------


def test_fused_trace_failure_propagates(monkeypatch):
    """A fused closure that raises at trace time is an error carrying the
    fingerprint — no per-batch eager rerun, no sticky broken state."""
    from blaze_tpu.core.batch import ColumnarBatch
    from blaze_tpu.ops import fused
    from blaze_tpu.ops.base import ExecContext
    from blaze_tpu.ops.basic import MemoryScanExec

    def boom(in_schema, steps):
        def closure(datas, valids, num_rows):
            raise TypeError("XLA:TPU refused the kernel")
        return closure

    fused.clear_fused_cache()
    monkeypatch.setattr(fused, "build_fused_closure", boom)
    batch = ColumnarBatch.from_arrow(pa.table({
        "k": pa.array([1, 2, 3], type=pa.int64()),
        "v": pa.array([10, 20, 30], type=pa.int64())}))
    leaf = N.BatchSource(batch.schema, "unused", 1)
    filt = N.Filter(leaf, [E.BinaryExpr(E.BinaryOp.GT, E.Column("k"),
                                        E.Literal(1, T.I64))])
    proj = N.Projection(filt, [E.BinaryExpr(E.BinaryOp.ADD, E.Column("k"),
                                            E.Column("v"))], ["kv"])
    op = fused.FusedStageExec(MemoryScanExec(batch.schema, [[batch]]),
                              N.FusedStage(child=leaf, ops=(filt, proj)))
    try:
        ctx = ExecContext()
        with pytest.raises(fused.FusedStageError,
                           match="refused the kernel") as err:
            list(op.execute(0, ctx))
        assert err.value.fingerprint == op.pipeline[0].fingerprint
        assert ctx.metrics.total("fused_fallback_batches") == 0
        assert not hasattr(fused, "_BROKEN")
    finally:
        fused.clear_fused_cache()  # drop the poisoned closure


# -- chip_smoke.py ------------------------------------------------------------


def _run_smoke(*args, devices=1):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *args],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)


def test_chip_smoke_refuses_cpu_without_flag():
    r = _run_smoke("--rows", "100000")
    assert r.returncode != 0
    assert "'cpu'" in r.stderr and "not 'tpu'" in r.stderr
    assert r.stdout.strip() == ""  # no result line


def _verdict_and_report(stdout):
    """The last stdout line is the verdict and holds nothing but ``ok`` and
    ``device``; the line before it is ``report: {...}`` with the readings."""
    import json

    *_, report_line, verdict_line = stdout.strip().splitlines()
    verdict = json.loads(verdict_line)
    assert set(verdict) == {"ok", "device"} and verdict["ok"] is True
    assert set(verdict["device"]) == {"platform", "kind", "count"}
    assert report_line.startswith("report: ")
    report = json.loads(report_line[len("report: "):])
    assert report["device"] == verdict["device"]
    return verdict, report


def test_chip_smoke_passes_on_cpu_when_allowed():
    r = _run_smoke("--rows", "100000", "--allow-cpu")
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    verdict, result = _verdict_and_report(r.stdout)
    assert verdict["device"] == {"platform": "cpu", "kind": "cpu", "count": 1}
    assert result["reduced"] == ["rows 28800991 -> 100000"]
    assert set(result["batch"]) == {"q01", "q06", "q17", "q47", "q67"}
    for shape in result["batch"].values():  # asserted by the smoke itself
        assert shape["fused_fallback_batches"] == 0
        assert shape["device_merge_batches"] >= 1
    assert result["known_exceptions"] == []  # q67 misses only at SF10 rows
    assert [r["compile_requests"] for r in result["serve"]] == [0, 0]
    assert result["mesh"] == "skipped (1 device)"


def test_chip_smoke_runs_the_mesh_leg_alone_on_four_devices():
    r = _run_smoke("--rows", "100000", "--allow-cpu", devices=4)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    verdict, result = _verdict_and_report(r.stdout)
    assert verdict["device"]["count"] == 4
    assert result["batch"] == result["serve"] == \
        "skipped (4 devices: the mesh leg runs instead)"
    for shape in ("q01", "q67"):
        assert result["mesh"][shape]["sharded_stages"] > 0
        assert result["mesh"][shape]["collective_bytes"] > 0
    assert len(result["mesh"]["peak_bytes_in_use_per_device"]) == 4
