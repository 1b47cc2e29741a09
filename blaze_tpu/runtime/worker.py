"""Worker process entry: executes TaskDefinitions shipped by the driver.

The reference's executor-side story (SURVEY.md §3.2): a Spark executor JVM
receives a serialized task, crosses into the native engine via
``JniBridge.callNative`` with the protobuf ``TaskDefinition``, and streams
the plan. Here the OS process IS the executor: it connects back to the
driver's unix socket, then loops — receive {task_bytes (proto
TaskDefinition), conf, resources} → build the operator tree → run it →
reply. Shuffle map tasks write data+index files to the shared filesystem
(the durable hand-off, like Spark local shuffle); the reply carries file
paths, not rows.

Run as: ``python -m blaze_tpu.runtime.worker <socket-path>``.
"""

from __future__ import annotations

import os
import sys
import traceback


def _configure_platform():
    """Pool workers run on the CPU backend: a chip belongs to one process,
    and that process is the driver that spawned this one. A child that asked
    for the accelerator would fail or hang, so the shm tier, lineage
    recovery and every pooled map task are host-only by construction.
    ``import blaze_tpu`` places the compile cache as in the driver."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    import blaze_tpu  # noqa: F401


def run_task(msg: dict, shared: dict = None) -> dict:
    import dataclasses

    from blaze_tpu.config import Config, set_config
    from blaze_tpu.ir.protoserde import task_definition_from_bytes
    from blaze_tpu.obs.stats import STATS_HUB
    from blaze_tpu.obs.telemetry import get_registry
    from blaze_tpu.obs.telemetry import configure_from as _telemetry_configure
    from blaze_tpu.obs.tracer import TRACER
    from blaze_tpu.obs.tracer import configure_from as _tracer_configure
    from blaze_tpu.ops.base import ExecContext, TaskContext
    from blaze_tpu.runtime.executor import build_operator
    from blaze_tpu.runtime.metrics import MetricNode
    from blaze_tpu.utils.logutil import clear_task_context, set_task_context

    conf = Config(**msg["conf"]) if msg.get("conf") else None
    if conf is not None:
        set_config(conf)
        _tracer_configure(conf)
        _telemetry_configure(conf)
        STATS_HUB.configure_from(conf)
        # fault injection must reach task code in THIS process, not just
        # the driver: arm (or disarm) from the conf that shipped with the
        # task, so a chaos soak's spec applies fleet-wide
        from blaze_tpu.runtime import failpoints

        failpoints.arm_from(conf)
    from blaze_tpu.runtime.failpoints import failpoint

    failpoint("worker.task")
    task, plan = task_definition_from_bytes(msg["task_bytes"])
    op = build_operator(plan)
    metrics = MetricNode("task")
    resources = dict(shared or {})
    resources.update(msg.get("resources") or {})
    ctx = ExecContext(
        task=task,
        conf=conf,
        resources=resources,
    )
    set_task_context(task.stage_id, task.partition_id)
    try:
        from blaze_tpu.runtime import placement

        where = placement.decide(conf) if conf is not None else "device"
        rows = 0
        with placement.placed(where), \
                TRACER.span("task", "task", {"stage": task.stage_id,
                                             "map": task.partition_id}):
            for batch in op.execute(task.partition_id, ctx, metrics):
                rows += batch.num_rows  # sink plans emit nothing; drain anyway
        reply = {"ok": True, "rows": rows, "metrics": metrics.to_dict()}
        if TRACER.enabled:
            # ship this task's spans back with the result; the driver
            # re-bases them into its timeline (Session._ship_stage_to_pool)
            reply["trace"] = {"events": TRACER.drain(),
                             "wall_epoch_ns": TRACER.wall_epoch_ns}
        # child-registry deltas ride the same reply (counters/histograms are
        # zeroed by the drain, so each task ships only its own increments)
        deltas = get_registry().drain_deltas()
        if deltas:
            reply["telemetry"] = deltas
        # radix histograms noted during execution merge driver-side into
        # the query's StatsPlane (Session._ship_stage_to_pool)
        stats = STATS_HUB.drain_all_merged()
        if stats:
            reply["stats"] = stats
        return reply
    finally:
        clear_task_context()


def main(sock_path: str):
    import socket

    from blaze_tpu.runtime.ipc import recv_msg, send_msg

    sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
    sock.connect(sock_path)
    send_msg(sock, {"hello": os.getpid()})
    shared: dict = {}
    while True:
        try:
            msg = recv_msg(sock)
        except EOFError:
            return
        if msg.get("shutdown"):
            return
        if "set_shared" in msg:
            # stage-level resources arrive ONCE per worker, not per task
            shared = msg["set_shared"] or {}
            send_msg(sock, {"ok": True})
            continue
        try:
            reply = run_task(msg, shared)
        except BaseException as exc:  # report, keep serving
            reply = {"ok": False, "error": f"{type(exc).__name__}: {exc}",
                     "traceback": traceback.format_exc()}
            from blaze_tpu.runtime.memmgr import SpillFailed
            from blaze_tpu.runtime.recovery import ShuffleOutputMissing

            if isinstance(exc, ShuffleOutputMissing):
                # structured fetch failure: the driver's lineage recovery
                # recomputes the named maps and re-queues this task
                reply["error_kind"] = "shuffle_missing"
                reply["stage"] = exc.stage
                reply["maps"] = exc.maps
            elif isinstance(exc, SpillFailed):
                # typed degradation: the owning QUERY must fail (it cannot
                # shed memory), but this worker process stays healthy — the
                # driver fails the stage fast instead of retrying into the
                # same full spill disk
                reply["error_kind"] = "spill_failed"
        send_msg(sock, reply)


if __name__ == "__main__":
    _configure_platform()
    main(sys.argv[1])
