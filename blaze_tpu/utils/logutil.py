"""Task-context logging.

Reference: ``auron/src/logging.rs:23-43`` — stderr logging with thread-local
``[stage.partition tid]`` prefixes, level from conf. Here a logging.Filter
injects the current task context set by the executor."""

from __future__ import annotations

import logging
import os
import threading

_ctx = threading.local()


def set_task_context(stage_id: int, partition_id: int, query_id=None):
    _ctx.stage = stage_id
    _ctx.partition = partition_id
    _ctx.query = query_id


def clear_task_context():
    _ctx.stage = None
    _ctx.partition = None
    _ctx.query = None


def task_context():
    """This thread's ``(stage, partition, query id)``, or None outside a
    task. The tracer stamps it on every span; a helper thread a task starts
    (the scan and shuffle prefetchers) passes its creator's to
    :func:`adopt_task_context` so its spans carry the same identity."""
    stage = getattr(_ctx, "stage", None)
    if stage is None:
        return None
    return stage, _ctx.partition, getattr(_ctx, "query", None)


def adopt_task_context(ctx):
    """Take over another thread's :func:`task_context` (None clears)."""
    if ctx is None:
        clear_task_context()
    else:
        set_task_context(*ctx)


class TaskContextFilter(logging.Filter):
    def filter(self, record):
        stage = getattr(_ctx, "stage", None)
        part = getattr(_ctx, "partition", None)
        if stage is None:
            record.task = "driver"
        else:
            record.task = f"{stage}.{part}"
        return True


def init_logging(level: str = None):
    """Configure engine logging (idempotent): stderr with task prefixes,
    level from BLAZE_TPU_LOG_LEVEL (reference: spark.auron.native.log.level)."""
    root = logging.getLogger("blaze_tpu")
    if getattr(root, "_blaze_configured", False):
        return root
    level = level or os.environ.get("BLAZE_TPU_LOG_LEVEL", "WARNING")
    handler = logging.StreamHandler()
    handler.addFilter(TaskContextFilter())
    handler.setFormatter(logging.Formatter(
        "%(asctime)s %(levelname)s [%(task)s %(threadName)s] %(name)s: %(message)s"))
    root.addHandler(handler)
    root.setLevel(level.upper())
    root._blaze_configured = True
    return root
