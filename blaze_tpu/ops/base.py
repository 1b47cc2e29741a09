"""Operator protocol and per-task execution context.

Reference: DataFusion ``ExecutionPlan`` impls driven by
``ExecutionContext`` (``datafusion-ext-plans/src/common/execution_context.rs:69``)
— execute/coalesce/stat/output_with_sender/cancel. Here an operator is a
schema-carrying object whose ``execute(partition, ctx)`` returns a python
generator of ColumnarBatches; generators give us the same pull-based
streaming the reference gets from tokio streams, with cooperative
cancellation checked between batches.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any, Dict, Iterator, List, Optional

from blaze_tpu.config import Config, get_config
from blaze_tpu.core.batch import ColumnarBatch
from blaze_tpu.ir import types as T
from blaze_tpu.obs.tracer import TRACER
from blaze_tpu.runtime.metrics import MetricNode

# Per-thread stack of [metric_node, resume_ts_ns] frames for self-time
# attribution: when a child operator's generator resumes it pauses the
# parent's clock, so ``elapsed_compute_time_ns`` on every node is SELF time
# (excludes children; consumer time is excluded because timing stops at
# yield — same discipline the reference gets from WrappedSender.exclude_time,
# execution_context.rs:705-730, here enforced structurally by the generator
# wrapper below).
_SELF_TIME = threading.local()

SELF_TIME_METRIC = "elapsed_compute_time_ns"


def _time_stack() -> list:
    stack = getattr(_SELF_TIME, "stack", None)
    if stack is None:
        stack = _SELF_TIME.stack = []
    return stack


class TaskCancelled(Exception):
    pass


class QueryCancelled(TaskCancelled):
    """Whole-query cancellation (client cancel or deadline) as opposed to a
    single task's cancel flag; carries the reason the serving layer set."""


class CancelToken:
    """Query-level cancellation + deadline token shared by every task of one
    query (reference: ``is_task_running`` flipped through the JNI on Spark
    task kill; here the serving layer owns the flip). Checked cooperatively
    between batches (``Operator.execute``), at stage boundaries
    (``Session._run_tasks``), and in the worker-pool scheduling loop
    (``WorkerPool.run_tasks``). ``deadline`` is a ``time.monotonic()``
    stamp; the token self-fires on the first check past it, so deadline
    enforcement needs no dedicated timer thread."""

    __slots__ = ("_event", "deadline", "reason")

    def __init__(self, deadline: Optional[float] = None):
        self._event = threading.Event()
        self.deadline = deadline
        self.reason: Optional[str] = None

    def cancel(self, reason: str = "cancelled"):
        if not self._event.is_set():
            self.reason = reason
            self._event.set()

    @property
    def cancelled(self) -> bool:
        if self._event.is_set():
            return True
        if self.deadline is not None and time.monotonic() >= self.deadline:
            self.cancel("deadline exceeded")
            return True
        return False

    def check(self):
        if self.cancelled:
            raise QueryCancelled(self.reason or "cancelled")


@dataclasses.dataclass
class TaskContext:
    """Identity of one task: (stage, partition, attempt) — reference:
    TaskDefinition/PartitionId in auron.proto:729-740."""

    stage_id: int = 0
    partition_id: int = 0
    task_id: int = 0


class ExecContext:
    """Per-task context handed to every operator: conf, metrics root, memory
    manager, the resource map (reference: JniBridge.resourcesMap), and the
    cooperative-cancellation flag (reference: is_task_running)."""

    def __init__(
        self,
        task: Optional[TaskContext] = None,
        conf: Optional[Config] = None,
        metrics: Optional[MetricNode] = None,
        resources: Optional[Dict[str, Any]] = None,
        mem_manager=None,
        cancel_token: Optional[CancelToken] = None,
    ):
        self.task = task or TaskContext()
        self.conf = conf or get_config()
        self.metrics = metrics or MetricNode("root")
        self.resources = resources if resources is not None else {}
        self._cancelled = threading.Event()
        # query-level token shared by every task of one query; the per-task
        # flag above stays for single-task cancellation (tests, tools)
        self.cancel_token = cancel_token
        if mem_manager is None:
            from blaze_tpu.runtime.memmgr import MemManager

            mem_manager = MemManager.get_or_init(self.conf)
        self.mem = mem_manager

    def cancel(self):
        self._cancelled.set()

    @property
    def is_cancelled(self) -> bool:
        return self._cancelled.is_set() or (
            self.cancel_token is not None and self.cancel_token.cancelled)

    def check_cancelled(self):
        if self.cancel_token is not None:
            self.cancel_token.check()  # raises QueryCancelled with reason
        if self._cancelled.is_set():
            raise TaskCancelled(f"task {self.task} cancelled")


class Operator:
    """Base operator. Subclasses set ``schema`` and ``children`` and implement
    ``_execute``; the base wraps it with batch/row counting and cancellation."""

    schema: T.Schema
    children: List["Operator"]

    def __init__(self, schema: T.Schema, children: List["Operator"]):
        self.schema = schema
        self.children = children

    @property
    def name(self) -> str:
        return type(self).__name__

    def num_partitions(self) -> int:
        if self.children:
            return self.children[0].num_partitions()
        return 1

    def execute(self, partition: int, ctx: ExecContext, metrics: Optional[MetricNode] = None
                ) -> Iterator[ColumnarBatch]:
        node = metrics if metrics is not None else ctx.metrics
        node.name = self.name
        gen = self._execute(partition, ctx, node)
        stack = _time_stack()
        trace = TRACER.active  # full trace OR the flight-recorder ring
        # full trace only: every stretch charged to a node below is also an
        # "op" span from the same two stamps, so an operator's op segments
        # sum to its SELF_TIME_METRIC and a task thread is in exactly one
        # of them at any instant (the "operator" span in the finally is the
        # generator's whole life: their parent, and no name for an idle gap)
        segments = TRACER.enabled
        span_t0 = time.perf_counter_ns() if trace else 0
        rows = 0
        try:
            while True:
                # resume charging THIS node; pause the caller's clock
                now = time.perf_counter_ns()
                if stack:
                    parent = stack[-1]
                    parent[0].add(SELF_TIME_METRIC, now - parent[1])
                    if segments:
                        TRACER.complete(parent[0].name, "op", parent[1],
                                        now - parent[1])
                stack.append([node, now])
                try:
                    batch = next(gen)
                except StopIteration:
                    return
                finally:
                    # stop charging at yield/exhaustion/error: consumer time
                    # and downstream work never land on this node
                    now = time.perf_counter_ns()
                    frame = stack.pop()
                    frame[0].add(SELF_TIME_METRIC, now - frame[1])
                    if segments:
                        TRACER.complete(node.name, "op", frame[1],
                                        now - frame[1])
                    if stack:
                        stack[-1][1] = now
                ctx.check_cancelled()
                node.add("output_rows", batch.num_rows)
                node.add("output_batches", 1)
                rows += batch.num_rows
                yield batch
        finally:
            if trace:
                t1 = time.perf_counter_ns()
                TRACER.complete(
                    self.name, "operator", span_t0, t1 - span_t0,
                    {"partition": partition, "rows": rows,
                     "self_time_ms": round(node.get(SELF_TIME_METRIC) / 1e6, 3)})

    def _execute(self, partition: int, ctx: ExecContext, metrics: MetricNode
                 ) -> Iterator[ColumnarBatch]:
        raise NotImplementedError

    # Does ``_execute`` know a wide decimal that arrives as one int64 device
    # plane (a window's result whose values fit, ops/window_device.py)? Every
    # other operator is handed such a column as its type's host column.
    takes_wide_planes = False

    # Does ``_execute`` know a var-width column that arrives CODED (int32
    # codes and validity on the device, one dictionary on the host:
    # core/batch.CodedColumn)? Every other operator is handed such a column
    # as a host column over the same dictionary (Arrow's dictionary array;
    # the codes are pulled, no value is touched), counted as
    # ``host_key_batches``.
    takes_coded = False

    def execute_child(self, i: int, partition: int, ctx: ExecContext,
                      metrics: MetricNode) -> Iterator[ColumnarBatch]:
        batches = self.children[i].execute(partition, ctx, metrics.child(i))
        fields = self.children[i].schema.fields
        if not self.takes_wide_planes and any(
                T.is_wide_decimal(f.dtype) for f in fields):
            batches = (b.by_type() for b in batches)
        if not self.takes_coded and any(
                T.is_var_width(f.dtype) for f in fields):
            batches = (b.coded_to_host(metrics) for b in batches)
        return batches

    def __repr__(self):
        return f"{self.name}({', '.join(repr(c) for c in self.children)})"
