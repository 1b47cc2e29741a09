"""Simple streaming operators: project, filter, limit, coalesce, rename,
union, empty, debug, expand.

Reference: ``project_exec.rs``, ``filter_exec.rs`` (with filter-project
fusion via CachedExprsEvaluator), ``limit_exec.rs``, ``coalesce_batches``,
``rename_columns_exec.rs``, ``union_exec.rs``, ``empty_partitions_exec.rs``,
``debug_exec.rs``, ``expand_exec.rs``.
"""

from __future__ import annotations

import functools
import logging
from typing import List, Optional, Tuple

import jax
import numpy as np

from blaze_tpu.core.batch import (CodedColumn, ColumnarBatch, DeviceColumn,
                                  has_planes)
import jax.numpy as jnp
from blaze_tpu.exprs.compiler import ExprEvaluator, reference_index
from blaze_tpu.ir import exprs as E
from blaze_tpu.ir import types as T
from blaze_tpu.ops.base import ExecContext, Operator

log = logging.getLogger(__name__)


class ProjectExec(Operator):
    takes_coded = True  # a reference to a coded column is that column

    def __init__(self, child: Operator, exprs: List[E.Expr], names: List[str],
                 schema: Optional[T.Schema] = None):
        self.exprs = exprs
        self.names = names
        if schema is None:
            schema = T.Schema(
                tuple(
                    T.StructField(n, E.infer_type(e, child.schema))
                    for n, e in zip(names, exprs)
                )
            )
        super().__init__(schema, [child])

    def _execute(self, partition, ctx, metrics):
        ev = ExprEvaluator(self.exprs, self.children[0].schema, metrics)
        for batch in self.execute_child(0, partition, ctx, metrics):
            # self-time lands in elapsed_compute_time_ns via Operator.execute
            cols = ev.evaluate(batch)
            yield ColumnarBatch(self.schema, cols, batch.num_rows)


class FilterExec(Operator):
    """Filter with optional fused projection (reference: filter-project
    fusion in filter_exec.rs/cached_exprs_evaluator.rs)."""

    takes_coded = True  # code planes compact like any plane

    def __init__(self, child: Operator, predicates: List[E.Expr],
                 projection: Optional[Tuple[List[E.Expr], List[str]]] = None):
        self.predicates = predicates
        self.projection = projection
        if projection is None:
            schema = child.schema
        else:
            exprs, names = projection
            schema = T.Schema(
                tuple(
                    T.StructField(n, E.infer_type(e, child.schema))
                    for n, e in zip(names, exprs)
                )
            )
        super().__init__(schema, [child])

    def _execute(self, partition, ctx, metrics):
        child_schema = self.children[0].schema
        pred_ev = ExprEvaluator(self.predicates, child_schema, metrics)
        proj_ev = (
            ExprEvaluator(self.projection[0], child_schema, metrics)
            if self.projection else None
        )
        for batch in self.execute_child(0, partition, ctx, metrics):
            mask = pred_ev.evaluate_predicate(batch)
            all_device = all(has_planes(c) for c in batch.columns)
            if all_device:
                # device-side stable compaction: one jitted dispatch and
                # one scalar pull (core/kernels.py)
                from blaze_tpu.core import kernels

                count, datas, valids = kernels.compact_planes(
                    [c.data for c in batch.columns],
                    [c.validity for c in batch.columns], mask)
                if count == 0:
                    continue
                if count == batch.num_rows:
                    out = batch
                else:
                    cols = [c.like(d, v) for c, d, v in
                            zip(batch.columns, datas, valids)]
                    out = ColumnarBatch(batch.schema, cols, count)
            else:
                indices = np.nonzero(np.asarray(mask))[0]
                if len(indices) == 0:
                    continue
                out = batch if len(indices) == batch.num_rows else batch.take(indices)
            if proj_ev is not None:
                cols = proj_ev.evaluate(out)
                out = ColumnarBatch(self.schema, cols, out.num_rows)
            yield out


class LimitExec(Operator):
    """Per-partition limit (reference: limit_exec.rs; global limit is this
    after a single-partition exchange)."""

    takes_coded = True

    def __init__(self, child: Operator, limit: int):
        self.limit = limit
        super().__init__(child.schema, [child])

    def _execute(self, partition, ctx, metrics):
        remaining = self.limit
        if remaining <= 0:
            return
        for batch in self.execute_child(0, partition, ctx, metrics):
            if batch.num_rows >= remaining:
                yield batch.slice(0, remaining)
                return
            remaining -= batch.num_rows
            yield batch


class CoalesceBatchesExec(Operator):
    """Merge small batches up to the configured batch size (reference:
    coalesce_batches_unchecked / ExecutionContext.coalesce)."""

    takes_coded = True

    def __init__(self, child: Operator, batch_size: Optional[int] = None):
        self.batch_size = batch_size
        super().__init__(child.schema, [child])

    def _execute(self, partition, ctx, metrics):
        target = self.batch_size or ctx.conf.batch_size
        staged: List[ColumnarBatch] = []
        staged_rows = 0
        for batch in self.execute_child(0, partition, ctx, metrics):
            if batch.num_rows == 0:
                continue
            if batch.num_rows >= target and not staged:
                yield batch
                continue
            staged.append(batch)
            staged_rows += batch.num_rows
            if staged_rows >= target:
                out = ColumnarBatch.concat(staged, self.schema, metrics)
                staged, staged_rows = [], 0
                yield out
        if staged:
            yield ColumnarBatch.concat(staged, self.schema, metrics)


class RenameColumnsExec(Operator):
    """Zero-copy schema rename (reference: rename_columns_exec.rs)."""

    takes_coded = True

    def __init__(self, child: Operator, names: List[str]):
        self.names = names
        super().__init__(child.schema.rename(names), [child])

    def _execute(self, partition, ctx, metrics):
        for batch in self.execute_child(0, partition, ctx, metrics):
            yield batch.rename(self.names)


class UnionExec(Operator):
    """Union with partition mapping (reference: union_exec.rs)."""

    takes_coded = True

    def __init__(self, inputs: List[Operator],
                 num_partitions: Optional[int] = None,
                 in_partitions: Optional[List[Tuple[int, int]]] = None):
        if not in_partitions:
            in_partitions = []
            for i, op in enumerate(inputs):
                for p in range(op.num_partitions()):
                    in_partitions.append((i, p))
        self.in_partitions = in_partitions
        # None: stack every input partition (Spark UnionExec semantics)
        self._num_partitions = len(in_partitions) \
            if num_partitions is None else num_partitions
        super().__init__(inputs[0].schema, inputs)

    def num_partitions(self):
        return self._num_partitions

    def _execute(self, partition, ctx, metrics):
        if partition >= len(self.in_partitions):
            return
        child_i, child_p = self.in_partitions[partition]
        for batch in self.children[child_i].execute(child_p, ctx, metrics.child(child_i)):
            if batch.schema.names != self.schema.names:
                batch = batch.rename(self.schema.names)
            yield batch


class EmptyPartitionsExec(Operator):
    def __init__(self, schema: T.Schema, num_partitions: int):
        self._num_partitions = num_partitions
        super().__init__(schema, [])

    def num_partitions(self):
        return self._num_partitions

    def _execute(self, partition, ctx, metrics):
        return iter(())


class DebugExec(Operator):
    """Batch-logging passthrough (reference: debug_exec.rs)."""

    def __init__(self, child: Operator, debug_id: str = ""):
        self.debug_id = debug_id
        super().__init__(child.schema, [child])

    def _execute(self, partition, ctx, metrics):
        for i, batch in enumerate(self.execute_child(0, partition, ctx, metrics)):
            log.info("[%s] partition %d batch %d: %d rows\n%s",
                     self.debug_id, partition, i, batch.num_rows,
                     batch.to_arrow().slice(0, 10).to_pandas())
            yield batch


class MemoryScanExec(Operator):
    """Leaf over in-memory batches, one list per partition — the test-source
    analogue of the reference's MemoryExec-based operator tests
    (SURVEY.md §4.1)."""

    def __init__(self, schema: T.Schema, partitions: List[List[ColumnarBatch]]):
        self.partitions = partitions
        super().__init__(schema, [])

    def num_partitions(self):
        return len(self.partitions)

    def _execute(self, partition, ctx, metrics):
        yield from self.partitions[partition]


class ExpandExec(Operator):
    """Grouping-sets expansion: each input batch emits one output batch per
    projection list (reference: expand_exec.rs).

    A projection that is a reference to a coded column yields that column
    (codes and dictionary, nothing computed); a typed NULL literal standing
    where another projection has such a reference yields an all-invalid
    plane over the SAME dictionary (`CodedColumn.nulls_like`: a ROLLUP's
    nulled key stays a coded key, so the aggregation above sees one kind of
    column and one dictionary a key); `spark_grouping_id` is a literal, a
    device constant. No pull and no per-row Python."""

    takes_coded = True

    def __init__(self, child: Operator, projections: List[List[E.Expr]],
                 schema: T.Schema):
        self.projections = projections
        super().__init__(schema, [child])

    def _coded_sources(self, child_schema: T.Schema):
        """Per output position: the child column a coded NULL may borrow its
        dictionary from (the first projection that references one there)."""
        sources = [None] * len(self.schema)
        for pos, f in enumerate(self.schema.fields):
            if not T.is_var_width(f.dtype):
                continue
            sources[pos] = next(
                (idx for idx in (reference_index(proj[pos], child_schema)
                                 for proj in self.projections)
                 if idx is not None), None)
        return sources

    def _execute(self, partition, ctx, metrics):
        from blaze_tpu.core import kernels
        from blaze_tpu.exprs.compiler import make_literal
        from blaze_tpu.utils.device import is_device_dtype

        child_schema = self.children[0].schema
        sources = self._coded_sources(child_schema)
        # per projection: the whole evaluator (the fallback), the places of
        # the NULLs that stand for a coded column, the places and device
        # scalars of the constants, and an evaluator of the rest
        plans = []
        for p in self.projections:
            nulls = [pos for pos, e in enumerate(p)
                     if sources[pos] is not None and isinstance(e, E.Literal)
                     and e.value is None]
            consts = [pos for pos, e in enumerate(p)
                      if isinstance(e, E.Literal) and e.value is not None
                      and is_device_dtype(e.dtype)]
            rest = [pos for pos in range(len(p))
                    if pos not in nulls and pos not in consts]
            plans.append((ExprEvaluator(p, child_schema, metrics), nulls, consts,
                          tuple(make_literal(p[pos].value, p[pos].dtype).data
                                for pos in consts),
                          rest, ExprEvaluator([p[pos] for pos in rest],
                                              child_schema, metrics)))
        for batch in self.execute_child(0, partition, ctx, metrics):
            coded = any(isinstance(c, CodedColumn) for c in batch.columns)
            for ev, nulls, consts, scalars, rest, ev_rest in plans:
                if all(isinstance(batch.columns[sources[pos]], CodedColumn)
                       for pos in nulls):
                    cols = [None] * len(self.schema)
                    for pos, col in zip(rest, ev_rest.evaluate(batch)):
                        cols[pos] = col
                    for pos in nulls:
                        cols[pos] = batch.columns[sources[pos]].nulls_like()
                    if consts:
                        planes, live = kernels._dispatch(
                            expand_literal, scalars,
                            jnp.int32(batch.num_rows), capacity=batch.capacity)
                        for pos, plane in zip(consts, planes):
                            cols[pos] = DeviceColumn(self.schema[pos].dtype,
                                                     plane, live)
                else:
                    cols = ev.evaluate(batch)
                metrics.add("rollup_rows", batch.num_rows)
                if coded:
                    metrics.add("coded_key_batches", 1)
                yield ColumnarBatch(self.schema, cols, batch.num_rows)


@functools.partial(jax.jit, static_argnames=("capacity",))
def expand_literal(scalars, num_rows, capacity):
    """A projection's constants (`spark_grouping_id` among them) as planes
    under the padding contract, and the validity they share, in ONE launch a
    projection and batch."""
    with jax.named_scope("constant"):
        live = jnp.arange(capacity, dtype=jnp.int32) < num_rows
        return tuple(jnp.where(live, v, jnp.zeros((), v.dtype))
                     for v in scalars), live
