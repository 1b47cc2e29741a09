"""Whole-stage fused operator: one jitted XLA computation per chain of
narrow operators.

``ir/fusion.py`` decides WHAT to fuse; this operator decides HOW it runs.
A FusedStage's op chain is lowered to steps and split at coalesce-batches
boundaries into jitted segments: each segment's project/filter/rename/expand
steps evaluate inside ONE ``jax.jit`` closure (``exprs.compiler.
build_fused_closure``) — filters narrow a live mask instead of compacting
mid-chain, and each output group compacts once at the end, so a
project-over-filter-over-project chain costs one dispatch and one scalar
sync per batch, exactly like a lone FilterExec. Closures are cached
process-wide by chain fingerprint (shared across queries); jax's own jit
cache then keys on the (capacity-bucket, dtype) shapes, and every dispatch
reports whether it hit that cache — the ``jit_cache_hits`` /
``jit_cache_misses`` tripwire counters.

The fusion pass only admits statically-traceable chains. A batch the
closure cannot take because it is not all-device (host/dictionary-encoded
columns, mixed capacities) runs per-batch through an eager evaluation with
the same semantics as the unfused operators (``fused_fallback_batches``
counts them). A closure that fails to trace, compile or run is an error:
``FusedStageError`` carries the chain fingerprint and the backend's message,
and nothing reroutes the batch.
"""

from __future__ import annotations

import threading
from typing import Dict, List, Optional

import jax
import numpy as np

from blaze_tpu.core.batch import ColumnarBatch, DeviceColumn
from blaze_tpu.exprs.compiler import ExprEvaluator, build_fused_closure, \
    fused_chain_schemas, fused_group_flags
from blaze_tpu.ir import nodes as N
from blaze_tpu.ir import types as T
from blaze_tpu.ir.fusion import chain_steps, fused_fingerprint
from blaze_tpu.ops.base import Operator
from blaze_tpu.utils.device import wait_int

# process-global jitted-closure cache: fingerprint -> jitted fn. Shared
# across batches, partitions, and queries — the second query with the same
# subplan shape skips straight to a jit-cache hit.
_CLOSURE_CACHE: Dict[str, object] = {}
_CACHE_LOCK = threading.Lock()


class FusedStageError(RuntimeError):
    """A fused closure failed to trace, compile or execute."""

    def __init__(self, fingerprint: str, err: BaseException):
        super().__init__(
            f"fused segment {fingerprint} failed: "
            f"{type(err).__name__}: {err}")
        self.fingerprint = fingerprint


_EXEC_NAMES = {
    N.Projection: "ProjectExec",
    N.Filter: "FilterExec",
    N.RenameColumns: "RenameColumnsExec",
    N.CoalesceBatches: "CoalesceBatchesExec",
    N.Expand: "ExpandExec",
}


def clear_fused_cache():
    """Test hook: drop all cached closures (and their jit caches)."""
    with _CACHE_LOCK:
        _CLOSURE_CACHE.clear()


class _FusedSegment:
    """One jitted run of non-coalesce steps."""

    def __init__(self, steps, in_schema: T.Schema):
        self.steps = steps
        self.in_schema = in_schema
        self.out_schema = fused_chain_schemas(in_schema, steps)[-1]
        self.group_flags = fused_group_flags(steps)
        self.fingerprint = fused_fingerprint(in_schema, steps)

    def closure(self):
        fp = self.fingerprint
        with _CACHE_LOCK:
            fn = _CLOSURE_CACHE.get(fp)
            if fn is None:
                fn = jax.jit(build_fused_closure(self.in_schema, self.steps))
                _CLOSURE_CACHE[fp] = fn
        return fn


class FusedStageExec(Operator):
    """Executes a fused chain: alternating jitted segments and host-side
    coalesce staging. ``fused_op_names`` lists the absorbed operators
    (innermost-first) for explain/debug rendering."""

    def __init__(self, child: Operator, node: N.FusedStage):
        super().__init__(node.output_schema, [child])
        self.node = node
        self.fused_op_names = [
            _EXEC_NAMES.get(type(op), type(op).__name__) for op in node.ops]
        steps = chain_steps(node.ops)
        self.pipeline = []  # ("coalesce", batch_size) | _FusedSegment
        schema = child.schema
        run: list = []
        for st in steps:
            if st[0] == "coalesce":
                if run:
                    seg = _FusedSegment(tuple(run), schema)
                    self.pipeline.append(seg)
                    schema = seg.out_schema
                    run = []
                self.pipeline.append(("coalesce", st[1]))
            else:
                run.append(st)
        if run:
            self.pipeline.append(_FusedSegment(tuple(run), schema))

    def _execute(self, partition, ctx, metrics):
        segs = [p for p in self.pipeline if isinstance(p, _FusedSegment)]
        metrics.add("fused_stages", len(segs))
        metrics.add("fused_ops", len(self.node.ops))
        stream = self.execute_child(0, partition, ctx, metrics)
        for part in self.pipeline:
            if isinstance(part, _FusedSegment):
                stream = self._fused_stream(stream, part, metrics)
            else:
                stream = self._coalesce_stream(stream, part[1], ctx)
        yield from stream

    # -- coalesce staging (same semantics as CoalesceBatchesExec) -------------

    def _coalesce_stream(self, stream, batch_size: Optional[int], ctx):
        target = batch_size or ctx.conf.batch_size
        staged: List[ColumnarBatch] = []
        staged_rows = 0
        for batch in stream:
            if batch.num_rows == 0:
                continue
            if batch.num_rows >= target and not staged:
                yield batch
                continue
            staged.append(batch)
            staged_rows += batch.num_rows
            if staged_rows >= target:
                out = ColumnarBatch.concat(staged, batch.schema)
                staged, staged_rows = [], 0
                yield out
        if staged:
            yield ColumnarBatch.concat(staged, staged[0].schema)

    # -- jitted segment --------------------------------------------------------

    def _fused_stream(self, stream, seg: _FusedSegment, metrics):
        for batch in stream:
            yield from self._single_batch(seg, batch, metrics)

    def _single_batch(self, seg: _FusedSegment, batch: ColumnarBatch, metrics):
        from blaze_tpu.core import kernels

        import jax.numpy as jnp

        cols = batch.columns
        fusable = (
            cols and all(isinstance(c, DeviceColumn) for c in cols)
            and len({c.capacity for c in cols}) == 1)
        if not fusable:
            metrics.add("fused_fallback_batches", 1)
            yield from self._eager_steps(seg, batch)
            return
        try:
            (groups, counts), compiled = kernels.fused_dispatch(
                seg.closure(),
                tuple(c.data for c in cols),
                tuple(c.validity for c in cols),
                jnp.int64(batch.num_rows))
        except Exception as err:
            raise FusedStageError(seg.fingerprint, err) from err
        metrics.add("jit_cache_misses" if compiled else "jit_cache_hits", 1)
        yield from self._emit_groups(seg, batch.num_rows, groups, counts)

    def _emit_groups(self, seg: _FusedSegment, batch_rows: int, groups, counts):
        for g, (datas, valids) in enumerate(groups):
            if seg.group_flags[g]:
                # one scalar sync, as FilterExec
                count = wait_int(counts[g], "fused_filter")
                if count == 0:
                    continue
            else:
                count = batch_rows
            out_cols = [
                DeviceColumn(f.dtype, d, v) for f, d, v in
                zip(seg.out_schema.fields, datas, valids)]
            yield ColumnarBatch(seg.out_schema, out_cols, count)

    # -- eager fallback (unfused semantics, per batch) -------------------------

    def _eager_steps(self, seg: _FusedSegment, batch: ColumnarBatch):
        yield from eager_steps(seg.steps, seg.in_schema, batch)


def eager_steps(steps, in_schema, batch: ColumnarBatch):
    """Unfused per-batch execution of a fused step chain — the fused
    stage's fallback, also used by the partial agg when it absorbed a chain
    whose batch turns out not to be jit-flattenable."""
    from blaze_tpu.core import kernels

    schemas = fused_chain_schemas(in_schema, steps)
    batches = [batch]
    for si, st in enumerate(steps):
        kind = st[0]
        schema_in = schemas[si]
        schema_out = schemas[si + 1]
        nxt: List[ColumnarBatch] = []
        for b in batches:
            if kind == "project":
                ev = ExprEvaluator(list(st[1]), schema_in)
                nxt.append(ColumnarBatch(
                    schema_out, ev.evaluate(b), b.num_rows))
            elif kind == "filter":
                ev = ExprEvaluator(list(st[1]), schema_in)
                mask = ev.evaluate_predicate(b)
                if all(isinstance(c, DeviceColumn) for c in b.columns):
                    count, datas, valids = kernels.compact_planes(
                        [c.data for c in b.columns],
                        [c.validity for c in b.columns], mask)
                    if count == 0:
                        continue
                    if count == b.num_rows:
                        nxt.append(b)
                    else:
                        nxt.append(ColumnarBatch(b.schema, [
                            DeviceColumn(c.dtype, d, v) for c, d, v in
                            zip(b.columns, datas, valids)], count))
                else:
                    indices = np.nonzero(np.asarray(mask))[0]
                    if len(indices) == 0:
                        continue
                    nxt.append(b if len(indices) == b.num_rows
                               else b.take(indices))
            elif kind == "rename":
                nxt.append(b.rename(list(st[1])))
            else:  # expand
                for proj in st[1]:
                    ev = ExprEvaluator(list(proj), schema_in)
                    nxt.append(ColumnarBatch(
                        schema_out, ev.evaluate(b), b.num_rows))
        batches = nxt
    yield from batches
