"""Broadcast / shuffled hash joins, all join types.

Reference: ``broadcast_join_exec.rs`` (677) + ``joins/bhj/*.rs`` — probes a
prebuilt JoinHashMap, caching the built map per executor by
``cached_build_hash_map_id`` (``broadcast_join_exec.rs:87-116``); the same
operator serves shuffled-hash-join via PartitionMode. Join types:
inner/left/right/full/semi/anti/existence on either side.

Matching is exact (host key interning, ops/joins/keymap.py); pair expansion
and row materialization are vectorized gathers (device for fixed-width
columns)."""

from __future__ import annotations

import threading
from typing import Dict, List, Optional, Tuple

import numpy as np

from blaze_tpu.core.batch import ColumnarBatch
from blaze_tpu.exprs.compiler import ExprEvaluator
from blaze_tpu.ir import exprs as E
from blaze_tpu.ir import types as T
from blaze_tpu.ir.nodes import JoinSide, JoinType, _join_output_schema
from blaze_tpu.ops.base import ExecContext, Operator
from blaze_tpu.ops.joins.keymap import JoinHashMap, key_codes

# executor-level build-map cache (reference: executor-cached by
# cached_build_hash_map_id, built once per executor per broadcast)
_BUILD_CACHE: Dict[str, JoinHashMap] = {}
_BUILD_CACHE_LOCK = threading.Lock()


import functools


@functools.lru_cache(maxsize=256)
def _inner_fast_kernel(n_probe_cols: int, nk: int):
    """Fused device inner-join kernel for unique-single-key build maps (the
    TPC-DS dimension join): probe, matched-row compaction and BOTH sides'
    rows in ONE jitted dispatch, one scalar sync for the surviving-row
    count (reference analogue: the probe+interleave loop of joins/bhj/*.rs
    fused into one XLA program).

    How the rows move is decided by what the TPU charges (PERF.md §6, PR
    27; 131,072-row batches): a scatter runs one update at a time, 9.2 ms
    for a row-sized int64 plane; a gather costs 0.94 ms for each 32-bit
    plane it is asked for (an int64 plane is two), and no more when an index
    fetches a row of many words; a two-operand 32-bit sort 0.1 ms. So
    nothing is scattered. The row map ``src`` (output row -> probe row) is the payload
    of a stable sort of the hit mask, and each side's planes move ONCE, as
    one matrix of words, by one gather (``take_rows_traced``): the probe's by
    ``src``, the match's rank riding along as one more plane, and the
    build's by that rank, straight from the build table. Rows past ``count``
    keep the padding contract (data 0, validity False)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from blaze_tpu.core.kernels import take_rows_traced
    from blaze_tpu.ops.joins.keymap import sorted_probe_traced

    def bhj_inner_fast(uniq, num_rows, kd, kv, *flat):
        probe_planes = flat[:2 * n_probe_cols]
        build_planes = flat[2 * n_probe_cols:]
        iota = jnp.arange(kd.shape[0], dtype=jnp.int32)
        with jax.named_scope("probe"):
            # keymap is the single authority for the key encoding
            rank, hit = sorted_probe_traced(uniq, kd, kv & (iota < num_rows),
                                            nk)
        with jax.named_scope("rowmap"):
            count = jnp.sum(hit)
            _, src = lax.sort(((~hit).astype(jnp.uint8), iota), num_keys=1,
                              is_stable=True)
            live = iota < count
        with jax.named_scope("gather"):
            (*pd_, build_row), pv = take_rows_traced(
                (*probe_planes[0::2], rank), probe_planes[1::2], src, live)
            # unique CSR: code c owns build row c exactly
            bd, bv = take_rows_traced(build_planes[0::2], build_planes[1::2],
                                      build_row, live)
        outs = [count]
        for d, v in zip((*pd_, *bd), (*pv, *bv)):
            outs += [d, v]
        return tuple(outs)

    return jax.jit(bhj_inner_fast)


def clear_build_cache():
    with _BUILD_CACHE_LOCK:
        _BUILD_CACHE.clear()


class _HashJoinBase(Operator):
    """Common probe logic; subclasses define how the build side loads."""

    # coded var-width columns pass on either side: the fused kernel and the
    # row movers of the generic probe carry their code planes
    takes_coded = True

    def __init__(self, left: Operator, right: Operator,
                 on: List[Tuple[E.Expr, E.Expr]], join_type: JoinType,
                 build_side: JoinSide, condition: Optional[E.Expr] = None):
        self.on = on
        self.join_type = join_type
        self.build_side = build_side
        # extra non-equi condition over left+right columns; matched pairs
        # failing it count as unmatched (reference: join filters)
        self.condition = condition
        self._pair_schema = left.schema + right.schema
        schema = _join_output_schema(left.schema, right.schema, join_type)
        super().__init__(schema, [left, right])

    def _apply_condition(self, batch, bmap, probe_idx, build_idx, probe_on_left,
                         cond_ev):
        """Filter matching pairs by the extra condition; returns the
        surviving (probe_idx, build_idx, counts-per-probe-row)."""
        n = batch.num_rows
        if cond_ev is None or len(probe_idx) == 0:
            counts = np.bincount(probe_idx, minlength=n) if len(probe_idx) else \
                np.zeros(n, dtype=np.int64)
            return probe_idx, build_idx, counts
        probe_out = batch.take(probe_idx)
        build_out = bmap.batch.take(build_idx)
        left, right = ((probe_out, build_out) if probe_on_left
                       else (build_out, probe_out))
        pair = ColumnarBatch(self._pair_schema, left.columns + right.columns,
                             len(probe_idx))
        keep = np.asarray(cond_ev.evaluate_predicate(pair))[: len(probe_idx)]
        probe_idx = probe_idx[keep]
        build_idx = build_idx[keep]
        counts = np.bincount(probe_idx, minlength=n) if len(probe_idx) else \
            np.zeros(n, dtype=np.int64)
        return probe_idx, build_idx, counts

    # -- orientation helpers --------------------------------------------------

    @property
    def _build_is_left(self) -> bool:
        return self.build_side == JoinSide.LEFT

    def _probe_child(self) -> int:
        return 1 if self._build_is_left else 0

    def _build_child(self) -> int:
        return 0 if self._build_is_left else 1

    def _key_exprs(self, for_build: bool) -> List[E.Expr]:
        pairs = self.on
        if for_build:
            return [l if self._build_is_left else r for l, r in pairs]
        return [r if self._build_is_left else l for l, r in pairs]

    # -- build ----------------------------------------------------------------

    def _load_build_map(self, partition, ctx, metrics) -> JoinHashMap:
        raise NotImplementedError

    def _build_from_child(self, partition, ctx, metrics) -> JoinHashMap:
        child = self._build_child()
        with metrics.timer("build_time_ns"):
            batches = list(self.execute_child(child, partition, ctx, metrics))
            return JoinHashMap.build(batches, self._key_exprs(for_build=True),
                                     self.children[child].schema, metrics)

    # -- probe ----------------------------------------------------------------

    def _execute(self, partition, ctx, metrics):
        bmap = self._load_build_map(partition, ctx, metrics)
        yield from self._probe_with_map(bmap, partition, ctx, metrics)

    def _probe_with_map(self, bmap: JoinHashMap, partition, ctx, metrics):
        jt = self.join_type
        probe_child = self._probe_child()
        probe_schema = self.children[probe_child].schema
        key_exprs = self._key_exprs(for_build=False)
        probe_on_left = probe_child == 0

        # which side's unmatched rows must be emitted?
        emit_unmatched_probe = (
            (jt == JoinType.FULL)
            or (jt == JoinType.LEFT and probe_on_left)
            or (jt == JoinType.RIGHT and not probe_on_left)
        )
        emit_unmatched_build = (
            (jt == JoinType.FULL)
            or (jt == JoinType.LEFT and not probe_on_left)
            or (jt == JoinType.RIGHT and probe_on_left)
        )
        semi_anti_exist = jt in (JoinType.LEFT_SEMI, JoinType.LEFT_ANTI,
                                 JoinType.RIGHT_SEMI, JoinType.RIGHT_ANTI,
                                 JoinType.EXISTENCE)

        track_build_matched = emit_unmatched_build or (
            semi_anti_exist and not self._semi_side_is_probe())

        key_ev = ExprEvaluator(key_exprs, probe_schema, metrics)
        cond_ev = ExprEvaluator([self.condition], self._pair_schema, metrics) \
            if self.condition is not None else None
        inner_fast_ok = (
            jt == JoinType.INNER and cond_ev is None
            and not track_build_matched and bmap.unique_single_key)
        for batch in self.execute_child(probe_child, partition, ctx, metrics):
            with metrics.timer("probe_time_ns"):
                cols = key_ev.evaluate(batch)
                if inner_fast_ok:
                    out = self._inner_fast(batch, bmap, cols, probe_on_left,
                                           metrics)
                    if out is not NotImplemented:
                        if out is not None and out.num_rows:
                            yield out
                        continue
                metrics.add("join_generic_batches", 1)
                codes, on_device = bmap.probe_codes(batch, cols)
                if on_device:
                    metrics.add("device_probe_batches", 1)
                probe_idx, build_idx, _ = bmap.probe(codes)
                probe_idx, build_idx, counts = self._apply_condition(
                    batch, bmap, probe_idx, build_idx, probe_on_left, cond_ev)
                if track_build_matched and len(build_idx):
                    bmap.matched[build_idx] = True
                out = self._emit_probe_batch(
                    batch, bmap, probe_idx, build_idx, counts,
                    emit_unmatched_probe, probe_on_left, jt)
            if out is not None and out.num_rows:
                yield out

        # post-pass: unmatched build rows (right/left-opposite/full, or
        # semi/anti/existence where the kept side was built)
        with metrics.timer("finish_time_ns"):
            tail = self._emit_build_tail(bmap, probe_on_left, jt,
                                         emit_unmatched_build)
        if tail is not None and tail.num_rows:
            yield tail

    def _inner_fast(self, batch, bmap, cols, probe_on_left, metrics):
        """Fused one-dispatch device inner join (unique-single-key build
        map). NotImplemented = not eligible for THIS batch (host columns):
        caller falls through to the generic probe. A coded var-width column
        on either side is eligible: its int32 code plane and its validity
        ride through the one matrix gather like any payload plane, and the
        output column keeps the side's dictionary by reference."""
        from blaze_tpu.core.batch import CodedColumn, DeviceColumn, has_planes

        if not (len(cols) == 1 and isinstance(cols[0], DeviceColumn)):
            return NotImplemented
        if not all(has_planes(c) for c in batch.columns):
            return NotImplemented
        bb = bmap.batch
        if not all(has_planes(c) for c in bb.columns):
            return NotImplemented
        import jax.numpy as jnp

        from blaze_tpu.utils.device import wait_int

        if bmap._dev_cell[0] is None:
            bmap._dev_cell[0] = jnp.asarray(
                bmap.sorted_keys if len(bmap.sorted_keys)
                else np.zeros(1, np.int64))
        kernel = _inner_fast_kernel(len(batch.columns), len(bmap.sorted_keys))
        flat = []
        for c in batch.columns:
            flat += [c.data, c.validity]
        for c in bb.columns:
            flat += [c.data, c.validity]
        outs = kernel(bmap._dev_cell[0], jnp.int64(batch.num_rows),
                      cols[0].data, cols[0].validity, *flat)
        count = wait_int(outs[0], "bhj_probe")  # sync point
        metrics.add("device_inner_batches", 1)
        # The probe itself ran on device inside the fused kernel; count it
        # under device_probe_batches too so the metric stays meaningful for
        # callers that only check whether probing happened on device.
        metrics.add("device_probe_batches", 1)
        if any(isinstance(c, CodedColumn)
               for c in (*batch.columns, *bb.columns)):
            metrics.add("coded_key_batches", 1)
        if count == 0:
            return None
        probe_cols = [c.like(outs[1 + 2 * i], outs[2 + 2 * i])
                      for i, c in enumerate(batch.columns)]
        off = 1 + 2 * len(batch.columns)
        build_cols = [c.like(outs[off + 2 * i], outs[off + 1 + 2 * i])
                      for i, c in enumerate(bb.columns)]
        left, right = ((probe_cols, build_cols) if probe_on_left
                       else (build_cols, probe_cols))
        return ColumnarBatch(self.schema, left + right, count)

    def _semi_side_is_probe(self) -> bool:
        jt = self.join_type
        if jt in (JoinType.LEFT_SEMI, JoinType.LEFT_ANTI, JoinType.EXISTENCE):
            return self._probe_child() == 0
        if jt in (JoinType.RIGHT_SEMI, JoinType.RIGHT_ANTI):
            return self._probe_child() == 1
        return False

    def _emit_probe_batch(self, batch, bmap, probe_idx, build_idx, counts,
                          emit_unmatched_probe, probe_on_left, jt):
        n = batch.num_rows
        matched_mask = counts > 0
        if jt == JoinType.EXISTENCE:
            if not self._semi_side_is_probe():
                return None
            from blaze_tpu.core.batch import DeviceColumn

            exists = DeviceColumn.from_numpy(T.BOOL, matched_mask, None, batch.capacity)
            return ColumnarBatch(self.schema, batch.columns + [exists], n)
        if jt in (JoinType.LEFT_SEMI, JoinType.RIGHT_SEMI):
            if not self._semi_side_is_probe():
                return None
            keep = np.nonzero(matched_mask)[0]
            return batch.take(keep) if len(keep) else None
        if jt in (JoinType.LEFT_ANTI, JoinType.RIGHT_ANTI):
            if not self._semi_side_is_probe():
                return None
            keep = np.nonzero(~matched_mask)[0]
            return batch.take(keep) if len(keep) else None

        # inner / outer: expand pairs
        if emit_unmatched_probe:
            un = np.nonzero(~matched_mask)[0]
            probe_idx = np.concatenate([probe_idx, un])
            build_idx = np.concatenate([build_idx, np.full(len(un), -1, np.int64)])
        if len(probe_idx) == 0:
            return None
        probe_out = batch.take(probe_idx)
        build_out = bmap.batch.take_nullable(build_idx)
        left, right = (build_out, probe_out) if not probe_on_left else (probe_out, build_out)
        return ColumnarBatch(self.schema, left.columns + right.columns,
                             len(probe_idx))

    def _emit_build_tail(self, bmap, probe_on_left, jt, emit_unmatched_build):
        build_n = bmap.batch.num_rows
        if build_n == 0:
            return None
        if jt in (JoinType.LEFT_SEMI, JoinType.RIGHT_SEMI) and not self._semi_side_is_probe():
            keep = np.nonzero(bmap.matched)[0]
            return bmap.batch.take(keep) if len(keep) else None
        if jt in (JoinType.LEFT_ANTI, JoinType.RIGHT_ANTI) and not self._semi_side_is_probe():
            keep = np.nonzero(~bmap.matched)[0]
            return bmap.batch.take(keep) if len(keep) else None
        if jt == JoinType.EXISTENCE and not self._semi_side_is_probe():
            from blaze_tpu.core.batch import DeviceColumn

            exists = DeviceColumn.from_numpy(T.BOOL, bmap.matched, None,
                                             bmap.batch.capacity)
            return ColumnarBatch(self.schema, bmap.batch.columns + [exists],
                                 build_n)
        if not emit_unmatched_build:
            return None
        un = np.nonzero(~bmap.matched)[0]
        if len(un) == 0:
            return None
        build_out = bmap.batch.take(un)
        probe_schema = self.children[self._probe_child()].schema
        probe_nulls = ColumnarBatch.empty(probe_schema).take_nullable(
            np.full(len(un), -1, np.int64))
        left, right = ((build_out, probe_nulls) if not probe_on_left
                       else (probe_nulls, build_out))
        return ColumnarBatch(self.schema, left.columns + right.columns, len(un))


class HashJoinExec(_HashJoinBase):
    """Shuffled hash join: build side read within this partition. When the
    build side turns out too large for an in-memory map, execution falls
    back to a sort-merge join over the same children (reference:
    SMJ_FALLBACK_* conf, AuronConverters.scala:522-557 — there the planner
    decides; here the runtime measures the actual build)."""

    def __init__(self, left, right, on, join_type, build_side=JoinSide.RIGHT,
                 condition=None):
        super().__init__(left, right, on, join_type, build_side, condition)

    def num_partitions(self):
        return self.children[self._probe_child()].num_partitions()

    def _load_build_map(self, partition, ctx, metrics):
        return self._build_from_child(partition, ctx, metrics)

    def _execute(self, partition, ctx, metrics):
        if ctx.conf.smj_fallback_enable:
            build_child = self.children[self._build_child()]
            batches = []
            rows = 0
            nbytes = 0
            too_big = False
            it = build_child.execute(partition, ctx,
                                     metrics.child(self._build_child()))
            for b in it:
                batches.append(b)
                rows += b.num_rows
                nbytes += b.nbytes()
                if rows > ctx.conf.smj_fallback_rows_threshold or \
                        nbytes > ctx.conf.smj_fallback_mem_size_threshold:
                    too_big = True
                    break
            if too_big:
                metrics.add("smj_fallback", 1)
                yield from self._fallback_smj(partition, ctx, metrics,
                                              batches, it)
                return
            bmap = JoinHashMap.build(batches, self._key_exprs(for_build=True),
                                     build_child.schema, metrics)
            yield from self._probe_with_map(bmap, partition, ctx, metrics)
            return
        yield from super()._execute(partition, ctx, metrics)

    def _fallback_smj(self, partition, ctx, metrics, staged, build_rest):
        """Re-plan this partition as sort + SMJ; the already-read build
        batches replay ahead of the remaining stream."""
        from blaze_tpu.ops.basic import MemoryScanExec
        from blaze_tpu.ops.joins.smj import SortMergeJoinExec
        from blaze_tpu.ops.sort import SortExec

        build_i = self._build_child()
        probe_i = self._probe_child()

        class _Replay(MemoryScanExec):
            def __init__(self, schema):
                super().__init__(schema, [[]])

            def _execute(self, p, c, m):
                yield from staged
                yield from build_rest

        build_src = _Replay(self.children[build_i].schema)
        sides = [None, None]
        sides[build_i] = SortExec(build_src,
                                  [E.SortOrder(e) for e in self._key_exprs(True)])
        sides[probe_i] = SortExec(self.children[probe_i],
                                  [E.SortOrder(e) for e in self._key_exprs(False)])
        smj = SortMergeJoinExec(sides[0], sides[1], self.on, self.join_type,
                                condition=self.condition)
        # the probe child must execute at `partition`; the replayed build is
        # partition-agnostic
        yield from smj._execute(partition, ctx, metrics)


class BroadcastJoinExec(_HashJoinBase):
    """Join against a broadcast build side; the built map is cached at
    executor scope under ``cached_build_hash_map_id``."""

    def __init__(self, left, right, on, join_type,
                 broadcast_side=JoinSide.RIGHT, cached_build_hash_map_id="",
                 condition=None):
        super().__init__(left, right, on, join_type, broadcast_side, condition)
        self.cached_build_hash_map_id = cached_build_hash_map_id

    def num_partitions(self):
        return self.children[self._probe_child()].num_partitions()

    def _load_build_map(self, partition, ctx, metrics):
        cache_id = self.cached_build_hash_map_id
        if not cache_id:
            # broadcast side is single-partition regardless of the probe
            # partition being executed
            return self._build_from_child(0, ctx, metrics)
        with _BUILD_CACHE_LOCK:
            cached = _BUILD_CACHE.get(cache_id)
        if cached is not None:
            # per-task matched flags: outer joins over a shared map must not
            # leak matches across tasks of different partitions
            m = JoinHashMap(cached.batch, cached.key_map, cached.offsets,
                            cached.schema, cached.sorted_keys)
            m._dev_cell = cached._dev_cell  # share the device-side upload
            return m
        built = self._build_from_child(0, ctx, metrics)
        with _BUILD_CACHE_LOCK:
            _BUILD_CACHE.setdefault(cache_id, built)
        m = JoinHashMap(built.batch, built.key_map, built.offsets,
                        built.schema, built.sorted_keys)
        m._dev_cell = built._dev_cell
        return m


class BroadcastJoinBuildHashMapExec(Operator):
    """Materializes a JoinHashMap from its input and emits it as a single
    binary row (reference: broadcast_join_build_hash_map_exec.rs — the
    executor-side build step between the broadcast read and the join)."""

    SCHEMA = T.Schema.of(("hash_map", T.BINARY, False))

    def __init__(self, child: Operator, keys: List[E.Expr]):
        self.keys = keys
        super().__init__(self.SCHEMA, [child])

    def _execute(self, partition, ctx, metrics):
        batches = list(self.execute_child(0, partition, ctx, metrics))
        with metrics.timer("build_time_ns"):
            m = JoinHashMap.build(batches, self.keys, self.children[0].schema,
                                  metrics)
            blob = m.serialize()
        yield ColumnarBatch.from_pydict({"hash_map": [blob]}, self.SCHEMA)
