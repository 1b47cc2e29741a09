"""External sort: device lexicographic sort + spilled-run merge, with TopK.

Reference: ``sort_exec.rs:88-1608`` — in-memory row-key blocks, loser-tree
k-way merge of squeezed spill blocks, key pruning, optional fetch limit
(TopK), and the ``execute_with_key_rows`` fast path shared with SMJ.

TPU design: a partition that fits the memory budget is concatenated and
sorted as ONE run on the device over normalized key operands
(ops/sort_keys.py): one key by ``jax.lax.sort`` with an index payload
(``jit(sort)``), several keys by ``jit(sort_order)`` — dense ranks of every
key packed into one word, two-operand sorts only, because one sort over
2k + 1 operands compiles for minutes on the chip (f64 keys, which do not
pack, keep it). The permutation is applied to the batch's device planes by
``jit(sort_take)`` — it never visits the host; only a batch that also
carries host (var-width) columns pulls it for them (``sync:sort_indices``).
A sort-merge join above takes that run whole (``whole_runs``); every other
consumer gets it in batches of ``batch_size``. Runs that exceed the budget
spill as compressed batch streams with their key columns appended (pulled
for the file: ``sync:sort_spill_keys``), and the final pass k-way-merges the
spilled runs on host. Sorts whose keys include var-width columns run on
host via arrow sort_indices.
"""

from __future__ import annotations

import functools
import heapq
from typing import Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from blaze_tpu.config import get_config
from blaze_tpu.core import kernels as K
from blaze_tpu.core.batch import ColumnarBatch, DeviceColumn
from blaze_tpu.ir import exprs as E
from blaze_tpu.ir import types as T
from blaze_tpu.ops import sort_keys as SK
from blaze_tpu.ops.base import ExecContext, Operator
from blaze_tpu.runtime.memmgr import MemConsumer, SpillFile
from blaze_tpu.utils.device import wait_array


def sort_batch(batch: ColumnarBatch, sort_orders: List[E.SortOrder],
               limit: Optional[int] = None) -> ColumnarBatch:
    """Sort one batch fully (device path when possible)."""
    if batch.num_rows <= 1:
        return batch
    n_out = batch.num_rows if limit is None else min(limit, batch.num_rows)
    if SK.device_sortable(batch, sort_orders):
        return _take_sorted(batch, SK.key_operands(batch, sort_orders),
                            n_out)[0]
    return batch.take(SK.host_sort_indices(batch, sort_orders)[:n_out])


@jax.jit
def sort_order(operands):
    """The permutation that sorts by SEVERAL keys' operands [rank0, val0,
    rank1, val1, ...]: ``lax.sort`` over all of them at once compiles for
    minutes on the chip, `K.lex_order_traced` gives the same order from
    two-operand sorts. A key's rank is its class (2, the rows ordered by
    value, is class 0; every other rank's rows carry value 0)."""
    columns = [(SK.orderable_word_traced(val), rank.astype(jnp.int8) - 2)
               for rank, val in zip(operands[::2], operands[1::2])]
    return K.lex_order_traced(columns)[0]


def _device_sort_indices(operands: List[jnp.ndarray], capacity: int) -> jnp.ndarray:
    if len(operands) > 2 and all(SK.packs_to_word(val.dtype)
                                 for val in operands[1::2]):
        return K._dispatch(sort_order, tuple(operands))
    iota = jnp.arange(capacity, dtype=jnp.int32)
    sorted_ops = jax.lax.sort(tuple(operands) + (iota,), num_keys=len(operands))
    return sorted_ops[-1]


@functools.partial(jax.jit, static_argnames=("out_cap",))
def sort_take(order, datas, valids, n_out, out_cap):
    """The first ``n_out`` rows of every plane in the order of the device
    permutation ``order``, at ``out_cap``: the ONE gather that applies a
    sort, all planes side by side (`K.take_rows_traced`)."""
    live = jnp.arange(out_cap, dtype=jnp.int32) < n_out
    with jax.named_scope("move"):
        return K.take_rows_traced(datas, valids, order[:out_cap], live)


def _take_sorted(batch: ColumnarBatch, operands, n_out: int):
    """``batch``'s first ``n_out`` rows in the operands' order, and the
    permutation, which stays on the device: ``jit(sort)`` or
    ``jit(sort_order)``, then ``jit(sort_take)``. Host columns alone need it
    on the host."""
    slots = batch._device_slots()
    out_cap = min(get_config().capacity_for(n_out), batch.capacity)
    order = _device_sort_indices(operands, batch.capacity)
    datas, valids = K._dispatch(
        sort_take, order,
        tuple(batch.columns[i].data for i in slots),
        tuple(batch.columns[i].validity for i in slots),
        np.int32(n_out), out_cap=out_cap)
    cols = list(batch.columns)
    for k, i in enumerate(slots):
        cols[i] = cols[i].like(datas[k], valids[k])
    if len(slots) < len(cols):
        indices = wait_array(order, "sort_indices")[:n_out].astype(np.int64)
        for i, c in enumerate(cols):
            if i not in slots:
                cols[i] = c.take_host(indices)
    return ColumnarBatch(batch.schema, cols, n_out), order


class SortExec(Operator):
    # a coded var-width column moves with the device planes, and as a KEY it
    # orders by its dictionary's rank plane (ops/sort_keys.coded_rank_plane)
    takes_coded = True

    def __init__(self, child: Operator, sort_orders: List[E.SortOrder],
                 fetch_limit: Optional[int] = None):
        self.sort_orders = sort_orders
        self.fetch_limit = fetch_limit
        # set by a consumer that works on the partition's whole sorted run
        # (the sort-merge join): an unspilled run is handed over as ONE
        # batch, not sliced into batches the consumer would concatenate
        self.whole_runs = False
        super().__init__(child.schema, [child])

    def _execute(self, partition, ctx, metrics):
        if self.fetch_limit is not None and self.fetch_limit <= 100_000:
            yield from self._execute_topk(partition, ctx, metrics)
            return
        yield from self._execute_full(partition, ctx, metrics)

    # -- TopK path (reference: sort with fetch) -------------------------------

    def _execute_topk(self, partition, ctx, metrics):
        k = self.fetch_limit
        current: Optional[ColumnarBatch] = None
        staged: List[ColumnarBatch] = []
        staged_rows = 0
        for batch in self.execute_child(0, partition, ctx, metrics):
            staged.append(batch)
            staged_rows += batch.num_rows
            if staged_rows >= max(4 * k, ctx.conf.batch_size):
                current = self._merge_topk(current, staged, k, metrics)
                staged, staged_rows = [], 0
        if staged:
            current = self._merge_topk(current, staged, k, metrics)
        if current is not None and current.num_rows > 0:
            yield current

    def _merge_topk(self, current, staged, k, metrics):
        # self-time lands in elapsed_compute_time_ns via Operator.execute
        parts = ([current] if current is not None else []) + staged
        merged = ColumnarBatch.concat(parts, self.schema, metrics)
        return sort_batch(merged, self.sort_orders, limit=k)

    # -- full sort with spill -------------------------------------------------

    def _execute_full(self, partition, ctx, metrics):
        device = SK.supports_device_sort(self.children[0].schema, self.sort_orders)
        state = _SortState(self, ctx, metrics, device)
        ctx.mem.register(state)
        try:
            for batch in self.execute_child(0, partition, ctx, metrics):
                state.insert(batch)
            yield from state.output()
        finally:
            ctx.mem.unregister(state)
            state.release()


class _SortState(MemConsumer):
    def __init__(self, op: SortExec, ctx: ExecContext, metrics, device: bool):
        super().__init__("SortExec", spillable=True)
        self.op = op
        self.ctx = ctx
        self.metrics = metrics
        self.device = device
        self.staged: List[ColumnarBatch] = []
        self.staged_bytes = 0
        self.runs: List[SpillFile] = []

    def insert(self, batch: ColumnarBatch):
        self.staged.append(batch)
        self.staged_bytes += batch.nbytes()
        self.update_mem_used(self.staged_bytes)

    def spill(self) -> int:
        if not self.staged:
            return 0
        freed = self.staged_bytes
        if self.device:
            # squeeze normalized keys into the spilled run so the merge
            # phase never re-evaluates sort keys (reference: squeezed key
            # blocks in sort_exec.rs); the packed matrix derives from the
            # very operands the run was sorted with (one expression
            # evaluation per run, zero re-derivation at merge time); u64
            # keys store order-preserving as i64 via a sign-bit flip
            # (host-side numpy — no device bitcasts)
            run, keys = self._sorted_run_with_keys()
            run = _append_key_columns(run, keys)
        else:
            run = self._sorted_run()
        spill = SpillFile("sort")
        with self.metrics.timer("spill_io_time_ns"):
            spill.writer.write_batch(run)
            spill.finish_write()
        self.metrics.add("spilled_bytes", spill.size)
        self.metrics.add("spill_count", 1)
        self.runs.append(spill)
        self.staged, self.staged_bytes = [], 0
        return freed

    def _sorted_run(self) -> ColumnarBatch:
        merged = ColumnarBatch.concat(self.staged, self.op.schema,
                                      self.metrics)
        return sort_batch(merged, self.op.sort_orders)

    def _sorted_run_with_keys(self) -> Tuple[ColumnarBatch, np.ndarray]:
        """Sorted run + its (n, 2k) uint64 merge-key matrix, computed from
        one operand kernel dispatch (device key path only)."""
        merged = ColumnarBatch.concat(self.staged, self.op.schema,
                                      self.metrics)
        operands = SK.key_operands(merged, self.op.sort_orders)
        if merged.num_rows <= 1:
            idx = np.arange(merged.num_rows, dtype=np.int64)
            return merged, SK.operands_merge_matrix(operands, idx)
        run, order = _take_sorted(merged, operands, merged.num_rows)
        idx = wait_array(order, "sort_spill_keys")[: merged.num_rows]
        return run, SK.operands_merge_matrix(operands, idx.astype(np.int64))

    def output(self) -> Iterator[ColumnarBatch]:
        batch_size = self.ctx.conf.batch_size
        if not self.runs:
            if not self.staged:
                return
            merged = self._sorted_run()
            if self.op.whole_runs:
                yield merged
                return
            for off in range(0, merged.num_rows, batch_size):
                yield merged.slice(off, batch_size)
            return
        if self.staged:
            self.spill()
        yield from self._merge_runs(batch_size)

    def _merge_runs(self, batch_size: int):
        """K-way merge of sorted spilled runs (reference: loser-tree merge).
        The vectorized chunk merge over squeezed (n, 2k) i64 key matrices is
        THE merge path for device-sortable keys (numpy lexsort over
        safe-to-emit prefixes; the per-row heap walk it replaced was ~1000x
        slower at 10M-row volume on the CPU). Only var-width (host-compared)
        keys fall back to the row heap."""
        if self.device:
            yield from self._merge_runs_vectorized(batch_size)
        else:
            yield from self._merge_runs_heap(batch_size)

    def _merge_runs_heap(self, batch_size: int):
        """Fallback per-row heap merge for var-width keys (python-comparable
        key tuples; no u64 normalization exists for these)."""
        cursors = []
        for rid, run in enumerate(self.runs):
            it = iter(run.read_batches())
            cur = _RunCursor(rid, it, self.op.sort_orders)
            if cur.advance_batch():
                cursors.append(cur)
        heap = [(c.key(), c.rid, c) for c in cursors]
        heapq.heapify(heap)
        out_parts: List[ColumnarBatch] = []
        pending: List[int] = []

        def flush_pending(cur):
            nonlocal pending
            if pending:
                out_parts.append(cur.batch.take(np.array(pending, dtype=np.int64)))
                pending = []

        while heap:
            _, _, cur = heapq.heappop(heap)
            pending.append(cur.pos)
            # drain any rows from this run that stay the minimum
            while True:
                if not cur.step():
                    flush_pending(cur)
                    if not cur.advance_batch():
                        break
                    heapq.heappush(heap, (cur.key(), cur.rid, cur))
                    break
                if heap and (cur.key(), cur.rid) > heap[0][:2]:
                    flush_pending(cur)
                    heapq.heappush(heap, (cur.key(), cur.rid, cur))
                    break
                pending.append(cur.pos)
            total = sum(b.num_rows for b in out_parts)
            if total >= batch_size:
                yield ColumnarBatch.concat(out_parts, self.op.schema)
                out_parts = []
        if out_parts:
            yield ColumnarBatch.concat(out_parts, self.op.schema)

    def _merge_runs_vectorized(self, batch_size: int):
        """Chunked vectorized merge: every iteration emits, in one lexsort,
        all rows whose key is <= the smallest last-key among the runs'
        CURRENT batches (later batches of any run start at or above their
        run's current last key, so those rows cannot interleave). At least
        the minimum run's whole batch drains per iteration — N log K work,
        all numpy."""
        cursors = []
        for rid, run in enumerate(self.runs):
            it = iter(run.read_batches())
            cur = _VecCursor(rid, it, self.op.sort_orders)
            if cur.advance_batch():
                cursors.append(cur)
        carry: List[ColumnarBatch] = []
        carry_rows = 0
        while cursors:
            bound = min(tuple(c.keys[-1]) for c in cursors)
            parts = []
            key_parts = []
            rid_parts = []
            for c in cursors:
                n = _prefix_le(c.keys, c.off, bound)
                if n > c.off:
                    idx = np.arange(c.off, n, dtype=np.int64)
                    parts.append(c.batch.take(idx))
                    key_parts.append(c.keys[c.off:n])
                    rid_parts.append(np.full(n - c.off, c.rid, np.int64))
                    c.off = n
            nxt = []
            for c in cursors:
                if c.off < len(c.keys) or c.advance_batch():
                    nxt.append(c)
            cursors = nxt
            if not parts:
                continue
            keys = np.concatenate(key_parts)
            rids = np.concatenate(rid_parts)
            chunk = ColumnarBatch.concat(parts, self.op.schema)
            # lexsort: primary = first key column (last in the sequence);
            # run id breaks exact ties for stable run order
            order = np.lexsort((rids,) + tuple(
                keys[:, j] for j in reversed(range(keys.shape[1]))))
            chunk = chunk.take(order)
            carry.append(chunk)
            carry_rows += chunk.num_rows
            if carry_rows >= batch_size:
                merged = ColumnarBatch.concat(carry, self.op.schema) \
                    if len(carry) > 1 else carry[0]
                for off in range(0, merged.num_rows, batch_size):
                    yield merged.slice(off, batch_size)
                carry, carry_rows = [], 0
        if carry:
            merged = ColumnarBatch.concat(carry, self.op.schema) \
                if len(carry) > 1 else carry[0]
            for off in range(0, merged.num_rows, batch_size):
                yield merged.slice(off, batch_size)

    def release(self):
        for r in self.runs:
            r.release()
        self.runs = []
        self.staged = []


def _prefix_le(keys: np.ndarray, off: int, bound: tuple) -> int:
    """Index (absolute) of the first row AFTER ``off`` whose key exceeds
    ``bound`` — rows are sorted, so <=-bound rows form a prefix."""
    sub = keys[off:]
    lt = np.zeros(len(sub), dtype=bool)
    eq = np.ones(len(sub), dtype=bool)
    for j in range(keys.shape[1]):
        c = sub[:, j]
        b = bound[j]
        lt |= eq & (c < b)
        eq &= c == b
    mask = lt | eq
    # prefix property: count of True == first False index
    return off + int(mask.sum())


class _VecCursor:
    __slots__ = ("rid", "it", "orders", "batch", "keys", "off")

    def __init__(self, rid, it, orders):
        self.rid = rid
        self.it = it
        self.orders = orders
        self.batch = None
        self.keys = None
        self.off = 0

    def advance_batch(self) -> bool:
        for b in self.it:
            if b.num_rows == 0:
                continue
            self.batch, keys = _strip_key_columns(b)
            if keys is None:  # legacy run without squeezed keys
                keys = (SK.merge_keys_matrix(self.batch, self.orders)
                        ^ np.uint64(1 << 63)).view(np.int64)
            self.keys = keys
            self.off = 0
            return True
        return False


_KEY_PREFIX = "#sortkey"


def _append_key_columns(run: ColumnarBatch, keys_u64: np.ndarray) -> ColumnarBatch:
    """Attach the (n, 2k) uint64 merge-key matrix as i64 columns."""
    from blaze_tpu.core.batch import DeviceColumn

    n = run.num_rows
    fields = list(run.schema.fields)
    cols = list(run.columns)
    flipped = (keys_u64 ^ np.uint64(1 << 63)).view(np.int64)
    for i in range(keys_u64.shape[1]):
        fields.append(T.StructField(f"{_KEY_PREFIX}{i}", T.I64, False))
        cols.append(DeviceColumn.from_numpy(T.I64, flipped[:, i], None, run.capacity))
    return ColumnarBatch(T.Schema(tuple(fields)), cols, n)


def _strip_key_columns(batch: ColumnarBatch):
    """Split a spilled run into (data batch, key matrix as flipped i64) —
    key tuples compare identically to the unflipped u64 ordering."""
    base = [i for i, f in enumerate(batch.schema.fields)
            if not f.name.startswith(_KEY_PREFIX)]
    keyi = [i for i, f in enumerate(batch.schema.fields)
            if f.name.startswith(_KEY_PREFIX)]
    if not keyi:
        return batch, None
    n = batch.num_rows
    from blaze_tpu.utils.device import pull_columns

    pulled = pull_columns([batch.columns[i] for i in keyi], n)
    keys = np.stack([p[0] for p in pulled], axis=1)
    return batch.select(base), keys


class _RunCursor:
    """Host-key cursor for the heap merge fallback (var-width keys only —
    device-sortable keys always ride _VecCursor)."""

    __slots__ = ("rid", "it", "orders", "batch", "keys", "pos")

    def __init__(self, rid, it, orders):
        self.rid = rid
        self.it = it
        self.orders = orders
        self.batch = None
        self.keys = None
        self.pos = 0

    def advance_batch(self) -> bool:
        for b in self.it:
            if b.num_rows == 0:
                continue
            self.batch = b
            self.keys = SK.host_keys_matrix(b, self.orders)
            self.pos = 0
            return True
        return False

    def key(self):
        return self.keys[self.pos]

    def step(self) -> bool:
        self.pos += 1
        return self.pos < self.batch.num_rows
