"""Row -> partition-id routing for shuffle writes.

Reference: ``datafusion-ext-plans/src/shuffle/mod.rs:56-279`` — murmur3
(seed 42) pmod for hash partitioning (bit-exact with Spark so routing
matches a JVM-side reducer), round-robin with retry-stable ordering, range
partitioning by binary-searching driver-sampled bounds, and the
single-partition collapse.
"""

from __future__ import annotations

import functools
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from blaze_tpu.core import kernels as K
from blaze_tpu.core.batch import (ColumnarBatch, DeviceColumn, HostBatch,
                                  RowWindow, has_planes)
from blaze_tpu.exprs import spark_hash as SH
from blaze_tpu.exprs.compiler import ExprEvaluator
from blaze_tpu.exprs.spark_hash import hash_batch
from blaze_tpu.ir import exprs as E
from blaze_tpu.ir import nodes as N
from blaze_tpu.ir import types as T
from blaze_tpu.ops import sort_keys as SK
from blaze_tpu.utils.device import wait_array


def _pmod(h, n: int):
    """Spark's pmod of the int32 murmur3 hashes by the partition count."""
    r = lax.rem(lax.bitcast_convert_type(h, jnp.int32), jnp.int32(n))
    return jnp.where(r < 0, r + jnp.int32(n), r)


@functools.partial(jax.jit, static_argnames=("how", "n"))
def exchange_route(keys, datas, valids, num_rows, how, n):
    """One batch routed to ``n`` partitions in ONE device program: the rows'
    partition ids, a stable order by them, every (data, validity) plane moved
    into that order by one matrix gather, and the ``n + 1`` partition
    offsets — all the host reads back. Nothing else crosses the link: no
    hash, order or index plane.

    ``how`` (static) says where the partition ids come from, ``keys`` holds
    its operands:

    - ``("hash", kinds)``: Spark's murmur3 (seed 42) of the key planes
      ``keys = (key_datas, key_valids)``, pmod ``n`` — bit for bit
      ``HashPartitioner.partition_ids_host``;
    - ``("range", spec)``: bounds <= the row's sort key, ``keys =
      (key_datas, key_valids, bound_ops)`` (``core/kernels._range_pids``);
    - ``("round_robin",)``: ``keys`` is the first row's partition;
    - ``("pid",)``: ``keys`` is the int32 plane of ids the host computed
      (keys that are no device planes: var-width, coded, wide decimals).

    Padding rows go past the last partition. The order is one sort of one
    operand — the id packed above the row's index in a 32- or 64-bit word —
    so it is stable by construction, and a one-operand sort compiles in
    seconds where a multi-operand 64-bit one takes a minute an operand
    (PERF.md section 6). Returns ``(offsets, order, datas, valids)``: rows
    ``offsets[p]:offsets[p + 1]`` of the moved planes are partition ``p``'s,
    in the batch's row order; ``order`` is the row standing at each place
    (for the host columns of a batch that has some)."""
    cap = min(p.shape[0] for p in (*datas, *valids))
    iota = jnp.arange(cap, dtype=jnp.int32)
    exists = iota < num_rows
    with jax.named_scope("exchange_route"):
        if how[0] == "hash":
            h = jnp.full((cap,), 42, jnp.uint32)
            for d, v, kind in zip(keys[0], keys[1], how[1]):
                h = SH.murmur3_update_column(h, d[:cap], v[:cap], kind)
            pid = _pmod(h, n)
        elif how[0] == "range":
            key_datas, key_valids, bound_ops = keys
            pid = K._range_pids(tuple(d[:cap] for d in key_datas),
                                tuple(v[:cap] for v in key_valids), exists,
                                bound_ops, how[1])
        elif how[0] == "round_robin":
            pid = lax.rem(iota + keys, jnp.int32(n))
        else:
            pid = keys[:cap]
        pid = jnp.where(exists, pid, jnp.int32(n))
        bits = max(1, (cap - 1).bit_length())
        word = jnp.uint32 if (n + 1) << bits <= 1 << 32 else jnp.uint64
        packed = lax.sort((pid.astype(word) << bits) | iota.astype(word))
        order = (packed & word((1 << bits) - 1)).astype(jnp.int32)
        offsets = jnp.searchsorted(
            packed, jnp.arange(n + 1, dtype=word) << bits).astype(jnp.int32)
        out_d, out_v = K.take_rows_traced(datas, valids, order, exists)
    return offsets, order, out_d, out_v


class Repartitioner:
    def __init__(self, num_partitions: int):
        self.num_partitions = num_partitions
        # split counters, surfaced as operator metrics by the shuffle
        # writers: the hot-path invariant is ONE row gather per non-trivial
        # input batch (no per-partition take loop)
        self.split_batches = 0
        self.split_gathers = 0
        # time spent routing rows (hash + gather + slice), surfaced as
        # repartition_time_ns on the writer's metric node
        self.split_time_ns = 0

    def partition_ids(self, batch: ColumnarBatch) -> np.ndarray:
        """(num_rows,) int32 partition id per row."""
        raise NotImplementedError

    def partition_ids_host(self, host: HostBatch) -> Optional[np.ndarray]:
        """Partition ids straight from already-pulled host planes, at numpy
        speed with no device dispatch. None = no host path (caller falls
        back to ``partition_ids`` on the device batch)."""
        return None

    @staticmethod
    def _ranges_of(sorted_pids: np.ndarray):
        """[(pid, start, end), ...] contiguous runs of an ascending pid
        array."""
        n = len(sorted_pids)
        boundaries = np.nonzero(np.diff(sorted_pids))[0] + 1
        starts = np.concatenate([[0], boundaries])
        ends = np.concatenate([boundaries, [n]])
        return [(int(sorted_pids[s]), int(s), int(e))
                for s, e in zip(starts, ends)]

    def _split_ranges(self, pids: np.ndarray):
        """Stable pid-sort split: (order, [(pid, start, end), ...])."""
        order = np.argsort(pids, kind="stable")
        return order, self._ranges_of(pids[order])

    def _route(self, batch: ColumnarBatch):
        """``(how, keys)`` of :func:`exchange_route` for this batch. The
        default hands the program the ids ``partition_ids`` computes (keys
        that are no device planes); a partitioner whose keys are device
        planes names them and the program computes the ids itself."""
        pids = np.full(batch.capacity, self.num_partitions, dtype=np.int32)
        pids[:batch.num_rows] = self.partition_ids(batch)
        return ("pid",), jnp.asarray(pids)

    def bucketize(self, batch: ColumnarBatch):
        """Split a batch into per-partition device sub-batches, the device
        shuffle tier's staging form: the batch routed by :meth:`route`, a
        partition's rows then a window of the one moved batch
        (``RowWindow``: nothing more is dispatched, the reduce side's concat
        copies the windows); a batch with host columns is cut into slices
        instead. The rows of every partition keep the batch's order, as
        ``bucketize_host``'s do (reference: radix sort by pid in
        buffered_data.rs). Returns ``[(pid, RowWindow | ColumnarBatch)]``
        for the partitions that got rows."""
        n = batch.num_rows
        if n == 0:
            return []
        self.split_batches += 1
        if self.num_partitions == 1:
            return [(0, batch)]
        moved, offsets = self.route(batch)
        windows = all(has_planes(c) for c in moved.columns)
        return [(pid, moved if e - s == n
                 else RowWindow(moved, s, e - s) if windows
                 else moved.slice(s, e - s))
                for pid, (s, e) in enumerate(zip(offsets, offsets[1:]))
                if e > s]

    def route(self, batch: ColumnarBatch):
        """The batch ordered by partition where its planes are, and the
        ``n + 1`` partition offsets: ONE device program
        (:func:`exchange_route`: ids, stable order, one matrix gather,
        offsets) and ONE small wait for the offsets
        (``sync:exchange_route``); host columns follow the order the device
        found. Rows ``offsets[p]:offsets[p + 1]`` of the moved batch are
        partition ``p``'s, in the batch's row order."""
        import time

        n = batch.num_rows
        if self.num_partitions == 1:
            return batch, [0, n]
        t0 = time.perf_counter_ns()
        how, keys = self._route(batch)
        slots = batch._device_slots()
        offsets, order, datas, valids = K._dispatch(
            exchange_route, keys,
            tuple(batch.columns[i].data for i in slots),
            tuple(batch.columns[i].validity for i in slots),
            np.int32(n), how=how, n=self.num_partitions)
        self.split_gathers += 1
        offsets = wait_array(offsets, "exchange_route").tolist()
        cols = list(batch.columns)
        for k, i in enumerate(slots):
            cols[i] = cols[i].like(datas[k], valids[k])
        if len(slots) < len(cols):
            # host columns follow the order the device found
            host_order = wait_array(
                order, "exchange_host_order")[:n].astype(np.int64)
            for i, c in enumerate(cols):
                if not has_planes(c):
                    cols[i] = c.take_host(host_order)
        self.split_time_ns += time.perf_counter_ns() - t0
        return ColumnarBatch(batch.schema, cols, n), offsets

    def bucketize_host(self, batch: ColumnarBatch) -> List[Tuple[int, HostBatch]]:
        """Shuffle-write fast path: ONE device pull, then numpy-speed routing.
        The device never sees the per-partition sub-batches (they go straight
        to the serializer), so this replaces num_partitions device gathers +
        num_partitions pulls with a single transfer (reference: staged
        host-side radix sort by partition id, buffered_data.rs:88+)."""
        import time

        n = batch.num_rows
        if n == 0:
            return []
        self.split_batches += 1
        host = HostBatch.from_batch(batch)
        if self.num_partitions == 1:
            return [(0, host)]
        t0 = time.perf_counter_ns()
        pids = self.partition_ids_host(host)
        if pids is None:
            pids = self.partition_ids(batch)
        order, ranges = self._split_ranges(pids)
        self.split_gathers += 1
        gathered = host.take(order)
        out = [(pid, gathered.slice(s, e - s)) for pid, s, e in ranges]
        self.split_time_ns += time.perf_counter_ns() - t0
        return out


class SinglePartitioner(Repartitioner):
    def __init__(self):
        super().__init__(1)

    def partition_ids(self, batch):
        return np.zeros(batch.num_rows, dtype=np.int32)


class HashPartitioner(Repartitioner):
    """murmur3(seed 42) pmod n — Spark's HashPartitioning routing."""

    def __init__(self, exprs: List[E.Expr], num_partitions: int, schema):
        super().__init__(num_partitions)
        self.exprs = exprs
        self.ev = ExprEvaluator(exprs, schema)

    def partition_ids(self, batch):
        cols = self.ev.evaluate(batch)
        hashes = hash_batch(cols, batch.num_rows, batch.capacity, seed=42)
        n = np.int64(self.num_partitions)
        return (((hashes.astype(np.int64) % n) + n) % n).astype(np.int32)

    def _route(self, batch):
        """Key planes for the device program where every key is one: a
        plain column reference is read off the batch (the evaluator pays two
        eager dispatches a reference, PERF.md section 7), any other
        expression is evaluated in front of the program. A key that is no
        fixed-width device plane (var-width or coded: hashed by value on the
        host; a wide decimal: hashed by its bytes) takes the ids the host
        computes."""
        names = [f.name for f in batch.schema.fields]
        if all(isinstance(e, E.Column) and e.name in names
               for e in self.exprs):
            idx = [names.index(e.name) for e in self.exprs]
            cols = [batch.columns[i] for i in idx]
            dtypes = [batch.schema[i].dtype for i in idx]
        else:
            cols = self.ev.evaluate(batch)
            dtypes = [c.dtype for c in cols]
        if not all(isinstance(c, DeviceColumn) and not T.is_wide_decimal(dt)
                   for c, dt in zip(cols, dtypes)):
            return super()._route(batch)
        return (("hash", tuple(SH._dtype_kind(dt) for dt in dtypes)),
                (tuple(c.data for c in cols),
                 tuple(c.validity for c in cols)))

    def partition_ids_host(self, host):
        """Numpy murmur3 over plain-column integer keys of an already
        pulled batch (the shuffle-write staging path): bit-exact with the
        device kernel, no dispatch + pull round trip. A coded var-width key
        (staged as Arrow's dictionary array) hashes BY CODE: Spark's murmur3
        of the value, read from the dictionary in place with the row's
        running hash as seed (core/dictionary.murmur3_by_code) — no string
        is built. Non-column exprs, other arrow-resident columns, and float
        keys (NaN/-0.0 normalization lives in the device kernel) decline."""
        import pyarrow as pa

        from blaze_tpu.core import dictionary as D
        from blaze_tpu.exprs import spark_hash as SH
        from blaze_tpu.ir import types as T

        names = [f.name for f in host.schema.fields]
        h = np.full(host.num_rows, 42, dtype=np.uint32)
        for e in self.exprs:
            if not isinstance(e, E.Column) or e.name not in names:
                return None
            idx = names.index(e.name)
            it = host.items[idx]
            if not isinstance(it, tuple):
                if not (T.is_var_width(host.schema[idx].dtype)
                        and isinstance(it, pa.DictionaryArray)):
                    return None
                codes = it.indices
                valid = ~np.asarray(codes.is_null()) \
                    if codes.null_count else None
                h = D.murmur3_by_code(
                    it.dictionary,
                    codes.fill_null(0).to_numpy(zero_copy_only=False),
                    valid, h)
                continue
            kind = SH._dtype_kind(host.schema[idx].dtype)
            if kind not in ("i32", "i64"):
                return None
            data, valid = it
            new = (SH.murmur3_int64_np(data, h) if kind == "i64"
                   else SH.murmur3_int32_np(data, h))
            h = np.where(valid, new, h) if valid is not None else new
        n = np.int64(self.num_partitions)
        return (((h.view(np.int32).astype(np.int64) % n) + n) % n).astype(np.int32)


class RoundRobinPartitioner(Repartitioner):
    """Round robin with a deterministic start so retried map tasks produce
    identical partitions (reference: shuffle_writer_exec.rs:139-164 pre-sorts
    for full determinism; we keep a stable per-task row order)."""

    def __init__(self, num_partitions: int, start: int = 0):
        super().__init__(num_partitions)
        self.next_pid = start % max(num_partitions, 1)

    def partition_ids(self, batch):
        n = batch.num_rows
        pids = (np.arange(n, dtype=np.int64) + self.next_pid) % self.num_partitions
        self.next_pid = int((self.next_pid + n) % self.num_partitions)
        return pids.astype(np.int32)

    def partition_ids_host(self, host):
        return self.partition_ids(host)  # only reads num_rows

    def _route(self, batch):
        start = self.next_pid
        self.next_pid = int((start + batch.num_rows) % self.num_partitions)
        return ("round_robin",), np.int32(start)


class RangePartitioner(Repartitioner):
    """Binary search of sampled bounds over normalized sort keys
    (reference: shuffle/mod.rs:204-279; bounds arrive in the plan as rows of
    the sort-key schema, sampled driver-side).

    Two vectorized routing paths, both bisect_right over the same total
    order (the former per-row python ``bisect`` walk was the measured 10M-row
    sort bottleneck, ~4 s per 262k-row batch):

    - device batches: ``exchange_route`` normalizes keys, counts bounds <=
      key (``core/kernels._range_pids``), orders the rows by partition and
      moves them in ONE dispatch against device-resident bound operands;
    - host (staged) batches: numpy ``searchsorted`` over fixed-width packed
      big-endian key rows (ops/sort_keys.pack_key_rows).
    """

    def __init__(self, sort_orders: List[E.SortOrder], num_partitions: int,
                 bounds: List[tuple], schema):
        super().__init__(num_partitions)
        self.sort_orders = sort_orders
        self.schema = schema
        self.bounds = bounds
        self._ev = None
        self._dev_bounds = None
        self._packed_bounds = None

    # -- bounds, normalized once ------------------------------------------

    def _bounds_batch(self):
        from blaze_tpu.ir import types as T

        key_types = [E.infer_type(so.child, self.schema) for so in self.sort_orders]
        data = {f"k{i}": [b[i] for b in self.bounds] for i in range(len(key_types))}
        bschema = T.Schema.of(*[(f"k{i}", t) for i, t in enumerate(key_types)])
        bb = ColumnarBatch.from_pydict(data, bschema)
        orders = [E.SortOrder(E.Column(f"k{i}"), so.ascending, so.nulls_first)
                  for i, so in enumerate(self.sort_orders)]
        return bb, orders

    def _device_bounds(self):
        """Bound rows as device-resident operand planes, sliced to the true
        bound count (the staging batch pads to capacity)."""
        if self._dev_bounds is None:
            import jax.numpy as jnp

            bb, orders = self._bounds_batch()
            ops = SK.key_operands(bb, orders)
            nb = len(self.bounds)
            self._dev_bounds = tuple(jnp.asarray(np.asarray(o)[:nb]) for o in ops)
        return self._dev_bounds

    def _bounds_packed(self):
        """Bound rows as packed byte keys for numpy searchsorted."""
        if self._packed_bounds is None:
            bb, orders = self._bounds_batch()
            self._packed_bounds = SK.pack_key_rows(SK.merge_keys_matrix(bb, orders))
        return self._packed_bounds

    # -- routing -----------------------------------------------------------

    def _key_planes(self, batch):
        if self._ev is None:
            self._ev = ExprEvaluator([so.child for so in self.sort_orders],
                                     batch.schema)
        from blaze_tpu.exprs.compiler import _broadcast

        datas, valids = [], []
        for so in self.sort_orders:
            v = self._ev._to_dev(self._ev._eval(so.child, batch), batch)
            data, validity = _broadcast(v, batch)
            datas.append(data)
            valids.append(validity)
        return datas, valids

    def partition_ids(self, batch):
        if not self.bounds:
            return np.zeros(batch.num_rows, dtype=np.int32)
        if SK.supports_device_sort(batch.schema, self.sort_orders):
            datas, valids = self._key_planes(batch)
            pids = K.range_partition_ids(datas, valids, batch.row_exists_mask(),
                                         self._device_bounds(),
                                         SK.key_spec(self.sort_orders))
            return wait_array(pids, "exchange_host_order")[
                : batch.num_rows].astype(np.int32)
        # var-width keys (no u64 normalization): per-row bisect over
        # python-comparable key tuples, as before
        import bisect

        bb, orders = self._bounds_batch()
        brows = SK.host_keys_matrix(bb, orders)
        rows = SK.host_keys_matrix(batch, self.sort_orders)
        return np.array([bisect.bisect_right(brows, r) for r in rows],
                        dtype=np.int32)

    def partition_ids_host(self, host):
        if not self.bounds:
            return np.zeros(host.num_rows, dtype=np.int32)
        names = [f.name for f in host.schema.fields]
        planes = []
        for so in self.sort_orders:
            if not isinstance(so.child, E.Column) or so.child.name not in names:
                return None
            it = host.items[names.index(so.child.name)]
            if not isinstance(it, tuple):
                return None
            planes.append((np.asarray(it[0]), np.asarray(it[1])))
        packed = SK.pack_key_rows(SK.planes_merge_matrix(planes, self.sort_orders))
        return np.searchsorted(self._bounds_packed(), packed,
                               side="right").astype(np.int32)

    def _route(self, batch):
        """The sort keys' planes and the resident bound operands where the
        keys normalize on the device; else the ids the host finds."""
        if not self.bounds or \
                not SK.supports_device_sort(batch.schema, self.sort_orders):
            return super()._route(batch)
        datas, valids = self._key_planes(batch)
        return (("range", SK.key_spec(self.sort_orders)),
                (tuple(datas), tuple(valids), self._device_bounds()))


def create_repartitioner(partitioning, schema) -> Repartitioner:
    if isinstance(partitioning, N.SinglePartitioning):
        return SinglePartitioner()
    if isinstance(partitioning, N.HashPartitioning):
        return HashPartitioner(partitioning.exprs, partitioning.num_partitions, schema)
    if isinstance(partitioning, N.RoundRobinPartitioning):
        return RoundRobinPartitioner(partitioning.num_partitions)
    if isinstance(partitioning, N.RangePartitioning):
        return RangePartitioner(partitioning.sort_orders, partitioning.num_partitions,
                                partitioning.bounds, schema)
    raise NotImplementedError(f"partitioning {partitioning!r}")
