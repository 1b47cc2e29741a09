"""Device-mesh distributed execution: the ICI shuffle path.

The reference's exchange transport is Spark's BlockManager/netty between
executors (SURVEY.md §5.8). On a TPU slice the native transport is ICI:
hash repartitioning becomes ``jax.lax.all_to_all`` inside a ``shard_map``
over a device mesh, broadcast becomes mesh replication, and global
aggregation merges with ``psum`` — XLA inserts the collectives
(scaling-book recipe: pick a mesh, annotate shardings, let XLA place
collectives on ICI).

Three layers:

- :func:`exchange_and_aggregate` — a single jittable SPMD step: local
  partial aggregation, all-to-all row exchange routed by spark-exact
  murmur3 pmod (so a row lands on the same reducer a file-based shuffle
  would pick), local final aggregation: what
  ``__graft_entry__.dryrun_multichip`` compiles.
- :class:`MeshBatchExchange` and :func:`task_chip` — the engine's exchange
  of a multichip Session: every task runs on the chip of its partition,
  map outputs arrive routed on their chips, and one all_to_all of
  compacted per-reducer segments moves them to the reducers' chips.
- :func:`make_mesh` — mesh construction over the available devices.

Fixed shapes: the collective is static-shaped (SURVEY.md §7.4.1)."""

from __future__ import annotations

import functools
import time
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map

from blaze_tpu.exprs.spark_hash import murmur3_int64
from blaze_tpu.obs.tracer import TRACER


def make_mesh(n_devices: Optional[int] = None, axis: str = "data") -> Mesh:
    devs = jax.devices()
    if n_devices is not None:
        devs = devs[:n_devices]
    return Mesh(np.array(devs), (axis,))


def pmod(hashes: jnp.ndarray, n: int) -> jnp.ndarray:
    """Spark pmod partition routing from int32 murmur3 hashes."""
    h = hashes.view(jnp.int32).astype(jnp.int64) if hashes.dtype == jnp.uint32 else hashes.astype(jnp.int64)
    return ((h % n) + n) % n


def _sorted_segment_agg(keys, vals, valid, num_segments: int):
    """Group-by-key via device sort + segment-sum (SURVEY.md §7.4.2: prefer
    sort-based grouping over hash tables on TPU). Returns padded
    (unique_keys, sums, counts, seg_valid)."""
    big = jnp.iinfo(jnp.int64).max
    skeys = jnp.where(valid, keys, big)
    order = jnp.argsort(skeys)
    k = skeys[order]
    v = jnp.where(valid, vals, 0)[order]
    is_new = jnp.concatenate([jnp.ones(1, bool), k[1:] != k[:-1]])
    seg_ids = jnp.cumsum(is_new) - 1
    sums = jax.ops.segment_sum(v, seg_ids, num_segments=num_segments)
    counts = jax.ops.segment_sum(
        valid[order].astype(jnp.int64), seg_ids, num_segments=num_segments)
    first_idx = jax.ops.segment_min(
        jnp.arange(k.shape[0]), seg_ids, num_segments=num_segments)
    uk = k[jnp.clip(first_idx, 0, k.shape[0] - 1)]
    seg_valid = (counts > 0) & (uk != big)
    return jnp.where(seg_valid, uk, 0), sums, counts, seg_valid


def exchange_and_aggregate(mesh: Mesh, capacity: int, axis: str = "data"):
    """Build the jitted SPMD step: (keys, vals, valid) sharded over the mesh
    -> per-device (unique_keys, sums, counts, valid) after one all-to-all
    exchange. Each device holds a (capacity,) shard."""
    n = mesh.shape[axis]

    def step(keys, vals, valid):
        # --- local partial aggregation (combiner before the exchange)
        pk, ps, pc, pv = _sorted_segment_agg(keys, vals, valid, capacity)

        # --- route each partial group to its reducer (spark-exact murmur3)
        h = murmur3_int64(pk, jnp.full(pk.shape, 42, jnp.uint32))
        pid = pmod(h.view(jnp.int32), n)
        pid = jnp.where(pv, pid, n)  # invalid rows route nowhere

        # --- build (n, capacity) masked tiles and exchange over ICI
        tile_mask = (pid[None, :] == jnp.arange(n)[:, None]) & pv[None, :]
        tk = jnp.where(tile_mask, pk[None, :], 0)
        ts = jnp.where(tile_mask, ps[None, :], 0)
        tc = jnp.where(tile_mask, pc[None, :], 0)
        tm = tile_mask
        tk, ts, tc, tm = [
            jax.lax.all_to_all(t, axis, split_axis=0, concat_axis=0, tiled=False)
            for t in (tk, ts, tc, tm)
        ]
        # received: (n, capacity) from every peer -> flatten and re-aggregate
        rk = tk.reshape(-1)
        rs = ts.reshape(-1)
        rc = tc.reshape(-1)
        rm = tm.reshape(-1)
        big = jnp.iinfo(jnp.int64).max
        skeys = jnp.where(rm, rk, big)
        order = jnp.argsort(skeys)
        k = skeys[order]
        is_new = jnp.concatenate([jnp.ones(1, bool), k[1:] != k[:-1]])
        seg_ids = jnp.cumsum(is_new) - 1
        nseg = rk.shape[0]  # a reducer may receive up to n*capacity groups
        sums = jax.ops.segment_sum(jnp.where(rm, rs, 0)[order], seg_ids,
                                   num_segments=nseg)
        counts = jax.ops.segment_sum(jnp.where(rm, rc, 0)[order], seg_ids,
                                     num_segments=nseg)
        first_idx = jax.ops.segment_min(jnp.arange(k.shape[0]), seg_ids,
                                        num_segments=nseg)
        uk = k[jnp.clip(first_idx, 0, k.shape[0] - 1)]
        out_valid = (counts > 0) & (uk != big)
        # global row count sanity via psum (every reducer learns the total)
        total_rows = jax.lax.psum(jnp.sum(valid.astype(jnp.int64)), axis)
        return (jnp.where(out_valid, uk, 0), sums, counts, out_valid, total_rows)


    sharded = shard_map(
        step, mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis)),
        out_specs=(P(axis), P(axis), P(axis), P(axis), P()),
    )
    return jax.jit(sharded)


def broadcast_join_sum(mesh: Mesh, capacity: int, build_capacity: int,
                       axis: str = "data"):
    """Build the jitted SPMD broadcast-join step: the build side (sorted
    keys + payload) is REPLICATED across the mesh (the broadcast strategy,
    SURVEY.md §2.5.6), the probe side is sharded; each device probes via
    ``searchsorted`` (log-n vectorized lookup — TPU-friendly, no hash table,
    SURVEY.md §7.2 L2') and the global matched-row count merges with psum.

    Returns per-device (matched_mask, gathered_payload, global_matches)."""
    n = mesh.shape[axis]

    def step(probe_keys, probe_valid, build_keys, build_vals, build_n):
        # build side is replicated: sorted keys enable binary-search probing
        idx = jnp.searchsorted(build_keys, probe_keys)
        idx = jnp.clip(idx, 0, build_capacity - 1)
        hit = (build_keys[idx] == probe_keys) & probe_valid & \
            (idx < build_n)
        payload = jnp.where(hit, build_vals[idx], 0)
        total = jax.lax.psum(jnp.sum(hit.astype(jnp.int64)), axis)
        return hit, payload, total


    sharded = shard_map(
        step, mesh=mesh,
        in_specs=(P(axis), P(axis), P(), P(), P()),
        out_specs=(P(axis), P(axis), P()),
    )
    return jax.jit(sharded)


def run_broadcast_join(probe_keys: np.ndarray, build_keys: np.ndarray,
                       build_vals: np.ndarray, mesh: Optional[Mesh] = None,
                       axis: str = "data"):
    """Host-facing: inner-join probe rows against a small replicated build
    side over the whole mesh; returns (payload per probe row or None,
    total matches)."""
    mesh = mesh or make_mesh()
    n = mesh.shape[axis]
    total = len(probe_keys)
    per = -(-total // n)
    capacity = 1
    while capacity < per:
        capacity *= 2
    bcap = 1
    while bcap < max(len(build_keys), 1):
        bcap *= 2
    order = np.argsort(build_keys, kind="stable")
    bk = np.full(bcap, np.iinfo(np.int64).max, dtype=np.int64)
    bv = np.zeros(bcap, dtype=np.int64)
    bk[: len(build_keys)] = np.asarray(build_keys)[order]
    bv[: len(build_keys)] = np.asarray(build_vals)[order]
    pk = np.zeros(n * capacity, dtype=np.int64)
    pm = np.zeros(n * capacity, dtype=bool)
    for d in range(n):
        lo, hi = d * per, min((d + 1) * per, total)
        if hi > lo:
            pk[d * capacity : d * capacity + (hi - lo)] = probe_keys[lo:hi]
            pm[d * capacity : d * capacity + (hi - lo)] = True
    step = broadcast_join_sum(mesh, capacity, bcap, axis)
    with mesh:
        hit, payload, tot = step(jnp.asarray(pk), jnp.asarray(pm),
                                 jnp.asarray(bk), jnp.asarray(bv),
                                 jnp.int64(len(build_keys)))
    hit, payload = np.asarray(hit), np.asarray(payload)
    out = []
    for d in range(n):
        lo, hi = d * per, min((d + 1) * per, total)
        for i in range(hi - lo):
            j = d * capacity + i
            out.append(int(payload[j]) if hit[j] else None)
    return out, int(tot)


# ---------------------------------------------------------------------------
# General ColumnarBatch exchange (the engine's exchange, not a demo kernel)
# ---------------------------------------------------------------------------


def task_chip(p: int, num_tasks: int, n: int) -> int:
    """The mesh slot that runs task ``p`` of a stage of ``num_tasks`` tasks:
    contiguous blocks of ``ceil(num_tasks / n)`` tasks a chip, ascending.
    It is the exchange's own grouping of reducers (``G = ceil(R / n)`` a
    chip), so a stage that reads an exchange runs every task on the chip
    that holds its reducer's rows, and a map stage's tasks fold onto the
    slots in map order (with the exchange's shard-major assembly, a
    reducer's rows then come in the file path's map-order concat at every
    mesh size)."""
    return p // -(-num_tasks // n)


@functools.partial(jax.jit, static_argnames=("scap",))
def _segments(pieces, starts, counts, round_, scap):
    """One shard's send buffer for one round, on the shard's chip: ``Rpad``
    segments of ``scap`` rows, segment ``r`` holding the shard's rows of
    reducer ``r`` from rank ``round_ * scap`` on. ``pieces`` are the
    shard's routed map outputs, ``(datas, valids)`` each, their rows in
    reducer order; ``starts[p, r]`` is the row (in the pieces' planes laid
    end to end) where piece ``p``'s rows of reducer ``r`` begin and
    ``counts[p, r]`` how many there are. A reducer's rows run piece after
    piece, each in its row order; slots past them are padding. One matrix
    gather moves every plane (``core/kernels.take_rows_traced``)."""
    from blaze_tpu.core.kernels import take_rows_traced

    ncols = len(pieces[0][0])
    datas = tuple(jnp.concatenate([p[0][i] for p in pieces])
                  for i in range(ncols))
    valids = tuple(jnp.concatenate([p[1][i] for p in pieces])
                   for i in range(ncols))
    rpad = counts.shape[1]
    with jax.named_scope("segments"):
        upto = jnp.cumsum(counts, axis=0)              # (P, Rpad)
        slot = jnp.arange(rpad * scap, dtype=jnp.int32)
        r = slot // scap
        q = round_ * scap + slot % scap                # rank in reducer r
        live = q < upto[-1][r]
        piece = jnp.sum(q[None, :] >= upto[:, r], axis=0)
        piece = jnp.minimum(piece, counts.shape[0] - 1)
        before = upto[piece, r] - counts[piece, r]
        src = jnp.where(live, starts[piece, r] + q - before, 0)
        return take_rows_traced(datas, valids, src, live)


@functools.partial(jax.jit, static_argnames=("n", "chunk", "scap", "out_cap"))
def _reducer_rows(rounds, counts, group, n, chunk, scap, out_cap):
    """One reducer's rows out of what its chip received, on that chip, as
    planes of ``out_cap`` under the padding contract. ``rounds`` are the
    received ``(datas, valids)`` of every round (``n`` peer chunks of
    ``chunk`` rows each, the reducer's segment ``group`` of each), and
    ``counts[s]`` the reducer's rows from shard ``s`` over all rounds: the
    rows come shard-major, each shard's in rank order, whatever the round
    split (the order does not depend on the mesh size)."""
    from blaze_tpu.core.kernels import take_rows_traced

    ncols = len(rounds[0][0])
    datas = tuple(jnp.concatenate([t[0][i] for t in rounds])
                  for i in range(ncols))
    valids = tuple(jnp.concatenate([t[1][i] for t in rounds])
                   for i in range(ncols))
    with jax.named_scope("extract"):
        upto = jnp.cumsum(counts)
        k = jnp.arange(out_cap, dtype=jnp.int32)
        live = k < upto[-1]
        shard = jnp.minimum(jnp.searchsorted(upto, k, side="right"), n - 1)
        q = k - (upto[shard] - counts[shard])
        idx = (q // scap) * (n * chunk) + shard * chunk + group * scap \
            + q % scap
        return take_rows_traced(datas, valids, jnp.where(live, idx, 0), live)


@functools.partial(jax.jit, static_argnames=("mesh", "axis", "nplanes",
                                             "chunk"))
def _exchange_compact_step(mesh, axis, nplanes, chunk, *planes):
    """SPMD all-to-all of COMPACTED per-reducer segments. Each device holds
    an (n*chunk,) shard per plane, already laid out as n peer-chunks of
    ``chunk`` rows (the rows routed to that peer's reducer group, compacted
    — not the old (n, capacity) masked tiles that shipped mostly padding).
    Received planes land flattened as n peer segments per device. Static
    shapes throughout (SURVEY.md §7.4.1); the segment capacity is sized
    from the exchanged per-reducer row counts, so bytes on the wire track
    the data actually routed (reference: ``shuffle/buffered_data.rs:48-541``
    compact-before-transport)."""

    n = mesh.shape[axis]

    def step(*planes):
        outs = []
        for p in planes:
            t = p.reshape(n, chunk)
            t = jax.lax.all_to_all(t, axis, split_axis=0, concat_axis=0)
            outs.append(t.reshape(-1))
        return tuple(outs)

    sharded = shard_map(
        step, mesh=mesh,
        in_specs=(P(axis),) * nplanes,
        out_specs=(P(axis),) * nplanes,
    )
    return sharded(*planes)


class MeshBatchExchange:
    """Exchange real ColumnarBatches over the ICI mesh — the TPU-native
    replacement for the reference's file/netty shuffle transport
    (``shuffle/buffered_data.rs:48-541`` + ``ipc_reader_exec.rs:132-325``,
    SURVEY.md §5.8 "TPU-native equivalent").

    Every map output arrives ROUTED on the chip that made it
    (``Repartitioner.route``: the rows in reducer order and the ``R + 1``
    offsets, the same spark-exact ids as the file path, so a row lands on
    the same reducer either way). Device columns (ints, floats, dates,
    timestamps, decimal<=18 as unscaled int64, agg partial states) never
    leave the chips: each shard's send buffer is cut from its routed
    batches on its chip, one all_to_all moves every plane, and each
    reducer's rows are gathered on the chip that received them. The host
    reads the offsets and nothing else. Host columns (strings, wide
    decimals) ride as int32 codes against one global dictionary built on the host
    and are rematerialized on the host."""

    def __init__(self, mesh: Mesh, axis: Optional[str] = None):
        assert len(mesh.axis_names) == 1, (
            f"MeshBatchExchange needs a 1-D mesh, got axes {mesh.axis_names}")
        self.mesh = mesh
        self.axis = axis or mesh.axis_names[0]
        self.n = mesh.shape[self.axis]
        self.devices = list(mesh.devices.flat)

    def run(self, schema, shards: List[List[tuple]], num_reducers: int,
            device_resident_budget: Optional[int] = None
            ) -> List[Optional["object"]]:
        """``shards[s]``: the routed map outputs that mesh slot ``s`` holds,
        in map order, each ``(batch, offsets)`` with the batch's planes on
        slot ``s``'s chip and ``offsets`` its ``num_reducers + 1`` reducer
        offsets. Returns one batch (or None when empty) per reducer: a
        ColumnarBatch whose device columns live on the reducer's chip
        (``task_chip(r, num_reducers, n)``), or a HostBatch where the payload is larger than
        ``device_resident_budget`` (default ``mesh_device_resident_max_bytes``).

        ``num_reducers`` may exceed the mesh size: reducers are grouped
        G = ceil(R/n) per device and each all_to_all chunk carries one
        device's reducer group. The segment capacity comes from the
        offsets, so the wire carries ~max-routed-rows per segment instead of
        the full producer capacity; one skewed reducer is bounded by
        ``mesh_exchange_round_bytes`` and takes more rounds of the same
        compiled step. ``last_wire_bytes`` / ``last_wire_bytes_uncompacted``
        record the realized vs naive payload for observability."""
        from blaze_tpu.config import get_config
        from blaze_tpu.core.batch import ColumnarBatch, DeviceColumn, \
            HostBatch, HostColumn
        from blaze_tpu.utils.device import is_device_dtype

        n, devs = self.n, self.devices
        R = num_reducers
        G = -(-R // n)          # reducer groups per device
        Rpad = G * n
        assert len(shards) == n
        ncols = len(schema)
        conf = get_config()
        host_slots = {i for i, f in enumerate(schema.fields)
                      if not is_device_dtype(f.dtype)}

        # --- counts first: rows per (shard, piece, reducer), from the
        # offsets the map tasks already read
        counts = np.zeros((n, Rpad), np.int64)
        piece_counts = []
        for s, pieces in enumerate(shards):
            pc = np.zeros((max(1, len(pieces)), Rpad), np.int64)
            for p, (_b, offsets) in enumerate(pieces):
                pc[p, :R] = np.diff(np.asarray(offsets, np.int64))
            piece_counts.append(pc)
            counts[s] = pc.sum(axis=0)
        maxc = int(counts.max()) if counts.size else 0

        dictionaries, codes = self._encode_host_columns(schema, shards,
                                                        host_slots)
        col_dtypes = self._plane_dtypes(schema, shards, host_slots)

        # --- segment capacity, bounded per round. scap is the max
        # per-(shard, reducer) routed-row count at 512 granularity (tight
        # enough for the >=5x wire win, coarse enough that repeated runs
        # reuse the compiled step); ONE skewed reducer would pad every
        # segment to the hot size, so the per-device send buffer is capped
        # at mesh_exchange_round_bytes and the exchange loops bounded
        # rounds over the same compiled step instead.
        slot_bytes = sum(np.dtype(dt).itemsize + 1 for dt in col_dtypes)
        budget = int(conf.mesh_exchange_round_bytes)
        # granularity scales DOWN for huge reducer counts: the 512-row
        # floor alone would allocate Rpad*512 slots and silently blow past
        # the configured budget for tens of thousands of reducers
        gran = 512
        while gran > 8 and Rpad * gran * slot_bytes > budget:
            gran //= 2
        if Rpad * gran * slot_bytes > budget:
            import logging

            logging.getLogger("blaze_tpu.mesh").warning(
                "mesh exchange: %d reducer segments at min granularity %d "
                "exceed mesh_exchange_round_bytes=%d; padded buffers will "
                "overshoot the budget", Rpad, gran, budget)
        scap_need = max(gran, -(-maxc // gran) * gran)
        scap_cap = max(gran, (budget // (Rpad * slot_bytes)) // gran * gran)
        scap = min(scap_need, scap_cap)
        rounds = max(1, -(-maxc // scap))
        chunk = G * scap
        seg_len = Rpad * scap  # == n * chunk

        # residency decision from the ACTUAL routed payload (padding-free):
        # device-resident only while the payload fits the budget the caller
        # hands in (session.py charges each resident exchange to its query
        # until the query is released); larger exchanges land in host RAM
        # like shuffle files so device memory cannot accumulate.
        self.last_payload_bytes = int(counts.sum()) * slot_bytes * 2
        resident_budget = conf.mesh_device_resident_max_bytes \
            if device_resident_budget is None else device_resident_budget
        device_resident = self.last_payload_bytes <= resident_budget
        self.last_device_resident = device_resident

        from jax.sharding import NamedSharding

        sharding = NamedSharding(self.mesh, P(self.axis))
        # each shard's pieces as planes on its chip, and where every piece's
        # rows of each reducer begin in them laid end to end
        shard_in = []
        for s, pieces in enumerate(shards):
            if not pieces:
                shard_in.append(None)
                continue
            planes, starts, base = [], np.zeros((len(pieces), Rpad), np.int64), 0
            for p, (b, offsets) in enumerate(pieces):
                planes.append(self._piece_planes(
                    b, schema, host_slots, codes[s][p], devs[s]))
                starts[p, :R] = base + np.asarray(offsets[:-1], np.int64)
                base += planes[-1][0][0].shape[0]
            shard_in.append((tuple(planes), starts.astype(np.int32),
                             piece_counts[s].astype(np.int32)))

        received = []  # per round: per plane, the n per-device shards
        self.last_wire_bytes = 0
        for t in range(rounds):
            segments = []
            for s in range(n):
                with jax.default_device(devs[s]):
                    if shard_in[s] is None:
                        segments.append(
                            (tuple(jnp.zeros(seg_len, dt) for dt in col_dtypes),
                             tuple(jnp.zeros(seg_len, bool) for _ in col_dtypes)))
                    else:
                        pieces, starts, pcounts = shard_in[s]
                        segments.append(_segments(pieces, starts, pcounts,
                                                  np.int32(t), scap=scap))
            gplanes = [jax.make_array_from_single_device_arrays(
                (n * seg_len,), sharding,
                [segments[s][kind][i] for s in range(n)])
                for kind in (0, 1) for i in range(ncols)]
            t0_ns = time.perf_counter_ns() if TRACER.active else 0
            with self.mesh:
                outs = _exchange_compact_step(self.mesh, self.axis,
                                              len(gplanes), chunk, *gplanes)
            if t0_ns:
                TRACER.complete("mesh_exchange", "collective", t0_ns,
                                time.perf_counter_ns() - t0_ns,
                                {"planes": len(gplanes), "devices": n})
            self.last_wire_bytes += sum(
                n * seg_len * np.dtype(p.dtype).itemsize for p in gplanes)
            # the outputs split into their per-device shards: reducer r's
            # slots live wholly inside device r//G's shard, so every gather
            # below is a plain single-device program (indexing the global
            # array instead compiles each take into an n-participant
            # collective)
            by_dev = []
            for p in outs:
                held = {next(iter(a.data.devices())): a.data
                        for a in p.addressable_shards}
                by_dev.append([held[d] for d in devs])
            received.append(by_dev)

        # wire observability: naive masked-tile equivalent for comparison
        cap = conf.capacity_for(max([b.num_rows for pieces in shards
                                     for b, _o in pieces] or [1]))
        self.last_wire_bytes_uncompacted = sum(
            n * n * cap * np.dtype(dt).itemsize
            for dt in [np.dtype(bool)]  # the masked tiles' live plane
            + list(col_dtypes) + [np.dtype(bool)] * ncols)

        # --- each reducer's rows, gathered on the chip that received them
        results: List[Optional[object]] = []
        for r in range(R):
            total = int(counts[:, r].sum())
            if total == 0:
                results.append(None)
                continue
            d, g = divmod(r, G)
            rounds_d = tuple(
                (tuple(rv[i][d] for i in range(ncols)),
                 tuple(rv[ncols + i][d] for i in range(ncols)))
                for rv in received)
            with jax.default_device(devs[d]):
                datas, valids = _reducer_rows(
                    rounds_d, counts[:, r].astype(np.int32), np.int32(g),
                    n=n, chunk=chunk, scap=scap,
                    out_cap=conf.capacity_for(total))
            cols = []
            for i, f in enumerate(schema.fields):
                if i in host_slots:
                    cols.append(HostColumn(f.dtype, self._decode(
                        dictionaries[i], datas[i], valids[i], total)))
                else:
                    cols.append(DeviceColumn(f.dtype, datas[i], valids[i]))
            batch = ColumnarBatch(schema, cols, total)
            results.append(batch if device_resident
                           else HostBatch.from_batch(batch))
        return results

    @staticmethod
    def _encode_host_columns(schema, shards, host_slots):
        """One dictionary a host column over every piece of every shard, and
        each piece's int32 codes and validity in its (routed) row order."""
        import pyarrow as pa

        from blaze_tpu.core.batch import HostColumn, decode_dictionary
        from blaze_tpu.ir import types as T

        dictionaries = {}
        codes = [[{} for _ in pieces] for pieces in shards]
        for i in sorted(host_slots):
            arrays, where = [], []
            for s, pieces in enumerate(shards):
                for p, (b, _o) in enumerate(pieces):
                    c = b.columns[i]
                    arr = c.array if isinstance(c, HostColumn) \
                        else c.to_arrow(b.num_rows)
                    if isinstance(arr, pa.ChunkedArray):
                        arr = arr.combine_chunks()
                    arrays.append(arr)
                    where.append((s, p))
            if not arrays:
                dictionaries[i] = pa.array(
                    [], type=T.to_arrow_type(schema[i].dtype))
                continue
            if len({a.type for a in arrays}) > 1:
                arrays = [decode_dictionary(a, schema[i].dtype)
                          for a in arrays]
            denc = pa.concat_arrays(arrays).dictionary_encode()
            # large_*-normalize the dictionary VALUES so the reducer's
            # `.take` emits the engine's convention type (plain `string`
            # would break downstream concat and caps offsets at 2GB)
            dictionaries[i] = decode_dictionary(denc.dictionary,
                                                schema[i].dtype)
            off = 0
            for (s, p), arr in zip(where, arrays):
                sl = denc.indices.slice(off, len(arr))
                valid = ~np.asarray(sl.is_null()) if sl.null_count \
                    else np.ones(len(arr), bool)
                codes[s][p][i] = (sl.fill_null(0).to_numpy(
                    zero_copy_only=False).astype(np.int32), valid)
                off += len(arr)
        return dictionaries, codes

    @staticmethod
    def _plane_dtypes(schema, shards, host_slots):
        """The dtype of each column's plane on the wire (a host column's
        codes are int32), read off the first piece."""
        from blaze_tpu.core.batch import arrow_fixed_planes, has_planes

        first = next((b for pieces in shards for b, _o in pieces), None)
        out = []
        for i, f in enumerate(schema.fields):
            c = None if first is None else first.columns[i]
            out.append(np.dtype(np.int32) if i in host_slots
                       else np.dtype(f.dtype.np_dtype or np.int64) if c is None
                       else np.dtype(c.data.dtype) if has_planes(c)
                       else arrow_fixed_planes(c.array, f.dtype)[0].dtype)
        return out

    @staticmethod
    def _piece_planes(batch, schema, host_slots, codes, device):
        """``(datas, valids)`` of one routed piece, every plane of the
        piece's capacity on ``device`` (where its device columns already
        are): host columns as their codes, a fixed-width column that is no
        device plane as its Arrow buffers."""
        from blaze_tpu.core.batch import arrow_fixed_planes, has_planes

        cap = batch.capacity
        datas, valids = [], []
        for i, f in enumerate(schema.fields):
            c = batch.columns[i]
            if i not in host_slots and has_planes(c):
                datas.append(c.data)
                valids.append(c.validity)
                continue
            if i in host_slots:
                d, v = codes[i]
            else:
                d, v = arrow_fixed_planes(c.array, f.dtype)
                v = np.ones(len(d), bool) if v is None else v
            pad = cap - len(d)
            datas.append(jax.device_put(
                np.concatenate([d, np.zeros(pad, d.dtype)]), device))
            valids.append(jax.device_put(
                np.concatenate([v, np.zeros(pad, bool)]), device))
        return tuple(datas), tuple(valids)

    @staticmethod
    def _decode(dictionary, data, validity, num_rows):
        import pyarrow as pa

        cd, cv = np.asarray(data)[:num_rows], np.asarray(validity)[:num_rows]
        codes = pa.array(cd, type=pa.int32()) if cv.all() else \
            pa.array(np.where(cv, cd, 0), type=pa.int32(), mask=~cv)
        return dictionary.take(codes)


def run_distributed_sum(keys: np.ndarray, vals: np.ndarray,
                        mesh: Optional[Mesh] = None,
                        axis: str = "data") -> dict:
    """Host-facing helper: global group-by-sum over all mesh devices; returns
    {key: (sum, count)} gathered on host (used by tests and the dryrun)."""
    mesh = mesh or make_mesh()
    n = mesh.shape[axis]
    total = len(keys)
    per = -(-total // n)
    capacity = 1
    while capacity < per:
        capacity *= 2
    kbuf = np.zeros(n * capacity, dtype=np.int64)
    vbuf = np.zeros(n * capacity, dtype=np.int64)
    mbuf = np.zeros(n * capacity, dtype=bool)
    for d in range(n):
        lo, hi = d * per, min((d + 1) * per, total)
        if hi > lo:
            kbuf[d * capacity : d * capacity + (hi - lo)] = keys[lo:hi]
            vbuf[d * capacity : d * capacity + (hi - lo)] = vals[lo:hi]
            mbuf[d * capacity : d * capacity + (hi - lo)] = True
    step = exchange_and_aggregate(mesh, capacity, axis)
    with mesh:
        uk, sums, counts, valid, total_rows = step(
            jnp.asarray(kbuf), jnp.asarray(vbuf), jnp.asarray(mbuf))
    uk, sums, counts, valid = map(np.asarray, (uk, sums, counts, valid))
    assert int(total_rows) == int(mbuf.sum())
    out = {}
    for i in np.nonzero(valid)[0]:
        k = int(uk[i])
        s, c = out.get(k, (0, 0))
        out[k] = (s + int(sums[i]), c + int(counts[i]))
    return out
