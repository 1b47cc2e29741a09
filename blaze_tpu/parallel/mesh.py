"""Device-mesh distributed execution: the ICI shuffle path.

The reference's exchange transport is Spark's BlockManager/netty between
executors (SURVEY.md §5.8). On a TPU slice the native transport is ICI:
hash repartitioning becomes ``jax.lax.all_to_all`` inside a ``shard_map``
over a device mesh, broadcast becomes mesh replication, and global
aggregation merges with ``psum`` — XLA inserts the collectives
(scaling-book recipe: pick a mesh, annotate shardings, let XLA place
collectives on ICI).

Two layers:

- :func:`exchange_and_aggregate` — a single jittable SPMD step: local
  partial aggregation, all-to-all row exchange routed by spark-exact
  murmur3 pmod (so a row lands on the same reducer a file-based shuffle
  would pick), local final aggregation. This is the building block the
  mesh session composes and what ``__graft_entry__.dryrun_multichip``
  compiles.
- :func:`make_mesh` — mesh construction over the available devices.

Fixed shapes: each device ships one (num_devices, capacity) tile pair per
exchanged column — rows not routed to a peer are masked, not compacted, so
the collective is static-shaped (SURVEY.md §7.4.1)."""

from __future__ import annotations

import functools
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map

from blaze_tpu.exprs.spark_hash import murmur3_int64


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

    Columns of any engine type move: device columns (ints, floats, dates,
    timestamps, decimal<=18 as unscaled int64, agg partial states) ship as
    raw planes + validity; host columns (strings, wide decimals) ship as
    dictionary codes against a driver-built global dictionary and are
    rematerialized on the reducer. Partition ids come from the SAME
    Repartitioner as the file path (spark-exact murmur3 pmod), so a row
    lands on the same reducer either way."""

    def __init__(self, mesh: Mesh, axis: Optional[str] = None):
        assert len(mesh.axis_names) == 1, (
            f"MeshBatchExchange needs a 1-D mesh, got axes {mesh.axis_names}")
        self.mesh = mesh
        self.axis = axis or mesh.axis_names[0]
        self.n = mesh.shape[self.axis]

    def run(self, schema, shard_batches: List[Optional["object"]],
            shard_pids: List[Optional[np.ndarray]],
            num_reducers: int,
            device_resident_budget: Optional[int] = None
            ) -> List[Optional["object"]]:
        """shard_batches[s]: ColumnarBatch (or None) held by mesh slot s;
        shard_pids[s]: per-row reducer ids. Returns one ColumnarBatch (or
        None when empty) per reducer — device columns stay DEVICE-RESIDENT
        end to end: producer device planes are permuted into compacted
        per-reducer segments on device, exchanged over the collective, and
        the reducer output is sliced out on device, so the next stage's
        device aggregation consumes them without a host round trip. Host
        columns (strings, wide decimals) ride as int32 dictionary codes
        against a driver-built global dictionary, exactly as before.

        ``num_reducers`` may exceed the mesh size: reducers are grouped
        G = ceil(R/n) per device and each all_to_all chunk carries one
        device's reducer group.

        The per-reducer segment capacity comes from the exchanged row
        counts (here a host bincount — the driver already holds the pids),
        so the wire carries ~max-routed-rows per segment instead of the
        full producer capacity; ``last_wire_bytes`` /
        ``last_wire_bytes_uncompacted`` record the realized vs naive
        payload for observability."""
        from blaze_tpu.config import get_config
        from blaze_tpu.core.batch import (ColumnarBatch, DeviceColumn,
                                          HostColumn, arrow_fixed_planes)
        from blaze_tpu.ir import types as T
        from blaze_tpu.utils.device import is_device_dtype

        import pyarrow as pa

        n = self.n
        R = num_reducers
        G = -(-R // n)          # reducer groups per device
        Rpad = G * n
        assert len(shard_batches) == n
        ncols = len(schema)
        conf = get_config()
        host_slots = [i for i, f in enumerate(schema.fields)
                      if not is_device_dtype(f.dtype)]

        # --- "exchange counts first": per-shard per-reducer row counts.
        # The driver orchestrates every shard in this embedding, so the
        # count exchange is a host bincount; on a multi-host runtime this
        # becomes one tiny all_gather of the (R,) count vectors.
        counts = np.zeros((n, Rpad), np.int64)
        for s, p in enumerate(shard_pids):
            if p is not None and len(p):
                counts[s] += np.bincount(p, minlength=Rpad)
        maxc = int(counts.max())

        # --- dictionary-encode host columns (global dict, as before)
        dictionaries: dict = {}
        host_codes = {i: [None] * n for i in host_slots}
        for i in host_slots:
            arrays, present = [], []
            for s, b in enumerate(shard_batches):
                if b is None or b.num_rows == 0:
                    continue
                c = b.columns[i]
                arr = c.array if isinstance(c, HostColumn) \
                    else c.to_arrow(b.num_rows)
                if isinstance(arr, pa.ChunkedArray):
                    arr = arr.combine_chunks()
                arrays.append(arr)
                present.append(s)
            if not arrays:
                dictionaries[i] = pa.array(
                    [], type=T.to_arrow_type(schema[i].dtype))
                continue
            if len({a.type for a in arrays}) > 1:
                from blaze_tpu.core.batch import decode_dictionary

                arrays = [decode_dictionary(a, schema[i].dtype)
                          for a in arrays]
            combined = pa.concat_arrays(arrays)
            denc = combined.dictionary_encode()
            from blaze_tpu.core.batch import decode_dictionary

            # large_*-normalize the dictionary VALUES so reducer-side
            # `.take` emits the engine's convention type (plain `string`
            # would break downstream concat and caps offsets at 2GB)
            dictionaries[i] = decode_dictionary(denc.dictionary,
                                                schema[i].dtype)
            codes = denc.indices
            off = 0
            for s in present:
                k = shard_batches[s].num_rows
                sl = codes.slice(off, k)
                valid = ~np.asarray(sl.is_null()) if sl.null_count \
                    else np.ones(k, bool)
                host_codes[i][s] = (
                    sl.fill_null(0).to_numpy(zero_copy_only=False)
                    .astype(np.int32), valid)
                off += k

        # --- column plane dtypes
        col_dtypes: List[np.dtype] = []
        for i in range(ncols):
            if i in host_slots:
                col_dtypes.append(np.dtype(np.int32))
                continue
            dt = None
            for b in shard_batches:
                if b is not None and b.num_rows:
                    c = b.columns[i]
                    dt = np.dtype(c.data.dtype) if isinstance(c, DeviceColumn) \
                        else None
                    if dt is None:
                        d, _ = arrow_fixed_planes(c.array, schema[i].dtype)
                        dt = d.dtype
                    break
            col_dtypes.append(dt or np.dtype(
                schema[i].dtype.np_dtype or np.int64))

        # --- segment capacity, bounded per round. scap is the max
        # per-(shard, reducer) routed-row count at 512 granularity (tight
        # enough for the >=5x wire win, coarse enough that repeated runs
        # reuse the compiled step); ONE skewed reducer would pad every
        # segment to the hot size, so the per-device send buffer is capped
        # at mesh_exchange_round_bytes and the exchange loops bounded
        # rounds over the same compiled step instead.
        slot_bytes = 1 + sum(np.dtype(dt).itemsize + 1 for dt in col_dtypes)
        budget = int(conf.mesh_exchange_round_bytes)
        # granularity scales DOWN for huge reducer counts (session no
        # longer caps num_reducers at mesh size): the 512-row floor alone
        # would allocate Rpad*512 slots and silently blow past the
        # configured budget for tens of thousands of reducers
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
        # device-resident only while the payload fits the remaining HBM
        # budget (the CALLER accounts across stacked exchanges —
        # session.py's _mesh_pinned_bytes); larger exchanges land in host
        # RAM like shuffle files so device memory cannot accumulate.
        total_rows = int(counts.sum())
        self.last_payload_bytes = total_rows * slot_bytes * 2
        resident_budget = conf.mesh_device_resident_max_bytes \
            if device_resident_budget is None else device_resident_budget
        device_resident = self.last_payload_bytes <= resident_budget
        self.last_device_resident = device_resident

        from jax.sharding import NamedSharding

        sharding = NamedSharding(self.mesh, P(self.axis))
        devs = list(self.mesh.devices.flat)

        # per-shard routing and device-resident column planes, precomputed
        # ONCE across rounds (only the round's permutation indices change
        # with t — re-uploading the full columns every round would multiply
        # host-to-device traffic by the round count)
        shard_route = []
        shard_cols: List[Optional[List]] = []
        for s, b in enumerate(shard_batches):
            if b is None or b.num_rows == 0:
                shard_route.append(None)
                shard_cols.append(None)
                continue
            pids = shard_pids[s]
            order = np.argsort(pids, kind="stable")
            starts = np.zeros(Rpad, np.int64)
            starts[1:] = np.cumsum(counts[s])[:-1]
            psort = pids[order]
            rank = np.arange(b.num_rows) - starts[psort]
            shard_route.append((order, psort, rank))
            scols = []
            for i in range(ncols):
                if i in host_slots:
                    d, v = host_codes[i][s]
                    scols.append((jnp.asarray(d), jnp.asarray(v)))
                else:
                    c = b.columns[i]
                    if isinstance(c, DeviceColumn):
                        scols.append((c.data, c.validity))
                    else:
                        d, v = arrow_fixed_planes(c.array, schema[i].dtype)
                        if v is None:
                            v = np.ones(len(d), bool)
                        scols.append((jnp.asarray(d), jnp.asarray(v)))
            shard_cols.append(scols)

        red_cnt = counts.sum(axis=0)
        pieces: List[List] = [[] for _ in range(Rpad)]  # per reducer, per round
        self.last_wire_bytes = 0
        for t in range(rounds):
            shard_planes: List[List] = [[] for _ in range(1 + 2 * ncols)]
            for s, b in enumerate(shard_batches):
                route = shard_route[s]
                if route is None:
                    shard_planes[0].append(jnp.zeros(seg_len, bool))
                    for i in range(ncols):
                        shard_planes[1 + 2 * i].append(
                            jnp.zeros(seg_len, col_dtypes[i]))
                        shard_planes[2 + 2 * i].append(
                            jnp.zeros(seg_len, bool))
                    continue
                order, psort, rank = route
                sel = (rank >= t * scap) & (rank < (t + 1) * scap)
                dest = psort[sel] * scap + (rank[sel] - t * scap)
                src = np.full(seg_len, -1, np.int64)
                src[dest] = order[sel]
                live_h = src >= 0
                sidx = jnp.asarray(np.where(live_h, src, 0).astype(np.int32))
                lv = jnp.asarray(live_h)
                shard_planes[0].append(lv)
                for i in range(ncols):
                    dd, vv = shard_cols[s][i]
                    shard_planes[1 + 2 * i].append(
                        jnp.where(lv, jnp.take(dd, sidx, mode="clip"),
                                  jnp.zeros((), dd.dtype)))
                    shard_planes[2 + 2 * i].append(
                        jnp.take(vv, sidx, mode="clip") & lv)

            # global sharded planes: each shard's segment placed directly
            # on ITS mesh device — no single-device concatenate funnel
            gplanes = []
            for ps in shard_planes:
                shards = [jax.device_put(p, devs[s])
                          for s, p in enumerate(ps)]
                gplanes.append(jax.make_array_from_single_device_arrays(
                    (n * seg_len,), sharding, shards))
            import time as _time

            from blaze_tpu.obs.tracer import TRACER

            t0_ns = _time.perf_counter_ns() if TRACER.active else 0
            with self.mesh:
                outs = _exchange_compact_step(self.mesh, self.axis,
                                              len(gplanes), chunk, *gplanes)
            if t0_ns:
                TRACER.complete("mesh_exchange", "collective", t0_ns,
                                _time.perf_counter_ns() - t0_ns,
                                {"planes": len(gplanes), "devices": n})
            self.last_wire_bytes += sum(
                n * seg_len * np.dtype(p.dtype).itemsize for p in gplanes)

            # per-reducer extraction for THIS round: gather only live rows
            # (device arrays sized by actual data, so cross-round storage
            # is bounded by the payload, not the padding). Split the
            # collective's outputs into their per-device shards FIRST:
            # reducer r's slots live wholly inside device r//G's shard, so
            # every gather below is a plain single-device program. Indexing
            # the global sharded array instead compiles each take into a
            # fresh n-participant collective, and at scale those interleave
            # with the next round's all_to_all and wedge the XLA CPU
            # rendezvous (observed: q67 at 2M rows on the 8-device mesh).
            shard_view: List[List] = []
            for p in outs:
                by_dev = {next(iter(s.data.devices())): s.data
                          for s in p.addressable_shards}
                shard_view.append([by_dev[dv] for dv in devs])
            live_np = [np.asarray(sv) for sv in shard_view[0]]
            for r in range(Rpad):
                if red_cnt[r] == 0:
                    continue
                d, g = divmod(r, G)
                base = np.add.outer(np.arange(n) * chunk + g * scap,
                                    np.arange(scap)).ravel()
                rows = np.nonzero(live_np[d][base])[0]
                if not len(rows):
                    continue
                fidx_dev = jnp.asarray(base[rows])
                cols_rt = []
                for i in range(ncols):
                    pd_ = jnp.take(shard_view[1 + 2 * i][d], fidx_dev)
                    pv = jnp.take(shard_view[2 + 2 * i][d], fidx_dev)
                    if device_resident and i not in host_slots:
                        # downstream single-stream operators expect all
                        # operands on the primary device
                        cols_rt.append((jax.device_put(pd_, devs[0]),
                                        jax.device_put(pv, devs[0])))
                    else:
                        cols_rt.append((np.asarray(pd_), np.asarray(pv)))
                # this round's live rows per source shard (the extraction
                # gather above is shard-major, ranks contiguous per shard)
                c_live = np.minimum(np.maximum(
                    counts[:, r] - t * scap, 0), scap)
                pieces[r].append((cols_rt, c_live))

        # wire observability: naive masked-tile equivalent for comparison
        cap = conf.capacity_for(
            max([b.num_rows for b in shard_batches if b is not None] or [1]))
        self.last_wire_bytes_uncompacted = sum(
            n * n * cap * np.dtype(dt).itemsize
            for dt in [np.dtype(bool)]  # live plane
            + [col_dtypes[i] for i in range(ncols)]
            + [np.dtype(bool)] * ncols)

        # --- final per-reducer assembly across rounds
        from blaze_tpu.core.batch import HostBatch

        results: List[Optional[ColumnarBatch]] = []
        for r in range(R):
            ps = pieces[r]
            cnt = sum(int(cl.sum()) for _, cl in ps) if ps else 0
            if cnt == 0:
                results.append(None)
                continue
            # canonical row order: each reducer's rows sorted shard-major
            # (source shard, then original row order), INDEPENDENT of the
            # round split. A skew-driven extra round appends rows
            # round-major; left unpermuted that row order — and with it
            # float accumulation order and sort-tie order downstream —
            # would depend on scap, i.e. on the mesh size, breaking the
            # bit-identical-across-meshes contract.
            perm = None
            if len(ps) > 1:
                key = np.concatenate(
                    [np.repeat(np.arange(n), cl) for _, cl in ps])
                p_ = np.argsort(key, kind="stable")
                if not np.array_equal(p_, np.arange(len(p_))):
                    perm = p_
            out_cap = conf.capacity_for(cnt)
            cols = []
            hitems = []
            for i, f in enumerate(schema.fields):
                dparts = [cr[i][0] for cr, _ in ps]
                vparts = [cr[i][1] for cr, _ in ps]
                if i in host_slots:
                    cd = np.concatenate(dparts)
                    cv = np.concatenate(vparts)
                    if perm is not None:
                        cd, cv = cd[perm], cv[perm]
                    codes = pa.array(cd, type=pa.int32()) if cv.all() else \
                        pa.array(np.where(cv, cd, 0), type=pa.int32(),
                                 mask=~cv)
                    taken = dictionaries[i].take(codes)
                    if device_resident:
                        cols.append(HostColumn(f.dtype, taken))
                    else:
                        hitems.append(taken)
                elif device_resident:
                    pad = out_cap - cnt
                    ddata = jnp.concatenate(dparts) if len(dparts) > 1 \
                        else dparts[0]
                    dvalid = jnp.concatenate(vparts) if len(vparts) > 1 \
                        else vparts[0]
                    if perm is not None:
                        jperm = jnp.asarray(perm)
                        ddata = jnp.take(ddata, jperm)
                        dvalid = jnp.take(dvalid, jperm)
                    if pad:
                        ddata = jnp.concatenate(
                            [ddata, jnp.zeros(pad, ddata.dtype)])
                        dvalid = jnp.concatenate([dvalid,
                                                  jnp.zeros(pad, bool)])
                    cols.append(DeviceColumn(f.dtype, ddata, dvalid))
                else:
                    cd = np.concatenate(dparts)
                    cv = np.concatenate(vparts)
                    if perm is not None:
                        cd, cv = cd[perm], cv[perm]
                    hitems.append((cd, cv))
            results.append(ColumnarBatch(schema, cols, cnt)
                           if device_resident
                           else HostBatch(schema, hitems, cnt))
        return results


class ShardedFusedRunner:
    """Run a fused-stage closure (ops/fused.py) data-parallel across the
    mesh: k <= n consecutive same-shape batches stack into one
    ``(n, capacity)`` NamedSharding global per column plane — one batch per
    device — and the ORIGINAL per-batch jitted closure runs inside a
    ``shard_map`` body that squeezes its device's leading axis. Per batch
    the math is byte-for-byte the single-device dispatch (no row resharding,
    no cross-shard compaction), so results are bit-identical across 1/2/8
    device meshes by construction; the win is the n bodies executing
    concurrently on n chips instead of queueing on one stream.

    Short flushes pad by repeating the last batch (padded outputs are
    dropped), so the compiled step is reused at one shape per
    (closure, capacity, dtypes) key. Outputs are consolidated onto the
    first mesh device: downstream single-stream operators (concat, agg
    state) must not see operands committed to different devices."""

    def __init__(self, mesh: Mesh, axis: Optional[str] = None):
        assert len(mesh.axis_names) == 1, (
            f"ShardedFusedRunner needs a 1-D mesh, got {mesh.axis_names}")
        self.mesh = mesh
        self.axis = axis or mesh.axis_names[0]
        self.n = mesh.shape[self.axis]
        self.devices = list(mesh.devices.flat)
        self._wrapped: dict = {}  # id(fn) -> (fn ref, shard_map'd closure)
        self.dispatches = 0

    def _wrap(self, fn):
        hit = self._wrapped.get(id(fn))
        if hit is not None:
            return hit[1]

        axis = self.axis

        def body(datas, valids, nrows):
            out = fn(tuple(d[0] for d in datas),
                     tuple(v[0] for v in valids), nrows[0])
            # re-add the leading per-device axis so out_specs=P(axis)
            # reassembles one global row per batch
            return jax.tree_util.tree_map(lambda a: a[None], out)

        wrapped = jax.jit(shard_map(
            body, mesh=self.mesh,
            in_specs=(P(axis), P(axis), P(axis)),
            out_specs=P(axis)))
        # hold fn so the id() key cannot be reused by a reclaimed closure
        self._wrapped[id(fn)] = (fn, wrapped)
        return wrapped

    def dispatch(self, fn, batch_datas, batch_valids, batch_nrows):
        """``batch_datas[i]``/``batch_valids[i]``: per-batch tuples of
        (capacity,) column planes; ``batch_nrows[i]``: that batch's row
        count. Returns ``(outs, compiled)`` where ``outs[i]`` is exactly
        what ``fn(datas, valids, nrows)`` returns for batch i, with every
        leaf committed to the first mesh device."""
        from jax.sharding import NamedSharding

        from blaze_tpu.core import kernels

        k = len(batch_datas)
        if k < self.n:  # pad with the tail batch; outputs dropped below
            batch_datas = list(batch_datas) + [batch_datas[-1]] * (self.n - k)
            batch_valids = list(batch_valids) + \
                [batch_valids[-1]] * (self.n - k)
            batch_nrows = list(batch_nrows) + \
                [batch_nrows[-1]] * (self.n - k)
        sharding = NamedSharding(self.mesh, P(self.axis))
        devs = self.devices

        def gput(per_batch):
            per_batch = [jnp.asarray(a) for a in per_batch]
            shards = [jax.device_put(a[None], devs[j])
                      for j, a in enumerate(per_batch)]
            return jax.make_array_from_single_device_arrays(
                (self.n,) + per_batch[0].shape, sharding, shards)

        ncols = len(batch_datas[0])
        gdatas = tuple(gput([bd[i] for bd in batch_datas])
                       for i in range(ncols))
        gvalids = tuple(gput([bv[i] for bv in batch_valids])
                        for i in range(ncols))
        gnrows = gput([jnp.asarray(nr, jnp.int64) for nr in batch_nrows])
        out, compiled = kernels.fused_dispatch(
            self._wrap(fn), gdatas, gvalids, gnrows)
        self.dispatches += 1
        # consolidate onto one device, then slice per batch: downstream
        # operators mix these leaves with driver-created arrays and jax
        # refuses ops across different committed devices
        dev0 = devs[0]
        out0 = jax.tree_util.tree_map(
            lambda a: jax.device_put(a, dev0), out)
        outs = [jax.tree_util.tree_map(lambda a, i=i: a[i], out0)
                for i in range(k)]
        return outs, compiled


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
