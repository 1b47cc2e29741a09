"""Jitted whole-batch device kernels for the batch plumbing hot path.

The reference's operator layer moves rows with vectorized Rust loops
(``arrow/selection.rs`` interleave/take, ``arrow/coalesce.rs``). The JAX
equivalent must avoid *eager* per-column jax.numpy dispatch — profiling shows
each un-jitted gather costs ~2-5ms of trace/dispatch overhead, dwarfing the
actual work at batch sizes. These kernels take ALL of a batch's device
columns at once as a pytree, so one ``jax.jit`` dispatch moves the whole
batch; jit's cache is keyed by (pytree structure, shapes, dtypes), and the
capacity-bucket discipline (config.capacity_for) makes those recur.

Rows move in one of two ways, chosen by what the caller knows of its
indexes: a contiguous move (``concat_planes``, ``slice_planes``) copies
slices and gathers nothing; a permutation move (``gather_planes``,
``compact_planes``, ``ops/sort.sort_take``, the joins' and the aggregation's
kernels) is ONE gather of the planes laid side by side as a matrix of 32-bit
words (``take_rows_traced``). No mover gathers a plane at a time: on the TPU
a gather costs by the index, 0.94 ms for each 32-bit plane at 131,072
indexes (PERF.md §6, PR 27 and PR 32).
"""

from __future__ import annotations

import functools
import math
import time
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


_TM = None


def _telemetry():
    # lazy so kernels.py stays importable before the registry (and to keep
    # module import free of blaze_tpu deps beyond jax)
    global _TM
    if _TM is None:
        from blaze_tpu.obs.telemetry import get_registry

        reg = get_registry()
        _TM = (
            reg,
            reg.histogram("blaze_kernel_dispatch_seconds",
                          "jitted kernel dispatch wall time"),
            reg.counter("blaze_kernel_jit_compile_total",
                        "dispatches that grew a jit cache (trace+compile)"),
            reg.histogram("blaze_kernel_jit_compile_seconds",
                          "wall time of compiling dispatches"),
            reg.counter("blaze_kernel_jit_cache_hits_total",
                        "fused-stage dispatches served from the jit cache"),
            reg.counter("blaze_kernel_jit_cache_misses_total",
                        "fused-stage dispatches that had to trace+compile"),
        )
    return _TM


def _dispatch(fn, *args, **kw):
    """Run one jitted kernel dispatch. What is timed here is the call of the
    jitted function: on an asynchronous backend that is the ENQUEUE (trace,
    compile where the jit cache grows, and the hand-off to the launch
    queue), not the kernel's run — the wait for the result is the ``sync:*``
    span of ``utils/device.wait_int`` (benchmark: ``device_wait_s``).

    Two readers take that time: with tracing enabled each dispatch is a
    ``kernel:<fn>`` span (the benchmark's ``idle_gaps`` names gaps by it);
    one that grew the jit cache (a fresh trace+compile) is labelled
    jit_compile instead — compile storms show up as wide blocks in the
    Perfetto timeline. The registry always gets
    ``blaze_kernel_dispatch_seconds`` and the compile counters (kernel spans
    would flood the flight-recorder ring, so those stay trace-gated).
    ``DEVICE_STATS.kernel_calls`` counts the dispatch. The span says what
    was enqueued (``operands``: the array leaves of the call's arguments;
    benchmark: ``enqueue_operands``)."""
    from blaze_tpu.obs.tracer import TRACER
    from blaze_tpu.utils.device import DEVICE_STATS

    reg, tm_dispatch, tm_jit, tm_jit_secs = _telemetry()[:4]
    trace = TRACER.enabled
    track = reg.enabled
    cache0 = -1
    if trace or track:
        try:
            cache0 = fn._cache_size()
        except Exception:
            cache0 = -1
    DEVICE_STATS.add_kernel_call()
    operands = _operands(args, kw) if trace else 0
    t0 = time.perf_counter()
    out = fn(*args, **kw)
    dt = time.perf_counter() - t0
    if trace or track:
        compiled = False
        if cache0 >= 0:
            try:
                compiled = fn._cache_size() > cache0
            except Exception:
                compiled = False
        if track:
            tm_dispatch.observe(dt)
            if compiled:
                tm_jit.inc()
                tm_jit_secs.observe(dt)
        if trace:
            name = getattr(fn, "__name__", None) or \
                getattr(getattr(fn, "__wrapped__", None), "__name__", "kernel")
            now = time.perf_counter_ns()
            TRACER.complete("jit_compile:" + name if compiled else name,
                            "kernel", now - int(dt * 1e9), int(dt * 1e9),
                            {"compiled": compiled, "operands": operands})
    return out


def _operands(*trees) -> int:
    """Array leaves of a dispatch's arguments: what the call flattens and
    hands to the runtime (counted under tracing only)."""
    return sum(1 for leaf in jax.tree_util.tree_leaves(trees)
               if hasattr(leaf, "dtype"))


def fused_dispatch(fn, *args):
    """Dispatch one fused-stage closure (its ``kernel:fused_stage`` span and
    its ``blaze_kernel_dispatch_seconds`` reading time the ENQUEUE on the
    chip; the wait is ``sync:*`` / ``device_wait_s``, see :func:`_dispatch`)
    and report whether it hit the jit cache. Unlike :func:`_dispatch`, the
    cache-size sample is unconditional: the fused-stage hit/miss counters are
    a fast-path tripwire (a recompile storm must be visible without tracing).
    Returns ``(out, compiled)``."""
    from blaze_tpu.obs.tracer import TRACER
    from blaze_tpu.utils.device import DEVICE_STATS

    reg, tm_dispatch, _, tm_jit_secs, tm_hit, tm_miss = _telemetry()
    try:
        cache0 = fn._cache_size()
    except Exception:
        cache0 = -1
    DEVICE_STATS.add_kernel_call()
    trace = TRACER.enabled
    operands = _operands(args) if trace else 0
    t0 = time.perf_counter()
    out = fn(*args)
    dt = time.perf_counter() - t0
    compiled = False
    if cache0 >= 0:
        try:
            compiled = fn._cache_size() > cache0
        except Exception:
            compiled = False
    if reg.enabled:
        tm_dispatch.observe(dt)
        if compiled:
            tm_miss.inc()
            tm_jit_secs.observe(dt)
        else:
            tm_hit.inc()
    if trace:
        now = time.perf_counter_ns()
        TRACER.complete(
            "jit_compile:fused_stage" if compiled else "fused_stage",
            "kernel", now - int(dt * 1e9), int(dt * 1e9),
            {"compiled": compiled, "operands": operands})
    return out, compiled


@jax.jit
def _gather(datas, valids, idx, live):
    with jax.named_scope("move"):
        return take_rows_traced(datas, valids, idx, live)


@jax.jit
def _gather_n(datas, valids, idx, n_out):
    live = jnp.arange(idx.shape[0], dtype=jnp.int32) < n_out
    with jax.named_scope("move"):
        return take_rows_traced(datas, valids, idx, live)


def gather_planes(datas: Sequence[jax.Array], valids: Sequence[jax.Array],
                  idx: np.ndarray, out_cap: int, n_out: int,
                  null_mask: np.ndarray = None):
    """Rows ``idx`` of every (data, validity) plane in ONE jitted dispatch and
    ONE device gather: the planes travel side by side as a matrix of 32-bit
    words (:func:`take_rows_traced`).

    ``idx`` is a host array of length n_out (already < num_rows, so it goes
    up as int32); rows where ``null_mask`` is True come out null (outer-join
    extension). The common no-null-mask case computes the live prefix mask
    ON DEVICE from the traced count — uploading it was a capacity-sized
    host->device transfer per call carrying information already present in
    one scalar."""
    buf = np.zeros(out_cap, dtype=np.int32)
    buf[:n_out] = idx
    if null_mask is None:
        return _dispatch(_gather_n, tuple(datas), tuple(valids),
                         jnp.asarray(buf), jnp.int32(n_out))
    lbuf = np.zeros(out_cap, dtype=bool)
    lbuf[:n_out] = ~null_mask
    return _dispatch(_gather, tuple(datas), tuple(valids), jnp.asarray(buf), jnp.asarray(lbuf))


# The word matrix of `take_rows_traced` at most this large (a row of it laid to
# sublanes of 8 words): at 1,048,576 rows 13 words (64 MiB) gather in 7.5 ms
# and 25 words (128 MiB) in 42; in two matrices the 25 take what two gathers
# take (PERF.md section 6, PR 32). The width alone costs nothing: 143 and 263
# words at 131,072 rows read 8.7 and 16.3 ms, 0.06 ms a word as at 25.
_WORD_MATRIX_BYTES = 64 << 20


def take_rows_traced(datas, valids, idx, live):
    """Traced: rows ``idx`` of every (data, validity) plane where ``live``,
    else the padding contract (data 0, validity False), with ONE gather for
    all the planes — how every mover of this module and every operator's
    kernel applies a permutation. On the TPU a gather costs by the index,
    not by what each index fetches: at 131,072 rows six int64 planes
    gathered one by one (twelve gathers, a 64-bit plane being two 32-bit
    ones) read 11.4 ms, and the same planes laid side by side as one matrix
    of 32-bit words, a row an index, 0.49 ms (PERF.md §6, PR 27). So every
    data plane is cut into uint32 words (8 bytes: two, bit for bit, so NaN
    payloads and -0.0 survive; 4 bytes: one; narrower: widened to int32),
    the validity planes are bit-packed 32 to a word, and the words of a row
    travel together — in one matrix up to ``_WORD_MATRIX_BYTES``, past it
    in as few as stay under it, by a rule on the static shapes alone.
    Planes of different capacities are cut to the shortest: ``idx`` names
    rows below the batch's row count, which every plane holds."""
    if not datas and not valids:
        return (), ()
    cap = min(p.shape[0] for p in (*datas, *valids))
    # the dtype each data plane is bitcast from: itself, or int32 if narrower
    wide = [jnp.int32 if d.dtype.itemsize < 4 else d.dtype for d in datas]
    blocks = [lax.bitcast_convert_type(d[:cap].astype(w), jnp.uint32)
              .reshape(cap, -1) for d, w in zip(datas, wide)]
    for at in range(0, len(valids), 32):
        word = jnp.zeros(cap, jnp.uint32)
        for bit, v in enumerate(valids[at:at + 32]):
            word = word | (v[:cap].astype(jnp.uint32) << bit)
        blocks.append(word[:, None])
    # one matrix while it stays small: the words go in groups, a gather each
    width = max(8, _WORD_MATRIX_BYTES // (4 * cap) // 8 * 8)
    place, groups, used = [], [[]], 0  # place: a block's (matrix, first word)
    for block in blocks:
        if used and used + block.shape[1] > width:
            groups.append([])
            used = 0
        place.append((len(groups) - 1, used))
        groups[-1].append(block)
        used += block.shape[1]
    matrices = [jnp.concatenate(group, axis=1) for group in groups]
    idx = jnp.clip(idx, 0, cap - 1)
    if idx.dtype.itemsize > 4:  # a capacity fits int32; the gather follows it
        idx = idx.astype(jnp.int32)
    rows = []
    for matrix in matrices:
        got = matrix[idx]
        rows.append(jnp.where(live[:, None], got, jnp.uint32(0)))
    out_d = []
    for d, w, block, (g, at) in zip(datas, wide, blocks, place):
        words = rows[g][:, at:at + block.shape[1]]
        out_d.append(lax.bitcast_convert_type(
            words if block.shape[1] == 2 else words[:, 0], w).astype(d.dtype))
    out_v = []
    for bit in range(len(valids)):
        g, at = place[len(datas) + bit // 32]
        out_v.append(((rows[g][:, at] >> (bit % 32)) & 1).astype(bool))
    return tuple(out_d), tuple(out_v)


@jax.jit
def _compact(datas, valids, mask):
    iota = jnp.arange(mask.shape[0], dtype=jnp.int32)
    with jax.named_scope("order"):
        # the kept rows' places, in row order: the payload of a stable
        # two-operand sort of the mask (PERF.md §6, PR 27: 0.4 ms at 131,072
        # rows, where a scatter of the prefix sums reads 0.7)
        count = jnp.sum(mask)
        _, order = lax.sort(((~mask).astype(jnp.uint8), iota), num_keys=1,
                            is_stable=True)
        live = iota < count
    with jax.named_scope("move"):
        out_d, out_v = take_rows_traced(datas, valids, order, live)
    return count, out_d, out_v


def compact_planes(datas: Sequence[jax.Array], valids: Sequence[jax.Array],
                   mask: jax.Array):
    """Stable device-side compaction of rows where ``mask`` holds (FilterExec
    hot path): one dispatch + one scalar sync for the surviving-row count."""
    from blaze_tpu.utils.device import wait_int

    count, out_d, out_v = _dispatch(_compact, tuple(datas), tuple(valids), mask)
    return wait_int(count, "compact"), out_d, out_v


def _mask_planes(datas, valids, live):
    """The padding contract over planes already in place: data 0 and
    validity False where ``live`` is not set."""
    return (tuple(jnp.where(live, d, jnp.zeros((), d.dtype)) for d in datas),
            tuple(v & live for v in valids))


@functools.partial(jax.jit, static_argnames=("out_cap",))
def _dyn_slice(datas, valids, offset, length, out_cap):
    # lax.dynamic_slice CLAMPS its start whenever offset + out_cap passes the
    # plane's end, silently returning another window; over a plane padded by
    # out_cap rows it cannot (offset <= capacity), and the pad fuses into the
    # slice. A copy, not a gather: no index is built or followed.
    def window(plane):
        padded = jnp.concatenate(
            [plane, jnp.zeros((out_cap,), plane.dtype)])
        return lax.dynamic_slice(padded, (offset,), (out_cap,))

    with jax.named_scope("copy"):
        datas = tuple(window(d) for d in datas)
        valids = tuple(window(v) for v in valids)
    with jax.named_scope("mask"):
        live = jnp.arange(out_cap, dtype=jnp.int32) < length
        return _mask_planes(datas, valids, live)


def slice_planes(datas: Sequence[jax.Array], valids: Sequence[jax.Array],
                 offset: int, length: int, out_cap: int):
    """Contiguous row window in ONE jitted dispatch, a slice copy a plane;
    offset/length are traced so every slice of the same shapes reuses one
    compiled program."""
    return _dispatch(_dyn_slice, tuple(datas), tuple(valids),
                     jnp.int32(offset), jnp.int32(length), out_cap=out_cap)


def _key_ops_traced(datas, valids, exists, spec):
    """Traced body shared by the sort-operand and range-partition kernels.

    Emits [rank0, val0, rank1, val1, ...] where rank is a u8 total-order
    class and val is the native-dtype payload, already direction-adjusted.
    NaNs are FOLDED into the rank (value zeroed) so plain IEEE compares —
    not just lax.sort's total-order comparator — see the same ordering:
      0 = null (nulls first)        1 = NaN under descending
      2 = valid                     3 = NaN under ascending
      4 = null (nulls last)         6 = padding row (always last)
    """
    ops = []
    for (ascending, nulls_first), data, validity in zip(spec, datas, valids):
        validity = validity & exists
        if jnp.issubdtype(data.dtype, jnp.floating):
            nan = jnp.isnan(data)
            val = jnp.where(nan | ~validity, jnp.zeros((), data.dtype), data)
            if not ascending:
                val = -val
            rank = jnp.where(nan, 3 if ascending else 1, 2)
        elif data.dtype == jnp.bool_:
            val = data.astype(jnp.uint8)
            if not ascending:
                val = jnp.uint8(1) - val
            val = jnp.where(validity, val, jnp.zeros((), jnp.uint8))
            rank = 2
        else:
            val = data if ascending else ~data
            val = jnp.where(validity, val, jnp.zeros((), val.dtype))
            rank = 2
        rank = jnp.where(validity, rank, 0 if nulls_first else 4)
        rank = jnp.where(exists, rank, 6).astype(jnp.uint8)
        ops.append(rank)
        ops.append(val)
    return tuple(ops)


def _dense_ranks(words):
    """(k, n) uint64 -> (k, n) int32: every line's values replaced by their
    dense ranks (equal values, equal ranks; the order kept). One batched
    two-operand sort, a prefix sum over the sorted values' changes, and a
    second batched sort that carries the ranks back to the rows' places."""
    k, n = words.shape
    iota = lax.broadcasted_iota(jnp.int32, (k, n), 1)
    sorted_words, perm = lax.sort((words, iota), dimension=1, num_keys=1,
                                  is_stable=False)
    new = jnp.concatenate(
        [jnp.zeros((k, 1), bool), sorted_words[:, 1:] != sorted_words[:, :-1]],
        axis=1)
    rank = jnp.cumsum(new, axis=1, dtype=jnp.int32)
    return lax.sort((perm, rank), dimension=1, num_keys=1, is_stable=False)[1]


# classes a column of `lex_order_traced` may give its rows: -_LEX_CLASSES..-1
# before the rows that compare by their word (class 0), 1.._LEX_CLASSES after
_LEX_CLASSES = 4


def lex_order_traced(columns):
    """Traced: the order of ``n`` rows by several columns, lexicographically,
    ties in row order — what ``lax.sort`` over all the columns at once gives,
    built from two-operand sorts only. On the TPU a sort's compile time grows
    faster than its operand count (a 64-bit word is two): a million rows on
    three nullable int64 keys compile for 400 s as one ten-operand sort and
    for half a minute this way, and run in about 22 ms for 8 (PERF.md §6,
    PR 26).

    A column is ``(word, cls)``: ``word`` a uint64 plane, ``cls`` a plane of
    small integers or None. Rows of class 0 (all rows, for None) order by
    their word; rows of a negative class stand before them and rows of a
    positive class after, ordered by class alone (|cls| <= ``_LEX_CLASSES``).
    Every word is replaced by its dense rank (`_dense_ranks`, all columns in
    one batched sort), the ranks and classes are packed into uint64 words, as
    many columns a word as fit, and while that is more than one word the
    words are ranked and packed again. Returns ``(order, key)``: the row
    standing at each position (int32), and the last packed word in that
    order — equal exactly where all columns are."""
    n = columns[0][0].shape[0]
    assert n < 1 << 30  # two columns a word at least, so the packing ends
    rank_bits = max(1, (n - 1).bit_length())
    ranks = _dense_ranks(jnp.stack([w for w, _ in columns]))
    codes = []
    for line, (_, cls) in zip(ranks, columns):
        if cls is None:
            codes.append((line.astype(jnp.uint64), rank_bits))
            continue
        c = cls.astype(jnp.int32)
        code = jnp.where(c < 0, c + _LEX_CLASSES, jnp.where(
            c == 0, line + _LEX_CLASSES, c + (n + _LEX_CLASSES - 1)))
        codes.append((code.astype(jnp.uint64),
                      (n + 2 * _LEX_CLASSES - 1).bit_length()))
    while True:
        words, used = [], 64
        for code, bits in codes:
            if used + bits > 64:
                words.append(code)
                used = bits
            else:
                words[-1] = (words[-1] << bits) | code
                used += bits
        if len(words) == 1:
            break
        codes = [(line.astype(jnp.uint64), rank_bits)
                 for line in _dense_ranks(jnp.stack(words))]
    key, order = lax.sort((words[0], jnp.arange(n, dtype=jnp.int32)),
                          num_keys=2, is_stable=False)
    return order, key


@functools.partial(jax.jit, static_argnames=("spec",))
def _key_ops(datas, valids, exists, spec):
    return _key_ops_traced(datas, valids, exists, spec)


def sort_key_operands(datas, valids, exists, spec):
    """All sort keys of a batch normalized in ONE jitted dispatch (replaces
    the former per-key eager jnp chain in ops/sort_keys.key_operands). The
    jit cache is keyed by (pytree structure, shapes, dtypes, spec) — spec is
    the static per-key (ascending, nulls_first) tuple."""
    return list(_dispatch(_key_ops, tuple(datas), tuple(valids), exists, spec))


def _lex_le_count(ops, bound_ops):
    """(rows,) count of bounds whose key tuple is <= the row's key tuple —
    bisect_right over B bounds via a broadcast lt/eq cascade."""
    nb = bound_ops[0].shape[0]
    rows = ops[0].shape[0]
    lt = jnp.zeros((rows, nb), dtype=jnp.bool_)
    eq = jnp.ones((rows, nb), dtype=jnp.bool_)
    for o, b in zip(ops, bound_ops):
        bb = b[None, :]
        oo = o[:, None]
        lt |= eq & (bb < oo)
        eq &= bb == oo
    return jnp.sum(lt | eq, axis=1)


@functools.partial(jax.jit, static_argnames=("spec",))
def _range_pids(datas, valids, exists, bound_ops, spec):
    ops = _key_ops_traced(datas, valids, exists, spec)
    pid = _lex_le_count(ops, bound_ops).astype(jnp.int32)
    # padding rows park past the last real partition so a pid-sorted batch
    # keeps them out of every partition slice
    return jnp.where(exists, pid, jnp.int32(bound_ops[0].shape[0] + 1))


def range_partition_ids(datas, valids, exists, bound_ops, spec):
    """Row-order partition ids for range partitioning, ONE jitted dispatch:
    key normalization + device searchsorted against resident bounds."""
    return _dispatch(_range_pids, tuple(datas), tuple(valids), exists,
                     tuple(bound_ops), spec)


@functools.partial(jax.jit, static_argnames=("out_cap",))
def _concat_gather(datas, valids, offsets, out_cap, starts=None):
    # (the name is the trace's: the benchmark's breakdown reads it; nothing
    # is gathered.) Part j's live rows are a prefix of it and land at
    # offsets[j], so the parts are written whole, in order, each over the
    # padding tail of the one before: offsets[j] + cap_j never passes the sum
    # of the capacities, so no update clamps. offsets[-1] is the row total.
    # With ``starts`` (else None: another program) part j's live rows begin
    # at its row starts[j] — a window of a batch the exchange routed — and
    # the part is read from there, padded by its own length so that the
    # read cannot clamp; what follows a window's rows is other rows, not
    # padding, and is written over or masked all the same.
    def lay(parts):
        rows = max(out_cap, sum(p.shape[0] for p in parts))
        buf = jnp.zeros((rows,), parts[0].dtype)
        for j, part in enumerate(parts):
            if starts is not None:
                part = lax.dynamic_slice(
                    jnp.concatenate([part, jnp.zeros_like(part)]),
                    (starts[j],), part.shape)
            buf = lax.dynamic_update_slice(buf, part, (offsets[j],))
        return buf[:out_cap]

    with jax.named_scope("copy"):
        datas = tuple(lay(parts) for parts in datas)
        valids = tuple(lay(parts) for parts in valids)
    with jax.named_scope("mask"):
        live = jnp.arange(out_cap, dtype=jnp.int32) < offsets[-1]
        return _mask_planes(datas, valids, live)


# -- segmented scans ---------------------------------------------------------
#
# Shared by the window operator (and usable by rollup/partial-agg): group
# structure arrives as a boundary MASK over pre-sorted rows, never as control
# flow. Every helper supports a "carry" so a segment spanning batch boundaries
# continues from the previous batch's accumulators instead of forcing the
# caller to buffer the open segment.


def seg_start_index(seg_start: np.ndarray) -> np.ndarray:
    """Per-row index of the most recent True in ``seg_start`` at or before
    the row; -1 for head rows that continue a segment carried in from the
    previous batch."""
    n = len(seg_start)
    idx = np.arange(n, dtype=np.int64)
    return np.maximum.accumulate(np.where(seg_start, idx, np.int64(-1)))


def restarting_counters(part_start: np.ndarray, new_peer: np.ndarray,
                        carry_rn: int = 0, carry_rank: int = 1,
                        carry_dense: int = 0):
    """row_number / rank / dense_rank as restart-at-segment prefix scans.

    ``part_start``/``new_peer`` are boundary masks over rows pre-sorted by
    (partition, order); every partition start must also be a peer start.
    Carries seed rows belonging to the partition left open by the previous
    batch: carry_rn = its last row_number, carry_rank = the rank of its open
    peer group, carry_dense = its last dense_rank."""
    n = len(part_start)
    idx = np.arange(n, dtype=np.int64)
    psi = seg_start_index(part_start)
    rn = np.where(psi >= 0, idx - psi + 1, idx + 1 + carry_rn)
    ppi = seg_start_index(new_peer)
    rank = np.where(ppi >= 0, rn[np.clip(ppi, 0, None)], carry_rank)
    c = np.cumsum(new_peer.astype(np.int64))
    base = np.where(psi >= 0, c[np.clip(psi, 0, None)] - 1,
                    np.int64(-carry_dense))
    dense = c - base
    return rn, rank, dense


def segment_cumsum(vals: np.ndarray, valid: np.ndarray,
                   seg_start: np.ndarray, carry_sum=0, carry_cnt: int = 0):
    """Inclusive per-row (sum, count) of ``vals`` masked by ``valid``,
    restarting at every True in ``seg_start``; head rows continue the carried
    accumulators. Works on numeric AND object (Decimal) planes — one global
    cumsum with per-segment base subtraction, no per-group loop."""
    n = len(vals)
    masked = np.where(valid, vals, 0)
    cs = np.cumsum(masked)
    cc = np.cumsum(valid.astype(np.int64))
    si = seg_start_index(seg_start)
    prev = np.clip(si - 1, 0, None)
    out_s = cs - np.where(si >= 1, cs[prev], 0)
    out_c = cc - np.where(si >= 1, cc[prev], 0)
    head = si < 0
    if head.any():
        out_s[head] += carry_sum
        out_c[head] += carry_cnt
    return out_s, out_c


def segment_running_reduce(vals: np.ndarray, valid: np.ndarray,
                           seg_start: np.ndarray, is_min: bool, carry=None):
    """Per-row running min/max within segments (restarting at ``seg_start``),
    invalid rows transparent; ``carry`` (or None) is the extremum of the open
    head segment. Min/max is not invertible, so instead of base subtraction
    this runs log2(n) masked Hillis-Steele doubling passes — still fully
    vectorized. Rows whose running count is 0 hold an identity sentinel
    (numeric) or None (object); callers null them out via the paired count."""
    n = len(vals)
    si = seg_start_index(seg_start)
    begin = np.where(si >= 0, si, 0)
    if vals.dtype == object:
        def _comb2(a, b):
            if a is None:
                return b
            if b is None:
                return a
            return min(a, b) if is_min else max(a, b)
        comb = np.frompyfunc(_comb2, 2, 1)
        out = np.where(valid, vals, None)
    else:
        if np.issubdtype(vals.dtype, np.floating):
            sent = np.array(np.inf if is_min else -np.inf, dtype=vals.dtype)
        else:
            info = np.iinfo(vals.dtype)
            sent = np.array(info.max if is_min else info.min, dtype=vals.dtype)
        comb = np.minimum if is_min else np.maximum
        out = np.where(valid, vals, sent)
    idx = np.arange(n, dtype=np.int64)
    off = 1
    while off < n:
        ok = idx - off >= begin
        if not ok.any():
            break
        out = np.where(ok, comb(out, out[np.clip(idx - off, 0, None)]), out)
        off <<= 1
    head = si < 0
    if carry is not None and head.any():
        out[head] = comb(out[head], carry)
    return out


@jax.jit
def _seg_scan(data, validity, exists, seg_start, carry_sum, carry_cnt):
    n = data.shape[0]
    idx = jnp.arange(n, dtype=jnp.int64)
    si = lax.cummax(jnp.where(seg_start, idx, jnp.int64(-1)), axis=0)
    if jnp.issubdtype(data.dtype, jnp.integer):
        data = data.astype(jnp.int64)  # match numpy's cumsum promotion
    validity = validity & exists
    masked = jnp.where(validity, data, jnp.zeros((), data.dtype))
    cs = jnp.cumsum(masked)
    cc = jnp.cumsum(validity.astype(jnp.int64))
    prev = jnp.clip(si - 1, 0, None)
    out_s = cs - jnp.where(si >= 1, cs[prev], jnp.zeros((), cs.dtype))
    out_c = cc - jnp.where(si >= 1, cc[prev], 0)
    head = si < 0
    out_s = out_s + jnp.where(head, carry_sum.astype(cs.dtype),
                              jnp.zeros((), cs.dtype))
    out_c = out_c + jnp.where(head, carry_cnt, 0)
    return out_s, out_c


def segment_scan_planes(data: jax.Array, validity: jax.Array,
                        exists: jax.Array, seg_start: np.ndarray,
                        carry_sum, carry_cnt: int):
    """Device-resident segmented (sum, count) scan in ONE jitted dispatch.

    A ``jax.ops.segment_sum`` formulation would key the jit cache on the
    dynamic per-batch segment count and recompile constantly; this cumsum +
    cummax-restart form is shape-stable (capacity buckets recur). seg_start
    has batch length n <= capacity and is padded here; padding rows carry
    exists False so they never perturb prefixes below n. Returns numpy
    (sum, count) planes for host-side frame backfill."""
    cap = data.shape[0]
    n = len(seg_start)
    pad = np.zeros(cap, dtype=bool)
    pad[:n] = seg_start
    cdt = data.dtype if jnp.issubdtype(data.dtype, jnp.floating) else jnp.int64
    out_s, out_c = _dispatch(
        _seg_scan, data, validity, exists, jnp.asarray(pad),
        jnp.asarray(carry_sum, dtype=cdt), jnp.int64(carry_cnt))
    return np.asarray(out_s)[:n], np.asarray(out_c)[:n]


# -- traced twins of the segmented scans --------------------------------------
#
# The same contracts inside a jitted program (``ops/window.py``'s
# ``jit(window_scan)``): masks and planes are device arrays of one capacity,
# carries are device scalars, and a segment restarts by the scan's own
# operator — no ``cs[prev]`` gather a plane (PERF.md section 6, PR 27), no
# scatter. A carry SEEDS row 0 when row 0 continues the segment the previous
# batch left open (``seg_start[0]`` unset), which is all "head rows continue
# the carried accumulators" needs: the scan carries the seed forward.


def segmented_scan_traced(combine, new, planes):
    """Inclusive scan of a tuple of row planes that restarts wherever ``new``
    is set: every row reads ``combine`` (associative, over such tuples) of
    its segment's rows up to itself, so a segment's last row reads the
    segment's."""

    def step(a, b):
        merged = combine(a[1:], b[1:])
        return (a[0] | b[0],
                *(jnp.where(b[0], y, m) for y, m in zip(b[1:], merged)))

    return lax.associative_scan(step, (new, *planes))[1:]


def _continues(seg_start):
    """Row 0, where it continues the segment carried in."""
    first = jnp.arange(seg_start.shape[0], dtype=jnp.int32) == 0
    return first & ~seg_start


def _add(a, b):
    return tuple(x + y for x, y in zip(a, b))


def restarting_counters_traced(part_start, new_peer, carry_rn, carry_rank,
                               carry_dense):
    """:func:`restarting_counters` traced: int64 (row_number, rank,
    dense_rank) planes from the two boundary masks and the three carried
    scalars."""
    cont = _continues(part_start)
    zero = jnp.int64(0)
    rn, dense = segmented_scan_traced(_add, part_start, (
        jnp.where(cont, carry_rn + 1, jnp.int64(1)),
        new_peer.astype(jnp.int64) + jnp.where(cont, carry_dense, zero)))
    # rank: the row number at the row's peer-group start; row numbers rise
    # within a partition, so that is a running maximum
    at_peer = jnp.where(new_peer, rn, jnp.where(cont, carry_rank, zero))
    (rank,) = segmented_scan_traced(
        lambda a, b: (jnp.maximum(a[0], b[0]),), part_start, (at_peer,))
    return rn, rank, dense


def segment_cumsum_traced(vals, valid, seg_start, carry_sum, carry_cnt):
    """:func:`segment_cumsum` traced, for an integer plane: int64 (sum,
    count) planes, wrapping as int64 does."""
    cont = _continues(seg_start)
    zero = jnp.int64(0)
    vals = jnp.where(valid, vals.astype(jnp.int64), zero)
    return segmented_scan_traced(_add, seg_start, (
        vals + jnp.where(cont, carry_sum, zero),
        valid.astype(jnp.int64) + jnp.where(cont, carry_cnt, zero)))


_LOW32 = 0xFFFFFFFF


def segment_cumsum_wide_traced(lo, hi, valid, seg_start, carry_lo, carry_hi,
                               carry_cnt):
    """:func:`segment_cumsum` traced and exact for sums wider than int64
    (Spark types SUM(decimal(p, s)) as decimal(p + 10, s)). A value is two
    int64 words, ``hi * 2^64 + uint64(lo)`` (decimal128's own layout; an
    int64 value is ``(v, v >> 63)``). The scan adds the low word's two
    32-bit chunks and the high word apart: below 2^31 rows no chunk's sum can
    wrap, and the carries move up once, at the end. Returns the (lo, hi,
    count) planes; a sum fits int64 where ``hi == lo >> 63``."""
    cont = _continues(seg_start)
    zero, low = jnp.int64(0), jnp.int64(_LOW32)

    def chunks(lo, hi, keep):
        return tuple(jnp.where(keep, x, zero)
                     for x in (lo & low, (lo >> 32) & low, hi))

    seed = chunks(carry_lo, carry_hi, cont)
    l0, l1, l2, cnt = segmented_scan_traced(_add, seg_start, (
        *_add(chunks(lo, hi, valid), seed),
        valid.astype(jnp.int64) + jnp.where(cont, carry_cnt, zero)))
    l1 = l1 + (l0 >> 32)
    return ((l1 & low) << 32) | (l0 & low), l2 + (l1 >> 32), cnt


def _lex_before(a, b):
    """Is ``a`` before ``b``? Planes compared in turn, the first signed and
    those after it unsigned (the words of one wide value, highest first)."""
    flip = jnp.int64(-1 << 63)
    before, tied = a[0] < b[0], a[0] == b[0]
    for x, y in zip(a[1:], b[1:]):
        before = before | (tied & ((x ^ flip) < (y ^ flip)))
        tied = tied & (x == y)
    return before


def segment_running_reduce_traced(vals, valid, seg_start, is_min: bool,
                                  carry_vals, carry_has):
    """:func:`segment_running_reduce` traced: the running extremum and a
    plane that says whether the row's segment has held a valid value yet
    (where it has not, the extremum reads 0). ``vals`` and ``carry_vals``
    are tuples of planes and of scalars: one plane for a value the device
    orders itself, (hi, lo) for a wide value in two int64 words."""
    cont = _continues(seg_start)
    seeded = cont & carry_has

    def pick(a, b):
        take_b = _lex_before(b, a) if is_min else _lex_before(a, b)
        return tuple(jnp.where(take_b, y, x) for x, y in zip(a, b))

    vals = tuple(jnp.where(valid, v, jnp.zeros((), v.dtype)) for v in vals)
    seeds = tuple(jnp.broadcast_to(c, v.shape).astype(v.dtype)
                  for c, v in zip(carry_vals, vals))
    vals = tuple(jnp.where(seeded, jnp.where(valid, p, c), v) for v, c, p in
                 zip(vals, seeds, pick(seeds, vals)))

    def combine(a, b):
        (ha, *va), (hb, *vb) = a, b
        both = pick(va, vb)
        return (ha | hb, *(jnp.where(ha & hb, m, jnp.where(hb, y, x))
                           for x, y, m in zip(va, vb, both)))

    has, *ext = segmented_scan_traced(combine, seg_start,
                                      (valid | seeded, *vals))
    return tuple(ext), has


def concat_planes(per_field_datas: List[Tuple[jax.Array, ...]],
                  per_field_valids: List[Tuple[jax.Array, ...]],
                  num_rows: Sequence[int], out_cap: int,
                  starts: Sequence[int] = None):
    """Concatenate k batches' planes field-wise and compact live rows, in ONE
    jitted dispatch of slice copies (replaces the arrow round trip the
    profiler flagged in ColumnarBatch.concat). ``per_field_datas[f]`` is the
    f-th field's array from each input batch; ``num_rows[j]`` is batch j's
    live row count, and ``starts[j]`` the row of it they begin at (None:
    every batch's rows are its prefix). The host sends the k + 1 running row
    totals (and the k starts), nothing capacity-sized."""
    offsets = np.zeros(len(num_rows) + 1, dtype=np.int32)
    np.cumsum(num_rows, out=offsets[1:])
    return _dispatch(
        _concat_gather,
        tuple(tuple(p) for p in per_field_datas),
        tuple(tuple(p) for p in per_field_valids),
        jnp.asarray(offsets), out_cap=out_cap,
        starts=None if starts is None else jnp.asarray(starts, jnp.int32))


# -- radix key partitioning ----------------------------------------------------
# Traced primitives shared by the dense-bucket and radix-partitioned hash
# aggregation kernels (ops/agg_device): integer group keys pack into ONE
# int64 slot code from per-key (base, pow2 size) strides, and the code's
# high bits are the radix bucket id — so dedup, scatter-accumulate, AND the
# per-bucket skew histogram all come out of the same scatter pass. These run
# INSIDE jitted kernels; sizes/strides are static, bases are traced.


def radix_strides(sizes: Sequence[int]) -> Tuple[int, ...]:
    """Row-major mixed-radix strides for per-key bucket sizes (the LAST key
    varies fastest, matching the dense-agg slot layout)."""
    strides = []
    acc = 1
    for s in reversed(sizes):
        strides.append(acc)
        acc *= s
    return tuple(reversed(strides))


def radix_pack(key_data, key_valid, exists, bases, sizes, strides,
               sentinel=None):
    """Traced: pack per-key integer planes into one slot code (seg: int32
    where the sentinel fits it, else int64).

    Per key, code 0 is the null bucket and 1..size-1 map base..base+size-2;
    per-key codes combine mixed-radix via ``strides``. ``bases`` is a traced
    int64 vector so one compiled kernel serves every batch of a stream;
    ``sizes`` and ``strides`` are static, or traced vectors beside a static
    ``sentinel`` above every slot. Returns (seg, fits): padding rows route
    to the sentinel slot, prod(sizes) by default; ``fits`` flips False when
    any existing valid key fell outside its
    range. The in-range test is overflow-safe: ``diff`` wraps when
    |key - base| exceeds 2^63, which could land a far-away key inside
    [0, size) and silently mis-bucket it — requiring d64 >= base AND
    diff >= 0 rejects both the wrapped case (wrapped diff is negative when
    d64 >= base) and key == base-1 (which would collide with the null
    bucket at code 0)."""
    S = math.prod(sizes) if sentinel is None else sentinel
    cap = exists.shape[0]
    seg = jnp.zeros(cap, jnp.int64)
    fits = jnp.bool_(True)
    for i, (d, v) in enumerate(zip(key_data, key_valid)):
        d64 = d.astype(jnp.int64)
        diff = d64 - bases[i]  # wrapping int64
        code = jnp.where(v, diff + jnp.int64(1), jnp.int64(0))
        infit = (d64 >= bases[i]) & (diff >= 0) & (diff < sizes[i] - 1)
        fits = fits & jnp.all(jnp.where(exists & v, infit, True))
        seg = seg + jnp.clip(code, 0, sizes[i] - 1) * strides[i]
    seg = jnp.where(exists, seg, S)
    if S < 1 << 31:
        seg = seg.astype(jnp.int32)
    return seg, fits


def radix_bucket_shift(S: int, nbuck: int) -> Tuple[int, int]:
    """(shift, effective bucket count): a slot code's high bits select its
    radix bucket. S and nbuck are powers of two; nbuck clamps to S."""
    nb = min(nbuck, S)
    return (S // nb).bit_length() - 1, nb


def radix_histogram(seg, exists, present, S: int, nbuck: int):
    """Traced per-bucket (rows, groups) histogram from one partial pass:
    ``seg`` routes each existing row to its slot (sentinel S for padding,
    dropped here), ``present`` marks occupied slots. This is the skew
    signal the partial-skipping heuristic and the Perfetto trace consume."""
    shift, nb = radix_bucket_shift(S, nbuck)
    rows = jnp.zeros(nb, jnp.int64).at[seg.astype(jnp.int64) >> shift].add(
        exists.astype(jnp.int64), mode="drop")
    iota_s = jnp.arange(S, dtype=jnp.int64) >> shift
    groups = jnp.zeros(nb, jnp.int64).at[iota_s].add(
        present.astype(jnp.int64), mode="drop")
    return rows, groups
