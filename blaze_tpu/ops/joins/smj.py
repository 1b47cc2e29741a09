"""Sort-merge join: a device merge where the keys live on the device, the
host's vectorized run matching where they do not.

Reference: ``sort_merge_join_exec.rs:57-375`` + ``joins/smj/*.rs`` — cursors
advancing equal-key runs. A literal cursor port pays per run; both inputs
arrive from full-materializing sorts, so buffering a side adds no asymptotic
memory, and the join works on each side's whole partition at once. Rows with
any null key match nothing (Spark equi-join semantics); many-to-many runs
expand to every pair, left-major.

**Device path** — every join key of both sides a fixed-width device column
and no extra ``condition``: three jitted programs a partition, no key column
pulled and no Python per key, run or row. ``jit(smj_probe)`` lays both
sides' canonical key words side by side and orders them once
(``keymap.merge_match_traced``: the encoding is the hash joins'; the order
comes from ``core/kernels.lex_order_traced``, two-operand sorts); prefix
scans over that order give every left row its run's right rows, and prefix
sums of the wanted rows (the pairs, the unmatched or matched rows of a side)
give each output slot its source. One wait (``sync:smj_count``) tells the
host how many rows each kind has; ``jit(smj_pairs)`` / ``jit(smj_rows)``
then fill one output batch a dispatch: the joint position of every slot,
by ONE int32 scatter of each run's first slot and a running max, then ONE
gather a side, its planes side by side (``kernels.take_rows_traced``).
Unmatched and semi/anti rows come out in key order (the input's order, when
it arrives sorted). The operator's metric node counts ``smj_device_joins``.

**Host path** — var-width keys, keys kept on the host (DOUBLE where the
chip has no exact f64), or a ``condition``: each side's key rows are
interned to integer codes once (``keymap.key_codes``), equal-code runs are
found with one boundary mask and paired by code, and matched (left, right)
row indices expand with repeat/arange arithmetic; emission is one gather per
output chunk. Counted as ``smj_host_joins``. Sort DIRECTION never matters on
either path: equal keys are adjacent either way, and they match by equality."""

from __future__ import annotations

import functools
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
from jax import lax

from blaze_tpu.core import kernels as K
from blaze_tpu.core.batch import ColumnarBatch, DeviceColumn, HostColumn
from blaze_tpu.ir import exprs as E
from blaze_tpu.ir import types as T
from blaze_tpu.ir.nodes import JoinType, _join_output_schema
from blaze_tpu.ops.base import Operator
from blaze_tpu.ops.joins import keymap
from blaze_tpu.ops.sort import SortExec
from blaze_tpu.utils.device import wait_array


def _gather_side(batch_iter, schema) -> ColumnarBatch:
    batches = [b for b in batch_iter if b.num_rows]
    if not batches:
        return ColumnarBatch.empty(schema)
    if len(batches) == 1:
        return batches[0]
    return ColumnarBatch.concat(batches, schema)


def _runs(codes: np.ndarray):
    """(start, end, code) per maximal equal-code run of a sorted side."""
    n = len(codes)
    if n == 0:
        e = np.empty(0, dtype=np.int64)
        return e, e, e
    starts = np.flatnonzero(np.concatenate([[True], codes[1:] != codes[:-1]]))
    ends = np.concatenate([starts[1:], [n]]).astype(np.int64)
    return starts.astype(np.int64), ends, codes[starts]


# which rows a join type emits, in emission order: "pairs" are the matched
# (left, right) pairs; the rest select rows of one side from the match flags
_SELECTIONS = {
    JoinType.INNER: ("pairs",),
    JoinType.LEFT: ("pairs", "l_unmatched"),
    JoinType.RIGHT: ("pairs", "r_unmatched"),
    JoinType.FULL: ("pairs", "l_unmatched", "r_unmatched"),
    JoinType.LEFT_SEMI: ("l_matched",),
    JoinType.LEFT_ANTI: ("l_unmatched",),
    JoinType.RIGHT_SEMI: ("r_matched",),
    JoinType.RIGHT_ANTI: ("r_unmatched",),
    JoinType.EXISTENCE: ("l_all",),
}


@functools.partial(jax.jit, static_argnames=("selections",))
def smj_probe(lkeys, rkeys, nl, nr, selections):
    """Match both sides' keys (``keymap.merge_match_traced``) and number the
    rows of every selection: returns the match arrays, per selection the
    inclusive prefix sum of its rows over the joint order, and the counts
    the host waits for (all key-matched pairs first, then each selection's
    rows)."""
    with jax.named_scope("probe"):
        m = keymap.merge_match_traced(lkeys, rkeys, nl, nr)
        l_matched = m["pairs"] > 0
        wanted = {
            "pairs": m["pairs"],
            "l_unmatched": m["is_left"] & ~l_matched,
            "l_matched": l_matched,
            "l_all": m["is_left"],
            "r_unmatched": m["is_right"] & ~m["r_matched"],
            "r_matched": m["r_matched"],
        }
        sums = tuple(jnp.cumsum(wanted[s], dtype=jnp.int64) for s in selections)
        counts = jnp.stack([jnp.sum(m["pairs"], dtype=jnp.int64)]
                           + [c[-1] for c in sums])
    return m["row"], m["run_start"], m["pairs"], sums, counts


def _slots(sums, offset, count, cap):
    """Output slots ``offset .. offset + cap - 1`` of a selection: which are
    live, and the joint position each takes its row from (the first whose
    prefix sum exceeds the slot number).

    Not a binary search of ``sums``: on a TPU v5e that costs a gather of
    the slots a step, 40 of the 48 ms of a launch at q29's shape (PERF.md
    §6). A position's rows are the slots ``sums - w .. sums - 1``. The
    position whose run holds the batch's first slot, and each later one
    whose run starts inside the batch, writes its number at its first slot
    in the batch — ONE int32 scatter of the joint length, every index its
    own — and since positions rise with their runs' starts, a running max
    carries a run's position over the rest of its slots. Slots past
    ``count`` take the last position, as the search's clip gave them."""
    n = sums.shape[0]
    at = jnp.arange(n, dtype=jnp.int32)
    before = jnp.concatenate([jnp.zeros(1, sums.dtype), sums[:-1]])
    first = jnp.maximum(before - offset, 0)  # the run's first slot here
    opens = (sums - offset > first) & (first < cap)
    idx = jnp.where(opens, first, cap + at).astype(jnp.int32)
    pos = lax.cummax(jnp.zeros(cap, jnp.int32).at[idx].set(
        at, mode="drop", unique_indices=True), axis=0)
    slot = offset + jnp.arange(cap, dtype=jnp.int64)
    live = slot < count
    return slot, live, jnp.where(live, pos, n - 1)


@functools.partial(jax.jit, static_argnames=("cap", "cap_r"))
def smj_pairs(row, run_start, pairs, sums, lplanes, rplanes, offset, count,
              cap, cap_r):
    """One output batch of matched pairs, left-major: slot -> (left row,
    right row), then both sides' planes gathered."""
    with jax.named_scope("expand"):
        slot, live, pos = _slots(sums, offset, count, cap)
        nth = slot - (sums[pos] - pairs[pos])  # which right row of the run
        li = row[pos] - cap_r
        ri = row[jnp.clip(run_start[pos] + nth, 0, row.shape[0] - 1)]
    with jax.named_scope("gather"):
        return li, ri, K.take_rows_traced(*lplanes, li, live), \
            K.take_rows_traced(*rplanes, ri, live)


@functools.partial(jax.jit, static_argnames=("cap", "base"))
def smj_rows(row, pairs, sums, planes, offset, count, cap, base):
    """One output batch of a side's selected rows (``base`` is the side's
    first joint row), with the EXISTENCE join's column: has the row a match."""
    with jax.named_scope("expand"):
        _slot, live, pos = _slots(sums, offset, count, cap)
        idx = row[pos] - base
    with jax.named_scope("gather"):
        exists = ((pairs[pos] > 0) & live, live)
        return idx, exists, K.take_rows_traced(*planes, idx, live)


def _key_columns(exprs: List[E.Expr], batch: ColumnarBatch):
    """A side's join-key columns. A plain column reference IS the batch's
    column (its padding rows are invalid by contract); evaluating it would
    cost two eager dispatches a key to mask them again. Anything else goes
    through the expression evaluator."""
    if all(isinstance(e, E.Column) for e in exprs):
        return [batch.columns[batch.schema.index_of(e.name)] for e in exprs]
    from blaze_tpu.exprs.compiler import ExprEvaluator

    return ExprEvaluator(exprs, batch.schema).evaluate(batch)


def _planes(batch: ColumnarBatch):
    cols = [c for c in batch.columns if isinstance(c, DeviceColumn)]
    return tuple(c.data for c in cols), tuple(c.validity for c in cols)


def _side_columns(batch: ColumnarBatch, planes, idx, n: int):
    """Output columns of one side: the gathered device planes, and host
    columns taken with the row indices pulled for them."""
    datas, valids = planes
    host_idx = None
    cols, k = [], 0
    for c in batch.columns:
        if isinstance(c, DeviceColumn):
            cols.append(DeviceColumn(c.dtype, datas[k], valids[k]))
            k += 1
        else:
            if host_idx is None:
                host_idx = wait_array(idx, "smj_rows")[:n].astype(np.int64)
            cols.append(c.take_host(host_idx))
    return cols


def _null_columns(schema, n: int, cap: int):
    """``n`` all-null rows of ``schema`` (an outer join's missing side)."""
    from blaze_tpu.utils.device import is_device_dtype

    return [DeviceColumn(f.dtype, jnp.zeros(cap, f.dtype.np_dtype),
                         jnp.zeros(cap, bool)) if is_device_dtype(f.dtype)
            else HostColumn(f.dtype, pa.nulls(n, T.to_arrow_type(f.dtype)))
            for f in schema.fields]


class SortMergeJoinExec(Operator):
    def __init__(self, left: Operator, right: Operator,
                 on: List[Tuple[E.Expr, E.Expr]], join_type: JoinType,
                 sort_options: Optional[List[Tuple[bool, bool]]] = None,
                 condition: Optional[E.Expr] = None):
        self.on = on
        self.join_type = join_type
        self.sort_options = sort_options or [(True, True)] * len(on)
        # extra non-equi condition over left+right columns (reference: SMJ
        # inequality-join option); key-matched pairs failing it are unmatched
        self.condition = condition
        self._pair_schema = left.schema + right.schema
        schema = _join_output_schema(left.schema, right.schema, join_type)
        super().__init__(schema, [left, right])
        for child in self.children:
            # the join buffers each side whole: take a sort's run as it is
            if isinstance(child, SortExec):
                child.whole_runs = True

    def num_partitions(self):
        return self.children[0].num_partitions()

    def _execute(self, partition, ctx, metrics):
        lbig = _gather_side(self.execute_child(0, partition, ctx, metrics),
                            self.children[0].schema)
        rbig = _gather_side(self.execute_child(1, partition, ctx, metrics),
                            self.children[1].schema)
        lkeys = _key_columns([l for l, _ in self.on], lbig)
        rkeys = _key_columns([r for _, r in self.on], rbig)
        if self.condition is None and all(
                isinstance(c, DeviceColumn) for c in lkeys + rkeys):
            metrics.add("smj_device_joins", 1)
            yield from self._join_on_device(lbig, rbig, lkeys, rkeys, ctx,
                                            metrics)
        else:
            metrics.add("smj_host_joins", 1)
            yield from self._join_on_host(lbig, rbig, lkeys, rkeys, ctx,
                                          metrics)

    def _join_on_device(self, lbig, rbig, lkeys, rkeys, ctx, metrics):
        jt = self.join_type
        selections = _SELECTIONS[jt]
        cap_r = rkeys[0].capacity
        row, run_start, pairs, sums, counts = K._dispatch(
            smj_probe, [(c.data, c.validity) for c in lkeys],
            [(c.data, c.validity) for c in rkeys],
            np.int32(lbig.num_rows), np.int32(rbig.num_rows),
            selections=selections)
        counts = wait_array(counts, "smj_count")
        metrics.add("smj_matched_pairs", int(counts[0]))
        lplanes, rplanes = _planes(lbig), _planes(rbig)
        lschema, rschema = self.children[0].schema, self.children[1].schema
        conf = ctx.conf
        for selection, csum, count in zip(selections, sums, counts[1:]):
            count = int(count)
            step = min(count, conf.batch_size)
            cap = conf.capacity_for(step)
            for offset in range(0, count, step or 1):
                n = min(step, count - offset)
                at = (np.int64(offset), np.int64(count))
                if selection == "pairs":
                    li, ri, lout, rout = K._dispatch(
                        smj_pairs, row, run_start, pairs, csum, lplanes,
                        rplanes, *at, cap=cap, cap_r=cap_r)
                    cols = _side_columns(lbig, lout, li, n) + \
                        _side_columns(rbig, rout, ri, n)
                elif selection.startswith("l_"):
                    idx, exists, out = K._dispatch(
                        smj_rows, row, pairs, csum, lplanes, *at, cap=cap,
                        base=cap_r)
                    cols = _side_columns(lbig, out, idx, n)
                    if jt == JoinType.EXISTENCE:
                        cols.append(DeviceColumn(T.BOOL, *exists))
                    elif jt in (JoinType.LEFT, JoinType.FULL):
                        cols += _null_columns(rschema, n, cap)
                else:
                    idx, _exists, out = K._dispatch(
                        smj_rows, row, pairs, csum, rplanes, *at, cap=cap,
                        base=0)
                    cols = _side_columns(rbig, out, idx, n)
                    if jt in (JoinType.RIGHT, JoinType.FULL):
                        cols = _null_columns(lschema, n, cap) + cols
                yield ColumnarBatch(self.schema, cols, n)

    def _join_on_host(self, lbig, rbig, lkeys, rkeys, ctx, metrics):
        jt = self.join_type
        nl, nr = lbig.num_rows, rbig.num_rows
        emitter = _Emitter(self, ctx.conf.batch_size)
        keep_left_unmatched = jt in (JoinType.LEFT, JoinType.FULL)
        keep_right_unmatched = jt in (JoinType.RIGHT, JoinType.FULL)

        key_map: dict = {}
        lcodes = keymap.key_codes(lbig, lkeys, key_map, insert=True) \
            if nl else np.empty(0, dtype=np.int64)
        rcodes = keymap.key_codes(rbig, rkeys, key_map, insert=False) \
            if nr else np.empty(0, dtype=np.int64)

        rstarts, rends, rrun_codes = _runs(rcodes)
        rrun = {int(c): (int(s), int(e))
                for s, e, c in zip(rstarts, rends, rrun_codes) if c >= 0}

        # per-left-row match window into the right side (one dict lookup per
        # left RUN, not per row; everything after this is array arithmetic)
        match_rs = np.zeros(nl, dtype=np.int64)
        counts = np.zeros(nl, dtype=np.int64)
        r_matched = np.zeros(nr, dtype=bool)
        lstarts, lends, lrun_codes = _runs(lcodes)
        for s, e, c in zip(lstarts, lends, lrun_codes):
            if c < 0:
                continue
            hit = rrun.get(int(c))
            if hit is None:
                continue
            rs, re = hit
            match_rs[s:e] = rs
            counts[s:e] = re - rs
            r_matched[rs:re] = True
        l_matched = counts > 0
        total = int(counts.sum())
        metrics.add("smj_matched_pairs", total)

        # matched pair index expansion, grouped by left row
        li = np.repeat(np.arange(nl, dtype=np.int64), counts)
        excl = np.cumsum(counts) - counts
        ri = np.repeat(match_rs, counts) + \
            (np.arange(total, dtype=np.int64) - np.repeat(excl, counts))

        bs = ctx.conf.batch_size
        cond = self.condition
        if cond is not None and total:
            # re-derive matched flags from pairs that actually pass
            l_matched = np.zeros(nl, dtype=bool)
            r_matched = np.zeros(nr, dtype=bool)
            emit_pairs = jt in (JoinType.INNER, JoinType.LEFT, JoinType.RIGHT,
                                JoinType.FULL)
            for a in range(0, total, bs):
                lic, ric = li[a:a + bs], ri[a:a + bs]
                lout = lbig.take(lic)
                rout = rbig.take(ric)
                pair = ColumnarBatch(self._pair_schema,
                                     lout.columns + rout.columns, len(lic))
                keep = np.asarray(
                    emitter.cond_ev.evaluate_predicate(pair))[:len(lic)]
                l_matched[lic[keep]] = True
                r_matched[ric[keep]] = True
                if emit_pairs and keep.any():
                    kept = pair.take(np.flatnonzero(keep))
                    yield from emitter._push(
                        ColumnarBatch(self.schema, kept.columns,
                                      kept.num_rows))
        elif total and jt in (JoinType.INNER, JoinType.LEFT, JoinType.RIGHT,
                              JoinType.FULL):
            for a in range(0, total, bs):
                lout = lbig.take(li[a:a + bs])
                rout = rbig.take(ri[a:a + bs])
                yield from emitter._push(
                    ColumnarBatch(self.schema, lout.columns + rout.columns,
                                  lout.num_rows))

        # membership join types resolve from the flags, in input order
        if jt == JoinType.LEFT_SEMI:
            yield from emitter._take_push(lbig, np.flatnonzero(l_matched))
        elif jt == JoinType.LEFT_ANTI:
            yield from emitter._take_push(lbig, np.flatnonzero(~l_matched))
        elif jt == JoinType.RIGHT_SEMI:
            yield from emitter._take_push(rbig, np.flatnonzero(r_matched))
        elif jt == JoinType.RIGHT_ANTI:
            yield from emitter._take_push(rbig, np.flatnonzero(~r_matched))
        elif jt == JoinType.EXISTENCE:
            for a in range(0, nl, bs):
                chunk = lbig.take(
                    np.arange(a, min(a + bs, nl), dtype=np.int64))
                yield from emitter._push(
                    emitter._with_exists(chunk, l_matched[a:a + bs]))
        if keep_left_unmatched:
            lun = np.flatnonzero(~l_matched)
            if len(lun):
                yield from emitter.left_unmatched(lbig.take(lun))
        if keep_right_unmatched:
            run_ = np.flatnonzero(~r_matched)
            if len(run_):
                yield from emitter.right_unmatched(rbig.take(run_))
        yield from emitter.flush()


class _Emitter:
    """Join-type-aware output assembly with batch-size buffering."""

    def __init__(self, op: SortMergeJoinExec, batch_size: int):
        self.op = op
        self.batch_size = batch_size
        self.buf: List[ColumnarBatch] = []
        self.rows = 0
        if op.condition is not None:
            from blaze_tpu.exprs.compiler import ExprEvaluator

            # one evaluator for all chunks: keeps the CSE/jit caches warm
            self.cond_ev = ExprEvaluator([op.condition], op._pair_schema)

    def _push(self, batch: Optional[ColumnarBatch]):
        if batch is None or batch.num_rows == 0:
            return
        self.buf.append(batch)
        self.rows += batch.num_rows
        while self.rows >= self.batch_size:
            merged = ColumnarBatch.concat(self.buf, self.op.schema)
            out, rest = merged.slice(0, self.batch_size), merged.slice(
                self.batch_size, merged.num_rows)
            self.buf = [rest] if rest.num_rows else []
            self.rows = rest.num_rows
            yield out

    def _take_push(self, batch: ColumnarBatch, idx: np.ndarray):
        for a in range(0, len(idx), self.batch_size):
            yield from self._push(batch.take(idx[a:a + self.batch_size]))

    def flush(self):
        if self.buf:
            yield ColumnarBatch.concat(self.buf, self.op.schema)
            self.buf, self.rows = [], 0

    def left_unmatched(self, lrun: ColumnarBatch):
        rnulls = _null_columns(self.op.children[1].schema, lrun.num_rows,
                               lrun.capacity)
        yield from self._push(
            ColumnarBatch(self.op.schema, lrun.columns + rnulls,
                          lrun.num_rows))

    def right_unmatched(self, rrun: ColumnarBatch):
        lnulls = _null_columns(self.op.children[0].schema, rrun.num_rows,
                               rrun.capacity)
        yield from self._push(
            ColumnarBatch(self.op.schema, lnulls + rrun.columns,
                          rrun.num_rows))

    def _with_exists(self, lrun: ColumnarBatch, flags: np.ndarray) -> ColumnarBatch:
        exists = DeviceColumn.from_numpy(T.BOOL, np.asarray(flags, dtype=bool),
                                         None, lrun.capacity)
        return ColumnarBatch(self.op.schema, lrun.columns + [exists], lrun.num_rows)
