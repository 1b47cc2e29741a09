"""Join-key canonicalization and the build-side hash map.

Reference: ``joins/join_hash_map.rs:44-284`` — an open-addressing table over
packed MapValues with SIMD-ish probing, serializable for broadcast. The TPU
re-design (SURVEY.md §7.4.2): random-access hash probing is hostile to the
device, so keys are interned on host exactly like the aggregation path —
vectorized per-batch dedup (``np.unique`` over the packed key matrix, C
speed) with dict lookups only on per-batch *distinct* keys — and the build
side becomes a CSR layout (slot -> contiguous build-row range) that turns
probing into vectorized gather/repeat, which the device executes well.

Null join keys never match (Spark equi-join semantics): rows with any null
key get code -1 on both sides."""

from __future__ import annotations

import functools
import pickle
from typing import Dict, List, Optional, Tuple

import numpy as np

from blaze_tpu.core.batch import Column, ColumnarBatch, DeviceColumn
from blaze_tpu.exprs.compiler import ExprEvaluator
from blaze_tpu.ir import exprs as E


def key_codes(batch: ColumnarBatch, cols: List[Column], key_map: Dict,
              insert: bool) -> np.ndarray:
    """Map each row's key tuple to an integer code. ``insert`` adds unseen
    keys (build side); otherwise unseen -> -1 (probe side). Rows with any
    null key always get -1."""
    n = batch.num_rows
    if n == 0:
        return np.empty(0, dtype=np.int64)
    all_device = all(isinstance(c, DeviceColumn) for c in cols)
    if all_device:
        from blaze_tpu.utils.device import pull_columns

        pulled = pull_columns(cols, n)
        mats = []
        null_any = np.zeros(n, dtype=bool)
        for c, (data, valid) in zip(cols, pulled):
            null_any |= ~valid
            if data.dtype == np.float64:
                d = np.where(valid, data, 0.0)
                # canonicalize before viewing bits: -0.0 -> +0.0 and every
                # NaN payload -> the quiet NaN, so float keys match by Spark
                # equality (not bit pattern) even without a frontend
                # normalize_nan_and_zero projection
                d = np.where(d == 0.0, 0.0, d)
                d = np.where(np.isnan(d), np.float64(np.nan), d)
                d64 = d.view(np.int64)
            elif data.dtype == np.float32:
                d = np.where(valid, data, np.float32(0))
                d = np.where(d == np.float32(0), np.float32(0), d)
                d = np.where(np.isnan(d), np.float32(np.nan), d)
                d64 = d.view(np.int32).astype(np.int64)
            else:
                d64 = np.where(valid, data, 0).astype(np.int64)
            mats.append(d64)
        mat = np.column_stack(mats)
        view = np.ascontiguousarray(mat).view(
            np.dtype((np.void, mat.dtype.itemsize * mat.shape[1]))).ravel()
        uniq, inverse = np.unique(view, return_inverse=True)
        lut = np.empty(len(uniq), dtype=np.int64)
        for i, u in enumerate(uniq):
            kb = u.tobytes()
            code = key_map.get(kb)
            if code is None:
                if insert:
                    code = len(key_map)
                    key_map[kb] = code
                else:
                    code = -1
            lut[i] = code
        codes = lut[inverse]
        codes[null_any] = -1
        return codes
    # host path: canonical python tuples
    pylists = [c.to_arrow(n).to_pylist() for c in cols]
    codes = np.empty(n, dtype=np.int64)
    for i in range(n):
        key = tuple(_canon_value(pl[i]) for pl in pylists)
        if any(v is None for v in key):
            codes[i] = -1
            continue
        kb = pickle.dumps(key, protocol=4)
        code = key_map.get(kb)
        if code is None:
            if insert:
                code = len(key_map)
                key_map[kb] = code
            else:
                code = -1
        codes[i] = code
    return codes


def _canon_value(v):
    """Canonical python key value (host paths): one NaN payload, -0.0
    folded — same equality as the device word encoding."""
    if isinstance(v, float):
        if v != v:
            return float("nan")
        if v == 0.0:
            return 0.0
    return v


def key_rows(batch: ColumnarBatch, cols: List[Column]):
    """Canonical PER-ROW key representation for sorted-adjacent consumers
    (window partition/peer boundaries): unlike ``key_codes`` there is no
    interning dict to rebuild per batch — a single row is O(1) to carry
    across a batch boundary, and nulls are grouped as values (null == null,
    Spark grouping semantics) instead of coding every null-keyed row -1.
    That also fixes the key_codes-based boundary detection merging adjacent
    (1, NULL) and (2, NULL) partitions, which both coded -1.

    Device columns -> (n, 2k) int64 matrix of (canonical word, null flag)
    pairs; any host column -> list of canonical python tuples."""
    n = batch.num_rows
    if all(isinstance(c, DeviceColumn) for c in cols):
        from blaze_tpu.utils.device import pull_columns

        pulled = pull_columns(cols, n)
        mats = []
        for data, valid in pulled:
            mats.append(_canon_words(np.where(valid, data, data.dtype.type(0))))
            mats.append((~valid).astype(np.int64))
        return np.column_stack(mats)
    pylists = [c.to_arrow(n).to_pylist() for c in cols]
    return [tuple(_canon_value(pl[i]) for pl in pylists) for i in range(n)]


class RunningKeyCodes:
    """Run-boundary detector over batches whose rows arrive sorted by the
    key (window input): O(1) carried state (the last row's canonical key)
    instead of a per-batch interning map, so partitions spanning batches are
    recognized as continuations for free."""

    def __init__(self):
        self.last = None      # canonical last key row seen (or None)
        self.next_code = 0    # next unassigned run code

    def push_rows(self, rows) -> np.ndarray:
        """Consume precomputed ``key_rows`` output; returns the (n,) bool
        run-start mask (True where the row differs from its predecessor,
        including across the batch boundary)."""
        if isinstance(rows, np.ndarray):
            n = rows.shape[0]
            if n == 0:
                return np.zeros(0, dtype=bool)
            ch = np.zeros(n, dtype=bool)
            ch[1:] = (rows[1:] != rows[:-1]).any(axis=1)
            ch[0] = self.last is None or not np.array_equal(rows[0], self.last)
            self.last = rows[-1].copy()
        else:
            n = len(rows)
            if n == 0:
                return np.zeros(0, dtype=bool)
            ch = np.zeros(n, dtype=bool)
            ch[1:] = np.fromiter(
                (rows[i] != rows[i - 1] for i in range(1, n)), bool, n - 1)
            ch[0] = self.last is None or rows[0] != self.last
            self.last = rows[-1]
        return ch

    def change_mask(self, batch: ColumnarBatch, cols: List[Column]) -> np.ndarray:
        return self.push_rows(key_rows(batch, cols))

    def codes(self, batch: ColumnarBatch, cols: List[Column]) -> np.ndarray:
        """Cross-batch-stable run codes (each maximal equal-key run gets the
        next integer; a run spanning batches keeps ONE code)."""
        ch = self.change_mask(batch, cols)
        out = (self.next_code - 1) + np.cumsum(ch.astype(np.int64))
        self.next_code = int(out[-1]) + 1 if len(out) else self.next_code
        return out


def _canon_words(data: np.ndarray) -> np.ndarray:
    """Numpy values -> canonical int64 key words (floats: -0.0 folded,
    NaN payloads unified — Spark float equality, see key_codes)."""
    if data.dtype == np.float64:
        d = np.where(data == 0.0, 0.0, data)
        d = np.where(np.isnan(d), np.float64(np.nan), d)
        return d.view(np.int64)
    if data.dtype == np.float32:
        d = np.where(data == np.float32(0), np.float32(0), data)
        d = np.where(np.isnan(d), np.float32(np.nan), d)
        return d.view(np.int32).astype(np.int64)
    return data.astype(np.int64)


def canon_word_traced(d):
    """Traceable canonical int64 join word — the single authority shared by
    every device-side probe (keymap._probe_fn, the fused inner-join kernel
    in ops/joins/bhj.py, and the join->agg fusion in ops/agg_device.py).
    Same folding as the host _canon_words: -0.0 -> +0.0, every NaN payload
    -> the quiet NaN, so float keys match by Spark equality."""
    import jax.numpy as jnp

    if jnp.issubdtype(d.dtype, jnp.floating):
        d = jnp.where(d == 0, jnp.zeros((), d.dtype), d)
        d = jnp.where(jnp.isnan(d), jnp.array(float("nan"), d.dtype), d)
        return d.view(jnp.int32).astype(jnp.int64) \
            if d.dtype == jnp.float32 else d.view(jnp.int64)
    return d.astype(jnp.int64)


def sorted_probe_traced(uniq, d, v, nk: int):
    """Traceable membership probe against sorted canonical keys: returns
    (rank clipped into [0, nk), hit mask). All device join probes MUST go
    through this so the key encoding can never desynchronize between the
    build map and a probe path.

    The search is a merge, not a binary search: on the TPU a binary search
    costs what its gathers cost, one row-sized gather a step (15 steps for
    18,000 keys: 30.1 ms for 131,072 rows against 0.48 ms this way; PERF.md
    §6, PR 27). The build words and the probe words are ordered by ONE
    stable two-operand sort (word, int32 row), build words first, so a build
    word stands just ahead of the probe words equal to it; ``uniq`` holds no
    word twice, so a probe word hits iff the last build word at or before it
    opened its own run of equal words. That, and the build word's rank, are
    prefix scans over the sorted order; a second two-operand sort, on the
    row, brings the ranks back to the probe's row order (a third of what the
    int32 scatter of that permutation costs). No gather, no scatter, and no
    64-bit operand but the first sort's key."""
    import jax.numpy as jnp
    from jax import lax

    w = canon_word_traced(d)
    m = uniq.shape[0]
    words, row = lax.sort(
        (jnp.concatenate([uniq, w]),
         jnp.arange(m + w.shape[0], dtype=jnp.int32)),
        num_keys=1, is_stable=True)
    is_build = row < m
    run = jnp.cumsum(jnp.concatenate(
        [jnp.ones(1, bool), words[1:] != words[:-1]]), dtype=jnp.int32)
    build_run = lax.cummax(jnp.where(is_build, run, 0), axis=0)
    build_row = lax.cummax(jnp.where(is_build, row, -1), axis=0)
    rank = jnp.where(~is_build & (build_run == run), build_row, -1)
    _, rank = lax.sort((row, rank), num_keys=1, is_stable=False)
    rank = rank[m:]
    hit = v & (rank >= 0) & (rank < nk)
    return jnp.clip(rank, 0, max(nk - 1, 0)), hit


def merge_match_traced(lkeys, rkeys, nl, nr):
    """Traceable exact match of two sides on several fixed-width keys — the
    sort-merge join's probe (ops/joins/smj.py). ``lkeys`` / ``rkeys`` are
    lists of (data, validity) planes, one pair a key, ``nl`` / ``nr`` the
    sides' row counts. Every key goes through :func:`canon_word_traced`, so
    the join compares the words the hash-join probes compare; several keys
    are compared as a tuple of words, never hashed into one.

    Both sides' rows are laid side by side (right rows first: row ``i`` of
    the right side is joint row ``i``, row ``i`` of the left side joint row
    ``cap_r + i``) and ordered ONCE, ties in row order, by (tag, word_1 ..
    word_k) — ``core/kernels.lex_order_traced``: the order of one sort over
    all of them, from two-operand sorts that compile in seconds; tag 0 is a
    row whose keys are all valid, 1 a row with a null key (it matches
    nothing: Spark's equi-join), 2 a padding row. Equal keys then form one
    run, its right rows before its left rows, each in input order; neither
    side needs to arrive sorted. Everything else is prefix scans over the
    sorted order — no gather, no scatter. Returns, per joint POSITION of the
    sorted order:

    - ``row``: the joint row standing there (int32);
    - ``run_start``: the position of its run's first row (the run's right
      rows stand at ``run_start .. run_start + pairs - 1``);
    - ``pairs``: at a left row, the right rows of its run; else 0;
    - ``is_left`` / ``is_right``: an existing row of that side, null-keyed
      ones included;
    - ``r_matched``: a right row whose run holds a left row."""
    import jax.numpy as jnp
    from jax import lax

    from blaze_tpu.core import kernels as K

    def side(keys, n):
        cap = keys[0][0].shape[0]
        exists = jnp.arange(cap, dtype=jnp.int32) < n
        valid = exists
        for _d, v in keys:
            valid = valid & v
        tag = jnp.where(exists, jnp.where(valid, 0, 1), 2).astype(jnp.uint8)
        words = [jnp.where(valid, canon_word_traced(d), jnp.int64(0))
                 for d, _v in keys]
        return tag, words

    ltag, lwords = side(lkeys, nl)
    rtag, rwords = side(rkeys, nr)
    cap_r = rtag.shape[0]
    total = cap_r + ltag.shape[0]
    tag = jnp.concatenate([rtag, ltag])
    # signed order of the words, as the sides' own sorts have it
    words = [jnp.concatenate([r, l]).astype(jnp.uint64) ^ jnp.uint64(1 << 63)
             for r, l in zip(rwords, lwords)]
    row, key = K.lex_order_traced(
        [(words[0], tag)] + [(w, None) for w in words[1:]])
    at = jnp.arange(total, dtype=jnp.int32)
    # the tag is the first column's class, so the sorted order is all of tag
    # 0, then 1, then 2: the tag at a position follows from the counts
    keyed = jnp.sum(tag == 0, dtype=jnp.int32)
    tag = jnp.where(at < keyed, 0, jnp.where(
        at < jnp.sum(tag < 2, dtype=jnp.int32), 1, 2))
    new_run = jnp.concatenate([jnp.ones(1, bool), key[1:] != key[:-1]])
    run_end = jnp.concatenate([new_run[1:], jnp.ones(1, bool)])
    on_left = row >= cap_r
    match_l = (tag == 0) & on_left
    match_r = (tag == 0) & ~on_left
    # counts are non-decreasing, so the count where a run starts (ends) is a
    # running max (a reversed running min) over the run's boundary
    seen_r = jnp.cumsum(match_r, dtype=jnp.int32)
    seen_l = jnp.cumsum(match_l, dtype=jnp.int32)
    r_before = lax.cummax(jnp.where(new_run, seen_r - match_r, 0), axis=0)
    l_before = lax.cummax(jnp.where(new_run, seen_l - match_l, 0), axis=0)
    l_through = lax.cummin(jnp.where(run_end, seen_l, total), axis=0,
                           reverse=True)
    return {
        "row": row,
        "run_start": lax.cummax(jnp.where(new_run, at, 0), axis=0),
        "pairs": jnp.where(match_l, seen_r - r_before, 0),
        "is_left": (tag < 2) & on_left,
        "is_right": (tag < 2) & ~on_left,
        "r_matched": match_r & (l_through > l_before),
    }


@functools.lru_cache(maxsize=None)
def _probe_fn(dtype_str: str, nk: int):
    """Module-level cache: one jitted probe per (dtype, key count) — a
    per-call closure would recompile for every probe batch."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def probe(uniq, d, v):
        cidx, hit = sorted_probe_traced(uniq, d, v, nk)
        return jnp.where(hit, cidx, -1)

    return probe


def _searchsorted_probe(sorted_keys, data, validity, n_keys: int):
    """Jitted device probe: canonical word -> rank in sorted_keys or -1."""
    return _probe_fn(str(data.dtype), n_keys)(sorted_keys, data, validity)


class JoinHashMap:
    """Build-side map: key code -> contiguous range of build rows (CSR over
    the concatenated, code-sorted build batch).

    Two code assignments share the CSR layout:

    - **device probe** (single fixed-width key): codes are ranks in the
      SORTED unique-key array; the probe ranks keys in it on device
      (``sorted_probe_traced``: a merge by sort, since a binary search is a
      row-sized gather a step on the TPU) — no per-row host work (reference
      analogue: the prefetched group-of-8 probe of
      ``joins/join_hash_map.rs:44-284``, SURVEY.md §7.2 L2').
    - **host interning** (multi-column / var-width keys): vectorized
      ``np.unique`` dedup + dict lookups on per-batch distincts.
    """

    def __init__(self, batch: ColumnarBatch, key_map: Optional[Dict],
                 offsets: np.ndarray, schema,
                 sorted_keys: Optional[np.ndarray] = None):
        self.batch = batch          # build rows sorted by key code
        self.key_map = key_map
        self.offsets = offsets      # (num_codes + 1,) row ranges
        self.schema = schema
        self.sorted_keys = sorted_keys  # device-probe path: sorted unique keys
        # one-element cell so per-task copies of a cached map SHARE the
        # device-resident sorted-key upload (one transfer per executor, not
        # one per probe task)
        self._dev_cell = [None]
        self.matched = np.zeros(batch.num_rows, dtype=bool)

    @property
    def num_codes(self) -> int:
        return len(self.offsets) - 1

    @property
    def unique_single_key(self) -> bool:
        """Device-probe map whose every key maps to exactly ONE build row
        (the dimension-table case): code c's rows are [c, c+1), so the code
        IS the build-row index — enabling the fused device inner-join
        kernel (ops/joins/bhj.py)."""
        if getattr(self, "_unique_csr", None) is None:
            self._unique_csr = self.sorted_keys is not None and bool(
                np.all(np.diff(self.offsets) == 1))
        return self._unique_csr

    @staticmethod
    def build(batches: List[ColumnarBatch], key_exprs: List[E.Expr],
              schema, metrics=None) -> "JoinHashMap":
        key_cols = []
        kept = []
        for b in batches:
            if b.num_rows == 0:
                continue
            ev = ExprEvaluator(key_exprs, b.schema, metrics)
            key_cols.append(ev.evaluate(b))
            kept.append(b)
        if not kept:
            empty = ColumnarBatch.empty(schema)
            return JoinHashMap(empty, {}, np.zeros(1, np.int64), schema)
        if len(key_exprs) == 1 and all(
                isinstance(cols[0], DeviceColumn) for cols in key_cols):
            return JoinHashMap._build_sorted(kept, key_cols, schema, metrics)
        key_map: Dict = {}
        code_arrays = [key_codes(b, cols, key_map, insert=True)
                       for b, cols in zip(kept, key_cols)]
        big = ColumnarBatch.concat(kept, schema, metrics)
        codes = np.concatenate(code_arrays)
        ncodes = len(key_map)
        return JoinHashMap._from_codes(big, codes, ncodes, key_map, None, schema)

    @staticmethod
    def _build_sorted(kept, key_cols, schema, metrics) -> "JoinHashMap":
        """Single fixed-width key: codes are ranks in the sorted unique-key
        array (canonical int64 words), enabling the device probe."""
        from blaze_tpu.utils.device import pull_columns

        words = []
        valids = []
        for b, cols in zip(kept, key_cols):
            (data, valid), = pull_columns(cols, b.num_rows)
            words.append(_canon_words(data))
            valids.append(valid)
        big = ColumnarBatch.concat(kept, schema, metrics)
        w = np.concatenate(words)
        v = np.concatenate(valids)
        uniq = np.unique(w[v])
        codes = np.searchsorted(uniq, w)
        codes = np.where(v & (codes < len(uniq)) &
                         (uniq[np.clip(codes, 0, max(len(uniq) - 1, 0))] == w),
                         codes, -1) if len(uniq) else np.full(len(w), -1)
        return JoinHashMap._from_codes(big, codes, len(uniq), None, uniq, schema)

    @staticmethod
    def _from_codes(big, codes, ncodes, key_map, sorted_keys, schema):
        # null-keyed build rows (-1) can never match: give them code
        # num_codes so they sort to the tail outside every CSR range
        codes = np.where(codes < 0, ncodes, codes)
        order = np.argsort(codes, kind="stable")
        sorted_codes = codes[order]
        big = big.take(order)
        counts = np.bincount(sorted_codes, minlength=ncodes + 1)[: ncodes + 1]
        offsets = np.zeros(ncodes + 1, dtype=np.int64)
        np.cumsum(counts[:ncodes], out=offsets[1:])
        return JoinHashMap(big, key_map, offsets, schema, sorted_keys)

    def probe_codes(self, batch: ColumnarBatch, cols: List[Column]) -> Tuple[np.ndarray, bool]:
        """Row key -> code for this map; returns (codes, used_device_probe)."""
        if self.sorted_keys is not None and len(cols) == 1 and \
                isinstance(cols[0], DeviceColumn):
            return self._device_probe(batch, cols[0]), True
        if self.key_map is None:
            # sorted-key map probed host-side (single fixed-width key whose
            # probe column happens to live on host): same canonical words,
            # numpy searchsorted
            from blaze_tpu.core.batch import arrow_fixed_planes

            assert len(cols) == 1
            data, valid = arrow_fixed_planes(
                cols[0].to_arrow(batch.num_rows), cols[0].dtype)
            w = _canon_words(data)
            uniq = self.sorted_keys
            if len(uniq) == 0:
                return np.full(batch.num_rows, -1, np.int64), False
            codes = np.searchsorted(uniq, w)
            hit = (codes < len(uniq)) & \
                (uniq[np.clip(codes, 0, len(uniq) - 1)] == w)
            if valid is not None:  # None = all rows valid
                hit = hit & valid
            return np.where(hit, codes, -1), False
        return key_codes(batch, cols, self.key_map, insert=False), False

    def _device_probe(self, batch: ColumnarBatch, col: DeviceColumn) -> np.ndarray:
        import jax.numpy as jnp

        if self._dev_cell[0] is None:
            self._dev_cell[0] = jnp.asarray(
                self.sorted_keys if len(self.sorted_keys)
                else np.zeros(1, np.int64))
        codes = _searchsorted_probe(
            self._dev_cell[0], col.data, col.validity,
            len(self.sorted_keys))
        return np.asarray(codes)[: batch.num_rows]

    def probe(self, codes: np.ndarray) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """codes (n,) -> (probe_idx, build_idx, match_counts): all matching
        row pairs, vectorized."""
        valid = (codes >= 0) & (codes < self.num_codes)
        safe = np.where(valid, codes, 0)
        starts = self.offsets[safe]
        ends = self.offsets[safe + 1]
        counts = np.where(valid, ends - starts, 0)
        total = int(counts.sum())
        if total == 0:
            return (np.empty(0, np.int64), np.empty(0, np.int64), counts)
        probe_idx = np.repeat(np.arange(len(codes)), counts)
        base = np.repeat(np.cumsum(counts) - counts, counts)
        build_idx = np.repeat(starts, counts) + (np.arange(total) - base)
        return probe_idx, build_idx, counts

    # -- broadcast serialization (reference: JoinHashMap::try_into_bytes) -----

    def serialize(self) -> bytes:
        import io

        from blaze_tpu.io.batch_serde import BatchWriter

        buf = io.BytesIO()
        BatchWriter(buf).write_batch(self.batch)
        payload = {
            "key_map": self.key_map,
            "offsets": self.offsets,
            "sorted_keys": self.sorted_keys,
            "batch": buf.getvalue(),
        }
        return pickle.dumps(payload, protocol=4)

    @staticmethod
    def deserialize(blob: bytes, schema) -> "JoinHashMap":
        import io

        from blaze_tpu.io.batch_serde import BatchReader

        payload = pickle.loads(blob)
        batches = list(BatchReader(io.BytesIO(payload["batch"])))
        batch = batches[0] if batches else ColumnarBatch.empty(schema)
        return JoinHashMap(batch, payload["key_map"], payload["offsets"], schema,
                           payload.get("sorted_keys"))
