"""In-memory segment registry: the single-process tiers of the zero-copy
data plane.

When a stage's consumer runs in the SAME process (pool-less local mode,
fused pipelines, the serve layer's subplan reuse), shipping partitions
through ``batch_serde`` — pull, frame, compress, write, re-read, decode,
re-upload — is pure overhead. Instead the shuffle writer stages its
``bucketize_host`` output per reducer and commits the staged batch
REFERENCES here; readers receive them through ``("batches", ...)`` blocks
with serde skipped entirely (the ``serde_elided_batches`` tripwire).

The registry is tier-AGNOSTIC about what a staged reference points at:
the process tier (stages on the CPU backend) commits host batches, the
"device" tier (a pool-less session on an accelerator, with or without a
mesh) commits device-resident references — windows of the batches that
``ops/shuffle/repartitioner.exchange_route`` routed on the chip
(``core/batch.RowWindow``), so the reduce side consumes them with no pull
and no upload: the ``device_shuffle_bytes`` tripwire. Both are plain heap objects holding their buffers alive;
release semantics are identical.

Lineage compatibility: each committed mem segment is paired with a
footer-only marker data file on disk (a 0-payload footer passes
``verify_map_output``), so PR 9's recovery machinery — chaos deletion of
a map output, ``StageLineage.missing()`` sweeps, recompute-then-verify —
keeps working verbatim: deleting the marker makes the map "missing",
recompute re-runs the map task, which re-commits the registry entry and
republishes the marker atomically. A registry miss at read time raises
the same typed ``ShuffleOutputMissing``.
"""

from __future__ import annotations

import threading
from typing import Dict, Iterable, List, Tuple


class MemSegmentRegistry:
    """(stage, map_id) -> per-reducer staged batch lists. Segments are
    owned by their query: the session releases a query's stages when it
    finishes (success, cancel or failure), and ``clear()`` drops everything
    at session close — reference-counted hygiene with no finalizer games,
    since batches are plain heap objects."""

    def __init__(self):
        self._mu = threading.Lock()
        self._segs: Dict[Tuple[int, int], Dict[int, list]] = {}
        self._nbytes: Dict[Tuple[int, int], int] = {}

    def commit(self, stage: int, map_id: int, parts: Dict[int, list],
               nbytes: int):
        """Publish one map task's staged output (replaces any prior attempt
        — recompute republishes just like the atomic file rename)."""
        with self._mu:
            self._segs[(stage, map_id)] = parts
            self._nbytes[(stage, map_id)] = int(nbytes)

    def get(self, stage: int, map_id: int):
        with self._mu:
            return self._segs.get((stage, map_id))

    def release_stages(self, stages: Iterable[int]):
        drop = set(stages)
        with self._mu:
            for key in [k for k in self._segs if k[0] in drop]:
                self._segs.pop(key, None)
                self._nbytes.pop(key, None)

    def clear(self):
        with self._mu:
            self._segs.clear()
            self._nbytes.clear()

    def total_bytes(self) -> int:
        with self._mu:
            return sum(self._nbytes.values())

    def stage_bytes(self, stages: Iterable[int]) -> int:
        """Bytes held by the named stages' segments — what a paused query's
        StageCursor is pinning in memory (serve preemption accounting)."""
        keep = set(stages)
        with self._mu:
            return sum(n for k, n in self._nbytes.items() if k[0] in keep)

    def __len__(self) -> int:
        with self._mu:
            return len(self._segs)


class MemSegmentBlockProvider:
    """Reduce-side provider over registry segments: partition -> one
    ``("batches", [...])`` block per map, in map order (the same order the
    file-segment providers serve, so results stay bit-identical with
    zero-copy off). Verifies each map's on-disk marker first — the chaos
    monkey and the lineage sweeps operate on files — then serves the
    registry entry. A map with no registry entry fell back to real data
    files mid-write (mem budget exceeded, spill pressure): its segments
    serve from disk like the classic provider. A map whose registry entry
    vanished but whose marker survived fails the index-size check —
    markers are 20 bytes, logical indexes are not — and surfaces as
    ``ShuffleOutputMissing`` so ordinary lineage recovery recomputes and
    re-commits it."""

    def __init__(self, registry: MemSegmentRegistry, stage: int,
                 indexes: List[Tuple[str, "object"]],
                 groups: List[List[int]] = None,
                 map_subsets: List[List[int]] = None):
        self.registry = registry
        self.stage = stage
        # [(data_path, offsets)] per map; offsets are LOGICAL byte
        # cumulative sums for registry-committed maps (AQE coalescing sizes
        # on them) and physical file offsets for degraded maps
        self.indexes = list(indexes)
        self.groups = groups  # provider partition -> reducer pids (AQE)
        # provider partition -> the maps that serve it, None = all of them
        # (the skew-join split: a sub-partition reads a subset of the maps)
        self.map_subsets = map_subsets

    def __call__(self, partition: int):
        from blaze_tpu.runtime.recovery import check_map_output

        pids = self.groups[partition] if self.groups is not None \
            else [partition]
        maps = self.map_subsets[partition] \
            if self.map_subsets is not None else None
        blocks = []
        for m, (data, offsets) in enumerate(self.indexes):
            if maps is not None and m not in maps:
                continue
            seg = self.registry.get(self.stage, m)
            if seg is not None:
                # marker still on disk? the chaos monkey and lineage sweeps
                # speak files, so deletion must be observed here
                check_map_output(data, stage=self.stage, map_id=m)
                batches = [b for p in pids for b in seg.get(p, ())]
                if batches:
                    blocks.append(("batches", batches))
                continue
            for r in pids:
                start, end = int(offsets[r]), int(offsets[r + 1])
                if end > start:
                    data = check_map_output(data, offsets=offsets,
                                            stage=self.stage, map_id=m)
                    blocks.append(("file_segment", data, start, end - start))
        return blocks
