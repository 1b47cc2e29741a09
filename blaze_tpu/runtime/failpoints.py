"""Failpoint fault injection: named sites armed with deterministic triggers.

The reference engine proves its degradation paths (spill-capable operators,
memory-manager pressure handling) under real memory pressure; our chaos gate
(PR 9) could only SIGKILL worker processes. This module gives every OTHER
failure mode a handle: a ``failpoint("site", payload)`` call compiled into
the hot path is a single dict lookup when nothing is armed, and an armed
site fires a configured *action* on a deterministic seeded *trigger* —
exactly reproducible run to run, which is what makes chaos results
diffable. Probability triggers draw from
a stream keyed by (seed, site, worker slot): slot salting keeps symmetric
workers — which otherwise draw identical streams — from firing in
lockstep, without giving up determinism.

Sites are a closed registry (``SITES``); scripts/check_failpoints.py lints
every call site against it. Arming travels in ``Config.failpoints`` so the
spec reaches worker processes through the task-message conf
(runtime/worker.py calls ``arm_from``), and ``BLAZE_TPU_FAILPOINTS``
overrides for out-of-band arming.

Spec grammar (';'-separated entries)::

    <site>=<action>[:<token>]*

    actions   enospc | ioerror | delay | hang | corrupt
    tokens    every<N>   fire on every Nth evaluation (default every1)
              p<FLOAT>   fire with probability FLOAT (seeded, deterministic)
              x<N>       stop after N firings (default unlimited)
              <FLOAT>    action parameter: delay/hang seconds

    shm.commit=enospc:every3            ENOSPC on every 3rd shm commit
    frame.decode=corrupt:p0.25:x2       flip a payload byte, 25%, twice max
    worker.task=hang:every5:30          5th task sleeps 30s (until unhang())

Actions:
    enospc   raise OSError(ENOSPC)
    ioerror  raise OSError(EIO)
    delay    sleep <param> seconds (default 0.05), then continue
    hang     sleep up to <param> seconds (default 3600) in small slices,
             releasable process-wide via ``unhang()``
    corrupt  payload bytes -> flipped copy returned; payload path -> one
             byte of the file's payload region flipped in place (the
             footer/crc machinery then detects it downstream)
"""

from __future__ import annotations

import errno
import os
import random
import threading
import time
import zlib
from typing import Dict, Optional

from blaze_tpu.obs.telemetry import get_registry

# the closed site registry: every failpoint(...) call site must use one of
# these names (scripts/check_failpoints.py enforces it statically)
SITES = (
    "shm.commit",     # ops/shuffle/writer.py — shm-tier segment commit
    "spill.write",    # runtime/memmgr.py — spill stream write/flush
    "map.commit",     # ops/shuffle/writer.py — map-output atomic publish
    "shuffle.fetch",  # ops/shuffle/reader.py — reduce-side block open
    "frame.decode",   # ops/shuffle/reader.py — frame payload decode
    "worker.task",    # runtime/worker.py — task entry in worker processes
    "device.put",     # core/batch.py — host->device column upload
    "serve.preempt",  # runtime/session.py — stage-boundary pause point
    "cache.put",      # cache/result_cache.py — result-cache fill/persist
    "ingest.append",  # cache/ingest.py — append-only ingest commit
)

ACTIONS = ("enospc", "ioerror", "delay", "hang", "corrupt")

_TM_FIRED = get_registry().counter(
    "blaze_failpoints_fired_total",
    "Failpoint firings by site (fault injection)")

_MU = threading.Lock()
_UNHANG = threading.Event()


def _salt() -> int:
    """Per-process stream salt: 0 in the driver; worker slot id + 1 in
    pool workers (WorkerPool.spawn exports BLAZE_TPU_FAILPOINT_SALT). A
    respawned worker inherits its slot's salt, so its stream is the same
    one its predecessor drew — reproducible run to run."""
    try:
        return int(os.environ.get("BLAZE_TPU_FAILPOINT_SALT", "0"))
    except ValueError:
        return 0


class _Rule:
    """One armed site: trigger state + action. Counters are per-process;
    seeded RNG makes probability triggers reproducible run to run."""

    def __init__(self, site: str, action: str, every: int, prob: float,
                 max_fires: int, param: Optional[float], seed: int):
        self.site = site
        self.action = action
        self.every = every
        self.prob = prob
        self.max_fires = max_fires
        self.param = param
        self.calls = 0
        self.fires = 0
        # site-keyed AND process-salted stream: arming two sites from one
        # seed does not correlate their firing patterns, and symmetric
        # worker processes (which otherwise draw IDENTICAL streams and so
        # fire in lockstep — a probability hang then takes the whole fleet
        # down at once) decorrelate by their pool slot id. Still fully
        # deterministic: the pool assigns slot salts, not PIDs.
        self.rng = random.Random(
            seed ^ zlib.crc32(site.encode()) ^ (_salt() * 0x9E3779B1))

    def should_fire(self) -> bool:
        self.calls += 1
        if self.max_fires and self.fires >= self.max_fires:
            return False
        if self.prob is not None:
            return self.rng.random() < self.prob
        return self.calls % self.every == 0


# armed rules + a module-level fast flag so unarmed hot paths pay one
# attribute load and a falsy check, nothing else
_ARMED: Dict[str, _Rule] = {}
_ACTIVE = False


def parse_spec(spec: str, seed: int = 0) -> Dict[str, _Rule]:
    """Parse an arming spec into site->rule. Raises ValueError on unknown
    sites/actions or malformed tokens (arming is config: fail loudly)."""
    rules: Dict[str, _Rule] = {}
    for entry in (spec or "").split(";"):
        entry = entry.strip()
        if not entry:
            continue
        if "=" not in entry:
            raise ValueError(f"failpoint entry {entry!r}: expected site=action")
        site, _, rest = entry.partition("=")
        site = site.strip()
        if site not in SITES:
            raise ValueError(
                f"failpoint entry {entry!r}: unknown site {site!r} "
                f"(registered: {', '.join(SITES)})")
        tokens = [t.strip() for t in rest.split(":") if t.strip()]
        if not tokens or tokens[0] not in ACTIONS:
            raise ValueError(
                f"failpoint entry {entry!r}: unknown action "
                f"(one of {', '.join(ACTIONS)})")
        action = tokens[0]
        every, prob, max_fires, param = 1, None, 0, None
        for tok in tokens[1:]:
            try:
                if tok.startswith("every"):
                    every = int(tok[5:])
                    if every < 1:
                        raise ValueError
                elif tok.startswith("p"):
                    prob = float(tok[1:])
                elif tok.startswith("x"):
                    max_fires = int(tok[1:])
                else:
                    param = float(tok)
            except ValueError:
                raise ValueError(
                    f"failpoint entry {entry!r}: bad token {tok!r}") from None
        rules[site] = _Rule(site, action, every, prob, max_fires, param, seed)
    return rules


_ARMED_KEY: Optional[tuple] = None  # (spec, seed) currently armed


def arm(spec: str, seed: int = 0):
    """Replace the armed rule set from a spec string ('' disarms)."""
    global _ACTIVE, _ARMED_KEY
    rules = parse_spec(spec, seed)
    with _MU:
        _ARMED.clear()
        _ARMED.update(rules)
        _ACTIVE = bool(_ARMED)
        _ARMED_KEY = (spec, seed)
        _UNHANG.clear()


def arm_from(conf):
    """Arm from a Config (worker processes call this on every task conf so
    injection reaches task code); BLAZE_TPU_FAILPOINTS overrides. Re-arming
    with an UNCHANGED (spec, seed) is a no-op: a long-lived worker keeps its
    call/fire counters across tasks, so every-N triggers and x-caps count
    per process lifetime, not per task."""
    spec = os.environ.get("BLAZE_TPU_FAILPOINTS")
    if spec is None:
        spec = getattr(conf, "failpoints", "") or ""
    seed = int(getattr(conf, "failpoint_seed", 0) or 0)
    with _MU:
        if (spec, seed) == _ARMED_KEY:
            return
    arm(spec, seed)


def disarm():
    arm("")


def unhang():
    """Release every in-flight ``hang`` action process-wide (tests)."""
    _UNHANG.set()


def is_armed(site: Optional[str] = None) -> bool:
    if site is None:
        return _ACTIVE
    with _MU:
        return site in _ARMED


def fired(site: Optional[str] = None):
    """Firing counts: {site: n} (or one site's count) — stamped into
    incident bundles by obs/dump.record_incident."""
    with _MU:
        if site is not None:
            r = _ARMED.get(site)
            return r.fires if r is not None else 0
        return {s: r.fires for s, r in _ARMED.items() if r.fires}


def _flip_byte_in_file(path: str, rng: random.Random):
    """Flip one byte inside the payload region of an on-disk file (keeps
    clear of the 24-byte footer so corruption is detected as a crc/payload
    mismatch, not a torn footer — both route to lineage recompute anyway)."""
    size = os.path.getsize(path)
    if size <= 0:
        return
    hi = max(size - 24, 1)
    off = rng.randrange(hi)
    with open(path, "r+b") as f:
        f.seek(off)
        b = f.read(1)
        if not b:
            return
        f.seek(off)
        f.write(bytes([b[0] ^ 0xFF]))
        f.flush()
        os.fsync(f.fileno())


def failpoint(name: str, payload=None):
    """Evaluate an injection site. Returns ``payload`` (possibly corrupted)
    when nothing fires; raises / sleeps when an armed rule does."""
    if not _ACTIVE:
        return payload
    with _MU:
        rule = _ARMED.get(name)
        if rule is None or not rule.should_fire():
            return payload
        rule.fires += 1
        action, param, rng = rule.action, rule.param, rule.rng
    _TM_FIRED.labels(site=name).inc()
    if action == "enospc":
        raise OSError(errno.ENOSPC,
                      f"No space left on device [failpoint {name}]")
    if action == "ioerror":
        raise OSError(errno.EIO, f"Input/output error [failpoint {name}]")
    if action == "delay":
        time.sleep(param if param is not None else 0.05)
        return payload
    if action == "hang":
        deadline = time.monotonic() + (param if param is not None else 3600.0)
        while time.monotonic() < deadline and not _UNHANG.is_set():
            time.sleep(0.1)
        return payload
    if action == "corrupt":
        if isinstance(payload, str):
            _flip_byte_in_file(payload, rng)
            return payload
        if isinstance(payload, (bytes, bytearray, memoryview)):
            buf = bytearray(payload)
            if buf:
                off = rng.randrange(len(buf))
                buf[off] ^= 0xFF
            return bytes(buf)
        return payload
    return payload
