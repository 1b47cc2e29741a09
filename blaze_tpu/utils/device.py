"""Platform capability probes.

A TPU has no native 64-bit arithmetic: XLA emulates int64 exactly from
32-bit pairs (safe for decimals/longs/hashes), but what it does for float64
is not IEEE double on every generation. A Spark-exact engine cannot tolerate
a double that is only nearly right, so ``supports_f64`` PROBES float64
arithmetic on the backend against numpy, bit for bit, and the single choke
point ``is_device_dtype`` routes Float64 columns to host (exact numpy
compute) whenever the probe fails — on CPU backends doubles stay on device.
Everything that decides device-vs-host placement (batch construction, the
expression compiler, agg accumulators, sort) must consult these helpers,
never ``dtype.is_fixed_width`` directly.
"""

from __future__ import annotations

import contextlib
import functools
import threading
import time

import numpy as np

from blaze_tpu.ir import types as T
from blaze_tpu.obs.tracer import TRACER


class DeviceStats:
    """Process-wide device counters, integers all: device<->host transfer
    bytes and calls, blocking syncs (``sync_calls``: the wait itself is the
    ``sync:*`` span of :func:`wait_int`, the benchmark's ``device_wait_s``),
    jitted-kernel dispatches and which PARTIAL aggregation kernel answered a
    batch, in which form, and where STDDEV_SAMP's moments were reduced and
    finished. Surfaced at /debug/device; the benchmark reads the deltas a
    query (``h2d_mb``, ``d2h_mb``, ``sync_points``, ``agg_dense_batches``,
    ``agg_slot_sorted_batches``, ``merge_slot_sorted_batches``,
    ``moment_device_batches``)."""

    def __init__(self):
        self._mu = threading.Lock()
        self._tls = threading.local()
        self.reset()

    def reset(self):
        with getattr(self, "_mu", threading.Lock()):
            self.to_host_calls = 0
            self.to_host_bytes = 0
            self.to_device_calls = 0
            self.to_device_bytes = 0
            self.kernel_calls = 0
            self.mapped_calls = 0
            self.mapped_bytes = 0
            self.sync_calls = 0
            self.agg_dense_batches = 0
            self.agg_slot_sorted_batches = 0
            self.agg_sort_batches = 0
            self.merge_slot_sorted_batches = 0
            self.moment_device_batches = 0
            self.moment_host_batches = 0
            self.moment_exact_groups = 0

    def add_to_host(self, nbytes: int):
        """One blocking pull of ``nbytes``: a transfer and a sync."""
        with self._mu:
            self.to_host_calls += 1
            self.to_host_bytes += int(nbytes)
            self.sync_calls += 1

    def add_to_device(self, nbytes: int):
        with self._mu:
            self.to_device_calls += 1
            self.to_device_bytes += int(nbytes)
        self._tls.staged = getattr(self._tls, "staged", 0) + int(nbytes)

    def staged_bytes(self) -> int:
        """Bytes THIS thread has booked through :meth:`add_to_device`; a
        ``transfer:stage`` span's bytes are the difference across it."""
        return getattr(self._tls, "staged", 0)

    def add_sync(self):
        """The host blocked on a device value (:func:`wait_int`)."""
        with self._mu:
            self.sync_calls += 1

    def add_agg_batch(self, dense: bool, slot_sorted: bool = False):
        """One PARTIAL aggregation batch answered: by the slot-table kernel
        (``jit(agg_dense_partial)``; ``slot_sorted`` where its table was
        large enough to reduce by one sort of the slot id) or by the sort
        kernel (``jit(agg_partial)``). Benchmark: ``agg_dense_batches``,
        ``agg_slot_sorted_batches``."""
        with self._mu:
            if dense:
                self.agg_dense_batches += 1
                if slot_sorted:
                    self.agg_slot_sorted_batches += 1
            else:
                self.agg_sort_batches += 1

    def add_merge_slot_sorted(self):
        """One FINAL / PARTIAL_MERGE merge reduced by ONE sort of its packed
        key id (``jit(agg_merge_sorted)``). Benchmark:
        ``merge_slot_sorted_batches``."""
        with self._mu:
            self.merge_slot_sorted_batches += 1

    def add_moment(self, device_batches: int = 0, host_batches: int = 0,
                   exact_groups: int = 0):
        """STDDEV_SAMP's moments: aggregation batches (partial or merge)
        whose moment state a device kernel reduced, those the generic table
        reduced (a DOUBLE or decimal argument's double state, or any table
        the device path declined), and groups whose finish went by Python
        integers (``ops/aggfns.moment_stddev``). Benchmark:
        ``moment_device_batches``."""
        with self._mu:
            self.moment_device_batches += device_batches
            self.moment_host_batches += host_batches
            self.moment_exact_groups += exact_groups

    def add_mapped(self, nbytes: int):
        """Bytes entering device arrays from MAPPED shuffle segments —
        buffers handed to jax straight off an mmap/registry view with no
        intermediate host staging copy (zero-copy tiers). Kept separate
        from to_device_bytes so artifacts distinguish mapped vs copied."""
        with self._mu:
            self.mapped_calls += 1
            self.mapped_bytes += int(nbytes)

    def add_kernel_call(self):
        """One jitted dispatch through ``core/kernels._dispatch`` or
        ``fused_dispatch``."""
        with self._mu:
            self.kernel_calls += 1

    def snapshot(self) -> dict:
        with self._mu:
            return {
                "to_host_calls": self.to_host_calls,
                "to_host_bytes": self.to_host_bytes,
                "to_device_calls": self.to_device_calls,
                "to_device_bytes": self.to_device_bytes,
                "kernel_calls": self.kernel_calls,
                "mapped_calls": self.mapped_calls,
                "mapped_bytes": self.mapped_bytes,
                "sync_calls": self.sync_calls,
                "agg_dense_batches": self.agg_dense_batches,
                "agg_slot_sorted_batches": self.agg_slot_sorted_batches,
                "agg_sort_batches": self.agg_sort_batches,
                "merge_slot_sorted_batches": self.merge_slot_sorted_batches,
                "moment_device_batches": self.moment_device_batches,
                "moment_host_batches": self.moment_host_batches,
                "moment_exact_groups": self.moment_exact_groups,
            }


DEVICE_STATS = DeviceStats()


def wait_int(x, what: str) -> int:
    """``int(x)`` of a device scalar: the host blocks here until the device
    has run everything ``x`` depends on, which drains the launch queue. Every
    such per-batch sync goes through this one place: always counted
    (``DEVICE_STATS.sync_calls``), and under full tracing a ``sync:<what>``
    span — the time a task thread waited for the device, as opposed to the
    operator's own Python (benchmark: ``device_wait_s``, ``*_host_s``)."""
    return int(wait_array(x, what))


def wait_array(x, what: str) -> np.ndarray:
    """``np.asarray(x)`` of a few device values the host decides on (a key
    range probe): the blocking wait behind :func:`wait_int`, counted and
    named the same way."""
    DEVICE_STATS.add_sync()
    with TRACER.detail(what, "sync"):
        return np.asarray(x)


@contextlib.contextmanager
def _staging(rows: int):
    bytes0 = DEVICE_STATS.staged_bytes()
    with TRACER.detail("stage", "transfer", {"rows": rows}) as span:
        yield
        span.set(bytes=DEVICE_STATS.staged_bytes() - bytes0)


_NOT_TRACED = contextlib.nullcontext()


def stage_span(rows: int):
    """``transfer:stage`` around one batch's upload: host seconds from Arrow
    arrays or numpy planes to the ``device_put`` hand-off, with the bytes
    ``DEVICE_STATS.add_to_device`` booked on this thread meanwhile (full
    tracing only; benchmark: ``stage_h2d_s``)."""
    return _staging(rows) if TRACER.enabled else _NOT_TRACED


@functools.cache
def _supports_f64_on(platform: str) -> bool:
    """Is float64 ARITHMETIC on this backend IEEE double, bit for bit? The
    operands need the full exponent range and all 53 mantissa bits, and go
    through add, subtract, multiply, divide and a reduction as runtime
    arguments (nothing for the compiler to fold): a transfer can survive
    where arithmetic does not, so a round trip alone proves nothing."""
    import jax
    import jax.numpy as jnp

    if not jax.config.jax_enable_x64:
        return False
    odd = 4503599627370497.0  # 2**52 + 1: every mantissa bit in use
    x = np.array([8e307, odd, 1.0 + 2.0 ** -52, 1e-300, 1e200, 3.0])
    y = np.array([2.0, 2.0, 2.0 ** -52, 7.0, 1e17 + 16.0, 1e-200])
    pair = np.array([odd, 2.0])  # two terms: the sum has one possible order

    def ops(xp, a, b, p):
        return a + b, a - b, a * b, a / b, xp.sum(p)

    want = ops(np, x, y, pair)
    try:
        got = jax.jit(lambda a, b, p: ops(jnp, a, b, p))(x, y, pair)
        got = [np.asarray(g) for g in got]
    except jax.errors.JaxRuntimeError:
        return False  # the backend refuses f64 programs outright
    return all(g.dtype == np.float64 and
               np.array_equal(g.view(np.uint64), np.asarray(w).view(np.uint64))
               for g, w in zip(got, want))


def effective_platform() -> str:
    """The platform this THREAD's jax ops execute on: the thread-local
    default device under adaptive placement (runtime/placement.py), else
    the process default backend."""
    import jax

    dev = jax.config.jax_default_device
    return dev.platform if dev is not None else jax.default_backend()


def supports_f64() -> bool:
    """Keyed by the thread's effective backend: under adaptive placement
    (runtime/placement.py) a host-pinned stage has real float64 even when
    the process default backend (TPU) demotes it."""
    return _supports_f64_on(effective_platform())


def is_device_dtype(dt: T.DataType) -> bool:
    """Can a column of this type live on device with exact semantics?"""
    if isinstance(dt, T.DecimalType):
        return dt.fits_int64
    if isinstance(dt, T.Float64Type):
        return supports_f64()
    return dt.is_fixed_width


def pull_columns(cols, n: int):
    """Fetch many device columns' (data[:n], validity[:n]) in one batched
    round trip. When ``n`` is far below the arrays' capacity (e.g. a
    400-group agg output in a 131k-row bucket) we first compact all planes
    to the small capacity bucket on device in ONE dispatch, then pull only
    those bytes (chosen for a link that is gone; not measured on the chip).
    Host columns pass through as None placeholders; a coded column's planes
    are its codes.

    Returns a list aligned with ``cols``: (np_data, np_validity) for device
    and coded columns, None for host columns."""
    from blaze_tpu.core.batch import has_planes

    dev_slots = [i for i, c in enumerate(cols) if has_planes(c)]
    if not dev_slots:
        return [None] * len(cols)
    from blaze_tpu.config import get_config
    from blaze_tpu.core import kernels

    max_cap = max(cols[i].capacity for i in dev_slots)
    small_cap = get_config().capacity_for(n)
    if small_cap * 2 <= max_cap:
        # compact on device: trade one async dispatch for pulling only the
        # live bucket instead of the padded tail
        datas, valids = kernels.slice_planes(
            [cols[i].data for i in dev_slots],
            [cols[i].validity for i in dev_slots], 0, n, small_cap)
        to_pull = [a for pair in zip(datas, valids) for a in pair]
    else:
        to_pull = [a for i in dev_slots for a in (cols[i].data, cols[i].validity)]
    # start every transfer before blocking on any, so the copies overlap
    t0_ns = time.perf_counter_ns() if TRACER.active else 0
    cpu0_ns = time.thread_time_ns() if TRACER.enabled else None
    for a in to_pull:
        a.copy_to_host_async()
    pulled = [np.asarray(a)[:n] for a in to_pull]
    nbytes = sum(a.nbytes for a in to_pull)
    DEVICE_STATS.add_to_host(nbytes)
    if t0_ns:
        cpu_ns = None if cpu0_ns is None else time.thread_time_ns() - cpu0_ns
        TRACER.complete("to_host", "transfer", t0_ns,
                        time.perf_counter_ns() - t0_ns, {"bytes": nbytes},
                        cpu_ns)
    out = [None] * len(cols)
    for k, i in enumerate(dev_slots):
        out[i] = (pulled[2 * k], pulled[2 * k + 1])
    return out
