"""ctypes binding for the native host-kernel library (native/).

The library is built from ``native/src/blaze_native.cc`` into the fixed,
git-ignored path ``native/build/libblaze_native.so`` — synchronously, when it
is missing or older than its source, at first use or by the explicit
``ensure_built()`` that Session, bench.py, chip_smoke.py and the test session
call before any query. A failed build raises: one set of host kernels serves
a whole run."""

from __future__ import annotations

import ctypes
import os
import shutil
import subprocess
import threading
from typing import Optional

import numpy as np

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_SRC_DIR = os.path.join(_REPO_ROOT, "native")
_SO_PATH = os.path.join(_SRC_DIR, "build", "libblaze_native.so")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_how: Optional[str] = None  # "built" | "loaded", how this process got _lib


def _configure(lib: ctypes.CDLL):
    lib.bt_version.restype = ctypes.c_int
    lib.bt_transpose.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                 ctypes.c_size_t, ctypes.c_size_t, ctypes.c_int]
    lib.bt_murmur3_bytes.argtypes = [ctypes.c_void_p] * 4 + [ctypes.c_size_t]
    lib.bt_xxh64_bytes.argtypes = [ctypes.c_void_p] * 4 + [ctypes.c_size_t]
    lib.bt_murmur3_codes.argtypes = [ctypes.c_void_p] * 6 + [ctypes.c_size_t]
    lib.bt_zstd_compress_bound.restype = ctypes.c_int64
    lib.bt_zstd_compress_bound.argtypes = [ctypes.c_int64]
    lib.bt_zstd_compress.restype = ctypes.c_int64
    lib.bt_zstd_compress.argtypes = [ctypes.c_void_p, ctypes.c_int64,
                                     ctypes.c_void_p, ctypes.c_int64, ctypes.c_int]
    lib.bt_zstd_decompress.restype = ctypes.c_int64
    lib.bt_zstd_decompress.argtypes = [ctypes.c_void_p, ctypes.c_int64,
                                       ctypes.c_void_p, ctypes.c_int64]
    lib.bt_lz4_available.restype = ctypes.c_int
    lib.bt_lz4_compress_bound.restype = ctypes.c_int64
    lib.bt_lz4_compress_bound.argtypes = [ctypes.c_int64]
    lib.bt_lz4_compress.restype = ctypes.c_int64
    lib.bt_lz4_compress.argtypes = [ctypes.c_void_p, ctypes.c_int64,
                                    ctypes.c_void_p, ctypes.c_int64]
    lib.bt_lz4_decompress.restype = ctypes.c_int64
    lib.bt_lz4_decompress.argtypes = [ctypes.c_void_p, ctypes.c_int64,
                                      ctypes.c_void_p, ctypes.c_int64]


def _stale() -> bool:
    """Missing, or older than what it is built from."""
    if not os.path.exists(_SO_PATH):
        return True
    built = os.path.getmtime(_SO_PATH)
    return any(os.path.getmtime(os.path.join(_SRC_DIR, f)) > built
               for f in ("CMakeLists.txt", os.path.join("src", "blaze_native.cc")))


def build():
    """Build the library with cmake in a per-process temp dir, then publish
    the .so atomically (concurrent builders in other processes each publish
    a complete file). Raises with the tool's output when the build fails."""
    bld = os.path.join(_SRC_DIR, f"build-tmp-{os.getpid()}")
    try:
        for cmd in (["cmake", "-S", _SRC_DIR, "-B", bld,
                     "-DCMAKE_BUILD_TYPE=Release"],
                    ["cmake", "--build", bld, "--", "-j2"]):
            try:
                r = subprocess.run(cmd, capture_output=True, text=True,
                                   cwd=_REPO_ROOT, timeout=300)
            except (OSError, subprocess.TimeoutExpired) as exc:
                raise RuntimeError(
                    f"native build could not run {cmd[0]!r}: {exc}") from exc
            if r.returncode != 0:
                raise RuntimeError(
                    f"native build failed ({' '.join(cmd)}):\n"
                    f"{r.stdout[-2000:]}\n{r.stderr[-2000:]}")
        os.makedirs(os.path.dirname(_SO_PATH), exist_ok=True)
        tmp_target = _SO_PATH + f".{os.getpid()}"
        shutil.copy2(os.path.join(bld, "libblaze_native.so"), tmp_target)
        os.replace(tmp_target, _SO_PATH)
    finally:
        shutil.rmtree(bld, ignore_errors=True)


_sys_zstd: Optional[ctypes.CDLL] = None
_sys_zstd_tried = False


def system_zstd() -> Optional[ctypes.CDLL]:
    """Bind the system libzstd's one-shot API (ZSTD_compress/ZSTD_decompress)
    directly. Serves compression when neither the repo's native library nor
    the python ``zstandard`` binding is available — the image often ships the
    shared library without headers or bindings."""
    global _sys_zstd, _sys_zstd_tried
    if _sys_zstd_tried:
        return _sys_zstd
    with _lock:
        if _sys_zstd_tried:
            return _sys_zstd
        try:
            import ctypes.util

            # find_library shells out (gcc/ldconfig) and can take hundreds
            # of ms: _sys_zstd_tried must only flip True AFTER the load
            # attempt settles, or the unlocked fast path above hands
            # concurrent first callers a spurious None — a decode pool
            # racing here would misread "no zstd" and fail valid frames
            name = ctypes.util.find_library("zstd") or "libzstd.so.1"
            l = ctypes.CDLL(name)
            l.ZSTD_compressBound.restype = ctypes.c_size_t
            l.ZSTD_compressBound.argtypes = [ctypes.c_size_t]
            l.ZSTD_compress.restype = ctypes.c_size_t
            l.ZSTD_compress.argtypes = [ctypes.c_void_p, ctypes.c_size_t,
                                        ctypes.c_void_p, ctypes.c_size_t,
                                        ctypes.c_int]
            l.ZSTD_decompress.restype = ctypes.c_size_t
            l.ZSTD_decompress.argtypes = [ctypes.c_void_p, ctypes.c_size_t,
                                          ctypes.c_void_p, ctypes.c_size_t]
            l.ZSTD_isError.restype = ctypes.c_uint
            l.ZSTD_isError.argtypes = [ctypes.c_size_t]
            _sys_zstd = l
        except (OSError, AttributeError):
            _sys_zstd = None
        _sys_zstd_tried = True
        return _sys_zstd


def lib() -> ctypes.CDLL:
    """The loaded library; the first call in a process builds it if stale.
    Never returns None — a library that cannot be built or loaded raises."""
    global _lib, _how
    if _lib is not None:
        return _lib
    with _lock:
        if _lib is None:
            how = "loaded"
            if _stale():
                build()
                how = "built"
            l = ctypes.CDLL(_SO_PATH)
            _configure(l)
            _lib, _how = l, how
        return _lib


def ensure_built() -> str:
    """Build-or-load now; returns "built" or "loaded" (how THIS process got
    the library). Call before the first query."""
    lib()
    return _how


# ---------------------------------------------------------------------------
# typed wrappers
# ---------------------------------------------------------------------------


def transpose(raw: np.ndarray, n: int, itemsize: int, forward: bool) -> np.ndarray:
    """Byte-plane transpose of ``n`` items of ``itemsize`` > 1 bytes each
    (``forward``: items -> planes). Callers skip empty and one-byte columns."""
    l = lib()
    src = np.ascontiguousarray(raw).view(np.uint8).reshape(-1)
    dst = np.empty(n * itemsize, dtype=np.uint8)
    l.bt_transpose(src.ctypes.data, dst.ctypes.data, n, itemsize,
                   1 if forward else 0)
    return dst


def murmur3_bytes(offsets: np.ndarray, data: np.ndarray, seeds: np.ndarray
                  ) -> np.ndarray:
    l = lib()
    n = len(offsets) - 1
    offsets = np.ascontiguousarray(offsets, dtype=np.int64)
    data = np.ascontiguousarray(data, dtype=np.uint8)
    seeds = np.ascontiguousarray(seeds, dtype=np.uint32)
    out = np.empty(n, dtype=np.uint32)
    l.bt_murmur3_bytes(offsets.ctypes.data, data.ctypes.data,
                       seeds.ctypes.data, out.ctypes.data, n)
    return out


def murmur3_codes(codes: np.ndarray, valid, offsets: np.ndarray,
                  data: np.ndarray, seeds: np.ndarray) -> np.ndarray:
    """`murmur3_bytes` of dictionary entries by code: row i hashes entry
    ``codes[i]``'s bytes with ``seeds[i]``; rows where ``valid`` (None: all)
    is False keep their seed."""
    l = lib()
    n = len(codes)
    codes = np.ascontiguousarray(codes, dtype=np.int32)
    offsets = np.ascontiguousarray(offsets, dtype=np.int64)
    data = np.ascontiguousarray(data, dtype=np.uint8)
    seeds = np.ascontiguousarray(seeds, dtype=np.uint32)
    if valid is not None:
        valid = np.ascontiguousarray(valid, dtype=np.uint8)
    out = np.empty(n, dtype=np.uint32)
    l.bt_murmur3_codes(codes.ctypes.data,
                       valid.ctypes.data if valid is not None else None,
                       offsets.ctypes.data, data.ctypes.data,
                       seeds.ctypes.data, out.ctypes.data, n)
    return out


def xxh64_bytes(offsets: np.ndarray, data: np.ndarray, seeds: np.ndarray
                ) -> np.ndarray:
    l = lib()
    n = len(offsets) - 1
    offsets = np.ascontiguousarray(offsets, dtype=np.int64)
    data = np.ascontiguousarray(data, dtype=np.uint8)
    seeds = np.ascontiguousarray(seeds, dtype=np.uint64)
    out = np.empty(n, dtype=np.uint64)
    l.bt_xxh64_bytes(offsets.ctypes.data, data.ctypes.data,
                     seeds.ctypes.data, out.ctypes.data, n)
    return out
