"""Native C++ kernel parity tests: the ctypes library must agree bit-for-bit
with the numpy fallbacks (which the golden Spark vectors anchor)."""

import numpy as np
import pytest

from blaze_tpu.utils import native


def _str_arrays(strings):
    enc = [s.encode("utf-8") for s in strings]
    offsets = np.zeros(len(enc) + 1, dtype=np.int64)
    np.cumsum([len(b) for b in enc], out=offsets[1:])
    data = np.frombuffer(b"".join(enc), dtype=np.uint8)
    return offsets, data


def test_murmur3_native_matches_numpy():
    import tests.test_spark_hash as tsh

    rng = np.random.default_rng(0)
    strings = ["".join(chr(rng.integers(32, 500)) for _ in range(rng.integers(0, 40)))
               for _ in range(300)]
    offsets, data = _str_arrays(strings)
    seeds = rng.integers(0, 2**32, size=len(strings), dtype=np.uint32)
    out = native.murmur3_bytes(offsets, data, seeds)
    expected = np.array(
        [tsh.mmh3_scalar(s.encode(), int(seed)) for s, seed in zip(strings, seeds)],
        dtype=np.uint32)
    np.testing.assert_array_equal(out, expected)


def test_xxh64_native_matches_numpy():
    import tests.test_spark_hash as tsh

    rng = np.random.default_rng(1)
    strings = ["".join(chr(rng.integers(32, 500)) for _ in range(rng.integers(0, 100)))
               for _ in range(300)]
    offsets, data = _str_arrays(strings)
    seeds = rng.integers(0, 2**63, size=len(strings), dtype=np.uint64)
    out = native.xxh64_bytes(offsets, data, seeds)
    expected = np.array(
        [tsh.xxh64_scalar(s.encode(), int(seed)) for s, seed in zip(strings, seeds)],
        dtype=np.uint64)
    np.testing.assert_array_equal(out, expected)


def test_transpose_roundtrip():
    rng = np.random.default_rng(2)
    for dtype in (np.int64, np.float32, np.int16):
        vals = rng.integers(0, 1000, 777).astype(dtype)
        n, itemsize = len(vals), vals.dtype.itemsize
        planes = native.transpose(vals, n, itemsize, forward=True)
        expected = np.ascontiguousarray(
            vals.view(np.uint8).reshape(n, itemsize).T).reshape(-1)
        np.testing.assert_array_equal(planes, expected)
        back = native.transpose(planes, n, itemsize, forward=False)
        np.testing.assert_array_equal(back.view(dtype), vals)


def test_lz4_codec_round_trip():
    """lz4 shuffle codec (reference: lz4+zstd, ipc_compression.rs) via the
    native lib's dlopen'd liblz4."""
    import io

    import pyarrow as pa

    from blaze_tpu.config import config_override
    from blaze_tpu.core.batch import ColumnarBatch
    from blaze_tpu.io.batch_serde import BatchReader, BatchWriter
    from blaze_tpu.utils import native

    if not native.lib().bt_lz4_available():
        pytest.skip("liblz4 unavailable")
    b = ColumnarBatch.from_pydict({
        "a": pa.array(list(range(1000)), type=pa.int64()),
        "s": pa.array([f"v{i % 9}" for i in range(1000)]),
    })
    buf = io.BytesIO()
    BatchWriter(buf, codec="lz4").write_batch(b)
    raw = buf.getvalue()
    import struct

    flags = struct.unpack_from("<4sI", raw)[1]
    assert flags == 2, "frame must be lz4-tagged"
    buf.seek(0)
    out = list(BatchReader(buf))
    assert out[0].to_pydict() == b.to_pydict()


def test_library_is_built_from_the_tree_and_loaded_once():
    """conftest built-or-loaded it before any test; later calls hand back the
    same handle, and it is at least as new as its source."""
    import os

    assert native.ensure_built() in ("built", "loaded")
    assert native.lib() is native.lib()
    assert not native._stale()
    assert os.path.getmtime(native._SO_PATH) >= os.path.getmtime(
        os.path.join(native._SRC_DIR, "src", "blaze_native.cc"))


def test_failed_build_raises(monkeypatch, tmp_path):
    """A build that cannot run is an error with the tool's name in it — never
    a quiet return to numpy."""
    monkeypatch.setattr(native, "_SRC_DIR", str(tmp_path / "no_such_tree"))
    with pytest.raises(RuntimeError, match="native build failed"):
        native.build()
