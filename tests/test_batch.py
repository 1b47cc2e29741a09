import numpy as np
import pyarrow as pa
import pytest

from blaze_tpu.core import ColumnarBatch, DeviceColumn, HostColumn
from blaze_tpu.ir import types as T


def make_batch():
    tbl = pa.table(
        {
            "i": pa.array([1, None, 3, 4], type=pa.int64()),
            "f": pa.array([1.5, 2.5, None, 4.0], type=pa.float64()),
            "s": pa.array(["a", "bb", None, "dddd"], type=pa.string()),
            "b": pa.array([True, False, None, True], type=pa.bool_()),
            "d": pa.array([1, 2, 3, None], type=pa.decimal128(10, 2)),
        }
    )
    return ColumnarBatch.from_arrow(tbl)


@pytest.mark.quick
def test_roundtrip():
    b = make_batch()
    assert b.num_rows == 4
    assert b.capacity >= 4
    assert isinstance(b.columns[0], DeviceColumn)
    assert isinstance(b.columns[2], HostColumn)
    out = b.to_arrow()
    assert out.column(0).to_pylist() == [1, None, 3, 4]
    assert out.column(1).to_pylist() == [1.5, 2.5, None, 4.0]
    assert out.column(2).to_pylist() == ["a", "bb", None, "dddd"]
    assert out.column(3).to_pylist() == [True, False, None, True]
    assert [str(x) if x is not None else None for x in out.column(4).to_pylist()] == [
        "1.00", "2.00", "3.00", None
    ]


def test_decimal_unscaled():
    from decimal import Decimal

    tbl = pa.table(
        {"d": pa.array([Decimal("12.34"), Decimal("-5.00"), None], type=pa.decimal128(9, 2))}
    )
    b = ColumnarBatch.from_arrow(tbl)
    col = b.columns[0]
    assert isinstance(col, DeviceColumn)
    np.testing.assert_array_equal(np.asarray(col.data[:3]), [1234, -500, 0])
    np.testing.assert_array_equal(np.asarray(col.validity[:3]), [True, True, False])
    out = b.to_arrow()
    assert [str(x) if x is not None else None for x in out.column(0).to_pylist()] == [
        "12.34", "-5.00", None
    ]


def test_take_and_slice():
    b = make_batch()
    t = b.take(np.array([3, 0]))
    assert t.num_rows == 2
    assert t.to_pydict()["i"] == [4, 1]
    assert t.to_pydict()["s"] == ["dddd", "a"]
    s = b.slice(1, 2)
    assert s.to_pydict()["i"] == [None, 3]


def test_concat():
    b1 = ColumnarBatch.from_pydict({"x": [1, 2]})
    b2 = ColumnarBatch.from_pydict({"x": [3]})
    c = ColumnarBatch.concat([b1, b2])
    assert c.num_rows == 3
    assert c.to_pydict()["x"] == [1, 2, 3]


def test_padding_is_zero_and_invalid():
    b = ColumnarBatch.from_pydict({"x": [1, 2, 3]})
    col = b.columns[0]
    cap = col.capacity
    assert cap >= 3
    data = np.asarray(col.data)
    validity = np.asarray(col.validity)
    assert (data[3:] == 0).all()
    assert (~validity[3:]).all()


def test_dictionary_read_strings_are_coded():
    """A dictionary-encoded string column arrives CODED: int32 codes and
    validity on the device under the padding contract, one dictionary on
    the host (`HostColumn.dict_encode`, the per-batch encoding this
    replaced, is gone)."""
    import pyarrow as pa

    from blaze_tpu.core.batch import CodedColumn

    arr = pa.array(["x", "y", "x", None]).dictionary_encode()
    b = ColumnarBatch.from_arrow(pa.table({"s": arr}))
    col = b.columns[0]
    assert isinstance(col, CodedColumn) and col.data.dtype == np.int32
    codes = np.asarray(col.data)
    validity = np.asarray(col.validity)
    assert validity[:4].tolist() == [True, True, True, False]
    assert (codes[3:] == 0).all() and (~validity[4:]).all()
    vals = col.dictionary.to_pylist()
    assert vals[codes[0]] == "x" and vals[codes[1]] == "y" and codes[0] == codes[2]
    assert b.to_arrow().column(0).to_pylist() == ["x", "y", "x", None]


def test_empty():
    schema = T.Schema.of(("a", T.I64), ("s", T.STRING))
    b = ColumnarBatch.empty(schema)
    assert b.num_rows == 0
    assert b.to_arrow().num_rows == 0


def test_schema_ops():
    s = T.Schema.of(("a", T.I64), ("b", T.STRING, False))
    assert s.index_of("b") == 1
    assert s["b"].nullable is False
    with pytest.raises(KeyError):
        s.index_of("zzz")
    assert (s + s).names == ["a", "b", "a", "b"]


def test_date_roundtrip():
    import datetime

    tbl = pa.table({"d": pa.array([datetime.date(1970, 1, 2), None,
                                   datetime.date(2020, 2, 29)], type=pa.date32())})
    b = ColumnarBatch.from_arrow(tbl)
    np.testing.assert_array_equal(np.asarray(b.columns[0].data[:3]), [1, 0, 18321])
    assert b.to_pydict()["d"] == [datetime.date(1970, 1, 2), None, datetime.date(2020, 2, 29)]


def test_timestamp_roundtrip():
    tbl = pa.table({"t": pa.array([1_000_000, None], type=pa.timestamp("us"))})
    b = ColumnarBatch.from_arrow(tbl)
    np.testing.assert_array_equal(np.asarray(b.columns[0].data[:2]), [1_000_000, 0])
    out = b.to_arrow()
    assert out.column(0).cast(pa.int64()).to_pylist() == [1_000_000, None]


def test_from_pydict_schema_order():
    schema = T.Schema.of(("a", T.I64), ("s", T.STRING))
    b = ColumnarBatch.from_pydict({"s": ["x"], "a": [1]}, schema)
    assert b.to_pydict() == {"a": [1], "s": ["x"]}


def test_uint64_overflow_raises():
    tbl = pa.table({"u": pa.array([2**63], type=pa.uint64())})
    with pytest.raises(OverflowError):
        ColumnarBatch.from_arrow(tbl)
    ok = ColumnarBatch.from_arrow(pa.table({"u": pa.array([7], type=pa.uint64())}))
    assert ok.to_pydict()["u"] == [7]


def test_concat_empty_needs_schema():
    with pytest.raises(ValueError):
        ColumnarBatch.concat([])
    schema = T.Schema.of(("a", T.I64))
    assert ColumnarBatch.concat([], schema).num_rows == 0


def test_with_capacity_shrink_guard():
    b = ColumnarBatch.from_pydict({"x": list(range(300))})
    with pytest.raises(AssertionError):
        b.with_capacity(256)
