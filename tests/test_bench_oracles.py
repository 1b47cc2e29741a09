"""bench.py's two references and the engine agree on the five shapes, so the
Acero reference can stand alone where pandas cannot finish (chip_smoke.py at
TPC-DS SF10 rows); and the vectorised decimal generator matches the per-value
loop it replaced."""

import decimal
import os
import sys

import numpy as np
import pyarrow as pa
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench  # noqa: E402

ROWS = 100_000


@pytest.fixture(scope="module")
def dataset(tmp_path_factory):
    paths = bench.make_data(str(tmp_path_factory.mktemp("bench_oracles")),
                            rows=ROWS, parts=4, seed=42)
    return paths, bench.load_dfs(paths), bench.load_tables(paths, list(paths))


@pytest.mark.parametrize("shape", [s[0] for s in bench.SHAPES])
def test_acero_pandas_engine_agree(dataset, shape):
    from blaze_tpu.runtime.session import Session

    paths, dfs, tables = dataset
    _, plan_fn, pandas_fn, acero_fn, check_fn, used = next(
        s for s in bench.SHAPES if s[0] == shape)
    oracle = pandas_fn(dfs)
    with Session() as sess:
        out = sess.execute_to_table(plan_fn(paths))
    check_fn(out, oracle)  # the check bench.py itself applies
    engine = bench.canon_rows(shape, out, "engine")
    assert engine, "empty answer proves nothing"
    assert engine == bench.canon_rows(shape, oracle, "pandas")
    assert engine == bench.canon_rows(
        shape, acero_fn({n: tables[n] for n in used}), "acero")


def test_uneven_rows_all_generated(tmp_path):
    paths = bench.make_data(str(tmp_path), rows=1003, parts=4, seed=7)
    for name in ("store_returns", "store_sales"):
        assert bench.load_tables(paths, [name])[name].num_rows == 1003


def test_decimal_array_matches_per_value_loop():
    for lo, hi, prec in ((0, 10_000_00, 7), (-500_00, 500_00, 7),
                         (10**14, 9 * 10**16, 38), (-9 * 10**16, -1, 38)):
        got = bench._decimal_array(np.random.default_rng(3), 257, lo, hi,
                                   prec=prec)
        want = pa.array(
            [decimal.Decimal(int(v)).scaleb(-2)
             for v in np.random.default_rng(3).integers(lo, hi, 257)],
            type=pa.decimal128(prec, 2))
        assert got.equals(want)
        got.validate(full=True)
