"""The real-TPC-DS gate: genuine query texts, Spark-shaped physical plans
through the frontend, executed end to end, checked against pandas oracles
(round-2 verdict item 6 — replaces the hand-built shape suite as the
correctness gate; reference: the 99-query Spark-vs-native workflow in
``tpcds-reusable.yml``)."""

import decimal
import json

import pytest

from blaze_tpu.frontend.converter import SparkPlanConverter
from blaze_tpu.runtime.session import Session
from tests.tpcds import data as tpcds_data
from tests.tpcds.queries import QUERIES


@pytest.fixture(scope="module")
def dataset(tmp_path_factory):
    d = tmp_path_factory.mktemp("tpcds_sf_tiny")
    tables = tpcds_data.generate(str(d))
    return tables, tpcds_data.load_dfs(tables)


def _norm(v):
    if isinstance(v, float):
        return round(v, 4)
    if isinstance(v, decimal.Decimal):
        return v
    return v


def _normrows(rows):
    return [tuple(_norm(v) for v in r) for r in rows]


def _sorted_if_tied(rows, flags):
    # queries whose ORDER BY does not fully determinize row order within
    # equal sort keys compare as sets of rows
    rows = _normrows(rows)
    return sorted(rows, key=repr) if "ties" in flags else rows


def _rows_equal(got, want, flags):
    if "approx" not in flags:
        return got == want
    # AVG queries: the engine divides decimals exactly (HALF_UP) while the
    # pandas oracle uses float means — compare numerics with tolerance
    if len(got) != len(want):
        return False
    for g, w in zip(got, want):
        if len(g) != len(w):
            return False
        for gv, wv in zip(g, w):
            if isinstance(gv, (float, decimal.Decimal)) and \
                    isinstance(wv, (float, decimal.Decimal)):
                if abs(float(gv) - float(wv)) > 0.02:
                    return False
            elif gv != wv:
                return False
    return True


@pytest.mark.parametrize("name", sorted(QUERIES))
def test_tpcds_query(name, dataset):
    tables, dfs = dataset
    plan_json, oracle, extract, flags = QUERIES[name]()
    conv = SparkPlanConverter(tables=tables)
    result = conv.convert(json.dumps(plan_json))
    fallbacks = [t for t in result.tags if "fallback" in t[1]]
    assert not fallbacks, f"{name}: unconverted nodes {fallbacks}"
    with Session() as sess:
        out = sess.execute_to_table(result.plan)
    if extract is None:
        # positional: converted column names carry Spark exprId suffixes;
        # the oracle emits tuples in the same (groups..., aggs...) order
        d = out.to_pydict()
        rows = list(zip(*d.values())) if d else []
    else:
        rows = extract(out)
    got = _sorted_if_tied(rows, flags)
    want = _sorted_if_tied(oracle(dfs), flags)
    assert _rows_equal(got, want, flags), (
        f"{name}: {len(got)} rows vs oracle {len(want)};"
        f" first diff: {next(((g, w) for g, w in zip(got, want) if g != w), None)}")


def _by_operator(node, metric, into=None):
    """A metric's totals by operator name over a `MetricNode.to_dict()`."""
    into = {} if into is None else into
    if node["values"].get(metric):
        into[node["name"]] = into.get(node["name"], 0) + node["values"][metric]
    for child in node["children"]:
        _by_operator(child, metric, into)
    return into


@pytest.mark.parametrize("name", ["q22", "q67"])
def test_rollup_names_stay_on_the_device_as_codes(name, dataset):
    """The two ROLLUPs over item names: the scan reads the names coded, and
    join payload, Expand, both aggregation stages and the exchange work on
    the codes; no batch's names are turned into a host column — in q67 but
    for the rank window's one batch: it orders by a decimal wider than
    int64, which keeps it off the device program (a window over a coded
    partition key alone runs there: tests/test_coded_column.py)."""
    tables, dfs = dataset
    plan_json, oracle, extract, flags = QUERIES[name]()
    result = SparkPlanConverter(tables=tables).convert(json.dumps(plan_json))
    with Session() as sess:
        out = sess.execute_to_table(result.plan)
        tree = sess.metrics.to_dict()
    rows = extract(out) if extract is not None else \
        list(zip(*out.to_pydict().values()))
    assert _rows_equal(_sorted_if_tied(rows, flags),
                       _sorted_if_tied(oracle(dfs), flags), flags)
    coded = _by_operator(tree, "coded_key_batches")
    assert {"BroadcastJoinExec", "ExpandExec", "AggExec"} <= set(coded), coded
    assert _by_operator(tree, "rollup_rows")["ExpandExec"] > 0
    assert not _by_operator(tree, "agg_reintern_rows")
    assert not _by_operator(tree, "join_generic_batches")
    assert _by_operator(tree, "host_key_batches") == (
        {} if name == "q22" else {"WindowExec": 1})
