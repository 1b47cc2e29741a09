"""The data generator: Table 3-2's row counts from the scale factor, the
same tables from the same seed whichever of them are written, and the
structure it keeps of dsdgen's store channel."""

import json
import os

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from tests.benchmark import helpers

helpers.load_run()  # puts benchmark/ on sys.path
from benchlib.registry import Registry  # noqa: E402

GEN = Registry([helpers.BENCH_DIR]).module("generators", "tpcds_star")
with open(os.path.join(helpers.BENCH_DIR, "configs", "tpcds_sf1_chip1.json")) as _f:
    CONFIG = json.load(_f)


def _tiny(**rows):
    config = json.loads(json.dumps(CONFIG))
    config["generator_params"]["table_rows"] = dict(helpers.TINY_ROWS, **rows)
    return config


def _read(paths):
    return pa.concat_tables([pq.read_table(p) for p in paths])


def test_the_configuration_has_table_3_2s_rows_at_sf1(tmp_path):
    """The real configuration, store_returns alone (a fifth of a second)."""
    assert "table_rows" not in CONFIG["generator_params"]
    assert GEN.table_rows(CONFIG["scale_factor"]) == {
        "source": GEN.table_rows(1)["source"], "scale_factor": 1,
        "store_sales": 2880404, "store_returns": 287514, "item": 18000,
        "store": 12, "customer": 100000}
    paths = GEN.generate(str(tmp_path), 3, CONFIG, ("store_returns",))
    assert set(paths) == {"store_returns"}
    assert len(paths["store_returns"]) == CONFIG["generator_params"]["fact_files"]
    table = _read(paths["store_returns"])
    assert table.num_rows == 287514
    assert table.schema.field("sr_return_amt").type == pa.decimal128(7, 2)
    # sales name the first revision of each of store's six business keys
    assert sorted(set(table["sr_store_sk"].to_pylist())) == [1, 2, 4, 7, 8, 10]


def test_an_unknown_scale_factor_is_an_error_that_names_it(tmp_path):
    config = dict(CONFIG, scale_factor=7)
    with pytest.raises(GEN.GeneratorError, match="sf7.json"):
        GEN.generate(str(tmp_path), 1, config)


def test_an_unknown_table_is_an_error_that_names_it(tmp_path):
    with pytest.raises(GEN.GeneratorError, match="web_sales"):
        GEN.generate(str(tmp_path), 1, _tiny(), ("web_sales",))


def test_same_seed_same_tables_whichever_are_written(tmp_path):
    (tmp_path / "all").mkdir(), (tmp_path / "one").mkdir(), (tmp_path / "other").mkdir()
    everything = GEN.generate(str(tmp_path / "all"), 5, _tiny())
    assert set(everything) == set(GEN.TABLES)
    for table in GEN.TABLES:
        alone = GEN.generate(str(tmp_path / "one"), 5, _tiny(), (table,))
        assert list(alone) == [table]
        assert _read(alone[table]).equals(_read(everything[table]))
    other = GEN.generate(str(tmp_path / "other"), 6, _tiny(), ("store_sales",))
    assert not _read(other["store_sales"]).equals(_read(everything["store_sales"]))


def test_tickets_returns_and_amounts(tmp_path):
    paths = GEN.generate(str(tmp_path), 9, _tiny(store_sales=20000, store_returns=2000))
    sales, returns = _read(paths["store_sales"]), _read(paths["store_returns"])
    assert sales.num_rows == 20000 and returns.num_rows == 2000
    s = {c: np.asarray(sales[c]) for c in
         ("ss_ticket_number", "ss_store_sk", "ss_customer_sk", "ss_item_sk", "ss_quantity")}
    # a ticket: 8 to 16 line items (the last may be cut), one store, one
    # customer, no item twice
    tickets, sizes = np.unique(s["ss_ticket_number"], return_counts=True)
    assert sizes[:-1].min() >= 8 and sizes.max() <= 16
    for column in ("ss_store_sk", "ss_customer_sk"):
        per_ticket = {}
        for t, v in zip(s["ss_ticket_number"], s[column]):
            assert per_ticket.setdefault(t, v) == v
    assert len(set(zip(s["ss_ticket_number"], s["ss_item_sk"]))) == sales.num_rows
    assert s["ss_quantity"].min() >= 1 and s["ss_quantity"].max() <= 100
    # a return is one of the sales' line items, at most the quantity sold,
    # and that many times the sales price
    sold = {(t, i): (q, p, st) for t, i, q, p, st in zip(
        s["ss_ticket_number"], s["ss_item_sk"], s["ss_quantity"],
        sales["ss_sales_price"].to_pylist(), s["ss_store_sk"])}
    for t, i, q, amt, st in zip(*(returns[c].to_pylist() for c in (
            "sr_ticket_number", "sr_item_sk", "sr_return_quantity",
            "sr_return_amt", "sr_store_sk"))):
        quantity, price, store = sold[(t, i)]
        assert 1 <= q <= quantity and amt == q * price and st == store


def test_first_revisions():
    assert GEN.first_revisions(12).tolist() == [1, 2, 4, 7, 8, 10]
    assert GEN.first_revisions(1).tolist() == [1]
