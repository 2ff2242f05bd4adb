"""The generator makes every column of TPC-H clause 1.4 at the published
widths, as the configuration's file lists them; the same seed gives the
same data; a configuration that extends another is laid over it."""

import os

import numpy as np
import pytest

from conftest import BENCH, SF
from lib import harness, tpch_gen

# clause 1.4: columns a table, and the widest a string column may be
COLUMNS = {"part": 9, "supplier": 7, "partsupp": 5, "customer": 8,
           "orders": 9, "lineitem": 16, "nation": 4, "region": 3}
WIDTHS = {"p_name": 55, "p_mfgr": 25, "p_brand": 10, "p_type": 25,
          "p_container": 10, "p_comment": 23, "s_name": 25, "s_address": 40,
          "s_phone": 15, "s_comment": 101, "ps_comment": 199, "c_name": 25,
          "c_address": 40, "c_phone": 15, "c_mktsegment": 10,
          "c_comment": 117, "o_orderstatus": 1, "o_orderpriority": 15,
          "o_clerk": 15, "o_comment": 79, "l_returnflag": 1,
          "l_linestatus": 1, "l_shipinstruct": 25, "l_shipmode": 10,
          "l_comment": 44, "n_name": 25, "n_comment": 152, "r_name": 25,
          "r_comment": 152}


@pytest.fixture(scope="module")
def everything():
    return tpch_gen.gen_tables(SF, 11, tpch_gen.TABLE_ORDER)


def test_every_column_of_clause_1_4_at_its_width(everything):
    import pyarrow.compute as pc

    config = harness.load_config(os.path.join(BENCH, "configs",
                                              "tpch_sf1_parquet.json"))
    strings = set()
    for table, (cols, schema) in everything.items():
        assert len(schema) == COLUMNS[table] == len(cols)
        assert dict(schema) == config["schema"][table]
        rows = {len(v) for v in cols.values()}
        assert len(rows) == 1
        if table not in ("nation", "region"):
            assert rows == {int(config["rows_at_sf1"][table] * SF)}
        for name, typ in schema:
            if typ == "string":
                strings.add(name)
                lengths = pc.utf8_length(cols[name])
                assert 1 <= pc.min(lengths).as_py()
                assert pc.max(lengths).as_py() <= WIDTHS[name], name
    assert strings == set(WIDTHS)
    li, part = everything["lineitem"][0], everything["part"][0]
    assert np.allclose(
        li["l_extendedprice"],
        li["l_quantity"] * part["p_retailprice"][li["l_partkey"]])
    assert part["p_retailprice"].min() >= 900 and \
        part["p_retailprice"].max() <= 2098.99
    # a comment is a cut of running text, not one of a handful of phrases
    assert len(set(li["l_comment"].to_pylist())) > 0.9 * len(li["l_comment"])
    assert everything["customer"][0]["c_phone"][0].as_py()[2] == "-"


def test_the_same_seed_gives_the_same_data_and_a_table_alone_too(everything):
    again = tpch_gen.gen_tables(SF, 11, ["orders", "lineitem"])
    other = tpch_gen.gen_tables(SF, 2**31 + 11, ["lineitem"])
    for table, (cols, _schema) in again.items():
        for name, values in cols.items():
            first = everything[table][0][name]
            if hasattr(values, "equals"):
                assert values.equals(first), name
            else:
                assert np.array_equal(values, first), name
    assert not np.array_equal(other["lineitem"][0]["l_quantity"],
                              everything["lineitem"][0]["l_quantity"])


def test_written_files_hold_the_layout(everything, tmp_path):
    import pyarrow.parquet as pq

    layout = {"files_per_table": 4, "row_groups_per_file": 3,
              "min_row_group_rows": 8, "compression": "snappy"}
    paths = tpch_gen.write_parquet(
        {"lineitem": everything["lineitem"]}, str(tmp_path), layout)
    files = sorted(os.listdir(paths["lineitem"]))
    assert len(files) == 4
    md = pq.ParquetFile(os.path.join(paths["lineitem"], files[0])).metadata
    assert md.num_columns == 16 and md.num_row_groups in (2, 3)
    assert md.row_group(0).column(0).compression == "SNAPPY"
    assert str(md.schema.to_arrow_schema().field("l_comment").type) == "string"


def test_a_configuration_that_extends_another():
    base = harness.load_config(os.path.join(BENCH, "configs",
                                            "tpch_sf1_parquet.json"))
    sink = harness.load_config(os.path.join(BENCH, "configs",
                                            "tpch_sf1_parquet_sink.json"))
    assert sink["name"] == "tpch_sf1_parquet_sink"
    for key in ("schema", "layout", "conf", "scale_factor", "assumed"):
        assert sink[key] == base[key]
    assert sink["guarantees"][:len(base["guarantees"])] == base["guarantees"]
    assert len(sink["guarantees"]) == len(base["guarantees"]) + 3
    assert sink["reduced"] == base["reduced"] == ["scale_factor"]
