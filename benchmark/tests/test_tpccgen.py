"""`tpccgen`: clause 4.3.3's cardinalities, key uniqueness, NULL rules and
10% rules at one warehouse, and the row packets it frames."""

import os

import numpy as np
import pyarrow.compute as pc
import pytest

from benchmark import run, tpccgen

SPEC = tpccgen.load_columns(os.path.join(run.HERE, "configs",
                                         "tpcc-columns.json"))


@pytest.fixture(scope="module")
def db():
    return tpccgen.generate(33, 1, SPEC)


def test_the_same_seed_gives_the_same_database(db):
    again = tpccgen.generate(33, 1, SPEC)
    other = tpccgen.generate(34, 1, SPEC)
    for name in ("customer", "order_line"):
        for c in db[name]["columns"]:
            a, b = db[name]["cols"][c["name"]], again[name]["cols"][c["name"]]
            assert a.equals(b) if hasattr(a, "equals") \
                else np.array_equal(a, b)
    assert not np.array_equal(db["orders"]["cols"]["o_ol_cnt"],
                              other["orders"]["cols"]["o_ol_cnt"])


def test_cardinalities_and_columns(db):
    rows = {n: t["rows"] for n, t in db.items()}
    lines = rows.pop("order_line")
    assert rows == {"warehouse": 1, "district": 10, "customer": 30000,
                    "history": 30000, "new_order": 9000, "orders": 30000,
                    "item": 100000, "stock": 100000}
    assert lines == int(db["orders"]["cols"]["o_ol_cnt"].sum())
    assert 290_000 < lines < 310_000
    assert {n: len(t["columns"]) for n, t in db.items()} == {
        "warehouse": 9, "district": 11, "customer": 21, "history": 8,
        "new_order": 3, "orders": 8, "order_line": 10, "item": 5,
        "stock": 17}
    assert db["history"]["key"] == []
    two = tpccgen.generate(33, 2, SPEC)
    assert two["stock"]["rows"] == 200000 and two["item"]["rows"] == 100000


@pytest.mark.parametrize("name", ["warehouse", "district", "customer",
                                  "new_order", "orders", "order_line",
                                  "item", "stock"])
def test_primary_keys_are_unique_and_in_order(db, name):
    t = db[name]
    keys = np.stack([t["cols"][k] for k in t["key"]], axis=1)
    assert len(np.unique(keys, axis=0)) == t["rows"]
    order = np.lexsort([t["cols"][k] for k in reversed(t["key"])])
    assert np.array_equal(order, np.arange(t["rows"]))


def test_null_rules(db):
    o, ol = db["orders"], db["order_line"]
    undelivered = o["cols"]["o_id"] > 2100
    assert np.array_equal(o["nulls"]["o_carrier_id"], undelivered)
    assert undelivered.sum() == 9000
    assert np.array_equal(ol["nulls"]["ol_delivery_d"],
                          ol["cols"]["ol_o_id"] > 2100)
    assert (ol["cols"]["ol_amount"][~ol["nulls"]["ol_delivery_d"]]
            == 0).all()
    assert (ol["cols"]["ol_amount"][ol["nulls"]["ol_delivery_d"]] > 0).all()
    assert np.array_equal(np.sort(db["new_order"]["cols"]["no_o_id"][:900]),
                          np.arange(2101, 3001))
    # an order's customers are a permutation of the district's
    first = o["cols"]["o_c_id"][:3000]
    assert np.array_equal(np.sort(first), np.arange(1, 3001))
    assert all(not t["nulls"] for n, t in db.items()
               if n not in ("orders", "order_line"))


def test_ten_percent_rules_and_string_lengths(db):
    credit = db["customer"]["cols"]["c_credit"]
    bad = pc.sum(pc.equal(credit, "BC")).as_py() / 30000
    assert 0.08 < bad < 0.12
    for table, col in (("item", "i_data"), ("stock", "s_data")):
        v = db[table]["cols"][col]
        share = pc.sum(pc.match_substring(v, "ORIGINAL")).as_py() / len(v)
        assert 0.08 < share < 0.12
        n = pc.utf8_length(v).to_numpy()
        assert n.min() >= 26 and n.max() <= 50
    n = pc.utf8_length(db["customer"]["cols"]["c_data"]).to_numpy()
    assert n.min() >= 300 and n.max() <= 500
    last = db["customer"]["cols"]["c_last"]
    assert last[0].as_py() == "BARBARBAR" and last[371].as_py() \
        == "PRICALLYOUGHT"
    assert len(pc.unique(last)) <= 1000
    zips = db["warehouse"]["cols"]["w_zip"][0].as_py()
    assert len(zips) == 9 and zips.endswith("11111")
    assert (db["customer"]["cols"]["c_balance"] == -1000).all()


def test_row_packets_hold_every_field(db):
    t = db["orders"]
    texts = tpccgen.text_columns(t)
    framed, offsets = tpccgen.frame_rows(texts)
    assert len(offsets) == t["rows"] + 1 and offsets[-1] == len(framed)
    raw = framed.tobytes()
    for row in (0, 2100, 2999, 29999):
        at = int(offsets[row])
        length = int.from_bytes(raw[at:at + 3], "little")
        assert at + 4 + length == offsets[row + 1]
        assert raw[at + 3] == row & 0xFF
        pos, got = at + 4, []
        for _ in t["columns"]:
            if raw[pos] == 0xFB:
                got.append(None)
                pos += 1
            else:
                got.append(raw[pos + 1:pos + 1 + raw[pos]].decode())
                pos += 1 + raw[pos]
        assert pos == offsets[row + 1]
        assert got == [x[row].as_py() for x in texts]
        assert (got[5] is None) == (int(got[0]) > 2100)
    wide = tpccgen.text_columns(db["customer"])
    framed, offsets = tpccgen.frame_rows([wide[0], wide[20]])
    at = int(offsets[0]) + 4 + 2
    n = len(wide[20][0].as_py())
    assert framed[at] == 0xFC and n >= 300
    assert int(framed[at + 1]) | int(framed[at + 2]) << 8 == n
    assert tpccgen.decimal_text(np.array([-1000, 5, 30000000]), 2) \
        .to_pylist() == ["-10.00", "0.05", "300000.00"]
    assert tpccgen.decimal_text(np.array([289]), 4).to_pylist() \
        == ["0.0289"]
