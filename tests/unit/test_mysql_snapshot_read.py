"""The MySQL source's snapshot read: one streamed result set a part (no
OFFSET for any table, composite key or none), rows to ColumnBatches a
column at a time, and the split of a large table into key ranges."""

import collections

import pytest

from tests.recipes.fake_mysql import FakeMySQL, FakeMyTable
from transferia_tpu.abstract.schema import TableID
from transferia_tpu.abstract.table import TableDescription
from transferia_tpu.providers.mysql import provider as mysql_provider
from transferia_tpu.providers.mysql.provider import (
    MySQLSourceParams,
    MySQLStorage,
)

CUSTOMER = [("c_id", "int", "int", True, True),
            ("c_d_id", "tinyint", "tinyint", True, True),
            ("c_w_id", "smallint", "smallint", True, True),
            ("c_last", "varchar", "varchar(16)", False, False),
            ("c_balance", "decimal", "decimal(12,2)", False, False),
            ("c_since", "datetime", "datetime", False, False)]
HISTORY = [("h_c_id", "int", "int", False, False),
           ("h_amount", "decimal", "decimal(6,2)", False, False),
           ("h_data", "varchar", "varchar(24)", False, False)]
ITEM = [("i_id", "int", "int", True, True),
        ("i_name", "varchar", "varchar(24)", False, False)]


def customers(warehouses, districts, per):
    return [{"c_id": str(c), "c_d_id": str(d), "c_w_id": str(w),
             "c_last": None if c % 7 == 0 else f"NAME{c}é",
             "c_balance": "-10.00" if c % 2 else "300000.05",
             "c_since": f"2024-01-{1 + c % 28:02d} 0{c % 10}:00:0{d % 10}"}
            for w in range(1, warehouses + 1)
            for d in range(1, districts + 1) for c in range(1, per + 1)]


@pytest.fixture
def fake():
    srv = FakeMySQL(user="root", password="pw")
    srv.refuse_offset = True
    srv.start()
    yield srv
    srv.stop()


def storage(fake, parts=1, **params):
    return MySQLStorage(MySQLSourceParams(
        host="127.0.0.1", port=fake.port, database="tpcc", user="root",
        password="pw", **params), parts=parts)


def load(st, td):
    got = []
    st.load_table(td, got.append)
    rows = []
    for b in got:
        d = b.to_pydict()
        rows += list(zip(*[d[n] for n in b.columns]))
    return got, rows


TABLES = {
    "composite": (CUSTOMER, customers(1, 3, 50)),
    "keyless": (HISTORY, [{"h_c_id": str(i % 5), "h_amount": "10.00",
                           "h_data": "x" * (i % 24)} for i in range(130)]),
    "single": (ITEM, [{"i_id": str(i), "i_name": f"item {i}"}
                      for i in range(1, 100)]),
    "empty": (ITEM, []),
}


@pytest.mark.parametrize("kind", sorted(TABLES))
def test_load_table_streams_any_table_without_offset(fake, kind):
    columns, rows = TABLES[kind]
    fake.add_table(FakeMyTable("tpcc", "t", columns, rows=rows))
    st = storage(fake, batch_rows=40)
    try:
        batches, got = load(st, TableDescription(id=TableID("tpcc", "t")))
    finally:
        st.close()
    assert fake.offset_statements == 0
    assert len(got) == len(rows)
    # every batch but the last is batch_rows: no table is held whole
    assert [b.n_rows for b in batches[:-1]] == [40] * (len(batches) - 1)
    names = [c[0] for c in columns]
    want = collections.Counter(
        tuple(r[n] for n in names) for r in rows)

    def text(n, v):
        if v is None:
            return None
        if n == "c_since":      # microseconds since the epoch
            import datetime

            return (datetime.datetime(1970, 1, 1)
                    + datetime.timedelta(microseconds=int(v))).strftime(
                        "%Y-%m-%d %H:%M:%S")
        return str(v)

    assert collections.Counter(
        tuple(text(n, v) for n, v in zip(names, r)) for r in got) == want


def test_a_value_arrow_cannot_read_goes_cell_by_cell(fake):
    rows = customers(1, 1, 30)
    rows[17]["c_since"] = "0000-00-00 00:00:00"
    fake.add_table(FakeMyTable("tpcc", "t", CUSTOMER, rows=rows))
    st = storage(fake, batch_rows=8)
    try:
        _batches, got = load(st, TableDescription(id=TableID("tpcc", "t")))
    finally:
        st.close()
    assert sorted(int(r[0]) for r in got) == list(range(1, 31))


SPLITS = {
    # name: (rows, parts wanted, pk order, parts expected)
    "one_warehouse_cut_by_district": (customers(1, 10, 12), 4,
                                      ["c_w_id", "c_d_id", "c_id"], 4),
    "warehouses_cut_first": (customers(5, 2, 6), 4,
                             ["c_w_id", "c_d_id", "c_id"], 4),
    "fewer_values_than_parts": (customers(2, 3, 20), 4,
                                ["c_w_id", "c_d_id", "c_id"], 2),
    "sparse_keys_leave_empty_ranges": (
        [r for r in customers(1, 10, 12) if r["c_d_id"] in ("1", "10")],
        4, ["c_w_id", "c_d_id", "c_id"], 4),
    "smaller_than_process_count": (customers(1, 1, 2), 8,
                                   ["c_w_id", "c_d_id", "c_id"], 2),
    "column_order_where_the_server_names_no_index": (
        customers(1, 4, 10), 4, None, 4),
    "one_row": (customers(1, 1, 1), 4, ["c_w_id", "c_d_id", "c_id"], 1),
}


@pytest.mark.parametrize("case", sorted(SPLITS))
def test_key_ranges_cover_a_table_once_and_only_once(fake, case,
                                                     monkeypatch):
    rows, parts, pk_order, expected = SPLITS[case]
    t = FakeMyTable("tpcc", "customer", CUSTOMER, rows=rows)
    t.pk_order = pk_order
    fake.add_table(t)
    monkeypatch.setattr(mysql_provider, "_MIN_PART_BYTES", 10)
    st = storage(fake, parts=parts)
    try:
        tds = st.shard_table(TableDescription(
            id=TableID("tpcc", "customer"), eta_rows=len(rows)))
        assert len(tds) == expected
        got = []
        for td in tds:
            got += load(st, td)[1]
    finally:
        st.close()
    assert fake.offset_statements == 0
    keys = collections.Counter((r[2], r[1], r[0]) for r in got)
    assert keys == collections.Counter(
        (int(r["c_w_id"]), int(r["c_d_id"]), int(r["c_id"])) for r in rows)
    assert max(keys.values()) == 1
    if pk_order and expected > 1 and case.startswith("one_warehouse"):
        # cut on the district under equality on the one warehouse
        assert all("`c_w_id` = 1" in td.filter and "`c_d_id`" in td.filter
                   for td in tds)


@pytest.mark.parametrize("why", ["small", "keyless", "filtered",
                                 "one_thread", "text_key"])
def test_a_table_that_is_not_cut_is_one_part(fake, why, monkeypatch):
    columns, rows = CUSTOMER, customers(1, 4, 10)
    parts, td_filter = 4, ""
    if why != "small":              # small: under two `_MIN_PART_BYTES`
        monkeypatch.setattr(mysql_provider, "_MIN_PART_BYTES", 10)
    if why == "keyless":
        columns, rows = TABLES["keyless"]
    elif why == "filtered":
        td_filter = "`c_d_id` > 2"
    elif why == "one_thread":
        parts = 1
    elif why == "text_key":
        columns = [("code", "varchar", "varchar(8)", True, True)] + ITEM[1:]
        rows = [{"code": f"k{i}", "i_name": "n"} for i in range(50)]
    fake.add_table(FakeMyTable("tpcc", "t", columns, rows=rows))
    st = storage(fake, parts=parts)
    try:
        td = TableDescription(id=TableID("tpcc", "t"), filter=td_filter)
        assert st.shard_table(td) == [td]
    finally:
        st.close()
