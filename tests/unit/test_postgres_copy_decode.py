"""The Postgres source's COPY decode, against tests/recipes/fake_postgres.py
on a seeded TPC-H LINEITEM of a few thousand rows (the benchmark's
generator, benchmark/tpchgen.py): every column of every batch equals the
generator's values - integers at their types, DATE as int32 days,
numeric(15,2) as the text the source sent with precision and scale in the
schema, CHAR(n) blank-padded - through ctid parts, with a message a row
as a PostgreSQL backend frames csv and with many rows a message; quoted
csv fields; the empty string against NULL; a batch boundary inside a
socket block; CopyData messages cut by the socket's blocks.
"""

import datetime
import os

import numpy as np
import pytest

from benchmark import tpchgen
from tests.recipes.fake_postgres import FakePG, FakeTable
from transferia_tpu.abstract import TableID
from transferia_tpu.abstract.schema import CanonicalType
from transferia_tpu.abstract.table import TableDescription
from transferia_tpu.providers.postgres import wire
from transferia_tpu.providers.postgres.provider import (
    PGSourceParams,
    PGStorage,
)
from transferia_tpu.stats import trace

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SPEC = tpchgen.load_columns(os.path.join(
    ROOT, "benchmark", "configs", "tpch-lineitem-columns.json"))
EPOCH = datetime.date(1970, 1, 1)
TID = TableID("public", "lineitem")


def lineitem(seed: int, scale_factor: float = 0.0005):
    """(the generator's table, the same rows as the text Postgres holds)"""
    table = tpchgen.generate(seed, scale_factor, SPEC)
    cols, pools = table["cols"], table["pools"]
    rows = []
    for i in range(table["rows"]):
        row = {}
        for c in SPEC["columns"]:
            name, v = c["name"], int(cols[c["name"]][i])
            if name in pools:
                row[name] = pools[name][v].decode()
            elif c["pg"].startswith("numeric"):
                row[name] = f"{v // 100}.{v % 100:02d}"
            elif c["pg"] == "date":
                row[name] = (EPOCH + datetime.timedelta(days=v)).isoformat()
            else:
                row[name] = str(v)
        rows.append(row)
    return table, rows


def serve(rows, rows_per_message, rows_per_page=56, nullable=()):
    key = set(SPEC["key"])
    srv = FakePG().start()
    srv.copy_rows_per_message = rows_per_message
    srv.add_table(FakeTable(
        "public", "lineitem",
        [(c["name"], c["pg"], c["name"] in key, c["name"] not in nullable)
         for c in SPEC["columns"]],
        rows=rows, rows_per_page=rows_per_page))
    return srv


def load(srv, **params):
    storage = PGStorage(PGSourceParams(
        host="127.0.0.1", port=srv.port, database="db", user="u", **params))
    batches = []
    try:
        schema = storage.table_schema(TID)
        for part in storage.shard_table(TableDescription(id=TID)):
            storage.load_table(part, batches.append)
    finally:
        storage.close()
    return schema, batches


def column_values(batches, name):
    out = []
    for b in batches:
        out.extend(b.column(name).to_pylist())
    return out


@pytest.mark.parametrize("rows_per_message", [1, 4096],
                         ids=["a_message_a_row", "packed_messages"])
@pytest.mark.parametrize("seed", [7, 2_147_483_777])
def test_every_column_of_every_batch_is_the_generators(seed,
                                                       rows_per_message):
    table, rows = lineitem(seed)
    srv = serve(rows, rows_per_message)
    trace.enable(True)
    trace.reset()
    try:
        # 3 ctid parts; batches of 700 rows cut them unevenly
        schema, batches = load(srv, desired_part_size_bytes=20 * 8192,
                               batch_rows=700)
        spans = trace.spans()
    finally:
        trace.enable(False)
        srv.stop()
    n = table["rows"]
    assert 2500 < n < 3500
    assert sum(b.n_rows for b in batches) == n
    assert max(b.n_rows for b in batches) == 700
    # a part's last batch alone is short
    assert sum(1 for b in batches if b.n_rows < 700) <= 3
    for c in SPEC["columns"]:
        name, want = c["name"], table["cols"][c["name"]]
        cs = schema.find(name)
        got = column_values(batches, name)
        if name in table["pools"]:
            assert cs.data_type == CanonicalType.UTF8
            assert got == [table["pools"][name][int(v)].decode()
                           for v in want], name
        elif c["pg"].startswith("numeric"):
            assert cs.data_type == CanonicalType.DECIMAL
            assert cs.original_type == "pg:numeric(15,2)"
            assert dict(cs.properties) == {"precision": 15, "scale": 2}
            assert [str(v) for v in got] == [
                f"{int(v) // 100}.{int(v) % 100:02d}" for v in want], name
        elif c["pg"] == "date":
            assert cs.data_type == CanonicalType.DATE
            assert batches[0].column(name).data.dtype == np.int32
            days = np.concatenate([b.column(name).data for b in batches])
            np.testing.assert_array_equal(days, want)
        else:
            assert cs.data_type == CanonicalType.INT32
            assert batches[0].column(name).data.dtype == np.int32
            np.testing.assert_array_equal(
                np.concatenate([b.column(name).data for b in batches]),
                want)
    # CHAR(n) arrives blank-padded, as Postgres sends it
    assert all(len(v) == 25 for v in column_values(batches,
                                                   "l_shipinstruct"))
    # the spans: what was read is what was decoded, row for row
    args = {}
    for s in spans:
        if s[6] >= 0:
            args.setdefault(s[0], []).append(s[7] or {})
    decodes = [a for a in args["source_decode"]
               if a.get("format") == "pg_copy"]
    assert sum(a["rows"] for a in decodes) == n
    assert sum(a["bytes"] for a in decodes) == \
        sum(a["bytes"] for a in args["pg_copy_read"]) > 100 * n
    if rows_per_message == 1:
        assert sum(a["messages"] for a in args["pg_copy_read"]) == n


def test_quoted_fields_and_the_empty_string_against_null():
    _, rows = lineitem(11, 0.0002)
    comments = ['furious, "quoted" deposits', "", None, "two\nlines, one row",
                'a lone " quote', ",", '""', " padded "]
    for i, text in enumerate(comments):
        rows[3 + 5 * i]["l_comment"] = text
    srv = serve(rows, 1, nullable=("l_comment",))
    try:
        schema, batches = load(srv, batch_rows=97)
    finally:
        srv.stop()
    got = column_values(batches, "l_comment")
    assert got == [r["l_comment"] for r in rows]
    for i, text in enumerate(comments):
        assert got[3 + 5 * i] == text
    assert got.count(None) == 1 and got.count("") == 1
    assert not schema.find("l_comment").required


@pytest.mark.parametrize("native", [True, False],
                         ids=["native", "python"])
def test_copydata_messages_cut_by_the_sockets_blocks(native, monkeypatch):
    """Whole messages are unframed, the cut one waits for its rest, and a
    message of another type stops the walk."""
    if not native:
        from transferia_tpu import native as native_mod

        monkeypatch.setattr(native_mod, "lib", lambda: None)
    lines = [b"1,0.05,abc\n", b"2,,\"x,y\"\n", b"3,17.00,\n"]
    stream = b"".join(b"d" + (len(x) + 4).to_bytes(4, "big") + x
                      for x in lines)
    for cut in range(len(stream) + 1):
        payload, rows, used = wire._unframe_copy_data(stream[:cut], 0)
        whole = [x for k, x in enumerate(lines)
                 if len(b"".join(lines[:k + 1])) + 5 * (k + 1) <= cut]
        assert payload == b"".join(whole) and rows == len(whole)
        assert used == len(payload) + 5 * rows
    tail = stream + b"c\x00\x00\x00\x04" + b"C\x00\x00\x00\x09COPY\x00"
    payload, rows, used = wire._unframe_copy_data(b"xx" + tail, 2)
    assert (payload, rows, used) == (b"".join(lines), 3, len(stream))


def test_a_copy_that_fails_raises_the_servers_error():
    _, rows = lineitem(5, 0.0002)
    srv = serve(rows, 1)
    try:
        storage = PGStorage(PGSourceParams(
            host="127.0.0.1", port=srv.port, database="db", user="u"))
        with pytest.raises(wire.PGError):
            storage.load_table(
                TableDescription(id=TableID("public", "no_such_table")),
                lambda b: None)
        storage.close()
    finally:
        srv.stop()
