"""The `tpch-lineitem-pg2ch` configuration's own pieces: the generator, the
Postgres stand-in's framing, the plain reference (and that a predicate made
in float32, in float64 on a rounded literal, or on the decimal's text fails
it), and the new readers on small recorded inputs."""

import os
import socket
import struct

import numpy as np
import pyarrow as pa
import pytest

from benchmark import reference_lineitem, run, tpchgen
from benchmark.chserver import Insert
from benchmark.pgserver import Heap, PostgresStandIn
from benchmark.readers import filter_roofline

SPEC = tpchgen.load_columns(
    os.path.join(run.HERE, "configs", "tpch-lineitem-columns.json"))
Q6 = run.load_json("workloads", "tpch-lineitem-q6.json")[
    "transformation"]["transformers"][0]["filter_rows"]["filter"]
D94, D95 = 8766, 9131          # 1994-01-01, 1995-01-01 in days


@pytest.fixture(scope="module")
def table():
    return tpchgen.generate(3_000_000_019, 0.02, SPEC)


# -- the generator -------------------------------------------------------------------

def test_columns_file_is_the_specifications_layout():
    cols = SPEC["columns"]
    assert len(cols) == 16
    kinds = [c["pg"] for c in cols]
    assert kinds.count("integer") == 4 and kinds.count("numeric(15,2)") == 4
    assert kinds.count("date") == 3 and kinds.count("character(1)") == 2
    assert {"character(25)", "character(10)",
            "character varying(44)"} <= set(kinds)
    config = run.load_json("configs", "tpch-lineitem-pg2ch.json")
    assert config["filter"] == Q6 and config["columns"].endswith(".json")


def test_one_seed_gives_one_table(table):
    again = tpchgen.generate(3_000_000_019, 0.02, SPEC)
    other = tpchgen.generate(3_000_000_020, 0.02, SPEC)
    assert again["rows"] == table["rows"]
    for name in table["names"]:
        assert np.array_equal(again["cols"][name], table["cols"][name])
    assert tpchgen.copy_text(again, 0, 5000) == \
        tpchgen.copy_text(table, 0, 5000)
    assert other["rows"] != table["rows"] or not np.array_equal(
        other["cols"]["l_partkey"], table["cols"]["l_partkey"])


def test_distributions_are_the_population_clauses(table):
    c, n = table["cols"], table["rows"]
    orders = 30000
    assert 3.9 * orders < n < 4.1 * orders          # 1-7 lines an order
    assert len(np.unique(c["l_orderkey"])) == orders
    assert ((c["l_orderkey"] - 1) % 32 < 8).all()   # sparse keys
    key = reference_lineitem.row_keys(c["l_orderkey"], c["l_linenumber"])
    assert len(np.unique(key)) == n
    assert c["l_linenumber"].min() == 1 and c["l_linenumber"].max() == 7
    q = c["l_quantity"]
    assert q.min() == 100 and q.max() == 5000 and (q % 100 == 0).all()
    assert set(np.unique(c["l_discount"])) == set(range(0, 11))
    assert set(np.unique(c["l_tax"])) == set(range(0, 9))
    retail = c["l_extendedprice"] // (q // 100)
    assert retail.min() >= 90000 and retail.max() <= 90000 + 20000 + 99900
    ship, commit, receipt = (c[k] for k in (
        "l_shipdate", "l_commitdate", "l_receiptdate"))
    assert ship.min() >= 8035 + 1 and ship.max() <= 10440 + 121
    assert (receipt > ship).all() and (receipt - ship).max() == 30
    assert (commit - ship).min() >= 30 - 121
    today = 9298                                     # 1995-06-17
    flags = np.asarray(table["pools"]["l_returnflag"])[c["l_returnflag"]]
    assert ((flags == b"N") == (receipt > today)).all()
    status = np.asarray(table["pools"]["l_linestatus"])[c["l_linestatus"]]
    assert ((status == b"O") == (ship > today)).all()
    for v in table["pools"]["l_comment"][:2000]:
        assert 1 <= len(v) <= 43
    assert all(len(v) == 25 for v in table["pools"]["l_shipinstruct"])


@pytest.mark.parametrize("seed", [1, 2_147_483_777, 3_000_000_019])
def test_q6_keeps_about_two_percent(seed):
    t = tpchgen.generate(seed, 0.05, SPEC)
    share = reference_lineitem.eval_filter(Q6, t).mean()
    assert 0.017 <= share <= 0.021
    c = t["cols"]
    by_hand = ((c["l_shipdate"] >= D94) & (c["l_shipdate"] < D95)
               & (c["l_discount"] >= 5) & (c["l_discount"] <= 7)
               & (c["l_quantity"] < 2400))
    assert np.array_equal(by_hand, reference_lineitem.eval_filter(Q6, t))
    # the boundary values are there to be got wrong: inside Q6's year and
    # quantity, each of 0.04, 0.05, 0.07, 0.08 holds some 0.6% of the table
    rest = (c["l_shipdate"] >= D94) & (c["l_shipdate"] < D95) \
        & (c["l_quantity"] < 2400)
    for cents in (4, 5, 7, 8):
        assert 0.004 < (rest & (c["l_discount"] == cents)).mean() < 0.009


def test_copy_text_is_what_postgres_writes(table):
    import csv
    import io

    text = tpchgen.copy_text(table, 100, 400).decode()
    rows = list(csv.reader(io.StringIO(text)))
    assert len(rows) == 300 and all(len(r) == 16 for r in rows)
    c = table["cols"]
    for k, r in zip(range(100, 400), rows):
        assert int(r[0]) == c["l_orderkey"][k]
        assert r[4] == "%d.%02d" % divmod(int(c["l_quantity"][k]), 100)
        assert r[6] == "0.%02d" % c["l_discount"][k]
        assert r[10] == str(np.datetime64(int(c["l_shipdate"][k]), "D"))
        assert len(r[13]) == 25 and len(r[14]) == 10     # character(n)
        assert r[15].encode() == \
            table["pools"]["l_comment"][c["l_comment"][k]]
    # a field is quoted only where it has to be
    lines = text.splitlines()
    quoted = [ln for ln in lines if '"' in ln]
    assert quoted and len(quoted) < len(lines)
    assert all("," in row[15] for row, ln in zip(rows, lines) if '"' in ln)


# -- the stand-in ------------------------------------------------------------------------

HAND = (b'1,0.05,1994-01-01,"a, b"\n', b"2,17.00,1995-01-01,plain\n",
        b'3,-12.30,1969-12-31,"say ""x"""\n', b"4,0.00,1970-01-01,\n",
        b"5,99999999999.99,1997-05-19,last\n")


def _framed(lines) -> bytes:
    return b"".join(b"d" + struct.pack("!I", len(ln) + 4) + ln
                    for ln in lines)


def test_frame_rows_is_one_copydata_a_row():
    framed, offsets = tpchgen.frame_rows(b"".join(HAND))
    assert framed.tobytes() == _framed(HAND)
    assert offsets.tolist() == np.cumsum(
        [0] + [len(ln) + 5 for ln in HAND]).tolist()
    with pytest.raises(ValueError):
        tpchgen.frame_rows(b"1,2\n3,4")


class _Client:
    """A hand-written protocol-v3 client: start-up, password, queries."""

    def __init__(self, port, password):
        self.sock = socket.create_connection(("127.0.0.1", port), timeout=10)
        body = b"user\x00u\x00database\x00d\x00\x00"
        self.sock.sendall(struct.pack("!II", len(body) + 8, 196608) + body)
        kind, payload = self.message()
        assert kind == b"R" and struct.unpack("!I", payload)[0] == 3
        self.send(b"p", password.encode() + b"\x00")
        seen = []
        while not seen or seen[-1][0] != b"Z":
            seen.append(self.message())
        assert seen[0] == (b"R", struct.pack("!I", 0))

    def send(self, kind, payload):
        self.sock.sendall(kind + struct.pack("!I", len(payload) + 4)
                          + payload)

    def exactly(self, n):
        out = b""
        while len(out) < n:
            chunk = self.sock.recv(n - len(out))
            if not chunk:
                raise ConnectionError("the stand-in hung up")
            out += chunk
        return out

    def message(self):
        head = self.exactly(5)
        return head[:1], self.exactly(struct.unpack("!I", head[1:])[0] - 4)

    def query(self, sql):
        self.send(b"Q", sql.encode() + b"\x00")
        out = []
        while not out or out[-1][0] != b"Z":
            out.append(self.message())
        return out


def test_standin_serves_catalog_and_ctid_ranges():
    framed, offsets = tpchgen.frame_rows(b"".join(HAND))
    heap = Heap("public", "t", [("id", "integer", True, True),
                                ("amt", "numeric(15,2)", False, True),
                                ("d", "date", False, True),
                                ("s", "character varying(44)", False,
                                 False)], framed, offsets, rows_per_page=2)
    assert heap.pages == 3 and heap.rows == 5
    srv = PostgresStandIn("secret")
    srv.add(heap)
    srv.start()
    try:
        with pytest.raises((AssertionError, ConnectionError)):
            _Client(srv.port, "wrong")
        c = _Client(srv.port, "secret")
        size = c.query("SELECT pg_relation_size('\"public\".\"t\"')")
        assert size[1][1].endswith(b"24576")
        pages = c.query("SELECT relpages FROM pg_class WHERE oid = "
                        "'\"public\".\"t\"'::regclass")
        assert pages[1][1].endswith(b"3")
        cols = c.query("SELECT a.attname AS name, format_type(a.atttypid, "
                       "a.atttypmod) AS typ FROM pg_attribute a WHERE "
                       "a.attrelid = '\"public\".\"t\"'::regclass")
        assert [m[0] for m in cols] == [b"T"] + [b"D"] * 4 + [b"C", b"Z"]
        assert b"numeric(15,2)" in cols[2][1]
        copy = ('COPY (SELECT "id", "amt", "d", "s" FROM "public"."t"%s) '
                "TO STDOUT WITH (FORMAT csv, HEADER false)")
        whole = c.query(copy % "")
        assert [m[0] for m in whole] == [b"H"] + [b"d"] * 5 + \
            [b"c", b"C", b"Z"]
        assert b"".join(m[1] for m in whole[1:6]) == b"".join(HAND)
        assert whole[7][1] == b"COPY 5\x00"
        part = c.query(copy % " WHERE ctid >= '(1,0)'::tid AND "
                              "ctid < '(4,0)'::tid")
        assert [m[1] for m in part if m[0] == b"d"] == list(HAND[2:])
        none = c.query(copy % " WHERE ctid >= '(3,0)'::tid AND "
                              "ctid < '(4,0)'::tid")
        assert not [m for m in none if m[0] == b"d"]
        bad = c.query("SELECT now()")
        assert bad[0][0] == b"E" and bad[-1][0] == b"Z"
        assert len(srv.errors) == 2        # the wrong password, and this
        assert srv.cost["copies"] == 3 and srv.cost["copy_rows"] == 8
        assert srv.cost["bytes"] == len(_framed(HAND)) + \
            len(_framed(HAND[2:]))
        c.send(b"X", b"")
    finally:
        srv.stop()


@pytest.mark.parametrize("statement", [
    "SELECT now()", "DELETE FROM public.t", "SELECT * FROM public.t",
    'COPY (SELECT "id" FROM "public"."t") TO STDOUT WITH (FORMAT csv, '
    "HEADER false)",
    'COPY (SELECT "id", "amt" FROM "public"."t" WHERE "id" > 3) TO STDOUT '
    "WITH (FORMAT csv, HEADER false)",
    "SELECT pg_relation_size('\"public\".\"nosuch\"')",
])
def test_standin_answers_what_it_does_not_know_with_an_error(statement):
    framed, offsets = tpchgen.frame_rows(b"".join(HAND))
    srv = PostgresStandIn("")
    srv.add(Heap("public", "t", [("id", "integer", True, True),
                                 ("amt", "numeric(15,2)", False, True)],
                 framed, offsets, rows_per_page=2))
    srv.start()
    try:
        c = socket.create_connection(("127.0.0.1", srv.port), timeout=10)
        client = _Client.__new__(_Client)
        client.sock = c
        body = b"user\x00u\x00database\x00d\x00\x00"
        c.sendall(struct.pack("!II", len(body) + 8, 196608) + body)
        while client.message()[0] != b"Z":
            pass
        got = client.query(statement)
        # an ErrorResponse and ReadyForQuery: never an empty answer
        assert [m[0] for m in got] == [b"E", b"Z"]
        assert len(srv.errors) == 1
        assert client.query("SELECT 1")[0][0] == b"T"   # and it lives on
    finally:
        srv.stop()


@pytest.mark.parametrize("parts", [1, 2, 3, 4, 7])
def test_ctid_ranges_partition_the_rows_exactly_once(table, parts):
    """The provider's own split (blocks over n parts, the last range one
    past the end) serves every row once, in order; a cursor on ctid
    serves the rows from its page on."""
    n = 5000
    text = tpchgen.copy_text(table, 0, n)
    framed, offsets = tpchgen.frame_rows(text)
    heap = Heap("public", "lineitem", [], framed, offsets, rows_per_page=56)
    per = -(-heap.pages // parts)
    got, rows = [], 0
    for i in range(parts):
        data, k = heap.slice(i * per, min(heap.pages + 1, (i + 1) * per))
        got.append(bytes(data))
        rows += k
    assert rows == n and b"".join(got) == framed.tobytes()
    data, k = heap.slice((parts - 1) * per, heap.pages)
    assert bytes(data) == got[-1] and k == n - (parts - 1) * per * 56


def test_the_row_count_is_the_specifications():
    for sf, rows in ((0.001, 6001), (0.0123, 73815)):
        t = tpchgen.generate(17, sf, SPEC)
        assert t["rows"] == rows == len(t["cols"]["l_orderkey"])
        lines = np.bincount(t["cols"]["l_orderkey"])
        assert lines[lines > 0].min() >= 1 and lines.max() <= 7
    assert SPEC["rows_per_scale_factor"] == 6_001_215


# -- the reference and the comparison --------------------------------------------------

def _landed(table, mask, spoil=None) -> Insert:
    """What a sink that kept `mask` would have been sent, as the
    ClickHouse stand-in keeps an insert."""
    keep = np.flatnonzero(mask)
    cols = {}
    for name in table["names"]:
        v = table["cols"][name][keep]
        pg = table["pg_types"][name]
        if name in table["pools"]:
            cols[name] = pa.array(table["pools"][name],
                                  type=pa.large_binary()).take(pa.array(v))
        elif pg.startswith("numeric"):
            cols[name] = pa.array(tpchgen.cents_text(v),
                                  type=pa.large_binary())
        else:
            cols[name] = v.astype(np.int32)
    if spoil:
        spoil(cols)
    return Insert(len(keep), 0, cols, {})


def _numbers(table, inserts) -> dict:
    expected = reference_lineitem.expected_rows(table, Q6)
    types = {c["name"]: c["ch"] for c in SPEC["columns"]}
    out = reference_lineitem.compare_snapshot(
        [{"inserts": inserts, "ch_types": types, "tables": ["lineitem"]}],
        expected)
    return {k: v[0] for k, v in out["numbers"].items()} | {
        "failed": out["failed"], "attempted": out["attempted"]}


def test_the_sound_answer_compares_equal(table):
    mask = reference_lineitem.eval_filter(Q6, table)
    half = np.flatnonzero(mask)[::2]
    a, b = mask.copy(), mask.copy()
    a[half] = False
    b[np.setdiff1d(np.flatnonzero(mask), half)] = False
    got = _numbers(table, [_landed(table, a), _landed(table, b)])
    assert got["attempted"] == int(mask.sum()) > 1500
    assert all(v == 0 for k, v in got.items() if k != "attempted"), got


def _q6_with(table, discount_ok, quantity_ok):
    c = table["cols"]
    return (c["l_shipdate"] >= D94) & (c["l_shipdate"] < D95) \
        & discount_ok & quantity_ok


def test_a_predicate_in_float32_or_on_text_fails_the_comparison(table):
    c = table["cols"]
    qty_ok = c["l_quantity"] < 2400
    text = np.array(reference_lineitem.cents_text(c["l_discount"]), dtype=object)
    qtext = np.array(reference_lineitem.cents_text(c["l_quantity"]), dtype=object)
    f32 = np.array([float(t) for t in text], dtype=np.float32)
    f64 = f32.astype(np.float64) * 0 + np.array([float(t) for t in text])
    wrong = {
        # the column in float32 against the literals as written (float64):
        # float32(0.07) is above 0.07, float32(0.05) above 0.05
        "float32": _q6_with(table, (f32 >= np.float64(0.05))
                            & (f32 <= np.float64(0.07)), qty_ok),
        # float64 on a literal put together as Q6 words it, 0.06 +- 0.01
        "float64_rounded_literal": _q6_with(
            table, (f64 >= 0.06 - 0.01) & (f64 <= 0.06 + 0.01), qty_ok),
        # the decimal's text against the literal's text
        "text": _q6_with(table, (text >= b"0.05") & (text <= b"0.07"),
                         qtext < b"24"),
    }
    for how, mask in wrong.items():
        got = _numbers(table, [_landed(table, mask)])
        assert got["rows_missing"] + got["rows_extra"] > 0, how
        assert got["failed"] > 0, how
    assert _numbers(table, [_landed(table, wrong["float32"])])[
        "rows_missing"] > 100


def test_every_kind_of_damage_has_its_number(table):
    mask = reference_lineitem.eval_filter(Q6, table)
    assert _numbers(table, [_landed(table, mask)] * 2)[
        "rows_duplicated"] == int(mask.sum())
    extra = mask.copy()
    extra[np.flatnonzero(~mask)[:7]] = True
    assert _numbers(table, [_landed(table, extra)])["rows_extra"] == 7
    assert _numbers(table, [])["rows_missing"] == int(mask.sum())

    def one_cent(cols):
        v = cols["l_extendedprice"].to_pylist()
        v[3] = v[3][:-1] + (b"0" if v[3][-1:] != b"0" else b"1")
        cols["l_extendedprice"] = pa.array(v, type=pa.large_binary())

    def one_day(cols):
        cols["l_commitdate"] = cols["l_commitdate"].copy()
        cols["l_commitdate"][5] += 1

    def no_padding(cols):
        cols["l_shipmode"] = pa.array(
            [v.rstrip() for v in cols["l_shipmode"].to_pylist()],
            type=pa.large_binary())

    for spoil, cells in ((one_cent, 1), (one_day, 1),
                         (no_padding, None)):
        got = _numbers(table, [_landed(table, mask, spoil)])
        assert got["cells_mismatched"] == (cells or got["cells_mismatched"])
        assert got["cells_mismatched"] > 0 and got["rows_missing"] == 0


def test_the_reference_refuses_what_it_cannot_read(table):
    for bad in ("l_discount < 0.055", "l_shipdate < 24",
                "l_comment = 'x'", "l_quantity < '1994-01-01'",
                "l_shipdate >= DATE '1994-01-01'"):
        with pytest.raises(ValueError):
            reference_lineitem.eval_filter(bad, table)


# -- the reader ------------------------------------------------------------------------------

def test_filter_roofline_on_a_made_up_trace():
    spec = run.load_json("metrics", "filter_program_roofline.q6.json")
    assert spec["reader"] == "filter_roofline"
    data = {"trace": {"window_s": 10.0, "busy_s": 0.002,
                      "modules": {"jit_program": 0.001, "jit_other": 0.5}},
            "telemetry_traced": {"filter_rows_device": 1_000_000},
            "peaks": {"hbm_bytes_per_s": 819e9}}
    # 4 + 4 + 4 bytes in and 1 bit out a row: the date as int32 days, the
    # two numeric(15,2) as the int32 unscaled integers the chip compares
    assert spec["params"]["bytes_in_per_row"] == 12
    assert spec["params"]["bits_out_per_row"] == 1
    want = 100.0 * (12.125e6 / 819e9) / 0.001
    assert filter_roofline.read(spec["params"], data) == \
        pytest.approx(want)
    assert 0 < want < 100
    for silent in ({"telemetry_traced": {}},
                   {"telemetry_traced": {"filter_rows_device": 0}},
                   {"trace": {"window_s": 10.0, "busy_s": 0.0,
                              "modules": {"jit_other": 0.5}}},
                   {"trace": {"window_s": 0.0, "busy_s": 0.0,
                              "modules": {}}}):
        assert filter_roofline.read(spec["params"],
                                    {**data, **silent}) is None
