"""`mysqlserver` and `broker_produce` against bare socket clients written
here: what a statement gets back, that OFFSET is answered and counted, that
an unknown statement gets an ERR packet at once, that a batch is checked at
append, and the transactional subset the staged publish relies on."""

import os
import socket
import struct

import numpy as np
import pytest

from benchmark import broker_produce, mysqlserver, run, tpccgen
from benchmark.tests import tpcc_helpers as h


# -- MySQL ---------------------------------------------------------------------------

class MyClient:
    def __init__(self, port, password="pw"):
        self.sock = socket.create_connection(("127.0.0.1", port), timeout=10)
        greeting = self.packet()
        assert greeting[0] == 10
        end = greeting.index(b"\x00", 1)
        nonce = greeting[end + 5:end + 13] + greeting[end + 32:end + 44]
        token = mysqlserver.native_password_token(password, nonce)
        self.send(struct.pack("<IIB23x", 0x1 | 0x200 | 0x8000 | 0x80000,
                              1 << 24, 33) + b"root\x00"
                  + bytes([len(token)]) + token
                  + b"mysql_native_password\x00", seq=1)
        self.auth = self.packet()

    def exact(self, n):
        out = b""
        while len(out) < n:
            chunk = self.sock.recv(n - len(out))
            assert chunk, "server went away"
            out += chunk
        return out

    def packet(self):
        head = self.exact(4)
        return self.exact(head[0] | head[1] << 8 | head[2] << 16)

    def send(self, payload, seq=0):
        self.sock.sendall(struct.pack("<I", len(payload))[:3]
                          + bytes([seq]) + payload)

    def query(self, sql):
        """[[field or None]] of a text result set; an ERR's message."""
        self.send(b"\x03" + sql.encode())
        first = self.packet()
        if first[:1] == b"\xff":
            return first[9:].decode()
        for _ in range(first[0] + 1):
            self.packet()
        rows = []
        while True:
            pkt = self.packet()
            if pkt[:1] == b"\xfe" and len(pkt) < 9:
                return rows
            pos, row = 0, []
            while pos < len(pkt):
                n = pkt[pos]
                if n == 0xFB:
                    row.append(None)
                    pos += 1
                    continue
                if n == 0xFC:
                    n = pkt[pos + 1] | pkt[pos + 2] << 8
                    pos += 2
                row.append(pkt[pos + 1:pos + 1 + n].decode())
                pos += 1 + n
            rows.append(row)


@pytest.fixture(scope="module")
def mysql():
    spec = h.small_spec()
    db = tpccgen.generate(9, 1, spec)
    srv = mysqlserver.MySQLStandIn(password="pw")
    for name, t in db.items():
        framed, offsets = tpccgen.frame_rows(tpccgen.text_columns(t))
        srv.add(mysqlserver.Table(
            "tpcc", name,
            [(c["name"], c["mysql"], c["name"] in t["nulls"])
             for c in t["columns"]], list(t["key"]),
            {c["name"]: t["cols"][c["name"]] for c in t["columns"]
             if c["kind"] == "int" and c["name"] not in t["nulls"]},
            framed, offsets))
    srv.start()
    yield srv, db
    srv.stop()


def test_handshake_checks_the_password(mysql):
    srv, _db = mysql
    assert MyClient(srv.port).auth[:1] == b"\x00"
    assert MyClient(srv.port, password="no").auth[:1] == b"\xff"


def test_catalog_statements(mysql):
    srv, db = mysql
    c = MyClient(srv.port)
    tables = c.query(
        "SELECT TABLE_NAME AS name, TABLE_ROWS AS eta FROM "
        "information_schema.TABLES WHERE TABLE_SCHEMA = 'tpcc' "
        "AND TABLE_TYPE = 'BASE TABLE'")
    assert {t[0]: int(t[1]) for t in tables} == {
        n: t["rows"] for n, t in db.items()}
    cols = c.query(
        "SELECT COLUMN_NAME AS name, DATA_TYPE AS typ, COLUMN_TYPE AS "
        "full_typ, IS_NULLABLE AS nullable, COLUMN_KEY AS ckey FROM "
        "information_schema.COLUMNS WHERE TABLE_SCHEMA = 'tpcc' AND "
        "TABLE_NAME = 'orders' ORDER BY ORDINAL_POSITION")
    assert [r[0] for r in cols] == [x["name"]
                                    for x in db["orders"]["columns"]]
    assert cols[0] == ["o_id", "int", "int", "NO", "PRI"]
    assert cols[5] == ["o_carrier_id", "tinyint", "tinyint", "YES", ""]
    assert [r[0] for r in c.query(
        "SELECT COLUMN_NAME AS name FROM information_schema.STATISTICS "
        "WHERE TABLE_SCHEMA = 'tpcc' AND TABLE_NAME = 'orders' AND "
        "INDEX_NAME = 'PRIMARY' ORDER BY SEQ_IN_INDEX")] == [
            "o_w_id", "o_d_id", "o_id"]
    assert c.query("SELECT COUNT(*) FROM `tpcc`.`history`") == [["400"]]
    size = c.query("SELECT DATA_LENGTH + INDEX_LENGTH FROM "
                   "information_schema.TABLES WHERE TABLE_SCHEMA = 'tpcc' "
                   "AND TABLE_NAME = 'stock'")
    assert int(size[0][0]) > 300 * 250
    assert c.query("SELECT MIN(`o_d_id`) AS lo, MAX(`o_d_id`) AS hi FROM "
                   "`tpcc`.`orders` WHERE `o_w_id` = 1") == [["1", "10"]]
    assert c.query("SELECT MIN(`o_d_id`) AS lo, MAX(`o_d_id`) AS hi FROM "
                   "`tpcc`.`orders` WHERE `o_w_id` = 7") == [[None, None]]
    c.send(b"\x0e")
    assert c.packet()[:1] == b"\x00"


def select(db, name, where="", tail=""):
    cols = ", ".join(f"`{c['name']}`" for c in db[name]["columns"])
    return f"SELECT {cols} FROM `tpcc`.`{name}`{where}{tail}"


def test_selects_by_key_range_and_offset_is_counted(mysql):
    srv, db = mysql
    c = MyClient(srv.port)
    whole = c.query(select(db, "orders"))
    assert len(whole) == 400
    assert whole[0][:3] == ["1", "1", "1"] and whole[29][5] is None
    cut = c.query(select(db, "orders", " WHERE `o_w_id` = 1 AND "
                                       "`o_d_id` >= 4 AND `o_d_id` < 7"))
    assert cut == [r for r in whole if 4 <= int(r[1]) < 7]
    assert c.query(select(db, "orders", " WHERE `o_d_id` > '8'")) == \
        [r for r in whole if int(r[1]) > 8]
    before = dict(srv.cost)
    # the parent's page of a composite key: the key's columns in the
    # table's column order, LIMIT and OFFSET
    page = c.query(select(db, "orders", "",
                          " ORDER BY `o_id`, `o_d_id`, `o_w_id` "
                          "LIMIT 50 OFFSET 100"))
    by_id = sorted(whole, key=lambda r: (int(r[0]), int(r[1])))
    assert page == by_id[100:150]
    assert c.query(select(db, "history", "", " LIMIT 30 OFFSET 390")) \
        == c.query(select(db, "history"))[390:]
    assert srv.cost["rows_skipped_by_offset"] \
        - before["rows_skipped_by_offset"] == 490
    assert srv.cost["offset_statements"] - before["offset_statements"] == 2
    keyset = c.query(select(db, "item", " WHERE `i_id` > 290",
                            " ORDER BY `i_id` LIMIT 4"))
    assert [r[0] for r in keyset] == ["291", "292", "293", "294"]


def test_an_unknown_statement_gets_an_error_at_once(mysql):
    srv, db = mysql
    c = MyClient(srv.port)
    n = len(srv.errors)
    for sql in ("SELECT `o_id` FROM `tpcc`.`orders`",
                "SELECT VERSION()",
                select(db, "orders", " WHERE `o_entry_d` > '2024'"),
                "SELECT COUNT(*) FROM `tpcc`.`nothing`"):
        assert isinstance(c.query(sql), str), sql
    assert len(srv.errors) == n + 4
    assert c.query("SELECT 1") == [["1"]]       # and lives on


# -- the broker -------------------------------------------------------------------

class KafkaClient:
    def __init__(self, port):
        self.sock = socket.create_connection(("127.0.0.1", port), timeout=10)
        self.corr = 0

    def call(self, api, version, body):
        self.corr += 1
        msg = struct.pack("!hhih", api, version, self.corr, 1) + b"c" + body
        self.sock.sendall(struct.pack("!i", len(msg)) + msg)
        size = struct.unpack("!i", self.sock.recv(4, socket.MSG_WAITALL))[0]
        out = self.sock.recv(size, socket.MSG_WAITALL)
        assert struct.unpack("!i", out[:4])[0] == self.corr
        return out[4:]

    def init(self, txn, epoch):
        out = self.call(22, 3, struct.pack("!h", len(txn)) + txn.encode()
                        + struct.pack("!iqh", 60000, -1, epoch))
        _throttle, err, pid, got = struct.unpack("!ihqh", out)
        return err, pid, got

    def produce(self, txn, batches, topic=b"cdc.tpcc"):
        """batches: [(partition, blob)] -> [(partition, error)]"""
        body = struct.pack("!h", -1) if txn is None else \
            struct.pack("!h", len(txn)) + txn.encode()
        body += struct.pack("!hii", -1, 30000, 1)
        body += struct.pack("!h", len(topic)) + topic
        body += struct.pack("!i", len(batches))
        for p, blob in batches:
            body += struct.pack("!ii", p, len(blob)) + blob
        out = self.call(0, 3, body)
        pos = 4 + 2 + len(topic)
        n, = struct.unpack_from("!i", out, pos)
        res = [struct.unpack_from("!ihqq", out, pos + 4 + 22 * i)[:2]
               for i in range(n)]
        return res


@pytest.fixture
def broker():
    b = broker_produce.ProduceBroker("cdc.tpcc", 16).start()
    yield b
    b.stop()


def recs(n, tag=b"v"):
    return [(b"k%d" % i, tag + b"%d" % i) for i in range(n)]


def test_metadata_names_the_sixteen_partitions(broker):
    c = KafkaClient(broker.port)
    out = c.call(3, 1, struct.pack("!ih", 1, 8) + b"cdc.tpcc")
    assert out.count(struct.pack("!h", 8) + b"cdc.tpcc") == 1
    # 16 partition entries, each error 0, id, leader 0
    tail = out[out.index(b"cdc.tpcc") + 9:]
    assert struct.unpack_from("!i", tail)[0] == 16
    unknown = c.call(3, 1, struct.pack("!ih", 1, 5) + b"other")
    assert struct.pack("!h", 3) + struct.pack("!h", 5) + b"other" in unknown


def test_a_batch_is_checked_at_append(broker):
    c = KafkaClient(broker.port)
    good = h.record_batch(recs(5))
    assert c.produce(None, [(3, good)]) == [(3, 0)]
    torn = bytearray(good)
    torn[-1] ^= 1
    assert c.produce(None, [(3, bytes(torn))]) == [(3, 2)]
    assert c.produce(None, [(16, good)]) == [(16, 3)]
    assert c.produce(None, [(0, good)], topic=b"other") == [(0, 3)]
    batches, cost = broker.take()
    assert batches == [(3, good)] and cost["records"] == 5
    assert cost["refused_batches"] == 3 and len(broker.errors) == 1
    assert broker.take()[0] == []


def test_a_transaction_is_fenced_whole_and_superseded(broker):
    c = KafkaClient(broker.port)
    first = h.record_batch(recs(4), epoch=1)
    # no InitProducerId: the id is unknown
    assert c.produce("trtpu.p0", [(1, first)]) == [(1, 47)]
    err, pid, epoch = c.init("trtpu.p0", 1)
    assert (err, epoch) == (0, 1) and pid >= 1000
    torn = bytearray(first)
    torn[70] ^= 1
    # one bad batch refuses the transaction: nothing of it lands
    assert [e for _p, e in c.produce(
        "trtpu.p0", [(1, first), (2, bytes(torn))])] == [0, 2]
    assert broker.take()[0] == []
    c.init("trtpu.p0", 1)
    assert c.produce("trtpu.p0", [(1, first), (2, first)]) \
        == [(1, 0), (2, 0)]
    # the part again under a newer epoch: in place of the first publish
    assert c.init("trtpu.p0", 2)[0] == 0
    again = h.record_batch(recs(6, b"w"), epoch=2)
    assert c.produce("trtpu.p0", [(5, again)]) == [(5, 0)]
    # the zombie: its epoch is behind the id's
    assert c.init("trtpu.p0", 1) == (90, -1, 2)
    assert c.produce("trtpu.p0", [(1, first)]) == [(1, 47)]
    # another part is its own id
    c.init("trtpu.p1", 1)
    assert c.produce("trtpu.p1", [(1, first)]) == [(1, 0)]
    batches, cost = broker.take()
    assert sorted(batches) == sorted([(5, again), (1, first)])
    assert cost["superseded_publishes"] == 1 and cost["fenced"] == 1


def test_the_control_loses_one_acknowledged_record(broker):
    c = KafkaClient(broker.port)
    broker.drop_one_acked_record = True
    c.init("trtpu.p0", 1)
    blob = h.record_batch(recs(9), epoch=1)
    assert c.produce("trtpu.p0", [(4, blob), (5, blob)]) == [(4, 0), (5, 0)]
    batches, _cost = broker.take()
    counts = sorted(broker_produce.check_batch(b)[0] for _p, b in batches)
    assert counts == [8, 9] and broker.dropped == [(4, 8)]
    from benchmark import reference_tpcc

    kept = [k for _p, b in batches if len(b) < len(blob)
            for k, _v in reference_tpcc.iter_records(b)]
    assert kept == [b"k%d" % i for i in range(8)]


def test_another_api_is_refused_not_ignored(broker):
    c = KafkaClient(broker.port)
    assert c.call(1, 4, b"\x00" * 16) == struct.pack("!h", 35)
    assert broker.errors
