"""What the TPC-C tests share: a small database, and a plain writer of
Debezium envelopes and record batches that is neither the program's nor
the reference's."""

import json
import os
import struct

import google_crc32c

from benchmark import reference_tpcc, run, tpccgen

SMALL = {"customers_per_district": 40, "orders_per_district": 40,
         "new_orders_per_district": 12, "stock": 300, "items": 300}
MASKED = {"customer": ["c_first", "c_last", "c_street_1", "c_street_2",
                       "c_phone"]}
SALT = b"salt-1"


def small_spec():
    spec = tpccgen.load_columns(os.path.join(
        run.HERE, "configs", "tpcc-columns.json"))
    small = dict(SMALL)
    spec["items"] = small.pop("items")
    spec["per_warehouse"].update(small)
    return spec


def config():
    return run.load_json("configs", "tpcc-mysql2kafka-debezium.json")


def expected(db, spec, seed=1, one_in=4):
    return reference_tpcc.Expected(db, spec, config(), MASKED, SALT, seed,
                                   one_in)


def varint(n: int) -> bytes:
    z = (n << 1) ^ (n >> 63)
    out = bytearray()
    while True:
        if z >> 7:
            out.append((z & 0x7F) | 0x80)
            z >>= 7
        else:
            out.append(z)
            return bytes(out)


def record_batch(records, epoch=1) -> bytes:
    """[(key or None, value)] -> one record batch v2."""
    body = b""
    for i, (k, v) in enumerate(records):
        rec = b"\x00" + varint(0) + varint(i)
        rec += varint(-1) if k is None else varint(len(k)) + k
        rec += varint(len(v)) + v + varint(0)
        body += varint(len(rec)) + rec
    tail = struct.pack("!hiqqqhii", 0x10, len(records) - 1, 0, 0, 7, epoch,
                       -1, len(records)) + body
    return struct.pack("!qiib", 0, 9 + len(tail), 0, 2) \
        + struct.pack("!I", google_crc32c.value(tail)) + tail


def field(c, masked):
    typ, name = ("string", None) if c["name"] in masked \
        else reference_tpcc.field_type(c)
    out = {"type": typ, "optional": True, "field": c["name"]}
    if name:
        out.update(name=name, version=1)
    return out


def envelope(exp, table, i):
    """(key or None, value) of row i as the configuration's file says an
    envelope is, written with json.dumps and nothing else."""
    t = exp.db[table]
    masked = MASKED.get(table, [])
    row = exp.row(table, i)
    fields = [field(c, masked) for c in t["columns"]]
    value_schema = {"type": "struct", "fields": [
        {"type": "struct", "field": "before", "fields": fields},
        {"type": "struct", "field": "after", "fields": fields},
        {"type": "struct", "field": "source", "fields": []},
        {"type": "string", "field": "op"},
        {"type": "int64", "field": "ts_ms"}]}
    value = {"schema": value_schema, "payload": {
        "before": None, "after": row,
        "source": {"connector": "mysql", "db": exp.database,
                   "table": table, "snapshot": "true"},
        "op": "r", "ts_ms": 5}}
    key = None
    if t["key"]:
        key = json.dumps({"schema": {"type": "struct", "fields": [
            field(c, masked) for c in t["columns"]
            if c["name"] in t["key"]]},
            "payload": {k: row[k] for k in t["key"]}}).encode()
    return key, json.dumps(value, separators=(",", ":")).encode()


def whole_pass(exp, n_partitions=16):
    """{partition: [(key, value)]} of every row, keyed rows by a hash of
    the key, the rest in turn."""
    out = {p: [] for p in range(n_partitions)}
    turn = 0
    for table, t in exp.db.items():
        for i in range(t["rows"]):
            k, v = envelope(exp, table, i)
            if k is None:
                p, turn = turn % n_partitions, turn + 1
            else:
                p = google_crc32c.value(k) % n_partitions
            out[p].append((k, v))
    return out


def digest(exp, spec, by_partition):
    d = reference_tpcc.PassDigest(spec, exp.seed, exp.one_in)
    for p, records in by_partition.items():
        if records:
            d.add(p, record_batch(records))
    return d
