"""Parser framework + plugins (cf. reference tests/canon/parser)."""

import json

import pytest

from transferia_tpu.abstract.schema import CanonicalType, TableID
from transferia_tpu.parsers import (
    Message,
    UNPARSED_TABLE,
    make_parser,
    registered_parsers,
)


def msg(value, topic="t1", partition=0, offset=0, key=b""):
    if isinstance(value, str):
        value = value.encode()
    return Message(value=value, key=key, topic=topic, partition=partition,
                   offset=offset, write_time_ns=1_700_000_000_000_000_000)


def test_registry_lists_builtins():
    names = registered_parsers()
    for expected in ("json", "generic", "tskv", "blank", "raw_to_table",
                     "debezium", "cloudevents", "native", "audittrailsv1",
                     "cloudlogging", "protobuf", "confluent_schema_registry"):
        assert expected in names, expected


class TestGenericJson:
    def make(self, **kw):
        return make_parser({"json": {
            "schema": [
                {"name": "id", "type": "int64", "key": True},
                {"name": "name", "type": "utf8"},
                {"name": "value", "type": "double"},
            ],
            "table": "events",
            **kw,
        }})

    def test_parses_batch_columnar(self):
        p = self.make()
        msgs = [msg(json.dumps({"id": i, "name": f"n{i}", "value": i * 0.5}))
                for i in range(10)]
        res = p.do_batch(msgs)
        assert res.unparsed is None
        assert len(res.batches) == 1
        b = res.batches[0]
        assert b.n_rows == 10
        assert b.to_pydict()["id"] == list(range(10))
        # system cols present and keyed (user key declared -> system not key)
        assert "_offset" in b.columns
        assert b.schema.find("id").primary_key

    def test_multiline_messages(self):
        p = self.make()
        payload = "\n".join(
            json.dumps({"id": i, "name": "x", "value": 1.0})
            for i in range(3)
        )
        res = p.do_batch([msg(payload)])
        assert res.batches[0].n_rows == 3
        assert res.batches[0].to_pydict()["_idx"] == [0, 1, 2]

    def test_bad_rows_to_unparsed(self):
        p = self.make()
        msgs = [
            msg('{"id": 1, "name": "a", "value": 1.0}'),
            msg('{broken json'),
            msg('{"id": 2, "name": "b", "value": 2.0}'),
            msg('[1,2,3]'),  # not an object
        ]
        res = p.do_batch(msgs)
        assert res.batches[0].n_rows == 2
        assert res.unparsed is not None
        assert res.unparsed.n_rows == 2
        assert res.unparsed.table_id == UNPARSED_TABLE
        reasons = res.unparsed.to_pydict()["reason"]
        assert all("invalid JSON" in r for r in reasons)

    def test_null_key_rejected(self):
        p = self.make()
        res = p.do_batch([msg('{"id": null, "name": "a", "value": 1.0}')])
        assert not res.batches
        assert res.unparsed.n_rows == 1
        assert "null value in key" in res.unparsed.to_pydict()["reason"][0]

    def test_coercion_from_strings(self):
        p = self.make()
        res = p.do_batch([msg('{"id": "5", "name": "a", "value": "2.5"}')])
        assert res.batches[0].to_pydict()["id"] == [5]
        assert res.batches[0].to_pydict()["value"] == [2.5]

    def test_schema_inference(self):
        p = make_parser({"json": {"table": "inferred"}})
        res = p.do_batch([msg('{"a": 1, "b": "x", "c": true}')])
        b = res.batches[0]
        assert b.schema.find("a").data_type == CanonicalType.INT64
        assert b.schema.find("b").data_type == CanonicalType.UTF8
        assert b.schema.find("c").data_type == CanonicalType.BOOLEAN

    def test_nested_path(self):
        p = make_parser({"json": {
            "schema": [{"name": "uid", "type": "int64", "path": "user.id"}],
            "table": "t",
        }})
        res = p.do_batch([msg('{"user": {"id": 42}}')])
        assert res.batches[0].to_pydict()["uid"] == [42]


def test_tskv_parser():
    p = make_parser({"tskv": {
        "schema": [{"name": "a", "type": "int64"},
                   {"name": "b", "type": "utf8"}],
        "table": "logs",
    }})
    res = p.do_batch([msg("tskv\ta=1\tb=hello"), msg("a=2\tb=wor\\tld")])
    d = res.batches[0].to_pydict()
    assert d["a"] == [1, 2]
    assert d["b"] == ["hello", "wor\tld"]


def test_blank_parser_mirror_schema():
    p = make_parser({"blank": {}})
    res = p.do_batch([msg(b"\x00\x01raw", topic="tp", partition=3,
                          offset=42, key=b"k")])
    b = res.batches[0]
    assert b.table_id == TableID("", "tp")
    d = b.to_pydict()
    assert d["data"] == [b"\x00\x01raw"]
    assert d["partition"] == [3] and d["offset"] == [42]


def test_cloudevents_parser():
    p = make_parser({"cloudevents": {}})
    ok = {"specversion": "1.0", "id": "e1", "source": "/svc",
          "type": "demo", "data": {"x": 1}}
    res = p.do_batch([msg(json.dumps(ok)), msg('{"no": "id"}')])
    assert res.batches[0].to_pydict()["id"] == ["e1"]
    assert res.unparsed.n_rows == 1


def test_confluent_sr_parser():
    p = make_parser({"confluent_schema_registry": {"table": "t"}})
    payload = b"\x00\x00\x00\x00\x07" + b'{"a": 1}'
    res = p.do_batch([msg(payload), msg(b"\x01nope")])
    assert res.batches[0].to_pydict()["a"] == [1]
    assert res.unparsed.n_rows == 1


def test_confluent_sr_avro_native_matches_python():
    """The C flat-record avro decoder (hostops.cpp avro_decode_flat) must
    produce byte-identical batches to the per-row AvroSchema reader —
    nulls, unicode, negative varints, floats, bytes."""
    import json as _json

    import pytest

    from transferia_tpu.native import lib as native_lib
    from transferia_tpu.parsers.plugins import ConfluentSRParser
    from transferia_tpu.schemaregistry.avro import AvroSchema

    if native_lib() is None or not hasattr(native_lib(),
                                           "avro_decode_flat"):
        pytest.skip("native lib unavailable")
    schema_json = _json.dumps({
        "type": "record", "name": "R", "fields": [
            {"name": "id", "type": "long"},
            {"name": "small", "type": "int"},
            {"name": "name", "type": ["null", "string"]},
            {"name": "blob", "type": ["string", "null"]},
            {"name": "score", "type": "double"},
            {"name": "ratio", "type": ["null", "float"]},
            {"name": "ok", "type": "boolean"},
            {"name": "raw", "type": ["null", "bytes"]},
        ]})
    avro = AvroSchema(schema_json)

    def zz(n):
        u = (n << 1) ^ (n >> 63)
        out = bytearray()
        while True:
            b = u & 0x7F
            u >>= 7
            out.append(b | (0x80 if u else 0))
            if not u:
                return bytes(out)

    import struct as _struct

    def enc(i):
        body = zz(i * 977 - 500_000) + zz(i % 1000 - 500)
        if i % 7 == 0:
            body += zz(0)  # name: null branch (index 0)
        else:
            s = f"котик-{i}\"x".encode()
            body += zz(1) + zz(len(s)) + s
        if i % 5 == 0:
            body += zz(1)  # blob: null branch is index 1 here
        else:
            s = f"b{i}".encode()
            body += zz(0) + zz(len(s)) + s
        body += _struct.pack("<d", i * 0.25)
        if i % 3 == 0:
            body += zz(0)
        else:
            body += zz(1) + _struct.pack("<f", i * 0.5)
        body += b"\x01" if i % 2 else b"\x00"
        if i % 11 == 0:
            body += zz(0)
        else:
            body += zz(1) + zz(3) + bytes([i % 256, 0, 255])
        return body

    msgs = [Message(value=enc(i), key=b"", topic="t", partition=0,
                    offset=i, write_time_ns=0) for i in range(500)]
    p = ConfluentSRParser(table="t")
    fast = p._avro_batch_native(avro, msgs)
    assert fast is not None, "fast path refused an in-envelope schema"
    # exact per-row comparison: decode with AvroSchema directly
    fb = fast.batches[0]
    for i in (0, 3, 5, 7, 11, 21, 33, 35, 499):
        want = avro.decode(msgs[i].value)
        got = {n: fb.column(n).to_pylist()[i] for n in want}
        assert got == want, (i, got, want)
    assert fb.n_rows == 500


def _epoch_messages(values, n=300):
    import json as _json

    return [Message(
        value=_json.dumps({"id": i, "name": f"n{i}",
                           "at": values[i % len(values)]}).encode(),
        topic="t", partition=0, offset=i, write_time_ns=1_000 + i)
        for i in range(n)]


@pytest.mark.parametrize("values,columnar", [
    ([1_790_000_000_000_000, 0, -5], True),      # JSON integers
    ([1_790_000_000_000_000, None], True),       # nulls stay nulls
    ([1.5e9, 7], False),                         # a float: general path
    (["1790000000", 7], False),                  # a digit string
    (["2026-10-03T00:00:00Z", 7], False),        # not a number: null
], ids=["ints", "nulls", "float", "digits", "iso"])
@pytest.mark.parametrize("kind", ["timestamp", "datetime"])
def test_epoch_columns_parse_columnar_as_the_general_path_does(
        kind, values, columnar, monkeypatch):
    """A batch whose DATETIME / TIMESTAMP values are JSON integers takes
    the whole-batch arrow path and reads what the per-row path reads;
    any other value leaves it to the per-row path."""
    from transferia_tpu.parsers.generic import GenericJsonParser, _Lines

    cfg = {"json": {"schema": [
        {"name": "id", "type": "int64", "key": True},
        {"name": "name", "type": "utf8"},
        {"name": "at", "type": kind},
    ], "table": "t"}}
    msgs = _epoch_messages(values)
    parser = make_parser(cfg)
    fast = parser._fast_columnar(msgs, _Lines(msgs))
    assert (fast is not None) == columnar
    got = parser.do_batch(msgs)
    monkeypatch.setattr(GenericJsonParser, "_fast_columnar",
                        lambda self, messages, lines: None)
    want = make_parser(cfg).do_batch(msgs)
    assert got.unparsed is None and want.unparsed is None
    a, b = got.batches[0], want.batches[0]
    assert a.schema == b.schema and list(a.columns) == list(b.columns)
    for name in b.columns:
        assert a.column(name).to_pylist() == b.column(name).to_pylist(), \
            name
        assert a.column(name).data.dtype == b.column(name).data.dtype, name
