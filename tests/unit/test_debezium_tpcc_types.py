"""The Debezium emitter on every column type of TPC-C's clause 1.3 as the
MySQL source reads it, and on a table without a primary key: the columnar
path's bytes equal the per-row path's, and say what the configuration's
file says of the handling modes."""

import json

import pyarrow as pa
import pytest

import transferia_tpu.providers.mysql.provider  # noqa: F401  (type rules)
from transferia_tpu.abstract.schema import ColSchema, TableID, TableSchema
from transferia_tpu.columnar.batch import ColumnBatch
from transferia_tpu.debezium.emitter import DebeziumEmitter
from transferia_tpu.stats import trace
from transferia_tpu.typesystem.rules import map_source_type

TS = 1_704_085_686_000_000       # 2024-01-01 05:08:06 UTC, microseconds
# mysql type -> (arrow array of three values, what `after` must hold,
#                Connect type, semantic name)
TYPES = {
    "tinyint": (pa.array([1, None, -3], pa.int8()), [1, None, -3],
                "int16", None),
    "smallint": (pa.array([10, 300, None], pa.int16()), [10, 300, None],
                 "int16", None),
    "int": (pa.array([3001, None, 100000], pa.int32()),
            [3001, None, 100000], "int32", None),
    "decimal(4,4)": (pa.array(["0.1234", "0.0000", None]),
                     ["0.1234", "0.0000", None], "string", None),
    "decimal(12,2)": (pa.array(["-10.00", "300000.00", None]),
                      ["-10.00", "300000.00", None], "string", None),
    "decimal(8,0)": (pa.array(["0", "12345678", None]),
                     ["0", "12345678", None], "string", None),
    "datetime": (pa.array([TS, None, TS + 999_999], pa.timestamp("us")),
                 [TS // 1000, None, TS // 1000 + 999], "int64",
                 "io.debezium.time.Timestamp"),
    "datetime(6)": (pa.array([TS, None, TS + 1], pa.timestamp("us")),
                    [TS, None, TS + 1], "int64",
                    "io.debezium.time.MicroTimestamp"),
    "char(2)": (pa.array(["OE", "", None]), ["OE", "", None], "string",
                None),
    "char(24)": (pa.array(["x" * 24, 'q"\\', None]),
                 ["x" * 24, 'q"\\', None], "string", None),
    "varchar(500)": (pa.array(["é" * 300, "ORIGINAL", None]),
                     ["é" * 300, "ORIGINAL", None], "string", None),
}


def batch_of(mysql_type, values, keyed=True):
    cols = [ColSchema(name="id", data_type=map_source_type("mysql", "int"),
                      primary_key=keyed, required=True,
                      original_type="mysql:int"),
            ColSchema(name="v", data_type=map_source_type(
                "mysql", mysql_type.split("(")[0]),
                original_type=f"mysql:{mysql_type}")]
    schema = TableSchema(cols)
    rb = pa.record_batch({"id": pa.array([1, 2, 3], pa.int32()),
                          "v": values})
    return ColumnBatch.from_arrow(rb, TableID("tpcc", "t"), schema)


def both_paths(batch, **cfg):
    # connector and db as the Kafka sink's factory fills them in for a
    # MySQL source
    e = DebeziumEmitter(topic_prefix="tpcc", connector="mysql",
                        source_db_type="tpcc", **cfg)
    fast = e._emit_columnar_fast(batch, snapshot=True)
    assert fast is not None
    slow = [p for it in batch.to_rows() for p in e.emit_item(it, True)]
    return fast, slow


def same_but_clock(fast, slow):
    """Byte for byte, the envelope's wall-clock stamps aside."""
    import re

    stamp = re.compile(rb'"ts_ms":\d+')
    assert [k for k, _ in fast] == [k for k, _ in slow]
    assert [stamp.sub(b"", v) for _, v in fast] == \
        [stamp.sub(b"", v) for _, v in slow]


@pytest.mark.parametrize("include_schema", [True, False])
@pytest.mark.parametrize("mysql_type", sorted(TYPES))
def test_fast_path_equals_row_path(mysql_type, include_schema):
    values, want, ctype, semantic = TYPES[mysql_type]
    fast, slow = both_paths(batch_of(mysql_type, values),
                            include_schema=include_schema)
    same_but_clock(fast, slow)
    docs = [json.loads(v) for _, v in fast]
    if include_schema:
        after = next(f for f in docs[0]["schema"]["fields"]
                     if f["field"] == "after")
        field = next(f for f in after["fields"] if f["field"] == "v")
        assert (field["type"], field.get("name")) == (ctype, semantic)
        assert field["optional"] is True
        docs = [d["payload"] for d in docs]
    assert [d["after"]["v"] for d in docs] == want
    assert all(d["op"] == "r" and d["before"] is None for d in docs)
    src = docs[0]["source"]
    assert (src["connector"], src["db"], src["table"],
            src["snapshot"]) == ("mysql", "tpcc", "t", "true")


@pytest.mark.parametrize("include_schema", [True, False])
def test_a_table_without_a_key_has_null_keys_on_both_paths(include_schema):
    batch = batch_of("varchar(500)", TYPES["varchar(500)"][0], keyed=False)
    fast, slow = both_paths(batch, include_schema=include_schema)
    same_but_clock(fast, slow)
    assert [k for k, _ in fast] == [None, None, None]


def test_an_explicit_source_type_keeps_its_say():
    batch = batch_of("int", TYPES["int"][0])
    e = DebeziumEmitter(source_db_type="mysql", connector="c1",
                        include_schema=False)
    src = json.loads(e.emit_batch(batch)[0][1])["source"]
    assert (src["connector"], src["db"]) == ("c1", "mysql")


def test_an_emitter_told_nothing_keeps_the_old_identity():
    batch = batch_of("int", TYPES["int"][0])
    src = json.loads(DebeziumEmitter(include_schema=False).emit_batch(
        batch)[0][1])["source"]
    assert (src["connector"], src["db"]) == ("transferia-tpu",
                                             "postgresql")


@pytest.mark.parametrize("path", ["native", "row"])
def test_serialize_span_and_counters_say_which_path(path):
    # (a batch of columns is the native renderer's since PR 34, and counts
    # as a columnar - "fast" - row too; the Python renderer's own span
    # and counters: test_debezium_native_render.py)
    batch = batch_of("int", TYPES["int"][0])
    items = batch if path == "native" else batch.to_rows()
    before = trace.TELEMETRY.snapshot()
    trace.enable(True)
    trace.reset()
    try:
        DebeziumEmitter().emit_batch(items, snapshot=True)
        spans = [s for s in trace.spans() if s[0] == "serialize"]
    finally:
        trace.enable(False)
        trace.reset()
    after = trace.TELEMETRY.snapshot()
    assert [s[7] for s in spans] == [
        {"format": "debezium", "path": path, "rows": 3}]
    assert after["debezium_rows"] - before["debezium_rows"] == 3
    assert after["debezium_rows_fast"] - before["debezium_rows_fast"] \
        == (3 if path == "native" else 0)
