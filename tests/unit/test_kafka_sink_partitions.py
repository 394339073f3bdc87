"""KafkaSinker on a topic of 16 partitions: a key lands in the same
partition on every publish, null keys are dealt round the partitions, a
republish of a part supersedes the first, and a snapshot sink marks its
envelopes as snapshot reads."""

import json

import pyarrow as pa
import pytest

from tests.recipes.fake_kafka import FakeKafka
from transferia_tpu.abstract.schema import ColSchema, TableID, TableSchema
from transferia_tpu.columnar.batch import ColumnBatch
from transferia_tpu.providers.kafka.provider import (
    KafkaSinker,
    KafkaTargetParams,
)
from transferia_tpu.providers.mysql.provider import MySQLSourceParams
from transferia_tpu.stats import trace
from transferia_tpu.typesystem.rules import map_source_type

TOPIC = "cdc.tpcc"


@pytest.fixture
def broker():
    b = FakeKafka(n_partitions=16).start()
    b.create_topic(TOPIC, 16)
    yield b
    b.stop()


def batch(n, keyed=True, start=0):
    cols = [ColSchema(name="w", data_type=map_source_type("mysql", "int"),
                      primary_key=keyed, required=True,
                      original_type="mysql:smallint"),
            ColSchema(name="id", data_type=map_source_type("mysql", "int"),
                      primary_key=keyed, required=True,
                      original_type="mysql:int"),
            ColSchema(name="v", data_type=map_source_type("mysql",
                                                          "varchar"),
                      original_type="mysql:varchar(8)")]
    ids = list(range(start, start + n))
    rb = pa.record_batch({"w": pa.array([1] * n, pa.int32()),
                          "id": pa.array(ids, pa.int32()),
                          "v": pa.array([f"v{i}" for i in ids])})
    return ColumnBatch.from_arrow(rb, TableID("tpcc", "t"),
                                  TableSchema(cols))


def sinker(broker, snapshot=True, source=None):
    return KafkaSinker(KafkaTargetParams(
        brokers=[f"127.0.0.1:{broker.port}"], topic=TOPIC,
        serializer="debezium",
        serializer_config={"include_schema": False}), snapshot=snapshot,
        source=source)


def landed(broker):
    """{key bytes or None: [partitions]} and the values, of the topic."""
    where, values = {}, []
    for p in range(16):
        for r in broker.records(TOPIC, p):
            where.setdefault(r.key, []).append(p)
            values.append(r.value)
    return where, values


def publish(s, key, epoch, batches):
    s.begin_part(key, epoch)
    for b in batches:
        s.push(b)
    return s.publish_part(key, epoch)


def test_a_key_lands_in_the_same_partition_on_every_publish(broker):
    s = sinker(broker)
    try:
        assert publish(s, "op/tpcc.t/0", 1, [batch(400)]) == 400
        first, _ = landed(broker)
        # the same part again, under a newer epoch, in two pushes
        assert publish(s, "op/tpcc.t/0", 2,
                       [batch(150), batch(250, start=150)]) == 400
    finally:
        s.close()
    again, values = landed(broker)
    # superseded, not appended: every key once, where it was
    assert len(values) == 400
    assert again == first and all(len(p) == 1 for p in again.values())
    assert len({p[0] for p in again.values()}) == 16
    assert json.loads(values[0])["op"] == "r"


def test_a_second_part_does_not_replace_the_first(broker):
    s = sinker(broker)
    try:
        publish(s, "op/tpcc.t/0", 1, [batch(100)])
        publish(s, "op/tpcc.t/1", 1, [batch(100, start=100)])
    finally:
        s.close()
    where, values = landed(broker)
    assert len(values) == 200 and len(where) == 200


def test_null_keys_go_round_the_partitions(broker):
    s = sinker(broker)
    try:
        publish(s, "op/tpcc.h/0", 1,
                [batch(40, keyed=False), batch(40, keyed=False, start=40)])
    finally:
        s.close()
    where, values = landed(broker)
    assert list(where) == [None] and len(values) == 80
    assert sorted(where[None]) == sorted(list(range(16)) * 5)


def test_a_replication_sink_still_says_create(broker):
    s = sinker(broker, snapshot=False)
    try:
        s.push(batch(3))
    finally:
        s.close()
    _where, values = landed(broker)
    assert {json.loads(v)["op"] for v in values} == {"c"}


@pytest.mark.parametrize("source, want", [
    (MySQLSourceParams(database="tpcc"), ("mysql", "tpcc")),
    (None, ("transferia-tpu", "postgresql")),
])
def test_the_source_block_names_the_transfers_source(broker, source, want):
    s = sinker(broker, source=source)
    try:
        s.push(batch(2))
    finally:
        s.close()
    _where, values = landed(broker)
    assert {(json.loads(v)["source"]["connector"],
             json.loads(v)["source"]["db"]) for v in values} == {want}


def test_produce_is_recorded_as_the_sinks_write(broker):
    s = sinker(broker)
    trace.enable(True)
    trace.reset()
    try:
        publish(s, "op/tpcc.t/0", 1, [batch(64)])
        spans = {s_[0]: s_[7] for s_ in trace.spans() if s_[6] >= 0}
    finally:
        trace.enable(False)
        trace.reset()
        s.close()
    assert spans["kafka_encode"]["records"] == 64
    push = spans["sink_push"]
    assert push["direction"] == "kafka_produce" and push["records"] == 64
    assert push["partitions"] == 16
    assert push["bytes"] == spans["kafka_encode"]["bytes"]
    assert spans["serialize"]["path"] == "native"
