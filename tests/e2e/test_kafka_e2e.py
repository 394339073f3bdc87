"""Kafka provider e2e over real sockets against the fake broker
(cf. reference kafka2ch suites)."""

import json
import threading
import time

import pytest

from transferia_tpu.abstract import TableID
from transferia_tpu.coordinator import MemoryCoordinator
from transferia_tpu.models import Transfer, TransferType
from transferia_tpu.providers.kafka import (
    KafkaSourceParams,
    KafkaTargetParams,
)
from transferia_tpu.providers.kafka.client import KafkaClient
from transferia_tpu.providers.kafka.protocol import (
    Record,
    decode_record_batches,
    encode_record_batch,
)
from transferia_tpu.providers.memory import MemoryTargetParams, get_store
from transferia_tpu.runtime import run_replication
from tests.recipes.fake_kafka import FakeKafka


def test_record_batch_roundtrip():
    records = [
        Record(key=b"k1", value=b"v1", timestamp_ms=1000),
        Record(key=None, value=b"v2", timestamp_ms=1005,
               headers=[(b"h", b"x")]),
        Record(key=b"k3", value=None, timestamp_ms=1010),
    ]
    blob = encode_record_batch(records, base_offset=40)
    back = decode_record_batches(blob)
    assert [r.offset for r in back] == [40, 41, 42]
    assert back[0].key == b"k1" and back[0].value == b"v1"
    assert back[1].key is None and back[1].headers == [(b"h", b"x")]
    assert back[2].value is None
    assert [r.timestamp_ms for r in back] == [1000, 1005, 1010]


def test_crc_validation():
    blob = bytearray(encode_record_batch([Record(key=b"k", value=b"v")]))
    blob[-1] ^= 0xFF  # corrupt payload
    with pytest.raises(ValueError, match="CRC"):
        decode_record_batches(bytes(blob))


@pytest.fixture
def broker():
    srv = FakeKafka(n_partitions=2).start()
    yield srv
    srv.stop()


def test_client_produce_fetch(broker):
    client = KafkaClient([f"127.0.0.1:{broker.port}"])
    meta = client.metadata(["t1"])
    assert meta == {"t1": [0, 1]}
    base = client.produce("t1", 0, [Record(key=b"a", value=b"1"),
                                    Record(key=b"b", value=b"2")])
    assert base == 0
    base2 = client.produce("t1", 0, [Record(key=b"c", value=b"3")])
    assert base2 == 2
    records, high = client.fetch("t1", 0, 0)
    assert [r.value for r in records] == [b"1", b"2", b"3"]
    assert high == 3
    # fetch from mid-offset
    records, _ = client.fetch("t1", 0, 2)
    assert [r.value for r in records] == [b"3"]
    assert client.list_offsets("t1", 0, -1) == 3
    assert client.list_offsets("t1", 0, -2) == 0
    client.close()


def test_kafka_roundtrip_span_tells_the_wait_from_the_read(broker):
    from transferia_tpu.stats import trace

    client = KafkaClient([f"127.0.0.1:{broker.port}"])
    client.produce("t2", 0, [Record(key=b"a", value=b"x" * 500)])
    trace.reset()
    trace.enable(True)
    try:
        records, _high = client.fetch("t2", 0, 0)
        rec = [s for s in trace.spans() if s[0] == "kafka_roundtrip"]
    finally:
        trace.enable(False)
        trace.reset()
        client.close()
    assert len(records) == 1
    fetch = rec[-1]
    # up to the first response byte (the broker's long poll), and what
    # the response carried
    assert 0 <= fetch[7]["wait_s"] <= fetch[4]
    assert fetch[7]["bytes"] > 500
    assert all({"api", "wait_s", "bytes"} <= set(s[7]) for s in rec)


def test_kafka_replication_to_memory(broker):
    client = KafkaClient([f"127.0.0.1:{broker.port}"])
    for i in range(100):
        client.produce("events", i % 2, [Record(
            key=str(i).encode(),
            value=json.dumps({"id": i, "v": f"x{i}"}).encode(),
        )])
    client.close()
    store = get_store("ke2e")
    store.clear()
    cp = MemoryCoordinator()
    t = Transfer(
        id="ke2e", type=TransferType.INCREMENT_ONLY,
        src=KafkaSourceParams(
            brokers=[f"127.0.0.1:{broker.port}"], topic="events",
            parser={"json": {"schema": [
                {"name": "id", "type": "int64", "key": True},
                {"name": "v", "type": "utf8"},
            ], "table": "events"}},
        ),
        dst=MemoryTargetParams(sink_id="ke2e"),
    )
    stop = threading.Event()
    th = threading.Thread(
        target=run_replication, args=(t, cp),
        kwargs={"stop_event": stop, "backoff": 0.1}, daemon=True,
    )
    th.start()
    deadline = time.monotonic() + 20
    while store.row_count() < 100 and time.monotonic() < deadline:
        time.sleep(0.05)
    assert store.row_count() == 100
    ids = sorted(r.value("id") for r in store.rows(TableID("", "events")))
    assert ids == list(range(100))
    # offsets checkpointed in the coordinator
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline:
        state = cp.get_transfer_state("ke2e").get("kafka_offsets", {})
        if state.get("events:0") == 49 and state.get("events:1") == 49:
            break
        time.sleep(0.05)
    assert cp.get_transfer_state("ke2e")["kafka_offsets"] == {
        "events:0": 49, "events:1": 49,
    }
    stop.set()
    th.join(timeout=10)


def test_kafka_sink_produces(broker):
    from transferia_tpu.abstract.schema import new_table_schema
    from transferia_tpu.columnar import ColumnBatch
    from transferia_tpu.providers.kafka.provider import KafkaSinker

    schema = new_table_schema([("id", "int64", True), ("name", "utf8")])
    batch = ColumnBatch.from_pydict(TableID("s", "t"), schema, {
        "id": list(range(10)), "name": [f"n{i}" for i in range(10)],
    })
    sinker = KafkaSinker(KafkaTargetParams(
        brokers=[f"127.0.0.1:{broker.port}"], topic="out",
        serializer="json", partition_by="id",
    ))
    sinker.push(batch)
    sinker.close()
    assert broker.size("out") == 10
    vals = [json.loads(r.value) for p in (0, 1)
            for r in broker.records("out", p)]
    assert sorted(v["id"] for v in vals) == list(range(10))
    # partitioning by id is deterministic: same batch -> same spread
    p0 = {json.loads(r.value)["id"] for r in broker.records("out", 0)}
    sinker2 = KafkaSinker(KafkaTargetParams(
        brokers=[f"127.0.0.1:{broker.port}"], topic="out",
        serializer="json", partition_by="id",
    ))
    sinker2.push(batch)
    sinker2.close()
    p0_after = {json.loads(r.value)["id"]
                for r in broker.records("out", 0)}
    assert p0 == p0_after


@pytest.fixture(scope="module")
def tls_cert(tmp_path_factory):
    import subprocess

    d = tmp_path_factory.mktemp("kafka_tls")
    cert, key = str(d / "cert.pem"), str(d / "key.pem")
    subprocess.run(
        ["openssl", "req", "-x509", "-newkey", "rsa:2048", "-nodes",
         "-keyout", key, "-out", cert, "-days", "1", "-subj",
         "/CN=127.0.0.1", "-addext", "subjectAltName=IP:127.0.0.1"],
        check=True, capture_output=True,
    )
    return cert, key


def test_gzip_compression_roundtrip(broker):
    client = KafkaClient([f"127.0.0.1:{broker.port}"])
    records = [Record(key=b"k", value=(f"v{i}" * 20).encode())
               for i in range(50)]
    client.produce("gz", 0, records, compression="gzip")
    got, _hw = client.fetch("gz", 0, 0)
    assert len(got) == 50
    assert got[7].value == b"v7" * 20
    # the produced batch really is gzip-framed (codec attribute bits
    # set at offset 21: base_offset 8 + len 4 + epoch 4 + magic 1 + crc 4)
    import struct as _struct

    blob = encode_record_batch(records, compression="gzip")
    attrs = _struct.unpack_from("!h", blob, 21)[0]
    assert attrs & 0x07 == 1, "gzip codec bit not set on the wire"
    assert len(blob) < len(encode_record_batch(records))  # it compressed
    client.close()


def test_sasl_scram_tls_replication(tls_cert):
    cert, key = tls_cert
    srv = FakeKafka(sasl=("SCRAM-SHA-256", "etl", "s3cr3t"),
                    tls_cert=(cert, key)).start()
    try:
        store = get_store("ks1")
        store.clear()
        cp = MemoryCoordinator()
        t = Transfer(
            id="ks1", type=TransferType.INCREMENT_ONLY,
            src=KafkaSourceParams(
                brokers=[f"127.0.0.1:{srv.port}"], topic="ev",
                tls=True, tls_ca=cert,
                sasl_mechanism="SCRAM-SHA-256",
                sasl_username="etl", sasl_password="s3cr3t",
                parser={"json": {"schema": [
                    {"name": "id", "type": "int64", "key": True},
                ], "table": "ev"}},
            ),
            dst=MemoryTargetParams(sink_id="ks1"),
        )
        # seed through an authenticated TLS producer
        producer = KafkaClient(
            [f"127.0.0.1:{srv.port}"], tls=True, tls_ca=cert,
            sasl_mechanism="SCRAM-SHA-256", sasl_username="etl",
            sasl_password="s3cr3t",
        )
        srv.create_topic("ev")
        producer.produce("ev", 0, [
            Record(key=b"", value=json.dumps({"id": i}).encode())
            for i in range(10)
        ], compression="gzip")
        producer.close()

        stop = threading.Event()
        th = threading.Thread(
            target=run_replication, args=(t, cp),
            kwargs={"stop_event": stop, "backoff": 0.2}, daemon=True,
        )
        th.start()
        deadline = time.monotonic() + 20
        while store.row_count() < 10 and time.monotonic() < deadline:
            time.sleep(0.05)
        stop.set()
        th.join(timeout=10)
        ids = sorted(r.value("id") for r in store.rows(TableID("", "ev")))
        assert ids == list(range(10))
        assert srv.auth_attempts >= 2  # scram is two rounds per conn
    finally:
        srv.stop()


def test_sasl_plain_bad_credentials():
    srv = FakeKafka(sasl=("PLAIN", "etl", "right")).start()
    try:
        from transferia_tpu.providers.kafka.client import KafkaError

        client = KafkaClient(
            [f"127.0.0.1:{srv.port}"], sasl_mechanism="PLAIN",
            sasl_username="etl", sasl_password="wrong",
        )
        with pytest.raises(KafkaError, match="sasl"):
            client.metadata(["t"])
        client.close()
        # and correct creds succeed on the same broker
        ok = KafkaClient(
            [f"127.0.0.1:{srv.port}"], sasl_mechanism="PLAIN",
            sasl_username="etl", sasl_password="right",
        )
        assert "t2" in ok.metadata(["t2"])
        ok.close()
    finally:
        srv.stop()


def test_unauthenticated_client_rejected():
    srv = FakeKafka(sasl=("PLAIN", "etl", "pw")).start()
    try:
        from transferia_tpu.providers.kafka.client import KafkaError

        client = KafkaClient([f"127.0.0.1:{srv.port}"])
        with pytest.raises(KafkaError):
            client.metadata(["t"])
        client.close()
    finally:
        srv.stop()


def test_eventhub_source_over_kafka_surface(tls_cert):
    """Event Hubs rides its Kafka-compatible endpoint: TLS + SASL PLAIN
    with user $ConnectionString (reference pkg/providers/eventhub/)."""
    from transferia_tpu.providers.eventhub import EventHubSourceParams

    cert, key = tls_cert
    conn_str = ("Endpoint=sb://ns.servicebus.windows.net/;"
                "SharedAccessKeyName=read;SharedAccessKey=abc123")
    srv = FakeKafka(sasl=("PLAIN", "$ConnectionString", conn_str),
                    tls_cert=(cert, key)).start()
    try:
        store = get_store("eh1")
        store.clear()
        cp = MemoryCoordinator()
        src = EventHubSourceParams(
            namespace="127.0.0.1", hub="ev",
            connection_string=conn_str, port=srv.port,
            tls=True, tls_ca=cert,
            parser={"json": {"schema": [
                {"name": "id", "type": "int64", "key": True},
            ], "table": "ev"}},
        )
        # namespace with a dot is used verbatim as the broker host
        assert src.to_kafka_params().brokers == [f"127.0.0.1:{srv.port}"]
        t = Transfer(id="eh1", type=TransferType.INCREMENT_ONLY,
                     src=src, dst=MemoryTargetParams(sink_id="eh1"))
        seed = KafkaClient(
            [f"127.0.0.1:{srv.port}"], tls=True, tls_ca=cert,
            sasl_mechanism="PLAIN", sasl_username="$ConnectionString",
            sasl_password=conn_str,
        )
        srv.create_topic("ev")
        seed.produce("ev", 0, [
            Record(key=b"", value=json.dumps({"id": i}).encode())
            for i in range(8)
        ])
        seed.close()
        stop = threading.Event()
        th = threading.Thread(
            target=run_replication, args=(t, cp),
            kwargs={"stop_event": stop, "backoff": 0.2}, daemon=True,
        )
        th.start()
        deadline = time.monotonic() + 20
        while store.row_count() < 8 and time.monotonic() < deadline:
            time.sleep(0.05)
        stop.set()
        th.join(timeout=10)
        ids = sorted(r.value("id") for r in store.rows(TableID("", "ev")))
        assert ids == list(range(8))
    finally:
        srv.stop()


def test_partitioned_replication_kafka_to_files(broker, tmp_path):
    """queue -> object storage runs one pipeline per partition
    (partitioned_strategy.go parity): every partition's records land,
    offsets checkpoint per partition."""
    from transferia_tpu.providers.file import FileTargetParams
    from transferia_tpu.runtime.local import is_partitioned_replication

    d = str(tmp_path / "out")
    seed = KafkaClient([f"127.0.0.1:{broker.port}"])
    broker.create_topic("pt")  # fake default: 2 partitions
    for p in (0, 1):
        seed.produce("pt", p, [
            Record(key=b"", value=json.dumps(
                {"id": p * 100 + i}).encode())
            for i in range(10)
        ])
    seed.close()
    cp = MemoryCoordinator()
    t = Transfer(
        id="part1", type=TransferType.INCREMENT_ONLY,
        src=KafkaSourceParams(
            brokers=[f"127.0.0.1:{broker.port}"], topic="pt",
            parser={"json": {"schema": [
                {"name": "id", "type": "int64", "key": True},
            ], "table": "pt"}},
        ),
        dst=FileTargetParams(path=d, format="jsonl"),
    )
    assert is_partitioned_replication(t)
    stop = threading.Event()
    th = threading.Thread(
        target=run_replication, args=(t, cp),
        kwargs={"stop_event": stop, "backoff": 0.2}, daemon=True,
    )
    th.start()

    import glob
    import os

    def rows_on_disk():
        out = []
        for f in glob.glob(os.path.join(d, "**", "*.jsonl"),
                           recursive=True):
            with open(f) as fh:
                out.extend(json.loads(ln) for ln in fh if ln.strip())
        return out

    deadline = time.monotonic() + 25
    while len(rows_on_disk()) < 20 and time.monotonic() < deadline:
        time.sleep(0.1)
    stop.set()
    th.join(timeout=10)
    ids = sorted(r["id"] for r in rows_on_disk())
    assert ids == sorted([p * 100 + i for p in (0, 1)
                          for i in range(10)])
    # both partitions checkpointed independently
    state = cp.get_transfer_state("part1")["kafka_offsets"]
    assert state.get("pt:0") == 9 and state.get("pt:1") == 9


def test_16_partition_fanin_with_transform_chain_to_ch():
    """BASELINE kafka2ch realism: 16-partition fan-in through the json
    parser + mask+filter transformer chain into the ClickHouse sink,
    exactly-once per offset, with a p99 push-latency readout."""
    from tests.recipes.fake_clickhouse import FakeCH
    from transferia_tpu.providers.clickhouse import CHTargetParams

    srv = FakeKafka(n_partitions=16).start()
    ch = FakeCH().start()
    try:
        seed = KafkaClient([f"127.0.0.1:{srv.port}"])
        srv.create_topic("hits")
        for p in range(16):
            seed.produce("hits", p, [
                Record(key=b"", value=json.dumps({
                    "id": p * 1000 + i, "url": f"https://x/{i}",
                    "region": i % 500,
                }).encode())
                for i in range(40)
            ])
        seed.close()
        cp = MemoryCoordinator()
        t = Transfer(
            id="fan16", type=TransferType.INCREMENT_ONLY,
            src=KafkaSourceParams(
                brokers=[f"127.0.0.1:{srv.port}"], topic="hits",
                parallelism=4,
                parser={"json": {"schema": [
                    {"name": "id", "type": "int64", "key": True},
                    {"name": "url", "type": "utf8"},
                    {"name": "region", "type": "int32"},
                ], "table": "hits"}},
            ),
            dst=CHTargetParams(host="127.0.0.1", port=ch.port,
                               bufferer=None),
            transformation={"transformers": [
                {"mask_field": {"columns": ["url"], "salt": "s"}},
                {"filter_rows": {"filter": "region < 20"}},
            ]},
        )
        stop = threading.Event()
        th = threading.Thread(
            target=run_replication, args=(t, cp),
            kwargs={"stop_event": stop, "backoff": 0.2}, daemon=True,
        )
        t0 = time.monotonic()
        th.start()
        expected = sum(1 for p in range(16) for i in range(40)
                       if i % 500 < 20)
        assert expected < 16 * 40  # the filter genuinely drops rows

        def ch_rows():
            return sum(len(tb["rows"]) for tb in ch.tables.values())

        deadline = time.monotonic() + 40
        while ch_rows() < expected and time.monotonic() < deadline:
            time.sleep(0.05)
        elapsed = time.monotonic() - t0
        # commits trail the pushes; wait for all 16 partitions to settle
        while time.monotonic() < deadline:
            state = cp.get_transfer_state("fan16").get("kafka_offsets", {})
            if len(state) == 16 and all(v == 39 for v in state.values()):
                break
            time.sleep(0.05)
        stop.set()
        th.join(timeout=10)
        assert ch_rows() == expected, (ch_rows(), expected)
        # masked urls are 64-hex everywhere (rows are dicts in the fake)
        for tb in ch.tables.values():
            for row in tb["rows"][:5]:
                assert len(row["url"]) == 64
        # offsets committed for all 16 partitions
        state = cp.get_transfer_state("fan16")["kafka_offsets"]
        assert len(state) == 16
        assert all(v == 39 for v in state.values())
        print(f"# fan-in 16p end-to-end latency: {elapsed:.2f}s "
              f"for {expected} rows")
    finally:
        srv.stop()
        ch.stop()


# -- the parsequeue pushes ahead of its acks ----------------------------------

_BACKLOG = 5 * 1024 + 100    # six fetched batches: `fetch` keeps 1,024


def _backlog_transfer(transfer_id, srv, ch):
    from transferia_tpu.providers.clickhouse import CHTargetParams

    seed = KafkaClient([f"127.0.0.1:{srv.port}"])
    srv.create_topic("backlog")
    for base in range(0, _BACKLOG, 256):
        seed.produce("backlog", 0, [
            Record(key=b"", value=json.dumps(
                {"id": i, "v": f"x{i}"}).encode())
            for i in range(base, min(base + 256, _BACKLOG))])
    seed.close()
    return Transfer(
        id=transfer_id, type=TransferType.INCREMENT_ONLY,
        src=KafkaSourceParams(
            brokers=[f"127.0.0.1:{srv.port}"], topic="backlog",
            parser={"json": {"schema": [
                {"name": "id", "type": "int64", "key": True},
                {"name": "v", "type": "utf8"},
            ], "table": "backlog"}},
        ),
        # the default bufferer: 100,000 rows / 1.0 s
        dst=CHTargetParams(host="127.0.0.1", port=ch.port),
    )


def _landed_ids(ch):
    return [row["id"] for name, tb in ch.tables.items()
            if not name.startswith("__trtpu") for row in tb["rows"]]


def _committed(cp, transfer_id):
    return cp.get_transfer_state(transfer_id).get(
        "kafka_offsets", {}).get("backlog:0")


def _until(cond, seconds):
    deadline = time.monotonic() + seconds
    while not cond() and time.monotonic() < deadline:
        time.sleep(0.05)
    return cond()


def test_a_backlog_lands_in_fewer_inserts_than_fetched_batches():
    """Six fetched batches wait in the broker; the push stage hands them
    all to the bufferer before its 1 s tick, so they land in fewer inserts
    than batches, every id once, and the last offset is committed."""
    from tests.recipes.fake_clickhouse import FakeCH
    from transferia_tpu.stats import trace

    srv = FakeKafka(n_partitions=1).start()
    ch = FakeCH().start()
    stop = threading.Event()
    th = None
    try:
        cp = MemoryCoordinator()
        t = _backlog_transfer("pushahead", srv, ch)
        before = trace.TELEMETRY.snapshot()
        trace.reset()
        trace.enable(True)
        th = threading.Thread(
            target=run_replication, args=(t, cp),
            kwargs={"stop_event": stop, "backoff": 0.2}, daemon=True)
        th.start()
        assert _until(lambda: _committed(cp, "pushahead") == _BACKLOG - 1,
                      40)
        stop.set()
        th.join(timeout=10)
        flushes = [s[7] for s in trace.spans() if s[0] == "bufferer_flush"]
        after = trace.TELEMETRY.snapshot()
    finally:
        stop.set()
        trace.enable(False)
        trace.reset()
        srv.stop()
        ch.stop()
    assert sorted(_landed_ids(ch)) == list(range(_BACKLOG))
    pushes = after["parsequeue_pushes"] - before["parsequeue_pushes"]
    assert pushes == 6
    assert sum(f["units"] for f in flushes) == pushes
    assert sum(f["rows"] for f in flushes) == _BACKLOG
    assert len(flushes) < pushes and max(f["units"] for f in flushes) > 1
    assert all(f["trigger"] in ("interval", "close") for f in flushes)
    assert after["parsequeue_pushes_ahead"] \
        > before["parsequeue_pushes_ahead"]


def test_a_large_response_is_fetched_once_and_acked_in_units():
    """One response carries the whole backlog (a producer batch of several
    `max_messages`): the source decodes it once and hands it out 1,024 at
    a time, so the broker sees one Fetch with rows where six units are
    pushed and acked; every id lands and the last offset is committed."""
    from transferia_tpu.stats import trace

    srv = FakeKafka(n_partitions=1).start()
    seed = KafkaClient([f"127.0.0.1:{srv.port}"])
    seed.produce("once", 0, [
        Record(key=b"", value=json.dumps({"id": i, "v": f"x{i}"}).encode())
        for i in range(_BACKLOG)])
    seed.close()
    store = get_store("fetch-once")
    store.clear()
    cp = MemoryCoordinator()
    t = Transfer(
        id="fetch-once", type=TransferType.INCREMENT_ONLY,
        src=KafkaSourceParams(
            brokers=[f"127.0.0.1:{srv.port}"], topic="once",
            parser={"json": {"schema": [
                {"name": "id", "type": "int64", "key": True},
                {"name": "v", "type": "utf8"},
            ], "table": "once"}},
        ),
        dst=MemoryTargetParams(sink_id="fetch-once"),
    )
    stop = threading.Event()
    before = trace.TELEMETRY.snapshot()
    th = threading.Thread(
        target=run_replication, args=(t, cp),
        kwargs={"stop_event": stop, "backoff": 0.2}, daemon=True)
    th.start()
    try:
        assert _until(lambda: cp.get_transfer_state("fetch-once").get(
            "kafka_offsets", {}).get("once:0") == _BACKLOG - 1, 40)
    finally:
        stop.set()
        th.join(timeout=10)
        srv.stop()
    assert not th.is_alive()
    after = trace.TELEMETRY.snapshot()
    ids = sorted(r.value("id") for r in store.rows(TableID("", "once")))
    assert sorted(set(ids)) == list(range(_BACKLOG))
    units = after["parsequeue_pushes"] - before["parsequeue_pushes"]
    assert units == 6
    assert after["kafka_handouts"] - before["kafka_handouts"] == units
    assert after["kafka_handouts_buffered"] \
        - before["kafka_handouts_buffered"] == units - 1
    assert srv.fetches_with_rows == 1


def test_a_worker_stopped_between_a_push_and_its_ack_rereads(monkeypatch):
    """The worker dies after its batches landed and before the second
    one's offset is committed: the restart reads again from the committed
    offset - duplicates above it, nothing missing, nothing below it twice."""
    from tests.recipes.fake_clickhouse import FakeCH
    from transferia_tpu.providers.kafka.provider import _KafkaQueueClient

    commit = _KafkaQueueClient.commit
    calls = []

    def commit_then_die(self, topic, partition, offset):
        calls.append(offset)
        if len(calls) == 2:
            raise ConnectionError("worker killed before the commit")
        commit(self, topic, partition, offset)

    monkeypatch.setattr(_KafkaQueueClient, "commit", commit_then_die)
    srv = FakeKafka(n_partitions=1).start()
    ch = FakeCH().start()
    stop = threading.Event()
    try:
        cp = MemoryCoordinator()
        t = _backlog_transfer("pushahead-kill", srv, ch)
        th = threading.Thread(
            target=run_replication, args=(t, cp),
            kwargs={"stop_event": stop, "backoff": 0.2}, daemon=True)
        th.start()
        assert _until(
            lambda: _committed(cp, "pushahead-kill") == _BACKLOG - 1, 40)
        stop.set()
        th.join(timeout=10)
    finally:
        stop.set()
        srv.stop()
        ch.stop()
    # the first worker committed one fetched batch and died on the second
    assert calls[:2] == [1023, 2047]
    counts = {}
    for i in _landed_ids(ch):
        counts[i] = counts.get(i, 0) + 1
    assert sorted(counts) == list(range(_BACKLOG))        # none missing
    assert all(counts[i] == 1 for i in range(1024))       # committed: once
    # what was pushed ahead of the failed ack had landed: read again
    assert sum(1 for i in range(1024, _BACKLOG) if counts[i] == 2) >= 1024
    assert max(counts.values()) == 2
