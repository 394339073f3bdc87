"""The Kafka sink's staged path as framed record sections: what a push
frames (a renderer's block as it is, other serializers' pairs laid into
one) against the Record path it replaced - the Produce request's body
byte for byte under one clock, every batch of it decoded and its CRC32C
checked; the native gather framer against the record encoder; a request
sent as a list of buffers; the counters that say which path framed."""

import socket
import struct
import threading
import time

import numpy as np
import pyarrow as pa
import pytest

from tests.recipes.fake_kafka import FakeKafka
from transferia_tpu import native
from transferia_tpu.abstract.change_item import ChangeItem
from transferia_tpu.abstract.kinds import Kind
from transferia_tpu.abstract.schema import ColSchema, TableID, TableSchema
from transferia_tpu.columnar.batch import ColumnBatch
from transferia_tpu.providers.kafka.client import (
    API_PRODUCE,
    KafkaClient,
    KafkaError,
)
from transferia_tpu.providers.kafka.protocol import (
    Reader,
    Record,
    crc32c,
    decode_record_batches,
    enc_str,
    enc_varint,
    encode_record_batch,
)
from transferia_tpu.providers.kafka.provider import (
    KafkaSinker,
    KafkaTargetParams,
)
from transferia_tpu.serializers import make_queue_serializer
from transferia_tpu.stats import trace
from transferia_tpu.transform.plugins.sharder import hash_column_to_shards
from transferia_tpu.typesystem.rules import map_source_type
from transferia_tpu.utils import net

TOPIC = "cdc.tpcc"
N_PARTS = 16
NOW = 1_753_000_000.0
PID_EPOCH = 3


@pytest.fixture(autouse=True)
def one_clock(monkeypatch):
    # the envelopes' ts_ms and the batches' timestamps: one reading
    monkeypatch.setattr(time, "time", lambda: NOW)


@pytest.fixture
def broker():
    b = FakeKafka(n_partitions=N_PARTS).start()
    b.create_topic(TOPIC, N_PARTS)
    yield b
    b.stop()


@pytest.fixture
def bodies(monkeypatch):
    """Every Produce request's body as it went out, joined; and into
    PIDS the producer id each InitProducerId got."""
    sent = []
    roundtrip = KafkaClient._roundtrip
    init_producer = KafkaClient.init_producer

    def record(self, api_key, api_version, body, *args, **kwargs):
        if api_key == API_PRODUCE:
            sent.append(b"".join(bytes(p) for p in (
                [body] if isinstance(body, bytes) else body)))
        return roundtrip(self, api_key, api_version, body, *args, **kwargs)

    def record_pid(self, *args, **kwargs):
        pid, epoch = init_producer(self, *args, **kwargs)
        PIDS.append(pid)
        return pid, epoch

    monkeypatch.setattr(KafkaClient, "_roundtrip", record)
    monkeypatch.setattr(KafkaClient, "init_producer", record_pid)
    PIDS.clear()
    return sent


PIDS: list = []


def no_native(mp):
    mp.setattr(native, "_lib", None)
    mp.setenv("TRANSFERIA_TPU_NO_NATIVE", "1")


def batch(n, keyed=True, start=0, lsns=False):
    cols = [ColSchema(name="w", data_type=map_source_type("mysql", "int"),
                      primary_key=keyed, required=True,
                      original_type="mysql:smallint"),
            ColSchema(name="id", data_type=map_source_type("mysql", "int"),
                      primary_key=keyed, required=True,
                      original_type="mysql:int"),
            ColSchema(name="v", data_type=map_source_type("mysql",
                                                          "varchar"),
                      original_type="mysql:varchar(40)")]
    ids = list(range(start, start + n))
    rb = pa.record_batch({"w": pa.array([1] * n, pa.int32()),
                          "id": pa.array(ids, pa.int32()),
                          "v": pa.array([f"v{i}é" * (i % 5)
                                         for i in ids])})
    out = ColumnBatch.from_arrow(rb, TableID("tpcc", "t"),
                                 TableSchema(cols))
    if lsns:  # replication's source metadata: the Python renderer's
        out.lsns = np.arange(1, n + 1, dtype=np.int64)
    return out


def rows_of(cb):
    """The batch as a row list: the row path's (emit_item per row)."""
    return list(cb.to_rows())


def sinker(broker, partition_by=""):
    return KafkaSinker(KafkaTargetParams(
        brokers=[f"127.0.0.1:{broker.port}"], topic=TOPIC,
        serializer="debezium", partition_by=partition_by,
        serializer_config={"include_schema": True}), snapshot=True)


def publish(s, key, pushes):
    s.begin_part(key, PID_EPOCH)
    for b in pushes:
        s.push(b)
    return s.publish_part(key, PID_EPOCH)


# -- the Record path, written out: what the sink produced before ------------

def reference_batch(records: list, pid: int) -> bytes:
    """One transactional RecordBatch v2 of Records, every field packed
    here and the CRC taken over the joined bytes."""
    now = int(NOW * 1000)
    recs = []
    for i, r in enumerate(records):
        body = b"\x00" + enc_varint(0) + enc_varint(i)
        for b in (r.key, r.value):
            body += enc_varint(-1) if b is None else enc_varint(len(b)) + b
        body += enc_varint(0)
        recs.append(enc_varint(len(body)) + body)
    tail = struct.pack("!hiqqqhii", 0x10, len(records) - 1, now, now,
                       pid, PID_EPOCH, -1, len(records)) + b"".join(recs)
    return struct.pack("!qiibI", 0, 9 + len(tail), 0, 2,
                       crc32c(tail)) + tail


class Reference:
    """The staged path as it was: pairs, a Record a row, one list per
    partition, the null-key turn kept across pushes and parts."""

    def __init__(self, serializer, partition_by=""):
        self.serializer = serializer
        self.by = partition_by
        self.turn = 0

    def body(self, txn_id: str, pid: int, pushes) -> bytes:
        staged = {}
        for b in pushes:
            pairs = self.serializer.serialize_messages(b)
            if not pairs:
                continue
            if self.by and isinstance(b, ColumnBatch) and \
                    len(pairs) == b.n_rows:
                parts = hash_column_to_shards(b.column(self.by), N_PARTS)
            elif all(k is None for k, _ in pairs):
                parts = [(self.turn + i) % N_PARTS
                         for i in range(len(pairs))]
                self.turn = (self.turn + len(pairs)) % N_PARTS
            else:
                parts = [crc32c(k or b"") % N_PARTS for k, _ in pairs]
            for (k, v), p in zip(pairs, parts):
                staged.setdefault(p, []).append(Record(key=k, value=v))
        out = enc_str(txn_id) + struct.pack("!hii", -1, 30_000,
                                            1 if staged else 0)
        if staged:
            out += enc_str(TOPIC) + struct.pack("!i", len(staged))
        for p, records in sorted(staged.items()):
            blob = reference_batch(records, pid)
            # the retained Record encoder gives the same batch
            assert blob == encode_record_batch(
                records, producer_id=pid, producer_epoch=PID_EPOCH)
            out += struct.pack("!ii", p, len(blob)) + blob
        return out


def decoded(body: bytes) -> dict:
    """{partition: [(key, value)]} of a transactional Produce body, every
    batch's CRC32C checked and its offset deltas counted from 0."""
    r = Reader(body)
    r.string()
    r.i16(), r.i32()
    out = {}
    for _ in range(r.i32()):
        assert r.string() == TOPIC
        for _ in range(r.i32()):
            p, size = r.i32(), r.i32()
            blob = body[r.pos:r.pos + size]
            r.pos += size
            (crc,) = struct.unpack_from("!I", blob, 17)
            assert crc == crc32c(blob[21:])
            recs = decode_record_batches(blob)
            assert [x.offset for x in recs] == list(range(len(recs)))
            out[p] = [(x.key, x.value) for x in recs]
    assert r.remaining() == 0
    return out


def run_parts(broker, bodies, parts, native_on=True, partition_by=""):
    """Publish each part's pushes; (the bodies sent, the reference's)."""
    with pytest.MonkeyPatch.context() as mp:
        if not native_on:
            no_native(mp)
        s = sinker(broker, partition_by)
        ref = Reference(make_queue_serializer(
            "debezium", include_schema=True, snapshot=True), partition_by)
        want = []
        try:
            for i, pushes in enumerate(parts):
                publish(s, f"op/tpcc.t/{i}", pushes)
                txn_id = Reader(bodies[-1]).string()
                want.append(ref.body(txn_id, PIDS[-1], pushes))
        finally:
            s.close()
    return bodies[-len(parts):], want


CASES = {
    "keyed": [[batch(300)]],
    "keyless_turn_goes_on": [[batch(40, keyed=False),
                              batch(37, keyed=False, start=40)],
                             [batch(21, keyed=False, start=77)]],
    "pushes_go_on_per_partition": [[batch(100), batch(150, start=100),
                                    batch(7, start=250)]],
    "native_fast_and_row_in_one_part": [[
        batch(120), batch(30, start=120, lsns=True),
        rows_of(batch(25, start=150)), batch(60, start=175)]],
    "empty": [[batch(0)], [batch(0), batch(5)]],
}


@pytest.mark.parametrize("native_on", [True, False], ids=["native", "python"])
@pytest.mark.parametrize("case", list(CASES))
def test_the_produce_body_is_the_record_paths_byte_for_byte(
        broker, bodies, case, native_on):
    got, want = run_parts(broker, bodies, CASES[case], native_on)
    assert got == want
    for body in got:
        decoded(body)
    # the broker checked every batch's CRC at append and landed them all
    rows = sum(b.n_rows if isinstance(b, ColumnBatch) else len(b)
               for part in CASES[case] for b in part)
    assert broker.size(TOPIC) == rows


@pytest.mark.parametrize("native_on", [True, False], ids=["native", "python"])
def test_partition_by_names_every_rows_partition_on_either_path(
        broker, bodies, native_on):
    # the configured column's hash, whatever the batch's size and whether
    # it rendered natively; the at-least-once push lands each row where
    # the staged publish did
    pushes = [batch(300), batch(90, keyed=False, start=300),
              batch(2000, start=390)]
    got, want = run_parts(broker, bodies, [pushes], native_on,
                          partition_by="id")
    assert got == want
    staged = decoded(got[0])
    with pytest.MonkeyPatch.context() as mp:
        if not native_on:
            no_native(mp)
        s = sinker(broker, partition_by="id")
        try:
            for b in pushes:
                s.push(b)
        finally:
            s.close()
    for p in range(N_PARTS):
        landed = [(r.key, r.value) for r in broker.records(TOPIC, p)]
        assert landed == staged.get(p, []) * 2


def test_switched_off_the_request_is_the_native_ones(broker, bodies):
    # TRANSFERIA_TPU_NO_NATIVE=1: the Python renderer, framer and CRC give
    # the bytes the native ones give
    parts = CASES["native_fast_and_row_in_one_part"] \
        + CASES["keyless_turn_goes_on"]
    on, _ = run_parts(broker, bodies, parts, native_on=True)
    off, _ = run_parts(broker, bodies, parts, native_on=False)
    assert on == off


def test_a_rendered_block_is_framed_with_no_record_built(
        broker, bodies, monkeypatch):
    built = []
    init = Record.__init__

    def counting(self, *args, **kwargs):
        built.append(1)
        init(self, *args, **kwargs)

    monkeypatch.setattr(Record, "__init__", counting)
    trace.TELEMETRY.reset()
    s = sinker(broker)
    try:
        assert publish(s, "op/tpcc.t/0", [batch(500), batch(64, start=500,
                                                            keyed=False)]) \
            == 564
        staged = bodies[-1]
        # nor on the at-least-once push
        s.push(batch(36, start=564))
    finally:
        s.close()
    assert built == []
    tel = trace.TELEMETRY.snapshot()
    assert tel["debezium_rows_native"] == 600
    assert tel["kafka_records_framed"] == tel["kafka_records_framed_block"] \
        == 600
    assert sum(map(len, decoded(staged).values())) == 564


def test_every_path_is_counted_framed_and_only_blocks_as_blocks(
        broker, bodies):
    trace.TELEMETRY.reset()
    (pushes,) = CASES["native_fast_and_row_in_one_part"]
    s = sinker(broker)
    try:
        publish(s, "op/tpcc.t/0", pushes)
    finally:
        s.close()
    tel = trace.TELEMETRY.snapshot()
    assert tel["kafka_records_framed"] == 120 + 30 + 25 + 60
    assert tel["kafka_records_framed_block"] == 120 + 60
    # the at-least-once push frames at push too
    s = sinker(broker)
    try:
        s.push(batch(10))
        s.push(batch(5, start=10, lsns=True))
    finally:
        s.close()
    tel = trace.TELEMETRY.snapshot()
    assert tel["kafka_records_framed"] == 250
    assert tel["kafka_records_framed_block"] == 190


def test_the_at_least_once_push_lands_the_same_records(broker):
    s = sinker(broker)
    try:
        s.push(batch(200))
        s.push(batch(30, keyed=False, start=200))
    finally:
        s.close()
    staged = FakeKafka(n_partitions=N_PARTS).start()
    staged.create_topic(TOPIC, N_PARTS)
    try:
        t = sinker(staged)
        try:
            publish(t, "op/tpcc.t/0", [batch(200),
                                        batch(30, keyed=False, start=200)])
        finally:
            t.close()
        for p in range(N_PARTS):
            assert [(r.key, r.value) for r in broker.records(TOPIC, p)] == \
                [(r.key, r.value) for r in staged.records(TOPIC, p)]
    finally:
        staged.stop()


# -- the native gather framer ------------------------------------------------

def _messages(rng, n):
    keys = [None if rng.random() < 0.2 else bytes(rng.integers(
        0, 256, rng.integers(0, 40), dtype=np.uint8)) for _ in range(n)]
    vals = [None if rng.random() < 0.1 else bytes(rng.integers(
        0, 256, rng.integers(0, 300), dtype=np.uint8)) for _ in range(n)]
    return keys, vals


def _laid(parts):
    data = b"".join(p or b"" for p in parts)
    off = np.zeros(len(parts) + 1, dtype=np.int64)
    np.cumsum([len(p or b"") for p in parts], out=off[1:])
    null = np.array([p is None for p in parts], dtype=np.uint8)
    return data, off, null


def _encode(cdll, keys, vals):
    """kafka_encode_records over the messages in the given order."""
    kd, ko, kn = _laid(keys)
    vd, vo, vn = _laid(vals)
    cap = len(kd) + len(vd) + 64 * len(keys) + 64
    out = np.empty(cap, dtype=np.uint8)
    got = cdll.kafka_encode_records(
        np.frombuffer(kd, np.uint8) if kd else np.zeros(1, np.uint8), ko,
        kn.ctypes.data, np.frombuffer(vd, np.uint8) if vd
        else np.zeros(1, np.uint8), vo, vn.ctypes.data, None, len(keys),
        out, cap)
    assert got >= 0
    return out[:got].tobytes()


def _gather(cdll, keys, vals, rows, first, keyless=False):
    kd, ko, kn = _laid(keys)
    vd, vo, vn = _laid(vals)
    args = (None if keyless else kd, None if keyless else ko.ctypes.data,
            None if keyless else kn.ctypes.data, vd, vo, vn.ctypes.data,
            rows, len(rows), first)
    size = cdll.kafka_frame_rows(*args, None, 0)
    out = native.new_bytes(None, size)
    assert cdll.kafka_frame_rows(*args, out, size) == size
    assert cdll.kafka_frame_rows(*args, out, size - 1) == -1 or size == 0
    return out


@pytest.mark.parametrize("first", [0, 1, 63, 64, 8191, 8192, 10 ** 6])
@pytest.mark.parametrize("keyless", [False, True])
def test_the_gather_framer_is_the_record_encoder_on_the_same_rows(
        first, keyless):
    cdll = native.lib()
    rng = np.random.default_rng(first + keyless)
    keys, vals = _messages(rng, 200)
    if keyless:
        keys = [None] * len(keys)
    rows = rng.permutation(200)[:150].astype(np.int64)
    got = _gather(cdll, keys, vals, rows, first, keyless)
    # offset deltas from `first`: the encoder's records after `first`
    # fillers, whose own bytes come off the front
    fill_k, fill_v = [b"f"] * first, [b"f"] * first
    head = _encode(cdll, fill_k, fill_v) if first else b""
    want = _encode(cdll, fill_k + [keys[r] for r in rows],
                   fill_v + [vals[r] for r in rows])
    assert want[:len(head)] == head
    assert got == want[len(head):]


def test_the_gather_framer_frames_nothing_for_no_rows():
    cdll = native.lib()
    rows = np.zeros(0, dtype=np.int64)
    assert _gather(cdll, [b"k"], [b"v"], rows, 5) == b""


# -- a request as a list of buffers ------------------------------------------

def _served(client, body):
    """Send `body` through _roundtrip on one end of a socket pair; the
    bytes the other end read."""
    a, b = socket.socketpair()
    a.settimeout(10)
    client._conns["boot"] = a
    got = []

    def serve():
        size = struct.unpack("!i", b.recv(4, socket.MSG_WAITALL))[0]
        req = b.recv(size, socket.MSG_WAITALL)
        got.append(req)
        corr = struct.unpack_from("!i", req, 4)[0]
        b.sendall(struct.pack("!ii", 4, corr))

    t = threading.Thread(target=serve)
    t.start()
    try:
        client._roundtrip(API_PRODUCE, 3, body)
    finally:
        t.join(timeout=10)
        client.close()
        b.close()
    return got[0]


def test_a_list_of_buffers_goes_out_as_the_one_buffer_would():
    pieces = [b"head", np.arange(70_000, dtype=np.uint8).tobytes(), b"",
              memoryview(b"x" * 5000), b"tail" * 300]
    whole = b"".join(bytes(p) for p in pieces)
    one = _served(KafkaClient(["127.0.0.1:1"]), whole)
    many = _served(KafkaClient(["127.0.0.1:1"]), pieces)
    assert one == many
    assert one.endswith(whole)


class _BrokenSock:
    def __init__(self):
        self.calls = 0
        self.closed = False

    def sendmsg(self, views):
        self.calls += 1
        if self.calls > 1:
            raise BrokenPipeError("peer went away")
        return 3

    def close(self):
        self.closed = True


def test_a_send_error_part_way_drops_the_connection():
    client = KafkaClient(["127.0.0.1:1"])
    sock = _BrokenSock()
    client._conns["boot"] = sock
    with pytest.raises(KafkaError, match="kafka io error"):
        client._roundtrip(API_PRODUCE, 3, [b"a" * 100, b"b" * 100])
    assert sock.calls == 2 and sock.closed
    assert "boot" not in client._conns


class _TrickleSock:
    """sendmsg takes a few bytes a call, and records what it took."""

    def __init__(self, step):
        self.step, self.out, self.calls = step, bytearray(), 0

    def sendmsg(self, views):
        assert len(views) <= net._IOV_MAX
        self.calls += 1
        take = self.step
        for v in views:
            part = bytes(v[:take])
            self.out += part
            take -= len(part)
            if not take:
                break
        return self.step - take


@pytest.mark.parametrize("step", [1, 7, 4096])
def test_a_partial_send_goes_on_where_it_stopped(monkeypatch, step):
    monkeypatch.setattr(net, "_IOV_MAX", 3)
    pieces = [bytes([i]) * (i * 37 % 500) for i in range(40)]
    sock = _TrickleSock(step)
    net.send_pieces(sock, pieces)
    assert bytes(sock.out) == b"".join(pieces)


def test_tls_sockets_take_a_sendall_a_piece(monkeypatch):
    sent = []

    class FakeTLS:
        def sendall(self, piece):
            sent.append(bytes(piece))

    monkeypatch.setattr(net.ssl, "SSLSocket", FakeTLS)
    net.send_pieces(FakeTLS(), [b"a", b"bc"])
    assert sent == [b"a", b"bc"]


def test_row_events_of_a_list_keep_their_table_as_the_topic(broker, bodies):
    # a sink with no fixed topic: the row list's first row names it
    s = KafkaSinker(KafkaTargetParams(
        brokers=[f"127.0.0.1:{broker.port}"], serializer="debezium",
        serializer_config={"include_schema": False}), snapshot=True)
    item = rows_of(batch(3))[0]
    assert isinstance(item, ChangeItem) and item.kind == Kind.INSERT
    try:
        publish(s, "op/tpcc.t/0", [rows_of(batch(3))])
    finally:
        s.close()
    r = Reader(bodies[-1])
    r.string()
    r.i16(), r.i32()
    assert r.i32() == 1 and r.string() == "tpcc.t"


@pytest.mark.parametrize("offsets", [[0, 2, 9], [0, 2]])
def test_a_block_whose_offsets_do_not_fit_is_refused_not_read(offsets):
    from transferia_tpu.providers.kafka.protocol import frame_messages
    from transferia_tpu.serializers.formats import MessageBlock

    block = MessageBlock(2, b"abc", np.array(offsets, dtype=np.int64))
    with pytest.raises(ValueError, match="do not fit"):
        frame_messages(block, [(np.array([0, 1], dtype=np.int64), 0)])
