"""The kafka queue client keeps the decoded remainder of a fetch response
(providers/kafka/provider.py::_KafkaQueueClient).

A response holds far more records than `fetch(max_messages)` hands out; the
rest stays decoded and is handed out by the next calls with no broker
request for that partition.  What must hold whatever a response's size:
every offset handed out exactly once and in order, no gap between one
response and the next, a dry partition asked on every call, memory bounded
by `max_bytes_per_fetch` plus a response, and nothing lost when a remainder
is dropped (close, restart, a fetch error): offsets are committed only by
acks, and a remainder lies above everything handed out.
"""

import pytest

from tests.recipes.fake_kafka import FakeKafka
from transferia_tpu.coordinator import MemoryCoordinator
from transferia_tpu.providers.kafka.client import (
    ERR_OFFSET_OUT_OF_RANGE,
    KafkaClient,
    KafkaError,
)
from transferia_tpu.providers.kafka.protocol import Record
from transferia_tpu.providers.kafka.provider import (
    KafkaSourceParams,
    _KafkaQueueClient,
)
from transferia_tpu.stats import trace

TOPIC = "remainder"


@pytest.fixture
def srv():
    s = FakeKafka(n_partitions=2).start()
    s.create_topic(TOPIC)
    yield s
    s.stop()


@pytest.fixture(autouse=True)
def _tracing_off():
    yield
    trace.enable(False)
    trace.reset()


def _produce(srv, partition, start, n, batch=50):
    """Records `start .. start + n` in producer batches of `batch`; the
    fake serves whole batches up to 1,000 records a response."""
    c = KafkaClient([f"127.0.0.1:{srv.port}"])
    try:
        for base in range(start, start + n, batch):
            c.produce(TOPIC, partition, [
                Record(key=b"k", value=b"v%06d" % i)
                for i in range(base, min(base + batch, start + n))])
    finally:
        c.close()


def _client(srv, cp=None, partitions=None, **params):
    return _KafkaQueueClient(
        KafkaSourceParams(brokers=[f"127.0.0.1:{srv.port}"], topic=TOPIC,
                          **params),
        "remainder-test", cp, partitions=partitions)


class _Calls:
    """Counts the client's broker Fetches, and can answer in their place."""

    def __init__(self, q):
        self.real = q.client.fetch_multi
        self.seen = []          # (partitions asked, max_bytes, max_wait_ms)
        self.answer = None      # callable(offsets) -> result, or None
        q.client.fetch_multi = self

    def __call__(self, topic, offsets, max_bytes=8 << 20, max_wait_ms=250):
        self.seen.append((sorted(offsets), max_bytes, max_wait_ms))
        if self.answer is not None:
            return self.answer(offsets)
        return self.real(topic, offsets, max_bytes=max_bytes,
                         max_wait_ms=max_wait_ms)


def _empty(offsets):
    return {p: ([], 0) for p in offsets}


def _offsets(batches, partition=0):
    return [m.offset for fb in batches if fb.partition == partition
            for m in fb.messages]


# -- (a) a response larger than max_messages: decoded once -----------------

@pytest.mark.parametrize("n,max_messages,batch", [
    (500, 64, 50), (1000, 100, 50), (65, 64, 65), (3000, 1024, 3000),
])
def test_a_backlog_is_handed_out_from_one_fetch(srv, n, max_messages,
                                                batch):
    _produce(srv, 0, 0, n, batch=batch)
    q = _client(srv)
    calls = _Calls(q)
    trace.TELEMETRY.reset()
    trace.enable(True)
    handed = []
    want = -(-n // max_messages)
    for _ in range(want):
        out = q.fetch(max_messages)
        assert [fb.partition for fb in out] == [0]
        assert len(out[0].messages) <= max_messages
        handed.append(out[0])
    q.close()
    assert _offsets(handed) == list(range(n))
    assert [len(fb.messages) for fb in handed[:-1]] \
        == [max_messages] * (want - 1)
    m = handed[0].messages[3]
    assert (m.value, m.key, m.topic, m.partition, m.offset, m.headers) \
        == (b"v000003", b"k", TOPIC, 0, 3, ())
    assert m.write_time_ns > 0
    # one broker Fetch held rows; partition 1, dry, was asked each call
    assert [c for c in calls.seen if 0 in c[0]] == [([0, 1], 8 << 20, 250)]
    decodes = [s[7] for s in trace.spans() if s[0] == "kafka_decode"]
    assert decodes == [{"partition": 0, "records": n,
                        "bytes": decodes[0]["bytes"]}]
    assert decodes[0]["bytes"] > n * len(b"kv000000")
    tel = trace.TELEMETRY.snapshot()
    assert tel["kafka_handouts"] == want
    assert tel["kafka_handouts_buffered"] == want - 1


def test_one_partition_sends_no_request_while_a_remainder_is_left(srv):
    _produce(srv, 0, 0, 300)
    q = _client(srv, partitions=[0])
    calls = _Calls(q)
    got = []
    for _ in range(5):
        got += q.fetch(64)
    assert _offsets(got) == list(range(300))
    assert len(calls.seen) == 1
    assert q.fetch(64) == [] and len(calls.seen) == 2
    q.close()


# -- (b) a response of max_messages or fewer: request for request ----------

@pytest.mark.parametrize("n,max_messages", [(10, 64), (64, 64), (1, 1)])
def test_a_small_response_leaves_no_remainder(srv, n, max_messages):
    _produce(srv, 0, 0, n)
    q = _client(srv)
    calls = _Calls(q)
    trace.TELEMETRY.reset()
    out = q.fetch(max_messages)
    assert _offsets(out) == list(range(n))
    assert q._remainders == {} and q.held_bytes() == 0
    assert q.fetch(max_messages) == []
    _produce(srv, 0, n, n)
    assert _offsets(q.fetch(max_messages)) == list(range(n, 2 * n))
    q.close()
    # every call asked the broker, for both partitions, with the long poll
    assert calls.seen == [([0, 1], 8 << 20, 250)] * 3
    tel = trace.TELEMETRY.snapshot()
    assert (tel["kafka_handouts"], tel["kafka_handouts_buffered"]) == (2, 0)


# -- (c) a dry partition is asked on every call ----------------------------

def test_a_late_record_does_not_wait_for_another_partitions_remainder(srv):
    _produce(srv, 0, 0, 640)
    q = _client(srv)
    calls = _Calls(q)
    first = q.fetch(64)
    assert [fb.partition for fb in first] == [0]
    _produce(srv, 1, 0, 1)
    nxt = q.fetch(64)
    assert [fb.partition for fb in nxt] == [0, 1]
    assert _offsets(nxt, 0) == list(range(64, 128))
    assert _offsets(nxt, 1) == [0]
    # asked alone, without the long poll, for what room the remainder left
    parts, max_bytes, wait = calls.seen[1]
    assert parts == [1] and wait == 0
    assert 0 < max_bytes == (8 << 20) - 576 * len(b"kv000000")
    rest = []
    for _ in range(8):
        rest += q.fetch(64)
    assert _offsets(rest, 0) == list(range(128, 640))
    assert _offsets(rest, 1) == []
    assert all(c[0] == [1] and c[2] == 0 for c in calls.seen[1:10])
    assert q.fetch(64) == [] and calls.seen[-1] == ([0, 1], 8 << 20, 250)
    q.close()


# -- (d) memory: max_bytes_per_fetch plus a response -----------------------

def test_bytes_held_stay_within_the_fetch_size_plus_a_response(srv):
    per_record = len(b"kv000000")
    logs = {0: 2000, 1: 1500}      # so that one runs dry before the other
    for p, n in logs.items():
        _produce(srv, p, 0, n)
    limit = 300 * per_record
    q = _client(srv, max_bytes_per_fetch=limit)
    calls = _Calls(q)
    # the fake's response: 1,000 records a partition, whatever max_bytes
    response = 2 * 1000 * per_record
    got, peak, skipped = [], 0, 0
    for _ in range(400):
        n_calls = len(calls.seen)
        dry = sorted(p for p in logs if p not in q._remainders)
        held = q.held_bytes()
        out = q.fetch(16)
        if dry and held < limit:
            assert calls.seen[n_calls:] == [
                (dry, limit - held, 0 if len(dry) < 2 else 250)]
        else:
            assert len(calls.seen) == n_calls
            skipped += bool(dry)   # a dry partition, and no room to ask
        peak = max(peak, q.held_bytes() + sum(
            len(m.value) + len(m.key) for fb in out for m in fb.messages))
        got += out
        if not out:
            break
    q.close()
    assert skipped > 0
    assert limit < peak <= limit + response
    for p, n in logs.items():
        assert _offsets(got, p) == list(range(n))


# -- (e) a restart reads on from the committed offset ----------------------

def test_a_closed_clients_remainder_is_read_again_after_restart(srv):
    _produce(srv, 0, 0, 500)
    cp = MemoryCoordinator()
    q = _client(srv, cp)
    first, second = q.fetch(64), q.fetch(64)
    q.commit(TOPIC, 0, first[0].messages[-1].offset)   # one unit acked
    assert q.held_bytes() > 0
    q.close()
    assert q._remainders == {}
    q2 = _client(srv, cp)
    assert q2.positions[0] == 64
    got = []
    for _ in range(7):
        got += q2.fetch(64)
    q2.close()
    # the un-acked second unit and the dropped remainder, again, no gap
    assert _offsets(second) == list(range(64, 128))
    assert _offsets(got) == list(range(64, 500))


# -- (f) the broker answers empty: the remainder still goes out ------------

@pytest.mark.parametrize("partitions", [[0], [0, 1]])
def test_an_empty_answer_does_not_shadow_a_remainder(srv, partitions):
    _produce(srv, 0, 0, 300)
    q = _client(srv, partitions=partitions)
    calls = _Calls(q)
    got = q.fetch(64)
    calls.answer = _empty          # the benchmark's fence
    for _ in range(4):
        out = q.fetch(64)
        assert out, "an empty answer shadowed the remainder"
        got += out
    assert _offsets(got) == list(range(300))
    assert q.fetch(64) == [] and q.fetch(64) == []
    # then the position asked for is behind the last record handed out
    assert calls.seen[-1][0] == partitions and q.positions[0] == 300
    q.close()


# -- (g) a fetch error drops what was decoded, and loses nothing -----------

@pytest.mark.parametrize("error", [
    KafkaError("offset out of range", code=ERR_OFFSET_OUT_OF_RANGE),
    KafkaError("kafka io error (node 0): reset"),
], ids=["offset_out_of_range", "io_error"])
def test_a_fetch_error_drops_the_remainder_and_loses_nothing(srv, error):
    _produce(srv, 0, 0, 300)
    _produce(srv, 1, 0, 5)
    q = _client(srv)
    calls = _Calls(q)
    got = q.fetch(64)
    assert [fb.partition for fb in got] == [0, 1] and 0 in q._remainders

    def fail(_offsets):
        raise error

    calls.answer = fail
    with pytest.raises(KafkaError):
        q.fetch(64)
    assert q._remainders == {} and q.positions == {0: 64, 1: 5}
    calls.answer = None
    for _ in range(4):
        got += q.fetch(64)
    q.close()
    assert calls.seen[-4][0] == [0, 1]      # both read again from positions
    assert _offsets(got, 0) == list(range(300))
    assert _offsets(got, 1) == list(range(5))


# -- the scanned blob as a sequence of Records -----------------------------

def _blob(n):
    from transferia_tpu.providers.kafka.protocol import encode_record_batch

    return b"".join(
        encode_record_batch(
            [Record(key=None if i % 5 == 0 else b"k%d" % i,
                    value=None if i % 7 == 0 else b"v%d" % i,
                    timestamp_ms=1000 + i)
             for i in range(base, min(base + 16, n))],
            base_offset=base)
        for base in range(0, n, 16))


def _plain(records):
    return [(r.key, r.value, r.offset, r.timestamp_ms, list(r.headers))
            for r in records]


@pytest.mark.parametrize("pick", [
    slice(None), slice(10, 30), slice(-5, None), slice(37, 37),
    slice(None, None, 3), 0, 41, -1,
], ids=str)
def test_a_record_view_reads_as_the_decoded_list(pick):
    from transferia_tpu.providers.kafka.protocol import (
        RecordView,
        _walk_record_batches,
        payload_bytes,
        scan_record_batches,
    )

    blob = _blob(42)
    view, walked = scan_record_batches(blob), _walk_record_batches(blob)
    assert isinstance(view, RecordView) and len(view) == len(walked) == 42
    got, want = view[pick], walked[pick]
    if isinstance(pick, slice):
        assert isinstance(got, RecordView) and len(got) == len(want)
        assert _plain(got) == _plain(want)
        assert payload_bytes(got) == payload_bytes(want)
    else:
        assert _plain([got]) == _plain([want])
    with pytest.raises(IndexError):
        view[42]


@pytest.mark.parametrize("headers,compression", [
    ([(b"h", b"x")], ""), ([], "gzip"),
], ids=["headers", "gzip"])
def test_what_the_scan_leaves_out_is_walked_into_a_list(headers,
                                                        compression):
    from transferia_tpu.providers.kafka.protocol import (
        encode_record_batch,
        scan_record_batches,
    )

    got = scan_record_batches(encode_record_batch(
        [Record(key=b"k", value=b"v%d" % i, headers=headers)
         for i in range(20)], base_offset=0, compression=compression))
    assert isinstance(got, list)
    assert [r.offset for r in got] == list(range(20))
    assert got[3].headers == headers
