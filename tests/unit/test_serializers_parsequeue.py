"""Serializers + ParseQueue ordering/ack guarantees
(cf. pkg/parsequeue/parsequeue_test.go)."""

import json
import threading
import time

import pytest

from transferia_tpu.abstract import TableID
from transferia_tpu.abstract.interfaces import AsyncSink
from transferia_tpu.abstract.schema import new_table_schema
from transferia_tpu.columnar import ColumnBatch
from transferia_tpu.parsequeue import ParseQueue
from transferia_tpu.parsers import Message
from transferia_tpu.providers.queue_common import FetchedBatch, QueueSource
from transferia_tpu.serializers import (
    make_queue_serializer,
    make_serializer,
)
from transferia_tpu.stats.trace import TELEMETRY

SCHEMA = new_table_schema([("id", "int64", True), ("name", "utf8")])
TID = TableID("s", "t")


def batch(n=3, start=0):
    return ColumnBatch.from_pydict(TID, SCHEMA, {
        "id": list(range(start, start + n)),
        "name": [f"n{i}" for i in range(start, start + n)],
    })


class TestSerializers:
    def test_json(self):
        out = make_serializer("json").serialize(batch(2)).decode()
        rows = [json.loads(l) for l in out.strip().split("\n")]
        assert rows == [{"id": 0, "name": "n0"}, {"id": 1, "name": "n1"}]

    def test_csv(self):
        out = make_serializer("csv", header=True).serialize(batch(2))
        assert out.decode().splitlines() == ["id,name", "0,n0", "1,n1"]

    def test_parquet_roundtrip(self):
        import io

        import pyarrow.parquet as pq

        out = make_serializer("parquet").serialize(batch(4))
        t = pq.read_table(io.BytesIO(out))
        assert t.column("id").to_pylist() == [0, 1, 2, 3]

    def test_raw(self):
        from transferia_tpu.parsers import Message, make_parser

        res = make_parser({"blank": {}}).do_batch([
            Message(value=b"line-a", topic="x"),
            Message(value=b"line-b", topic="x"),
        ])
        out = make_serializer("raw").serialize(res.batches[0])
        assert out == b"line-a\nline-b\n"

    def test_queue_json_keys(self):
        pairs = make_queue_serializer("json").serialize_messages(batch(2))
        assert json.loads(pairs[0][0]) == {"id": 0}
        assert json.loads(pairs[1][1])["name"] == "n1"

    def test_queue_native_roundtrip(self):
        from transferia_tpu.parsers import Message, make_parser

        pairs = make_queue_serializer("native").serialize_messages(batch(3))
        p = make_parser({"native": {}})
        res = p.do_batch([Message(value=v) for _, v in pairs])
        assert res.batches[0].to_pydict()["id"] == [0, 1, 2]

    def test_queue_debezium(self):
        pairs = make_queue_serializer("debezium").serialize_messages(batch(1))
        v = json.loads(pairs[0][1])
        assert v["payload"]["op"] == "c"

    def test_queue_mirror(self):
        from transferia_tpu.parsers import Message, make_parser

        res = make_parser({"blank": {}}).do_batch([
            Message(value=b"payload", key=b"k1", topic="x"),
        ])
        pairs = make_queue_serializer("mirror").serialize_messages(
            res.batches[0]
        )
        assert pairs == [(b"k1", b"payload")]


class OrderedSink(AsyncSink):
    def __init__(self, delay_first=0.0):
        self.pushed = []
        self.delay_first = delay_first
        self.lock = threading.Lock()

    def async_push(self, b):
        import concurrent.futures

        fut = concurrent.futures.Future()
        if self.delay_first and not self.pushed:
            time.sleep(self.delay_first)
        with self.lock:
            self.pushed.append(b)
        fut.set_result(None)
        return fut


class ManualSink(AsyncSink):
    """Futures the test resolves by hand, as a buffered sink's flush would."""

    def __init__(self):
        self.pushed = []
        self.futs = []
        self.lock = threading.Lock()

    def async_push(self, b):
        import concurrent.futures

        fut = concurrent.futures.Future()
        with self.lock:
            self.pushed.append(b)
            self.futs.append(fut)
        return fut


def wait_until(cond, timeout=5.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.005)
    return cond()


class TestParseQueue:
    def test_order_preserved_under_parallel_parse(self):
        sink = OrderedSink()
        acks = []

        def slow_parse(i):
            # earlier items parse slower: order must still hold
            time.sleep(0.02 * (8 - i) / 8)
            return batch(1, start=i)

        pq = ParseQueue(4, sink, slow_parse,
                        lambda raw, err: acks.append((raw, err)))
        for i in range(8):
            pq.add(i)
        pq.wait()
        pq.close()
        pushed_ids = [b.to_pydict()["id"][0] for b in sink.pushed]
        assert pushed_ids == list(range(8))      # push order == add order
        assert [a[0] for a in acks] == list(range(8))  # ack order too
        assert all(a[1] is None for a in acks)

    def test_ack_after_push(self):
        events = []

        class RecordingSink(AsyncSink):
            def async_push(self, b):
                import concurrent.futures

                events.append(("push", b.to_pydict()["id"][0]))
                fut = concurrent.futures.Future()
                fut.set_result(None)
                return fut

        pq = ParseQueue(2, RecordingSink(), lambda i: batch(1, start=i),
                        lambda raw, err: events.append(("ack", raw)))
        for i in range(4):
            pq.add(i)
        pq.wait()
        pq.close()
        # for each i, push precedes ack
        for i in range(4):
            assert events.index(("push", i)) < events.index(("ack", i))

    def test_parse_error_acked_with_error_and_latched(self):
        sink = OrderedSink()
        acks = []

        def parse(i):
            if i == 2:
                raise ValueError("bad payload")
            return batch(1, start=i)

        pq = ParseQueue(2, sink, parse,
                        lambda raw, err: acks.append((raw, err)))
        for i in range(4):
            pq.add(i)
        pq.wait_quiet()
        assert pq.failure is not None
        with pytest.raises(ValueError):
            pq.add(99)
        pq.close()
        errs = {raw: err for raw, err in acks}
        assert errs[2] is not None and isinstance(errs[2], ValueError)


    # -- the push stage runs ahead of the ack stage ---------------------------

    @pytest.mark.parametrize("n", [2, 5, 16])
    def test_every_unit_is_pushed_before_the_first_future_resolves(self, n):
        sink = ManualSink()
        acks = []
        before = TELEMETRY.snapshot()
        pq = ParseQueue(2, sink, lambda i: batch(1, start=i),
                        lambda raw, err: acks.append((raw, err)))
        for i in range(n):
            pq.add(i)
        assert wait_until(lambda: len(sink.futs) == n)
        assert [b.to_pydict()["id"][0] for b in sink.pushed] \
            == list(range(n))
        time.sleep(0.05)
        assert acks == []                 # nothing resolved, nothing acked
        for f in sink.futs:
            f.set_result(None)
        pq.wait()
        pq.close()
        assert acks == [(i, None) for i in range(n)]
        after = TELEMETRY.snapshot()
        assert after["parsequeue_pushes"] \
            - before["parsequeue_pushes"] == n
        # all but the first were handed over behind an unacked unit
        assert after["parsequeue_pushes_ahead"] \
            - before["parsequeue_pushes_ahead"] == n - 1

    def test_an_inline_sink_is_never_pushed_ahead(self):
        before = TELEMETRY.snapshot()
        pq = ParseQueue(2, OrderedSink(), lambda i: batch(1, start=i),
                        lambda raw, err: None)
        for i in range(6):
            pq.add(i)
            pq.wait()    # as a rebalance does: acked before the next
        pq.close()
        after = TELEMETRY.snapshot()
        assert after["parsequeue_pushes"] \
            - before["parsequeue_pushes"] == 6
        assert after["parsequeue_pushes_ahead"] \
            == before["parsequeue_pushes_ahead"]

    @pytest.mark.parametrize("order", [[3, 2, 1, 0], [2, 0, 3, 1],
                                       [1, 3, 0, 2]])
    def test_acks_fire_in_add_order_when_futures_resolve_out_of_order(
            self, order):
        sink = ManualSink()
        acks = []
        pq = ParseQueue(2, sink, lambda i: batch(1, start=i),
                        lambda raw, err: acks.append((raw, err)))
        for i in range(4):
            pq.add(i)
        assert wait_until(lambda: len(sink.futs) == 4)
        resolved = set()
        for k in order:
            sink.futs[k].set_result(None)
            resolved.add(k)
            # what may be acked: the resolved prefix, and no more
            prefix = next(i for i in range(5) if i not in resolved)
            assert wait_until(lambda: len(acks) == prefix)
            time.sleep(0.02)
            assert acks == [(i, None) for i in range(prefix)]
        pq.wait()
        pq.close()
        assert acks == [(i, None) for i in range(4)]

    def test_a_unit_with_two_batches_is_acked_only_after_both(self):
        sink = ManualSink()
        acks = []
        pq = ParseQueue(
            2, sink, lambda i: [batch(1, start=i), batch(2, start=10 + i)],
            lambda raw, err: acks.append((raw, err)))
        pq.add(0)
        pq.add(1)
        assert wait_until(lambda: len(sink.futs) == 4)
        sink.futs[1].set_result(None)      # the second batch of unit 0
        sink.futs[2].set_result(None)
        sink.futs[3].set_result(None)      # all of unit 1
        time.sleep(0.05)
        assert acks == []
        sink.futs[0].set_result(None)
        pq.wait()
        pq.close()
        assert acks == [(0, None), (1, None)]

    @pytest.mark.parametrize("k", [0, 2])
    def test_a_failed_future_fails_its_unit_and_every_later_one(self, k):
        sink = ManualSink()
        acks = []
        gate = threading.Event()
        n, held = 6, 4           # unit `held` is still parsing at the failure

        def parse(i):
            if i == held:
                gate.wait(5)
            return batch(1, start=i)

        pq = ParseQueue(2, sink, parse,
                        lambda raw, err: acks.append((raw, err)))
        for i in range(n):
            pq.add(i)
        assert wait_until(lambda: len(sink.futs) == held)
        boom = RuntimeError("flush failed")
        for i in range(k):
            sink.futs[i].set_result(None)
        sink.futs[k].set_exception(boom)
        # units behind it that were handed over already: landed or not,
        # they are acked with the error
        for f in sink.futs[k + 1:]:
            f.set_result(None)
        assert wait_until(lambda: pq.failure is boom)
        gate.set()
        pq.wait_quiet()
        assert acks == [(i, None) for i in range(k)] \
            + [(i, boom) for i in range(k, n)]
        # nothing was pushed once the failure was seen
        assert len(sink.pushed) == held
        with pytest.raises(RuntimeError, match="flush failed"):
            pq.add(99)
        with pytest.raises(RuntimeError, match="flush failed"):
            pq.wait()
        pq.close()

    def test_a_failing_ack_fn_latches_too(self):
        acks = []

        def ack(raw, err):
            acks.append((raw, err))
            if raw == 1 and err is None:
                raise OSError("commit refused")

        pq = ParseQueue(2, OrderedSink(), lambda i: batch(1, start=i), ack)
        for i in range(3):
            pq.add(i)
        pq.wait_quiet()
        assert isinstance(pq.failure, OSError)
        assert [r for r, _ in acks] == [0, 1, 2]
        assert acks[0][1] is None and acks[1][1] is None
        assert isinstance(acks[2][1], OSError)
        pq.close()

    def test_max_inflight_blocks_add_at_the_bound_and_releases_on_ack(self):
        sink = ManualSink()
        acks = []
        pq = ParseQueue(2, sink, lambda i: batch(1, start=i),
                        lambda raw, err: acks.append(raw), max_inflight=3)
        for i in range(3):
            pq.add(i)
        th = threading.Thread(target=pq.add, args=(3,), daemon=True)
        th.start()
        th.join(0.2)
        assert th.is_alive()              # three added and none acked
        assert len(sink.futs) == 3
        sink.futs[1].set_result(None)     # resolved, but not the oldest
        th.join(0.1)
        assert th.is_alive() and acks == []
        sink.futs[0].set_result(None)
        th.join(5)
        assert not th.is_alive()
        assert wait_until(lambda: len(sink.futs) == 4)
        for f in sink.futs[2:]:
            f.set_result(None)
        pq.wait()
        pq.close()
        assert acks == [0, 1, 2, 3]

    @pytest.mark.parametrize("inline", [True, False])
    def test_close_returns_with_both_stages_stopped(self, inline):
        sink = OrderedSink() if inline else ManualSink()
        acks = []
        pq = ParseQueue(2, sink, lambda i: batch(1, start=i),
                        lambda raw, err: acks.append(raw))
        for i in range(5):
            pq.add(i)
        if not inline:
            assert wait_until(lambda: len(sink.futs) == 5)
            for f in sink.futs:
                f.set_result(None)
        # close() without wait(): what is queued is pushed and acked
        pq.close()
        assert acks == list(range(5))
        assert not pq._pusher.is_alive() and not pq._acker.is_alive()
        with pytest.raises(RuntimeError, match="closed"):
            pq.add(5)
        pq.close()                        # a second close is a no-op


class TestQueueSourceCommits:
    """QueueSource + Sequencer over a stub client and a sink whose futures
    the test resolves: what is committed when a flush fails."""

    class Client:
        def __init__(self, n_batches, per_batch):
            self.batches = [
                FetchedBatch("t", 0, [
                    Message(value=b"x", topic="t", partition=0,
                            offset=b * per_batch + i)
                    for i in range(per_batch)])
                for b in range(n_batches)]
            self.commits = []
            self.closed = False

        def fetch(self, max_messages=1024):
            return [self.batches.pop(0)] if self.batches else []

        def commit(self, topic, partition, offset):
            self.commits.append(offset)

        def close(self):
            self.closed = True

    def _start(self, client):
        sink = ManualSink()
        src = QueueSource(client, None, parallelism=2, stop_poll=0.01)
        raised = []

        def run():
            try:
                src.run(sink)
            except BaseException as e:
                raised.append(e)

        th = threading.Thread(target=run, daemon=True)
        th.start()
        return sink, src, th, raised

    def test_a_failed_flush_commits_nothing_past_the_last_acked_offset(
            self):
        client = self.Client(n_batches=5, per_batch=10)
        sink, _src, th, raised = self._start(client)
        assert wait_until(lambda: len(sink.futs) == 5)
        assert client.commits == []       # pushed ahead, nothing acked
        sink.futs[0].set_result(None)
        assert wait_until(lambda: client.commits == [9])
        boom = RuntimeError("insert refused")
        sink.futs[1].set_exception(boom)
        # the batches behind it landed (say): still no commit for them
        for f in sink.futs[2:]:
            f.set_result(None)
        th.join(5)
        assert not th.is_alive() and raised == [boom]
        assert client.commits == [9] and client.closed

    def test_commits_follow_the_acks_in_order(self):
        client = self.Client(n_batches=4, per_batch=10)
        sink, src, th, raised = self._start(client)
        assert wait_until(lambda: len(sink.futs) == 4)
        for k in (2, 1):
            sink.futs[k].set_result(None)
        time.sleep(0.05)
        assert client.commits == []
        sink.futs[0].set_result(None)
        assert wait_until(lambda: client.commits == [9, 19, 29])
        src.stop()
        sink.futs[3].set_result(None)
        th.join(5)
        assert not th.is_alive() and raised == []
        assert client.commits == [9, 19, 29, 39]


# helper used above: wait() raises on failure; tests need a non-raising wait
def _wait_quiet(self):
    with self._cv:
        while self._outstanding > 0:
            self._cv.wait(timeout=0.5)


ParseQueue.wait_quiet = _wait_quiet
