"""Serializers + ParseQueue ordering/ack guarantees
(cf. pkg/parsequeue/parsequeue_test.go)."""

import json
import random
import threading
import time

import pytest

from transferia_tpu.abstract import TableID
from transferia_tpu.abstract.interfaces import AsyncSink
from transferia_tpu.abstract.schema import new_table_schema
from transferia_tpu.columnar import ColumnBatch
from transferia_tpu.parsequeue import ParseQueue
from transferia_tpu.parsers import Message
from transferia_tpu.providers.queue_common import (
    FetchedBatch,
    QueueSource,
    Sequencer,
)
from transferia_tpu.stats import trace
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


class PerOffsetSequencer:
    """The commit contract written out one offset at a time: every
    in-flight offset a `[offset, acked]` entry in start order; an ack
    marks its offsets and frees the acked front."""

    def __init__(self):
        self.inflight = {}

    def start_processing(self, topic, partition, offsets):
        self.inflight.setdefault((topic, partition), []).extend(
            [o, False] for o in offsets)

    def ack(self, topic, partition, offsets):
        lst = self.inflight.get((topic, partition), [])
        done = set(offsets)
        for entry in lst:
            if entry[0] in done:
                entry[1] = True
        commit = None
        while lst and lst[0][1]:
            commit = lst.pop(0)[0]
        return commit


def random_units(rng, n_units, start=0):
    """Ascending offset lists of 1 to 1,024 offsets, with gaps between
    the units and inside them."""
    units, o = [], start
    for _ in range(n_units):
        o += rng.choice((0, 0, 1, 7, 300))       # a gap before the unit
        unit = []
        for _ in range(rng.choice((1, 2, rng.randint(1, 1024), 1024))):
            unit.append(o)
            o += 1 + (rng.random() < 0.05) * rng.randint(1, 40)
        units.append(unit)
    return units


class TestSequencer:
    """The per-unit Sequencer against the per-offset model of the same
    contract: the highest offset with no unacked predecessor."""

    @pytest.mark.parametrize("seed", [1, 2, 3, 2**31 + 11])
    def test_every_ack_commits_what_the_per_offset_model_commits(
            self, seed):
        rng = random.Random(seed)
        parts = [("t", 0), ("t", 1), ("u", 0)]
        pending = {key: random_units(rng, 24, start=rng.randint(0, 10**9))
                   for key in parts}
        seq, model = Sequencer(), PerOffsetSequencer()
        started, commits = [], 0
        while any(pending.values()) or started:
            if started and (not any(pending.values())
                            or rng.random() < 0.45):
                key, unit = started.pop(rng.randrange(len(started)))
                got = seq.ack(*key, unit[0])
                assert got == model.ack(*key, unit)
                commits += got is not None
            else:
                key = rng.choice([k for k in parts if pending[k]])
                unit = pending[key].pop(0)
                seq.start_processing(*key, unit[0], unit[-1])
                model.start_processing(*key, unit)
                started.append((key, unit))
            if rng.random() < 0.1:
                # a unit never started: past every offset, or a
                # partition with nothing in flight
                key = rng.choice(parts + [("t", 9)])
                depth = seq.inflight_units(*key)
                assert seq.ack(*key, 2**62) is None
                assert model.ack(*key, [2**62]) is None
                assert seq.inflight_units(*key) == depth
        assert commits > 0
        assert all(seq.inflight_units(*k) == 0 for k in parts)

    def test_a_unit_never_started_changes_nothing(self):
        seq = Sequencer()
        assert seq.ack("t", 0, 0) is None
        seq.start_processing("t", 0, 0, 9)
        seq.start_processing("t", 0, 10, 19)
        assert seq.ack("t", 0, 5) is None        # inside a unit, not one
        assert seq.ack("t", 1, 0) is None        # another partition
        assert seq.inflight_units("t", 0) == 2
        assert seq.ack("t", 0, 0) == 9
        assert seq.ack("t", 0, 0) is None        # acked already
        assert seq.ack("t", 0, 10) == 19

    def test_a_unit_of_one_offset(self):
        seq = Sequencer()
        seq.start_processing("t", 0, 5, 5)
        seq.start_processing("t", 0, 6, 6)
        seq.start_processing("t", 0, 9, 9)
        assert seq.ack("t", 0, 6) is None        # 5 is still in flight
        assert seq.ack("t", 0, 5) == 6
        assert seq.ack("t", 0, 9) == 9
        assert seq.inflight_units("t", 0) == 0

    def test_the_record_holds_one_entry_a_unit_at_depth(self):
        seq = Sequencer()
        for u in range(64):
            seq.start_processing("t", 0, u * 1024, u * 1024 + 1023)
        # 65,536 offsets in flight, 64 entries
        assert seq.inflight_units("t", 0) == 64
        assert len(seq._inflight[("t", 0)]) == 64
        for u in range(64):
            assert seq.ack("t", 0, u * 1024) == u * 1024 + 1023
            # an in-order ack pops exactly its own entry
            assert seq.inflight_units("t", 0) == 63 - u
        # out of order at depth: the acked tail waits for the head
        for u in range(64, 128):
            seq.start_processing("t", 0, u * 1024, u * 1024 + 1023)
        for u in range(127, 64, -1):
            assert seq.ack("t", 0, u * 1024) is None
            assert seq.inflight_units("t", 0) == 64
        assert seq.ack("t", 0, 64 * 1024) == 128 * 1024 - 1
        assert seq.inflight_units("t", 0) == 0


class TestQueueSourceCommits:
    """QueueSource + Sequencer over a stub client and a sink whose futures
    the test resolves: what is committed when a flush fails."""

    class Client:
        def __init__(self, n_batches, per_batch, gaps=False):
            # with gaps: every other offset of a batch, and 5 skipped
            # between batches (compaction, transaction markers)
            step, skip = (2, 5) if gaps else (1, 0)
            span = per_batch * step + skip
            self.batches = [
                FetchedBatch("t", 0, [
                    Message(value=b"x", topic="t", partition=0,
                            offset=b * span + i * step)
                    for i in range(per_batch)])
                for b in range(n_batches)]
            self.last = [fb.last_offset for fb in self.batches]
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

    def _failed_flush(self, gaps):
        client = self.Client(n_batches=5, per_batch=10, gaps=gaps)
        last = client.last
        sink, _src, th, raised = self._start(client)
        assert wait_until(lambda: len(sink.futs) == 5)
        assert client.commits == []       # pushed ahead, nothing acked
        sink.futs[0].set_result(None)
        assert wait_until(lambda: client.commits == [last[0]])
        boom = RuntimeError("insert refused")
        sink.futs[1].set_exception(boom)
        # the batches behind it landed (say): still no commit for them
        for f in sink.futs[2:]:
            f.set_result(None)
        th.join(5)
        assert not th.is_alive() and raised == [boom]
        assert client.commits == [last[0]] and client.closed
        return client.commits

    def _commits_in_order(self, gaps):
        client = self.Client(n_batches=4, per_batch=10, gaps=gaps)
        last = client.last
        sink, src, th, raised = self._start(client)
        assert wait_until(lambda: len(sink.futs) == 4)
        for k in (2, 1):
            sink.futs[k].set_result(None)
        time.sleep(0.05)
        assert client.commits == []
        sink.futs[0].set_result(None)
        assert wait_until(lambda: client.commits == last[:3])
        src.stop()
        sink.futs[3].set_result(None)
        th.join(5)
        assert not th.is_alive() and raised == []
        assert client.commits == last
        return client.commits

    def test_a_failed_flush_commits_nothing_past_the_last_acked_offset(
            self):
        assert self._failed_flush(gaps=False) == [9]

    def test_with_gaps_a_failed_flush_commits_nothing_past_the_last_ack(
            self):
        assert self._failed_flush(gaps=True) == [18]

    def test_commits_follow_the_acks_in_order(self):
        assert self._commits_in_order(gaps=False) == [9, 19, 29, 39]

    def test_commits_follow_the_acks_in_order_with_gaps(self):
        assert self._commits_in_order(gaps=True) == [18, 43, 68, 93]

    def test_every_acked_unit_records_a_source_ack_span(self):
        trace.enable(True)
        trace.reset()
        try:
            self._commits_in_order(gaps=False)
        finally:
            trace.enable(False)
        acks = [s for s in trace.spans() if s[0] == "source_ack"]
        trace.reset()
        assert len(acks) == 4
        depths = [s[7]["inflight_units"] for s in acks]
        # units 2 and 1 are acked while 0 is in flight before them
        assert all(1 <= d <= 4 for d in depths)
        assert depths[-1] == 1                  # the last unit alone


# helper used above: wait() raises on failure; tests need a non-raising wait
def _wait_quiet(self):
    with self._cv:
        while self._outstanding > 0:
            self._cv.wait(timeout=0.5)


ParseQueue.wait_quiet = _wait_quiet
