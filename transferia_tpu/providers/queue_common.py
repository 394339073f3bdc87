"""Shared queue-source machinery (reference: pkg/providers/kafka/source.go
fetch/parse/ack loop + sequencer dedup, pkg/parsers wiring via the
endpoint's Parseable capability).

Any broker provider (in-memory mq, kafka, kinesis, eventhub) composes:
  reader (broker client) -> Sequencer -> ParseQueue(parser) -> AsyncSink
                               ^ commit offsets only after confirmed push
"""

from __future__ import annotations

import logging
import threading
import time
from collections import deque
from dataclasses import dataclass
from typing import Optional

from transferia_tpu.abstract.interfaces import AsyncSink, Source
from transferia_tpu.chaos.failpoints import failpoint
from transferia_tpu.parsequeue import ParseQueue
from transferia_tpu.parsers import Message, Parser, make_parser
from transferia_tpu.stats import trace
from transferia_tpu.stats.registry import Metrics, SourceStats
from transferia_tpu.stats.watermark import POLL_PREFIX, WATERMARKS

logger = logging.getLogger(__name__)


class Sequencer:
    """Tracks in-flight fetched units per (topic, partition); yields the
    highest offset safe to commit once pushes confirm (kafka/source.go
    sequencer: out-of-order acks must not commit past an unacked
    message).

    A unit is the range of one fetched batch, recorded by its first and
    last offset: its offsets ascend, with gaps allowed (compaction,
    transaction markers), and the units of a partition do not overlap.
    Start and ack cost O(1) a unit whatever the depth in flight; a unit
    is acked whole."""

    def __init__(self):
        self._lock = threading.Lock()
        # partition -> in-flight units [first, last, acked], oldest first
        self._inflight: dict[tuple[str, int], deque[list]] = {}
        # (topic, partition, a unit's first offset) -> its entry above
        self._by_first: dict[tuple[str, int, int], list] = {}

    def start_processing(self, topic: str, partition: int,
                         first_offset: int, last_offset: int) -> None:
        entry = [first_offset, last_offset, False]
        with self._lock:
            self._inflight.setdefault((topic, partition),
                                      deque()).append(entry)
            self._by_first[(topic, partition, first_offset)] = entry

    def inflight_units(self, topic: str, partition: int) -> int:
        with self._lock:
            return len(self._inflight.get((topic, partition), ()))

    def ack(self, topic: str, partition: int,
            first_offset: int) -> Optional[int]:
        """Mark the unit that starts at `first_offset` done; return the
        new committable high-water mark (the largest offset with no
        unacked predecessors), or None.  A unit never started, or acked
        already, changes nothing."""
        with self._lock:
            entry = self._by_first.pop((topic, partition, first_offset),
                                       None)
            if entry is None:
                return None
            entry[2] = True
            units = self._inflight[(topic, partition)]
            commit = None
            while units and units[0][2]:
                commit = units.popleft()[1]
            return commit


@dataclass
class FetchedBatch:
    topic: str
    partition: int
    messages: list[Message]

    @property
    def first_offset(self) -> int:
        return self.messages[0].offset

    @property
    def last_offset(self) -> int:
        return self.messages[-1].offset


def pump_checkpoint(fb: FetchedBatch,
                    stats: Optional[SourceStats] = None,
                    transfer_id: str = "") -> None:
    """Per-fetched-batch pump bookkeeping, shared by every replication
    pump over a fetch/commit client (the QueueSource below and the
    MVCC activation pump, mvcc/pump.py): the `replication.pump`
    failpoint — a kill between fetch and enqueue, which the resuming
    pump must absorb by restarting from its last committed/admitted
    offset — plus the trace instant and source counters."""
    failpoint("replication.pump")
    trace.instant("replication_pump", topic=fb.topic,
                  partition=fb.partition,
                  messages=len(fb.messages))
    if stats is not None:
        stats.changeitems.inc(len(fb.messages))
        stats.read_bytes.inc(sum(len(m.value) for m in fb.messages))
    if transfer_id:
        # poll watermark: the newest broker write time seen for this
        # partition — the stand-in event time for batches whose
        # parser drops it
        wm = max((m.write_time_ns for m in fb.messages), default=0)
        if wm:
            WATERMARKS.advance(
                transfer_id, f"{POLL_PREFIX}{fb.topic}:{fb.partition}",
                event_ns=wm, origin="poll")


class QueueSource(Source):
    """Generic replication source over a fetch/commit client.

    client contract:
      fetch(max_messages) -> list[FetchedBatch] (blocking up to poll
        timeout; each batch non-empty, its offsets ascending)
      commit(topic, partition, offset) -> None
      close() -> None
    """

    def __init__(self, client, parser_config, parallelism: int = 4,
                 metrics: Optional[Metrics] = None,
                 stop_poll: float = 0.2, transfer_id: str = ""):
        self.client = client
        self.parser: Parser = make_parser(parser_config) \
            if parser_config else make_parser({"blank": {}})
        self.parallelism = parallelism
        self.stats = SourceStats(metrics or Metrics())
        self.sequencer = Sequencer()
        self._stop = threading.Event()
        self.stop_poll = stop_poll
        self.transfer_id = transfer_id

    def run(self, sink: AsyncSink) -> None:
        def parse(fb: FetchedBatch):
            t0 = time.monotonic()
            result = self.parser.do_batch(fb.messages)
            self.stats.decode_time.observe(time.monotonic() - t0)
            self.stats.parsed_rows.inc(result.row_count())
            if result.unparsed is not None:
                self.stats.unparsed_rows.inc(result.unparsed.n_rows)
            batches = list(result.batches)
            if result.unparsed is not None:
                batches.append(result.unparsed)
            return batches

        def ack(fb: FetchedBatch, err: Optional[BaseException]):
            if err is not None:
                return  # failure latches in the parsequeue; no commit
            with trace.span("source_ack") as sp:
                if sp:
                    sp.add(inflight_units=self.sequencer.inflight_units(
                        fb.topic, fb.partition))
                commit = self.sequencer.ack(fb.topic, fb.partition,
                                            fb.first_offset)
                if commit is not None:
                    self.client.commit(fb.topic, fb.partition, commit)

        pq = ParseQueue(self.parallelism, sink, parse, ack)
        try:
            while not self._stop.is_set():
                if pq.failure is not None:
                    raise pq.failure
                fetched = self.client.fetch(max_messages=1024)
                if not fetched:
                    self._stop.wait(self.stop_poll)
                    continue
                for fb in fetched:
                    pump_checkpoint(fb, self.stats, self.transfer_id)
                    self.sequencer.start_processing(
                        fb.topic, fb.partition,
                        fb.first_offset, fb.last_offset)
                    pq.add(fb)
            pq.wait()
            if pq.failure is not None:
                raise pq.failure
        finally:
            pq.close()
            self.client.close()

    def stop(self) -> None:
        self._stop.set()
