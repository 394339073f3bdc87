"""Kafka source/sink over the wire client.

The source composes the shared QueueSource machinery (sequencer +
parsequeue + post-push commits); offsets checkpoint through the transfer
coordinator (kafka/source.go commits after push :251 — at-least-once).
The sink serializes batches and produces per partition, reusing the
column-hash partitioner when configured.
"""

from __future__ import annotations

import logging
import threading
from collections.abc import Sequence
from dataclasses import dataclass, field
from typing import Optional

import numpy as np

from transferia_tpu.abstract.commit import StagedSinker
from transferia_tpu.abstract.interfaces import Batch, Sinker, is_columnar
from transferia_tpu.coordinator.interface import Coordinator
from transferia_tpu.models.endpoint import EndpointParams, register_endpoint
from transferia_tpu.parsers import Message
from transferia_tpu.providers.kafka.client import KafkaClient, KafkaError
from transferia_tpu.native import lib as native_lib
from transferia_tpu.providers.kafka.protocol import (
    Record,
    RecordSection,
    crc32c,
    frame_messages,
    payload_bytes,
)
from transferia_tpu.providers.queue_common import FetchedBatch, QueueSource
from transferia_tpu.providers.registry import (
    Provider,
    TestResult,
    register_provider,
)
from transferia_tpu.serializers import make_queue_serializer
from transferia_tpu.serializers.formats import MessageBlock
from transferia_tpu.stats import trace
from transferia_tpu.transform.plugins.sharder import hash_column_to_shards

logger = logging.getLogger(__name__)


@register_endpoint
@dataclass
class KafkaSourceParams(EndpointParams):
    PROVIDER = "kafka"
    IS_SOURCE = True
    # queue sources cannot be re-read from scratch: reupload
    # is forbidden (model/endpoint.go AppendOnlySource)
    is_append_only = True

    brokers: list[str] = field(default_factory=lambda: ["localhost:9092"])
    topic: str = ""
    parser: Optional[dict] = None
    parallelism: int = 4
    max_bytes_per_fetch: int = 8 << 20
    start_from: str = "earliest"   # earliest | latest
    # -- security (reference: franz-go auth in pkg/providers/kafka/writer/)
    tls: bool = False
    tls_ca: str = ""              # CA bundle path (custom/self-signed)
    tls_verify: bool = True
    sasl_mechanism: str = ""      # PLAIN | SCRAM-SHA-256 | SCRAM-SHA-512
    sasl_username: str = ""
    sasl_password: str = ""

    def __post_init__(self):
        if self.start_from not in ("earliest", "latest"):
            # a typo silently meaning "latest" would skip all existing data
            raise ValueError(
                f"kafka start_from must be 'earliest' or 'latest', "
                f"got {self.start_from!r}"
            )

    def parser_config(self):
        return self.parser


@register_endpoint
@dataclass
class KafkaTargetParams(EndpointParams):
    PROVIDER = "kafka"
    IS_TARGET = True

    brokers: list[str] = field(default_factory=lambda: ["localhost:9092"])
    topic: str = ""               # "" -> per-table "<ns>.<name>"
    serializer: str = "json"
    serializer_config: dict = field(default_factory=dict)
    partition_by: str = ""
    compression: str = ""         # "" | gzip
    # -- security (reference: franz-go auth in pkg/providers/kafka/writer/)
    tls: bool = False
    tls_ca: str = ""              # CA bundle path (custom/self-signed)
    tls_verify: bool = True
    sasl_mechanism: str = ""      # PLAIN | SCRAM-SHA-256 | SCRAM-SHA-512
    sasl_username: str = ""
    sasl_password: str = ""


def _make_client(params) -> KafkaClient:
    return KafkaClient(
        params.brokers,
        tls=getattr(params, "tls", False),
        tls_ca=getattr(params, "tls_ca", ""),
        tls_verify=getattr(params, "tls_verify", True),
        sasl_mechanism=getattr(params, "sasl_mechanism", ""),
        sasl_username=getattr(params, "sasl_username", ""),
        sasl_password=getattr(params, "sasl_password", ""),
    )


class _KafkaQueueClient:
    """QueueSource client contract over KafkaClient with coordinator-backed
    offset checkpoints (state key kafka_offsets).

    A response holds what `max_bytes_per_fetch` buys (some 73,000 messages
    of 100 bytes), `fetch` hands out `max_messages` a partition: the rest
    stays in a per-partition remainder, scanned once (a
    `protocol.RecordView`: the blob and its index), and the broker is
    asked only for partitions that have none.  `positions[p]` is the offset
    behind the last record HANDED OUT; a remainder holds only records at
    or above it, contiguous with it, so dropping one (close, a fetch
    error, anything that moves `positions[p]`) loses nothing: the records
    are fetched again from `positions[p]`, and a restarted client starts
    at committed + 1."""

    STATE_KEY = "kafka_offsets"

    # one lock for ALL clients of a process: the partitioned strategy runs
    # one client per partition against the same transfer-state blob, and a
    # per-instance lock would let concurrent read-modify-writes lose
    # another partition's committed offset
    _commit_lock = threading.Lock()

    def __init__(self, params: KafkaSourceParams, transfer_id: str,
                 coordinator: Optional[Coordinator],
                 partitions: Optional[list[int]] = None):
        """partitions: restrict to a subset (the partitioned replication
        strategy runs one client per partition)."""
        self.params = params
        self.transfer_id = transfer_id
        self.cp = coordinator
        self.client = _make_client(params)
        meta = self.client.metadata([params.topic])
        all_partitions = meta.get(params.topic)
        if not all_partitions:
            raise KafkaError(f"topic {params.topic!r} not found")
        if partitions is not None:
            all_partitions = [p for p in all_partitions
                              if p in set(partitions)]
        partitions = all_partitions
        saved = {}
        if self.cp is not None:
            saved = self.cp.get_transfer_state(transfer_id).get(
                self.STATE_KEY, {}
            )
        self.positions: dict[int, int] = {}
        self._remainders: dict[int, Sequence[Record]] = {}
        for p in partitions:
            key = f"{params.topic}:{p}"
            if key in saved:
                self.positions[p] = int(saved[key]) + 1
            else:
                ts = -2 if params.start_from == "earliest" else -1
                self.positions[p] = self.client.list_offsets(
                    params.topic, p, ts
                )

    def held_bytes(self) -> int:
        """Payload bytes decoded and not handed out yet, all partitions."""
        return sum(payload_bytes(r) for r in self._remainders.values())

    def _refill(self) -> set[int]:
        """Ask the broker for the partitions with nothing left to hand
        out; returns the partitions asked.  One multi-partition Fetch per
        leader (not one round-trip per partition: a 64-partition fan-in
        would pay 64 RTTs per cycle)."""
        dry = {p: pos for p, pos in self.positions.items()
               if p not in self._remainders}
        if not dry:
            return set()
        # what is held and what is asked for stay within
        # max_bytes_per_fetch together, so memory is that plus the one
        # response in hand (a broker sends a first batch of any size)
        room = self.params.max_bytes_per_fetch - self.held_bytes()
        if room <= 0:
            return set()
        try:
            fetched = self.client.fetch_multi(
                self.params.topic, dry, max_bytes=room,
                # the long poll is for a client with nothing to hand
                # out: it would stall the others' remainders
                max_wait_ms=0 if self._remainders else 250,
            )
        except KafkaError:
            self._remainders.clear()
            raise
        for p, (records, _high) in fetched.items():
            if len(records):
                self._remainders[p] = records
        return set(dry)

    def fetch(self, max_messages: int = 1024) -> list[FetchedBatch]:
        asked = self._refill()
        out = []
        for p in sorted(self._remainders):
            rem = self._remainders.pop(p)
            records = rem[:max_messages]
            if len(rem) > max_messages:
                self._remainders[p] = rem[max_messages:]
            self.positions[p] = records[-1].offset + 1
            trace.TELEMETRY.record_kafka_handout(buffered=p not in asked)
            out.append(FetchedBatch(
                self.params.topic, p,
                [
                    Message(
                        value=r.value or b"", key=r.key or b"",
                        topic=self.params.topic, partition=p,
                        offset=r.offset,
                        write_time_ns=r.timestamp_ms * 1_000_000,
                        headers=tuple(r.headers),
                    )
                    for r in records
                ],
            ))
        return out

    def commit(self, topic: str, partition: int, offset: int) -> None:
        if self.cp is None:
            return
        with _KafkaQueueClient._commit_lock:
            state = self.cp.get_transfer_state(self.transfer_id).get(
                self.STATE_KEY, {}
            )
            state[f"{topic}:{partition}"] = offset
            self.cp.set_transfer_state(
                self.transfer_id, {self.STATE_KEY: state}
            )

    def close(self) -> None:
        self._remainders.clear()
        self.client.close()


def topic_partitions(params: KafkaSourceParams) -> list[int]:
    """Partition ids of the source topic (partitioned strategy fan-out)."""
    client = _make_client(params)
    try:
        meta = client.metadata([params.topic])
        return sorted(meta.get(params.topic) or [])
    finally:
        client.close()


class KafkaSinker(Sinker, StagedSinker):
    """Produce sink; staged-commit capable (abstract/commit.py): with an
    open part stage the serialized messages buffer sink-side and land in
    the broker through ONE transactional produce tied to the part's
    epoch-keyed transactional id (`trtpu.<part slug>`) — kafka's own
    KIP-98 producer fencing rejects a zombie (its InitProducerId /
    produce with the stale epoch fails PRODUCER_FENCED, surfaced as
    StaleEpochPublishError), and a republish under the same
    transactional id SUPERSEDES the previous publish instead of
    appending duplicates.

    Protocol bound: this speaks the KIP-98 SUBSET the in-repo fake
    broker implements — one transactional Produce request = one
    committed transaction, with broker-side supersede-in-place of the
    id's previous publish.  A full Apache Kafka deployment additionally
    needs AddPartitionsToTxn/EndTxn + commit markers and read_committed
    consumers (its log is append-only: the republish-supersede there
    would ride transaction aborts, not segment rewrite); until then
    the exactly-once claim holds for the fake-backed wire, and real
    brokers should keep the at-least-once path."""

    def __init__(self, params: KafkaTargetParams, snapshot: bool = False,
                 source=None):
        """snapshot: this sink takes the rows of an initial load (the
        provider's `snapshot_sinker`): the Debezium serializer marks
        them `op: "r"`, `source.snapshot: "true"`, as Debezium's
        snapshot.mode=initial does.  source: the transfer's source
        endpoint, for the envelope's `source` block."""
        self.params = params
        self.client = _make_client(params)
        cfg = dict(params.serializer_config or {})
        if params.serializer == "debezium":
            if source is not None and source.provider() == "mysql":
                # what Debezium's MySQL connector says of itself
                cfg.setdefault("connector", "mysql")
                cfg.setdefault("source_db_type", source.database)
            if params.topic:
                # single-topic sinks: SR subjects must derive from the
                # real topic (TopicNameStrategy)
                cfg.setdefault("topic", params.topic)
            if snapshot:
                cfg.setdefault("snapshot", True)
        self._null_key_turn = 0
        self.serializer = make_queue_serializer(params.serializer, **cfg)
        self._partitions: dict[str, list[int]] = {}
        self._stage = None  # staging.PartStage when open
        self._stage_key = ""
        # per (topic, partition): the part's records, framed at push
        self._staged: dict[tuple[str, int], RecordSection] = {}

    def _topic_partitions(self, topic: str) -> list[int]:
        if topic not in self._partitions:
            meta = self.client.metadata([topic])
            self._partitions[topic] = meta.get(topic) or [0]
        return self._partitions[topic]

    def _partition_of(self, batch: Batch, block: MessageBlock,
                      n_parts: int) -> np.ndarray:
        """Each message's partition index: the configured column's hash
        where the batch gave one message a row, else crc32c(key) % n_parts
        over the block's key buffer as it is, batched through the native
        lib when present (Kafka's own default partitioner hashes the key
        with murmur2: a key's partition here is stable, not the one a Java
        producer would pick; a null key among keys hashes as empty).  A
        batch of null keys (a table without a primary key) is dealt round
        the partitions in turn, as Kafka's partitioner spreads records
        without a key."""
        by = self.params.partition_by
        if is_columnar(batch) and by and by in batch.columns and \
                block.n == batch.n_rows:
            return np.asarray(hash_column_to_shards(batch.column(by),
                                                    n_parts))
        if block.keys is None:
            turn = self._null_key_turn
            self._null_key_turn = (turn + block.n) % n_parts
            return (np.arange(block.n, dtype=np.int64) + turn) % n_parts
        # deterministic key hash (crc32c): built-in hash() is randomized
        # per process and would break per-key partition affinity across
        # restarts
        cdll = native_lib()
        if cdll is None:
            offs = block.key_offsets.tolist()
            return np.array([crc32c(block.keys[lo:hi])
                             for lo, hi in zip(offs, offs[1:])],
                            dtype=np.int64) % n_parts
        data = np.frombuffer(block.keys, dtype=np.uint8)
        out = np.empty(block.n, dtype=np.uint32)
        cdll.crc32c_batch(data if data.size else np.zeros(1, np.uint8),
                          block.key_offsets, block.n, out)
        return out % n_parts

    def _frame(self, batch: Batch,
               sections: dict[tuple[str, int], RecordSection]) -> None:
        """Serialize one batch and frame its records at push into the
        per-(topic, partition) sections, each partition's offset deltas
        going on from its records so far: a renderer's block as it is,
        other serializers' pairs laid into one first."""
        out = self.serializer.serialize_block(batch)
        rendered = isinstance(out, MessageBlock)
        if not rendered:
            if not out:
                return
            out = MessageBlock.from_pairs(out)
        block = out
        if is_columnar(batch):
            topic = self.params.topic or str(batch.table_id)
        else:
            rows = list(batch)
            topic = self.params.topic or (
                str(rows[0].table_id) if rows else "controls"
            )
        partitions = self._topic_partitions(topic)
        part_idx = self._partition_of(batch, block, len(partitions))
        order = np.argsort(part_idx, kind="stable")
        groups = []
        lo = 0
        for i, count in enumerate(np.bincount(
                part_idx, minlength=len(partitions)).tolist()):
            if count:
                section = sections.setdefault((topic, partitions[i]),
                                              RecordSection())
                groups.append((section, order[lo:lo + count]))
                lo += count
        framed = frame_messages(block, [(rows, section.count)
                                        for section, rows in groups])
        for (section, rows), buf in zip(groups, framed):
            section.add(buf, len(rows))
        trace.TELEMETRY.record_kafka_framed(block.n, rendered)

    def push(self, batch: Batch) -> None:
        if self._stage is not None:
            batch = self._stage.stage(batch)
            try:
                self._frame(batch, self._staged)
            except BaseException:
                # serialization died after the dedup window recorded
                # the batch: only a full part restage is safe
                self._stage.mark_failed()
                raise
            return
        sections: dict[tuple[str, int], RecordSection] = {}
        self._frame(batch, sections)
        for (topic, p), section in sections.items():
            self.client.produce(
                topic, p, section,
                compression=getattr(self.params, "compression", ""))

    # -- StagedSinker (publish = one kafka transaction) ---------------------
    def begin_part(self, key: str, epoch: int) -> None:
        from transferia_tpu.providers.staging import PartStage

        # hold=False: the serialized record buffer is the stage; the
        # PartStage only runs the dedup window over the pushed batches
        self._stage = PartStage(key, epoch, hold=False)
        self._stage_key = key
        self._staged = {}

    def publish_part(self, key: str, epoch: int) -> int:
        from transferia_tpu.abstract.errors import StaleEpochPublishError
        from transferia_tpu.chaos.failpoints import failpoint
        from transferia_tpu.providers.kafka.client import (
            is_producer_fenced,
        )
        from transferia_tpu.providers.staging import part_slug, \
            publish_guard

        stage = self._stage
        if stage is None or self._stage_key != key:
            raise RuntimeError(f"kafka sink: no open stage for {key!r}")
        with publish_guard(key, epoch):
            txn_id = f"trtpu.{part_slug(key)}"
            trace.instant("kafka_publish_txn", part=key, epoch=epoch,
                          rows=stage.rows)
            failpoint("sink.kafka.publish")
            try:
                pid, accepted = self.client.init_producer(txn_id, epoch)
                n = self.client.txn_produce(txn_id, pid, accepted,
                                            self._staged)
            except KafkaError as e:
                if is_producer_fenced(e):
                    # KIP-98 zombie fencing IS the sink-side epoch
                    # fence: a newer owner holds the transactional id.
                    # Brokers that don't disclose the winning epoch
                    # (real ones return -1) get the epoch+1 lower bound
                    won = getattr(e, "fence_epoch", None)
                    raise StaleEpochPublishError(
                        key, epoch,
                        won if won is not None else epoch + 1) from e
                raise
            self.last_dedup_dropped = stage.dedup_dropped
            rows = stage.rows
        self._stage = None
        self._stage_key = ""
        self._staged = {}
        return rows

    def abort_part(self, key: str) -> None:
        self._stage = None
        self._stage_key = ""
        self._staged = {}

    def note_push_retry(self) -> None:
        if self._stage is not None:
            self._stage.note_push_retry()

    def close(self) -> None:
        self.client.close()


@register_provider
class KafkaProvider(Provider):
    NAME = "kafka"

    def source(self):
        if isinstance(self.transfer.src, KafkaSourceParams):
            p = self.transfer.src
            client = _KafkaQueueClient(p, self.transfer.id,
                                       self.coordinator)
            return QueueSource(client, p.parser,
                               parallelism=p.parallelism,
                               metrics=self.metrics,
                               transfer_id=self.transfer.id)
        return None

    def sinker(self):
        if isinstance(self.transfer.dst, KafkaTargetParams):
            return KafkaSinker(self.transfer.dst, source=self.transfer.src)
        return None

    def snapshot_sinker(self):
        if isinstance(self.transfer.dst, KafkaTargetParams):
            return KafkaSinker(self.transfer.dst, snapshot=True,
                               source=self.transfer.src)
        return None

    def test(self) -> TestResult:
        result = TestResult(ok=True)
        params = self.transfer.src if isinstance(
            self.transfer.src, KafkaSourceParams) else self.transfer.dst
        try:
            client = _make_client(params)
            client.metadata()
            client.close()
            result.add("metadata")
        except Exception as e:
            result.add("metadata", e)
        return result
