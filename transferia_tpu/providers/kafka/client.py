"""Minimal Kafka broker client: Metadata, Produce, Fetch, ListOffsets.

Request framing: int32 size + apiKey(2) apiVersion(2) correlationId(4)
clientId(STRING) + body.  API versions used are old-but-universally-
supported non-flexible ones (Metadata v1, Produce v3, Fetch v4,
ListOffsets v1) so the codec stays simple and works against any broker
>= 0.11 as well as compatibility layers (Redpanda, the test fake).

Partition leadership: Metadata responses populate a node table and a
(topic, partition) -> leader map; produce/fetch/list_offsets route to the
partition leader and refresh metadata + retry once on NOT_LEADER or
connection failures, so multi-broker clusters work, not just the
single-broker case.
"""

from __future__ import annotations

import bisect
import logging
import operator
import socket
import struct
import threading
import time
from collections.abc import Sequence
from typing import Optional

from transferia_tpu.abstract.errors import CategorizedError
from transferia_tpu.providers.kafka.protocol import (
    Reader,
    Record,
    RecordSection,
    batch_header,
    decode_record_batches,
    enc_bytes,
    enc_str,
    encode_record_batch,
    scan_record_batches,
)
from transferia_tpu.utils.net import recv_exact, send_pieces

logger = logging.getLogger(__name__)

API_PRODUCE = 0
API_FETCH = 1
API_LIST_OFFSETS = 2
API_METADATA = 3
API_INIT_PRODUCER_ID = 22

ERR_NONE = 0
ERR_OFFSET_OUT_OF_RANGE = 1
ERR_UNKNOWN_TOPIC = 3
ERR_LEADER_NOT_AVAILABLE = 5
ERR_NOT_LEADER = 6
ERR_INVALID_PRODUCER_EPOCH = 47
ERR_PRODUCER_FENCED = 90

_RETRIABLE = {ERR_LEADER_NOT_AVAILABLE, ERR_NOT_LEADER}
_record_offset = operator.attrgetter("offset")
_FENCED = {ERR_INVALID_PRODUCER_EPOCH, ERR_PRODUCER_FENCED}


def is_producer_fenced(err: "KafkaError") -> bool:
    """True when the broker rejected a transactional operation because
    a NEWER producer epoch owns the transactional id (KIP-98 zombie
    fencing) — the staged-commit publish maps this onto
    StaleEpochPublishError."""
    return err.code in _FENCED


class KafkaError(CategorizedError):
    def __init__(self, message: str, code: int = -1):
        super().__init__(CategorizedError.SOURCE, message)
        self.code = code


class KafkaClient:
    def __init__(self, brokers: list[str], client_id: str = "transferia-tpu",
                 timeout: float = 30.0, tls: bool = False,
                 tls_ca: str = "", tls_verify: bool = True,
                 sasl_mechanism: str = "", sasl_username: str = "",
                 sasl_password: str = ""):
        self.bootstrap = brokers
        self.client_id = client_id
        self.timeout = timeout
        self.tls = tls
        self.tls_ca = tls_ca
        self.tls_verify = tls_verify
        self.sasl_mechanism = sasl_mechanism.upper()
        self.sasl_username = sasl_username
        self.sasl_password = sasl_password
        if self.sasl_mechanism not in (
                "", "PLAIN", "SCRAM-SHA-256", "SCRAM-SHA-512"):
            raise KafkaError(
                f"unsupported sasl mechanism {sasl_mechanism!r}")
        self._conns: dict[object, socket.socket] = {}  # node_id | "boot"
        self._nodes: dict[int, tuple[str, int]] = {}
        self._leaders: dict[tuple[str, int], int] = {}
        self._corr = 0
        self._lock = threading.Lock()

    # -- connections --------------------------------------------------------
    def _dial(self, host: str, port: int) -> socket.socket:
        s = socket.create_connection((host, port), timeout=self.timeout)
        s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        if self.tls:
            import ssl

            ctx = ssl.create_default_context()
            if self.tls_ca:
                ctx.load_verify_locations(self.tls_ca)
            if not self.tls_verify:
                ctx.check_hostname = False
                ctx.verify_mode = ssl.CERT_NONE
            s = ctx.wrap_socket(s, server_hostname=host)
        if self.sasl_mechanism:
            self._sasl_authenticate(s)
        return s

    # -- SASL (SaslHandshake v1 + SaslAuthenticate v1 frames) ---------------
    def _raw_roundtrip(self, sock: socket.socket, api_key: int,
                       api_version: int, body: bytes) -> Reader:
        # only reached from _conn_for(), i.e. under self._lock
        self._corr += 1  # trtpu: ignore[LCK001]
        corr = self._corr
        header = struct.pack("!hhi", api_key, api_version, corr) \
            + enc_str(self.client_id)
        msg = header + body
        sock.sendall(struct.pack("!i", len(msg)) + msg)
        size = struct.unpack("!i", recv_exact(sock, 4))[0]
        r = Reader(recv_exact(sock, size))
        if r.i32() != corr:
            raise KafkaError("sasl correlation mismatch")
        return r

    def _sasl_round(self, sock: socket.socket, data: bytes) -> bytes:
        r = self._raw_roundtrip(sock, 36, 1, enc_bytes(data))
        err = r.i16()
        err_msg = r.string()
        auth = r.bytes_()
        if err:
            raise KafkaError(
                f"sasl authentication failed: {err_msg or err}", err)
        return auth or b""

    def _sasl_authenticate(self, sock: socket.socket) -> None:
        r = self._raw_roundtrip(
            sock, 17, 1, enc_str(self.sasl_mechanism))
        err = r.i16()
        if err:
            n = r.i32()
            offered = [r.string() for _ in range(max(0, n))]
            raise KafkaError(
                f"broker rejected mechanism {self.sasl_mechanism} "
                f"(offers {offered})", err)
        if self.sasl_mechanism == "PLAIN":
            token = (b"\x00" + self.sasl_username.encode()
                     + b"\x00" + self.sasl_password.encode())
            self._sasl_round(sock, token)
            return
        from transferia_tpu.utils.scram import ScramError, client_exchange

        try:
            client_exchange(
                self.sasl_mechanism, self.sasl_username,
                self.sasl_password,
                lambda msg: self._sasl_round(sock, msg),
            )
        except ScramError as e:
            raise KafkaError(f"sasl scram failed: {e}") from e

    def _conn_for(self, node) -> socket.socket:
        sock = self._conns.get(node)
        if sock is not None:
            return sock
        if node == "boot":
            last: Optional[Exception] = None
            for b in self.bootstrap:
                host, _, port = b.partition(":")
                try:
                    sock = self._dial(host, int(port or 9092))
                    break
                except OSError as e:
                    last = e
                    sock = None
            if sock is None:
                raise KafkaError(f"no kafka broker reachable: {last}")
        else:
            addr = self._nodes.get(node)
            if addr is None:
                raise KafkaError(f"unknown broker node {node}")
            try:
                sock = self._dial(*addr)
            except OSError as e:
                raise KafkaError(
                    f"broker node {node} {addr} unreachable: {e}"
                ) from e
        self._conns[node] = sock
        return sock

    def _drop_conn(self, node) -> None:
        sock = self._conns.pop(node, None)
        if sock is not None:
            sock.close()

    def close(self) -> None:
        with self._lock:
            for node in list(self._conns):
                self._drop_conn(node)

    def _roundtrip(self, api_key: int, api_version: int, body,
                   node="boot", span: str = "kafka_roundtrip",
                   **span_args) -> Reader:
        """One request and its response.  `body` is bytes, or a list of
        buffers that go out in order as they are (a transactional
        Produce's record sections: no join).  `span` names the span it is
        recorded as: a Produce is the sink's write (`sink_push` with
        `direction="kafka_produce"`, its `bytes` the request's), every
        other call `kafka_roundtrip` (its `bytes` the response's)."""
        from transferia_tpu.chaos.failpoints import failpoint
        from transferia_tpu.stats import trace

        pieces = [body] if isinstance(body, bytes) else body
        body_len = sum(map(len, pieces))
        failpoint("client.kafka.roundtrip")  # before the lock: may sleep
        with trace.span(span, api=api_key, **span_args) as sp, self._lock:
            sock = self._conn_for(node)
            self._corr += 1
            corr = self._corr
            header = struct.pack("!hhi", api_key, api_version, corr) \
                + enc_str(self.client_id)
            # I/O under self._lock is the design: the lock serializes
            # request/response framing on the single broker socket
            try:
                send_pieces(  # trtpu: ignore[LCK001]
                    sock, [struct.pack("!i", len(header) + body_len)
                           + header, *pieces])
                t_sent = time.perf_counter() if sp else 0.0
                size = struct.unpack(
                    "!i", recv_exact(sock, 4))[0]  # trtpu: ignore[LCK001]
                if sp:
                    # to the first response byte: the broker's long
                    # poll, apart from reading the response
                    sp.add(wait_s=round(time.perf_counter() - t_sent, 6),
                           **({"bytes": size} if "bytes" not in span_args
                              else {"response_bytes": size}))
                payload = recv_exact(sock, size)  # trtpu: ignore[LCK001]
            except (OSError, ConnectionError) as e:
                self._drop_conn(node)
                raise KafkaError(f"kafka io error (node {node}): {e}") from e
        r = Reader(payload)
        got_corr = r.i32()
        if got_corr != corr:
            with self._lock:
                self._drop_conn(node)
            raise KafkaError(
                f"correlation mismatch: {got_corr} != {corr}"
            )
        return r

    # -- metadata -----------------------------------------------------------
    def metadata(self, topics: Optional[list[str]] = None) -> dict:
        """topic -> [partition ids]; refreshes node + leader maps."""
        if topics is None:
            body = struct.pack("!i", -1)
        else:
            body = struct.pack("!i", len(topics))
            for t in topics:
                body += enc_str(t)
        r = self._roundtrip(API_METADATA, 1, body)
        with self._lock:
            for _ in range(r.i32()):
                node_id = r.i32()
                host = r.string()
                port = r.i32()
                r.string()       # rack
                self._nodes[node_id] = (host or "", port)
            r.i32()              # controller id
            n_topics = r.i32()
            out: dict[str, list[int]] = {}
            for _ in range(n_topics):
                err = r.i16()
                name = r.string()
                r.i8()           # is_internal
                parts = []
                for _ in range(r.i32()):
                    r.i16()      # partition error
                    pid = r.i32()
                    leader = r.i32()
                    for _ in range(r.i32()):
                        r.i32()  # replicas
                    for _ in range(r.i32()):
                        r.i32()  # isr
                    parts.append(pid)
                    if name is not None:
                        self._leaders[(name, pid)] = leader
                if err == ERR_NONE and name is not None:
                    out[name] = sorted(parts)
        return out

    def _leader_node(self, topic: str, partition: int):
        leader = self._leaders.get((topic, partition))
        if leader is None or leader not in self._nodes:
            self.metadata([topic])
            leader = self._leaders.get((topic, partition))
        # fall back to bootstrap when metadata gave nothing (test fakes
        # reporting no broker list still answer everything themselves)
        return leader if leader is not None and leader in self._nodes \
            else "boot"

    def _routed(self, topic: str, partition: int, api: int, version: int,
                body: bytes, **span_args) -> Reader:
        """Round-trip to the partition leader; one metadata-refresh retry
        on routing errors."""
        node = self._leader_node(topic, partition)
        try:
            return self._roundtrip(api, version, body, node, **span_args)
        except KafkaError:
            self.metadata([topic])
            node = self._leader_node(topic, partition)
            return self._roundtrip(api, version, body, node, **span_args)

    # -- produce ------------------------------------------------------------
    def produce(self, topic: str, partition: int,
                records, acks: int = -1,
                timeout_ms: int = 30_000, compression: str = "") -> int:
        """Append records (a list of Records, or a RecordSection the sink
        framed); returns the base offset assigned (Produce v3)."""
        from transferia_tpu.stats import trace

        n = records.count if isinstance(records, RecordSection) \
            else len(records)
        with trace.span("kafka_encode") as sp:
            batch = encode_record_batch(records, compression=compression)
            if sp:
                sp.add(records=n, bytes=len(batch))
        body = enc_str(None)                      # transactional id
        body += struct.pack("!hi", acks, timeout_ms)
        body += struct.pack("!i", 1) + enc_str(topic)
        body += struct.pack("!i", 1) + struct.pack("!i", partition)
        body += enc_bytes(batch)

        def attempt() -> int:
            r = self._routed(topic, partition, API_PRODUCE, 3, body,
                             span="sink_push", direction="kafka_produce",
                             bytes=len(body), records=n,
                             partitions=1)
            base_offset = -1
            for _ in range(r.i32()):
                r.string()
                for _ in range(r.i32()):
                    r.i32()              # partition
                    err = r.i16()
                    base_offset = r.i64()
                    r.i64()              # log append time
                    if err != ERR_NONE:
                        raise KafkaError(f"produce failed: error {err}",
                                         code=err)
            r.i32()  # throttle
            return base_offset

        try:
            return attempt()
        except KafkaError as e:
            if e.code not in _RETRIABLE:
                raise
            self.metadata([topic])
            return attempt()

    # -- transactions (KIP-98 subset) ----------------------------------------
    def init_producer(self, transactional_id: str,
                      producer_epoch: int) -> tuple[int, int]:
        """InitProducerId for an epoch-keyed transactional id.

        KIP-360 shape: the client proposes its producer epoch (here the
        part's assignment epoch — monotone per part key) and the broker
        fences a proposal OLDER than the id's current epoch with
        PRODUCER_FENCED, which is exactly the zombie-publish fence.
        Returns (producer_id, accepted_epoch)."""
        body = enc_str(transactional_id)
        body += struct.pack("!i", 60_000)           # txn timeout
        body += struct.pack("!qh", -1, producer_epoch)
        r = self._roundtrip(API_INIT_PRODUCER_ID, 3, body)
        r.i32()  # throttle
        err = r.i16()
        pid = r.i64()
        epoch = r.i16()
        if err != ERR_NONE:
            e = KafkaError(
                f"init_producer({transactional_id!r}) failed: "
                f"error {err}", code=err)
            # a fencing response carries the id's CURRENT epoch when
            # the broker discloses it (the in-repo fake does; real
            # brokers return -1) — the staged-commit publish maps it
            # onto StaleEpochPublishError's published_epoch
            e.fence_epoch = int(epoch) if epoch >= 0 else None
            raise e
        return pid, epoch

    def txn_produce(self, transactional_id: str, producer_id: int,
                    producer_epoch: int,
                    sections: dict[tuple[str, int], RecordSection],
                    acks: int = -1, timeout_ms: int = 30_000) -> int:
        """One transactional produce: every (topic, partition) record
        section lands in a single Produce request carrying the
        transactional id and producer-epoch-stamped batches — the
        broker applies it atomically and fences a stale epoch.
        Returns records produced."""
        by_topic: dict[str, list[tuple[int, RecordSection]]] = {}
        for (topic, partition), section in sorted(sections.items()):
            by_topic.setdefault(topic, []).append((partition, section))
        from transferia_tpu.stats import trace

        # the request as buffers: a batch's header, then its section's
        # buffers as the push framed them (a part's records are hundreds
        # of megabytes: nothing joins them)
        now = int(time.time() * 1000)
        head = enc_str(transactional_id) \
            + struct.pack("!hii", acks, timeout_ms, len(by_topic))
        body: list = []
        total = 0
        with trace.span("kafka_encode") as sp:
            for topic, parts in sorted(by_topic.items()):
                head += enc_str(topic) + struct.pack("!i", len(parts))
                for partition, section in parts:
                    header = batch_header(
                        section, now, now, producer_id=producer_id,
                        producer_epoch=producer_epoch)
                    body.append(head + struct.pack(
                        "!ii", partition, len(header) + section.nbytes)
                        + header)
                    body.extend(section.buffers)
                    head = b""
                    total += section.count
            if head:
                body.append(head)
            size = sum(map(len, body))
            if sp:
                sp.add(records=total, bytes=size)
        r = self._roundtrip(API_PRODUCE, 3, body, span="sink_push",
                            direction="kafka_produce", bytes=size,
                            records=total, partitions=len(sections))
        for _ in range(r.i32()):
            r.string()
            for _ in range(r.i32()):
                r.i32()              # partition
                err = r.i16()
                r.i64()              # base offset
                r.i64()              # log append time
                if err != ERR_NONE:
                    raise KafkaError(
                        f"transactional produce failed: error {err}",
                        code=err)
        r.i32()  # throttle
        return total

    # -- offsets ------------------------------------------------------------
    def list_offsets(self, topic: str, partition: int,
                     timestamp: int = -2) -> int:
        """-2 = earliest, -1 = latest (ListOffsets v1)."""
        body = struct.pack("!i", -1)              # replica id
        body += struct.pack("!i", 1) + enc_str(topic)
        body += struct.pack("!i", 1)
        body += struct.pack("!iq", partition, timestamp)
        r = self._routed(topic, partition, API_LIST_OFFSETS, 1, body)
        offset = 0
        for _ in range(r.i32()):
            r.string()
            for _ in range(r.i32()):
                r.i32()
                err = r.i16()
                r.i64()              # timestamp
                offset = r.i64()
                if err != ERR_NONE:
                    raise KafkaError(f"list_offsets failed: {err}",
                                     code=err)
        return offset

    # -- fetch --------------------------------------------------------------
    def fetch(self, topic: str, partition: int, offset: int,
              max_bytes: int = 8 << 20,
              max_wait_ms: int = 250) -> tuple[list[Record], int]:
        """Returns (records, high_watermark) from the given offset
        (Fetch v4)."""
        body = struct.pack("!iiii", -1, max_wait_ms, 1, max_bytes)
        body += b"\x00"                           # isolation level
        body += struct.pack("!i", 1) + enc_str(topic)
        body += struct.pack("!i", 1)
        body += struct.pack("!iqi", partition, offset, max_bytes)

        def attempt():
            r = self._routed(topic, partition, API_FETCH, 4, body)
            r.i32()  # throttle
            records: list[Record] = []
            high = 0
            for _ in range(r.i32()):
                r.string()
                for _ in range(r.i32()):
                    r.i32()              # partition
                    err = r.i16()
                    high = r.i64()
                    r.i64()              # last stable offset
                    for _ in range(r.i32()):
                        r.i64()          # aborted txn producer id
                        r.i64()          # first offset
                    blob = r.bytes_() or b""
                    if err == ERR_OFFSET_OUT_OF_RANGE:
                        raise KafkaError("offset out of range", code=err)
                    if err != ERR_NONE:
                        raise KafkaError(f"fetch failed: error {err}",
                                         code=err)
                    records.extend(decode_record_batches(blob))
            return records, high

        try:
            records, high = attempt()
        except KafkaError as e:
            if e.code not in _RETRIABLE:
                raise
            self.metadata([topic])
            records, high = attempt()
        # the broker may return records below the requested offset (batch
        # alignment); trim client-side
        return [rec for rec in records if rec.offset >= offset], high

    def fetch_multi(self, topic: str, offsets: dict[int, int],
                    max_bytes: int = 8 << 20, max_wait_ms: int = 250,
                    ) -> dict[int, tuple[Sequence[Record], int]]:
        """Fetch many partitions in few round-trips: partitions group by
        leader and each leader gets ONE Fetch request carrying all of its
        partitions (the wire format is multi-partition; issuing one
        request per partition costs n_partitions round-trips per poll
        cycle — the 64-partition fan-in killer).  Returns
        {partition: (records, high_watermark)}, the records a sequence
        that builds them when taken (`protocol.scan_record_batches`: a
        consumer takes part of a response at a time); per-partition
        retriable errors retry once through the single-partition path."""
        from transferia_tpu.stats import trace

        by_node: dict[object, list[int]] = {}
        for p in offsets:
            by_node.setdefault(self._leader_node(topic, p), []).append(p)
        out: dict[int, tuple[Sequence[Record], int]] = {}
        retry: list[int] = []
        self._fetch_rotation = getattr(self, "_fetch_rotation", 0) + 1
        for node, parts in by_node.items():
            # Rotate the partition order per request: brokers fill
            # partitions in request order until max_bytes runs out, so a
            # fixed order lets one backlogged low partition starve the
            # rest indefinitely (the KIP-74 fairness problem).
            parts = sorted(parts)
            rot = self._fetch_rotation % len(parts)
            parts = parts[rot:] + parts[:rot]
            body = struct.pack("!iiii", -1, max_wait_ms, 1, max_bytes)
            body += b"\x00"                       # isolation level
            body += struct.pack("!i", 1) + enc_str(topic)
            body += struct.pack("!i", len(parts))
            for p in parts:
                body += struct.pack("!iqi", p, offsets[p], max_bytes)
            try:
                r = self._roundtrip(API_FETCH, 4, body, node)
            except KafkaError:
                retry.extend(parts)
                continue
            r.i32()  # throttle
            for _ in range(r.i32()):
                r.string()
                for _ in range(r.i32()):
                    p = r.i32()
                    err = r.i16()
                    high = r.i64()
                    r.i64()              # last stable offset
                    for _ in range(r.i32()):
                        r.i64()          # aborted txn producer id
                        r.i64()          # first offset
                    blob = r.bytes_() or b""
                    if err == ERR_OFFSET_OUT_OF_RANGE:
                        raise KafkaError("offset out of range", code=err)
                    if err != ERR_NONE:
                        retry.append(p)
                        continue
                    off = offsets.get(p, 0)
                    recs = []
                    if blob:    # an empty long poll decodes nothing
                        with trace.span("kafka_decode", partition=p,
                                        bytes=len(blob)) as sp:
                            recs = scan_record_batches(blob)
                            # whole batches come back, in offset order:
                            # drop what lies below the offset asked for
                            recs = recs[bisect.bisect_left(
                                recs, off, key=_record_offset):]
                            if sp:
                                sp.add(records=len(recs))
                    out[p] = (recs, high)
        for p in retry:
            if p in offsets:
                out[p] = self.fetch(topic, p, offsets[p],
                                    max_bytes=max_bytes,
                                    max_wait_ms=max_wait_ms)
        return out
