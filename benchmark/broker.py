"""The benchmark's Kafka stand-in: one broker, one topic, the wire-protocol
subset a consumer needs (Metadata v1, ListOffsets v1, Fetch v4).

A copy in spirit of `tests/recipes/fake_kafka.py`, with a codec of its own
(later PRs may edit both `tests/` and the program's codec; the comparison of
every landed field is what holds this codec and the program's to each
other).  Producers do not come over the wire: the load generator lives in
this process and appends record batches it has encoded itself, so the time
an event was due and the time it was appended are read from one clock.

What it keeps of a real broker:
  * a partition log of record batches (v2, CRC32C), served verbatim, batch
    aligned, from the batch that holds the requested offset;
  * long polling: a Fetch that finds nothing waits for data up to its
    `max_wait_ms`, as `min_bytes` = 1 asks;
  * a response filled to the request's byte limits (KIP-74): whole
    batches, in request order, up to the partition's `max_bytes` and the
    request's, and always the first batch, however large.

The broker never learns of commits (the source keeps its offsets in the
transfer's coordinator): the consumer's position is the highest offset a
Fetch has asked for, and what lies below it is what the consumer took.
"""

from __future__ import annotations

import bisect
import socketserver
import struct
import threading
import time

import google_crc32c
import numpy as np

RECORDS_PER_BATCH = 64   # offsetDelta < 64 encodes as one zigzag byte


# -- record batches (v2) ----------------------------------------------------------

def _zigzag_varint(n: int) -> bytes:
    z = (n << 1) ^ (n >> 63)
    out = bytearray()
    while True:
        b = z & 0x7F
        z >>= 7
        if z:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def record_frame(value_len: int) -> tuple[bytes, bytes, int]:
    """(prefix, suffix, offset of the offsetDelta byte) of a record with a
    null key, no headers, timestampDelta 0 and a value of `value_len`
    bytes: prefix + value + suffix is the record; only the offsetDelta byte
    (zigzag of a position < 64) differs between the records of a batch."""
    body_len = 1 + 1 + 1 + 1 + len(_zigzag_varint(value_len)) + value_len + 1
    prefix = (_zigzag_varint(body_len) + b"\x00"      # length, attributes
              + b"\x00")                              # timestampDelta 0
    delta_at = len(prefix)
    prefix += b"\x00" + b"\x01" + _zigzag_varint(value_len)  # delta, key -1
    return prefix, b"\x00", delta_at                  # headers: 0


def encode_records(values: np.ndarray, positions: np.ndarray) -> np.ndarray:
    """(n, L) value bytes + position of each in its batch -> (n, R) record
    bytes."""
    n, length = values.shape
    prefix, suffix, delta_at = record_frame(length)
    out = np.empty((n, len(prefix) + length + len(suffix)), dtype=np.uint8)
    out[:, :len(prefix)] = np.frombuffer(prefix, dtype=np.uint8)
    out[:, delta_at] = (positions * 2).astype(np.uint8)
    out[:, len(prefix):len(prefix) + length] = values
    out[:, len(prefix) + length:] = np.frombuffer(suffix, dtype=np.uint8)
    return out


def encode_batch(records: bytes, count: int, timestamp_ms: int) -> bytes:
    """One record batch around `count` encoded records; baseOffset 0 (the
    log sets it on append, outside what the CRC covers)."""
    tail = struct.pack("!hiqqqhii", 0, count - 1, timestamp_ms,
                       timestamp_ms, -1, -1, -1, count) + records
    crc = google_crc32c.value(tail)
    return (struct.pack("!qiib", 0, 4 + 1 + 4 + len(tail), 0, 2)
            + struct.pack("!I", crc) + tail)


# -- the log ---------------------------------------------------------------------------

class PartitionLog:
    def __init__(self):
        self.bases: list[int] = []     # base offset of each batch
        self.counts: list[int] = []
        self.blobs: list[bytes] = []
        self.end = 0                   # next offset

    def append(self, blob: bytes, count: int) -> int:
        base = self.end
        self.bases.append(base)
        self.counts.append(count)
        self.blobs.append(struct.pack("!q", base) + blob[8:])
        self.end += count
        return base

    def read(self, offset: int, limit: int, max_bytes: int) -> bytes:
        """Whole batches from the one that holds `offset`, up to
        `max_bytes` (the first whatever its size); nothing at or past
        `limit`."""
        i = bisect.bisect_right(self.bases, offset) - 1
        end = min(self.end, limit)
        if i < 0 or offset >= end:
            return b""
        out, taken = [], 0
        while i < len(self.blobs) and self.bases[i] < end \
                and (not out or taken + len(self.blobs[i]) <= max_bytes):
            out.append(self.blobs[i])
            taken += len(self.blobs[i])
            i += 1
        return b"".join(out)


class _Reader:
    def __init__(self, buf: bytes):
        self.buf, self.pos = buf, 0

    def take(self, fmt: str):
        v = struct.unpack_from(fmt, self.buf, self.pos)
        self.pos += struct.calcsize(fmt)
        return v if len(v) > 1 else v[0]

    def string(self):
        n = self.take("!h")
        if n < 0:
            return None
        s = self.buf[self.pos:self.pos + n].decode()
        self.pos += n
        return s


def _enc_str(s) -> bytes:
    if s is None:
        return struct.pack("!h", -1)
    b = s.encode()
    return struct.pack("!h", len(b)) + b


class BrokerStandIn:
    def __init__(self, topic: str, n_partitions: int):
        self.topic = topic
        self.logs = [PartitionLog() for _ in range(n_partitions)]
        self.cond = threading.Condition()
        self.positions = [0] * n_partitions   # highest offset fetched from
        self.limits = [1 << 62] * n_partitions
        self.fetches_with_rows = 0
        self.fetches_empty = 0
        self._srv = None
        self._thread = None
        self.port = 0

    # -- what the generator and the world use -----------------------------------
    def append_many(self, items: list[tuple[int, bytes, int]]) -> None:
        with self.cond:
            for partition, blob, count in items:
                self.logs[partition].append(blob, count)
            self.cond.notify_all()

    def produced(self) -> int:
        with self.cond:
            return sum(log.end for log in self.logs)

    def consumed(self) -> int:
        with self.cond:
            return sum(self.positions)

    def fence(self) -> list[int]:
        """From now on serve nothing at or past the consumer's position;
        returns the fenced offsets.  A consumer that took part of its last
        response asks on from where it stopped: its position still moves,
        and what lies below it in the end is what it has to land."""
        with self.cond:
            self.limits = list(self.positions)
            return list(self.limits)

    def backlog(self) -> list[int]:
        with self.cond:
            return [log.end - pos
                    for log, pos in zip(self.logs, self.positions)]

    # -- lifecycle -----------------------------------------------------------------
    def start(self) -> "BrokerStandIn":
        broker = self

        class Handler(socketserver.BaseRequestHandler):
            def handle(self):
                try:
                    while True:
                        size = struct.unpack("!i", self._exact(4))[0]
                        resp = broker.handle_request(self._exact(size))
                        self.request.sendall(
                            struct.pack("!i", len(resp)) + resp)
                except (ConnectionError, OSError):
                    return

            def _exact(self, n: int) -> bytes:
                out = bytearray()
                while len(out) < n:
                    chunk = self.request.recv(n - len(out))
                    if not chunk:
                        raise ConnectionError()
                    out += chunk
                return bytes(out)

        class Server(socketserver.ThreadingTCPServer):
            allow_reuse_address = True
            daemon_threads = True

        self._srv = Server(("127.0.0.1", 0), Handler)
        self.port = self._srv.server_address[1]
        self._thread = threading.Thread(target=self._srv.serve_forever,
                                        name="broker-standin", daemon=True)
        self._thread.start()
        return self

    def stop(self) -> None:
        if self._srv is not None:
            self._srv.shutdown()
            self._srv.server_close()
            self._thread.join(timeout=10)
            self._srv = None

    # -- protocol ------------------------------------------------------------------
    def handle_request(self, payload: bytes) -> bytes:
        r = _Reader(payload)
        api_key, _version, corr = r.take("!hhi")
        r.string()  # client id
        handler = {3: self._metadata, 1: self._fetch,
                   2: self._list_offsets}.get(api_key)
        body = handler(r) if handler else b""
        return struct.pack("!i", corr) + body

    def _metadata(self, r: _Reader) -> bytes:
        n = r.take("!i")
        wanted = [r.string() for _ in range(n)] if n >= 0 else [self.topic]
        out = struct.pack("!i", 1)                      # one broker
        out += struct.pack("!i", 0) + _enc_str("127.0.0.1") \
            + struct.pack("!i", self.port) + _enc_str(None)
        out += struct.pack("!i", 0)                     # controller
        out += struct.pack("!i", len(wanted))
        for name in wanted:
            known = name == self.topic
            out += struct.pack("!h", 0 if known else 3) + _enc_str(name) \
                + b"\x00"
            parts = len(self.logs) if known else 0
            out += struct.pack("!i", parts)
            for pid in range(parts):
                out += struct.pack("!hiii", 0, pid, 0, 1)
                out += struct.pack("!i", 0)             # replicas
                out += struct.pack("!i", 0)             # isr
        return out

    def _list_offsets(self, r: _Reader) -> bytes:
        r.take("!i")  # replica id
        n_topics = r.take("!i")
        out = struct.pack("!i", n_topics)
        for _ in range(n_topics):
            topic = r.string()
            n_parts = r.take("!i")
            out += _enc_str(topic) + struct.pack("!i", n_parts)
            for _ in range(n_parts):
                partition, ts = r.take("!iq")
                with self.cond:
                    end = self.logs[partition].end \
                        if topic == self.topic else 0
                out += struct.pack("!ihqq", partition, 0, -1,
                                   0 if ts == -2 else end)
        return out

    def _fetch(self, r: _Reader) -> bytes:
        _replica, max_wait_ms, _min_bytes, max_bytes = r.take("!iiii")
        r.take("!b")  # isolation level
        asked: list[tuple[str, list[tuple[int, int]]]] = []
        for _ in range(r.take("!i")):
            topic = r.string()
            parts = []
            for _ in range(r.take("!i")):
                parts.append(r.take("!iqi"))
            asked.append((topic, parts))
        deadline = time.monotonic() + max_wait_ms / 1000.0
        with self.cond:
            ours = [(p, o) for t, parts in asked if t == self.topic
                    for p, o, _pmax in parts]
            for p, o in ours:
                self.positions[p] = max(self.positions[p], o)
            while not any(min(self.logs[p].end, self.limits[p]) > o
                          for p, o in ours):
                left = deadline - time.monotonic()
                if left <= 0:
                    break
                self.cond.wait(left)
            out = struct.pack("!i", 0)                  # throttle
            out += struct.pack("!i", len(asked))
            any_rows = False
            for topic, parts in asked:
                out += _enc_str(topic) + struct.pack("!i", len(parts))
                for partition, offset, pmax in parts:
                    if topic == self.topic:
                        log = self.logs[partition]
                        high = min(log.end, self.limits[partition])
                        blob = log.read(offset, self.limits[partition],
                                        max(0, min(pmax, max_bytes)))
                        max_bytes -= len(blob)
                    else:
                        high, blob = 0, b""
                    any_rows = any_rows or bool(blob)
                    out += struct.pack("!ihqq", partition, 0, high, high)
                    out += struct.pack("!i", 0)         # aborted txns
                    out += struct.pack("!i", len(blob)) + blob
            if any_rows:
                self.fetches_with_rows += 1
            else:
                self.fetches_empty += 1
        return out
