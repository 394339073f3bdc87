"""The benchmark's Kafka stand-in for a producing system: one broker, one
topic of N partitions, the wire-protocol subset the Kafka sink of the
system under test speaks - ApiVersions, Metadata v1, Produce v3 and
InitProducerId v3.

Written from the sink's wire behaviour (`providers/kafka/client.py`
`produce`, `init_producer`, `txn_produce`) and Kafka's protocol documents,
with a codec of its own (nothing of `tests/` or `transferia_tpu/` is
imported; the comparison of every landed field holds this codec and the
program's to each other).  What it keeps of a real broker:

  * a record batch (v2) is checked at append - magic, length, CRC32C over
    the bytes the CRC covers, the record count - and refused with
    CORRUPT_MESSAGE otherwise; the acknowledgement goes out after the
    check;
  * a producer's transactional id: InitProducerId takes the epoch the
    client proposes (KIP-360's shape), fences a proposal older than the
    id's current epoch with PRODUCER_FENCED and says which epoch holds it;
    a Produce that carries a transactional id is one transaction - all its
    batches or none - is refused with INVALID_PRODUCER_EPOCH when the id
    is unknown or a batch's producer epoch is older than the id's, and
    SUPERSEDES what the id published before (the subset the sink's staged
    publish relies on: a retried part lands once);
  * a Produce without a transactional id appends, and nothing ever
    replaces it.

What a pass landed is taken out whole (`take`): every batch's bytes as
they came, by publish.  Decoding the records is the reference's work, on
a thread of the world and off the acknowledgement's path.
"""

from __future__ import annotations

import socketserver
import struct
import threading

import google_crc32c

ERR_CORRUPT_MESSAGE = 2
ERR_UNKNOWN_TOPIC = 3
ERR_INVALID_PRODUCER_EPOCH = 47
ERR_PRODUCER_FENCED = 90


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

    def blob(self) -> memoryview:
        n = self.take("!i")
        out = memoryview(self.buf)[self.pos:self.pos + max(n, 0)]
        self.pos += max(n, 0)
        return out


def _enc_str(s) -> bytes:
    if s is None:
        return struct.pack("!h", -1)
    b = s.encode()
    return struct.pack("!h", len(b)) + b


def check_batch(blob) -> tuple[int, int]:
    """(record count, producer epoch) of one well-formed record batch v2;
    raises ValueError for anything else."""
    if len(blob) < 61:
        raise ValueError("record batch shorter than its header")
    _base, length, _leader, magic, crc = struct.unpack_from("!qiibI", blob)
    if magic != 2 or length != len(blob) - 12:
        raise ValueError(f"record batch magic {magic}, length {length} "
                         f"of {len(blob) - 12}")
    if google_crc32c.value(bytes(blob[21:])) != crc:
        raise ValueError("record batch CRC32C mismatch")
    epoch, = struct.unpack_from("!h", blob, 51)
    count, = struct.unpack_from("!i", blob, 57)
    return count, epoch


class ProduceBroker:
    def __init__(self, topic: str, n_partitions: int,
                 drop_one_acked_record: bool = False):
        self.topic = topic
        self.n_partitions = n_partitions
        self.lock = threading.Lock()
        # transactional id -> {"pid", "epoch"}
        self.txns: dict[str, dict] = {}
        self._next_pid = 1000
        # what the pass has landed: transactional id -> its one live
        # publish, [(partition, batch bytes)]; plain produces in order
        self.published: dict[str, list[tuple[int, bytes]]] = {}
        self.plain: list[tuple[int, bytes]] = []
        self.cost = {"produce_requests": 0, "produce_bytes": 0,
                     "batches": 0, "records": 0, "superseded_publishes": 0,
                     "fenced": 0, "refused_batches": 0}
        self.errors: list[str] = []
        # the control: the first publish after arming loses the last
        # record of one batch AFTER it was acknowledged
        self.drop_one_acked_record = drop_one_acked_record
        self.dropped: list[tuple[int, int]] = []
        self.port = 0
        self._srv = None
        self._thread = None

    def take(self) -> tuple[list[tuple[int, bytes]], dict]:
        """Everything landed since the last take, and the counters; the
        topic is empty and no transactional id is known afterwards (a
        pass is a transfer's first day)."""
        with self.lock:
            batches = list(self.plain)
            for txn in self.published.values():
                batches.extend(txn)
            self.plain, self.published, self.txns = [], {}, {}
            return batches, dict(self.cost)

    # -- lifecycle -----------------------------------------------------------------
    def start(self) -> "ProduceBroker":
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
                out = bytearray(n)
                view, got = memoryview(out), 0
                while got < n:
                    k = self.request.recv_into(view[got:], n - got)
                    if not k:
                        raise ConnectionError()
                    got += k
                return bytes(out)

        class Server(socketserver.ThreadingTCPServer):
            allow_reuse_address = True
            daemon_threads = True

        self._srv = Server(("127.0.0.1", 0), Handler)
        self.port = self._srv.server_address[1]
        self._thread = threading.Thread(target=self._srv.serve_forever,
                                        name="produce-broker", daemon=True)
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
        api_key, version, corr = r.take("!hhi")
        r.string()  # client id
        handler = {0: self._produce, 3: self._metadata,
                   18: self._api_versions,
                   22: self._init_producer_id}.get(api_key)
        if handler is None:
            with self.lock:
                self.errors.append(f"api key {api_key} v{version} "
                                   f"not spoken")
            # UNSUPPORTED_VERSION where a response starts with an error
            # code; a client that reads something else fails on it at once
            return struct.pack("!ih", corr, 35)
        return struct.pack("!i", corr) + handler(r)

    def _api_versions(self, r: _Reader) -> bytes:
        keys = ((0, 3, 3), (3, 1, 1), (18, 0, 0), (22, 3, 3))
        out = struct.pack("!hi", 0, len(keys))
        for key, lo, hi in keys:
            out += struct.pack("!hhh", key, lo, hi)
        return out

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
            out += struct.pack("!h", 0 if known else ERR_UNKNOWN_TOPIC) \
                + _enc_str(name) + b"\x00"
            parts = self.n_partitions if known else 0
            out += struct.pack("!i", parts)
            for pid in range(parts):
                out += struct.pack("!hiii", 0, pid, 0, 1)
                out += struct.pack("!i", 0)             # replicas
                out += struct.pack("!i", 0)             # isr
        return out

    def _init_producer_id(self, r: _Reader) -> bytes:
        txn_id = r.string()
        r.take("!i")                                    # txn timeout
        _pid, epoch = r.take("!qh")
        with self.lock:
            state = self.txns.get(txn_id)
            if state is None:
                state = {"pid": self._next_pid, "epoch": epoch}
                self._next_pid += 1
                self.txns[txn_id] = state
            elif epoch < state["epoch"]:
                self.cost["fenced"] += 1
                return struct.pack("!ihqh", 0, ERR_PRODUCER_FENCED, -1,
                                   state["epoch"])
            else:
                state["epoch"] = epoch
            return struct.pack("!ihqh", 0, 0, state["pid"], state["epoch"])

    def _produce(self, r: _Reader) -> bytes:
        txn_id = r.string()
        r.take("!hi")                                   # acks, timeout
        asked: list[tuple[str, int, memoryview]] = []
        for _ in range(r.take("!i")):
            topic = r.string()
            for _ in range(r.take("!i")):
                partition = r.take("!i")
                asked.append((topic, partition, r.blob()))
        results = []
        good: list[tuple[int, bytes]] = []
        records = 0
        with self.lock:
            state = self.txns.get(txn_id) if txn_id is not None else None
        for topic, partition, blob in asked:
            err = 0
            if topic != self.topic or not 0 <= partition < self.n_partitions:
                err = ERR_UNKNOWN_TOPIC
            else:
                try:
                    count, epoch = check_batch(blob)
                    if txn_id is not None and (
                            state is None or epoch < state["epoch"]):
                        err = ERR_INVALID_PRODUCER_EPOCH
                    else:
                        good.append((partition, bytes(blob)))
                        records += count
                except ValueError as e:
                    err = ERR_CORRUPT_MESSAGE
                    with self.lock:
                        self.errors.append(str(e))
            results.append((topic, partition, err))
        failed = any(err for _t, _p, err in results)
        with self.lock:
            self.cost["produce_requests"] += 1
            self.cost["produce_bytes"] += len(r.buf)
            self.cost["refused_batches"] += sum(
                1 for _t, _p, err in results if err)
            if txn_id is None:
                self.plain.extend(good)     # each batch on its own
                self.cost["batches"] += len(good)
                self.cost["records"] += records
            elif not failed:                # a transaction: all or none
                if txn_id in self.published:
                    self.cost["superseded_publishes"] += 1
                self.published[txn_id] = good
                self.cost["batches"] += len(good)
                self.cost["records"] += records
                if self.drop_one_acked_record and good:
                    self._drop_last_record(txn_id)
        out = b""
        by_topic: dict[str, list] = {}
        for topic, partition, err in results:
            by_topic.setdefault(topic, []).append((partition, err))
        out += struct.pack("!i", len(by_topic))
        for topic, parts in by_topic.items():
            out += _enc_str(topic) + struct.pack("!i", len(parts))
            for partition, err in parts:
                out += struct.pack("!ihqq", partition, err, 0, -1)
        return out + struct.pack("!i", 0)               # throttle

    def _drop_last_record(self, txn_id: str) -> None:
        """The control's fault, under the lock: the records of the first
        batch less the last one, the count and the CRC made good, so that
        what is kept is a well-formed batch of one record fewer."""
        partition, blob = self.published[txn_id][0]
        count, _epoch = check_batch(blob)
        if count < 2:
            return
        pos = 61
        for _ in range(count - 1):
            length, pos = read_varint(blob, pos)
            pos += length
        body = bytearray(blob[:pos])
        struct.pack_into("!i", body, 8, len(body) - 12)
        struct.pack_into("!i", body, 23, count - 2)     # lastOffsetDelta
        struct.pack_into("!i", body, 57, count - 1)
        struct.pack_into("!I", body, 17,
                         google_crc32c.value(bytes(body[21:])))
        self.published[txn_id][0] = (partition, bytes(body))
        self.dropped.append((partition, count - 1))
        self.drop_one_acked_record = False


def read_varint(buf, pos: int) -> tuple[int, int]:
    """A zigzag varint at `pos`: (value, the position after it)."""
    shift = z = 0
    while True:
        b = buf[pos]
        pos += 1
        z |= (b & 0x7F) << shift
        if not b & 0x80:
            return (z >> 1) ^ -(z & 1), pos
        shift += 7
