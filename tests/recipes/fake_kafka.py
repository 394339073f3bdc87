"""In-process fake Kafka broker (wire-protocol subset).

Server side of what the provider's client speaks: ApiVersions ignored,
Metadata v1, Produce v3 (stores the raw record batch, re-serving it on
fetch — a real broker does the same), Fetch v4, ListOffsets v1.
"""

from __future__ import annotations

import socketserver
import struct
import threading
from typing import Optional

from transferia_tpu.providers.kafka.protocol import (
    Reader,
    decode_record_batches,
    enc_bytes,
    enc_str,
    enc_str as _enc_str,
    encode_record_batch,
)


def _index_frames(blob: bytes) -> Optional[list]:
    """[(frame_pos, record_count)] straight from the batch header(s) —
    no decode.  recordCount sits at fixed offset 57 of each v2 frame."""
    from transferia_tpu.providers.kafka.protocol import crc32c

    frames = []
    pos = 0
    n = len(blob)
    while pos + 61 <= n:
        batch_len = struct.unpack_from("!i", blob, pos + 8)[0]
        magic = blob[pos + 16]
        # a non-positive length would loop forever; corrupt frames must
        # land on the eager-decode path, which raises on produce
        if magic != 2 or batch_len <= 0 or pos + 12 + batch_len > n:
            return None
        # brokers validate the CRC at append time; so does this fake —
        # a corrupt batch errors the PRODUCER, not a later consumer
        expect = struct.unpack_from("!I", blob, pos + 17)[0]
        if crc32c(blob[pos + 21:pos + 12 + batch_len]) != expect:
            return None
        frames.append((pos, struct.unpack_from("!i", blob, pos + 57)[0]))
        pos += 12 + batch_len
    if pos != n:
        return None
    return frames


class _PartitionLog:
    """Partition storage as a real broker keeps it: raw produced batch
    blobs, decoded lazily when a fetch actually reads them.  Exposes the
    list surface the fixtures/tests use (len, slicing, iteration,
    append of decoded records)."""

    def __init__(self):
        # [base, count, blob|None, records|None]
        self._segments: list[list] = []
        self._n = 0

    def __len__(self) -> int:
        return self._n

    def append_blob(self, blob: bytes) -> bool:
        frames = _index_frames(blob)
        if frames is None:
            return False
        total = sum(c for _, c in frames)
        if not total:
            return True
        # assign offsets the broker way: rewrite each frame's baseOffset
        # in place, so the stored bytes can be served verbatim on fetch
        ba = bytearray(blob)
        base = self._n
        for pos, count in frames:
            struct.pack_into("!q", ba, pos, base)
            base += count
        self._segments.append([self._n, total, bytes(ba), None])
        self._n += total
        return True

    def raw_from(self, offset: int, max_records: int = 1000) -> bytes:
        """Stored frames covering [offset, ...), served verbatim (the
        client trims records below the requested offset, exactly as with
        a real broker's batch-aligned responses)."""
        out = []
        taken = 0
        for seg in self._segments:
            if seg[0] + seg[1] <= offset:
                continue
            if taken >= max_records:
                break
            if seg[2] is not None:
                out.append(seg[2])
            else:
                out.append(encode_record_batch(seg[3],
                                               base_offset=seg[0]))
            taken += seg[1]
        return b"".join(out)

    def append(self, rec) -> None:
        rec.offset = self._n
        if self._segments and self._segments[-1][2] is None:
            seg = self._segments[-1]
            seg[3].append(rec)
            seg[1] += 1
        else:
            self._segments.append([self._n, 1, None, [rec]])
        self._n += 1

    def _records_of(self, seg: list) -> list:
        if seg[3] is None:
            recs = decode_record_batches(seg[2])
            for i, r in enumerate(recs):
                r.offset = seg[0] + i
            seg[3] = recs
        return seg[3]

    def __getitem__(self, idx):
        if isinstance(idx, slice):
            lo, hi, step = idx.indices(self._n)
            if step != 1:
                return [self[i] for i in range(lo, hi, step)]
            out = []
            for seg in self._segments:
                base, count = seg[0], seg[1]
                if base + count <= lo or base >= hi:
                    continue
                recs = self._records_of(seg)
                out.extend(recs[max(0, lo - base):hi - base])
            return out
        if idx < 0:
            idx += self._n
        for seg in self._segments:
            if seg[0] <= idx < seg[0] + seg[1]:
                return self._records_of(seg)[idx - seg[0]]
        raise IndexError(idx)

    def __iter__(self):
        for seg in self._segments:
            yield from self._records_of(seg)


class FakeKafka:
    def __init__(self, n_partitions: int = 2,
                 auto_create_topics: bool = True,
                 sasl: Optional[tuple] = None,
                 tls_cert: Optional[tuple] = None):
        """sasl: (mechanism, username, password) to REQUIRE auth;
        tls_cert: (certfile, keyfile) to serve TLS."""
        self.n_partitions = n_partitions
        self.auto_create = auto_create_topics
        # topic -> partition -> _PartitionLog (absolute offsets = index)
        self.topics: dict[str, list[_PartitionLog]] = {}
        self.lock = threading.RLock()
        self.port = 0
        self._srv = None
        self.sasl = sasl
        # transactional state (KIP-98 subset for the staged-commit
        # sink): transactional id -> {"pid", "epoch", "published":
        # [(topic, partition, segment)] of the LAST committed
        # transaction}, so a republish SUPERSEDES instead of appending
        # and a stale producer epoch is fenced
        self.txns: dict[str, dict] = {}
        self._next_pid = 1000
        self.auth_attempts = 0
        self.fetches_with_rows = 0
        self._ssl_ctx = None
        if tls_cert is not None:
            import ssl

            self._ssl_ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
            self._ssl_ctx.load_cert_chain(tls_cert[0], tls_cert[1])

    def create_topic(self, name: str,
                     n_partitions: Optional[int] = None) -> None:
        with self.lock:
            if name not in self.topics:
                self.topics[name] = [
                    _PartitionLog()
                    for _ in range(n_partitions or self.n_partitions)
                ]

    def records(self, topic: str, partition: int = 0) -> list:
        with self.lock:
            return list(self.topics.get(topic, [[]])[partition])

    def size(self, topic: str) -> int:
        with self.lock:
            return sum(len(p) for p in self.topics.get(topic, []))

    # -- lifecycle ----------------------------------------------------------
    def start(self) -> "FakeKafka":
        fake = self

        class Handler(socketserver.BaseRequestHandler):
            def handle(self):
                try:
                    if fake._ssl_ctx is not None:
                        self.request = fake._ssl_ctx.wrap_socket(
                            self.request, server_side=True)
                    session = {"authed": fake.sasl is None,
                               "verifier": None}
                    while True:
                        raw = self._recv_exact(4)
                        size = struct.unpack("!i", raw)[0]
                        payload = self._recv_exact(size)
                        resp = fake.handle_request(payload, session)
                        self.request.sendall(
                            struct.pack("!i", len(resp)) + resp
                        )
                except (ConnectionError, OSError):
                    return
                except Exception:
                    return  # TLS handshake failures etc.

            def _recv_exact(self, n):
                out = b""
                while len(out) < n:
                    chunk = self.request.recv(n - len(out))
                    if not chunk:
                        raise ConnectionError()
                    out += chunk
                return out

        class Server(socketserver.ThreadingTCPServer):
            allow_reuse_address = True
            daemon_threads = True

        self._srv = Server(("127.0.0.1", 0), Handler)
        self.port = self._srv.server_address[1]
        threading.Thread(target=self._srv.serve_forever,
                         daemon=True).start()
        return self

    def stop(self):
        if self._srv:
            self._srv.shutdown()

    # -- dispatch -----------------------------------------------------------
    def handle_request(self, payload: bytes,
                       session: Optional[dict] = None) -> bytes:
        session = session if session is not None else {"authed": True}
        r = Reader(payload)
        api_key = r.i16()
        api_version = r.i16()
        corr = r.i32()
        r.string()  # client id
        if api_key == 17:
            return struct.pack("!i", corr) + self._sasl_handshake(r)
        if api_key == 36:
            return struct.pack("!i", corr) + \
                self._sasl_authenticate(r, session)
        if not session.get("authed"):
            # real brokers drop unauthenticated connections on SASL
            # listeners
            raise ConnectionError("unauthenticated request")
        body = {
            3: self._metadata,
            0: self._produce,
            1: self._fetch,
            2: self._list_offsets,
            22: self._init_producer_id,
        }.get(api_key, lambda _r: b"")(r)
        return struct.pack("!i", corr) + body

    def _sasl_handshake(self, r: Reader) -> bytes:
        mech = r.string() or ""
        want = self.sasl[0] if self.sasl else ""
        if not self.sasl or mech != want:
            return (struct.pack("!h", 33)  # UNSUPPORTED_SASL_MECHANISM
                    + struct.pack("!i", 1) + enc_str(want or "NONE"))
        return struct.pack("!h", 0) + struct.pack("!i", 1) + enc_str(want)

    def _sasl_authenticate(self, r: Reader, session: dict) -> bytes:
        from transferia_tpu.utils.scram import ScramError, ServerVerifier

        def resp(err: int, msg: Optional[str], auth: bytes) -> bytes:
            return (struct.pack("!h", err) + enc_str(msg)
                    + enc_bytes(auth) + struct.pack("!q", 0))

        data = r.bytes_() or b""
        mech, user, password = self.sasl
        self.auth_attempts += 1
        if mech == "PLAIN":
            parts = data.split(b"\x00")
            if len(parts) == 3 and parts[1].decode() == user \
                    and parts[2].decode() == password:
                session["authed"] = True
                return resp(0, None, b"")
            return resp(58, "bad credentials", b"")  # SASL_AUTH_FAILED
        try:
            if session.get("verifier") is None:
                session["verifier"] = ServerVerifier(mech, user, password)
                return resp(0, None, session["verifier"].first(data))
            out = session["verifier"].final(data)
            session["authed"] = True
            session["verifier"] = None
            return resp(0, None, out)
        except ScramError as e:
            session["verifier"] = None
            return resp(58, str(e), b"")

    def _metadata(self, r: Reader) -> bytes:
        n = r.i32()
        wanted = None
        if n >= 0:
            wanted = [r.string() for _ in range(n)]
        with self.lock:
            if wanted:
                for t in wanted:
                    if self.auto_create:
                        self.create_topic(t)
            names = wanted if wanted is not None else list(self.topics)
            out = struct.pack("!i", 1)  # one broker
            out += struct.pack("!i", 0) + _enc_str("127.0.0.1") \
                + struct.pack("!i", self.port) + _enc_str(None)
            out += struct.pack("!i", 0)  # controller
            out += struct.pack("!i", len(names))
            for name in names:
                parts = self.topics.get(name)
                err = 0 if parts is not None else 3
                out += struct.pack("!h", err) + _enc_str(name) + b"\x00"
                out += struct.pack("!i", len(parts or []))
                for pid in range(len(parts or [])):
                    out += struct.pack("!hiii", 0, pid, 0, 1)
                    out += struct.pack("!i", 0)       # replicas
                    out += struct.pack("!i", 0)       # isr
        return out

    def live_size(self, topic: str) -> int:
        """Record count excluding superseded transactional segments
        (offsets still cover them, like aborted-txn gaps on a real
        broker)."""
        with self.lock:
            n = 0
            for p in self.topics.get(topic, []):
                for seg in p._segments:
                    if seg[2] is None and seg[3] == []:
                        continue
                    n += seg[1]
            return n

    @staticmethod
    def _frame_producer_epoch(blob: bytes) -> int:
        """producerEpoch of the first v2 frame (offset 51 of the
        frame: 12-byte outer header + 39 bytes to the epoch field)."""
        if len(blob) < 61:
            return -1
        return struct.unpack_from("!h", blob, 51)[0]

    def _init_producer_id(self, r: Reader) -> bytes:
        """InitProducerId (KIP-360 shape): the client proposes its
        epoch; an OLDER proposal than the id's current epoch is fenced
        (error 90), else the id adopts the proposal."""
        txn_id = r.string()
        r.i32()              # transaction timeout
        r.i64()              # producer id proposal (-1)
        epoch = r.i16()
        with self.lock:
            state = self.txns.get(txn_id)
            if state is None:
                state = {"pid": self._next_pid, "epoch": epoch,
                         "published": []}
                self._next_pid += 1
                self.txns[txn_id] = state
            elif epoch < state["epoch"]:
                # fenced: disclose the id's current epoch so the
                # client's StaleEpochPublishError names the real winner
                return struct.pack("!ihqh", 0, 90, -1, state["epoch"])
            else:
                state["epoch"] = epoch
            return struct.pack("!ihqh", 0, 0, state["pid"],
                               state["epoch"])

    def _produce(self, r: Reader) -> bytes:
        txn_id = r.string()  # transactional id (None = plain produce)
        r.i16()              # acks
        r.i32()              # timeout
        incoming = []
        for _ in range(r.i32()):
            topic = r.string()
            for _ in range(r.i32()):
                partition = r.i32()
                blob = r.bytes_() or b""
                incoming.append((topic, partition, blob))
        err = 0
        bases = {}
        with self.lock:
            state = self.txns.get(txn_id) if txn_id else None
            if txn_id is not None:
                if state is None:
                    err = 47  # unknown producer for the txn id
                else:
                    for _t, _p, blob in incoming:
                        if self._frame_producer_epoch(blob) \
                                < state["epoch"]:
                            err = 47  # stale producer epoch: fenced
                            break
            if not err:
                if state is not None:
                    # one transactional produce = one committed
                    # transaction: SUPERSEDE the previous publish of
                    # this transactional id in place (offsets keep
                    # their slots, like aborted-txn gaps)
                    for _t, _p, seg in state["published"]:
                        seg[2] = None
                        seg[3] = []
                    state["published"] = []
                for topic, partition, blob in incoming:
                    self.create_topic(topic)
                    plist = self.topics[topic][partition]
                    bases[(topic, partition)] = len(plist)
                    segs_before = len(plist._segments)
                    # store the raw blob (a real broker never decodes);
                    # unparseable frames fall back to eager decode so
                    # protocol tests still see their errors on produce
                    if not plist.append_blob(blob):
                        for rec in decode_record_batches(blob):
                            plist.append(rec)
                    if state is not None:
                        for seg in plist._segments[segs_before:]:
                            state["published"].append(
                                (topic, partition, seg))
        out = struct.pack("!i", len(incoming))
        for topic, partition, _blob in incoming:
            base = bases.get((topic, partition), -1)
            out += _enc_str(topic) + struct.pack("!i", 1)
            out += struct.pack("!ihqq", partition, err, base, -1)
        out += struct.pack("!i", 0)  # throttle
        return out

    def _list_offsets(self, r: Reader) -> bytes:
        r.i32()  # replica id
        out = b""
        n_topics = r.i32()
        out += struct.pack("!i", n_topics)
        for _ in range(n_topics):
            topic = r.string()
            n_parts = r.i32()
            out += _enc_str(topic) + struct.pack("!i", n_parts)
            for _ in range(n_parts):
                partition = r.i32()
                ts = r.i64()
                with self.lock:
                    plist = self.topics.get(topic, [[]] * (partition + 1))
                    n = len(plist[partition]) if partition < len(plist) \
                        else 0
                offset = 0 if ts == -2 else n
                out += struct.pack("!ihqq", partition, 0, -1, offset)
        return out

    def _fetch(self, r: Reader) -> bytes:
        r.i32()  # replica
        r.i32()  # max wait
        r.i32()  # min bytes
        r.i32()  # max bytes
        r.i8()   # isolation
        n_topics = r.i32()
        out = struct.pack("!i", 0)  # throttle
        out += struct.pack("!i", n_topics)
        any_rows = False
        for _ in range(n_topics):
            topic = r.string()
            n_parts = r.i32()
            out += _enc_str(topic) + struct.pack("!i", n_parts)
            for _ in range(n_parts):
                partition = r.i32()
                offset = r.i64()
                r.i32()  # partition max bytes
                with self.lock:
                    plist = self.topics.get(topic)
                    if plist is not None:
                        log = plist[partition]
                        high = len(log)
                        # stored frames serve verbatim (batch-aligned,
                        # like a real broker; clients trim the head)
                        blob = log.raw_from(offset)
                    else:
                        blob = b""
                        high = 0
                any_rows = any_rows or bool(blob)
                out += struct.pack("!ihqq", partition, 0, high, high)
                out += struct.pack("!i", 0)   # aborted txns
                out += struct.pack("!i", len(blob)) + blob
        with self.lock:
            self.fetches_with_rows += any_rows
        return out
