"""Kafka wire protocol primitives: framing, record batches v2, codecs.

Binary conventions: big-endian fixed ints; STRING = int16 len + utf8
(-1 = null); BYTES = int32 len + data (-1 = null); record-batch internals
use zigzag varints.  CRC32C (Castagnoli) covers the batch from the
attributes field onward.
"""

from __future__ import annotations

import struct
import time
from collections.abc import Sequence
from dataclasses import dataclass, field
from typing import Optional

try:
    import google_crc32c

    def crc32c(data: bytes) -> int:
        return google_crc32c.value(data)

    def _crc_extend(crc: int, data) -> int:
        return google_crc32c.extend(crc, data)
except ImportError:
    # native SSE4.2 path (hostops.cpp crc32c_buf) with a pure-python
    # table as the last resort; resolved lazily so importing this module
    # never triggers a native build
    def _make_table():
        poly = 0x82F63B78
        table = []
        for n in range(256):
            c = n
            for _ in range(8):
                c = (c >> 1) ^ poly if c & 1 else c >> 1
            table.append(c)
        return table

    def _crc_py(data: bytes, init: int = 0) -> int:
        crc = init ^ 0xFFFFFFFF
        for b in bytes(data):
            crc = _TABLE[(crc ^ b) & 0xFF] ^ (crc >> 8)
        return crc ^ 0xFFFFFFFF

    _crc_extend = _crc_py

    _TABLE = _make_table()
    _crc_impl = None

    def crc32c(data: bytes) -> int:
        global _crc_impl
        if _crc_impl is None:
            from transferia_tpu.native import lib as _native_lib

            cdll = _native_lib()
            if cdll is None:  # TRANSFERIA_TPU_NO_NATIVE=1
                _crc_impl = _crc_py
            else:
                import numpy as _np

                def _crc_native(data: bytes,
                                _c=cdll.crc32c_buf, _np=_np) -> int:
                    return int(_c(_np.frombuffer(data, _np.uint8),
                                  len(data), 0))

                _crc_impl = _crc_native
        return _crc_impl(data)


# -- primitive codecs --------------------------------------------------------

def enc_str(s: Optional[str]) -> bytes:
    if s is None:
        return struct.pack("!h", -1)
    b = s.encode()
    return struct.pack("!h", len(b)) + b


def enc_bytes(b: Optional[bytes]) -> bytes:
    if b is None:
        return struct.pack("!i", -1)
    return struct.pack("!i", len(b)) + b


def enc_varint(n: int) -> bytes:
    """Zigzag varint."""
    z = (n << 1) ^ (n >> 63)
    out = b""
    while True:
        b = z & 0x7F
        z >>= 7
        if z:
            out += bytes([b | 0x80])
        else:
            return out + bytes([b])


class Reader:
    __slots__ = ("buf", "pos")

    def __init__(self, buf: bytes, pos: int = 0):
        self.buf = buf
        self.pos = pos

    def i8(self) -> int:
        v = struct.unpack_from("!b", self.buf, self.pos)[0]
        self.pos += 1
        return v

    def i16(self) -> int:
        v = struct.unpack_from("!h", self.buf, self.pos)[0]
        self.pos += 2
        return v

    def i32(self) -> int:
        v = struct.unpack_from("!i", self.buf, self.pos)[0]
        self.pos += 4
        return v

    def i64(self) -> int:
        v = struct.unpack_from("!q", self.buf, self.pos)[0]
        self.pos += 8
        return v

    def string(self) -> Optional[str]:
        n = self.i16()
        if n < 0:
            return None
        s = self.buf[self.pos:self.pos + n].decode()
        self.pos += n
        return s

    def bytes_(self) -> Optional[bytes]:
        n = self.i32()
        if n < 0:
            return None
        b = self.buf[self.pos:self.pos + n]
        self.pos += n
        return bytes(b)

    def varint(self) -> int:
        z = 0
        shift = 0
        while True:
            b = self.buf[self.pos]
            self.pos += 1
            z |= (b & 0x7F) << shift
            if not b & 0x80:
                break
            shift += 7
        return (z >> 1) ^ -(z & 1)

    def remaining(self) -> int:
        return len(self.buf) - self.pos


# -- record batches v2 -------------------------------------------------------

@dataclass
class Record:
    key: Optional[bytes]
    value: Optional[bytes]
    offset: int = 0
    timestamp_ms: int = 0
    headers: list = field(default_factory=list)


_CODEC_GZIP = 1
# attributes bit 4: this batch is part of a transaction
_ATTR_TRANSACTIONAL = 0x10


def crc32c_chain(pieces) -> int:
    """CRC32C of the buffers end to end, chained from one to the next
    with no concatenation: natively (`crc32c_buf` takes the running value,
    GIL released) where the library is loaded."""
    import numpy as np

    from transferia_tpu.native import lib as native_lib

    cdll = native_lib()
    crc = 0
    for piece in pieces:
        if not len(piece):
            continue
        if cdll is None:
            crc = _crc_extend(crc, piece)
        else:
            data = np.frombuffer(piece, dtype=np.uint8)
            crc = cdll.crc32c_buf(data, data.size, crc)
    return int(crc)


@dataclass
class RecordSection:
    """One partition's records framed as a RecordBatch v2 carries them
    (offset deltas 0 .. count - 1 in order, no headers): buffers that go
    into a request end to end, the records they hold and their bytes.
    What the Kafka sink stages between a push and its publish;
    `batch_header` makes the batch around it."""

    buffers: list = field(default_factory=list)
    count: int = 0
    nbytes: int = 0

    def add(self, buf, count: int) -> None:
        self.buffers.append(buf)
        self.count += count
        self.nbytes += len(buf)


def batch_header(section: RecordSection, first_ts: int, max_ts: int,
                 base_offset: int = 0, attrs: int = 0,
                 producer_id: int = -1, producer_epoch: int = -1) -> bytes:
    """The 61 bytes of a RecordBatch v2 before its record section, the
    CRC32C chained over the header's tail and the section's buffers.
    `producer_id`/`producer_epoch` stamp the batch for transactional
    produce (the broker fences a batch whose producer epoch is older than
    the transactional id's current one)."""
    if producer_id >= 0:
        attrs |= _ATTR_TRANSACTIONAL
    # attributes, lastOffsetDelta, first and max timestamp, producerId,
    # producerEpoch, baseSequence, recordCount: what the CRC covers
    tail = struct.pack("!hiqqqhii", attrs, max(0, section.count - 1),
                       first_ts, max_ts, producer_id, producer_epoch, -1,
                       section.count)
    crc = crc32c_chain([tail, *section.buffers])
    # baseOffset, batchLength, partitionLeaderEpoch, magic, crc
    return struct.pack("!qiibI", base_offset,
                       9 + len(tail) + section.nbytes, 0, 2, crc) + tail


def _record_py(delta: int, ts_delta: int, key: Optional[bytes],
               value: Optional[bytes], headers=()) -> bytes:
    """One record with its length prefix, in Python."""
    body = [b"\x00", enc_varint(ts_delta), enc_varint(delta)]  # attributes
    for b in (key, value):
        if b is None:
            body.append(enc_varint(-1))
        else:
            body.append(enc_varint(len(b)))
            body.append(b)
    body.append(enc_varint(len(headers)))
    for hk, hv in headers:
        body.append(enc_varint(len(hk)))
        body.append(hk)
        body.append(enc_varint(len(hv)))
        body.append(hv)
    blob = b"".join(body)
    return enc_varint(len(blob)) + blob


def frame_messages(block, groups: list) -> list[bytes]:
    """The records of a message block's rows (serializers/formats.py
    `MessageBlock`), a group at a time: groups are (row indices as int64,
    first offset delta), and each comes back as one buffer of records
    with offset deltas from its first and timestamp delta 0.  Natively
    (`kafka_frame_rows`: a walk for the size, a walk that writes, both
    with the GIL released); record by record in Python under
    TRANSFERIA_TPU_NO_NATIVE=1, the same bytes."""
    import numpy as np

    from transferia_tpu import native

    cdll = native.lib()
    if cdll is None:
        pairs = block.pairs()
        return [b"".join(_record_py(first + i, 0, *pairs[r])
                         for i, r in enumerate(rows.tolist()))
                for rows, first in groups]

    def addr(a):
        return None if a is None else a.ctypes.data

    for buf, offs, null in ((block.values, block.value_offsets,
                             block.value_null),
                            (block.keys, block.key_offsets, block.key_null)):
        if buf is not None and (
                offs.dtype != np.int64 or offs.shape != (block.n + 1,)
                or offs[-1] > len(buf)
                or null is not None and null.shape != (block.n,)):
            raise ValueError(f"a message block of {block.n} messages "
                             f"whose offsets do not fit its buffers")
    args = (block.keys, addr(block.key_offsets), addr(block.key_null),
            block.values, block.value_offsets, addr(block.value_null))
    out = []
    for rows, first in groups:
        size = cdll.kafka_frame_rows(*args, rows, len(rows), first, None, 0)
        buf = native.new_bytes(None, size)
        written = cdll.kafka_frame_rows(*args, rows, len(rows), first,
                                        buf, size)
        if written != size:
            raise RuntimeError(
                f"kafka framer wrote {written} of {size} bytes")
        out.append(buf)
    return out


def _encode_records_native(records: list[Record], now: int,
                           base_ts: int) -> Optional[bytes]:
    """Record section via the C encoder (hostops.cpp); None when out of
    envelope (per-record headers) or the native lib is absent."""
    from transferia_tpu.native import lib as native_lib

    cdll = native_lib()
    if cdll is None:
        return None
    if any(r.headers for r in records):
        return None
    import numpy as np

    n = len(records)
    key_parts = [r.key or b"" for r in records]
    val_parts = [r.value or b"" for r in records]
    key_off = np.zeros(n + 1, dtype=np.int64)
    np.cumsum([len(k) for k in key_parts], out=key_off[1:])
    val_off = np.zeros(n + 1, dtype=np.int64)
    np.cumsum([len(v) for v in val_parts], out=val_off[1:])
    key_null = np.fromiter((r.key is None for r in records),
                           dtype=np.uint8, count=n)
    val_null = np.fromiter((r.value is None for r in records),
                           dtype=np.uint8, count=n)
    ts = [(r.timestamp_ms or now) - base_ts for r in records]
    ts_arr = None
    if any(ts):
        ts_arr = np.asarray(ts, dtype=np.int64)
    key_data = np.frombuffer(b"".join(key_parts), dtype=np.uint8) \
        if key_off[-1] else np.zeros(0, dtype=np.uint8)
    val_data = np.frombuffer(b"".join(val_parts), dtype=np.uint8) \
        if val_off[-1] else np.zeros(0, dtype=np.uint8)
    cap = int(key_off[-1] + val_off[-1]) + 64 * n + 64
    out = np.empty(cap, dtype=np.uint8)
    rc = cdll.kafka_encode_records(
        key_data, key_off,
        key_null.ctypes.data, val_data, val_off,
        val_null.ctypes.data,
        ts_arr.ctypes.data if ts_arr is not None else None,
        n, out, cap)
    if rc < 0:  # pragma: no cover - cap formula guarantees fit
        return None
    return out[:rc].tobytes()


def encode_record_batch(records,
                        base_offset: int = 0,
                        compression: str = "",
                        producer_id: int = -1,
                        producer_epoch: int = -1) -> bytes:
    """Records, or a RecordSection framed at push (its timestamps all
    now), -> one RecordBatch v2 blob (optionally gzip-compressed): the
    at-least-once produce's, with the header `batch_header` writes for
    every batch."""
    now = int(time.time() * 1000)
    if isinstance(records, RecordSection):
        base_ts = max_ts = now
        count = records.count
        recs = b"".join(records.buffers)
    else:
        base_ts = records[0].timestamp_ms or now if records else now
        max_ts = (records[-1].timestamp_ms or now) if records else now
        count = len(records)
        recs = _encode_records_native(records, now, base_ts) \
            if records else None
        if recs is None:
            recs = b"".join(
                _record_py(i, (r.timestamp_ms or now) - base_ts, r.key,
                           r.value, r.headers)
                for i, r in enumerate(records))
    attrs = 0
    if compression == "gzip":
        import gzip as _gzip

        recs = _gzip.compress(recs)
        attrs = _CODEC_GZIP
    elif compression:
        raise ValueError(f"unsupported compression {compression!r} "
                         f"(only gzip ships dependency-free)")
    section = RecordSection([recs], count, len(recs))
    return batch_header(section, base_ts, max_ts, base_offset, attrs,
                        producer_id, producer_epoch) + recs


class RecordView(Sequence):
    """The Records of one natively scanned blob, built when asked for: a
    fetch response holds some 73,000 of them and its consumer takes 1,024
    at a time, so the scan's index (one row a record: key start and end,
    value start and end, -1 for null, offset, timestamp) and the blob are
    what is kept.  A slice is a view (it pins the whole blob until it is
    dropped); an item or an iteration builds Records."""

    __slots__ = ("_data", "_rows")

    def __init__(self, data: bytes, rows):
        self._data = data
        self._rows = rows           # (n, 6) int64

    def __len__(self) -> int:
        return len(self._rows)

    def __getitem__(self, i):
        rows = self._rows[i]
        if isinstance(i, slice):
            return RecordView(self._data, rows)
        return next(iter(RecordView(self._data, rows[None])))

    def payload_bytes(self) -> int:
        ks, ke, vs, ve = self._rows[:, :4].T
        return int((ke - ks)[ks >= 0].sum() + (ve - vs)[vs >= 0].sum())

    def __iter__(self):
        data = self._data
        for ks, ke, vs, ve, off, ts in self._rows.tolist():
            yield Record(
                key=data[ks:ke] if ks >= 0 else None,
                value=data[vs:ve] if vs >= 0 else None,
                offset=off, timestamp_ms=ts)


def _scan_records_native(data: bytes) -> Optional[RecordView]:
    """C fast path (hostops.cpp kafka_scan_records): zero-copy scan of
    uncompressed, header-less frames; None defers to the Python walk."""
    from transferia_tpu.native import lib as native_lib

    cdll = native_lib()
    if cdll is None:
        return None
    import numpy as np

    # upper bound on records: sum of frame recordCount headers
    max_n = 0
    pos = 0
    n = len(data)
    while pos + 61 <= n:
        batch_len = struct.unpack_from("!i", data, pos + 8)[0]
        count = struct.unpack_from("!i", data, pos + 57)[0]
        if batch_len <= 0 or count < 0 or data[pos + 16] != 2:
            return None  # corrupt/foreign framing: python path decides
        max_n += count
        pos += 12 + batch_len
    arr = np.empty(max_n * 6, dtype=np.int64)
    if max_n == 0:
        return RecordView(data, arr.reshape(0, 6)) if pos else None
    blob = np.frombuffer(data, dtype=np.uint8)
    rc = cdll.kafka_scan_records(blob, len(data), arr, max_n)
    if rc < 0:
        if rc == -1:
            raise ValueError("record batch CRC mismatch or corrupt frame")
        return None  # -2: compression/headers — python path handles
    return RecordView(data, arr[:rc * 6].reshape(-1, 6))


def scan_record_batches(data: bytes) -> Sequence[Record]:
    """`decode_record_batches` for a consumer that takes a part at a
    time: a RecordView where the native scan applies, the list else."""
    native = _scan_records_native(data)
    return native if native is not None else _walk_record_batches(data)


def decode_record_batches(data: bytes) -> list[Record]:
    """RecordBatch v2 blob(s) -> Records with absolute offsets."""
    return list(scan_record_batches(data))


def payload_bytes(records: Sequence[Record]) -> int:
    """Key and value bytes of the records, what a consumer holds of them."""
    if isinstance(records, RecordView):
        return records.payload_bytes()
    return sum(len(r.key or b"") + len(r.value or b"") for r in records)


def _walk_record_batches(data: bytes) -> list[Record]:
    """The Python walk: every codec and header the scan leaves out."""
    out: list[Record] = []
    pos = 0
    n = len(data)
    while pos + 12 <= n:
        base_offset, batch_len = struct.unpack_from("!qi", data, pos)
        end = pos + 12 + batch_len
        if end > n:
            break  # partial batch at the end of a fetch response
        r = Reader(data, pos + 12)
        r.i32()            # partitionLeaderEpoch
        magic = r.i8()
        if magic != 2:
            raise ValueError(f"unsupported record batch magic {magic}")
        expect_crc = struct.unpack_from("!I", data, r.pos)[0]
        r.pos += 4
        if crc32c(data[r.pos:end]) != expect_crc:
            raise ValueError("record batch CRC mismatch")
        attributes = r.i16()
        codec = attributes & 0x07
        if codec not in (0, _CODEC_GZIP):
            raise ValueError(
                f"compressed record batch codec {codec} not supported "
                f"(gzip=1 is; snappy/lz4/zstd need codecs this "
                f"environment does not ship) — configure the producers "
                f"accordingly"
            )
        if attributes & 0x20:
            # control batch: txn commit/abort markers are broker metadata,
            # never data — skip, but keep offset accounting moving
            pos = end
            continue
        r.i32()            # lastOffsetDelta
        base_ts = r.i64()
        r.i64()            # maxTimestamp
        r.i64()            # producerId
        r.i16()            # producerEpoch
        r.i32()            # baseSequence
        count = r.i32()
        if codec == _CODEC_GZIP:
            import gzip as _gzip

            r = Reader(_gzip.decompress(bytes(r.buf[r.pos:end])))
        for _ in range(count):
            r.varint()                 # record length
            r.i8()                     # attributes
            ts_delta = r.varint()
            off_delta = r.varint()
            klen = r.varint()
            key = None
            if klen >= 0:
                key = bytes(r.buf[r.pos:r.pos + klen])
                r.pos += klen
            vlen = r.varint()
            value = None
            if vlen >= 0:
                value = bytes(r.buf[r.pos:r.pos + vlen])
                r.pos += vlen
            hcount = r.varint()
            headers = []
            for _ in range(hcount):
                hklen = r.varint()
                hk = bytes(r.buf[r.pos:r.pos + hklen])
                r.pos += hklen
                hvlen = r.varint()
                hv = b""
                if hvlen >= 0:
                    hv = bytes(r.buf[r.pos:r.pos + hvlen])
                    r.pos += hvlen
                headers.append((hk, hv))
            out.append(Record(
                key=key, value=value,
                offset=base_offset + off_delta,
                timestamp_ms=base_ts + ts_delta,
                headers=headers,
            ))
        pos = end
    return out
