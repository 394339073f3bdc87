"""Serializer implementations (pkg/serializer/{json,csv,parquet,raw}.go and
serializer/queue/{debezium,json,native,mirror}*.go)."""

from __future__ import annotations

import abc
import csv
import io
import itertools
import json
from dataclasses import dataclass
from typing import Any, Optional, Sequence

import numpy as np

from transferia_tpu.abstract.change_item import ChangeItem
from transferia_tpu.abstract.interfaces import Batch, is_columnar
from transferia_tpu.columnar.batch import ColumnBatch


def _rows_of(batch: Batch) -> list[ChangeItem]:
    if is_columnar(batch):
        return batch.to_rows()
    return [it for it in batch if it.is_row_event()]


class BatchSerializer(abc.ABC):
    """Whole-batch byte encoder (serializer/interface.go:17)."""

    @abc.abstractmethod
    def serialize(self, batch: Batch) -> bytes:
        ...


class JsonSerializer(BatchSerializer):
    """JSON lines of row value maps."""

    def __init__(self, add_meta: bool = False):
        self.add_meta = add_meta

    def serialize(self, batch: Batch) -> bytes:
        buf = io.BytesIO()
        for it in _rows_of(batch):
            row: dict[str, Any] = it.as_dict()
            if self.add_meta:
                row = {"__kind": it.kind.value,
                       "__table": str(it.table_id), **row}
            buf.write(json.dumps(row, separators=(",", ":"),
                                 default=_json_default).encode())
            buf.write(b"\n")
        return buf.getvalue()


def _json_default(v):
    if isinstance(v, bytes):
        return v.decode("utf-8", errors="replace")
    return str(v)


class CsvSerializer(BatchSerializer):
    """RFC-4180 CSV (pkg/csv splitter counterpart on the write side)."""

    def __init__(self, header: bool = False, delimiter: str = ","):
        self.header = header
        self.delimiter = delimiter

    def serialize(self, batch: Batch) -> bytes:
        out = io.StringIO()
        w = csv.writer(out, delimiter=self.delimiter, lineterminator="\n")
        rows = _rows_of(batch)
        if not rows:
            return b""
        if self.header:
            w.writerow(rows[0].column_names)
        for it in rows:
            w.writerow([
                v.decode("utf-8", "replace") if isinstance(v, bytes)
                else ("" if v is None else v)
                for v in it.column_values
            ])
        return out.getvalue().encode()


class ParquetSerializer(BatchSerializer):
    """Arrow-native parquet encoding — columnar batches never re-row."""

    def __init__(self, compression: str = "snappy"):
        self.compression = compression

    def serialize(self, batch: Batch) -> bytes:
        import pyarrow as pa
        import pyarrow.parquet as pq

        if not is_columnar(batch):
            rows = _rows_of(batch)
            if not rows:
                return b""
            batch = ColumnBatch.from_rows(rows)
        rb = batch.to_arrow()
        sink = io.BytesIO()
        pq.write_table(pa.Table.from_batches([rb]), sink,
                       compression=self.compression)
        return sink.getvalue()


class RawSerializer(BatchSerializer):
    """First column's raw bytes, newline-joined (serializer/raw.go)."""

    def __init__(self, column: str = "data"):
        self.column = column

    def serialize(self, batch: Batch) -> bytes:
        out = io.BytesIO()
        for it in _rows_of(batch):
            v = it.value(self.column)
            if v is None and it.column_values:
                v = it.column_values[0]
            if isinstance(v, str):
                v = v.encode()
            out.write(v or b"")
            out.write(b"\n")
        return out.getvalue()


@dataclass
class MessageBlock:
    """A batch's messages end to end, with no object per message: message
    i's value is values[value_offsets[i]:value_offsets[i + 1]] (int64
    offsets, n + 1 of them) and its key likewise; `keys` None means every
    key is null.  *_null (uint8, n) flag the messages whose key or value
    is null, where any is.  What the Debezium emitter's native renderer
    hands a sink, a batch's rows (`DebeziumEmitter.emit_block`), and
    what pairs become to be framed (`from_pairs`)."""

    n: int
    values: bytes
    value_offsets: np.ndarray
    keys: Optional[bytes] = None
    key_offsets: Optional[np.ndarray] = None
    key_null: Optional[np.ndarray] = None
    value_null: Optional[np.ndarray] = None

    @staticmethod
    def _laid(parts: Sequence) -> tuple:
        """(buffer, offsets, null flags or None) of bytes-or-None parts."""
        lens = np.fromiter(map(len, (p or b"" for p in parts)),
                           dtype=np.int64, count=len(parts))
        offsets = np.zeros(len(parts) + 1, dtype=np.int64)
        np.cumsum(lens, out=offsets[1:])
        null = np.fromiter((p is None for p in parts), dtype=np.uint8,
                           count=len(parts))
        return (b"".join(p or b"" for p in parts), offsets,
                null if null.any() else None)

    @classmethod
    def from_pairs(cls, pairs: Sequence) -> "MessageBlock":
        """(key, value) pairs laid end to end, once per batch."""
        keys = [k for k, _ in pairs]
        values, value_offsets, value_null = cls._laid(
            [v for _, v in pairs])
        block = cls(len(pairs), values, value_offsets,
                    value_null=value_null)
        if any(k is not None for k in keys):
            block.keys, block.key_offsets, block.key_null = \
                cls._laid(keys)
        return block

    def pairs(self) -> list[tuple[Optional[bytes], Optional[bytes]]]:
        """The messages as (key, value) pairs of bytes, the one place a
        block is cut into objects."""
        def cut(buf, offsets, null) -> list:
            offs = offsets.tolist()
            out = list(map(buf.__getitem__, map(slice, offs, offs[1:])))
            if null is not None:
                out = [None if z else b for b, z in zip(out, null.tolist())]
            return out

        values = cut(self.values, self.value_offsets, self.value_null)
        if self.keys is None:
            return list(zip(itertools.repeat(None), values))
        return list(zip(cut(self.keys, self.key_offsets, self.key_null),
                        values))


class QueueSerializer(abc.ABC):
    """Per-row (key, value) pairs for message brokers."""

    @abc.abstractmethod
    def serialize_messages(self, batch: Batch
                           ) -> list[tuple[bytes, Optional[bytes]]]:
        ...

    def serialize_block(self, batch: Batch):
        """The batch's messages as one MessageBlock where the serializer
        renders them into buffers, else as serialize_messages' pairs."""
        return self.serialize_messages(batch)


class JsonQueueSerializer(QueueSerializer):
    def serialize_messages(self, batch):
        out = []
        for it in _rows_of(batch):
            key = json.dumps(
                {c.name: it.value(c.name)
                 for c in (it.table_schema.key_columns()
                           if it.table_schema else [])},
                separators=(",", ":"), default=_json_default,
            ).encode()
            value = json.dumps(it.as_dict(), separators=(",", ":"),
                               default=_json_default).encode()
            out.append((key, value))
        return out


class NativeQueueSerializer(QueueSerializer):
    def serialize_messages(self, batch):
        return [
            (str(it.table_id).encode(),
             json.dumps(it.to_json(), separators=(",", ":"),
                        default=_json_default).encode())
            for it in _rows_of(batch)
        ]


class DebeziumQueueSerializer(QueueSerializer):
    """config: emitter params + snapshot: bool (emits op 'r' instead of 'c'
    for initial-load rows, Debezium's snapshot-read marker)."""

    def __init__(self, snapshot: bool = False, **cfg):
        from transferia_tpu.debezium import DebeziumEmitter

        self.emitter = DebeziumEmitter(**cfg)
        self.snapshot = snapshot

    def serialize_messages(self, batch):
        return self.emitter.emit_batch(batch, snapshot=self.snapshot)

    def serialize_block(self, batch):
        return self.emitter.emit_block(batch, snapshot=self.snapshot)


class MirrorQueueSerializer(QueueSerializer):
    """Raw pass-through for queue mirroring (queue/mirror: key/data cols
    from the blank parser's RAW_SCHEMA)."""

    def serialize_messages(self, batch):
        out = []
        for it in _rows_of(batch):
            key = it.value("key") or b""
            data = it.value("data") or b""
            if isinstance(key, str):
                key = key.encode()
            if isinstance(data, str):
                data = data.encode()
            out.append((key, data))
        return out


_SERIALIZERS = {
    "json": JsonSerializer,
    "csv": CsvSerializer,
    "parquet": ParquetSerializer,
    "raw": RawSerializer,
}

def _raw_column_queue_serializer(**cfg):
    from transferia_tpu.serializers.batch import RawColumnQueueSerializer

    return RawColumnQueueSerializer(**cfg)


_QUEUE_SERIALIZERS = {
    "json": JsonQueueSerializer,
    "native": NativeQueueSerializer,
    "debezium": DebeziumQueueSerializer,
    "mirror": MirrorQueueSerializer,
    "raw_column": _raw_column_queue_serializer,
}


def make_serializer(fmt: str, concurrency: int = 1,
                    threshold: int = 0, **cfg) -> BatchSerializer:
    """Build a serializer; concurrency > 1 wraps row-shaped formats in the
    threshold-gated parallel chunker (batch.go:28).  Parquet is a
    whole-file format and is never wrapped."""
    if fmt not in _SERIALIZERS:
        raise KeyError(
            f"unknown serializer {fmt!r}; known: {sorted(_SERIALIZERS)}"
        )
    inner = _SERIALIZERS[fmt](**cfg)
    # whole-file formats and headered csv must not be chunk-concatenated
    # (every chunk would re-emit the header mid-file)
    unwrappable = fmt == "parquet" or (fmt == "csv" and cfg.get("header"))
    if concurrency > 1 and not unwrappable:
        from transferia_tpu.serializers.batch import (
            DEFAULT_THRESHOLD,
            ConcurrentBatchSerializer,
        )

        return ConcurrentBatchSerializer(
            inner, concurrency=concurrency,
            threshold=threshold or DEFAULT_THRESHOLD)
    return inner


def make_queue_serializer(fmt: str, threads: int = 1,
                          threshold: int = 0, **cfg) -> QueueSerializer:
    """Build a queue serializer; threads > 1 returns the ordered parallel
    wrapper with one inner serializer per worker
    (queue/debezium_multithreading.go)."""
    if fmt not in _QUEUE_SERIALIZERS:
        raise KeyError(
            f"unknown queue serializer {fmt!r}; known: "
            f"{sorted(_QUEUE_SERIALIZERS)}"
        )
    if threads > 1:
        from transferia_tpu.serializers.batch import (
            DEFAULT_THRESHOLD,
            ConcurrentQueueSerializer,
        )

        return ConcurrentQueueSerializer(
            lambda: _QUEUE_SERIALIZERS[fmt](**cfg),
            concurrency=threads,
            threshold=threshold or DEFAULT_THRESHOLD)
    return _QUEUE_SERIALIZERS[fmt](**cfg)
