"""Text-protocol row packets to columns, a column at a time.

A row packet is its fields end to end, each a length-encoded string
(0xFB alone is NULL).  `field_spans` walks one field of every row of a
block at once with numpy, so the cost is a few array passes a column and
not a Python step a cell; `gather` copies a column's bytes out of the
block, and `to_arrow` casts the text to the arrow type the column's
canonical type reads as (integers, floats, DATE and DATETIME through
arrow's own parsers; DECIMAL stays the text the server sent, as the
Postgres COPY path keeps it).
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from transferia_tpu.abstract.schema import CanonicalType

_INT = {
    CanonicalType.INT8: "int8", CanonicalType.INT16: "int16",
    CanonicalType.INT32: "int32", CanonicalType.INT64: "int64",
    CanonicalType.UINT8: "uint8", CanonicalType.UINT16: "uint16",
    CanonicalType.UINT32: "uint32", CanonicalType.UINT64: "uint64",
    CanonicalType.FLOAT: "float32", CanonicalType.DOUBLE: "float64",
}
# what `to_arrow` reads; a schema with any other type takes the per-cell
# path (provider.py _push_rows)
COLUMNAR_TYPES = frozenset(_INT) | {
    CanonicalType.DECIMAL, CanonicalType.UTF8, CanonicalType.STRING,
    CanonicalType.DATE, CanonicalType.TIMESTAMP,
}


def field_spans(buf: np.ndarray, pos: np.ndarray
                ) -> tuple[np.ndarray, np.ndarray, Optional[np.ndarray],
                           np.ndarray]:
    """One field of every row: (where its bytes start, their length, a
    NULL mask or None, where the next field starts)."""
    first = buf[pos]
    length = first.astype(np.int64)
    start = pos + 1
    null = None
    wide = first >= 0xFB
    if wide.any():
        null = first == 0xFB
        if null.any():
            length[null] = 0
        else:
            null = None
        for marker, width in ((0xFC, 2), (0xFD, 3), (0xFE, 8)):
            at = np.flatnonzero(first == marker)
            if len(at):
                p = pos[at] + 1
                v = np.zeros(len(at), dtype=np.int64)
                for k in range(width):
                    v |= buf[p + k].astype(np.int64) << (8 * k)
                length[at] = v
                start[at] += width
    return start, length, null, start + length


def gather(buf: np.ndarray, start: np.ndarray, length: np.ndarray
           ) -> tuple[np.ndarray, np.ndarray]:
    """(the fields' bytes end to end, int64 offsets of n + 1)."""
    offsets = np.zeros(len(start) + 1, dtype=np.int64)
    np.cumsum(length, out=offsets[1:])
    index = np.arange(offsets[-1], dtype=np.int64)
    index += np.repeat(start - offsets[:-1], length)
    return buf[index], offsets


def to_arrow(ctype: CanonicalType, data: np.ndarray, offsets: np.ndarray,
             null: Optional[np.ndarray]):
    """One column's text as the arrow array `ColumnBatch.from_arrow`
    takes for `ctype`; raises pyarrow.ArrowInvalid for text arrow cannot
    read as that type (a zero date, a bare `inf`)."""
    import pyarrow as pa

    n = len(offsets) - 1
    validity = None
    nulls = 0
    if null is not None:
        nulls = int(null.sum())
        validity = pa.py_buffer(np.packbits(~null, bitorder="little"))
    binary = ctype == CanonicalType.STRING
    small = offsets[-1] < (1 << 31)
    if small:
        offsets = offsets.astype(np.int32)
        typ = pa.binary() if binary else pa.string()
    else:
        typ = pa.large_binary() if binary else pa.large_string()
    arr = pa.Array.from_buffers(
        typ, n, [validity, pa.py_buffer(offsets), pa.py_buffer(data)],
        nulls)
    if ctype in _INT:
        return arr.cast(getattr(pa, _INT[ctype])())
    if ctype == CanonicalType.DATE:
        return arr.cast(pa.date32())
    if ctype == CanonicalType.TIMESTAMP:
        return arr.cast(pa.timestamp("us"))
    return arr


def decode(data: bytes, starts, schema):
    """The rows of one block as a pyarrow RecordBatch in `schema`'s
    columns."""
    import pyarrow as pa

    buf = np.frombuffer(data, dtype=np.uint8)
    pos = np.asarray(starts, dtype=np.int64)
    arrays = []
    for cs in schema:
        start, length, null, pos = field_spans(buf, pos)
        col, offsets = gather(buf, start, length)
        arrays.append(to_arrow(cs.data_type, col, offsets, null))
    return pa.RecordBatch.from_arrays(arrays, names=schema.names())


def rows_as_dicts(data: bytes, starts, names: list[str]) -> list[dict]:
    """The per-cell reading `MySQLConnection.query` gives, of one block:
    for the schemas `decode` does not take."""
    from transferia_tpu.providers.mysql.wire import MySQLConnection

    lenenc = MySQLConnection._lenenc
    rows = []
    for pos in starts:
        vals = []
        for _ in names:
            ln, pos = lenenc(data, pos)
            if ln is None:
                vals.append(None)
            else:
                vals.append(data[pos:pos + ln].decode("utf-8", "replace"))
                pos += ln
        rows.append(dict(zip(names, vals)))
    return rows
