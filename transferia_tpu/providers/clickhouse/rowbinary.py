"""RowBinary encoder/decoder.

RowBinary is row-major (per row: each column's fixed-width value or
varint-length-prefixed bytes), which fights columnar layouts.
`encode_rowbinary` hands the whole batch to the native writer
(`native/hostops.cpp`: `rowbinary_size`, then `rowbinary_write` straight
from the columns' own buffers into one output, the GIL released), and
does per column only what has to be numpy: the cast to the wire dtype.
The numpy encoder below it is the same bytes without the library
(`TRANSFERIA_TPU_NO_NATIVE=1`) and the reference the tests compare the
writer with: it never loops over rows in Python - per column it computes
each row's field byte-length, derives global row offsets with cumsums,
and scatters column bytes into the output with flat numpy gathers.  The
decoder is the inverse and powers the CH snapshot source.

Type wire formats (ClickHouse RowBinary):
  ints/floats: little-endian fixed width
  String:      LEB128 varint length + bytes
  Date:        uint16 days since epoch; Date32: int32 days
  DateTime:    uint32 seconds; DateTime64(6): int64 microseconds
  Bool:        uint8
  Nullable(T): 0x00 value-follows / 0x01 null (no value)
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from transferia_tpu.abstract.schema import CanonicalType
from transferia_tpu.columnar.batch import Column, ColumnBatch, _offsets_from_lengths
from transferia_tpu.native import new_bytes


def _leb128_lengths(values: np.ndarray) -> np.ndarray:
    """Byte count of each value's LEB128 varint."""
    out = np.ones(len(values), dtype=np.int64)
    v = values.astype(np.int64)
    thresh = 128
    while (v >= thresh).any():
        out += v >= thresh
        thresh <<= 7
    return out


def _encode_varints(values: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """values -> (flat varint bytes, per-value byte length)."""
    n = len(values)
    vlens = _leb128_lengths(values)
    offsets = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(vlens, out=offsets[1:])
    out = np.zeros(int(offsets[-1]), dtype=np.uint8)
    v = values.astype(np.uint64).copy()
    max_bytes = int(vlens.max()) if n else 0
    for b in range(max_bytes):
        active = vlens > b
        last = vlens == b + 1
        byte = (v & 0x7F).astype(np.uint8)
        byte = np.where(last, byte, byte | 0x80)
        idx = (offsets[:-1] + b)[active]
        out[idx] = byte[active]
        v >>= np.uint64(7)
    return out, vlens


def _fixed_width(ctype: CanonicalType) -> Optional[tuple[np.dtype, int]]:
    """Wire dtype for fixed-width canonical types."""
    table = {
        CanonicalType.INT8: np.dtype("<i1"),
        CanonicalType.INT16: np.dtype("<i2"),
        CanonicalType.INT32: np.dtype("<i4"),
        CanonicalType.INT64: np.dtype("<i8"),
        CanonicalType.UINT8: np.dtype("<u1"),
        CanonicalType.UINT16: np.dtype("<u2"),
        CanonicalType.UINT32: np.dtype("<u4"),
        CanonicalType.UINT64: np.dtype("<u8"),
        CanonicalType.FLOAT: np.dtype("<f4"),
        CanonicalType.DOUBLE: np.dtype("<f8"),
        CanonicalType.BOOLEAN: np.dtype("<u1"),
        CanonicalType.DATE: np.dtype("<i4"),      # as Date32
        CanonicalType.DATETIME: np.dtype("<u4"),
        CanonicalType.TIMESTAMP: np.dtype("<i8"),  # DateTime64(6)
        CanonicalType.INTERVAL: np.dtype("<i8"),
    }
    dt = table.get(ctype)
    return (dt, dt.itemsize) if dt is not None else None


class _EncodedColumn:
    """Per-row encoded field bytes for one column."""

    __slots__ = ("data", "lens")

    def __init__(self, data: np.ndarray, lens: np.ndarray):
        self.data = data   # flat uint8
        self.lens = lens   # (n,) int64 per-row field length


def _encode_column(col: Column, nullable: bool) -> _EncodedColumn:
    n = col.n_rows
    null_mask = None
    if col.validity is not None:
        null_mask = ~col.validity
    fixed = _fixed_width(col.ctype)
    if fixed is not None:
        dt, width = fixed
        vals = col.data.astype(dt.base, copy=False).astype(dt)
        body = np.ascontiguousarray(vals).view(np.uint8).reshape(n, width)
        if nullable:
            prefix = np.zeros((n, 1), dtype=np.uint8)
            if null_mask is not None:
                prefix[null_mask, 0] = 1
                body = body.copy()
                body[null_mask] = 0
                data = np.concatenate([prefix, body], axis=1)
                lens = np.where(null_mask, 1, 1 + width).astype(np.int64)
                # null rows carry only the prefix byte: compact via gather
                flat = data.reshape(-1)
                keep = np.ones((n, 1 + width), dtype=bool)
                keep[null_mask, 1:] = False
                return _EncodedColumn(flat[keep.reshape(-1)], lens)
            data = np.concatenate([prefix, body], axis=1)
            return _EncodedColumn(
                data.reshape(-1), np.full(n, 1 + width, dtype=np.int64)
            )
        if null_mask is not None and null_mask.any():
            body = body.copy()
            body[null_mask] = 0  # non-nullable target: nulls become zero
        return _EncodedColumn(
            body.reshape(-1), np.full(n, width, dtype=np.int64)
        )
    # var-width: varint(len) + bytes
    lens = (col.offsets[1:] - col.offsets[:-1]).astype(np.int64)
    if null_mask is not None:
        lens = np.where(null_mask, 0, lens)
    varint_bytes, varint_lens = _encode_varints(lens)
    field_lens = varint_lens + lens
    prefix_len = 0
    if nullable:
        field_lens = field_lens + 1
        prefix_len = 1
        if null_mask is not None:
            field_lens = np.where(null_mask, 1, field_lens)
    out_offsets = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(field_lens, out=out_offsets[1:])
    out = np.zeros(int(out_offsets[-1]), dtype=np.uint8)
    pos = out_offsets[:-1]
    if nullable:
        if null_mask is not None:
            out[pos[null_mask]] = 1
        pos = pos + prefix_len
        if null_mask is not None:
            # null rows: only the prefix byte, stop here for them
            active = ~null_mask
        else:
            active = np.ones(n, dtype=bool)
    else:
        active = np.ones(n, dtype=bool) if null_mask is None else ~null_mask
        if null_mask is not None and null_mask.any():
            # non-nullable target: null strings encode as empty
            pass
    # scatter varints
    vo = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(varint_lens, out=vo[1:])
    if nullable and null_mask is not None:
        write_varint = active
    else:
        write_varint = np.ones(n, dtype=bool)
    sel = np.nonzero(write_varint)[0]
    if len(sel):
        vl = varint_lens[sel]
        total_v = int(vl.sum())
        dst = np.repeat(pos[sel], vl) + (
            np.arange(total_v) - np.repeat(
                np.concatenate([[0], np.cumsum(vl)[:-1]]), vl
            )
        )
        src = np.repeat(vo[:-1][sel], vl) + (
            np.arange(total_v) - np.repeat(
                np.concatenate([[0], np.cumsum(vl)[:-1]]), vl
            )
        )
        out[dst] = varint_bytes[src]
    # scatter string bodies
    body_sel = np.nonzero(active & (lens > 0))[0]
    if len(body_sel):
        bl = lens[body_sel]
        total_b = int(bl.sum())
        inner = np.arange(total_b) - np.repeat(
            np.concatenate([[0], np.cumsum(bl)[:-1]]), bl
        )
        dst = np.repeat(pos[body_sel] + varint_lens[body_sel], bl) + inner
        src = np.repeat(col.offsets[:-1][body_sel].astype(np.int64), bl) \
            + inner
        out[dst] = col.data[src]
    return _EncodedColumn(out, field_lens)


def encoder_path() -> str:
    """Which encoder `encode_rowbinary` runs, as the `serialize` span
    names it: "native" unless the library is switched off."""
    from transferia_tpu.native import lib

    return "numpy" if lib() is None else "native"


def encode_rowbinary(batch: ColumnBatch,
                     nullable: Optional[dict[str, bool]] = None) -> bytes:
    """ColumnBatch -> RowBinary bytes (column order = batch.columns order)."""
    if batch.n_rows == 0:
        return b""
    from transferia_tpu.native import lib

    nullable = nullable or {}
    cdll = lib()
    if cdll is None:
        return _encode_numpy(batch, nullable)
    return _encode_native(cdll, batch, nullable)


def _encode_native(cdll, batch: ColumnBatch,
                   nullable: dict[str, bool]) -> bytes:
    n = batch.n_rows
    n_cols = len(batch.columns)
    widths = np.zeros(n_cols, dtype=np.int32)
    data = np.zeros(n_cols, dtype=np.uint64)
    offsets = np.zeros(n_cols, dtype=np.uint64)
    validity = np.zeros(n_cols, dtype=np.uint64)
    nullable_flags = np.zeros(n_cols, dtype=np.uint8)
    held = []  # the buffers whose addresses the writer reads
    for c, (name, col) in enumerate(batch.columns.items()):
        fixed = _fixed_width(col.ctype)
        if fixed is not None:
            dt, widths[c] = fixed
            values = np.ascontiguousarray(col.data.astype(dt, copy=False))
            if values.shape != (n,):
                raise ValueError(
                    f"column {name}: {values.shape} values in a batch of "
                    f"{n} rows")
        else:
            values = np.ascontiguousarray(col.data, dtype=np.uint8)
            off = np.ascontiguousarray(col.offsets, dtype=np.int32)
            if off.shape != (n + 1,) or off[0] < 0 \
                    or off[-1] > len(values):
                raise ValueError(
                    f"column {name}: offsets do not fit {n} rows over "
                    f"{len(values)} bytes")
            offsets[c] = off.ctypes.data
            held.append(off)
        data[c] = values.ctypes.data
        held.append(values)
        if col.validity is not None:
            valid = np.ascontiguousarray(col.validity, dtype=np.bool_)
            if valid.shape != (n,):
                raise ValueError(
                    f"column {name}: validity of {valid.shape} in a "
                    f"batch of {n} rows")
            validity[c] = valid.ctypes.data
            held.append(valid)
        nullable_flags[c] = nullable.get(name, col.validity is not None)
    total = cdll.rowbinary_size(n, n_cols, widths, offsets, validity,
                                nullable_flags)
    if total < 0:
        raise ValueError("a var-width column's offsets decrease")
    # the result itself, uninitialised until the writer has filled it
    out = new_bytes(None, total)
    written = cdll.rowbinary_write(n, n_cols, widths, data, offsets,
                                   validity, nullable_flags, out)
    if written != total:
        raise RuntimeError(
            f"rowbinary writer wrote {written} of {total} bytes")
    return out


def _encode_numpy(batch: ColumnBatch, nullable: dict[str, bool]) -> bytes:
    n = batch.n_rows
    encoded = [
        _encode_column(col, nullable.get(name,
                                         col.validity is not None))
        for name, col in batch.columns.items()
    ]
    row_lens = np.zeros(n, dtype=np.int64)
    for e in encoded:
        row_lens += e.lens
    row_offsets = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(row_lens, out=row_offsets[1:])
    out = np.zeros(int(row_offsets[-1]), dtype=np.uint8)
    field_start = row_offsets[:-1].copy()
    for e in encoded:
        lens = e.lens
        total = int(lens.sum())
        if total:
            src_off = np.zeros(n, dtype=np.int64)
            np.cumsum(lens[:-1], out=src_off[1:])
            inner = np.arange(total) - np.repeat(src_off, lens)
            dst = np.repeat(field_start, lens) + inner
            out[dst] = e.data
        field_start += lens
    return out.tobytes()


# ---------------------------------------------------------------------------
# Decoder (CH snapshot source + tests)
# ---------------------------------------------------------------------------

class _NeedMore(Exception):
    """Row parse ran off the end of the buffer (partial network chunk)."""


def _wire_fixed(cs) -> Optional[tuple[np.dtype, int]]:
    """Per-column wire format, honoring the CH-native original type: a
    ClickHouse `Date` column is uint16 days on the wire while our canonical
    DATE encodes as Date32 (int32)."""
    if cs.original_type == "ch:Date":
        return np.dtype("<u2"), 2
    return _fixed_width(cs.data_type)


def _parse_row(buf: memoryview, pos: int, schema, nullable: dict,
               fixed: dict, out: dict) -> int:
    n = len(buf)
    for c in schema:
        if nullable.get(c.name, False):
            if pos >= n:
                raise _NeedMore()
            flag = buf[pos]
            pos += 1
            if flag == 1:
                out[c.name].append(None)
                continue
        fx = fixed[c.name]
        if fx is not None:
            dt, width = fx
            if pos + width > n:
                raise _NeedMore()
            v = np.frombuffer(buf[pos:pos + width], dtype=dt)[0]
            if c.data_type == CanonicalType.BOOLEAN:
                out[c.name].append(bool(v))
            elif c.data_type.is_float:
                out[c.name].append(float(v))
            else:
                out[c.name].append(int(v))
            pos += width
        else:
            ln = 0
            shift = 0
            while True:
                if pos >= n:
                    raise _NeedMore()
                b = buf[pos]
                pos += 1
                ln |= (b & 0x7F) << shift
                if not b & 0x80:
                    break
                shift += 7
            if pos + ln > n:
                raise _NeedMore()
            raw = bytes(buf[pos:pos + ln])
            pos += ln
            if c.data_type == CanonicalType.STRING:
                out[c.name].append(raw)
            else:
                out[c.name].append(raw.decode("utf-8", "replace"))
    return pos


def decode_rowbinary(data: bytes, schema,
                     nullable: Optional[dict[str, bool]] = None
                     ) -> ColumnBatch:
    """RowBinary bytes -> ColumnBatch (whole buffer; tests + small reads)."""
    from transferia_tpu.abstract.schema import TableID

    nullable = nullable or {}
    buf = memoryview(data)
    pos = 0
    cols: dict[str, list] = {c.name: [] for c in schema}
    fixed = {c.name: _wire_fixed(c) for c in schema}
    while pos < len(buf):
        pos = _parse_row(buf, pos, schema, nullable, fixed, cols)
    return ColumnBatch.from_pydict(TableID("", "decoded"), schema, cols)


def decode_rowbinary_stream(read_fn, schema,
                            nullable: Optional[dict[str, bool]] = None,
                            batch_rows: int = 131_072,
                            chunk_bytes: int = 8 << 20):
    """Incremental decode: read_fn(n) -> bytes ('' = EOF).  Yields
    ColumnBatches of up to batch_rows rows in constant memory — partial
    rows at chunk boundaries carry over to the next chunk."""
    from transferia_tpu.abstract.schema import TableID

    nullable = nullable or {}
    fixed = {c.name: _wire_fixed(c) for c in schema}
    leftover = b""
    cols: dict[str, list] = {c.name: [] for c in schema}
    rows = 0
    eof = False
    while not eof:
        chunk = read_fn(chunk_bytes)
        if not chunk:
            eof = True
        data = leftover + chunk if leftover else chunk
        buf = memoryview(data)
        pos = 0
        while pos < len(buf):
            row_start = pos
            try:
                pos = _parse_row(buf, pos, schema, nullable, fixed, cols)
            except _NeedMore:
                if eof:
                    raise ValueError(
                        "rowbinary stream truncated mid-row"
                    ) from None
                pos = row_start
                break
            rows += 1
            if rows >= batch_rows:
                yield ColumnBatch.from_pydict(
                    TableID("", "decoded"), schema, cols
                )
                cols = {c.name: [] for c in schema}
                rows = 0
        leftover = bytes(buf[pos:])
    if rows:
        yield ColumnBatch.from_pydict(TableID("", "decoded"), schema, cols)
