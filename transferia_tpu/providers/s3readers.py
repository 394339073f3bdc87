"""S3 object format readers (reference: pkg/providers/s3/reader/registry/
— csv/json/line/nginx/parquet/proto with schema inference,
reader/abstract.go:40-52).

A Reader turns one object into ColumnBatches.  Formats:
  parquet — arrow row groups straight to columnar (zero pivot)
  csv     — arrow CSV, schema declared (`output_schema`) or inferred
  jsonl   — newline-delimited JSON, schema declared (`output_schema`) or
            inferred from a sample; read a block at a time by the block
            decode the JSON parser has (`read_json_lines`, which the
            `fs` source calls too)
  line    — each line one row (utf8) + system columns
  nginx   — nginx log_format template parsing ($var tokens), typed fields
  proto   — varint length-prefixed protobuf frames through the protobuf
            parser plugin (descriptor config)

line/nginx add the reference's system columns __file_name/__row_index
(reader/abstract.go:16-17, AppendSystemColsTableSchema) as primary keys so
replicated rows stay addressable.  Parse failures follow unparsed_policy:
"route" sends them to the parsers' `_unparsed` system table
(pkg/parsers/utils.go:145), "skip" drops them counted, "fail" raises.
"""

from __future__ import annotations

import abc
import io
import json
import logging
import re
from typing import Callable, Iterable, Optional

from transferia_tpu.abstract.errors import CategorizedError
from transferia_tpu.abstract.schema import (
    CanonicalType,
    ColSchema,
    TableID,
    TableSchema,
)
from transferia_tpu.columnar.batch import ColumnBatch, arrow_to_table_schema
from transferia_tpu.parsers.base import Message, unparsed_batch

logger = logging.getLogger(__name__)

FILE_NAME_COL = "__file_name"
ROW_INDEX_COL = "__row_index"

Pusher = Callable[[ColumnBatch], None]


class ReaderError(CategorizedError):
    def __init__(self, message: str):
        super().__init__(CategorizedError.SOURCE, message)


class Reader(abc.ABC):
    """One-object reader; fs is an fsspec filesystem."""

    # the source's `output_schema`, for the formats that take one
    declared: Optional[TableSchema] = None

    @abc.abstractmethod
    def infer_schema(self, fs, path: str) -> TableSchema:
        ...

    @abc.abstractmethod
    def read(self, fs, path: str, tid: TableID, schema: TableSchema,
             batch_rows: int, pusher: Pusher) -> None:
        ...

    def estimate_rows(self, fs, path: str) -> int:
        return 0


def _system_cols() -> list[ColSchema]:
    return [
        ColSchema(FILE_NAME_COL, CanonicalType.UTF8, primary_key=True),
        ColSchema(ROW_INDEX_COL, CanonicalType.INT64, primary_key=True),
    ]


class ParquetReader(Reader):
    def infer_schema(self, fs, path: str) -> TableSchema:
        import pyarrow.parquet as pq

        with fs.open(path, "rb") as fh:
            return arrow_to_table_schema(pq.read_schema(fh))

    def estimate_rows(self, fs, path: str) -> int:
        import pyarrow.parquet as pq

        with fs.open(path, "rb") as fh:
            return pq.ParquetFile(fh).metadata.num_rows

    def read(self, fs, path, tid, schema, batch_rows, pusher) -> None:
        import pyarrow.parquet as pq

        with fs.open(path, "rb") as fh:
            pf = pq.ParquetFile(fh)
            for rb in pf.iter_batches(batch_size=batch_rows):
                if rb.num_rows:
                    batch = ColumnBatch.from_arrow(rb, tid, schema)
                    batch.read_bytes = rb.nbytes
                    pusher(batch)


def csv_convert_options(schema: Optional[TableSchema]):
    """A declared schema's types for arrow's CSV reader: every declared
    column converted to its type (DateTime from `YYYY-MM-DD hh:mm:ss`,
    Date from `YYYY-MM-DD`), never guessed."""
    import pyarrow.csv as pacsv

    from transferia_tpu.columnar.batch import _ARROW_TYPES

    if schema is None:
        return pacsv.ConvertOptions()
    return pacsv.ConvertOptions(
        column_types={c.name: _ARROW_TYPES[c.data_type] for c in schema},
        include_columns=schema.names())


class CsvReader(Reader):
    def __init__(self, declared: Optional[TableSchema] = None):
        self.declared = declared

    def infer_schema(self, fs, path: str) -> TableSchema:
        import pyarrow.csv as pacsv

        if self.declared is not None:
            return self.declared
        with fs.open(path, "rb") as fh:
            head = fh.read(1 << 20)
        with pacsv.open_csv(io.BytesIO(head)) as reader:
            return arrow_to_table_schema(reader.schema)

    def read(self, fs, path, tid, schema, batch_rows, pusher) -> None:
        import pyarrow.csv as pacsv

        with fs.open(path, "rb") as fh:
            data = fh.read()
        with pacsv.open_csv(
                io.BytesIO(data),
                convert_options=csv_convert_options(self.declared)
        ) as reader:
            for rb in reader:
                if rb.num_rows:
                    batch = ColumnBatch.from_arrow(rb, tid, schema)
                    batch.read_bytes = rb.nbytes
                    pusher(batch)


# a text object is read this many bytes at a time; a block handed to the
# decode ends at a newline
JSONL_BLOCK_BYTES = 16 << 20


def _text_blocks(fh, path: str):
    """The object's bytes in blocks that end at a newline (the last one
    where the object ends), one `file_read` span a read."""
    from transferia_tpu.stats import trace

    def read() -> bytes:
        with trace.span("file_read") as sp:
            chunk = fh.read(JSONL_BLOCK_BYTES)
            if sp:
                sp.add(bytes=len(chunk), path=path)
        return chunk

    tail = b""
    chunk = read()
    while chunk:
        following = read()
        cut = chunk.rfind(b"\n") + 1 if following else len(chunk)
        if cut:
            yield tail + chunk[:cut], not following
            tail = chunk[cut:]
        else:               # a line longer than a read
            tail += chunk
        chunk = following


def read_json_lines(fh, path: str, tid: TableID, schema: TableSchema,
                    batch_rows: int, pusher: Pusher,
                    use_threads: bool = True) -> None:
    """The one JSON-lines reader of the file sources (`fs` and `s3`): the
    object a block at a time through the JSON parser's block decode
    (parsers/generic.py::JsonBlockDecoder: arrow's C++ reader with the
    schema's types, the GIL released, never a list of dicts), the blocks'
    tables cut into ColumnBatches of at most `batch_rows`.  A line the
    block path cannot take goes through the row path alone (one
    `json.loads`) and is counted (`jsonl_rows` - `jsonl_rows_block`); a
    line neither takes fails the read.  A schema the block decode does
    not support (ANY columns, as inference gives for nested values) goes
    through the row path whole.  `use_threads`: whether arrow spreads a
    block over its own pool (a caller whose parts already decode side by
    side says no)."""
    import pyarrow as pa

    from transferia_tpu.parsers.generic import (
        JsonBlockDecoder,
        JsonLineError,
        row_values,
    )
    from transferia_tpu.stats import trace
    from transferia_tpu.stats.trace import TELEMETRY

    fields = list(schema)
    carry = None          # decoded rows short of a batch: an arrow table

    def by_block(block: bytes, last: bool):
        nonlocal carry
        tbl, taken = dec.decode(block)
        rows = tbl.num_rows
        if carry is not None:
            tbl = pa.concat_tables([carry, tbl])
        n = tbl.num_rows
        whole = n if last else n - n % batch_rows
        batches = []
        for lo in range(0, whole, batch_rows):
            rb = tbl.slice(lo, min(batch_rows, whole - lo)) \
                .combine_chunks().to_batches()[0]
            batches.append(ColumnBatch.from_arrow(rb, tid, schema))
            batches[-1].read_bytes = rb.nbytes
        carry = tbl.slice(whole) if whole < n else None
        return rows, taken, batches

    def by_row(block: bytes, last: bool):
        # no arrow type for these columns: batches cut from the block's
        # lines
        lines = [ln for ln in block.split(b"\n") if ln.strip()]
        batches = []
        for lo in range(0, len(lines), batch_rows):
            batches.append(ColumnBatch.from_pydict(
                tid, schema, row_values(lines[lo:lo + batch_rows], fields)))
            batches[-1].read_bytes = \
                len(block) * batches[-1].n_rows // len(lines)
        return len(lines), 0, batches

    if JsonBlockDecoder.supports(fields):
        dec, decode = JsonBlockDecoder(fields, use_threads), by_block
    else:
        decode = by_row
    lines_seen = 0
    for block, last in _text_blocks(fh, path):
        with trace.span("source_decode", format="jsonl") as sp:
            try:
                rows, taken, batches = decode(block, last)
            except JsonLineError as e:
                raise ReaderError(
                    f"{path}: unparsed JSON line after line "
                    f"{lines_seen}: {e}") from e
            lines_seen += rows
            if sp:
                sp.add(rows=rows, bytes=len(block),
                       path="block" if taken == rows else "row")
        TELEMETRY.record_jsonl(rows, taken, len(block))
        for batch in batches:
            pusher(batch)


def infer_json_lines_schema(fh) -> TableSchema:
    """The schema of JSON lines where none is declared: what arrow makes
    of the first 100 rows (every integer Int64, times and dates text)."""
    import pyarrow as pa

    rows = []
    for line in fh:
        if line.strip():
            rows.append(json.loads(line))
            if len(rows) >= 100:
                break
    return arrow_to_table_schema(pa.Table.from_pylist(rows).schema)


class JsonlReader(Reader):
    def __init__(self, declared: Optional[TableSchema] = None):
        self.declared = declared

    def infer_schema(self, fs, path: str) -> TableSchema:
        if self.declared is not None:
            return self.declared
        with fs.open(path, "rb") as fh:
            return infer_json_lines_schema(fh)

    def read(self, fs, path, tid, schema, batch_rows, pusher) -> None:
        with fs.open(path, "rb") as fh:
            read_json_lines(fh, path, tid, schema, batch_rows, pusher)


class LineReader(Reader):
    """Each line one row (registry/line): `line` utf8 + system columns."""

    SCHEMA = TableSchema([ColSchema("line", CanonicalType.UTF8)]
                         + _system_cols())

    def infer_schema(self, fs, path: str) -> TableSchema:
        return self.SCHEMA

    def read(self, fs, path, tid, schema, batch_rows, pusher) -> None:
        lines: list[str] = []
        idx0 = 0
        nbytes = 0
        row = 0
        with fs.open(path, "rb") as fh:
            for raw in fh:
                text = raw.decode("utf-8", errors="replace").rstrip("\r\n")
                if not text.strip():
                    row += 1
                    continue
                if not lines:
                    idx0 = row
                lines.append(text)
                nbytes += len(raw)
                row += 1
                if len(lines) >= batch_rows:
                    self._push(lines, idx0, nbytes, path, tid, pusher)
                    lines, nbytes = [], 0
        if lines:
            self._push(lines, idx0, nbytes, path, tid, pusher)

    def _push(self, lines, idx0, nbytes, path, tid, pusher):
        # row indices are per-pushed-row dense from the first line of the
        # buffer; blank lines advance the file row counter but aren't rows
        batch = ColumnBatch.from_pydict(tid, self.SCHEMA, {
            "line": lines,
            FILE_NAME_COL: [path] * len(lines),
            ROW_INDEX_COL: list(range(idx0, idx0 + len(lines))),
        })
        batch.read_bytes = nbytes
        pusher(batch)


# default combined log format (nginx docs)
NGINX_COMBINED = (
    '$remote_addr - $remote_user [$time_local] "$request" '
    '$status $body_bytes_sent "$http_referer" "$http_user_agent"'
)

_NGINX_INT = {"status", "body_bytes_sent", "bytes_sent", "request_length",
              "connection", "connection_requests", "content_length"}
_NGINX_FLOAT = {"request_time", "upstream_response_time", "msec",
                "upstream_connect_time", "upstream_header_time"}
_VAR_RE = re.compile(r"\$([A-Za-z0-9_]+)")


class NginxReader(Reader):
    """nginx log_format template parser (registry/nginx): literals match
    exactly, variables capture up to the next literal."""

    def __init__(self, log_format: str = "",
                 unparsed_policy: str = "route"):
        fmt = (log_format or NGINX_COMBINED).strip()
        fmt = re.sub(r"[ \t]*\n[ \t]*", " ", fmt)
        self.tokens: list[tuple[bool, str]] = []
        last = 0
        for m in _VAR_RE.finditer(fmt):
            if m.start() > last:
                self.tokens.append((False, fmt[last:m.start()]))
            self.tokens.append((True, m.group(1)))
            last = m.end()
        if last < len(fmt):
            self.tokens.append((False, fmt[last:]))
        self.fields = [v for is_var, v in self.tokens if is_var]
        if not self.fields:
            raise ReaderError(f"nginx format has no variables: {fmt!r}")
        self.unparsed_policy = unparsed_policy
        cols = []
        for f in self.fields:
            if f in _NGINX_INT:
                t = CanonicalType.INT64
            elif f in _NGINX_FLOAT:
                t = CanonicalType.DOUBLE
            else:
                t = CanonicalType.UTF8
            cols.append(ColSchema(f, t))
        self.schema = TableSchema(cols + _system_cols())

    def infer_schema(self, fs, path: str) -> TableSchema:
        return self.schema

    def parse_line(self, line: str) -> Optional[list]:
        values: list = []
        pos = 0
        n = len(self.tokens)
        for i, (is_var, val) in enumerate(self.tokens):
            if not is_var:
                lit = val
                if line.startswith(lit, pos):
                    pos += len(lit)
                elif i == 0 and line.startswith(lit.lstrip(), pos):
                    pos += len(lit.lstrip())
                else:
                    return None
                continue
            # variable: capture up to the next literal (or line end)
            if i + 1 < n and not self.tokens[i + 1][0]:
                nxt = self.tokens[i + 1][1]
                end = line.find(nxt, pos)
                if end < 0:
                    return None
            else:
                end = len(line)
            values.append(line[pos:end])
            pos = end
        out: list = []
        for f, raw in zip(self.fields, values):
            if f in _NGINX_INT:
                try:
                    out.append(int(raw))
                except ValueError:
                    out.append(None)
            elif f in _NGINX_FLOAT:
                try:
                    out.append(float(raw))
                except ValueError:
                    out.append(None)  # e.g. '-' for upstream times
            else:
                out.append(raw)
        return out

    def read(self, fs, path, tid, schema, batch_rows, pusher) -> None:
        good: list[list] = []
        good_idx: list[int] = []
        bad: list[Message] = []
        reasons: list[str] = []
        nbytes = 0
        with fs.open(path, "rb") as fh:
            for row, raw in enumerate(fh):
                text = raw.decode("utf-8",
                                  errors="replace").rstrip("\r\n")
                if not text.strip():
                    continue
                nbytes += len(raw)
                vals = self.parse_line(text)
                if vals is None:
                    if self.unparsed_policy == "fail":
                        raise ReaderError(
                            f"nginx parse failed at {path}:{row}: "
                            f"{text[:200]!r}")
                    if self.unparsed_policy == "route":
                        bad.append(Message(value=raw, topic=path,
                                           offset=row))
                        reasons.append("nginx format mismatch")
                        if len(bad) >= batch_rows:
                            # flush: a fully-mismatched multi-GB log must
                            # not accumulate in memory
                            pusher(unparsed_batch(bad, reasons))
                            bad, reasons = [], []
                    continue
                good.append(vals)
                good_idx.append(row)
                if len(good) >= batch_rows:
                    self._push(good, good_idx, nbytes, path, tid, pusher)
                    good, good_idx, nbytes = [], [], 0
        if good:
            self._push(good, good_idx, nbytes, path, tid, pusher)
        if bad:
            pusher(unparsed_batch(bad, reasons))

    def _push(self, rows, idx, nbytes, path, tid, pusher):
        data = {f: [r[i] for r in rows]
                for i, f in enumerate(self.fields)}
        data[FILE_NAME_COL] = [path] * len(rows)
        data[ROW_INDEX_COL] = idx
        batch = ColumnBatch.from_pydict(tid, self.schema, data)
        batch.read_bytes = nbytes
        pusher(batch)


class ProtoReader(Reader):
    """Varint length-prefixed protobuf frames (registry/proto) decoded by
    the protobuf parser plugin (descriptor config in `parser`)."""

    def __init__(self, parser_config: dict):
        from transferia_tpu.parsers import make_parser

        if not parser_config or "protobuf" not in parser_config:
            raise ReaderError(
                "proto format needs a {'protobuf': {...}} parser config")
        self.parser = make_parser(parser_config)

    def infer_schema(self, fs, path: str) -> TableSchema:
        schema = self.parser.result_schema()
        if schema is not None:
            return schema
        # sample the first frames of the object (reader/abstract.go:40-52)
        with fs.open(path, "rb") as fh:
            data = fh.read(1 << 20)
        msgs: list[Message] = []
        try:
            for idx, frame in self._frames(data):
                msgs.append(Message(value=frame, topic=path, offset=idx))
                if len(msgs) >= 100:
                    break
        except ReaderError:
            pass  # truncated tail of the sample window
        result = self.parser.do_batch(msgs)
        if result.batches:
            return result.batches[0].schema
        raise ReaderError(f"could not infer proto schema from {path}")

    @staticmethod
    def _frames(data: bytes) -> Iterable[tuple[int, bytes]]:
        pos, n, idx = 0, len(data), 0
        while pos < n:
            shift, length = 0, 0
            while True:
                if pos >= n:
                    raise ReaderError("truncated varint length prefix")
                b = data[pos]
                pos += 1
                length |= (b & 0x7F) << shift
                if not (b & 0x80):
                    break
                shift += 7
                if shift > 63:
                    raise ReaderError("varint length prefix overflow")
            if pos + length > n:
                raise ReaderError("truncated protobuf frame")
            yield idx, data[pos:pos + length]
            pos += length
            idx += 1

    def read(self, fs, path, tid, schema, batch_rows, pusher) -> None:
        with fs.open(path, "rb") as fh:
            data = fh.read()
        msgs: list[Message] = []
        for idx, frame in self._frames(data):
            msgs.append(Message(value=frame, topic=path, offset=idx))
            if len(msgs) >= batch_rows:
                self._flush(msgs, tid, pusher)
                msgs = []
        if msgs:
            self._flush(msgs, tid, pusher)

    def _flush(self, msgs, tid, pusher):
        result = self.parser.do_batch(msgs)
        for b in result.batches:
            pusher(b.rename_table(tid))
        if result.unparsed is not None and result.unparsed.n_rows:
            pusher(result.unparsed)


def make_reader(fmt: str, *, nginx_format: str = "",
                unparsed_policy: str = "route",
                parser_config: Optional[dict] = None,
                declared: Optional[TableSchema] = None) -> Reader:
    if fmt == "parquet":
        return ParquetReader()
    if fmt == "csv":
        return CsvReader(declared)
    if fmt == "jsonl":
        return JsonlReader(declared)
    if fmt == "line":
        return LineReader()
    if fmt == "nginx":
        return NginxReader(nginx_format, unparsed_policy)
    if fmt == "proto":
        return ProtoReader(parser_config or {})
    raise ReaderError(f"unknown s3 format {fmt!r} (parquet/csv/jsonl/"
                      f"line/nginx/proto)")
