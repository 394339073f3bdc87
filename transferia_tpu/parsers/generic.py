"""Generic schema-driven JSON/TSKV parser.

Reference parity: pkg/parsers/generic/generic_parser.go (the ~2.3 KLoC CPU
hot loop of the reference) + lookup.go field tables.  Re-designed columnar:
the whole message batch decodes in one vectorized pass (pyarrow's JSON block
reader into arrow columns -> ColumnBatch, no per-row Go/Python loop), with
per-row error localization by recursive bisection — a failed block splits in
halves until bad rows are isolated (O(log n) vectorized parses when errors
are rare), which solves SURVEY.md §7 hard-part (d) without giving up batch
decode.  Failed rows go to `_unparsed` (utils.go:145 policy).

System columns (_timestamp/_partition/_offset/_idx) become the primary key
like the reference's generic parser output schema.

`JsonBlockDecoder` is the one block decode of JSON lines: this parser's
columnar shortcut and the file sources' JSON-lines reader
(providers/s3readers.py::read_json_lines, which `fs` and `s3` share) both
call it, so a change to it shows on the Kafka path and the file path alike.
"""

from __future__ import annotations

import calendar
import datetime
import json
from typing import Any, Optional, Sequence

import numpy as np

from transferia_tpu.abstract.schema import (
    CanonicalType,
    ColSchema,
    TableID,
    TableSchema,
)
from transferia_tpu.columnar.batch import ColumnBatch, Column
from transferia_tpu.parsers.base import (
    Message,
    ParseResult,
    Parser,
    unparsed_batch,
)
from transferia_tpu.parsers.registry import register_parser

_SYSTEM_COLS = [
    ColSchema("_timestamp", CanonicalType.TIMESTAMP, primary_key=True),
    ColSchema("_partition", CanonicalType.UTF8, primary_key=True),
    ColSchema("_offset", CanonicalType.UINT64, primary_key=True),
    ColSchema("_idx", CanonicalType.UINT32, primary_key=True),
]


def _field_to_colschema(f: dict) -> ColSchema:
    return ColSchema(
        name=f["name"],
        data_type=CanonicalType(f.get("type", "any")),
        primary_key=bool(f.get("key", False)),
        required=bool(f.get("required", False)),
        path=f.get("path", ""),
    )

# -- the block decode ------------------------------------------------------------

_TEMPORAL = (CanonicalType.DATE, CanonicalType.DATETIME,
             CanonicalType.TIMESTAMP)
_TEXT = (CanonicalType.UTF8, CanonicalType.STRING)
_EPOCH_DATE = datetime.date(1970, 1, 1)


def _int_range(t: CanonicalType) -> tuple[int, int]:
    info = np.iinfo(t.np_dtype)
    return int(info.min), int(info.max)


def temporal_from_text(t: CanonicalType, text: str) -> int:
    """`YYYY-MM-DD` / `YYYY-MM-DD hh:mm:ss[.ffffff]` (no zone) as the
    type's epoch count: days, seconds or microseconds.  ValueError for
    anything else, and for a fraction a DATETIME cannot hold."""
    if t == CanonicalType.DATE:
        return (datetime.date.fromisoformat(text) - _EPOCH_DATE).days
    d = datetime.datetime.fromisoformat(text)
    if d.tzinfo is not None:
        raise ValueError(f"{text!r}: a zone offset")
    seconds = calendar.timegm(d.timetuple())
    if t == CanonicalType.TIMESTAMP:
        return seconds * 1_000_000 + d.microsecond
    if d.microsecond:
        raise ValueError(f"{text!r}: a fraction of a second")
    return seconds


def row_value(cs: ColSchema, v: Any) -> Any:
    """One value of a `json.loads` row as the column holds it - the row
    path's side of the block decode: integers from numbers or decimal
    text, checked against the column's width; DATE / DATETIME / TIMESTAMP
    from epoch counts or `YYYY-MM-DD[ hh:mm:ss]` text.  ValueError for a
    value the column cannot hold."""
    t = cs.data_type
    if v is None:
        return None
    if t.is_integer or t in _TEMPORAL:
        if isinstance(v, str):
            try:
                v = int(v)
            except ValueError:
                if t not in _TEMPORAL:
                    raise
                v = temporal_from_text(t, v)
        elif isinstance(v, float) and v.is_integer():
            v = int(v)
        if isinstance(v, bool) or not isinstance(v, int):
            raise ValueError(f"{cs.name}: {v!r} is no integer")
        lo, hi = _int_range(t)
        if not lo <= v <= hi:
            raise ValueError(f"{cs.name}: {v} is outside {t.value}")
        return v
    if t.is_float:
        if isinstance(v, bool) or not isinstance(v, (int, float, str)):
            raise ValueError(f"{cs.name}: {v!r} is no number")
        return float(v)
    if t == CanonicalType.BOOLEAN:
        if isinstance(v, str) and v.lower() in ("true", "false"):
            return v.lower() == "true"
        if not isinstance(v, bool):
            raise ValueError(f"{cs.name}: {v!r} is no boolean")
        return v
    if t in _TEXT:
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            return str(v)
        if not isinstance(v, str):
            raise ValueError(f"{cs.name}: {v!r} is no text")
    return v


class JsonLineError(ValueError):
    """A JSON line that neither the block path nor the row path takes."""


def row_values(lines: Sequence[bytes], fields: Sequence[ColSchema]
               ) -> dict[str, list]:
    """The row path: one `json.loads` a line, `row_value` a cell; a
    missing key is NULL.  JsonLineError names the first line it cannot
    take by its index in `lines`."""
    out: dict[str, list] = {f.name: [] for f in fields}
    for i, line in enumerate(lines):
        try:
            row = json.loads(line)
            if not isinstance(row, dict):
                raise ValueError("not an object")
            for f in fields:
                out[f.name].append(row_value(f, row.get(f.name)))
        except ValueError as e:
            err = JsonLineError(f"line {i}: {e}: {bytes(line[:200])!r}")
            err.index = i
            raise err from e
    return out


class JsonBlockDecoder:
    """JSON lines to arrow columns of the declared types, a block at a
    time: pyarrow's C++ block reader (the GIL released while it runs)
    with the fields as its explicit schema - a missing key is NULL, keys
    come in any order, an undeclared key is ignored, every JSON escape is
    arrow's to undo.  Integers are read at their declared width, so a
    value outside it fails the block; DATE / DATETIME / TIMESTAMP are
    epoch counts.

    A column whose numbers or times come as JSON strings - ClickHouse's
    `JSONEachRow` quotes 64-bit integers and writes DateTime as
    `"YYYY-MM-DD hh:mm:ss"` - is read as text and cast a column at a time
    by arrow.  Which columns those are is learnt from the first line of
    the first block that fails as it stands (one `json.loads`), and kept
    for the blocks that follow.

    `read` takes one block whole or raises; `decode` isolates the lines a
    block fails on by halving and gives those to the row path
    (`row_values`)."""

    # lines of one block the row path takes one by one before the rest of
    # the failing range goes to it whole: halving a range that is bad all
    # over costs more than reading it row by row
    MAX_ISOLATED = 32

    def __init__(self, fields: Sequence[ColSchema],
                 use_threads: bool = True):
        import pyarrow as pa

        self.fields = list(fields)
        # arrow's own pool over a block's chunks and columns: a reader
        # whose parts already decode side by side turns it off
        self.use_threads = use_threads
        wide = {CanonicalType.DATETIME: pa.int64(),
                CanonicalType.TIMESTAMP: pa.int64(),
                CanonicalType.DATE: pa.int32(),
                CanonicalType.FLOAT: pa.float64(),
                CanonicalType.DOUBLE: pa.float64(),
                CanonicalType.BOOLEAN: pa.bool_(),
                CanonicalType.UTF8: pa.string(),
                CanonicalType.STRING: pa.string()}
        # what the table holds a column as
        self.types = {
            f.name: wide.get(f.data_type) or pa.from_numpy_dtype(
                f.data_type.np_dtype) for f in self.fields}
        self.schema = pa.schema(
            [pa.field(f.name, self.types[f.name]) for f in self.fields])
        self._via = {CanonicalType.DATE: pa.date32(),
                     CanonicalType.DATETIME: pa.timestamp("s"),
                     CanonicalType.TIMESTAMP: pa.timestamp("us")}
        # (the columns read as text and cast, the schema arrow reads by):
        # one attribute, swapped whole, since a parser is shared by threads
        self._variant: tuple = (frozenset(), self.schema)

    @staticmethod
    def supports(fields: Sequence[ColSchema]) -> bool:
        """Scalar columns read by their own name; nested paths and ANY /
        DECIMAL variants are the row path's."""
        return bool(fields) and all(
            not f.path and (f.data_type.is_numeric
                            or f.data_type in _TEMPORAL + _TEXT
                            or f.data_type == CanonicalType.BOOLEAN)
            for f in fields)

    def _sniff(self, blob) -> Optional[frozenset]:
        """The non-text columns that the block's first line holds as JSON
        strings; None where that line is no JSON object."""
        end = blob.find(b"\n")
        try:
            row = json.loads(blob[:end if end >= 0 else len(blob)])
        except ValueError:
            return None
        if not isinstance(row, dict):
            return None
        return frozenset(
            f.name for f in self.fields
            if f.data_type not in _TEXT and isinstance(row.get(f.name), str))

    def _read_as(self, blob, variant: tuple, n_lines: Optional[int]):
        import pyarrow as pa
        import pyarrow.compute as pc
        import pyarrow.json as pajson

        text, schema = variant
        tbl = pajson.read_json(
            pa.BufferReader(blob),
            read_options=pajson.ReadOptions(use_threads=self.use_threads),
            parse_options=pajson.ParseOptions(
                newlines_in_values=False,
                explicit_schema=schema,
                unexpected_field_behavior="ignore",
            ),
        )
        if n_lines is not None and tbl.num_rows != n_lines:
            raise pa.ArrowInvalid(
                f"{tbl.num_rows} rows in {n_lines} lines")
        for f in self.fields:
            if f.name not in text:
                continue
            i = tbl.schema.get_field_index(f.name)
            col = tbl.column(i)
            via = self._via.get(f.data_type)
            if via is not None:
                col = pc.cast(col, via)
            tbl = tbl.set_column(i, f.name,
                                 pc.cast(col, self.types[f.name]))
        return tbl

    def read(self, blob, n_lines: Optional[int] = None):
        """One block as an arrow table of `self.schema`; pa.ArrowInvalid
        where the block as a whole cannot be taken (a bad line, a value
        outside its column's width, `n_lines` given and not the rows
        read)."""
        import pyarrow as pa

        variant = self._variant
        try:
            return self._read_as(blob, variant, n_lines)
        except (pa.ArrowInvalid, pa.ArrowNotImplementedError) as e:
            text = self._sniff(blob)
            if text is None or text == variant[0]:
                raise pa.ArrowInvalid(str(e)) from e
        variant = (text, pa.schema(
            [pa.field(f.name, pa.string() if f.name in text
                      else self.types[f.name]) for f in self.fields]))
        try:
            tbl = self._read_as(blob, variant, n_lines)
        except pa.ArrowNotImplementedError as e:
            raise pa.ArrowInvalid(str(e)) from e
        self._variant = variant
        return tbl

    def rows_table(self, lines: Sequence[bytes]):
        """`lines` through the row path, as a table of `self.schema`."""
        import pyarrow as pa

        data = row_values(lines, self.fields)
        try:
            return pa.table(
                [pa.array(data[f.name], type=self.types[f.name])
                 for f in self.fields], schema=self.schema)
        except (pa.ArrowInvalid, pa.ArrowTypeError,
                UnicodeEncodeError) as e:
            raise JsonLineError(f"{e}: {bytes(lines[0][:200])!r}") from e

    def decode(self, block: bytes):
        """(the block's rows in line order as a table of `self.schema`,
        how many of them the block path took).  Blank lines are no rows.
        JsonLineError for a line neither path takes."""
        import pyarrow as pa

        n = block.count(b"\n") + (not block.endswith(b"\n"))
        try:
            return self.read(block, n), n
        except pa.ArrowInvalid:
            pass
        lines = [ln for ln in block.split(b"\n") if ln.strip()]
        pieces: list = []
        by_row = [0]

        def attempt(lo: int, hi: int) -> None:
            if by_row[0] <= self.MAX_ISOLATED:
                try:
                    pieces.append(
                        self.read(b"\n".join(lines[lo:hi]), hi - lo))
                    return
                except pa.ArrowInvalid:
                    if hi - lo > 1:
                        mid = (lo + hi) // 2
                        attempt(lo, mid)
                        attempt(mid, hi)
                        return
            try:
                pieces.append(self.rows_table(lines[lo:hi]))
            except JsonLineError as e:
                raise JsonLineError(
                    f"line {lo + getattr(e, 'index', 0)} of the block: "
                    f"{e}") from e
            by_row[0] += hi - lo

        if len(lines) == n > 1:
            # the range that has just failed: its halves
            attempt(0, n // 2)
            attempt(n // 2, n)
        elif lines:
            attempt(0, len(lines))
        if not pieces:
            return self.schema.empty_table(), 0
        return pa.concat_tables(pieces), len(lines) - by_row[0]


class _Lines:
    """Flattened (message, line) view of a batch."""

    __slots__ = ("values", "msg_index", "line_index", "arrow_failed_full")

    def __init__(self, messages: Sequence[Message]):
        self.values: list[bytes] = []
        self.msg_index: list[int] = []
        self.line_index: list[int] = []
        self.arrow_failed_full = False
        for mi, m in enumerate(messages):
            for li, line in enumerate(m.value.split(b"\n")):
                if line.strip():
                    self.values.append(line)
                    self.msg_index.append(mi)
                    self.line_index.append(li)


@register_parser("json")
@register_parser("generic")
class GenericJsonParser(Parser):
    """config: schema: [{name,type,key?,path?,required?}] (None = infer),
    table, namespace, add_system_cols, unescape_string_values."""

    def __init__(self, schema: Optional[list[dict]] = None,
                 table: str = "data", namespace: str = "",
                 add_system_cols: bool = True,
                 null_keys_allowed: bool = False):
        self.fields = [_field_to_colschema(f) for f in (schema or [])]
        self.table = TableID(namespace, table)
        self.add_system_cols = add_system_cols
        self.null_keys_allowed = null_keys_allowed
        self._schema: Optional[TableSchema] = None
        self._block: Optional[JsonBlockDecoder] = None
        if self.fields:
            self._schema = self._build_schema(self.fields)

    def _build_schema(self, fields: list[ColSchema]) -> TableSchema:
        cols = list(fields)
        if self.add_system_cols:
            has_user_key = any(c.primary_key for c in cols)
            sys_cols = [
                ColSchema(c.name, c.data_type,
                          primary_key=not has_user_key,
                          required=c.required)
                for c in _SYSTEM_COLS
            ]
            cols = sys_cols + cols
        return TableSchema(cols)

    def result_schema(self) -> Optional[TableSchema]:
        return self._schema

    # -- decoding -----------------------------------------------------------
    def _decode_rows(self, values: list[bytes],
                     skip_full_arrow: bool = False) -> list[Optional[dict]]:
        """Vectorized decode with bisecting error isolation.

        Returns one dict per line (None = unparseable).  The fast path
        decodes the whole block in one C++ pass (pyarrow's JSON reader for
        large batches, a single stdlib json.loads for small ones); only
        blocks containing a bad row pay the recursive split.
        """
        out: list[Optional[dict]] = [None] * len(values)

        def block_decode(lo: int, hi: int) -> Optional[list[dict]]:
            blob = b"[" + b",".join(values[lo:hi]) + b"]"
            try:
                rows = json.loads(blob)
            except ValueError:
                return None
            if len(rows) != hi - lo or \
                    not all(isinstance(r, dict) for r in rows):
                return None
            return rows

        def block_decode_arrow(lo: int, hi: int) -> Optional[list[dict]]:
            """One vectorized pass over newline-joined rows (arrow's C++
            block reader) — ~5-10x json.loads on wide batches.  Used only
            with an explicit scalar schema so arrow can't reinterpret
            values (e.g. date-like strings) differently from json.loads;
            any mismatch falls back to the bisecting stdlib path."""
            import io

            try:
                import pyarrow as pa
                import pyarrow.json as pajson
            except ImportError:
                return block_decode(lo, hi)
            schema = self._arrow_schema()
            if schema is None:
                return block_decode(lo, hi)
            blob = b"\n".join(values[lo:hi])
            try:
                tbl = pajson.read_json(
                    io.BytesIO(blob),
                    parse_options=pajson.ParseOptions(
                        newlines_in_values=False,
                        explicit_schema=schema,
                        unexpected_field_behavior="ignore",
                    ),
                )
            except (pa.ArrowInvalid, pa.ArrowNotImplementedError):
                return None
            if tbl.num_rows != hi - lo:
                return None
            return tbl.to_pylist()

        def attempt(lo: int, hi: int, skip_arrow: bool = False) -> None:
            use_arrow = hi - lo >= 256 and not skip_arrow
            rows = (block_decode_arrow(lo, hi) if use_arrow
                    else block_decode(lo, hi))
            if rows is not None:
                out[lo:hi] = rows
                return
            if hi - lo == 1:
                return  # isolated bad row stays None
            mid = (lo + hi) // 2
            attempt(lo, mid)
            attempt(mid, hi)

        if values:
            # skip_full_arrow: the caller already ran (and failed) the
            # full-range arrow parse — don't pay it twice
            attempt(0, len(values), skip_arrow=skip_full_arrow)
        return out

    def _block_decoder(self) -> Optional[JsonBlockDecoder]:
        """The block decode for the declared fields, or None when they
        need features it lacks (nested paths, ANY variants, inference)
        or pyarrow is absent."""
        if self._block is None and JsonBlockDecoder.supports(self.fields):
            try:
                self._block = JsonBlockDecoder(self.fields)
            except ImportError:  # minimal install: general path only
                return None
        return self._block

    def _arrow_schema(self):
        """The explicit arrow schema the general path's block reads use:
        the decoder's, every column as its JSON type (numbers bare), so
        that arrow reads a value as `json.loads` would."""
        dec = self._block_decoder()
        return dec.schema if dec is not None else None

    def _extract(self, rows: list[dict], cs: ColSchema) -> list[Any]:
        if cs.path:
            parts = cs.path.split(".")

            def get(r):
                cur: Any = r
                for p in parts:
                    if not isinstance(cur, dict) or p not in cur:
                        return None
                    cur = cur[p]
                return cur

            return [get(r) for r in rows]
        return [r.get(cs.name) for r in rows]

    def _fast_columnar(self, messages: Sequence[Message],
                       lines: "_Lines") -> Optional[ParseResult]:
        """Whole-batch columnar shortcut: arrow-decode straight into the
        ColumnBatch with vectorized system columns — no per-row dicts.
        Returns None when anything (bad rows, null keys, exotic schema)
        needs the general path."""
        if type(self) is not GenericJsonParser or not self.fields:
            return None
        if len(lines.values) < 256:
            return None
        dec = self._block_decoder()
        if dec is None:
            return None
        import pyarrow as pa

        try:
            tbl = dec.read(b"\n".join(lines.values), len(lines.values))
        except pa.ArrowInvalid:
            # tell the general path the full-range arrow parse is a known
            # failure so it goes straight to bisection
            lines.arrow_failed_full = True
            return None
        keep = np.ones(tbl.num_rows, dtype=bool)
        if not self.null_keys_allowed:
            # null-key offenders route to _unparsed without abandoning the
            # already-done C++ parse
            for cs in self.fields:
                if cs.primary_key and tbl.column(cs.name).null_count:
                    keep &= np.asarray(
                        tbl.column(cs.name).combine_chunks().is_valid()
                    )
        kept_pos = np.nonzero(keep)[0]
        if len(kept_pos) != tbl.num_rows:
            tbl = tbl.take(pa.array(kept_pos))
        out_schema = self._schema or self._build_schema(self.fields)
        batch = ColumnBatch.from_arrow(
            tbl.combine_chunks().to_batches()[0], self.table,
            out_schema.project([c.name for c in self.fields]),
        ) if tbl.num_rows else None
        cols = dict(batch.columns) if batch is not None else {}
        if self.add_system_cols and batch is not None:
            midx = np.asarray(lines.msg_index)[kept_pos]
            write_ns = np.array(
                [m.write_time_ns for m in messages], dtype=np.int64
            )
            offsets_arr = np.array(
                [m.offset for m in messages], dtype=np.uint64
            )
            parts = [f"{m.topic}:{m.partition}" for m in messages]
            cols["_timestamp"] = Column(
                "_timestamp", CanonicalType.TIMESTAMP,
                (write_ns // 1000)[midx],
            )
            cols["_partition"] = Column.from_pylist(
                "_partition", CanonicalType.UTF8,
                [parts[i] for i in midx],
            )
            cols["_offset"] = Column("_offset", CanonicalType.UINT64,
                                     offsets_arr[midx])
            cols["_idx"] = Column(
                "_idx", CanonicalType.UINT32,
                np.asarray(lines.line_index,
                           dtype=np.uint32)[kept_pos],
            )
        result = ParseResult()
        if batch is not None:
            ordered = {
                c.name: cols[c.name] for c in out_schema if c.name in cols
            }
            result.batches.append(
                ColumnBatch(self.table, out_schema, ordered)
            )
        bad_pos = np.nonzero(~keep)[0]
        if len(bad_pos):
            bad_msgs = [
                Message(
                    value=lines.values[i],
                    topic=messages[lines.msg_index[i]].topic,
                    partition=messages[lines.msg_index[i]].partition,
                    offset=messages[lines.msg_index[i]].offset,
                    write_time_ns=messages[lines.msg_index[i]]
                    .write_time_ns,
                )
                for i in bad_pos
            ]
            result.unparsed = unparsed_batch(
                bad_msgs, ["null value in key column"] * len(bad_pos)
            )
        return result

    def do_batch(self, messages: Sequence[Message]) -> ParseResult:
        lines = _Lines(messages)
        fast = self._fast_columnar(messages, lines)
        if fast is not None:
            return fast
        decoded = self._decode_rows(
            lines.values, skip_full_arrow=lines.arrow_failed_full
        )

        # line index -> failure reason; grows as validation rejects rows
        bad: dict[int, str] = {
            i: "invalid " + ("JSON" if type(self) is GenericJsonParser
                             else self.TYPE)
            for i, d in enumerate(decoded) if d is None
        }
        good_idx = [i for i in range(len(decoded)) if i not in bad]

        fields = self.fields
        if not fields and good_idx:
            # schema inference from the first good rows
            seen: dict[str, CanonicalType] = {}
            for i in good_idx[:100]:
                for k, v in decoded[i].items():
                    seen.setdefault(k, _infer_type(v))
            fields = [ColSchema(k, t) for k, t in seen.items()]

        schema = self._schema or self._build_schema(fields)
        rows = [decoded[i] for i in good_idx]
        data: dict[str, list] = {}
        for cs in fields:
            data[cs.name] = self._extract(rows, cs)
        # null-key validation — offenders move to _unparsed
        if not self.null_keys_allowed:
            for kn in (c.name for c in fields if c.primary_key):
                for j, v in enumerate(data[kn]):
                    if v is None and good_idx[j] not in bad:
                        bad[good_idx[j]] = f"null value in key column {kn}"
        if len(bad) and rows:
            keep = [j for j, i in enumerate(good_idx) if i not in bad]
            data = {k: [v[j] for j in keep] for k, v in data.items()}
            good_idx = [good_idx[j] for j in keep]

        if self.add_system_cols:
            metas = [messages[lines.msg_index[i]] for i in good_idx]
            data["_timestamp"] = [m.write_time_ns // 1000 for m in metas]
            data["_partition"] = [
                f"{m.topic}:{m.partition}" for m in metas
            ]
            data["_offset"] = [m.offset for m in metas]
            data["_idx"] = [lines.line_index[i] for i in good_idx]

        result = ParseResult()
        if good_idx:
            coerced = _coerce(data, schema)
            result.batches.append(
                ColumnBatch.from_pydict(self.table, schema, coerced)
            )
        if bad:
            order = sorted(bad)
            bad_msgs = [
                Message(
                    value=lines.values[i],
                    topic=messages[lines.msg_index[i]].topic,
                    partition=messages[lines.msg_index[i]].partition,
                    offset=messages[lines.msg_index[i]].offset,
                    write_time_ns=messages[lines.msg_index[i]].write_time_ns,
                )
                for i in order
            ]
            result.unparsed = unparsed_batch(
                bad_msgs, [bad[i] for i in order]
            )
        return result


def _infer_type(v: Any) -> CanonicalType:
    if isinstance(v, bool):
        return CanonicalType.BOOLEAN
    if isinstance(v, int):
        return CanonicalType.INT64
    if isinstance(v, float):
        return CanonicalType.DOUBLE
    if isinstance(v, str):
        return CanonicalType.UTF8
    return CanonicalType.ANY


def _coerce(data: dict[str, list], schema: TableSchema) -> dict[str, list]:
    """Best-effort scalar coercion to the declared types."""
    out = {}
    for name, values in data.items():
        cs = schema.find(name)
        if cs is None:
            continue
        t = cs.data_type
        if t.is_numeric or t in (CanonicalType.DATETIME,
                                 CanonicalType.TIMESTAMP,
                                 CanonicalType.DATE):
            bounds = None if t.is_float else _int_range(t)

            def conv(v):
                if isinstance(v, str):
                    try:
                        v = float(v) if t.is_float else int(v)
                    except ValueError:
                        # "YYYY-MM-DD hh:mm:ss" where the block decode
                        # reads it too
                        if t not in _TEMPORAL:
                            return None
                        try:
                            v = temporal_from_text(t, v)
                        except ValueError:
                            return None
                elif v is not None and not isinstance(v, (int, float)):
                    return None
                if bounds and isinstance(v, int) \
                        and not bounds[0] <= v <= bounds[1]:
                    return None     # the column's width cannot hold it
                return v
            out[name] = [conv(v) for v in values]
        elif t == CanonicalType.BOOLEAN:
            out[name] = [
                None if v is None else
                (v if isinstance(v, bool) else str(v).lower() == "true")
                for v in values
            ]
        else:
            out[name] = values
    return out


@register_parser("tskv")
class TskvParser(GenericJsonParser):
    """TSKV (tab-separated key=value) lines -> same output contract."""

    def _decode_rows(self, values: list[bytes],
                     skip_full_arrow: bool = False) -> list[Optional[dict]]:
        out: list[Optional[dict]] = []
        for line in values:
            try:
                text = line.decode("utf-8")
                if text.startswith("tskv\t"):
                    text = text[5:]
                row: dict[str, Any] = {}
                import re as _re

                unescape = {"t": "\t", "n": "\n", "r": "\r", "0": "\0",
                            "\\": "\\", "=": "="}
                for pair in text.split("\t"):
                    if not pair:
                        continue
                    if "=" not in pair:
                        raise ValueError(f"no '=' in {pair!r}")
                    k, v = pair.split("=", 1)
                    # single-pass unescape: sequential .replace corrupts
                    # escaped backslashes followed by t/n
                    row[k] = _re.sub(
                        r"\\(.)",
                        lambda m: unescape.get(m.group(1), m.group(1)),
                        v,
                    )
                out.append(row if row else None)
            except (ValueError, UnicodeDecodeError):
                out.append(None)
        return out
