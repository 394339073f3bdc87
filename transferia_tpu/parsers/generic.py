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
"""

from __future__ import annotations

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

    def _arrow_schema(self):
        """Explicit arrow schema for the C++ fast path, or None when the
        declared fields need features arrow can't mirror (nested paths,
        ANY variants, inference) or pyarrow is absent."""
        if not self.fields:
            return None
        try:
            import pyarrow as pa
        except ImportError:
            return None

        scalar = {
            CanonicalType.INT8: pa.int64(), CanonicalType.INT16: pa.int64(),
            CanonicalType.INT32: pa.int64(),
            CanonicalType.INT64: pa.int64(),
            CanonicalType.FLOAT: pa.float64(),
            CanonicalType.DOUBLE: pa.float64(),
            CanonicalType.BOOLEAN: pa.bool_(),
            CanonicalType.UTF8: pa.string(),
            CanonicalType.STRING: pa.string(),
            # epoch counts held as int64, as `_coerce` takes them: JSON
            # integers; a float or a string fails the read and the
            # general path decides
            CanonicalType.DATETIME: pa.int64(),
            CanonicalType.TIMESTAMP: pa.int64(),
        }
        out = []
        for cs in self.fields:
            if cs.path or cs.data_type not in scalar:
                return None
            out.append(pa.field(cs.name, scalar[cs.data_type]))
        return pa.schema(out)

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
        import io

        import numpy as np

        try:
            import pyarrow as pa
            import pyarrow.json as pajson
        except ImportError:  # minimal install: general path only
            return None
        schema = self._arrow_schema()
        if schema is None:
            return None
        try:
            tbl = pajson.read_json(
                io.BytesIO(b"\n".join(lines.values)),
                parse_options=pajson.ParseOptions(
                    newlines_in_values=False,
                    explicit_schema=schema,
                    unexpected_field_behavior="ignore",
                ),
            )
        except (pa.ArrowInvalid, pa.ArrowNotImplementedError):
            # tell the general path the full-range arrow parse is a known
            # failure so it goes straight to bisection
            lines.arrow_failed_full = True
            return None
        if tbl.num_rows != len(lines.values):
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
            def conv(v):
                if v is None or isinstance(v, (int, float)):
                    return v
                try:
                    return float(v) if t.is_float else int(v)
                except (TypeError, ValueError):
                    return None
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
