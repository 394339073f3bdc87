"""Debezium envelope emitter (pkg/debezium/emitter_*.go, packer/).

Produces (key_bytes, value_bytes) JSON pairs per row.  Deletes also emit the
tombstone (key, None) message when configured, matching Debezium's default
topic compaction contract.

A batch takes one of three paths, which the `serialize` span's `path` arg
and the `debezium_rows*` counters name; all three give the same bytes
(pinned by differential tests):

"native"  An insert-only ColumnBatch in JSON mode (no schema-registry
    packer) that carries no `lsns`, `commit_times` or `txn_ids` of its
    own, so that one `source` block serves every row: a snapshot's every
    batch.  The reference multithreads exactly this serialization
    (pkg/serializer/queue/debezium_multithreading.go); under one GIL the
    equivalent is to leave Python.  The schema block and every static byte
    of the envelope render once per (table, schema, mode) into
    %s-templates, which are cut at their slots into constant pieces;
    native/hostops.cpp then walks the batch twice with the GIL released,
    so part threads render side by side: once for every message's size,
    then writing the values, and the keys, into one buffer each.  It
    reads the columns' own buffers: integers (DATE, DATETIME and
    TIMESTAMP scaled with numpy first) as digits, UTF8 and DECIMAL text
    quoted exactly as json.dumps quotes under ensure_ascii.  A column it
    cannot read that way - FLOAT/DOUBLE, BOOLEAN, STRING, a `pg` or
    _SLOW_MYSQL original type, a lazy dictionary - comes to it as the
    fragments the Python renderer makes, to be copied.  The buffers and
    their offsets are the batch's MessageBlock (serializers/formats.py),
    which the Kafka sink frames as it is (emit_block); emit_batch cuts
    its pairs from it.
"fast"  The same batch rendered in Python, a str per cell and two `%` per
    row: what runs under TRANSFERIA_TPU_NO_NATIVE=1, for a batch of
    inserts with source metadata row by row (replication's, a few rows a
    batch: under some 5 rows the native call's fixed cost is more than it
    saves, and no benchmark cell sends them), and where the native walk
    declines (a text cell that is not UTF-8, whose replacement characters
    are Python's decoder's to choose; fragments past 2 GiB).
"row"  Everything outside the envelope - CDC kinds other than insert,
    packers, NaN or infinity in a float column, a row list - goes through
    emit_item, a ChangeItem and a json.dumps per row.
"""

from __future__ import annotations

import base64
import json
import re
import time
from typing import Iterable, Optional

import numpy as np

from transferia_tpu.abstract.change_item import ChangeItem
from transferia_tpu.abstract.kinds import Kind
from transferia_tpu.abstract.schema import CanonicalType, TableSchema
from transferia_tpu.columnar.batch import ColumnBatch
from transferia_tpu.debezium.types import (
    _split_original,
    encode_value,
    mysql_datetime_millis,
    to_connect,
)
from transferia_tpu import native
from transferia_tpu.serializers.formats import MessageBlock
from transferia_tpu.stats import trace

# column kinds of native/hostops.cpp's debezium_render_*
_I64, _U64, _TEXT, _RAW = 0, 1, 2, 3


def _digit_column(data: np.ndarray, validity) -> Optional[tuple]:
    """Integers as the native renderer's column; None for a dtype whose
    digits numpy's string cast must spell."""
    if data.dtype.kind not in "iu":
        return None
    if data.dtype == np.uint64:
        return _U64, data, None, validity
    return _I64, data.astype(np.int64, copy=False), None, validity


def _packed_fragments(frags: list) -> Optional[tuple]:
    """Rendered cells (ASCII, as json.dumps, repr and base64 give them)
    end to end with int32 offsets, as the native renderer's column; None
    where they do not fit."""
    data = "".join(frags).encode()
    lens = np.fromiter(map(len, frags), dtype=np.int64, count=len(frags))
    if len(data) != lens.sum() or len(data) >= 2 ** 31:
        return None
    offsets = np.zeros(len(frags) + 1, dtype=np.int32)
    np.cumsum(lens, out=offsets[1:])
    return _RAW, np.frombuffer(data, dtype=np.uint8), offsets, None


def _cut(fmt: str) -> list[str]:
    """A %-template's constant pieces, cut at its %s slots ("%%" is a
    literal "%"): len(slots) + 1 of them."""
    tokens = re.split("%(.)", fmt, flags=re.DOTALL)
    pieces = [tokens[0]]
    for directive, text in zip(tokens[1::2], tokens[2::2]):
        if directive == "s":
            pieces.append(text)
        else:
            pieces[-1] += "%" + text
    return pieces


def _splice(head: list, tail: list) -> list:
    """Two runs of pieces as one: the last of one joins the first of the
    other, the slots between the rest stay."""
    return head[:-1] + [head[-1] + tail[0]] + tail[1:]


def _field_schema(cs) -> dict:
    ctype, semantic, params = to_connect(cs)
    if isinstance(ctype, dict):  # Connect array: {"type","items"}
        out = dict(ctype)
        out.update({"optional": not cs.required, "field": cs.name})
    else:
        out = {"type": ctype, "optional": not cs.required,
               "field": cs.name}
    if semantic:
        out["name"] = semantic
        out["version"] = 1
    if params:
        out["parameters"] = dict(params)
    return out


class DebeziumEmitter:
    """config mirrors the reference's parameters/ subset: topic_prefix,
    connector name, include_schema (schema block on/off), emit_tombstones."""

    VERSION = "2.5.0.transferia-tpu"

    def __init__(self, topic_prefix: str = "transfer",
                 connector: str = "transferia-tpu",
                 include_schema: bool = True,
                 emit_tombstones: bool = False,
                 source_db_type: str = "postgresql",
                 packer: str = "",
                 topic: str = "",
                 schema_registry_url: str = "",
                 schema_registry_user: str = "",
                 schema_registry_password: str = ""):
        """packer: '' -> include_schema flag decides (include_schema /
        skip_schema); 'schema_registry' -> Confluent wire format
        (pkg/debezium/packer/ parity).  topic: the sink's FIXED topic when
        it writes into one topic — SR subjects derive from the topic the
        messages actually land on (TopicNameStrategy); default is the
        kafka sink's per-table naming '<namespace>.<table>'.
        connector / source_db_type: what `source.connector` and
        `source.db` say; the sink's factory fills them in from the
        transfer's source endpoint where it knows what Debezium's own
        connector for that source says (providers/kafka)."""
        self.sink_topic = topic
        self.topic_prefix = topic_prefix
        self.connector = connector
        self.include_schema = include_schema
        self.emit_tombstones = emit_tombstones
        self.source_db_type = source_db_type
        self.key_packer = self.value_packer = None
        # keyed on schema.fingerprint(), never id(schema): a freed
        # TableSchema's address can be reused by a new schema for the
        # same table (same column count after a rename/type change),
        # which would silently serve a stale envelope — the exact trap
        # parsers/plugins.py _flat_spec avoids by caching on the object
        # (TableSchema is slotted, so the fingerprint key is the
        # equivalent here; it is computed once and cached on the schema)
        self._value_schema_cache: dict = {}
        self._key_schema_cache: dict = {}
        # rendered %s-templates for the columnar paths, and the same cut
        # into pieces for the native renderer
        self._fast_tmpl_cache: dict = {}
        if packer == "schema_registry":
            from transferia_tpu.debezium.packer import SchemaRegistryPacker
            from transferia_tpu.schemaregistry import SchemaRegistryClient

            client = SchemaRegistryClient(
                schema_registry_url, user=schema_registry_user,
                password=schema_registry_password)
            self.key_packer = SchemaRegistryPacker(client, is_key=True)
            self.value_packer = SchemaRegistryPacker(client, is_key=False)
        elif packer not in ("", "include_schema", "skip_schema"):
            raise ValueError(f"unknown debezium packer {packer!r}")
        elif packer:
            self.include_schema = packer == "include_schema"

    def topic_for(self, item: ChangeItem) -> str:
        """The topic this item's message lands on: the sink's fixed topic
        when configured, else the kafka sink's per-table '<ns>.<table>'.
        SR subject names must match this (TopicNameStrategy), or
        consumers looking up '<actual-topic>-value' find nothing."""
        if self.sink_topic:
            return self.sink_topic
        return f"{item.schema}.{item.table}" if item.schema \
            else item.table

    # -- schema blocks (cached per table schema fingerprint) ---------------
    def _value_schema(self, item: ChangeItem, schema: TableSchema) -> dict:
        fqtn = f"{self.topic_prefix}.{item.schema}.{item.table}"
        cached = self._value_schema_cache.get((fqtn, schema.fingerprint()))
        if cached is not None:
            return cached
        row_fields = [_field_schema(c) for c in schema]
        row_struct = lambda name: {  # noqa: E731
            "type": "struct", "optional": True, "field": name,
            "fields": row_fields,
            "name": f"{fqtn}.Value",
        }
        out = {
            "type": "struct",
            "name": f"{fqtn}.Envelope",
            "optional": False,
            "fields": [
                row_struct("before"),
                row_struct("after"),
                {
                    "type": "struct", "optional": False, "field": "source",
                    "name": "io.debezium.connector.common.Source",
                    "fields": [
                        {"type": "string", "optional": False,
                         "field": "version"},
                        {"type": "string", "optional": False,
                         "field": "connector"},
                        {"type": "string", "optional": False, "field": "name"},
                        {"type": "int64", "optional": False, "field": "ts_ms"},
                        {"type": "string", "optional": True,
                         "field": "snapshot"},
                        {"type": "string", "optional": False, "field": "db"},
                        {"type": "string", "optional": True, "field": "schema"},
                        {"type": "string", "optional": False, "field": "table"},
                        {"type": "int64", "optional": True, "field": "lsn"},
                        {"type": "string", "optional": True, "field": "txId"},
                    ],
                },
                {"type": "string", "optional": False, "field": "op"},
                {"type": "int64", "optional": True, "field": "ts_ms"},
            ],
        }
        self._value_schema_cache[(fqtn, schema.fingerprint())] = out
        return out

    def _key_schema(self, item: ChangeItem, schema: TableSchema) -> dict:
        fqtn = f"{self.topic_prefix}.{item.schema}.{item.table}"
        cached = self._key_schema_cache.get((fqtn, schema.fingerprint()))
        if cached is not None:
            return cached
        out = {
            "type": "struct", "optional": False, "name": f"{fqtn}.Key",
            "fields": [_field_schema(c) for c in schema.key_columns()],
        }
        self._key_schema_cache[(fqtn, schema.fingerprint())] = out
        return out

    # -- payload ------------------------------------------------------------
    def _row_payload(self, names, values, schema: TableSchema) -> dict:
        out = {}
        for n, v in zip(names, values):
            cs = schema.find(n)
            out[n] = encode_value(cs.data_type, v,
                                  cs.original_type) if cs else v
        return out

    def _source(self, item: ChangeItem, snapshot: bool) -> dict:
        return {
            "version": self.VERSION,
            "connector": self.connector,
            "name": self.topic_prefix,
            "ts_ms": item.commit_time_ns // 1_000_000 or
            int(time.time() * 1000),
            "snapshot": "true" if snapshot else "false",
            "db": self.source_db_type,
            "schema": item.schema,
            "table": item.table,
            "lsn": item.lsn or None,
            "txId": item.txn_id or None,
        }

    def emit_item(self, item: ChangeItem, snapshot: bool = False
                  ) -> list[tuple[Optional[bytes], Optional[bytes]]]:
        """One row -> [(key, value)] (+ tombstone for deletes); the key
        is None for a table without a primary key."""
        schema = item.table_schema
        if schema is None:
            raise ValueError("debezium emitter requires table_schema")
        op = {Kind.INSERT: "r" if snapshot else "c",
              Kind.UPDATE: "u", Kind.DELETE: "d"}.get(item.kind)
        if op is None:
            return []  # control events don't serialize to debezium

        key_vals = {}
        for c in schema.key_columns():
            if item.kind == Kind.DELETE and item.old_keys.key_names:
                key_vals[c.name] = encode_value(
                    c.data_type, item.old_keys.as_dict().get(c.name),
                    c.original_type,
                )
            else:
                key_vals[c.name] = encode_value(
                    c.data_type, item.value(c.name), c.original_type,
                )

        after = None
        before = None
        if item.kind != Kind.DELETE:
            after = self._row_payload(item.column_names, item.column_values,
                                      schema)
        if item.kind in (Kind.UPDATE, Kind.DELETE) and \
                item.old_keys.key_names:
            before = self._row_payload(
                item.old_keys.key_names, item.old_keys.key_values, schema
            )

        value_payload = {
            "before": before,
            "after": after,
            "source": self._source(item, snapshot),
            "op": op,
            "ts_ms": int(time.time() * 1000),
        }
        keyless = not schema.key_columns()
        if self.value_packer is not None:
            # Confluent wire format: schemas live in the registry
            topic = self.topic_for(item)
            key_b = None if keyless else self.key_packer.pack(
                topic, self._key_schema(item, schema), key_vals)
            value_b = self.value_packer.pack(
                topic, self._value_schema(item, schema), value_payload)
            out = [(key_b, value_b)]
            if item.kind == Kind.DELETE and self.emit_tombstones:
                out.append((key_b, None))
            return out
        if self.include_schema:
            key_obj = {"schema": self._key_schema(item, schema),
                       "payload": key_vals}
            value_obj = {"schema": self._value_schema(item, schema),
                         "payload": value_payload}
        else:
            key_obj, value_obj = key_vals, value_payload
        # a table without a primary key: a null message key, as Debezium
        # gives it (the sink spreads null keys over the partitions)
        key_b = None if keyless else json.dumps(
            key_obj, separators=(",", ":"), default=str).encode()
        value_b = json.dumps(value_obj, separators=(",", ":"),
                             default=str).encode()
        out: list[tuple[Optional[bytes], Optional[bytes]]] = [
            (key_b, value_b)]
        if item.kind == Kind.DELETE and self.emit_tombstones:
            out.append((key_b, None))
        return out

    def emit_batch(self, batch, snapshot: bool = False
                   ) -> list[tuple[Optional[bytes], Optional[bytes]]]:
        """ColumnBatch or row list -> envelope pairs, order-preserving."""
        return self._emit(batch, snapshot, cut=True)

    def emit_block(self, batch, snapshot: bool = False):
        """The native path's messages as one MessageBlock, with no object
        per row; the pairs of the path that took the batch where another
        did (nothing renders twice)."""
        return self._emit(batch, snapshot, cut=False)

    def _emit(self, batch, snapshot: bool, cut: bool):
        """Render in the `serialize` span; cut: pairs on every path, the
        block cut here (the one place that cuts it)."""
        with trace.span("serialize", format="debezium") as sp:
            out, rows, path = self._emit_batch(batch, snapshot)
            if cut and path == "native":
                out = out.pairs()
            if sp:
                sp.add(path=path, rows=rows)
        if rows:
            trace.TELEMETRY.record_debezium_rows(rows, path)
        return out

    def _emit_batch(self, batch, snapshot: bool) -> tuple:
        """(a MessageBlock or pairs, rows rendered, the path that took
        them: "native" - the block -, "fast" or "row")."""
        items: Iterable[ChangeItem]
        if isinstance(batch, ColumnBatch):
            taken = self._emit_columnar(batch, snapshot)
            if taken is not None:
                return taken[0], batch.n_rows, taken[1]
            items = batch.to_rows()
        else:
            items = batch
        out = []
        rows = 0
        for it in items:
            if it.is_row_event():
                rows += 1
                out.extend(self.emit_item(it, snapshot))
        return out, rows, "row"

    # -- insert-only columnar batches: the native and the Python renderer ----

    # original_type (provider, base) combinations encode_value special-
    # cases; columns carrying them take the per-value path
    _SLOW_MYSQL = ("bigint unsigned", "time", "year", "enum", "set", "bit")
    # chars safe to embed in a JSON string unescaped under ensure_ascii:
    # printable ASCII minus '"' and '\'
    _JSON_SAFE = re.compile(r'[^ !#-\[\]-~]')
    # canonical types whose cell is the digits of an integer
    _DIGITS = (CanonicalType.INT8, CanonicalType.INT16,
               CanonicalType.INT32, CanonicalType.INT64,
               CanonicalType.UINT8, CanonicalType.UINT16,
               CanonicalType.UINT32, CanonicalType.UINT64,
               CanonicalType.DATE, CanonicalType.DATETIME,
               CanonicalType.TIMESTAMP)

    @classmethod
    def _slow_original(cls, orig: str) -> bool:
        if not orig:
            return False
        provider, base, _args = _split_original(orig)
        # pg arrays/money/ranges/bits: keep exact
        return provider == "pg" or (
            provider == "mysql" and base in cls._SLOW_MYSQL)

    @staticmethod
    def _digit_values(col, ct, orig: str) -> Optional[np.ndarray]:
        """The integers whose digits are the cells of a _DIGITS column,
        scaled to what Debezium's semantic type counts."""
        data = col.data
        if data is None:
            return None
        if ct == CanonicalType.DATETIME:
            if data.dtype.kind == "M":
                data = data.astype("datetime64[s]").astype(np.int64)
            # seconds -> ms (io.debezium.time.Timestamp)
            return data.astype(np.int64) * 1000
        if ct == CanonicalType.TIMESTAMP:
            if data.dtype.kind == "M":
                data = data.astype("datetime64[us]").astype(np.int64)
            if mysql_datetime_millis(orig):
                data = data.astype(np.int64) // 1000
            return data
        if ct == CanonicalType.DATE and data.dtype.kind == "M":
            data = data.astype("datetime64[D]").astype(np.int64)
        return data

    def _col_fragments(self, col, cs) -> Optional[list]:
        """Per-row JSON value fragments for one column, byte-identical to
        json.dumps(encode_value(...)); None = out of the fast envelope."""
        orig = cs.original_type or ""
        slow_orig = self._slow_original(orig)
        ct = cs.data_type
        frags: Optional[list] = None
        if not slow_orig:
            if ct in self._DIGITS:
                data = self._digit_values(col, ct, orig)
                if data is None:
                    return None
                frags = data.astype("U").tolist()
            elif ct in (CanonicalType.FLOAT, CanonicalType.DOUBLE):
                data = col.data
                # NaN/inf spell differently in json ('NaN'/'Infinity');
                # rare — keep the exact per-row path for those batches
                if data is None or not np.isfinite(data).all():
                    return None
                frags = list(map(repr, data.astype(np.float64).tolist()))
            elif ct == CanonicalType.BOOLEAN:
                data = col.data
                if data is None:
                    return None
                frags = [("true" if v else "false")
                         for v in data.tolist()]
            elif ct in (CanonicalType.UTF8, CanonicalType.DECIMAL):
                safe = self._JSON_SAFE
                dumps = json.dumps
                frags = [
                    "null" if s is None
                    else ('"' + s + '"') if not safe.search(s)
                    else dumps(s)
                    for s in col.to_pylist()
                ]
            elif ct == CanonicalType.STRING:
                b64 = base64.b64encode
                frags = [
                    "null" if v is None
                    else '"' + b64(v).decode() + '"'
                    for v in col.to_pylist()
                ]
        if frags is None:
            # exact fallback: per-value encode + dumps (still columnar —
            # no ChangeItem materialization)
            dumps = json.dumps
            frags = [
                dumps(encode_value(ct, v, orig), separators=(",", ":"),
                      default=str)
                for v in col.to_pylist()
            ]
            return frags
        if col.validity is not None:
            frags = [f if ok else "null"
                     for f, ok in zip(frags, col.validity.tolist())]
        return frags

    def _emit_columnar_fast(self, batch: ColumnBatch, snapshot: bool
                            ) -> Optional[list]:
        """The pairs of an insert-only JSON-mode batch from its columns;
        None defers to the per-row path."""
        taken = self._emit_columnar(batch, snapshot)
        if taken is None:
            return None
        out, path = taken
        return out.pairs() if path == "native" else out

    def _emit_columnar(self, batch: ColumnBatch, snapshot: bool
                       ) -> Optional[tuple]:
        """(a MessageBlock, "native") or (pairs, "fast") for a batch
        inside the envelope (the module docstring says which batch takes
        which path)."""
        if self.value_packer is not None:
            return None
        schema = batch.schema
        if schema is None or batch.n_rows == 0:
            return None
        if batch.kinds is not None:
            from transferia_tpu.abstract.kinds import KIND_CODES

            if not (batch.kinds == KIND_CODES[Kind.INSERT]).all():
                return None
        key_cols = schema.key_columns()
        names = [cs.name for cs in schema]
        if set(n for n in names) - set(batch.columns.keys()):
            return None

        cdll = native.lib()
        if batch.commit_times is not None or batch.lsns is not None \
                or getattr(batch, "txn_ids", None) is not None:
            # a source block row by row is replication's: batches of a
            # few inserts, which the Python renderer takes quicker
            cdll = None
        # a column the native renderer cannot read from the batch's own
        # buffers comes to it as the Python renderer's fragments, which
        # are made once for both
        cols = []
        frag_by_name = {}
        for cs in schema:
            col = batch.columns[cs.name]
            spec = None if cdll is None else self._native_column(col, cs)
            if spec is None:
                frags = self._col_fragments(col, cs)
                if frags is None:
                    return None
                frag_by_name[cs.name] = frags
                spec = None if cdll is None else _packed_fragments(frags)
            cols.append(spec)
        if cdll is not None and all(spec is not None for spec in cols):
            block = self._render_native(cdll, batch, schema, names,
                                        key_cols, cols, snapshot)
            if block is not None:
                return block, "native"
        for cs in schema:
            if cs.name not in frag_by_name:
                frags = self._col_fragments(batch.columns[cs.name], cs)
                if frags is None:
                    return None
                frag_by_name[cs.name] = frags
        return self._render_fast(batch, schema, names, key_cols,
                                 frag_by_name, snapshot), "fast"

    def _native_column(self, col, cs) -> Optional[tuple]:
        """(kind, values, offsets, validity) of a column the native
        renderer reads from the batch's own buffers - integers to write
        as digits, UTF8 or DECIMAL text to quote - by the column's type,
        original type and dtype alone; None: _col_fragments renders it."""
        orig = cs.original_type or ""
        if col.is_lazy_dict or self._slow_original(orig):
            return None
        ct = cs.data_type
        if ct in self._DIGITS:
            data = self._digit_values(col, ct, orig)
            return None if data is None else _digit_column(
                data, col.validity)
        if ct in (CanonicalType.UTF8, CanonicalType.DECIMAL) \
                and col.offsets is not None:
            return _TEXT, col.data, col.offsets, col.validity
        return None

    def _render_native(self, cdll, batch: ColumnBatch, schema, names,
                       key_cols, cols: list, snapshot: bool
                       ) -> Optional[MessageBlock]:
        """The messages of a batch with no source metadata of its own as a
        MessageBlock, its values and its keys written by
        native/hostops.cpp into one buffer each with the GIL released;
        None where it takes no part (a text cell that is not UTF-8): the
        Python renderer decides."""
        n = batch.n_rows
        now_ms = int(time.time() * 1000)
        after_p, key_p, (head, mid, tail), src_p = self._templates(
            batch, schema, names, key_cols, snapshot)[-1]
        # one source block for every row: ts_ms now, no lsn, no txId
        src_p = [src_p[0] + str(now_ms) + src_p[1] + "null"
                 + src_p[2] + "null" + src_p[3]]
        value_p = _splice(_splice([head], after_p), _splice([mid], src_p))
        value_p[-1] += tail.replace("\x00TS\x00", str(now_ms))

        kinds = np.zeros(len(cols), dtype=np.int32)
        data = np.zeros(len(cols), dtype=np.uint64)
        offsets = np.zeros(len(cols), dtype=np.uint64)
        validity = np.zeros(len(cols), dtype=np.uint64)
        held = []  # the buffers whose addresses the renderer reads
        for c, (kind, values, off, valid) in enumerate(cols):
            kinds[c] = kind
            if off is None:
                values = np.ascontiguousarray(values)
                if values.shape != (n,):
                    return None
            else:
                values = np.ascontiguousarray(values, dtype=np.uint8)
                off = np.ascontiguousarray(off, dtype=np.int32)
                if off.shape != (n + 1,) or off[0] < 0 \
                        or off[-1] > len(values):
                    raise ValueError(
                        f"column {c} of {batch.table_id}: offsets do not "
                        f"fit {n} rows over {len(values)} bytes")
                offsets[c] = off.ctypes.data
                held.append(off)
            data[c] = values.ctypes.data
            held.append(values)
            if valid is not None:
                valid = np.ascontiguousarray(valid, dtype=np.bool_)
                if valid.shape != (n,):
                    return None
                validity[c] = valid.ctypes.data
                held.append(valid)

        def render(pieces: list, slots: list) -> Optional[tuple]:
            """(buffer, every row's offset in it) of a message's constant
            pieces and slots: a walk for the sizes, a walk that writes;
            None: a cell is not UTF-8."""
            raw = [p.encode() for p in pieces]
            piece_off = np.zeros(len(raw) + 1, dtype=np.int64)
            np.cumsum([len(p) for p in raw], out=piece_off[1:])
            slot_cols = np.asarray(slots, dtype=np.int32)
            row_off = np.empty(n + 1, dtype=np.int64)
            total = cdll.debezium_render_size(
                n, kinds, data, offsets, validity, len(slots), slot_cols,
                piece_off, row_off)
            if total == -2:
                return None
            if total < 0:
                raise ValueError(
                    f"{batch.table_id}: a column's offsets decrease")
            out = native.new_bytes(None, total)
            written = cdll.debezium_render_write(
                0, n, kinds, data, offsets, validity, len(slots),
                slot_cols, b"".join(raw), piece_off, out)
            if written != total:
                raise RuntimeError(
                    f"debezium renderer wrote {written} of {total} bytes")
            return out, row_off

        values = render(value_p, list(range(len(names))))
        if values is None:
            return None
        block = MessageBlock(n, *values)
        if key_cols:
            keys = render(key_p, [names.index(c.name) for c in key_cols])
            if keys is None:
                return None
            block.keys, block.key_offsets = keys
        # no primary key: no keys, every message key null (emit_item's
        # rule)
        return block

    def _templates(self, batch: ColumnBatch, schema, names, key_cols,
                   snapshot: bool) -> tuple:
        """ALL static bytes (incl. the full schema blocks) render once
        per (table, schema, mode) and cache - re-dumping a multi-KB
        schema json per small CDC batch would dwarf the row rendering
        the columnar paths accelerate.  _build_templates' four
        %s-templates, then the same four cut at their slots into the
        constant pieces the native renderer copies."""
        tid = batch.table_id
        cache_key = (tid.namespace, tid.name, schema.fingerprint(),
                     snapshot)
        tmpl = self._fast_tmpl_cache.get(cache_key)
        if tmpl is None:
            fmts = self._build_templates(schema, names, key_cols,
                                         tid.namespace, tid.name, snapshot)
            tmpl = fmts + (tuple(_cut(f) for f in fmts),)
            self._fast_tmpl_cache[cache_key] = tmpl
        return tmpl

    def _build_templates(self, schema, names, key_cols, item_schema,
                         item_table, snapshot) -> tuple:
        """All static envelope bytes as %s-templates (cached upstream)."""
        def esc(s: str) -> str:
            # static json text going into a %-template
            return json.dumps(s, separators=(",", ":"),
                              default=str).replace("%", "%%")

        after_fmt = "{" + ",".join(esc(n) + ":%s" for n in names) + "}"
        key_payload_fmt = "{" + ",".join(
            esc(c.name) + ":%s" for c in key_cols) + "}"
        op = "r" if snapshot else "c"
        src_fmt = (
            '{"version":' + esc(self.VERSION)
            + ',"connector":' + esc(self.connector)
            + ',"name":' + esc(self.topic_prefix)
            + ',"ts_ms":%s,"snapshot":'
            + ('"true"' if snapshot else '"false"')
            + ',"db":' + esc(self.source_db_type)
            + ',"schema":' + esc(item_schema)
            + ',"table":' + esc(item_table)
            + ',"lsn":%s,"txId":%s}'
        )
        env_core = ('{"before":null,"after":%s,"source":%s,"op":"' + op
                    + '","ts_ms":\x00TS\x00}')
        if self.include_schema:
            # only schema-block naming reads .schema/.table off the item
            class _Shim:
                schema = item_schema
                table = item_table

            shim = _Shim()
            vschema = json.dumps(self._value_schema(shim, schema),
                                 separators=(",", ":"), default=str)
            kschema = json.dumps(self._key_schema(shim, schema),
                                 separators=(",", ":"), default=str)
            value_fmt = ('{"schema":' + vschema.replace("%", "%%")
                         + ',"payload":' + env_core + "}")
            key_fmt = ('{"schema":' + kschema.replace("%", "%%")
                       + ',"payload":' + key_payload_fmt + "}")
        else:
            value_fmt = env_core
            key_fmt = key_payload_fmt
        return after_fmt, key_fmt, value_fmt, src_fmt

    def _render_fast(self, batch: ColumnBatch, schema, names, key_cols,
                     frag_by_name: dict, snapshot: bool) -> list:

        now_ms = int(time.time() * 1000)
        # \x00TS\x00 marks the envelope timestamp slot (a NUL can never
        # appear in json text)
        after_fmt, key_fmt_t, value_fmt_t, src_fmt = self._templates(
            batch, schema, names, key_cols, snapshot)[:4]
        key_fmt = key_fmt_t
        value_fmt = value_fmt_t.replace("\x00TS\x00", str(now_ms))
        n = batch.n_rows
        if batch.commit_times is not None:
            ts_list = [str(t // 1_000_000) if t else str(now_ms)
                       for t in batch.commit_times.tolist()]
        else:
            ts_list = None  # constant
        if batch.lsns is not None:
            lsn_list = [str(int(v)) if v else "null"
                        for v in batch.lsns.tolist()]
        else:
            lsn_list = None
        txns = getattr(batch, "txn_ids", None)
        if txns is not None:
            # substituted values are literal — plain json escaping only
            txn_list = [json.dumps(t) if t else "null" for t in txns]
        else:
            txn_list = None
        if ts_list is None and lsn_list is None and txn_list is None:
            src_strs = [src_fmt % (now_ms, "null", "null")] * n
        else:
            ts_it = ts_list or [str(now_ms)] * n
            lsn_it = lsn_list or ["null"] * n
            txn_it = txn_list or ["null"] * n
            src_strs = list(map(src_fmt.__mod__,
                                zip(ts_it, lsn_it, txn_it)))

        col_frags = [frag_by_name[nm] for nm in names]
        after_strs = list(map(after_fmt.__mod__, zip(*col_frags)))
        value_strs = list(map(value_fmt.__mod__,
                              zip(after_strs, src_strs)))
        if not key_cols:
            # no primary key: a null message key (emit_item's rule)
            return [(None, v.encode()) for v in value_strs]
        key_frags = [frag_by_name[c.name] for c in key_cols]
        key_strs = list(map(key_fmt.__mod__, zip(*key_frags)))
        return [(k.encode(), v.encode())
                for k, v in zip(key_strs, value_strs)]
