"""Parser registry plugins beyond the generic JSON/TSKV pair.

Reference parity: pkg/parsers/registry/ — audittrailsv1, blank, cloudevents,
cloudlogging, confluentschemaregistry, debezium, json, logfeller, native,
protobuf, raw_to_table, tskv.  json/tskv live in generic.py; logfeller is
Yandex-internal and intentionally out of scope.
"""

from __future__ import annotations

import json
import logging
from typing import Optional, Sequence

logger = logging.getLogger(__name__)

from transferia_tpu.abstract.change_item import ChangeItem
from transferia_tpu.abstract.schema import (
    CanonicalType,
    ColSchema,
    TableID,
    TableSchema,
)
from transferia_tpu.columnar.batch import Column, ColumnBatch
from transferia_tpu.parsers.base import (
    Message,
    ParseResult,
    Parser,
    unparsed_batch,
)
from transferia_tpu.parsers.generic import GenericJsonParser
from transferia_tpu.parsers.registry import register_parser

import transferia_tpu.parsers.generic  # noqa: F401  (registers json/tskv)


# Raw queue-mirror schema (changeitem/mirror.go: topic/partition/offset/
# write time + raw data as the row).
RAW_SCHEMA = TableSchema([
    ColSchema("topic", CanonicalType.UTF8, primary_key=True),
    ColSchema("partition", CanonicalType.UINT32, primary_key=True),
    ColSchema("offset", CanonicalType.UINT64, primary_key=True),
    ColSchema("timestamp", CanonicalType.TIMESTAMP),
    ColSchema("key", CanonicalType.STRING),
    ColSchema("data", CanonicalType.STRING),
])


@register_parser("blank")
@register_parser("raw_to_table")
class BlankParser(Parser):
    """Messages pass through as raw rows (registry/blank, raw_to_table)."""

    def __init__(self, table: str = "", namespace: str = ""):
        self.table = table
        self.namespace = namespace

    def do_batch(self, messages: Sequence[Message]) -> ParseResult:
        if not messages:
            return ParseResult()
        table = TableID(self.namespace,
                        self.table or messages[0].topic or "data")
        batch = ColumnBatch.from_pydict(table, RAW_SCHEMA, {
            "topic": [m.topic for m in messages],
            "partition": [m.partition for m in messages],
            "offset": [m.offset for m in messages],
            "timestamp": [m.write_time_ns // 1000 for m in messages],
            "key": [m.key for m in messages],
            "data": [m.value for m in messages],
        })
        return ParseResult(batches=[batch])

    def result_schema(self) -> TableSchema:
        return RAW_SCHEMA


@register_parser("debezium")
class DebeziumParser(Parser):
    """Debezium envelopes -> ChangeItems -> columnar blocks
    (registry/debezium + engine)."""

    def __init__(self, schema_registry_url: str = "",
                 schema_registry_user: str = "",
                 schema_registry_password: str = "", **kw):
        from transferia_tpu.debezium import DebeziumReceiver

        unpacker = None
        if schema_registry_url:
            # Confluent wire-format messages (0x00 + schema id frame)
            from transferia_tpu.debezium.packer import Unpacker
            from transferia_tpu.schemaregistry import SchemaRegistryClient

            unpacker = Unpacker(SchemaRegistryClient(
                schema_registry_url, user=schema_registry_user,
                password=schema_registry_password))
        self.receiver = DebeziumReceiver(unpacker=unpacker)

    def do_batch(self, messages: Sequence[Message]) -> ParseResult:
        items: list[ChangeItem] = []
        bad: list[Message] = []
        reasons: list[str] = []
        for m in messages:
            try:
                it = self.receiver.receive(m.value, m.key or None)
                if it is not None:
                    items.append(it)
            except (ValueError, KeyError, TypeError) as e:
                bad.append(m)
                reasons.append(f"debezium: {e}")
        result = ParseResult()
        # group consecutive same-(table, schema) runs into columnar blocks
        run: list[ChangeItem] = []

        def flush():
            if run:
                result.batches.append(ColumnBatch.from_rows(run))
                run.clear()

        for it in items:
            if run and (it.table_id != run[0].table_id
                        or it.table_schema != run[0].table_schema):
                flush()
            run.append(it)
        flush()
        if bad:
            result.unparsed = unparsed_batch(bad, reasons)
        return result


@register_parser("cloudevents")
class CloudEventsParser(Parser):
    """CloudEvents 1.0 structured-JSON messages (registry/cloudevents)."""

    SCHEMA = TableSchema([
        ColSchema("id", CanonicalType.UTF8, primary_key=True),
        ColSchema("source", CanonicalType.UTF8, primary_key=True),
        ColSchema("specversion", CanonicalType.UTF8),
        ColSchema("type", CanonicalType.UTF8),
        ColSchema("subject", CanonicalType.UTF8),
        ColSchema("time", CanonicalType.UTF8),
        ColSchema("datacontenttype", CanonicalType.UTF8),
        ColSchema("data", CanonicalType.ANY),
    ])

    def __init__(self, table: str = "cloudevents", namespace: str = ""):
        self.table = TableID(namespace, table)

    def do_batch(self, messages: Sequence[Message]) -> ParseResult:
        rows, bad, reasons = [], [], []
        for m in messages:
            try:
                obj = json.loads(m.value)
                if not isinstance(obj, dict) or "id" not in obj \
                        or "source" not in obj:
                    raise ValueError("missing required id/source")
                rows.append(obj)
            except ValueError as e:
                bad.append(m)
                reasons.append(f"cloudevents: {e}")
        result = ParseResult()
        if rows:
            result.batches.append(ColumnBatch.from_pydict(
                self.table, self.SCHEMA, {
                    c.name: [r.get(c.name) for r in rows]
                    for c in self.SCHEMA
                }
            ))
        if bad:
            result.unparsed = unparsed_batch(bad, reasons)
        return result


@register_parser("native")
class NativeParser(Parser):
    """Framework-native ChangeItem JSON lines (registry/native)."""

    def do_batch(self, messages: Sequence[Message]) -> ParseResult:
        items, bad, reasons = [], [], []
        for m in messages:
            for line in m.value.split(b"\n"):
                if not line.strip():
                    continue
                try:
                    items.append(ChangeItem.from_json(json.loads(line)))
                except (ValueError, KeyError) as e:
                    bad.append(Message(value=line, topic=m.topic,
                                       partition=m.partition,
                                       offset=m.offset))
                    reasons.append(f"native: {e}")
        result = ParseResult()
        run: list[ChangeItem] = []
        for it in items:
            if run and (it.table_id != run[0].table_id
                        or it.table_schema != run[0].table_schema):
                result.batches.append(ColumnBatch.from_rows(run))
                run = []
            run.append(it)
        if run:
            result.batches.append(ColumnBatch.from_rows(run))
        if bad:
            result.unparsed = unparsed_batch(bad, reasons)
        return result


@register_parser("audittrailsv1")
def _audittrails(cfg: dict) -> Parser:
    """Audit-trails preset of the generic parser (registry/audittrailsv1)."""
    return GenericJsonParser(
        schema=[
            {"name": "event_id", "type": "utf8", "key": True},
            {"name": "event_source", "type": "utf8"},
            {"name": "event_type", "type": "utf8"},
            {"name": "event_time", "type": "utf8"},
            {"name": "authentication", "type": "any"},
            {"name": "authorization", "type": "any"},
            {"name": "resource_metadata", "type": "any"},
            {"name": "request_metadata", "type": "any"},
            {"name": "event_status", "type": "utf8"},
            {"name": "details", "type": "any"},
        ],
        table=cfg.get("table", "audit_trails"),
        add_system_cols=False,
    )


@register_parser("cloudlogging")
def _cloudlogging(cfg: dict) -> Parser:
    """Cloud-logging preset (registry/cloudlogging)."""
    return GenericJsonParser(
        schema=[
            {"name": "uid", "type": "utf8", "key": True},
            {"name": "resource", "type": "any"},
            {"name": "timestamp", "type": "utf8"},
            {"name": "ingested_at", "type": "utf8"},
            {"name": "saved_at", "type": "utf8"},
            {"name": "level", "type": "utf8"},
            {"name": "message", "type": "utf8"},
            {"name": "json_payload", "type": "any"},
            {"name": "stream_name", "type": "utf8"},
        ],
        table=cfg.get("table", "cloud_logging"),
        add_system_cols=False,
    )


@register_parser("protobuf")
class ProtobufParser(Parser):
    """Protobuf messages via a compiled message class
    (registry/protobuf; lazy per-field decode is a later optimization).

    config: message: "package.module:MessageClass", table, namespace.
    """

    def __init__(self, message: str, table: str = "data",
                 namespace: str = ""):
        import importlib

        mod, cls = message.split(":", 1)
        self.msg_cls = getattr(importlib.import_module(mod), cls)
        self.table = TableID(namespace, table)

    def do_batch(self, messages: Sequence[Message]) -> ParseResult:
        rows, bad, reasons = [], [], []
        from google.protobuf.json_format import MessageToDict

        for m in messages:
            try:
                pb = self.msg_cls()
                pb.ParseFromString(m.value)
                rows.append(MessageToDict(pb, preserving_proto_field_name=True))
            except Exception as e:  # protobuf raises DecodeError etc.
                bad.append(m)
                reasons.append(f"protobuf: {e}")
        result = ParseResult()
        if rows:
            seen: dict[str, CanonicalType] = {}
            for r in rows[:100]:
                for k, v in r.items():
                    from transferia_tpu.parsers.generic import _infer_type

                    seen.setdefault(k, _infer_type(v))
            schema = TableSchema([ColSchema(k, t) for k, t in seen.items()])
            result.batches.append(ColumnBatch.from_pydict(
                self.table, schema,
                {k: [r.get(k) for r in rows] for k in seen}
            ))
        if bad:
            result.unparsed = unparsed_batch(bad, reasons)
        return result


@register_parser("confluent_schema_registry")
class ConfluentSRParser(Parser):
    """Confluent wire format (magic byte 0 + 4-byte schema id + payload).

    Resolves schemas through a pluggable resolver (pkg/schemaregistry
    equivalent).  JSON-schema payloads decode via the generic parser;
    AVRO payloads decode with the in-repo schema-driven binary decoder
    (schemaregistry/avro.py) using the registered writer schema.
    """

    def __init__(self, table: str = "data", namespace: str = "",
                 resolver: Optional[object] = None,
                 registry_url: str = "", registry_user: str = "",
                 registry_password: str = ""):
        self.table = table
        self.namespace = namespace
        # resolver: callable(schema_id) -> field-spec list (the generic
        # parser's `schema` config) or None; a registry_url builds one over
        # the Confluent REST API (pkg/schemaregistry equivalent); absent
        # falls back to schema inference
        if resolver is None and registry_url:
            from transferia_tpu.schemaregistry import sr_resolver

            resolver = sr_resolver(registry_url, user=registry_user,
                                   password=registry_password)
        self.resolver = resolver
        self.registry_url = registry_url
        self.registry_user = registry_user
        self.registry_password = registry_password
        self._parsers: dict[int, GenericJsonParser] = {}
        self._avro: dict[int, object] = {}
        self._client = None

    def _sr_client(self):
        if self._client is None:
            # reuse the resolver's client when it exposes one (sr_resolver
            # does) — one connection/config/cache, not two
            self._client = getattr(self.resolver, "client", None)
        if self._client is None and self.registry_url:
            from transferia_tpu.schemaregistry import SchemaRegistryClient

            self._client = SchemaRegistryClient(
                self.registry_url, user=self.registry_user,
                password=self.registry_password)
        return self._client

    def _avro_for(self, schema_id: int):
        """AvroSchema for a registered AVRO entry; None when the registry
        says the id is NOT Avro (cached).  Transient registry failures
        RAISE: dead-lettering valid data on an outage would consume the
        offsets forever — the parse failure propagates so the runtime
        retries the batch without committing (at-least-once)."""
        if schema_id in self._avro:
            return self._avro[schema_id]
        client = self._sr_client()
        avro = None
        if client is not None:
            try:
                entry = client.schema_by_id(schema_id)
            except Exception as e:
                if "404" in str(e):
                    # PERMANENTLY absent id (deleted / foreign registry):
                    # cache the miss so the message dead-letters instead
                    # of poisoning the partition with endless retries
                    logger.warning("schema id %d not registered (404)",
                                   schema_id)
                    self._avro[schema_id] = None
                    return None
                raise  # transient outage: abort the batch for retry
            if entry.get("schemaType", "AVRO") == "AVRO":
                from transferia_tpu.schemaregistry.avro import AvroSchema

                try:
                    avro = AvroSchema(entry["schema"])
                except Exception as e:
                    logger.warning("schema id %d: bad avro schema (%s)",
                                   schema_id, e)
                    avro = None  # permanently undecodable: cacheable
        self._avro[schema_id] = avro
        return avro

    @staticmethod
    def _avro_col_type(node) -> CanonicalType:
        prim = {
            "int": CanonicalType.INT32, "long": CanonicalType.INT64,
            "float": CanonicalType.FLOAT, "double": CanonicalType.DOUBLE,
            "boolean": CanonicalType.BOOLEAN,
            "string": CanonicalType.UTF8, "bytes": CanonicalType.STRING,
        }
        if isinstance(node, str):
            return prim.get(node, CanonicalType.ANY)
        if node[0] == "union":
            # only the nullable-field idiom has a single concrete type;
            # multi-branch unions can carry any branch's value
            concrete = [b for b in node[1] if b != "null"]
            if len(concrete) == 1:
                return ConfluentSRParser._avro_col_type(concrete[0])
            return CanonicalType.ANY
        if node[0] == "enum":
            return CanonicalType.UTF8
        if node[0] == "fixed":
            return CanonicalType.STRING
        return CanonicalType.ANY

    # avro primitive -> (C type code, canonical type) for the flat-record
    # native fast path (hostops.cpp avro_decode_flat)
    _AVRO_C_TYPES = {
        "boolean": (1, CanonicalType.BOOLEAN),
        "int": (2, CanonicalType.INT32),
        "long": (2, CanonicalType.INT64),
        "float": (3, CanonicalType.FLOAT),
        "double": (4, CanonicalType.DOUBLE),
        "string": (5, CanonicalType.UTF8),
        "bytes": (5, CanonicalType.STRING),
    }

    def _flat_spec(self, avro):
        """(name, c_code, ctype, nullable, null_branch) per field when the
        schema is a flat record of primitives (None = out of envelope);
        cached per AvroSchema instance."""
        # cached ON the schema object: an id()-keyed dict would serve a
        # stale spec if a freed AvroSchema's address got reused
        spec = getattr(avro, "_flat_spec_cache", False)
        if spec is not False:
            return spec
        spec = None
        root = avro.root
        if isinstance(root, list) and root[0] == "record":
            out = []
            for name, t in root[2]:
                nullable, null_branch = False, 0
                node = t
                if isinstance(node, list) and node[0] == "union" \
                        and len(node[1]) == 2 and "null" in node[1]:
                    nullable = True
                    null_branch = node[1].index("null")
                    node = node[1][1 - null_branch]
                if not isinstance(node, str) \
                        or node not in self._AVRO_C_TYPES:
                    out = None
                    break
                code, ctype = self._AVRO_C_TYPES[node]
                out.append((name, code, ctype, nullable, null_branch))
            spec = out or None
        try:
            avro._flat_spec_cache = spec
        except AttributeError:  # slotted schema object: just recompute
            pass
        return spec

    def _avro_batch_native(self, avro, msgs: list[Message]):
        """Columnar decode of a flat-record run via the C decoder; None
        defers to the exact per-row path (out of envelope, native lib
        absent, or any malformed message in the run)."""
        from transferia_tpu.native import lib as native_lib

        cdll = native_lib()
        if cdll is None:
            return None
        spec = self._flat_spec(avro)
        if spec is None:
            return None
        import numpy as np

        n = len(msgs)
        payloads = [m.value for m in msgs]
        data = np.frombuffer(b"".join(payloads), dtype=np.uint8)
        offs = np.zeros(n + 1, dtype=np.int64)
        np.cumsum([len(p) for p in payloads], out=offs[1:])
        if int(offs[-1]) > 0x7FFF0000:
            # var-width offsets are int32 in the C decoder
            return None
        ftypes = np.array([c for _, c, _, _, _ in spec], dtype=np.uint8)
        fnull = np.array([1 if nl else 0 for *_, nl, _ in spec],
                         dtype=np.uint8)
        fbr = np.array([br for *_, br in spec], dtype=np.uint8)
        tasks = np.zeros((len(spec), 6), dtype=np.int64)
        holds = []
        for i, (name, code, ctype, nullable, _br) in enumerate(spec):
            validity = np.empty(n, dtype=np.uint8) if nullable else None
            if code == 5:
                cap = int(offs[-1])
                vdata = np.empty(max(cap, 1), dtype=np.uint8)
                voffs = np.empty(n + 1, dtype=np.int32)
                tasks[i, 1] = vdata.ctypes.data
                tasks[i, 2] = voffs.ctypes.data
                tasks[i, 3] = cap
                holds.append((vdata, voffs, validity))
            else:
                dt = {1: np.uint8, 2: np.int64, 3: np.float32,
                      4: np.float64}[code]
                out = np.empty(n, dtype=dt)
                tasks[i, 0] = out.ctypes.data
                holds.append((out, validity))
            if validity is not None:
                tasks[i, 4] = validity.ctypes.data
        rc = cdll.avro_decode_flat(
            data if data.size else np.zeros(1, dtype=np.uint8),
            offs, n, ftypes, fnull, fbr, len(spec), tasks.reshape(-1))
        if rc != n:
            return None
        cols = {}
        for i, (name, code, ctype, nullable, _br) in enumerate(spec):
            h = holds[i]
            validity = h[-1]
            v = None
            if validity is not None and not validity.all():
                v = validity.astype(np.bool_)
            if code == 5:
                vdata, voffs = h[0], h[1]
                flat = vdata[:int(voffs[n])]
                if ctype == CanonicalType.UTF8:
                    # the exact path DECODES strings (and dead-letters
                    # rows with invalid utf-8); one bulk validation over
                    # the flat buffer keeps the classification identical
                    try:
                        flat.tobytes().decode("utf-8")
                    except UnicodeDecodeError:
                        return None
                cols[name] = Column(name, ctype, flat, voffs, v)
            else:
                vals = h[0]
                if ctype == CanonicalType.INT32:
                    vals = vals.astype(np.int32)
                elif ctype == CanonicalType.BOOLEAN:
                    vals = vals.view(np.bool_)
                cols[name] = Column(name, ctype, vals, None, v)
        schema = TableSchema([
            ColSchema(name, ctype) for name, _, ctype, _, _ in spec])
        result = ParseResult()
        result.batches.append(ColumnBatch(
            TableID(self.namespace, self.table), schema, cols))
        return result

    def _avro_batch(self, avro, msgs: list[Message]) -> ParseResult:
        fast = None
        try:
            fast = self._avro_batch_native(avro, msgs)
        except Exception:  # any surprise: the exact path decides
            logger.debug("native avro fast path failed", exc_info=True)
        if fast is not None:
            return fast
        result = ParseResult()
        rows, bad, reasons = [], [], []
        for m in msgs:
            try:
                rows.append(avro.decode(m.value))
            except Exception as e:
                bad.append(m)
                reasons.append(f"avro: {e}")
        if rows:
            root = avro.root
            if isinstance(root, list) and root[0] == "record":
                cols = [(name, self._avro_col_type(t))
                        for name, t in root[2]]
            else:  # non-record root: single value column
                cols = [("value", self._avro_col_type(root))]
                rows = [{"value": r} for r in rows]
            schema = TableSchema([ColSchema(n, t) for n, t in cols])
            result.batches.append(ColumnBatch.from_pydict(
                TableID(self.namespace, self.table), schema,
                {n: [r.get(n) for r in rows] for n, _ in cols},
            ))
        if bad:
            result.unparsed = unparsed_batch(bad, reasons)
        return result

    def _parser_for(self, schema_id: int) -> GenericJsonParser:
        p = self._parsers.get(schema_id)
        if p is None:
            fields = None
            resolver_ok = True
            if self.resolver is not None:
                try:
                    fields = self.resolver(schema_id)
                except Exception as e:
                    # transient registry outage: fall back to inference for
                    # this batch but do NOT cache, so the id retries later
                    logger.warning(
                        "schema registry lookup for id %d failed (%s); "
                        "falling back to inference", schema_id, e,
                    )
                    resolver_ok = False
            p = GenericJsonParser(schema=fields, table=self.table,
                                  namespace=self.namespace)
            if resolver_ok:
                self._parsers[schema_id] = p
        return p

    def do_batch(self, messages: Sequence[Message]) -> ParseResult:
        import struct

        # contiguous runs per schema id: offset order within the batch must
        # survive schema evolution (CDC consumers replay in emit order)
        runs: list[tuple[int, list[Message]]] = []
        bad, reasons = [], []
        for m in messages:
            v = m.value
            if len(v) >= 5 and v[0] == 0:
                schema_id = struct.unpack(">I", v[1:5])[0]
                payload = v[5:]
                stripped = Message(
                    value=payload, key=m.key, topic=m.topic,
                    partition=m.partition, offset=m.offset,
                    write_time_ns=m.write_time_ns,
                )
                # the registry's schemaType is authoritative: an Avro
                # payload may begin with 0x7b ('{') by coincidence (e.g.
                # a long field encoding -62), so byte-sniffing only
                # decides when the id has no registered Avro schema
                if self._avro_for(schema_id) is not None:
                    kind = "avro"
                elif payload[:1] in (b"{", b"["):
                    kind = "json"
                else:
                    bad.append(m)
                    reasons.append(
                        "confluent-sr: binary payload and no AVRO schema "
                        "registered for this id"
                    )
                    continue
                if runs and runs[-1][0] == (schema_id, kind):
                    runs[-1][1].append(stripped)
                else:
                    runs.append(((schema_id, kind), [stripped]))
            else:
                bad.append(m)
                reasons.append("confluent-sr: missing magic byte")
        result = ParseResult()
        for (schema_id, kind), msgs in runs:
            if kind == "avro":
                sub = self._avro_batch(self._avro_for(schema_id), msgs)
            else:
                sub = self._parser_for(schema_id).do_batch(msgs)
            result.batches.extend(sub.batches)
            if sub.unparsed is not None:
                result.unparsed = sub.unparsed \
                    if result.unparsed is None else \
                    ColumnBatch.concat([result.unparsed, sub.unparsed])
        if bad:
            ub = unparsed_batch(bad, reasons)
            result.unparsed = ub if result.unparsed is None else \
                ColumnBatch.concat([result.unparsed, ub])
        return result
