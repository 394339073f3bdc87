"""ClickHouse provider: sharded sink, snapshot storage, DDL builder.

Reference parity: providers/clickhouse/sink.go:24-100 (sharder -> per-shard
lazy sinks), schema/ (DDL from canonical types), storage (SELECT-based
snapshot).  Typesystem target rules registered for "ch".
"""

from __future__ import annotations

import logging
from dataclasses import dataclass, field
from typing import Optional

import numpy as np

from transferia_tpu.abstract.commit import StagedSinker
from transferia_tpu.abstract.interfaces import (
    Batch,
    Pusher,
    SampleableStorage,
    Sinker,
    Storage,
    TableInfo,
    is_columnar,
)
from transferia_tpu.abstract.kinds import Kind
from transferia_tpu.abstract.schema import (
    CanonicalType,
    ColSchema,
    TableID,
    TableSchema,
)
from transferia_tpu.abstract.table import TableDescription
from transferia_tpu.columnar.batch import ColumnBatch
from transferia_tpu.models.endpoint import (
    CleanupPolicy,
    EndpointParams,
    register_endpoint,
)
from transferia_tpu.providers.clickhouse.client import CHClient, CHError
from transferia_tpu.providers.clickhouse.rowbinary import (
    encode_rowbinary,
    encoder_path,
)
from transferia_tpu.providers.registry import (
    Provider,
    TestResult,
    register_provider,
)
from transferia_tpu.stats import trace
from transferia_tpu.transform.plugins.sharder import hash_column_to_shards
from transferia_tpu.typesystem.rules import (
    register_source_rules,
    register_target_rules,
)

logger = logging.getLogger(__name__)

register_target_rules("ch", {
    CanonicalType.INT8: "Int8", CanonicalType.INT16: "Int16",
    CanonicalType.INT32: "Int32", CanonicalType.INT64: "Int64",
    CanonicalType.UINT8: "UInt8", CanonicalType.UINT16: "UInt16",
    CanonicalType.UINT32: "UInt32", CanonicalType.UINT64: "UInt64",
    CanonicalType.FLOAT: "Float32", CanonicalType.DOUBLE: "Float64",
    CanonicalType.BOOLEAN: "Bool", CanonicalType.STRING: "String",
    CanonicalType.UTF8: "String", CanonicalType.DATE: "Date32",
    CanonicalType.DATETIME: "DateTime",
    CanonicalType.TIMESTAMP: "DateTime64(6)",
    CanonicalType.INTERVAL: "Int64", CanonicalType.DECIMAL: "String",
    CanonicalType.ANY: "String",
})

register_source_rules("ch", {
    "int8": CanonicalType.INT8, "int16": CanonicalType.INT16,
    "int32": CanonicalType.INT32, "int64": CanonicalType.INT64,
    "uint8": CanonicalType.UINT8, "uint16": CanonicalType.UINT16,
    "uint32": CanonicalType.UINT32, "uint64": CanonicalType.UINT64,
    "float32": CanonicalType.FLOAT, "float64": CanonicalType.DOUBLE,
    "bool": CanonicalType.BOOLEAN, "string": CanonicalType.STRING,
    "date": CanonicalType.DATE, "date32": CanonicalType.DATE,
    "datetime": CanonicalType.DATETIME,
    "datetime64": CanonicalType.TIMESTAMP,
    "*": CanonicalType.ANY,
})


@dataclass
class CHShard:
    name: str
    hosts: list[str] = field(default_factory=list)


@register_endpoint
@dataclass
class CHTargetParams(EndpointParams):
    PROVIDER = "ch"
    IS_TARGET = True

    host: str = "localhost"
    port: int = 8123
    database: str = "default"
    user: str = "default"
    password: str = ""
    secure: bool = False
    shards: dict = field(default_factory=dict)   # name -> [host:port,...]
    cluster: str = ""   # discover shards from system.clusters instead
    shard_by: str = ""                           # column; "" = first PK
    engine: str = ""                             # override table engine
    insert_settings: dict = field(default_factory=dict)
    is_shardeable: bool = True
    bufferer: Optional[dict] = field(
        default_factory=lambda: {"trigger_rows": 100_000,
                                 "trigger_interval": 1.0}
    )

    def bufferer_config(self):
        return self.bufferer

    def shard_list(self) -> list[CHShard]:
        if not self.shards and self.cluster:
            return discover_cluster_shards(self)
        if not self.shards:
            return [CHShard("default", [f"{self.host}:{self.port}"])]
        return [CHShard(n, list(h)) for n, h in self.shards.items()]


def discover_cluster_shards(params: "CHTargetParams") -> list["CHShard"]:
    """Topology discovery (reference clickhouse/topology/): read the
    cluster's shard/replica layout from system.clusters on the seed host.
    Replicas within a shard become the shard's failover host list."""
    from transferia_tpu.providers.clickhouse.client import CHClient

    client = CHClient(host=params.host, port=params.port,
                      database=params.database, user=params.user,
                      password=params.password, secure=params.secure)
    rows = client.query_json(
        "SELECT shard_num, host_name, host_address, port "
        "FROM system.clusters "
        f"WHERE cluster = '{params.cluster}' "
        "ORDER BY shard_num, replica_num"
    )
    if not rows:
        raise ValueError(
            f"cluster {params.cluster!r} not found in system.clusters "
            f"on {params.host}:{params.port}"
        )
    by_shard: dict[int, list[str]] = {}
    for r in rows:
        host = r.get("host_address") or r.get("host_name")
        # system.clusters reports the NATIVE port; this provider speaks
        # HTTP, and cluster nodes conventionally share one HTTP port —
        # reuse the seed's (override with explicit `shards` otherwise)
        by_shard.setdefault(int(r["shard_num"]), []).append(
            f"{host}:{params.port}")
    out = [CHShard(f"shard{num}", hosts)
           for num, hosts in sorted(by_shard.items())]
    logger.info("discovered cluster %r: %d shards", params.cluster,
                len(out))
    return out


@register_endpoint
@dataclass
class CHSourceParams(EndpointParams):
    PROVIDER = "ch"
    IS_SOURCE = True

    host: str = "localhost"
    port: int = 8123
    database: str = "default"
    user: str = "default"
    password: str = ""
    secure: bool = False
    batch_rows: int = 131_072


def ddl_for_schema(table: TableID, schema: TableSchema,
                   engine: str = "", extra_cols: Optional[list] = None,
                   partition_by: str = "") -> str:
    """CREATE TABLE DDL from canonical schema (clickhouse/schema/).

    `extra_cols` ([(name, ch type)]) and `partition_by` serve the
    staged-commit sink: the final table carries the hidden
    `__trtpu_part` column and partitions by it, so a part publish maps
    onto ClickHouse's own atomic partition primitive
    (REPLACE/DROP PARTITION)."""
    from transferia_tpu.typesystem.rules import map_target_type

    cols = []
    for c in schema:
        ch_type = map_target_type("ch", c.data_type)
        if not c.required and not c.primary_key:
            ch_type = f"Nullable({ch_type})"
        cols.append(f"`{c.name}` {ch_type}")
    for name_, ch_type in extra_cols or []:
        cols.append(f"`{name_}` {ch_type}")
    keys = [f"`{c.name}`" for c in schema.key_columns()]
    order = ", ".join(keys) if keys else "tuple()"
    eng = engine or "MergeTree()"
    part = f" PARTITION BY `{partition_by}`" if partition_by else ""
    name = f"`{table.name}`" if not table.namespace \
        else f"`{table.namespace}__{table.name}`"
    return (
        f"CREATE TABLE IF NOT EXISTS {name} ({', '.join(cols)}) "
        f"ENGINE = {eng}{part} ORDER BY ({order})"
    )


def ch_table_name(table: TableID) -> str:
    return table.name if not table.namespace \
        else f"{table.namespace}__{table.name}"


class CHSinker(Sinker, StagedSinker):
    """Sharded insert sink (sink.go:24-100): rows fan out to shards by key
    hash; per-shard clients are lazy.  Deletes/updates collapse into
    ReplacingMergeTree semantics upstream (collapse middleware) — the sink
    itself inserts.

    Staged-commit capable on SINGLE-shard targets (abstract/commit.py):
    batches land in a per-(part, epoch) staging table and publish maps
    onto ClickHouse's atomic partition primitive — the final table is
    `PARTITION BY` the hidden `__trtpu_part` column and the publish is
    one `ALTER TABLE ... REPLACE PARTITION ID '<slug>' FROM <staging>`
    (empty restage: `DROP PARTITION ID`), fenced by the persisted
    max-epoch row per part in `__trtpu_commits`.  Multi-shard targets
    keep the at-least-once path: a part's rows span shards and there is
    no cross-shard atomic flip to map the publish onto.

    Migration bound: a final table created by the at-least-once path
    has no partition key, and ClickHouse cannot retrofit PARTITION BY
    onto an existing MergeTree — the first staged publish against such
    a table fails loudly at REPLACE PARTITION.  Recreate the table
    (CleanupPolicy.DROP does this at activation) before switching a
    pre-existing CH target to staged commits."""

    def __init__(self, params: CHTargetParams):
        self.params = params
        self.shards = params.shard_list()
        self._clients: dict[int, CHClient] = {}
        self._created: set[str] = set()
        self._stage = None  # staging.WireStage when open
        self._fence_ready = False

    def _client(self, shard_idx: int) -> CHClient:
        if shard_idx not in self._clients:
            host = self.shards[shard_idx].hosts[0]
            h, _, p = host.partition(":")
            self._clients[shard_idx] = CHClient(
                host=h, port=int(p or 8123),
                database=self.params.database, user=self.params.user,
                password=self.params.password, secure=self.params.secure,
                settings=self.params.insert_settings,
            )
        return self._clients[shard_idx]

    def close(self) -> None:
        # keep-alive pools hold sockets until released
        for client in self._clients.values():
            client.close()

    def _ensure_table(self, shard_idx: int, batch: ColumnBatch) -> None:
        self.ensure_table(shard_idx, batch.table_id, batch.schema)

    def ensure_table(self, shard_idx: int, table_id: TableID,
                     schema: TableSchema) -> None:
        """Create the target table on a shard once (also the a2 target's
        Init-event DDL path — one key scheme, one DDL builder)."""
        name = ch_table_name(table_id)
        key = f"{shard_idx}/{name}"
        if key in self._created:
            return
        ddl = ddl_for_schema(table_id, schema, self.params.engine)
        self._client(shard_idx).execute(ddl)
        self._created.add(key)

    def _shard_of(self, batch: ColumnBatch) -> np.ndarray:
        n_shards = len(self.shards)
        if n_shards == 1:
            return np.zeros(batch.n_rows, dtype=np.int32)
        col_name = self.params.shard_by
        if not col_name:
            keys = batch.schema.key_columns()
            col_name = keys[0].name if keys else next(iter(batch.columns))
        return hash_column_to_shards(batch.column(col_name), n_shards)

    def push(self, batch: Batch) -> None:
        if not is_columnar(batch):
            rows = [it for it in batch if it.is_row_event()]
            for it in batch:
                if it.kind in (Kind.TRUNCATE, Kind.DROP):
                    self._apply_cleanup(it.table_id, it.kind)
            if not rows:
                return
            batch = ColumnBatch.from_rows(rows)
        if batch.kinds is not None:
            raise ValueError(
                "CH sink is insert-only; collapse updates/deletes upstream "
                "or use a ReplacingMergeTree flow with version columns"
            )
        if self._stage is not None:
            self._stage_push(batch)
            return
        shards = self._shard_of(batch)
        for shard_idx in np.unique(shards):
            part = batch.filter(shards == shard_idx) \
                if len(self.shards) > 1 else batch
            self._ensure_table(int(shard_idx), part)
            self._insert(int(shard_idx), ch_table_name(part.table_id),
                         part)

    def _insert(self, shard_idx: int, table: str,
                batch: ColumnBatch) -> None:
        """One insert, two spans: the RowBinary encoding (`serialize`)
        and the HTTP POST that waits for the server (`sink_push`)."""
        nullable = {
            c.name: (not c.required and not c.primary_key)
            for c in batch.schema
        }
        sp = trace.span("serialize")
        with sp:
            payload = encode_rowbinary(batch, nullable)
            if sp:
                sp.add(format="rowbinary", path=encoder_path(),
                       rows=batch.n_rows, columns=len(batch.columns),
                       bytes=len(payload))
        sp = trace.span("sink_push")
        if sp:
            sp.add(direction="clickhouse_http", bytes=len(payload))
        with sp:
            self._client(shard_idx).insert_rowbinary(
                table, list(batch.columns), payload)

    def _apply_cleanup(self, table: TableID, kind: Kind) -> None:
        stmt = "TRUNCATE TABLE IF EXISTS" if kind == Kind.TRUNCATE \
            else "DROP TABLE IF EXISTS"
        for i in range(len(self.shards)):
            self._client(i).execute(f"{stmt} `{ch_table_name(table)}`")

    # -- StagedSinker (publish = atomic partition swap) ---------------------
    def staged_commit_available(self) -> bool:
        # a part's rows span shards on a sharded target: no single
        # atomic partition flip exists to map the publish onto
        return len(self.shards) == 1

    def _ensure_fence_table(self) -> None:
        from transferia_tpu.providers.staging import COMMITS_TABLE

        if self._fence_ready:
            return
        self._client(0).execute(
            f"CREATE TABLE IF NOT EXISTS `{COMMITS_TABLE}` "
            f"(`part_key` String, `epoch` Int64) "
            f"ENGINE = MergeTree() ORDER BY (`part_key`)")
        self._fence_ready = True

    def begin_part(self, key: str, epoch: int) -> None:
        from transferia_tpu.providers.staging import (
            WireStage,
            stage_ident_prefix,
        )

        stage = WireStage(key, epoch)
        # begin replaces — for EVERY epoch of this key (a crashed
        # earlier owner's staging table would otherwise leak forever)
        pfx = stage_ident_prefix(key)
        for r in self._client(0).query_json(
                "SELECT name, total_rows FROM system.tables "
                f"WHERE database = '{self.params.database}'"):
            if str(r.get("name", "")).startswith(pfx):
                self._client(0).execute(
                    f"DROP TABLE IF EXISTS `{r['name']}`")
        self._ensure_fence_table()
        self._stage = stage

    def _stage_push(self, batch: ColumnBatch) -> None:
        from transferia_tpu.providers.staging import META_COLUMN

        stage = self._stage
        staged = stage.state.stage(batch)
        if stage.schema is None:
            stage.tid = batch.table_id
            stage.schema = batch.schema
            # SAME structure + partition key as the final table
            # (REPLACE PARTITION requires it); the part column
            # DEFAULTs to this part's slug so inserts that omit it
            # land the whole staging table in partition <slug>
            self._client(0).execute(ddl_for_schema(
                TableID("", stage.table), batch.schema,
                self.params.engine,
                extra_cols=[(META_COLUMN,
                             f"String DEFAULT '{stage.slug}'")],
                partition_by=META_COLUMN))
        if staged.n_rows == 0:
            return
        try:
            self._insert(0, stage.table, staged)
        except BaseException:
            # the staging write died after the dedup window recorded
            # this batch: only a full part restage is safe
            stage.state.mark_failed()
            raise

    def _fence_epoch(self, slug: str):
        from transferia_tpu.providers.staging import COMMITS_TABLE

        v = self._client(0).scalar(
            f"SELECT max(`epoch`) FROM `{COMMITS_TABLE}` "
            f"WHERE `part_key` = '{slug}'")
        return int(v) if v is not None else None

    @staticmethod
    def _fence_row(slug: str, epoch: int) -> bytes:
        import struct

        raw = slug.encode()
        out = b""
        n = len(raw)
        while True:
            b7 = n & 0x7F
            n >>= 7
            if n:
                out += bytes([b7 | 0x80])
            else:
                out += bytes([b7])
                break
        return out + raw + struct.pack("<q", epoch)

    def publish_part(self, key: str, epoch: int) -> int:
        from transferia_tpu.abstract.errors import StaleEpochPublishError
        from transferia_tpu.chaos.failpoints import failpoint
        from transferia_tpu.providers.staging import (
            COMMITS_TABLE,
            META_COLUMN,
            publish_guard,
        )
        from transferia_tpu.stats import trace

        stage = self._stage
        if stage is None or stage.key != key:
            raise RuntimeError(f"ch sink: no open stage for {key!r}")
        with publish_guard(key, epoch):
            prev = self._fence_epoch(stage.slug)
            if prev is not None and epoch < prev:
                raise StaleEpochPublishError(key, epoch, prev)
            trace.instant("ch_publish_partition", part=key, epoch=epoch,
                          rows=stage.state.rows)
            failpoint("sink.ch.publish")
            client = self._client(0)
            if stage.schema is not None:
                final = ch_table_name(stage.tid)
                client.execute(ddl_for_schema(
                    stage.tid, stage.schema, self.params.engine,
                    extra_cols=[(META_COLUMN, "String")],
                    partition_by=META_COLUMN))
                # the atomic flip: this part's partition of the final
                # table becomes exactly the staged rows
                client.execute(
                    f"ALTER TABLE `{final}` REPLACE PARTITION ID "
                    f"'{stage.slug}' FROM `{stage.table}`")
            # persist the fence AFTER visibility: a crash in between
            # republishes idempotently (REPLACE swaps the same rows in)
            client.insert_rowbinary(
                COMMITS_TABLE, ["part_key", "epoch"],
                self._fence_row(stage.slug, epoch))
            client.execute(f"DROP TABLE IF EXISTS `{stage.table}`")
            self.last_dedup_dropped = stage.state.dedup_dropped
            rows = stage.state.rows
        self._stage = None
        return rows

    def abort_part(self, key: str) -> None:
        stage = self._stage
        if stage is None or stage.key != key:
            return
        self._stage = None
        try:
            self._client(0).execute(
                f"DROP TABLE IF EXISTS `{stage.table}`")
        except CHError as e:
            logger.warning("ch staged abort of %s: %s", key, e)

    def note_push_retry(self) -> None:
        if self._stage is not None:
            self._stage.state.note_push_retry()


class CHStorage(Storage, SampleableStorage):
    """Snapshot source over SELECT (storage + storage_sharding.go)."""

    def __init__(self, params: CHSourceParams):
        self.params = params
        self.client = CHClient(
            host=params.host, port=params.port, database=params.database,
            user=params.user, password=params.password,
            secure=params.secure,
        )
        self._name_cache: dict[TableID, str] = {}

    def close(self) -> None:
        self.client.close()

    def table_list(self, include=None):
        from transferia_tpu.providers.staging import is_meta_name

        rows = self.client.query_json(
            f"SELECT name, total_rows FROM system.tables "
            f"WHERE database = '{self.params.database}'"
        )
        out = {}
        for r in rows:
            if is_meta_name(r["name"]):
                continue  # staging/fence tables are not user data
            tid = TableID(self.params.database, r["name"])
            if include and not any(tid.include_matches(p) for p in include):
                continue
            out[tid] = TableInfo(eta_rows=int(r.get("total_rows") or 0))
        return out

    def _resolve_name(self, table: TableID) -> str:
        """Resolve a foreign TableID to this database's table name.

        The CH sink flattens "ns"."t" into `ns__t` (ch_table_name); a
        checksum against a CH target must find rows under that name when
        the bare name is absent."""
        name = table.name
        if not table.namespace or table.namespace == self.params.database:
            return name
        cached = self._name_cache.get(table)
        if cached is not None:
            return cached
        flat = f"{table.namespace}__{table.name}"
        n = self.client.scalar(
            "SELECT count() FROM system.tables "
            f"WHERE database = '{self.params.database}' "
            f"AND name = '{flat}'"
        )
        resolved = flat if int(n or 0) else name
        self._name_cache[table] = resolved
        return resolved

    def table_schema(self, table: TableID) -> TableSchema:
        from transferia_tpu.typesystem.rules import map_source_type

        rows = self.client.query_json(
            f"SELECT name, type, is_in_primary_key FROM system.columns "
            f"WHERE database = '{self.params.database}' "
            f"AND table = '{self._resolve_name(table)}'"
        )
        from transferia_tpu.providers.staging import is_meta_name

        cols = []
        for r in rows:
            if is_meta_name(r["name"]):
                continue  # hidden staged-commit part column
            ch_type = r["type"]
            nullable = ch_type.startswith("Nullable(")
            base = ch_type[9:-1] if nullable else ch_type
            cols.append(ColSchema(
                name=r["name"],
                data_type=map_source_type("ch", base.lower()),
                primary_key=bool(int(r.get("is_in_primary_key") or 0)),
                required=not nullable,
                original_type=f"ch:{ch_type}",
            ))
        return TableSchema(cols)

    def exact_table_rows_count(self, table: TableID) -> int:
        return int(self.client.scalar(
            f"SELECT count() FROM `{self._resolve_name(table)}`"
        ) or 0)

    def estimate_table_rows_count(self, table: TableID) -> int:
        return self.exact_table_rows_count(table)

    @staticmethod
    def _select_expr(c: ColSchema) -> str:
        """Types this decoder can't take off the wire (Decimal, UUID, Array,
        anything mapped to ANY/DECIMAL) are cast server-side to String."""
        if c.data_type in (CanonicalType.ANY, CanonicalType.DECIMAL):
            return f"toString(`{c.name}`) AS `{c.name}`"
        return f"`{c.name}`"

    def load_table(self, table: TableDescription, pusher: Pusher) -> None:
        where = f" WHERE {table.filter}" if table.filter else ""
        self._load_select(table.id, where_order_limit=where, pusher=pusher)

    def _load_select(self, tid: TableID, where_order_limit: str,
                     pusher: Pusher) -> None:
        from transferia_tpu.providers.clickhouse.rowbinary import (
            decode_rowbinary_stream,
        )

        schema = self.table_schema(tid)
        nullable = {c.name: not c.required for c in schema}
        cols = ", ".join(self._select_expr(c) for c in schema)
        read_fn, close_fn = self.client.execute_stream(
            f"SELECT {cols} FROM `{self._resolve_name(tid)}`"
            f"{where_order_limit} FORMAT RowBinary"
        )
        try:
            for batch in decode_rowbinary_stream(
                    read_fn, schema, nullable,
                    batch_rows=self.params.batch_rows):
                out = ColumnBatch(tid, schema, batch.columns)
                out.read_bytes = out.nbytes()
                pusher(out)
        finally:
            close_fn()

    # -- checksum sampling (clickhouse/storage_sampleable.go) ---------------
    RANDOM_SAMPLE_LIMIT = 2000
    TOP_BOTTOM_LIMIT = 1000

    def table_size_in_bytes(self, table: TableID) -> int:
        v = self.client.scalar(
            "SELECT sum(bytes_on_disk) FROM system.parts "
            f"WHERE database = '{self.params.database}' "
            f"AND table = '{self._resolve_name(table)}' AND active"
        )
        try:
            return int(v or 0)
        except (TypeError, ValueError):
            return 0

    def _order_cols(self, tid: TableID) -> list[str]:
        schema = self.table_schema(tid)
        return [c.name for c in schema.key_columns()]

    def load_random_sample(self, table: TableDescription,
                           pusher: Pusher) -> None:
        order = self._order_cols(table.id)
        by = " ORDER BY " + ", ".join(f"`{c}`" for c in order) if order \
            else ""
        # rand() is uniform over UInt32; 0.05 of the range
        cutoff = int(0.05 * 0xFFFFFFFF)
        self._load_select(
            table.id,
            f" WHERE rand() <= {cutoff}{by} "
            f"LIMIT {self.RANDOM_SAMPLE_LIMIT}",
            pusher,
        )

    def load_top_bottom_sample(self, table: TableDescription,
                               pusher: Pusher) -> None:
        order = self._order_cols(table.id)
        if not order:
            raise CHError(f"no sorting key on {table.id.name}; "
                          "cannot take top/bottom sample")
        asc = ", ".join(f"`{c}`" for c in order)
        desc = ", ".join(f"`{c}` DESC" for c in order)
        n = self.TOP_BOTTOM_LIMIT
        self._load_select(
            table.id, f" ORDER BY {asc} LIMIT {n}", pusher)
        self._load_select(
            table.id, f" ORDER BY {desc} LIMIT {n}", pusher)

    @staticmethod
    def _ch_literal(v) -> str:
        if v is None:
            return "NULL"
        if isinstance(v, bool):
            return "1" if v else "0"
        if isinstance(v, (int, float)):
            return str(v)
        if isinstance(v, bytes):
            v = v.decode("utf-8", "replace")
        s = str(v).replace("\\", "\\\\").replace("'", "\\'")
        return f"'{s}'"

    def load_sample_by_set(self, table: TableDescription, key_set,
                           pusher: Pusher) -> None:
        conds = [
            "(" + " AND ".join(
                f"`{name}` = {self._ch_literal(val)}"
                for name, val in key.items()) + ")"
            for key in key_set
        ]
        where = " OR ".join(conds) if conds else "0"
        self._load_select(table.id, f" WHERE {where}", pusher)

    def ping(self) -> None:
        self.client.ping()


@register_provider
class ClickHouseProvider(Provider):
    NAME = "ch"

    def storage(self):
        if isinstance(self.transfer.src, CHSourceParams):
            return CHStorage(self.transfer.src)
        return None

    def destination_storage(self):
        dst = self.transfer.dst
        if isinstance(dst, CHTargetParams):
            return CHStorage(CHSourceParams(
                host=dst.host, port=dst.port, database=dst.database,
                user=dst.user, password=dst.password, secure=dst.secure,
            ))
        return None

    def event_target(self):
        if isinstance(self.transfer.dst, CHTargetParams):
            from transferia_tpu.providers.clickhouse.a2 import CHEventTarget

            return CHEventTarget(self.transfer.dst)
        return None

    def sinker(self):
        if isinstance(self.transfer.dst, CHTargetParams):
            return CHSinker(self.transfer.dst)
        return None

    def cleanup(self, tables: list) -> None:
        params = self.transfer.dst
        sinker = CHSinker(params)
        kind = Kind.DROP if params.cleanup_policy == CleanupPolicy.DROP \
            else Kind.TRUNCATE
        for td in tables or []:
            tid = td.id if hasattr(td, "id") else td
            sinker._apply_cleanup(tid, kind)

    def test(self) -> TestResult:
        result = TestResult(ok=True)
        params = self.transfer.dst or self.transfer.src
        client = CHClient(host=params.host, port=params.port,
                          database=params.database, user=params.user,
                          password=params.password, secure=params.secure)
        try:
            client.ping()
            result.add("ping")
        except Exception as e:
            result.add("ping", e)
        return result
