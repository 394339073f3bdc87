"""MySQL storage/sink (providers/mysql/storage.go, schema discovery,
typesystem.go rules; sharded reads via key-range splitting)."""

from __future__ import annotations

import datetime
import logging
import threading
from dataclasses import dataclass, field
from typing import Optional

from transferia_tpu.abstract.interfaces import (
    SampleableStorage,
    Batch,
    IncrementalStorage,
    PositionalStorage,
    Pusher,
    ShardingStorage,
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
from transferia_tpu.providers.mysql import textrows
from transferia_tpu.providers.mysql.wire import MySQLConnection, MySQLError
from transferia_tpu.providers.registry import (
    Provider,
    TestResult,
    register_provider,
)
from transferia_tpu.stats import trace
from transferia_tpu.typesystem.rules import (
    register_source_rules,
    register_target_rules,
)

logger = logging.getLogger(__name__)

register_source_rules("mysql", {
    "tinyint": CanonicalType.INT8, "smallint": CanonicalType.INT16,
    "mediumint": CanonicalType.INT32, "int": CanonicalType.INT32,
    "bigint": CanonicalType.INT64,
    "tinyint unsigned": CanonicalType.UINT8,
    "smallint unsigned": CanonicalType.UINT16,
    "int unsigned": CanonicalType.UINT32,
    "bigint unsigned": CanonicalType.UINT64,
    "float": CanonicalType.FLOAT, "double": CanonicalType.DOUBLE,
    "decimal": CanonicalType.DECIMAL,
    "bit": CanonicalType.UINT64, "bool": CanonicalType.BOOLEAN,
    "char": CanonicalType.UTF8, "varchar": CanonicalType.UTF8,
    "text": CanonicalType.UTF8, "tinytext": CanonicalType.UTF8,
    "mediumtext": CanonicalType.UTF8, "longtext": CanonicalType.UTF8,
    "binary": CanonicalType.STRING, "varbinary": CanonicalType.STRING,
    "blob": CanonicalType.STRING, "tinyblob": CanonicalType.STRING,
    "mediumblob": CanonicalType.STRING, "longblob": CanonicalType.STRING,
    "date": CanonicalType.DATE, "datetime": CanonicalType.TIMESTAMP,
    "timestamp": CanonicalType.TIMESTAMP, "time": CanonicalType.UTF8,
    "year": CanonicalType.INT32, "json": CanonicalType.ANY,
    "enum": CanonicalType.UTF8, "set": CanonicalType.UTF8,
    "*": CanonicalType.ANY,
})

register_target_rules("mysql", {
    CanonicalType.INT8: "tinyint", CanonicalType.INT16: "smallint",
    CanonicalType.INT32: "int", CanonicalType.INT64: "bigint",
    CanonicalType.UINT8: "tinyint unsigned",
    CanonicalType.UINT16: "smallint unsigned",
    CanonicalType.UINT32: "int unsigned",
    CanonicalType.UINT64: "bigint unsigned",
    CanonicalType.FLOAT: "float", CanonicalType.DOUBLE: "double",
    CanonicalType.BOOLEAN: "tinyint(1)", CanonicalType.STRING: "longblob",
    CanonicalType.UTF8: "longtext", CanonicalType.DATE: "date",
    CanonicalType.DATETIME: "datetime", CanonicalType.TIMESTAMP: "datetime(6)",
    CanonicalType.INTERVAL: "bigint", CanonicalType.DECIMAL: "decimal(65,30)",
    CanonicalType.ANY: "json",
})


@register_endpoint
@dataclass
class MySQLSourceParams(EndpointParams):
    PROVIDER = "mysql"
    IS_SOURCE = True

    host: str = "localhost"
    port: int = 3306
    database: str = ""
    user: str = "root"
    password: str = ""
    batch_rows: int = 65_536


@register_endpoint
@dataclass
class MySQLTargetParams(EndpointParams):
    PROVIDER = "mysql"
    IS_TARGET = True

    host: str = "localhost"
    port: int = 3306
    database: str = ""
    user: str = "root"
    password: str = ""


def _conn(params) -> MySQLConnection:
    return MySQLConnection(
        host=params.host, port=params.port, database=params.database,
        user=params.user, password=params.password,
    ).connect()


def _sql_literal(v) -> str:
    """Escaped SQL literal (shared by cursor filters and the sink)."""
    if v is None:
        return "NULL"
    if isinstance(v, bool):
        return "1" if v else "0"
    if isinstance(v, (int, float)):
        return str(v)
    if isinstance(v, bytes):
        return "x'" + v.hex() + "'"
    s = str(v).replace("\\", "\\\\").replace("'", "''")
    return f"'{s}'"


def _coerce(cs: ColSchema, v: Optional[str]):
    if v is None:
        return None
    t = cs.data_type
    if t.is_integer:
        try:
            return int(v)
        except ValueError:
            return v
    if t.is_float:
        try:
            return float(v)
        except ValueError:
            return v
    if t == CanonicalType.BOOLEAN:
        return v not in ("0", "", "false")
    if t == CanonicalType.STRING:
        return v.encode("utf-8", "surrogateescape")
    if t in (CanonicalType.TIMESTAMP, CanonicalType.DATE):
        # DATETIME/TIMESTAMP text -> microseconds, DATE -> days since
        # 1970-01-01; a zero date (0000-00-00) is no instant: NULL
        try:
            at = datetime.datetime.fromisoformat(v)
        except ValueError:
            return None
        since = at - datetime.datetime(1970, 1, 1)
        return since.days if t == CanonicalType.DATE \
            else since // datetime.timedelta(microseconds=1)
    return v


# The least a key range has to carry to be a part of its own
# (MySQLStorage.shard_table).  A part's fixed cost - its connection and
# handshake, its staged commit - is 0.09-0.17 s, and a part thread moves
# 0.35-0.5 MB of result-set text a second through decode, serializer and
# produce (PERF.md section 6, PR 33): from here on the fixed cost is
# under a tenth of the part.
_MIN_PART_BYTES = 1 << 20


class MySQLStorage(Storage, PositionalStorage, IncrementalStorage,
                   SampleableStorage, ShardingStorage):
    def __init__(self, params: MySQLSourceParams, parts: int = 1):
        """parts: how many part threads the transfer runs (a large table
        is cut into that many key ranges, `shard_table`)."""
        self.params = params
        self.parts = max(1, int(parts))
        self._c: Optional[MySQLConnection] = None
        # part threads share one storage: a table's schema is read once
        self._load_lock = threading.Lock()
        self._load_schemas: dict[TableID, TableSchema] = {}

    @property
    def conn(self) -> MySQLConnection:
        if self._c is None:
            self._c = _conn(self.params)
        return self._c

    def close(self) -> None:
        if self._c is not None:
            self._c.close()
            self._c = None

    def ping(self) -> None:
        self.conn.ping()

    def table_list(self, include=None):
        rows = self.conn.query(
            "SELECT TABLE_NAME AS name, TABLE_ROWS AS eta "
            "FROM information_schema.TABLES "
            f"WHERE TABLE_SCHEMA = '{self.params.database}' "
            "AND TABLE_TYPE = 'BASE TABLE'"
        )
        out = {}
        for r in rows:
            tid = TableID(self.params.database, r["name"])
            if include and not any(tid.include_matches(p) for p in include):
                continue
            out[tid] = TableInfo(eta_rows=int(r["eta"] or 0))
        return out

    def table_schema(self, table: TableID) -> TableSchema:
        from transferia_tpu.typesystem.rules import map_source_type

        rows = self.conn.query(
            "SELECT COLUMN_NAME AS name, DATA_TYPE AS typ, "
            "COLUMN_TYPE AS full_typ, IS_NULLABLE AS nullable, "
            "COLUMN_KEY AS ckey "
            "FROM information_schema.COLUMNS "
            f"WHERE TABLE_SCHEMA = '{table.namespace}' "
            f"AND TABLE_NAME = '{table.name}' ORDER BY ORDINAL_POSITION"
        )
        cols = []
        for r in rows:
            typ = r["typ"].lower()
            if "unsigned" in (r["full_typ"] or "").lower():
                typ = f"{typ} unsigned"
            cols.append(ColSchema(
                name=r["name"],
                data_type=map_source_type("mysql", typ),
                primary_key=r["ckey"] == "PRI",
                required=r["nullable"] == "NO",
                original_type=f"mysql:{r['full_typ']}",
            ))
        return TableSchema(cols)

    def exact_table_rows_count(self, table: TableID) -> int:
        return int(self.conn.scalar(
            f"SELECT COUNT(*) FROM `{table.namespace}`.`{table.name}`"
        ) or 0)

    def position(self) -> dict:
        """Binlog/gtid position (MysqlGtidState parity).

        MySQL 8.4 removed SHOW MASTER STATUS in favor of SHOW BINARY LOG
        STATUS; try both, and never silently checkpoint an empty position.
        """
        last_err = None
        for stmt in ("SHOW MASTER STATUS", "SHOW BINARY LOG STATUS"):
            try:
                rows = self.conn.query(stmt)
            except MySQLError as e:
                last_err = e
                continue
            if rows:
                r = rows[0]
                return {
                    "binlog_file": r.get("File"),
                    "binlog_pos": r.get("Position"),
                    "gtid_set": r.get("Executed_Gtid_Set", ""),
                }
        logger.warning(
            "could not read binlog position (binary logging off, "
            "insufficient privileges, or unsupported server): %s", last_err,
        )
        return {}

    # -- intra-table sharding: ranges of the primary key -------------------
    def shard_table(self, table: TableDescription) -> list[TableDescription]:
        """A table in as many ranges of its primary key as there are
        part threads, or as many as leave each `_MIN_PART_BYTES` of the
        table's size if that is fewer.  The ranges
        are cut on the first key column that takes more than one value
        (MIN/MAX under equality on the columns before it: one warehouse
        of a (warehouse, district, id) key is cut by district), the first
        open below and the last open above, so rows written since are in
        one of them.  No integer key column to cut on, a filter already
        in place or a small table: one part."""
        if self.parts <= 1 or table.filter:
            return [table]
        want = min(self.parts,
                   self.table_size_in_bytes(table.id) // _MIN_PART_BYTES)
        if want <= 1:
            return [table]
        ref = f"`{table.id.namespace}`.`{table.id.name}`"
        fixed: list[str] = []
        for key in self._primary_key(table.id):
            if not key.data_type.is_integer:
                break
            where = f" WHERE {' AND '.join(fixed)}" if fixed else ""
            row = self.conn.query(
                f"SELECT MIN(`{key.name}`) AS lo, MAX(`{key.name}`) AS hi "
                f"FROM {ref}{where}")
            if not row or row[0]["lo"] is None:
                break
            lo, hi = int(row[0]["lo"]), int(row[0]["hi"])
            if lo == hi:
                fixed.append(f"`{key.name}` = {lo}")
                continue
            n = min(want, hi - lo + 1)
            cuts = [lo + (hi - lo + 1) * i // n for i in range(1, n)]
            out = []
            for i in range(n):
                conds = list(fixed)
                if i > 0:
                    conds.append(f"`{key.name}` >= {cuts[i - 1]}")
                if i < n - 1:
                    conds.append(f"`{key.name}` < {cuts[i]}")
                out.append(TableDescription(
                    id=table.id, filter=" AND ".join(conds),
                    eta_rows=table.eta_rows // n))
            return out
        return [table]

    def _primary_key(self, table: TableID) -> list[ColSchema]:
        """The primary key's columns in the index's order (the order a
        range on a leading column can use), which need not be the
        table's column order; that order where the server does not say."""
        schema = self.table_schema(table)
        try:
            rows = self.conn.query(
                "SELECT COLUMN_NAME AS name "
                "FROM information_schema.STATISTICS "
                f"WHERE TABLE_SCHEMA = '{table.namespace}' "
                f"AND TABLE_NAME = '{table.name}' "
                "AND INDEX_NAME = 'PRIMARY' ORDER BY SEQ_IN_INDEX")
        except MySQLError:
            rows = []
        named = [schema.find(r["name"]) for r in rows]
        if named and all(c is not None and c.primary_key for c in named):
            return named
        return schema.key_columns()

    # -- snapshot load -------------------------------------------------------
    def load_table(self, table: TableDescription, pusher: Pusher) -> None:
        """One part as ONE streamed result set, with neither ORDER BY nor
        OFFSET: a table with a composite key or none reads as a table
        with one key column does, and the server scans what it serves
        once.  Rows leave as ColumnBatches of `batch_rows`; what is held
        is one flush of the socket's bytes and the rows short of a
        batch."""
        with self._load_lock:
            schema = self._load_schemas.get(table.id)
            if schema is None:
                schema = self.table_schema(table.id)
                self._load_schemas[table.id] = schema
        cols = ", ".join(f"`{c.name}`" for c in schema)
        where = f" WHERE {table.filter}" if table.filter else ""
        columnar = all(c.data_type in textrows.COLUMNAR_TYPES
                       for c in schema)
        per = self.params.batch_rows
        trace.TELEMETRY.record_mysql_part()
        # dedicated connection: parts stream in parallel threads
        conn = _conn(self.params)
        try:
            blocks = conn.query_stream(
                f"SELECT {cols} FROM `{table.id.namespace}`."
                f"`{table.id.name}`{where}")
            carry = None      # decoded rows short of a batch (arrow)
            more = True
            while more:
                # one span a flush: the wait for and the read of row
                # packets from the socket, their framing walked
                flush: list[tuple[bytes, list[int]]] = []
                rows = nbytes = 0
                with trace.span("mysql_read") as sp:
                    for data, starts in blocks:
                        flush.append((data, starts))
                        rows += len(starts)
                        nbytes += len(data)
                        if rows >= per or nbytes >= 32 << 20:
                            break
                    else:
                        more = False
                    if sp:
                        sp.add(table=table.id.name, rows=rows,
                               bytes=nbytes)
                if not columnar:
                    self._push_cells(flush, table.id, schema, pusher)
                    continue
                carry = self._flush_rows(flush, table.id, schema, pusher,
                                         carry, last=not more)
        finally:
            conn.close()

    def _push_cells(self, flush, tid: TableID, schema: TableSchema,
                    pusher: Pusher) -> None:
        """A flush cell by cell (`_coerce`), a batch a block: for what
        `textrows.decode` does not take."""
        for data, starts in flush:
            self._push_rows(
                textrows.rows_as_dicts(data, starts, schema.names()),
                schema, tid, pusher)

    def _flush_rows(self, flush, tid: TableID, schema: TableSchema,
                    pusher: Pusher, carry, last: bool):
        """Row packets -> arrow, a column at a time -> ColumnBatches of
        `batch_rows`; returns the rows short of a batch (an arrow table)
        for the next flush to start with, None after the last."""
        import pyarrow as pa

        batches = []
        with trace.span("source_decode", format="mysql_text") as sp:
            try:
                new = [textrows.decode(data, starts, schema)
                       for data, starts in flush]
            except pa.ArrowInvalid:
                # text arrow's parsers do not read as the column's type
                # (a zero date): this flush goes cell by cell
                if carry is not None and carry.num_rows:
                    pusher(ColumnBatch.from_arrow(
                        carry.combine_chunks().to_batches()[0], tid,
                        schema))
                self._push_cells(flush, tid, schema, pusher)
                return None
            tbl = carry
            if new:
                fresh = pa.Table.from_batches(new)
                tbl = fresh if carry is None \
                    else pa.concat_tables([carry, fresh])
            n = tbl.num_rows if tbl is not None else 0
            per = self.params.batch_rows
            whole = n if last else n - n % per
            for lo in range(0, whole, per):
                rb = tbl.slice(lo, min(per, whole - lo)) \
                    .combine_chunks().to_batches()[0]
                batch = ColumnBatch.from_arrow(rb, tid, schema)
                batch.read_bytes = rb.nbytes
                batches.append(batch)
            if sp:
                sp.add(rows=whole,
                       bytes=sum(len(data) for data, _ in flush))
        for batch in batches:
            pusher(batch)
        return tbl.slice(whole) if whole < n else None

    @staticmethod
    def _push_rows(rows, schema, tid, pusher: Pusher) -> None:
        data = {
            c.name: [_coerce(c, r.get(c.name)) for r in rows]
            for c in schema
        }
        pusher(ColumnBatch.from_pydict(tid, schema, data))

    # -- checksum sampling (mysql/sampleable_storage.go) --------------------
    RANDOM_SAMPLE_LIMIT = 2000
    TOP_BOTTOM_LIMIT = 1000

    def table_size_in_bytes(self, table: TableID) -> int:
        v = self.conn.scalar(
            "SELECT DATA_LENGTH + INDEX_LENGTH "
            "FROM information_schema.TABLES "
            f"WHERE TABLE_SCHEMA = '{table.namespace}' "
            f"AND TABLE_NAME = '{table.name}'"
        )
        return int(v or 0)

    def _sample_query(self, tid: TableID, schema: TableSchema, sql: str,
                      pusher: Pusher) -> None:
        rows = self.conn.query(sql)
        if rows:
            self._push_rows(rows, schema, tid, pusher)

    def _sample_parts(self, tid: TableID):
        schema = self.table_schema(tid)
        cols = ", ".join(f"`{c.name}`" for c in schema)
        order = ", ".join(f"`{c.name}`" for c in schema.key_columns())
        ref = f"`{tid.namespace}`.`{tid.name}`"
        return schema, cols, order, ref

    def load_random_sample(self, table: TableDescription,
                           pusher: Pusher) -> None:
        schema, cols, order, ref = self._sample_parts(table.id)
        by = f" ORDER BY {order}" if order else ""
        self._sample_query(
            table.id, schema,
            f"SELECT {cols} FROM {ref} WHERE RAND() <= 0.05{by} "
            f"LIMIT {self.RANDOM_SAMPLE_LIMIT}",
            pusher,
        )

    def load_top_bottom_sample(self, table: TableDescription,
                               pusher: Pusher) -> None:
        schema, cols, order, ref = self._sample_parts(table.id)
        if not order:
            raise MySQLError(f"no primary key on {ref}; "
                             "cannot take top/bottom sample")
        desc = ", ".join(f"{c} DESC" for c in order.split(", "))
        n = self.TOP_BOTTOM_LIMIT
        self._sample_query(
            table.id, schema,
            f"(SELECT {cols} FROM {ref} ORDER BY {order} LIMIT {n}) "
            f"UNION ALL "
            f"(SELECT {cols} FROM {ref} ORDER BY {desc} LIMIT {n})",
            pusher,
        )

    def load_sample_by_set(self, table: TableDescription, key_set,
                           pusher: Pusher) -> None:
        schema, cols, _, ref = self._sample_parts(table.id)
        conds = [
            "(" + " AND ".join(
                f"`{name}` = {_sql_literal(val)}"
                for name, val in key.items()) + ")"
            for key in key_set
        ]
        where = " OR ".join(conds) if conds else "FALSE"
        self._sample_query(
            table.id, schema,
            f"SELECT {cols} FROM {ref} WHERE {where}", pusher)

    # -- IncrementalStorage -------------------------------------------------
    def get_increment_state(self, tables, state):
        out = []
        for t in tables:
            cursor = state.get(str(t.table), t.initial_state or None)
            if cursor in (None, ""):
                out.append(TableDescription(id=t.table))
            else:
                out.append(TableDescription(
                    id=t.table,
                    filter=f"`{t.cursor_field}` > {_sql_literal(cursor)}",
                ))
        return out

    def next_increment_state(self, tables):
        out = {}
        for t in tables:
            v = self.conn.scalar(
                f"SELECT MAX(`{t.cursor_field}`) FROM "
                f"`{t.table.namespace}`.`{t.table.name}`"
            )
            if v is not None:
                out[str(t.table)] = v
        return out


class MySQLSinker(Sinker):
    def __init__(self, params: MySQLTargetParams):
        self.params = params
        self._c: Optional[MySQLConnection] = None
        self._created: set[TableID] = set()

    @property
    def conn(self) -> MySQLConnection:
        if self._c is None:
            self._c = _conn(self.params)
        return self._c

    def close(self) -> None:
        if self._c is not None:
            self._c.close()
            self._c = None

    _literal = staticmethod(_sql_literal)

    def _table_ref(self, tid: TableID) -> str:
        ns = tid.namespace or self.params.database
        return f"`{ns}`.`{tid.name}`"

    def _ensure_table(self, tid: TableID, schema: TableSchema) -> None:
        if tid in self._created:
            return
        from transferia_tpu.typesystem.rules import map_target_type

        cols = []
        for c in schema:
            typ = map_target_type("mysql", c.data_type)
            # TEXT/BLOB key columns need a length-limited index type
            if c.primary_key and typ in ("longtext", "longblob"):
                typ = "varchar(255)" if typ == "longtext" \
                    else "varbinary(255)"
            nn = " NOT NULL" if (c.required or c.primary_key) else ""
            cols.append(f"`{c.name}` {typ}{nn}")
        keys = ", ".join(f"`{c.name}`" for c in schema.key_columns())
        pk = f", PRIMARY KEY ({keys})" if keys else ""
        self.conn.query(
            f"CREATE TABLE IF NOT EXISTS {self._table_ref(tid)} "
            f"({', '.join(cols)}{pk})"
        )
        self._created.add(tid)

    def push(self, batch: Batch) -> None:
        if not is_columnar(batch):
            rows = [it for it in batch if it.is_row_event()]
            if not rows:
                return
            batch = ColumnBatch.from_rows(rows)
        self._ensure_table(batch.table_id, batch.schema)
        if batch.kinds is None:
            self._insert(batch, upsert=batch.schema.has_primary_key())
        else:
            for it in batch.to_rows():
                self._apply_row(it)

    def _insert(self, batch: ColumnBatch, upsert: bool) -> None:
        names = list(batch.columns)
        cols = ", ".join(f"`{n}`" for n in names)
        data = batch.to_pydict()
        # multi-row VALUES in chunks to bound statement size
        chunk = 500
        for start in range(0, batch.n_rows, chunk):
            rows_sql = []
            for i in range(start, min(batch.n_rows, start + chunk)):
                rows_sql.append(
                    "(" + ", ".join(
                        self._literal(data[n][i]) for n in names
                    ) + ")"
                )
            sql = f"INSERT INTO {self._table_ref(batch.table_id)} " \
                  f"({cols}) VALUES {', '.join(rows_sql)}"
            if upsert:
                keys = {c.name for c in batch.schema.key_columns()}
                sets = ", ".join(
                    f"`{n}` = VALUES(`{n}`)" for n in names
                    if n not in keys
                )
                if sets:
                    sql += f" ON DUPLICATE KEY UPDATE {sets}"
            self.conn.query(sql)

    def _apply_row(self, it) -> None:
        ref = self._table_ref(it.table_id)
        if it.kind == Kind.INSERT:
            cols = ", ".join(f"`{n}`" for n in it.column_names)
            vals = ", ".join(self._literal(v) for v in it.column_values)
            self.conn.query(
                f"REPLACE INTO {ref} ({cols}) VALUES ({vals})"
            )
        elif it.kind == Kind.UPDATE:
            sets = ", ".join(
                f"`{n}` = {self._literal(v)}"
                for n, v in zip(it.column_names, it.column_values)
            )
            self.conn.query(
                f"UPDATE {ref} SET {sets} WHERE {self._key_where(it)}"
            )
        elif it.kind == Kind.DELETE:
            self.conn.query(
                f"DELETE FROM {ref} WHERE {self._key_where(it)}"
            )

    def _key_where(self, it) -> str:
        names = [c.name for c in it.table_schema.key_columns()]
        return " AND ".join(
            f"`{n}` = {self._literal(v)}"
            for n, v in zip(names, it.effective_key())
        )


@register_provider
class MySQLProvider(Provider):
    NAME = "mysql"

    def storage(self):
        if isinstance(self.transfer.src, MySQLSourceParams):
            return MySQLStorage(
                self.transfer.src,
                parts=self.transfer.runtime.sharding.process_count)
        return None

    def destination_storage(self):
        dst = self.transfer.dst
        if isinstance(dst, MySQLTargetParams):
            return MySQLStorage(MySQLSourceParams(
                host=dst.host, port=dst.port, database=dst.database,
                user=dst.user, password=dst.password,
            ))
        return None

    def source(self):
        """Binlog ROW replication (canal.go)."""
        if isinstance(self.transfer.src, MySQLSourceParams):
            from transferia_tpu.providers.mysql.binlog import (
                MySQLBinlogSource,
            )

            return MySQLBinlogSource(
                self.transfer.src, self.transfer.id, self.coordinator
            )
        return None

    def sinker(self):
        if isinstance(self.transfer.dst, MySQLTargetParams):
            return MySQLSinker(self.transfer.dst)
        return None

    def cleanup(self, tables: list) -> None:
        params = self.transfer.dst
        conn = _conn(params)
        try:
            stmt = "DROP TABLE IF EXISTS" \
                if params.cleanup_policy == CleanupPolicy.DROP \
                else "TRUNCATE TABLE"
            for td in tables or []:
                tid = td.id if hasattr(td, "id") else td
                ns = tid.namespace or params.database
                try:
                    conn.query(f"{stmt} `{ns}`.`{tid.name}`")
                except MySQLError as e:
                    if e.errno == 1146:  # table doesn't exist
                        continue
                    raise
        finally:
            conn.close()

    def test(self) -> TestResult:
        result = TestResult(ok=True)
        params = self.transfer.src if isinstance(
            self.transfer.src, MySQLSourceParams) else self.transfer.dst
        try:
            conn = _conn(params)
            conn.ping()
            conn.close()
            result.add("connect")
        except Exception as e:
            result.add("connect", e)
        return result
