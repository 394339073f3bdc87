"""Greenplum provider (reference: pkg/providers/greenplum/).

Greenplum speaks the PostgreSQL protocol; the provider specializes the PG
storage two ways:

  - segment-parallel reads THROUGH THE MASTER: `gp_segment_id`
    partitions a table across segments, one part per segment
    (load_snapshot.go:23) — correct everywhere, but every byte rides
    the master connection
  - the gpfdist SEGMENT-DIRECT path (gpfdist_storage.go /
    gpfdist_sink.go): the worker runs an in-process gpfdist endpoint
    (providers/gpfdist.py) and the master only executes CREATE EXTERNAL
    TABLE + INSERT...SELECT control statements; the DATA flows straight
    between the segments and the worker over HTTP, which is what makes
    Greenplum bulk load/unload fast

Unload: CREATE WRITABLE EXTERNAL TABLE (LIKE src) LOCATION
('gpfdist://worker/slot'); INSERT INTO ext SELECT * FROM src — segments
POST their rows as CSV to the worker, which decodes through the same CSV
-> ColumnBatch path as PG COPY.  Load: CREATE READABLE EXTERNAL TABLE
(LIKE target); INSERT INTO target SELECT * FROM ext — segments GET CSV
chunks from the worker.  Filtered parts (predicate pushdown) keep the
master path: gpfdist transfers are whole-table.

Real-service behaviors intentionally NOT covered (FakeGP plays the
segment side of the protocol, so e2e cannot prove these): the gpfdist
TLS variant (gpfdists://), segment-host liveness monitoring during a
long transfer (reference liveness_monitor.go restarts stalled
segments), and multi-NIC worker addressing — gpfdist_host is a single
address all segments must reach.
"""

from __future__ import annotations

import logging
import threading
import uuid
from dataclasses import dataclass

from transferia_tpu.abstract.interfaces import Pusher, is_columnar
from transferia_tpu.abstract.table import TableDescription
from transferia_tpu.columnar.batch import ColumnBatch
from transferia_tpu.models.endpoint import register_endpoint
from transferia_tpu.providers.gpfdist import GpfdistServer
from transferia_tpu.providers.postgres.provider import (
    PGSinker,
    PGSourceParams,
    PGStorage,
    PGTargetParams,
    _conn,
)
from transferia_tpu.providers.postgres.wire import PGError
from transferia_tpu.providers.registry import Provider, register_provider

logger = logging.getLogger(__name__)


@register_endpoint
@dataclass
class GPSourceParams(PGSourceParams):
    PROVIDER = "greenplum"

    segment_parallel: bool = True
    # segment-direct unload through an in-process gpfdist endpoint
    gpfdist: bool = False
    gpfdist_host: str = "127.0.0.1"   # address segments can reach


@register_endpoint
@dataclass
class GPTargetParams(PGTargetParams):
    PROVIDER = "greenplum"

    # segment-direct load through an in-process gpfdist endpoint
    gpfdist: bool = False
    gpfdist_host: str = "127.0.0.1"


class GPStorage(PGStorage):
    def _segment_count(self) -> int:
        try:
            return int(self.conn.scalar(
                "SELECT count(*) FROM gp_segment_configuration "
                "WHERE role = 'p' AND content >= 0"
            ) or 0)
        except PGError:
            return 0  # not actually a Greenplum cluster

    def shard_table(self, table: TableDescription) -> list[TableDescription]:
        if self.params.gpfdist:
            # one gpfdist transfer moves the whole table with the
            # segments as the parallel axis: no part fan-out needed
            return [table]
        if not getattr(self.params, "segment_parallel", True):
            return super().shard_table(table)
        n_segments = self._segment_count()
        if n_segments == 0:
            # plain-PG fallback: ctid split
            return super().shard_table(table)
        if n_segments <= 1:
            return [table]
        return [
            TableDescription(
                id=table.id,
                filter=f"gp_segment_id = {seg}",
                eta_rows=table.eta_rows // n_segments,
            )
            for seg in range(n_segments)
        ]

    # -- gpfdist segment-direct unload (gpfdist_storage.go) ------------------
    def load_table(self, table: TableDescription, pusher: Pusher) -> None:
        if not self.params.gpfdist or table.filter:
            # filtered parts (predicate pushdown) keep the master path
            return super().load_table(table, pusher)
        n_segments = self._segment_count()
        if n_segments == 0:
            return super().load_table(table, pusher)
        schema = self.table_schema(table.id)
        slot = f"u{uuid.uuid4().hex[:12]}"
        server = GpfdistServer(self.params.gpfdist_host).start()
        ext = (f'"{table.id.namespace}".'
               f'"{table.id.name}__trtpu_wext_{slot}"')
        lock = threading.Lock()
        # PER-SEGMENT reframing state: each segment's stream splits at
        # its own arbitrary byte boundaries, and a record boundary is a
        # newline at EVEN quote parity (CSV-quoted fields may embed
        # newlines, so plain rfind-newline reframing is unsound)
        tails: dict[str, bytes] = {}

        def _safe_split(data: bytes) -> int:
            last = -1
            in_quote = False
            for i, b in enumerate(data):
                if b == 0x22:            # '"' (doubled quotes toggle twice)
                    in_quote = not in_quote
                elif b == 0x0A and not in_quote:
                    last = i
            return last

        def on_chunk(seg: str, data: bytes, done: bool) -> None:
            with lock:
                data = tails.pop(seg, b"") + data
                if done:
                    if data:
                        if not data.endswith(b"\n"):
                            data += b"\n"
                        self._flush_csv(data, table.id, schema, pusher)
                    return
                nl = _safe_split(data)
                if nl < 0:
                    tails[seg] = data
                    return
                tails[seg] = data[nl + 1:]
                self._flush_csv(data[:nl + 1], table.id, schema, pusher)

        try:
            server.register_sink(slot, on_chunk, n_segments)
            control = _conn(self.params)
            try:
                control.query(
                    f"CREATE WRITABLE EXTERNAL TABLE {ext} "
                    f"(LIKE {table.id.fqtn()}) "
                    f"LOCATION ('{server.location(slot)}') "
                    f"FORMAT 'CSV'")
                cols = ", ".join(f'"{c.name}"' for c in schema)
                control.query(
                    f"INSERT INTO {ext} SELECT {cols} "
                    f"FROM {table.id.fqtn()}")
                server.wait_done(slot)
            finally:
                try:
                    control.query(f"DROP EXTERNAL TABLE IF EXISTS {ext}")
                finally:
                    control.close()
        finally:
            server.release(slot)
            server.stop()


class GPSinker(PGSinker):
    """PG sink plus the gpfdist segment-direct bulk-insert path
    (gpfdist_sink.go:193): snapshot batches load via READABLE EXTERNAL
    TABLE with the segments pulling CSV straight from the worker; CDC
    row events keep the per-statement master path."""

    def __init__(self, params: GPTargetParams):
        super().__init__(params)
        self._server: GpfdistServer | None = None

    def _gpfdist(self) -> GpfdistServer:
        if self._server is None:
            self._server = GpfdistServer(
                self.params.gpfdist_host).start()
        return self._server

    def close(self) -> None:
        super().close()
        if self._server is not None:
            self._server.stop()
            self._server = None

    def _copy_insert(self, batch: ColumnBatch) -> None:
        if not getattr(self.params, "gpfdist", False):
            return super()._copy_insert(batch)
        server = self._gpfdist()
        slot = f"l{uuid.uuid4().hex[:12]}"
        tid = batch.table_id
        ext = f'"{tid.namespace}"."{tid.name}__trtpu_rext_{slot}"'
        data = batch.to_pydict()
        names = list(batch.columns)
        lines = []
        for i in range(batch.n_rows):
            lines.append(",".join(
                self._csv_cell(data[n][i]) for n in names))
        server.put_chunk(slot, ("\n".join(lines) + "\n").encode())
        server.finish(slot)
        cols = ", ".join(f'"{n}"' for n in names)
        # EXPLICIT column defs in batch-column order: (LIKE target) would
        # bind the positional CSV to the target's full column list, which
        # breaks when the target pre-exists with extra/reordered columns
        from transferia_tpu.typesystem.rules import map_target_type

        by_name = {c.name: c for c in batch.schema}
        defs = ", ".join(
            f'"{n}" {map_target_type("pg", by_name[n].data_type)}'
            for n in names)
        try:
            self.conn.query(
                f"CREATE READABLE EXTERNAL TABLE {ext} ({defs}) "
                f"LOCATION ('{server.location(slot)}') FORMAT 'CSV'")
            self.conn.query(
                f"INSERT INTO {tid.fqtn()} ({cols}) "
                f"SELECT {cols} FROM {ext}")
        finally:
            try:
                self.conn.query(f"DROP EXTERNAL TABLE IF EXISTS {ext}")
            finally:
                server.release(slot)


@register_provider
class GreenplumProvider(Provider):
    NAME = "greenplum"

    def storage(self):
        if isinstance(self.transfer.src, GPSourceParams):
            return GPStorage(self.transfer.src)
        return None

    def sinker(self):
        if isinstance(self.transfer.dst, GPTargetParams):
            return GPSinker(self.transfer.dst)
        return None
