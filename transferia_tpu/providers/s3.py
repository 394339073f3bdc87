"""S3 / object-storage provider.

Reference parity: pkg/providers/s3/ — snapshot source with format readers
(parquet/csv/jsonl/line/nginx/proto via providers/s3readers.py, schema
inference per reader/abstract.go:40-52), the snapshot/replication sinks
with file splitting (sink/file_splitter.go), and a replication source
(providers/s3source.py): set `event_source: sqs` (bucket notifications
through an SQS queue, s3/source/ + object_fetcher_sqs.go) or
`event_source: poll` (listing watermark in the coordinator state,
object_fetcher_poller.go).  Storage access goes through fsspec, so the
same provider serves s3://, gs://, and file:// URLs depending on which
backends the environment ships.  Parquet objects stream row-group-parallel
straight into columnar batches — the ClickBench snapshot path.
"""

from __future__ import annotations

import json
import logging
from dataclasses import dataclass, field
from typing import Optional

from transferia_tpu.abstract.commit import StagedSinker
from transferia_tpu.abstract.errors import CategorizedError
from transferia_tpu.abstract.interfaces import (
    AsyncPartDiscovery,
    Batch,
    Pusher,
    ShardingStorage,
    Sinker,
    Storage,
    TableInfo,
    is_columnar,
)
from transferia_tpu.abstract.kinds import Kind
from transferia_tpu.abstract.schema import TableID, TableSchema
from transferia_tpu.abstract.table import TableDescription
from transferia_tpu.columnar.batch import ColumnBatch, arrow_to_table_schema
from transferia_tpu.models.endpoint import EndpointParams, register_endpoint
from transferia_tpu.providers.file import declared_text_schema
from transferia_tpu.providers.registry import (
    Provider,
    TestResult,
    register_provider,
)

logger = logging.getLogger(__name__)


@register_endpoint
@dataclass
class S3SourceParams(EndpointParams):
    PROVIDER = "s3"
    IS_SOURCE = True

    url: str = ""              # e.g. s3://bucket/prefix/*.parquet
    format: str = "parquet"    # parquet | jsonl | csv | line | nginx | proto
    table: str = "data"
    namespace: str = "s3"
    batch_rows: int = 65_536
    endpoint_url: str = ""     # custom S3 endpoint (minio etc.)
    anon: bool = True
    storage_options: dict = field(default_factory=dict)
    nginx_format: str = ""     # log_format template (default: combined)
    unparsed_policy: str = "route"   # route | skip | fail
    parser: Optional[dict] = None    # protobuf descriptor config (proto)
    # the table's columns, declared ([{name, type, key}]; upstream's
    # OutputSchema): for jsonl and csv, as providers/file.py has it
    output_schema: list = field(default_factory=list)

    # -- replication (reference pkg/providers/s3/source/) -------------------
    event_source: str = ""     # "" (snapshot-only) | poll | sqs
    poll_interval: float = 5.0
    sqs_queue_url: str = ""
    sqs_region: str = "us-east-1"
    sqs_access_key: str = ""
    sqs_secret_key: str = ""
    sqs_endpoint: str = ""     # custom endpoint (localstack / fakes)
    sqs_wait_seconds: int = 10
    path_pattern: str = ""     # restrict replicated keys (glob)

    def __post_init__(self):
        declared_text_schema(self)

    def make_reader(self):
        from transferia_tpu.providers.s3readers import make_reader

        return make_reader(
            self.format, nginx_format=self.nginx_format,
            unparsed_policy=self.unparsed_policy,
            parser_config=self.parser,
            declared=declared_text_schema(self),
        )


@register_endpoint
@dataclass
class S3TargetParams(EndpointParams):
    PROVIDER = "s3"
    IS_TARGET = True

    url: str = ""              # output directory URL
    format: str = "parquet"    # parquet | jsonl
    endpoint_url: str = ""
    anon: bool = False
    storage_options: dict = field(default_factory=dict)
    max_rows_per_file: int = 1_000_000   # file splitting (file_splitter.go)
    # -- staged-commit credentials (the exactly-once object path signs
    # its own requests through the SigV4 client; fsspec's anonymous /
    # ambient-credential modes stay on the at-least-once path)
    access_key: str = ""
    secret_key: str = ""
    region: str = "us-east-1"


def _fs_for(url: str, params) -> tuple[object, str]:
    """fsspec filesystem + path for a URL."""
    try:
        import fsspec
    except ImportError as e:  # pragma: no cover
        raise CategorizedError(
            CategorizedError.INTERNAL,
            "fsspec is required for the s3 provider",
        ) from e
    opts = dict(params.storage_options or {})
    if url.startswith("s3://"):
        opts.setdefault("anon", params.anon)
        if params.endpoint_url:
            opts.setdefault("client_kwargs",
                            {"endpoint_url": params.endpoint_url})
    try:
        fs, path = fsspec.core.url_to_fs(url, **opts)
    except ImportError as e:
        raise CategorizedError(
            CategorizedError.SOURCE,
            f"no fsspec backend for {url.split('://')[0]}:// "
            f"(install s3fs/gcsfs): {e}",
        ) from e
    return fs, path


class S3Storage(Storage, ShardingStorage, AsyncPartDiscovery):
    def __init__(self, params: S3SourceParams):
        self.params = params
        self.table = TableID(params.namespace, params.table)
        self._schema: Optional[TableSchema] = None
        self._fs = None
        self._files: Optional[list[str]] = None
        self._reader = None

    @property
    def fs(self):
        if self._fs is None:
            self._fs, self._root = _fs_for(self.params.url, self.params)
        return self._fs

    def files(self) -> list[str]:
        if self._files is None:
            fs = self.fs
            if "*" in self._root or "?" in self._root:
                found = sorted(fs.glob(self._root))
            elif fs.isdir(self._root):
                found = sorted(
                    p for p in fs.find(self._root) if not p.endswith("/")
                )
            else:
                found = [self._root] if fs.exists(self._root) else []
            # the staged-commit sink keeps in-flight parts and publish
            # markers under `.staging/` in the same prefix; readers
            # must never ingest them as table data
            found = [p for p in found if "/.staging/" not in f"/{p}"]
            if not found:
                raise FileNotFoundError(
                    f"s3 source: no objects match {self.params.url!r}"
                )
            self._files = found
        return self._files

    @property
    def reader(self):
        if self._reader is None:
            self._reader = self.params.make_reader()
        return self._reader

    # -- schema inference (reader/abstract.go:40-52) ------------------------
    def table_schema(self, table: TableID) -> TableSchema:
        if self._schema is None:
            # a declared schema is the reader's own: nothing is listed
            self._schema = self.reader.declared \
                or self.reader.infer_schema(self.fs, self.files()[0])
        return self._schema

    def table_list(self, include=None):
        if include and not any(
                self.table.include_matches(p) for p in include):
            return {}
        eta = 0
        if self.params.format == "parquet":
            for f in self.files():
                eta += self.reader.estimate_rows(self.fs, f)
        return {self.table: TableInfo(
            eta_rows=eta, schema=self.table_schema(self.table)
        )}

    def estimate_table_rows_count(self, table: TableID) -> int:
        info = self.table_list().get(self.table)
        return info.eta_rows if info else 0

    def shard_table(self, table: TableDescription) -> list[TableDescription]:
        out = []
        for f in self.files():
            eta = 0
            if self.params.format == "parquet":
                eta = self.reader.estimate_rows(self.fs, f)
            out.append(TableDescription(id=table.id, filter=f"obj:{f}",
                                        eta_rows=eta))
        return out

    def iter_table_parts(self, table: TableDescription):
        """Stream per-object parts while upload runs (huge listings must
        not serialize activation — tpp_setter_async.go parity)."""
        for f in self.files():
            eta = 0
            if self.params.format == "parquet":
                eta = self.reader.estimate_rows(self.fs, f)
            yield TableDescription(id=table.id, filter=f"obj:{f}",
                                   eta_rows=eta)

    def load_table(self, table: TableDescription, pusher: Pusher) -> None:
        files = [table.filter[4:]] if table.filter.startswith("obj:") \
            else self.files()
        schema = self.table_schema(table.id)
        for f in files:
            self.reader.read(self.fs, f, table.id, schema,
                             self.params.batch_rows, pusher)

    def ping(self) -> None:
        self.files()


def _s3_stage(key: str, epoch: int, prefix: str):
    """One part's staging state inside the S3 object sink: the shared
    WireStage plus the staging key prefix and an object sequence."""
    from transferia_tpu.providers.staging import WireStage

    stage = WireStage(key, epoch)
    # slug is a path COMPONENT ("/" cannot appear in a slug), so one
    # part's staging prefix can never prefix-match another's even for
    # dotted slugs where "a.t" prefixes "a.t.z"
    stage.dir = f"{prefix}.staging/{stage.slug}/e{epoch}/"
    stage.seq = 0
    return stage


class S3Sinker(Sinker, StagedSinker):
    """Object sink with size-based file splitting (sink/file_splitter.go).

    Staged-commit capable on s3:// targets with explicit credentials
    (abstract/commit.py): with an open part stage each pushed batch
    lands as an object under `.staging/<part slug>.e<epoch>/` —
    invisible to readers, which skip the `.staging/` prefix — and
    publish FIRST advances the persisted
    `.staging/.published.<slug>.json` marker with a CONDITIONAL PUT
    (If-Match on the observed marker ETag / If-None-Match on first
    publish), THEN does the batched copy-to-final (delete the part's
    previous objects under `<prefix><slug>/`, copy the staged keys
    in).  Racing publishers serialize at the store on the marker CAS:
    a zombie raises StaleEpochPublishError before touching any final
    object, and a crash between the marker and the copy is repaired by
    the retried part republishing idempotently under the same epoch."""

    def __init__(self, params: S3TargetParams):
        import uuid as _uuid

        self.params = params
        self._fs = None
        self._root: Optional[str] = None
        self.token = _uuid.uuid4().hex[:8]
        self._counters: dict[TableID, int] = {}
        self._rows_in_file: dict[TableID, int] = {}
        self._writers: dict[TableID, object] = {}
        self._handles: dict[TableID, object] = {}
        self._stage = None  # staging.WireStage (+ dir/seq) when open
        self._client = None

    @property
    def fs(self):
        if self._fs is None:
            self._fs, self._root = _fs_for(self.params.url, self.params)
        return self._fs

    @property
    def root(self) -> str:
        if self._root is None:
            self.fs  # resolves both
        return self._root

    def _next_path(self, tid: TableID, ext: str) -> str:
        n = self._counters.get(tid, 0)
        return f"{self.root.rstrip('/')}/" \
               f"{tid.namespace}.{tid.name}.{self.token}.{n:06d}.{ext}"

    def push(self, batch: Batch) -> None:
        if not is_columnar(batch):
            if self._stage is None:
                for it in batch:
                    if it.kind in (Kind.DONE_TABLE_LOAD,
                                   Kind.DONE_SHARDED_TABLE_LOAD):
                        self._finish(it.table_id)
            rows = [it for it in batch if it.is_row_event()]
            if not rows:
                return
            batch = ColumnBatch.from_rows(rows)
        if self._stage is not None:
            self._stage_push(batch)
            return
        tid = batch.table_id
        if self.params.format == "parquet":
            import pyarrow.parquet as pq

            rb = batch.to_arrow()
            w = self._writers.get(tid)
            if w is None:
                fh = self.fs.open(self._next_path(tid, "parquet"), "wb")
                w = pq.ParquetWriter(fh, rb.schema)
                self._writers[tid] = w
                self._handles[tid] = fh
                self._rows_in_file[tid] = 0
            if rb.schema != w.schema:
                # dict-encoded vs flat batches of one table (see the fs
                # sink): cast to the file's schema
                rb = rb.cast(w.schema)
            w.write_batch(rb)
            self._rows_in_file[tid] += batch.n_rows
            if self._rows_in_file[tid] >= self.params.max_rows_per_file:
                self._finish(tid)
        else:
            # object stores have no append: keep one open handle per table
            # and rotate whole objects at the row threshold
            fh = self._handles.get(tid)
            if fh is None:
                fh = self.fs.open(self._next_path(tid, "jsonl"), "wb")
                self._handles[tid] = fh
                self._rows_in_file[tid] = 0
            for row in batch.to_rows():
                fh.write(json.dumps(
                    row.as_dict(), default=str
                ).encode() + b"\n")
            self._rows_in_file[tid] += batch.n_rows
            if self._rows_in_file[tid] >= self.params.max_rows_per_file:
                self._finish(tid)

    def _finish(self, tid: TableID) -> None:
        w = self._writers.pop(tid, None)
        if w is not None:
            w.close()
        fh = self._handles.pop(tid, None)
        if fh is not None:
            fh.close()
        if w is not None or fh is not None:
            self._counters[tid] = self._counters.get(tid, 0) + 1

    def close(self) -> None:
        for tid in set(list(self._writers) + list(self._handles)):
            self._finish(tid)

    # -- StagedSinker (publish = batched copy behind a marker fence) --------
    def _bucket_prefix(self) -> tuple[str, str]:
        rest = self.params.url[len("s3://"):]
        bucket, _, prefix = rest.partition("/")
        prefix = prefix.strip("/")
        return bucket, (prefix + "/") if prefix else ""

    def staged_commit_available(self) -> bool:
        if not self.params.url.startswith("s3://"):
            return False
        opts = self.params.storage_options or {}
        if not ((self.params.access_key or opts.get("key"))
                and (self.params.secret_key or opts.get("secret"))):
            return False
        if self.params.format == "parquet":
            try:
                import pyarrow  # noqa: F401
            except ImportError:
                return False
        return self.params.format in ("parquet", "jsonl")

    def _staged_client(self):
        if self._client is None:
            from transferia_tpu.coordinator.s3client import S3Client

            opts = self.params.storage_options or {}
            bucket, _ = self._bucket_prefix()
            self._client = S3Client(
                bucket=bucket,
                endpoint=self.params.endpoint_url,
                region=self.params.region,
                access_key=self.params.access_key or opts.get("key", ""),
                secret_key=self.params.secret_key
                or opts.get("secret", ""),
            )
        return self._client

    def _serialize_batch(self, batch: ColumnBatch) -> tuple[str, bytes]:
        if self.params.format == "parquet":
            import io

            import pyarrow as pa
            import pyarrow.parquet as pq

            buf = io.BytesIO()
            rb = batch.to_arrow()
            pq.write_table(pa.Table.from_batches([rb]), buf)
            return "parquet", buf.getvalue()
        lines = [
            json.dumps(row.as_dict(), default=str).encode() + b"\n"
            for row in batch.to_rows()
        ]
        return "jsonl", b"".join(lines)

    def begin_part(self, key: str, epoch: int) -> None:
        _, prefix = self._bucket_prefix()
        stage = _s3_stage(key, epoch, prefix)
        client = self._staged_client()
        # begin replaces — for EVERY epoch of this key: sweep crashed
        # earlier attempts' staged objects too (all epochs live under
        # the part's own `.staging/<slug>/`), or a steal's epoch bump
        # would leak them forever
        for obj in client.list(f"{prefix}.staging/{stage.slug}/"):
            client.delete(obj.key)
        self._stage = stage

    def _stage_push(self, batch: ColumnBatch) -> None:
        stage = self._stage
        staged = stage.state.stage(batch)
        if staged.n_rows == 0:
            return
        ext, body = self._serialize_batch(staged)
        tid = staged.table_id
        stage_key = (f"{stage.dir}{stage.seq:06d}."
                     f"{tid.namespace}.{tid.name}.{ext}")
        stage.seq += 1
        try:
            self._staged_client().put(stage_key, body)
        except BaseException:
            # the staging write died after the dedup window recorded
            # this batch: only a full part restage is safe
            stage.state.mark_failed()
            raise

    def _marker_key(self, slug: str) -> str:
        _, prefix = self._bucket_prefix()
        return f"{prefix}.staging/.published.{slug}.json"

    def _advance_marker(self, key: str, epoch: int, slug: str) -> None:
        """Persist the publish epoch with a conditional write; racing
        publishers serialize at the store, the loser re-checks."""
        from transferia_tpu.abstract.errors import StaleEpochPublishError
        from transferia_tpu.coordinator.s3client import (
            ConditionalUnsupported,
            PreconditionFailed,
        )

        client = self._staged_client()
        body = json.dumps({"epoch": epoch, "key": key}).encode()
        for _ in range(8):
            cur = client.get(self._marker_key(slug))
            if cur is not None:
                prev = int(json.loads(cur[0]).get("epoch", -1))
                if epoch < prev:
                    raise StaleEpochPublishError(key, epoch, prev)
            try:
                if cur is None:
                    client.put(self._marker_key(slug), body,
                               if_none_match=True)
                else:
                    client.put(self._marker_key(slug), body,
                               if_match=cur[1])
                return
            except PreconditionFailed:
                continue  # lost the race: re-read and re-fence
            except ConditionalUnsupported:
                # endpoint without conditional writes: last-writer-wins
                # degrade, same contract as the s3 coordinator backend
                logger.warning(
                    "s3 target lacks conditional writes; publish "
                    "marker for %s written last-writer-wins", key)
                client.put(self._marker_key(slug), body)
                return
        raise CategorizedError(
            CategorizedError.TARGET,
            f"publish marker CAS for {key!r} did not converge")

    def publish_part(self, key: str, epoch: int) -> int:
        from transferia_tpu.chaos.failpoints import failpoint
        from transferia_tpu.providers.staging import publish_guard
        from transferia_tpu.stats import trace

        stage = self._stage
        if stage is None or stage.key != key:
            raise RuntimeError(f"s3 sink: no open stage for {key!r}")
        client = self._staged_client()
        _, prefix = self._bucket_prefix()
        with publish_guard(key, epoch):
            trace.instant("s3_publish_copy", part=key, epoch=epoch,
                          rows=stage.state.rows)
            failpoint("sink.s3.publish")
            # fence FIRST: the conditional marker write must win before
            # any final object is touched, so a zombie raises here with
            # the survivor's objects intact.  A crash after the marker
            # but before the copy is repaired by the retried part
            # republishing idempotently under the same epoch.
            self._advance_marker(key, epoch, stage.slug)
            # replace: drop what an older publish of this part landed.
            # The part's final objects live under their own slug-keyed
            # "directory", so the listing is O(this part) and cannot
            # match another part's keys by substring accident.
            part_prefix = f"{prefix}{stage.slug}/"
            for obj in client.list(part_prefix):
                client.delete(obj.key)
            # batched copy-to-final: staged keys become
            # `<prefix><slug>/<seq>.<ns>.<table>.<ext>` objects
            staged_objs = sorted(client.list(stage.dir),
                                 key=lambda o: o.key)
            for obj in staged_objs:
                got = client.get(obj.key)
                if got is None:
                    continue  # concurrent abort of a superseded stage
                name = obj.key[len(stage.dir):]
                client.put(f"{part_prefix}{name}", got[0])
            for obj in staged_objs:
                client.delete(obj.key)
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
            client = self._staged_client()
            for obj in client.list(stage.dir):
                client.delete(obj.key)
        except Exception as e:
            logger.warning("s3 staged abort of %s: %s", key, e)

    def note_push_retry(self) -> None:
        if self._stage is not None:
            self._stage.state.note_push_retry()


@register_provider
class S3Provider(Provider):
    NAME = "s3"

    def storage(self):
        if isinstance(self.transfer.src, S3SourceParams):
            return S3Storage(self.transfer.src)
        return None

    def sinker(self):
        if isinstance(self.transfer.dst, S3TargetParams):
            return S3Sinker(self.transfer.dst)
        return None

    def source(self):
        if isinstance(self.transfer.src, S3SourceParams) \
                and self.transfer.src.event_source:
            from transferia_tpu.providers.s3source import (
                S3ReplicationSource,
            )

            return S3ReplicationSource(
                self.transfer.src, self.transfer.id, self.coordinator)
        return None

    def test(self) -> TestResult:
        result = TestResult(ok=True)
        try:
            if isinstance(self.transfer.src, S3SourceParams):
                S3Storage(self.transfer.src).ping()
            result.add("list")
        except Exception as e:
            result.add("list", e)
        return result
