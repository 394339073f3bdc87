"""Local-filesystem object provider: parquet/jsonl/csv files as tables.

Reference parity: the S3 provider's format-reader stack
(pkg/providers/s3/reader/registry/: csv/json/line/parquet + schema
inference reader/abstract.go:40-52) operating on a local directory; the S3
provider proper layers remote listing on top of this (providers/s3.py).

Parquet is the columnar fast path: row groups map straight to ColumnBatch
via arrow with no row pivot (the ClickBench north-star read path), and each
row group is a shardable part.
"""

from __future__ import annotations

import glob as globmod
import json
import os
import threading
import uuid
from dataclasses import dataclass, field
from typing import Optional

import numpy as np

from transferia_tpu.abstract.commit import StagedSinker
from transferia_tpu.abstract.interfaces import (
    Batch,
    Pusher,
    ScanPredicateStorage,
    ShardingStorage,
    Sinker,
    Storage,
    TableInfo,
    is_columnar,
)
from transferia_tpu.abstract.kinds import Kind
from transferia_tpu.abstract.schema import (
    TableID,
    TableSchema,
    declared_schema,
)

from transferia_tpu.abstract.table import TableDescription
from transferia_tpu.columnar.batch import ColumnBatch, arrow_to_table_schema
from transferia_tpu.models.endpoint import EndpointParams, register_endpoint
from transferia_tpu.runtime import knobs
from transferia_tpu.providers.registry import Provider, register_provider


@register_endpoint
@dataclass
class FileSourceParams(EndpointParams):
    PROVIDER = "fs"
    IS_SOURCE = True

    path: str = ""            # file, dir, or glob
    format: str = "parquet"   # parquet | jsonl | csv
    table: str = "data"       # logical table name
    namespace: str = "fs"
    batch_rows: int = 65_536
    # decode-pipeline knobs (ARCHITECTURE.md "Decode pipeline"):
    # decode_threads: column-parallel native decode width; 0 = auto
    # (effective CPUs / upload workers — parts already decode in
    # parallel across workers, so K only widens when cores are spare).
    # readahead_groups: decoded row groups in flight per part (the one
    # the consumer holds + queued + decoding); -1 = auto (2 with >1
    # effective CPU, else 0), 0 = serial decode.  readahead_bytes adds
    # an optional in-flight decoded-payload cap on top (0 = none).
    # rowgroups_per_part: consecutive row groups per shard part; 0 =
    # auto (1 with readahead off — today's per-group parts — else up to
    # 4, keeping ~4 parts queued per upload worker).  Parts spanning
    # several groups are what give the per-part readahead a g+1 to
    # prefetch.
    decode_threads: int = 0
    readahead_groups: int = -1
    readahead_bytes: int = 0
    rowgroups_per_part: int = 0
    # the table's columns, declared: [{name, type, key}], the types by
    # abstract/schema.py CanonicalType's names (upstream's OutputSchema).
    # For jsonl and csv; with it no line is read to learn the schema and
    # the text is decoded to these types.  Empty: the schema is inferred
    # from the first file
    output_schema: list = field(default_factory=list)

    def __post_init__(self):
        declared_text_schema(self)


def declared_text_schema(params) -> Optional[TableSchema]:
    """The `output_schema` of an `fs` / `s3` source as a TableSchema, None
    where it declares none; ValueError for one that cannot be read or
    that a format with a schema of its own is given."""
    where = f"{params.PROVIDER} source"
    schema = declared_schema(params.output_schema, where)
    if schema is not None and params.format not in ("jsonl", "csv"):
        raise ValueError(
            f"{where}: output_schema is for format jsonl or csv; "
            f"{params.format!r} carries its own schema")
    return schema


@register_endpoint
@dataclass
class FileTargetParams(EndpointParams):
    PROVIDER = "fs"
    IS_TARGET = True

    path: str = ""            # output directory
    format: str = "parquet"   # parquet | jsonl


def _expand(path: str) -> list[str]:
    if os.path.isdir(path):
        return sorted(
            p for p in globmod.glob(os.path.join(path, "**", "*"),
                                    recursive=True)
            if os.path.isfile(p)
        )
    return sorted(globmod.glob(path))


class FileStorage(Storage, ShardingStorage, ScanPredicateStorage):
    def __init__(self, params: FileSourceParams, metrics=None,
                 upload_workers: int = 1):
        self.params = params
        self.table = TableID(params.namespace, params.table)
        self._schema: Optional[TableSchema] = None
        self._scan_predicates: dict[TableID, object] = {}
        self._pred_fns: dict[TableID, object] = {}
        self._pruned_lock = threading.Lock()
        self.scan_rows_pruned = 0
        self._upload_workers = max(1, upload_workers)
        self._readahead_gauges = None
        if metrics is not None:
            from transferia_tpu.stats.registry import DeviceStats

            ds = DeviceStats(metrics)
            self._readahead_gauges = (ds.readahead_depth,
                                      ds.readahead_bytes)

    # -- decode-pipeline knob resolution ------------------------------------
    def _decode_threads(self) -> int:
        env = knobs.env_raw("TRANSFERIA_TPU_DECODE_THREADS")
        k = int(env) if env else self.params.decode_threads
        if k <= 0:
            # auto: each upload worker already runs a consumer thread
            # and a readahead decoder, so claim only half the per-worker
            # core share — K = cpus/(2*workers), measured neutral at 4
            # workers on 24 cores where cpus/workers oversubscribed ~9%
            from transferia_tpu.runtime.limits import effective_cpus

            k = int(effective_cpus()) // (2 * self._upload_workers)
        return max(1, min(8, k))

    def _readahead_groups(self) -> int:
        env = knobs.env_raw("TRANSFERIA_TPU_READAHEAD_GROUPS")
        n = int(env) if env else self.params.readahead_groups
        if n < 0:  # auto: overlap decode unless there's a single core
            from transferia_tpu.runtime.limits import effective_cpus

            n = 2 if effective_cpus() >= 2 else 0
        return n

    def _readahead(self, groups, decode, nbytes):
        from transferia_tpu.providers.readahead import RowGroupReadahead

        return RowGroupReadahead(
            groups, decode,
            max_groups=self._readahead_groups(),
            max_bytes=self.params.readahead_bytes or None,
            nbytes=nbytes, gauges=self._readahead_gauges)

    def _count_pruned(self, n: int) -> None:
        # upload workers share this storage across threads
        with self._pruned_lock:
            self.scan_rows_pruned += n

    def _files(self) -> list[str]:
        files = _expand(self.params.path)
        if not files:
            raise FileNotFoundError(
                f"fs source: no files match {self.params.path!r}"
            )
        return files

    # -- schema inference ---------------------------------------------------
    def table_schema(self, table: TableID) -> TableSchema:
        if self._schema is None:
            self._schema = declared_text_schema(self.params)
        if self._schema is None:
            f = self._files()[0]
            if self.params.format == "parquet":
                import pyarrow.parquet as pq

                self._schema = arrow_to_table_schema(
                    pq.read_schema(f)
                )
            elif self.params.format == "csv":
                import pyarrow.csv as pacsv

                # stream only the first block — never parse the whole file
                # just to learn the schema
                with pacsv.open_csv(f) as reader:
                    self._schema = arrow_to_table_schema(reader.schema)
            else:  # jsonl: sample first lines
                from transferia_tpu.providers.s3readers import (
                    infer_json_lines_schema,
                )

                with open(f, "rb") as fh:
                    self._schema = infer_json_lines_schema(fh)
        return self._schema

    def table_list(self, include=None):
        if include and not any(
                self.table.include_matches(p) for p in include):
            return {}
        eta = 0
        if self.params.format == "parquet":
            from transferia_tpu.providers.parquet_native import (
                parquet_metadata,
            )

            for f in self._files():
                eta += parquet_metadata(f).num_rows
        return {self.table: TableInfo(
            eta_rows=eta, schema=self.table_schema(self.table)
        )}

    def estimate_table_rows_count(self, table: TableID) -> int:
        info = self.table_list().get(self.table)
        return info.eta_rows if info else 0

    # -- sharding: parquet shards per row-group run, other formats per file -
    def _groups_per_part(self, n_groups: int) -> int:
        """Row groups per shard part.  One group per part (the original
        sharding) maximizes worker-level parallelism but starves the
        per-part readahead — there is no g+1 inside a single-group part.
        Auto keeps ~4 parts queued per upload worker and caps the run
        at 4 groups so one straggler part never serializes the tail."""
        p = self.params.rowgroups_per_part
        if p <= 0:
            if self._readahead_groups() <= 0:
                return 1  # serial decode: per-group parts, as before
            p = min(4, max(1, n_groups // (4 * self._upload_workers)))
        return max(1, p)

    def shard_table(self, table: TableDescription) -> list[TableDescription]:
        files = self._files()
        out = []
        for f in files:
            if self.params.format == "parquet":
                from transferia_tpu.providers.parquet_native import (
                    parquet_metadata,
                )

                meta = parquet_metadata(f)
                n_groups = meta.num_row_groups
                step = self._groups_per_part(n_groups)
                for lo in range(0, n_groups, step):
                    hi = min(lo + step, n_groups)
                    out.append(TableDescription(
                        id=table.id, filter=f"rg:{lo}:{hi}:{f}",
                        eta_rows=sum(meta.row_group(g).num_rows
                                     for g in range(lo, hi)),
                    ))
            else:
                out.append(TableDescription(id=table.id, filter=f"file:{f}"))
        return out

    # -- load ---------------------------------------------------------------
    def load_table(self, table: TableDescription, pusher: Pusher) -> None:
        schema = self.table_schema(table.id)
        if table.filter.startswith("rg:"):
            _, lo, hi, path = table.filter.split(":", 3)
            self._load_row_groups(path, int(lo), int(hi), table.id, schema,
                                  pusher)
            return
        if table.filter.startswith("file:"):
            files = [table.filter[5:]]
        else:
            files = self._files()
        for f in files:
            self._load_file(f, table.id, schema, pusher)

    def set_scan_predicate(self, table: TableID, node) -> bool:
        """ScanPredicateStorage: arrow-side pre-filter of record batches
        before the columnar pivot (predicate/arroweval.py).  Advisory —
        batches where arrow evaluation bails flow through unfiltered and
        the chain's own filter drops the rows instead."""
        self._scan_predicates[table] = node
        return True

    def _scan_filter(self, tid: TableID, rb):
        node = self._scan_predicates.get(tid)
        if node is None or rb.num_rows == 0:
            return rb
        from transferia_tpu.predicate.arroweval import eval_mask
        from transferia_tpu.stats import trace

        # on the part thread, between the decode and the `batch` it feeds
        with trace.span("scan_filter"):
            mask = eval_mask(node, rb)
            if mask is None:
                return rb
            filtered = rb.filter(mask)  # null mask entries drop (SQL 3VL)
        self._count_pruned(rb.num_rows - filtered.num_rows)
        return filtered

    def _prune_row_groups(self, pf, groups: list[int],
                          tid: TableID) -> list[int]:
        """Zone-map pruning: drop whole row groups whose min/max stats
        disprove the scan predicate (predicate/stats.py) — the only form
        of pushdown that skips DECODE, not just pivot/transform."""
        node = self._scan_predicates.get(tid)
        if node is None:
            return groups
        from transferia_tpu.predicate.stats import (
            ColumnRange,
            range_disproves,
        )

        pred_cols = node.columns()
        kept = []
        for g in groups:
            rg = pf.metadata.row_group(g)
            ranges = {}
            for ci in range(rg.num_columns):
                col = rg.column(ci)
                if col.path_in_schema not in pred_cols:
                    continue  # wide tables: only the predicate's columns
                st = col.statistics
                if st is None or not st.has_min_max:
                    continue
                ranges[col.path_in_schema] = ColumnRange(
                    min=st.min, max=st.max,
                    null_count=(st.null_count
                                if st.has_null_count else None))
            try:
                if ranges and range_disproves(node, ranges):
                    self._count_pruned(rg.num_rows)
                    continue
            except Exception:  # trtpu: ignore[EXC001]
                pass  # odd stats types: scan the group normally
            kept.append(g)
        return kept

    def _batch_filter(self, tid: TableID, batch: ColumnBatch
                      ) -> ColumnBatch:
        """Scan-predicate filter over an already-pivoted batch (native
        decode path) — numpy compiler, same 3VL as the chain's filter."""
        node = self._scan_predicates.get(tid)
        if node is None or batch.n_rows == 0:
            return batch
        from transferia_tpu.predicate.compile import compile_mask
        from transferia_tpu.stats import trace

        fn = self._pred_fns.get(tid)
        if fn is None:
            fn = compile_mask(node)
            self._pred_fns[tid] = fn
        # on the part thread, between the decode and the `batch` it feeds:
        # the mask and the gather of every column the batch has
        with trace.span("scan_filter"):
            keep = fn(batch)
            if keep.all():
                return batch
            out = batch.filter(keep)
        self._count_pruned(batch.n_rows - out.n_rows)
        return out

    def _has_huge_row_groups(self, pf, groups: list[int]) -> bool:
        """Row groups too large to materialize whole per part thread
        (externally-written files can carry ~1M-row groups): both the
        native and arrow paths stream those through iter_batches."""
        max_rg_rows = max(
            pf.metadata.row_group(g).num_rows for g in groups)
        return max_rg_rows > max(8 * self.params.batch_rows, 1 << 20)

    def _load_groups_native(self, pf, path: str, groups: list[int],
                            tid: TableID, schema: TableSchema,
                            pusher: Pusher) -> bool:
        """Decode row groups via the C++ chunk decoder; False -> use arrow."""
        from transferia_tpu.providers.parquet_native import (
            NativeParquetReader,
            slice_columns,
        )

        if self._has_huge_row_groups(pf, groups):
            return False  # stream huge row groups through arrow instead
        reader = NativeParquetReader.open(
            path, pf, schema, decode_threads=self._decode_threads())
        if reader is None:
            return False

        decode = reader.read_row_group

        def cols_nbytes(cols):
            return sum(c.nbytes() for c in cols.values())

        with self._readahead(groups, decode, cols_nbytes) as ra:
            for g, cols in ra:
                n = pf.metadata.row_group(g).num_rows
                for b_lo in range(0, n, self.params.batch_rows):
                    b_hi = min(b_lo + self.params.batch_rows, n)
                    batch = ColumnBatch(
                        tid, schema, slice_columns(cols, b_lo, b_hi))
                    batch.read_bytes = batch.nbytes()
                    batch = self._batch_filter(tid, batch)
                    if batch.n_rows:
                        pusher(batch)
        return True

    def _load_groups_arrow(self, pf, groups: list[int], tid: TableID,
                           schema: TableSchema, pusher: Pusher) -> None:
        """Arrow decode with the same row-group readahead as the native
        path: whole-group reads release the GIL inside arrow C++, so
        dict-heavy/nested files overlap decode with downstream too."""
        def decode(g):
            return pf.read_row_group(g, use_threads=False)

        with self._readahead(groups, decode, lambda t: t.nbytes) as ra:
            for g, tbl in ra:
                for rb in tbl.to_batches(
                        max_chunksize=self.params.batch_rows):
                    rb = self._scan_filter(tid, rb)
                    if rb.num_rows:
                        batch = ColumnBatch.from_arrow(rb, tid, schema)
                        batch.read_bytes = rb.nbytes
                        pusher(batch)

    def _load_row_groups(self, path: str, lo: int, hi: int, tid: TableID,
                         schema: TableSchema, pusher: Pusher) -> None:
        from transferia_tpu.chaos.failpoints import failpoint
        from transferia_tpu.providers.parquet_native import (
            parquet_file_cached,
        )

        failpoint("storage.file.open")
        # footer metadata memoizes per (path, mtime, size): a multi-part
        # load re-opens the same file once per part, and the thrift
        # footer parse was 3.9% of a CPU-host headline profile
        pf = parquet_file_cached(path)
        groups = self._prune_row_groups(pf, list(range(lo, hi)), tid)
        from transferia_tpu.stats import trace

        trace.instant("file_part_open", path=path, lo=lo, hi=hi,
                      groups=len(groups))
        if not groups:
            return
        if self._load_groups_native(pf, path, groups, tid, schema,
                                    pusher):
            return
        # whole-row-group reads beat iter_batches for dict-heavy files
        # (one dictionary unification per group, not per batch) and the
        # batch slices share dictionary buffers — which is what lets the
        # columnar layer pool-cache them (batch.py _adopt_dict_pool).
        # Var-width columns read dict-PRESERVING (read_dictionary): dict
        # pages surface as DictionaryArrays and adopt as shared
        # DictPools instead of decoding flat just to re-encode
        # downstream.  Externally-written files can carry huge row
        # groups (pyarrow default ~1M rows); cap the per-part
        # materialization and stream those through iter_batches instead.
        pf = self._dict_preserving_reader(pf, path, schema)
        if self._has_huge_row_groups(pf, groups):
            it = pf.iter_batches(batch_size=self.params.batch_rows,
                                 row_groups=groups)
            while True:
                rb = next(it, None)
                if rb is None:
                    return
                rb = self._scan_filter(tid, rb)
                if rb.num_rows:
                    batch = ColumnBatch.from_arrow(rb, tid, schema)
                    batch.read_bytes = rb.nbytes
                    pusher(batch)
            return
        self._load_groups_arrow(pf, groups, tid, schema, pusher)

    @staticmethod
    def _dict_preserving_reader(pf, path: str, schema: TableSchema):
        """A reader whose dict-encoded var-width columns keep their
        encoding (arrow paths only; the native path adopts pages
        itself).  Only columns whose chunks actually carry dictionary
        encodings qualify — read_dictionary on a PLAIN column would
        make arrow BUILD a dictionary, a pure loss for
        high-cardinality strings."""
        from transferia_tpu.providers.parquet_native import (
            dict_encoded_columns,
            parquet_file_cached,
        )

        var_cols = [cs.name for cs in schema
                    if cs.data_type.is_variable_width]
        dict_cols = dict_encoded_columns(pf.metadata, var_cols)
        if not dict_cols:
            return pf
        return parquet_file_cached(path, read_dictionary=dict_cols)

    def _load_file(self, path: str, tid: TableID, schema: TableSchema,
                   pusher: Pusher) -> None:
        fmt = self.params.format
        if fmt == "parquet":
            from transferia_tpu.providers.parquet_native import (
                parquet_metadata,
            )

            n_groups = parquet_metadata(path).num_row_groups
            self._load_row_groups(path, 0, n_groups, tid, schema, pusher)
        elif fmt == "csv":
            import pyarrow.csv as pacsv

            from transferia_tpu.providers.s3readers import (
                csv_convert_options,
            )

            with pacsv.open_csv(
                path,
                read_options=pacsv.ReadOptions(
                    block_size=max(1 << 20, self.params.batch_rows * 64)
                ),
                convert_options=csv_convert_options(
                    schema if self.params.output_schema else None),
            ) as reader:
                for rb in reader:
                    rb = self._scan_filter(tid, rb)
                    if rb.num_rows:
                        batch = ColumnBatch.from_arrow(rb, tid, schema)
                        batch.read_bytes = rb.nbytes
                        pusher(batch)
        elif fmt == "jsonl":
            from transferia_tpu.providers.s3readers import read_json_lines

            with open(path, "rb") as fh:
                # the parquet decode's rule for its column threads: none
                # where the upload workers already fill the cores
                read_json_lines(fh, path, tid, schema,
                                self.params.batch_rows, pusher,
                                use_threads=self._decode_threads() > 1)
        else:
            raise ValueError(f"fs source: unknown format {fmt!r}")


class FileSinker(Sinker, StagedSinker):
    """Writes per-table files; parquet goes through arrow zero-pivot.

    File names embed a per-sinker instance token: the snapshot loader builds
    one sink pipeline per table part in parallel (load_snapshot.go per-part
    sinks), so concurrent instances must never share an output path —
    the same contract as the reference S3 sink's part-scoped file splitting
    (s3/sink/file_splitter.go).

    Staged-commit capable (abstract/commit.py): with an open part stage
    the batches write into `<path>/.staging/<part>/` and only an
    epoch-fenced `publish_part` renames them into the output directory
    (replacing any files an earlier publish of the same part landed).
    """

    def __init__(self, params: FileTargetParams):
        self.params = params
        os.makedirs(params.path, exist_ok=True)
        self._token = uuid.uuid4().hex[:8]
        self._writers: dict[TableID, object] = {}
        self._counters: dict[TableID, int] = {}
        self._stage = None  # staging.DirectoryPartStage when open

    # -- StagedSinker -------------------------------------------------------
    def begin_part(self, key: str, epoch: int) -> None:
        from transferia_tpu.providers.staging import DirectoryPartStage

        self._stage = DirectoryPartStage(
            self.params.path, key, epoch,
            lambda d: FileSinker(FileTargetParams(
                path=d, format=self.params.format)))

    def publish_part(self, key: str, epoch: int) -> int:
        if self._stage is None:
            raise RuntimeError(f"fs sink: no open stage for {key!r}")
        rows = self._stage.publish()
        self.last_dedup_dropped = self._stage.state.dedup_dropped
        self._stage = None
        return rows

    def abort_part(self, key: str) -> None:
        if self._stage is not None:
            self._stage.abort()
            self._stage = None

    def note_push_retry(self) -> None:
        if self._stage is not None:
            self._stage.note_push_retry()

    def _base_name(self, tid: TableID) -> str:
        # empty namespaces must not produce hidden ".name..." dotfiles
        return f"{tid.namespace}.{tid.name}" if tid.namespace \
            else tid.name

    def _out_path(self, tid: TableID, ext: str) -> str:
        self._counters[tid] = self._counters.get(tid, 0)
        return os.path.join(
            self.params.path,
            f"{self._base_name(tid)}.{self._token}."
            f"{self._counters[tid]:06d}.{ext}",
        )

    def push(self, batch: Batch) -> None:
        if self._stage is not None:
            self._stage.push(batch)
            return
        if is_columnar(batch):
            self._write_columnar(batch)
            return
        # rows before a done-marker must land in the file that marker
        # finalizes; reuse the shared ordering-preserving splitter
        from transferia_tpu.middlewares.helpers import split_rows_controls

        for part in split_rows_controls(batch):
            items = list(part)
            if items and items[0].is_row_event():
                self._write_columnar(ColumnBatch.from_rows(items))
                continue
            for it in items:
                if it.kind in (Kind.DONE_TABLE_LOAD,
                               Kind.DONE_SHARDED_TABLE_LOAD):
                    self._finish_table(it.table_id)

    def _write_columnar(self, batch: ColumnBatch) -> None:
        tid = batch.table_id
        if self.params.format == "parquet":
            import pyarrow.parquet as pq

            rb = batch.to_arrow()
            w = self._writers.get(tid)
            if w is None:
                w = pq.ParquetWriter(
                    self._out_path(tid, "parquet"), rb.schema
                )
                self._writers[tid] = w
            if rb.schema != w.schema:
                # encoding can vary per batch (dictionary-encoded row
                # groups decode as dict, fallback/plain groups as flat
                # strings) — cast to the writer's schema (arrow C++
                # dict<->string casts) so one file stays one schema
                rb = rb.cast(w.schema)
            w.write_batch(rb)
        elif self.params.format == "jsonl":
            path = os.path.join(
                self.params.path,
                f"{self._base_name(tid)}.{self._token}.jsonl",
            )
            with open(path, "a") as fh:
                for row in batch.to_rows():
                    fh.write(json.dumps(row.as_dict(), default=str) + "\n")
        else:
            raise ValueError(f"fs sink: unknown format {self.params.format!r}")

    def _finish_table(self, tid: TableID) -> None:
        w = self._writers.pop(tid, None)
        if w is not None:
            w.close()
            self._counters[tid] = self._counters.get(tid, 0) + 1

    def close(self) -> None:
        if self._stage is not None:
            # an unpublished stage at close is an abandoned attempt
            # (error path / fenced part): discard, never auto-publish
            self._stage.abort()
            self._stage = None
        for w in self._writers.values():
            w.close()
        self._writers.clear()


@register_provider
class FileProvider(Provider):
    NAME = "fs"

    def storage(self):
        return FileStorage(
            self.transfer.src, metrics=self.metrics,
            upload_workers=self.transfer.runtime.sharding.process_count)

    def sinker(self):
        return FileSinker(self.transfer.dst)

    def cleanup(self, tables: list) -> None:
        """Drop the named tables' output files (every sink run writes
        uniquely-suffixed files, so without cleanup a reupload would
        duplicate data side by side).  Matches the exact
        `<base>.<8-hex-token>.<6-digit-counter>.<ext>` layout so a table
        named "A"."B" never deletes "A"."B.X" files."""
        import re as _re

        path = getattr(self.transfer.dst, "path", "")
        if not path or not os.path.isdir(path):
            return
        for t in tables or []:
            tid = getattr(t, "id", t)
            base = f"{tid.namespace}.{tid.name}" if tid.namespace \
                else tid.name
            # parquet: base.token.counter.ext; jsonl: base.token.jsonl
            # also matches staged-commit published names, which insert
            # a `.part-<slug>` infix before the extension
            pat = _re.compile(
                _re.escape(base)
                + r"\.[0-9a-f]{8}(\.\d{6})?(\.part-[\w.-]+)?\.\w+$")
            for fname in os.listdir(path):
                if pat.fullmatch(fname):
                    os.unlink(os.path.join(path, fname))
