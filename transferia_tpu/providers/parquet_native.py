"""Native parquet row-group reader: column chunks -> Columns directly.

The snapshot north-star's host decode stage (reference methodology:
docs/benchmarks.md rows/sec on ClickBench `hits`) is bound by parquet
decode on a single core.  This reader pairs pyarrow's *metadata* (footer
parsing, row-group/chunk layout, schema) with the C++ chunk decoder
(native/parquetdec.cpp): pages go straight into the engine's columnar
layout — flat (data, offsets) buffers, or int32 codes + pool adopted as
DictEnc with no dictionary unification or index materialization.

The decode envelope: DataPage v1+v2; UNCOMPRESSED/SNAPPY/GZIP/ZSTD
codecs (GZIP and ZSTD ride dlopen'd system zlib/libzstd); PLAIN,
RLE_DICTIONARY, DELTA_BINARY_PACKED, DELTA_LENGTH_BYTE_ARRAY and
DELTA_BYTE_ARRAY encodings; BOOLEAN/INT32/INT64/FLOAT/DOUBLE/BYTE_ARRAY
physical types; flat schemas (max_def <= 1, no repetition).  Anything
outside falls back to arrow per column, so the reader is never less
capable than pyarrow.

All columns of a row group decode in ONE ctypes call
(pq_decode_rowgroup): the per-column Python + pyarrow-metadata overhead
was ~40% of decode wall on the wide ClickBench-shaped bench.  ctypes
releases the GIL for the call, so upload worker threads overlap decode
with sink pushes.
"""

from __future__ import annotations

import logging
import os
import threading
from typing import Optional

import numpy as np

from transferia_tpu.abstract.schema import CanonicalType, TableSchema
from transferia_tpu.columnar.batch import Column, DictEnc, DictPool
from transferia_tpu.runtime import knobs

logger = logging.getLogger(__name__)


# -- per-file footer/metadata + memmap memoization ---------------------------
#
# Multi-part loads open the SAME file once per part: sharding reads the
# footer to enumerate row groups, then every part re-runs
# `ParquetFile.__init__` (a full thrift footer parse — 3.9% of a
# CPU-host headline profile) and every NativeParquetReader re-creates
# the file memmap (1.6%).  Both are pure functions of (path, mtime_ns, size), so
# they memoize under that key; a rewritten file gets a fresh entry.
# Bounded FIFO; the lock guards the loader's concurrent part threads.

_FOOTER_CACHE: dict = {}     # (path, mtime_ns, size) -> FileMetaData
_MMAP_CACHE: dict = {}       # (path, mtime_ns, size) -> np.memmap
_FILE_CACHE_MAX = 32
_FILE_CACHE_LOCK = threading.Lock()


def _file_key(path: str) -> tuple:
    st = os.stat(path)
    return (os.path.abspath(path), st.st_mtime_ns, st.st_size)


def parquet_file_cached(path: str, read_dictionary=None):
    """A fresh pyarrow ParquetFile whose footer parses at most once per
    (path, mtime, size) — the FileMetaData is memoized and handed back
    to `ParquetFile(metadata=...)`, so each caller still gets its OWN
    reader object (pyarrow readers are not safe to share across part
    threads) without re-running the thrift parse per part.

    `read_dictionary` (a sequence of column names) makes the reader
    surface those columns as arrow DictionaryArrays instead of decoding
    dict pages to flat values — the arrow-path twin of the native
    decoder's DictEnc adoption (the importer then adopts the dictionary
    as a shared DictPool instead of re-encoding downstream)."""
    import pyarrow.parquet as pq

    kw = {}
    if read_dictionary:
        kw["read_dictionary"] = list(read_dictionary)
    key = _file_key(path)
    with _FILE_CACHE_LOCK:
        meta = _FOOTER_CACHE.get(key)
    if meta is not None:
        return pq.ParquetFile(path, metadata=meta, **kw)
    pf = pq.ParquetFile(path, **kw)
    with _FILE_CACHE_LOCK:
        while len(_FOOTER_CACHE) >= _FILE_CACHE_MAX:
            _FOOTER_CACHE.pop(next(iter(_FOOTER_CACHE)), None)
        _FOOTER_CACHE[key] = pf.metadata
    return pf


def parquet_metadata(path: str):
    """Memoized footer metadata only (sharding/row-count callers that
    never read pages skip constructing a reader entirely)."""
    key = _file_key(path)
    with _FILE_CACHE_LOCK:
        meta = _FOOTER_CACHE.get(key)
    if meta is not None:
        return meta
    return parquet_file_cached(path).metadata


def shared_memmap(path: str) -> np.ndarray:
    """One read-only memmap per (path, mtime, size), shared by every
    row-group reader of the file (readers only ever slice it)."""
    key = _file_key(path)
    with _FILE_CACHE_LOCK:
        mm = _MMAP_CACHE.get(key)
        if mm is not None:
            return mm
    mm = np.memmap(path, dtype=np.uint8, mode="r")
    with _FILE_CACHE_LOCK:
        hit = _MMAP_CACHE.get(key)
        if hit is not None:
            return hit
        while len(_MMAP_CACHE) >= _FILE_CACHE_MAX:
            _MMAP_CACHE.pop(next(iter(_MMAP_CACHE)), None)
        _MMAP_CACHE[key] = mm
    return mm


def reset_file_caches() -> None:
    with _FILE_CACHE_LOCK:
        _FOOTER_CACHE.clear()
        _MMAP_CACHE.clear()
        _PAGE_POOL_CACHE.clear()


# -- dict-page pool sharing --------------------------------------------------
#
# One decoded dict page -> one DictPool, shared by every reader of it.
# Two layers:
#  - identity: (path, mtime, size, column, dictionary_page_offset) — a
#    part re-decoding the SAME page (multi-part loads re-open each row
#    group's chunk once per part thread) reuses the pool with no digest;
#  - content (columnar/batch.intern_pool keyed by (path, column)): row
#    groups of one file usually carry byte-identical dict pages at
#    different offsets, so their pools converge on one object and the
#    pool-keyed memos (hexed HMAC pool, rowhash accumulators, device
#    digest matrices) amortize across the whole file, parts included.
_PAGE_POOL_CACHE: dict = {}
_PAGE_POOL_CACHE_MAX = 256

# copy-vs-view economics for the pool slice out of the cap-sized decode
# buffer: keeping a view is free NOW but pins the whole buffer (cap
# covers the code pages too) for as long as the pool lives — which,
# with pool sharing, is the whole transfer.  Keep the view only when
# the pinned remainder is small both relatively AND absolutely; the old
# `pool_bytes * 2 < nbytes` test alone kept views that pinned megabytes
# when the pool sat just under half the buffer.
_POOL_PIN_MAX_WASTE = 256 * 1024

# bench/diagnostic visibility: which columns fell out of the native
# envelope (and how often) — silent arrow fallbacks regress the headline
# without this.  Upload workers share a reader across threads, so the
# counter update takes a lock.
_fallback_columns: dict[str, int] = {}
_fallback_lock = threading.Lock()


def fallback_stats() -> dict[str, int]:
    with _fallback_lock:
        return dict(_fallback_columns)


def reset_fallback_stats() -> None:
    with _fallback_lock:
        _fallback_columns.clear()


# parquet CompressionCodec enum values (GZIP/ZSTD support is probed at
# runtime — they need the system zlib/libzstd)
_CODECS = {"UNCOMPRESSED": 0, "SNAPPY": 1, "GZIP": 2, "ZSTD": 6}
_FIXED_WIDTH = {"INT32": 4, "INT64": 8, "FLOAT": 4, "DOUBLE": 8}

# (physical width, output width, output view dtype) per canonical type.
# Narrow logical ints (int8/16) truncate DURING the native decode
# (little-endian low bytes == two's-complement truncation), so no numpy
# astype pass runs afterwards.
_VIEW_DTYPES = {
    CanonicalType.INT8: (4, 1, np.int8),
    CanonicalType.INT16: (4, 2, np.int16),
    CanonicalType.INT32: (4, 4, np.int32),
    CanonicalType.INT64: (8, 8, np.int64),
    CanonicalType.UINT8: (4, 1, np.uint8),
    CanonicalType.UINT16: (4, 2, np.uint16),
    CanonicalType.UINT32: (4, 4, np.uint32),
    CanonicalType.UINT64: (8, 8, np.uint64),
    CanonicalType.FLOAT: (4, 4, np.float32),
    CanonicalType.DOUBLE: (8, 8, np.float64),
    CanonicalType.DATE: (4, 4, np.int32),
    CanonicalType.DATETIME: (8, 8, np.int64),
    CanonicalType.TIMESTAMP: (8, 8, np.int64),
}

# task-array columns for pq_decode_rowgroup (native/parquetdec.cpp)
_T_OFF, _T_LEN, _T_CODEC, _T_KIND, _T_WIDTH, _T_NVAL, _T_MAXDEF = range(7)
_T_VALUES, _T_CAP, _T_OFFSETS, _T_CODES, _T_VALIDITY = range(7, 12)
_T_RESULT, _T_OUTKIND, _T_NEEDED, _T_NULLS = range(12, 16)
_T_FIELDS = 16

_E_GROW = -2


class NativeParquetReader:
    """Per-file reader; None from open() when the native lib is absent."""

    def __init__(self, path: str, pf, schema: TableSchema, cdll,
                 decode_threads: int = 1):
        self._pf = pf
        self._meta = pf.metadata
        self._schema = schema
        self._cdll = cdll
        self._decode_threads = max(1, int(decode_threads))
        self._mm = shared_memmap(path)
        self._path = path
        self._file_key = _file_key(path)
        self._fb_readers: dict[tuple, object] = {}
        # column index by name (flat schemas only — nested fall back)
        self._col_idx = {}
        for i in range(self._meta.num_columns):
            name = self._meta.row_group(0).column(i).path_in_schema
            self._col_idx[name] = i
        self._pq_schema = pf.schema
        # arrow logical types (timestamp units etc.)
        self._arrow_fields = {f.name: f for f in pf.schema_arrow}
        self._codec_ok_cache: dict[int, bool] = {}
        # (tasks template, specs, static fallback names) per row group
        self._task_cache: dict[int, tuple] = {}
        self._cache_lock = threading.Lock()

    @classmethod
    def open(cls, path: str, pf, schema: TableSchema,
             decode_threads: int = 1
             ) -> Optional["NativeParquetReader"]:
        from transferia_tpu.native import lib as native_lib

        if knobs.env_str("TRANSFERIA_TPU_NATIVE_PARQUET", "1") == "0":
            return None
        cdll = native_lib()
        if cdll is None:
            return None
        if pf.metadata.num_row_groups == 0:
            return None
        try:
            return cls(path, pf, schema, cdll, decode_threads)
        except (OSError, ValueError):
            return None

    def _codec_ok(self, codec: int) -> bool:
        ok = self._codec_ok_cache.get(codec)
        if ok is None:
            ok = bool(self._cdll.pq_codec_supported(codec))
            self._codec_ok_cache[codec] = ok
        return ok

    # -- row-group task preparation -----------------------------------------
    def _chunk_range(self, col) -> tuple[int, int]:
        start = col.data_page_offset
        if (col.dictionary_page_offset is not None
                and col.dictionary_page_offset >= 0):
            start = min(start, col.dictionary_page_offset)
        return start, col.total_compressed_size

    def _rg_tasks(self, g: int) -> tuple:
        with self._cache_lock:
            cached = self._task_cache.get(g)
        if cached is not None:
            return cached
        rg = self._meta.row_group(g)
        specs: list[tuple] = []
        static_fb: list[str] = []
        rows: list[list[int]] = []
        for cs in self._schema:
            idx = self._col_idx.get(cs.name)
            if idx is None:
                continue  # column absent from the file entirely
            col = rg.column(idx)
            codec = _CODECS.get(col.compression)
            sc = self._pq_schema.column(idx)
            kind = width = ow = None
            view_dt = None
            ok = (codec is not None and self._codec_ok(codec)
                  and sc.max_repetition_level == 0
                  and sc.max_definition_level <= 1)
            if ok:
                ptype = col.physical_type
                if ptype in _FIXED_WIDTH:
                    spec = _VIEW_DTYPES.get(cs.data_type)
                    if spec is None or spec[0] != _FIXED_WIDTH[ptype]:
                        ok = False
                    else:
                        kind, (width, ow, view_dt) = 0, spec
                elif (ptype == "BOOLEAN"
                      and cs.data_type == CanonicalType.BOOLEAN):
                    kind, width, ow, view_dt = 2, 1, 1, np.bool_
                elif (ptype == "BYTE_ARRAY"
                      and cs.data_type.is_variable_width):
                    kind, width, ow = 1, 0, 0
                else:
                    ok = False
            if ok:
                start, length = self._chunk_range(col)
                if start < 0 or start + length > len(self._mm):
                    ok = False
            if not ok:
                static_fb.append(cs.name)
                continue
            n = col.num_values
            max_def = sc.max_definition_level
            # field 8: data cap for byte arrays, output width for fixed
            cap = (max(col.total_uncompressed_size, 4096)
                   if kind == 1 else ow)
            rows.append([start, length, codec, kind, width, n, max_def,
                         0, cap, 0, 0, 0, 0, 0, 0, 0])
            dict_off = (col.dictionary_page_offset
                        if col.dictionary_page_offset is not None else -1)
            specs.append((cs, kind, ow, n, max_def, cap, view_dt,
                          dict_off))
        tasks = (np.array(rows, dtype=np.int64)
                 if rows else np.zeros((0, _T_FIELDS), dtype=np.int64))
        out = (tasks, specs, static_fb)
        with self._cache_lock:
            self._task_cache[g] = out
        return out

    # -- per-column post-processing -----------------------------------------
    def _finish_fixed(self, cs, vals: np.ndarray,
                      validity: Optional[np.ndarray]) -> Column:
        v = None
        if validity is not None:
            v = validity.astype(np.bool_)
        ct = cs.data_type
        f = self._arrow_fields.get(cs.name)
        if ct in (CanonicalType.DATETIME, CanonicalType.TIMESTAMP) \
                and f is not None:
            import pyarrow.types as pt

            unit = f.type.unit if pt.is_timestamp(f.type) else "us"
            vals = vals.astype(np.int64, copy=False)
            if ct == CanonicalType.DATETIME:
                div = {"s": 1, "ms": 1_000, "us": 1_000_000,
                       "ns": 1_000_000_000}[unit]
                vals = vals // div
            else:
                scale = {"s": 1_000_000, "ms": 1_000, "us": 1, "ns": 1}[unit]
                vals = (vals * scale if unit in ("s", "ms")
                        else vals // (1000 if unit == "ns" else 1))
        elif ct.np_dtype != vals.dtype:
            vals = vals.astype(ct.np_dtype)
        return Column(cs.name, ct, np.ascontiguousarray(vals), None, v)

    def _finish_bytearray(self, cs, rc: int, out_kind: int, n: int,
                          data: np.ndarray, offsets: np.ndarray,
                          codes: np.ndarray,
                          validity: Optional[np.ndarray],
                          dict_off: int = -1) -> Column:
        v = validity.astype(np.bool_) if validity is not None else None
        if out_kind == 1:
            # dict result: rc == n_pool; codes hold n_pool for nulls
            dpool, remap = self._adopt_dict_page(cs, rc, data, offsets,
                                                 dict_off)
            if remap is not None:
                # order-insensitive sharing: this page carries the
                # canonical pool's values in a different first-
                # occurrence order — rewrite the codes onto it
                codes = remap[codes]
            return Column(cs.name, cs.data_type, validity=v,
                          dict_enc=DictEnc(codes, pool=dpool))
        flat = data[:rc]
        if rc * 2 < data.nbytes:
            flat = flat.copy()
        return Column(cs.name, cs.data_type, flat, offsets, v)

    def _adopt_dict_page(self, cs, n_pool: int, data: np.ndarray,
                         offsets: np.ndarray, dict_off: int
                         ) -> tuple[DictPool, Optional[np.ndarray]]:
        """Decoded dict page -> (shared DictPool, optional code remap).

        Sharing layers (module cache comment): identity by page offset;
        then order-INSENSITIVE value matching against the column's
        canonical pool — parquet writers build each row group's
        dictionary in first-occurrence order, so pages across row
        groups usually carry the same value SET permuted; a remap table
        rewrites this page's codes onto the canonical pool (one
        O(values) lookup per page, O(rows) int32 gather) so the
        pool-keyed memos amortize file-wide; exact-content interning
        covers the first page / changed dictionaries."""
        from transferia_tpu.chaos.failpoints import failpoint
        from transferia_tpu.columnar.batch import intern_peek, intern_pool
        from transferia_tpu.stats import trace
        from transferia_tpu.stats.trace import TELEMETRY

        failpoint("decode.dict_adopt")
        intern_key = self._file_key + (cs.name,)
        page_key = None
        if dict_off >= 0:
            page_key = self._file_key + (cs.name, dict_off)
            with _FILE_CACHE_LOCK:
                hit = _PAGE_POOL_CACHE.get(page_key)
            if hit is not None:
                TELEMETRY.record_pool_share_hit()
                return hit
        pool_off = np.append(offsets[:n_pool + 1],
                             offsets[n_pool]).astype(np.int32)
        pool_bytes = int(offsets[n_pool])
        trace.instant("dict_adopt", col=cs.name, values=n_pool,
                      bytes=pool_bytes)
        canon = intern_peek(intern_key)
        if canon is not None:
            remap = _remap_codes(canon, data, offsets, n_pool)
            if remap is not None:
                TELEMETRY.record_pool_share_hit()
                if np.array_equal(remap,
                                  np.arange(n_pool + 1,
                                            dtype=np.int32)):
                    remap = None  # identical order: skip the gather
                out = (canon, remap)
                self._cache_page_pool(page_key, out)
                return out

        def finalize(pdata, poff):
            # the pool slice views the cap-sized decode buffer (cap
            # covers the code pages too): keeping the view pins the
            # whole buffer for the pool's lifetime, so copy out unless
            # the pinned remainder is small relatively AND absolutely
            waste = int(data.nbytes) - pool_bytes
            if pool_bytes * 2 < data.nbytes \
                    or waste > _POOL_PIN_MAX_WASTE:
                TELEMETRY.record_pool_buffer(copied=pool_bytes)
                return pdata.copy(), poff
            TELEMETRY.record_pool_buffer(pinned=waste)
            return pdata, poff

        dpool = intern_pool(intern_key, data[:pool_bytes], pool_off,
                            null_code=n_pool, finalize=finalize)
        out = (dpool, None)
        self._cache_page_pool(page_key, out)
        return out

    @staticmethod
    def _cache_page_pool(page_key, entry) -> None:
        if page_key is None:
            return
        with _FILE_CACHE_LOCK:
            while len(_PAGE_POOL_CACHE) >= _PAGE_POOL_CACHE_MAX:
                _PAGE_POOL_CACHE.pop(next(iter(_PAGE_POOL_CACHE)), None)
            _PAGE_POOL_CACHE[page_key] = entry

    def _retry_bytearray(self, g: int, cs, cap: int) -> Optional[Column]:
        """GROW retry: single-column decode with an enlarged data cap."""
        import ctypes

        idx = self._col_idx[cs.name]
        col = self._meta.row_group(g).column(idx)
        codec = _CODECS.get(col.compression)
        if codec is None:
            return None
        dict_off = (col.dictionary_page_offset
                    if col.dictionary_page_offset is not None else -1)
        sc = self._pq_schema.column(idx)
        max_def = sc.max_definition_level
        n = col.num_values
        start, length = self._chunk_range(col)
        chunk = np.ascontiguousarray(self._mm[start:start + length])
        # the legacy single-column ABI seeds validity all-defined itself
        validity = np.empty(n, dtype=np.uint8) if max_def else None
        offsets = np.empty(n + 1, dtype=np.int32)
        codes = np.empty(n, dtype=np.int32)
        for _attempt in range(4):
            data = np.empty(cap, dtype=np.uint8)
            kind = ctypes.c_int32(-1)
            needed = ctypes.c_int64(0)
            rc = self._cdll.pq_decode_bytearray(
                chunk, length, codec, n, max_def,
                data, cap, offsets, codes.ctypes.data,
                validity.ctypes.data if validity is not None else None,
                ctypes.byref(kind), ctypes.byref(needed))
            if rc == _E_GROW:
                cap = max(needed.value, cap * 2)
                continue
            if rc < 0:
                return None
            v = validity
            if v is not None and v.all():
                v = None
            return self._finish_bytearray(cs, rc, kind.value, n, data,
                                          offsets, codes, v, dict_off)
        return None

    def _decode_tasks(self, tasks: np.ndarray, n: int) -> None:
        """Run the native decoder over the task rows, column-parallel
        when decode_threads > 1.  Task rows are independent (each
        decodes one column chunk into buffers only it points at) and
        pq_decode_rowgroup releases the GIL, so K threads decode K
        columns genuinely in parallel.  K=1 is today's single batched
        call, byte for byte.

        Work is handed out one column at a time from a largest-
        compressed-chunk-first order (LPT balancing: one 20MB URL
        column must not serialize behind 60 already-claimed int8s);
        the per-call ctypes overhead is microseconds against multi-ms
        chunk decodes, so per-column granularity costs nothing."""
        k = min(self._decode_threads, n)
        if k <= 1:
            if n:
                self._cdll.pq_decode_rowgroup(self._mm, len(self._mm),
                                              tasks, n)
            return
        order = iter(np.argsort(-tasks[:, _T_LEN], kind="stable"))
        errors: list[BaseException] = []

        def run() -> None:
            try:
                while True:
                    # next() on a shared iterator is atomic under the GIL
                    i = next(order, None)
                    if i is None:
                        return
                    self._cdll.pq_decode_rowgroup(
                        self._mm, len(self._mm), tasks[i:i + 1], 1)
            except BaseException as e:  # ctypes arg errors: re-raise below
                errors.append(e)

        threads = [threading.Thread(target=run, name=f"pq-decode-{j}",
                                    daemon=True) for j in range(k - 1)]
        for t in threads:
            t.start()
        run()
        for t in threads:
            t.join()
        if errors:
            raise errors[0]

    # -- public --------------------------------------------------------------
    def read_row_group(self, g: int) -> dict[str, Column]:
        """All schema columns for one row group.

        Columns outside the native envelope (unsupported codec/encoding/
        type, nested, >2GiB flat) are filled through an arrow read of just
        those columns — the result is always complete."""
        from transferia_tpu.chaos.failpoints import failpoint

        failpoint("decode.native.rowgroup")
        template, specs, static_fb = self._rg_tasks(g)
        tasks = template.copy()
        holds: list[tuple] = []
        for i, (cs, kind, ow, n, max_def, cap, view_dt,
                _dict_off) in enumerate(specs):
            if kind == 1:
                data = np.empty(cap, dtype=np.uint8)
                offsets = np.empty(n + 1, dtype=np.int32)
                codes = np.empty(n, dtype=np.int32)
                tasks[i, _T_VALUES] = data.ctypes.data
                tasks[i, _T_OFFSETS] = offsets.ctypes.data
                tasks[i, _T_CODES] = codes.ctypes.data
                bufs = (data, offsets, codes)
            else:
                out = np.empty(n, dtype=view_dt)
                tasks[i, _T_VALUES] = out.ctypes.data
                bufs = (out,)
            if max_def:
                val = np.empty(n, dtype=np.uint8)
                tasks[i, _T_VALIDITY] = val.ctypes.data
            else:
                val = None
            holds.append((bufs, val))
        from transferia_tpu.stats import trace

        with trace.span("native_rowgroup_decode", group=g,
                        cols=len(specs)):
            self._decode_tasks(tasks, len(specs))
        cols: dict[str, Column] = {}
        fallback: list[str] = list(static_fb)
        for i, (cs, kind, ow, n, max_def, cap, view_dt,
                dict_off) in enumerate(specs):
            rc = int(tasks[i, _T_RESULT])
            nulls = int(tasks[i, _T_NULLS])
            bufs, val = holds[i]
            validity = val if (max_def and nulls > 0) else None
            try:
                if kind == 1:
                    if rc == _E_GROW:
                        c = self._retry_bytearray(
                            g, cs, max(int(tasks[i, _T_NEEDED]), cap * 2))
                    elif rc < 0:
                        c = None
                    else:
                        c = self._finish_bytearray(
                            cs, rc, int(tasks[i, _T_OUTKIND]), n,
                            bufs[0], bufs[1], bufs[2], validity,
                            dict_off)
                elif rc != n:
                    c = None
                elif kind == 2:
                    c = Column(cs.name, cs.data_type, bufs[0], None,
                               validity.astype(np.bool_)
                               if validity is not None else None)
                else:
                    c = self._finish_fixed(cs, bufs[0], validity)
            except Exception:  # corrupt chunk etc: arrow decides
                logger.debug("native decode failed for %s", cs.name,
                             exc_info=True)
                c = None
            if c is None:
                fallback.append(cs.name)
            else:
                cols[cs.name] = c
        if fallback:
            from transferia_tpu.columnar.batch import _arrow_to_column

            with _fallback_lock:
                for name in fallback:
                    _fallback_columns[name] = (
                        _fallback_columns.get(name, 0) + 1)

            by_name = {cs.name: cs for cs in self._schema}
            # dict pages of var-width fallback columns stay encoded:
            # the dict-preserving reader surfaces DictionaryArrays that
            # _arrow_to_column adopts as shared DictPools — the arrow
            # escape hatch no longer flattens what the rest of the
            # pipeline would immediately re-encode
            pf = self._fallback_reader(dict_encoded_columns(
                self._meta,
                [name for name in fallback
                 if by_name[name].data_type.is_variable_width]))
            tbl = pf.read_row_group(g, columns=fallback,
                                    use_threads=False)
            for name in fallback:
                arr = tbl.column(name).combine_chunks()
                cols[name] = _arrow_to_column(by_name[name], arr)
        return cols

    def _fallback_reader(self, dict_cols: tuple):
        """Memoized arrow reader for fallback reads; dict_cols surface
        as DictionaryArrays (empty tuple -> the plain shared reader)."""
        if not dict_cols:
            return self._pf
        with self._cache_lock:
            pf = self._fb_readers.get(dict_cols)
        if pf is None:
            pf = parquet_file_cached(self._path,
                                     read_dictionary=dict_cols)
            with self._cache_lock:
                pf = self._fb_readers.setdefault(dict_cols, pf)
        return pf


# order-insensitive code remap onto a canonical pool: the guard-chain
# and byte-exact verification live with the intern machinery in
# columnar/batch.py (shared with the arrow dictionary adoption path)
from transferia_tpu.columnar.batch import remap_codes_onto as _remap_codes


def dict_encoded_columns(meta, names) -> tuple:
    """The subset of `names` whose chunks carry a dictionary encoding
    (RLE/PLAIN_DICTIONARY) in EVERY row group — the columns worth
    reading with `read_dictionary`.  The all-groups quantifier matters:
    `read_dictionary` applies file-wide, and a writer whose dictionary
    page overflowed partway (dictionary_pagesize_limit) leaves later
    row groups PLAIN — forcing dictionary reads there would make arrow
    BUILD a dictionary for a high-cardinality column, a pure loss."""
    if meta.num_row_groups == 0:
        return ()
    rg0 = meta.row_group(0)
    by_name = {}
    for i in range(meta.num_columns):
        by_name[rg0.column(i).path_in_schema] = i
    out = []
    for name in names:
        idx = by_name.get(name)
        if idx is None:
            continue
        ok = True
        for g in range(meta.num_row_groups):
            encs = meta.row_group(g).column(idx).encodings
            if "RLE_DICTIONARY" not in encs \
                    and "PLAIN_DICTIONARY" not in encs:
                ok = False
                break
        if ok:
            out.append(name)
    return tuple(sorted(out))


def slice_columns(cols: dict[str, Column], lo: int,
                  hi: int) -> dict[str, Column]:
    """Row-range views over decoded columns (no gathers).

    Fixed-width slices are numpy views; var-width rebases offsets (small
    copy); dictionary columns slice codes and share the pool — which is
    what makes per-batch slicing of a decoded row group nearly free."""
    out = {}
    for name, c in cols.items():
        validity = c.validity[lo:hi] if c.validity is not None else None
        if c.is_lazy_dict:
            out[name] = Column(
                name, c.ctype, validity=validity,
                dict_enc=DictEnc(c.dict_enc.indices[lo:hi],
                                 pool=c.dict_enc.pool))
        elif c.offsets is not None:
            base = int(c.offsets[lo])
            if base == 0 and c.offsets.dtype == np.int32:
                # first batch of every group: offsets are already
                # zero-based — the view costs nothing, the astype copies
                off = c.offsets[lo:hi + 1]
            else:
                off = (c.offsets[lo:hi + 1] - base).astype(np.int32)
            out[name] = Column(name, c.ctype,
                               c.data[base:int(c.offsets[hi])], off,
                               validity)
        else:
            out[name] = Column(name, c.ctype, c.data[lo:hi], None,
                               validity)
    return out
