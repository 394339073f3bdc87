"""ColumnBatch: Arrow-style columnar block shared by host and device.

Design notes (TPU-first):
- Fixed-width canonical types map 1:1 to numpy dtypes (`CanonicalType.np_dtype`)
  so a column ships to the device with zero copies beyond the HBM transfer.
- Variable-width types (string/utf8/any/decimal) are a flat uint8 byte buffer
  plus (n_rows+1) int32 offsets — the layout Pallas string kernels consume.
- NULLs are a boolean validity array (True = valid), matching Arrow semantics.
- Row-count bucketing (`bucket_rows`) pads batches to power-of-two-ish sizes
  so XLA compiles once per (schema fingerprint, bucket) instead of once per
  batch — the shape-static analogue of the reference's schema-hash keyed
  transformer plan cache (pkg/transformer/transformation.go:47-60).

Reference parity: this replaces the []ChangeItem batch of
pkg/abstract/changeitem as the bulk currency; ChangeItems remain the row view.
"""

from __future__ import annotations

import json
import threading
from dataclasses import dataclass, field
from typing import Any, Iterable, Optional, Sequence

import numpy as np

from transferia_tpu.abstract.change_item import ChangeItem, OldKeys
from transferia_tpu.abstract.kinds import CODE_KINDS, KIND_CODES, Kind
from transferia_tpu.abstract.schema import (
    CanonicalType,
    ColSchema,
    TableID,
    TableSchema,
)
from transferia_tpu.runtime import lockwatch

_BUCKETS = (256, 1024, 4096, 16384, 65536, 262144, 1048576)
_INT32_MAX = 2**31 - 1


def _offsets_from_lengths(lengths) -> np.ndarray:
    """Build int32 offsets from per-row byte lengths, guarding overflow.

    Device kernels index with int32; a single batch's var-width column must
    stay under 2 GiB (the bufferer flushes far earlier) — fail loudly rather
    than let numpy wrap the cumsum.
    """
    off64 = np.zeros(len(lengths) + 1, dtype=np.int64)
    if len(lengths):
        np.cumsum(lengths, dtype=np.int64, out=off64[1:])
    if off64[-1] > _INT32_MAX:
        raise ValueError(
            f"variable-width column exceeds 2GiB in one batch "
            f"({int(off64[-1])} bytes); split the batch"
        )
    return off64.astype(np.int32)


def _gather_varwidth(data: np.ndarray, offsets: np.ndarray,
                     indices: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Gather var-width rows by index: C++ fast path, numpy fallback.

    Shared by Column.take and DictEnc.materialize (a dict materialization
    IS a gather of the pool by the code array).  The native form is two
    passes — lengths fold into offsets (gather_var_offsets), then one
    memcpy loop (gather_var_bytes) — replacing the numpy lens gather +
    int64 cumsum that profiled as ~5.5% of the snapshot wall."""
    from transferia_tpu.native import lib as _native_lib

    n = len(indices)
    cdll = _native_lib()
    if cdll is not None and n:
        idx = np.ascontiguousarray(indices, dtype=np.int64)
        # the C loops are unchecked: out-of-range / negative indices
        # must keep numpy's semantics (raise / wrap) instead of
        # reading stray memory — same guard as gather_fixed
        if int(idx.min()) < 0 or int(idx.max()) >= len(offsets) - 1:
            cdll = None
    if cdll is not None and n:
        src_off = np.ascontiguousarray(offsets, dtype=np.int32)
        out_offsets = np.empty(n + 1, dtype=np.int32)
        total = cdll.gather_var_offsets(src_off, idx, n, out_offsets)
        if total > _INT32_MAX:
            raise ValueError(
                f"variable-width column exceeds 2GiB in one batch "
                f"({int(total)} bytes); split the batch"
            )
        out = np.empty(int(total), dtype=np.uint8)
        if total:
            cdll.gather_var_bytes(np.ascontiguousarray(data), src_off,
                                  idx, n, out_offsets, out)
        return out, out_offsets
    lens = (offsets[1:] - offsets[:-1])[indices].astype(np.int64)
    new_offsets = _offsets_from_lengths(lens)  # guards the 2GiB limit
    total = int(new_offsets[-1])
    if cdll is not None and total:
        # prebuilt .so without the two-pass symbols: the one-pass gather
        # still beats the numpy scatter chain (indices validated above)
        out = np.empty(total, dtype=np.uint8)
        out_offsets = np.empty(n + 1, dtype=np.int32)
        cdll.gather_varwidth(
            np.ascontiguousarray(data),
            np.ascontiguousarray(offsets, dtype=np.int32),
            idx, n, out, out_offsets,
        )
        return out, out_offsets
    starts = offsets[:-1][indices].astype(np.int64)
    intra = np.arange(total, dtype=np.int64) - np.repeat(
        new_offsets[:-1].astype(np.int64), lens)
    src = np.repeat(starts, lens) + intra
    out = data[src] if total else np.zeros(0, dtype=np.uint8)
    return out, new_offsets


def _contiguous_span(indices) -> Optional[tuple[int, int]]:
    """[lo, hi) when indices is exactly lo, lo+1, ..., hi-1; else None.

    The O(n) monotonicity check only runs after the O(1) endpoints test
    matches, so random gathers pay two scalar reads."""
    n = len(indices)
    if n == 0 or not isinstance(indices, np.ndarray) \
            or indices.dtype.kind not in "iu":
        return None
    lo = int(indices[0])
    hi = int(indices[-1]) + 1
    if hi - lo != n or lo < 0:
        return None
    if n > 1 and not bool((np.diff(indices) == 1).all()):
        return None
    return lo, hi


def _gather_fixed(data: np.ndarray, indices: np.ndarray) -> np.ndarray:
    """Fixed-width gather: native width-specialized loop, numpy fallback."""
    from transferia_tpu.native import lib as _native_lib

    n = len(indices)
    cdll = _native_lib()
    width = data.dtype.itemsize
    if (cdll is None or n == 0
            or not data.flags.c_contiguous
            or not isinstance(indices, np.ndarray)
            or indices.dtype.kind not in "iu"):
        return data[indices]
    # the C loop is unchecked: out-of-range / negative indices must keep
    # numpy's semantics (raise / wrap) instead of reading stray memory
    if int(indices.min()) < 0 or int(indices.max()) >= len(data):
        return data[indices]
    out = np.empty(n, dtype=data.dtype)
    cdll.gather_fixed(
        data.view(np.uint8), np.ascontiguousarray(indices, dtype=np.int64),
        n, width, out.view(np.uint8))
    return out


def bucket_rows(n: int) -> int:
    """Smallest standard bucket >= n (caps XLA recompiles)."""
    for b in _BUCKETS:
        if n <= b:
            return b
    # beyond the largest bucket: round up to a multiple of it
    top = _BUCKETS[-1]
    return ((n + top - 1) // top) * top


class DictPool:
    """The value pool of a dictionary encoding, shareable across batches.

    values_data/values_offsets: the pool as flat uint8 bytes + (k+1) int32.
    null_code: index of the designated empty-bytes sentinel entry, if one
    was appended at adoption (nulls materialize as empty bytes — the
    canonical null representation of the flat path).
    memos: per-pool computation cache, e.g. the HMAC'd hex pool keyed by
    mask key — a pool shared by many batches is hashed once.
    """

    __slots__ = ("values_data", "values_offsets", "null_code", "_memos",
                 "_keepalive")

    def __init__(self, values_data: np.ndarray, values_offsets: np.ndarray,
                 null_code: Optional[int] = None, keepalive=None):
        self.values_data = values_data
        self.values_offsets = values_offsets
        self.null_code = null_code
        self._memos: dict = {}
        self._keepalive = keepalive  # pins adopted arrow buffers

    @property
    def n_values(self) -> int:
        return len(self.values_offsets) - 1

    def nbytes(self) -> int:
        return self.values_data.nbytes + self.values_offsets.nbytes

    def value_bytes(self, code: int) -> bytes:
        return bytes(self.values_data[
            self.values_offsets[code]:self.values_offsets[code + 1]])

    def memo_get(self, key):
        return self._memos.get(key)

    def memo_set(self, key, value) -> None:
        self._memos[key] = value


# Adopted arrow dictionaries keyed by buffer identity: batch slices of one
# row group share the same dict buffers, so they share one DictPool (and
# its memos).  Entries pin the arrow pool array, which is what makes the
# address a valid identity.  Bounded FIFO; lock guards the loader's
# concurrent part threads.
_POOL_CACHE: dict = {}
_POOL_CACHE_MAX = 64
_POOL_CACHE_LOCK = lockwatch.named_lock("pool.intern")

# Content-interned pools (intern_pool): every producer that re-creates a
# value pool with identical bytes — the native parquet reader decoding
# one file's per-row-group dict pages, the sample source re-emitting its
# preset pools per batch — converges on ONE DictPool object, so the
# pool-keyed memos (hexed HMAC pool, rowhash accumulators, device digest
# matrices, arrow wrapping) amortize across row groups AND parts.
_INTERN_CACHE: dict = {}   # key -> (content digest, DictPool)
_INTERN_CACHE_MAX = 128


def pool_sharing_enabled() -> bool:
    """TRANSFERIA_TPU_POOL_SHARING=0 disables content interning (each
    producer keeps private pools — the pre-sharing wire)."""
    from transferia_tpu.runtime import knobs

    return knobs.env_str("TRANSFERIA_TPU_POOL_SHARING", "1") != "0"


def _pool_digest(values_data: np.ndarray, values_offsets: np.ndarray,
                 null_code: Optional[int]) -> bytes:
    import hashlib

    h = hashlib.sha1()
    h.update(np.ascontiguousarray(values_data).tobytes())
    h.update(np.ascontiguousarray(values_offsets).tobytes())
    h.update(str(null_code).encode())
    return h.digest()


def intern_pool(key, values_data: np.ndarray, values_offsets: np.ndarray,
                null_code: Optional[int] = None, keepalive=None,
                finalize=None) -> "DictPool":
    """A canonical DictPool per (key, content).

    `key` scopes the cache entry (e.g. ``(path, column)`` for a parquet
    file, ``("sample", preset, column)`` for the generator); a candidate
    whose content digest matches the cached entry returns the CACHED
    pool — object identity is what downstream memo/fast paths key on.
    A changed digest under the same key replaces the entry (rewritten
    file, new dictionary page).

    `finalize(values_data, values_offsets)` runs only when the candidate
    is actually kept (a hit discards the candidate buffers), letting the
    caller defer a pin-avoiding copy of a decode-buffer view until it is
    known the buffers will live on.  Returns the buffers to store.
    """
    from transferia_tpu.stats.trace import TELEMETRY

    if not pool_sharing_enabled():
        if finalize is not None:
            values_data, values_offsets = finalize(values_data,
                                                   values_offsets)
        return DictPool(values_data, values_offsets, null_code, keepalive)
    digest = _pool_digest(values_data, values_offsets, null_code)
    if key is None:
        key = digest  # pure content identity (no producer scope)
    with _POOL_CACHE_LOCK:
        hit = _INTERN_CACHE.get(key)
        if hit is not None and hit[0] == digest:
            TELEMETRY.record_pool_share_hit()
            return hit[1]
    if finalize is not None:
        values_data, values_offsets = finalize(values_data, values_offsets)
    pool = DictPool(values_data, values_offsets, null_code, keepalive)
    with _POOL_CACHE_LOCK:
        hit = _INTERN_CACHE.get(key)
        if hit is not None and hit[0] == digest:
            TELEMETRY.record_pool_share_hit()
            return hit[1]
        while len(_INTERN_CACHE) >= _INTERN_CACHE_MAX:
            _INTERN_CACHE.pop(next(iter(_INTERN_CACHE)), None)
        _INTERN_CACHE[key] = (digest, pool)
    return pool


def intern_peek(key) -> Optional["DictPool"]:
    """The currently-interned pool under `key` (None when absent) —
    lets a producer try an order-insensitive CODE REMAP onto the
    canonical pool before falling back to exact-content interning."""
    with _POOL_CACHE_LOCK:
        hit = _INTERN_CACHE.get(key)
    return hit[1] if hit is not None else None


def reset_intern_cache() -> None:
    with _POOL_CACHE_LOCK:
        _INTERN_CACHE.clear()


def remap_codes_onto(canon: "DictPool", data: np.ndarray,
                     offsets: np.ndarray,
                     n_pool: int) -> Optional[np.ndarray]:
    """Remap table from a candidate pool's codes onto the canonical
    pool's, or None when the candidate carries a value outside the
    canonical pool (a genuinely new dictionary — the caller re-interns
    instead).  Shared by every order-insensitive pool-sharing consumer
    (parquet dict pages across row groups, arrow dictionaries across
    IPC/Flight streams) so the verification discipline can't fork.

    The canonical pool's bytes→code index memoizes on the pool; the
    null SENTINEL slot is excluded from it so a real empty-bytes value
    can never alias onto the sentinel (the mask plane empties the
    sentinel's hex slot — aliasing would silently unmask '' rows).
    The returned table has n_pool+1 entries: the candidate's own
    sentinel (code n_pool) maps to the canonical sentinel."""
    if canon.null_code is None:
        return None
    if n_pool == 0:
        return np.array([canon.null_code], dtype=np.int32)
    from transferia_tpu.ops.rowhash import pool_accumulators

    memo = canon.memo_get(("remap_keys",))
    if memo is None:
        a1, a2 = pool_accumulators(canon)
        ckeys = (a1.astype(np.uint64) << np.uint64(32)) \
            | a2.astype(np.uint64)
        # poison the sentinel's key: a real empty-bytes value must
        # never alias onto the null sentinel; the exact verification
        # below backstops any residual collision with the poison value
        ckeys = ckeys.copy()
        ckeys[canon.null_code] = np.uint64(0xFFFFFFFFFFFFFFFF)
        sorter = np.argsort(ckeys, kind="stable")
        memo = (ckeys[sorter], sorter)
        canon.memo_set(("remap_keys",), memo)
    sorted_keys, sorter = memo
    pool_bytes = int(offsets[n_pool])
    cand_pool = DictPool(data[:pool_bytes],
                         np.ascontiguousarray(offsets[:n_pool + 1],
                                              dtype=np.int32))
    p1, p2 = pool_accumulators(cand_pool)
    pkeys = (p1.astype(np.uint64) << np.uint64(32)) \
        | p2.astype(np.uint64)
    pos = np.searchsorted(sorted_keys, pkeys)
    cand = sorter[np.minimum(pos, canon.n_values - 1)]
    # the keys are 64-bit content hashes — verify the implied mapping
    # byte-EXACTLY (one native gather + two memcmps); any miss (value
    # outside the pool, or a hash collision) rejects the remap and the
    # caller re-interns, so a wrong code can never reach a consumer
    g_data, g_off = _gather_varwidth(
        canon.values_data,
        np.ascontiguousarray(canon.values_offsets, dtype=np.int32),
        cand.astype(np.int64))
    if not (np.array_equal(g_off, offsets[:n_pool + 1])
            and np.array_equal(g_data, data[:pool_bytes])):
        return None
    return np.append(cand.astype(np.int32),
                     np.int32(canon.null_code))


class DictEnc:
    """Dictionary encoding of a variable-width column (ClickHouse
    LowCardinality / Arrow DictionaryArray analogue).

    indices: (n,) int32 codes into the shared value pool.

    Columns carrying a DictEnc stay dictionary-encoded end-to-end: parquet
    dict pages adopt zero-copy on read (from_arrow), filters gather int32
    codes instead of strings, the HMAC mask hashes the pool once instead
    of every row, and to_arrow re-emits a DictionaryArray so parquet sinks
    write dict pages back.  Flat (data, offsets) materialize lazily the
    first time a consumer asks — correctness never depends on a consumer
    knowing about the encoding.
    """

    __slots__ = ("indices", "pool")

    def __init__(self, indices: np.ndarray,
                 values_data: Optional[np.ndarray] = None,
                 values_offsets: Optional[np.ndarray] = None,
                 pool: Optional[DictPool] = None):
        self.indices = indices
        self.pool = pool if pool is not None else DictPool(
            values_data, values_offsets)

    # -- pool passthroughs ---------------------------------------------------
    @property
    def values_data(self) -> np.ndarray:
        return self.pool.values_data

    @property
    def values_offsets(self) -> np.ndarray:
        return self.pool.values_offsets

    @property
    def n_values(self) -> int:
        return self.pool.n_values

    def value_bytes(self, code: int) -> bytes:
        return self.pool.value_bytes(code)

    def nbytes(self) -> int:
        return self.indices.nbytes + self.pool.nbytes()

    def materialize(self) -> tuple[np.ndarray, np.ndarray]:
        """Flatten to (data, offsets): a gather of the pool by codes."""
        return _gather_varwidth(self.pool.values_data,
                                self.pool.values_offsets,
                                self.indices.astype(np.int64))


class Column:
    """One column of a batch.

    data: fixed-width -> (n,) array of ctype.np_dtype
          variable-width -> (total_bytes,) uint8 buffer
    offsets: (n+1,) int32 — only for variable-width columns
    validity: (n,) bool (True = present) or None meaning all-valid
    dict_enc: optional dictionary encoding (var-width only); when set with
          data=None the flat buffers materialize lazily on first access
    """

    __slots__ = ("name", "ctype", "_data", "_offsets", "validity",
                 "dict_enc", "memo")

    def __init__(self, name: str, ctype: CanonicalType,
                 data: Optional[np.ndarray] = None,
                 offsets: Optional[np.ndarray] = None,
                 validity: Optional[np.ndarray] = None,
                 dict_enc: Optional[DictEnc] = None):
        self.name = name
        self.ctype = ctype
        self._data = data
        self._offsets = offsets
        self.validity = validity
        self.dict_enc = dict_enc
        # a derived form of this column's values, kept by who derived it
        # (predicate/exact.py: a DECIMAL column's integers at its scale)
        self.memo = None
        if ctype.is_variable_width:
            if offsets is None and dict_enc is None:
                raise ValueError(
                    f"column {name}: var-width requires offsets")
        elif data is None:
            raise ValueError(f"column {name}: fixed-width requires data")

    def _materialize(self) -> None:
        if self._data is None:
            # counted: every flatten of a dict column is a defeat of the
            # code-native pipeline — the dict_flat_materializations /
            # lazy_dict_preserved pair makes regressions visible
            from transferia_tpu.stats.trace import TELEMETRY

            TELEMETRY.record_dict_materialize()
            self._data, self._offsets = self.dict_enc.materialize()

    @property
    def data(self) -> np.ndarray:
        if self._data is None:
            self._materialize()
        return self._data

    @data.setter
    def data(self, v: np.ndarray) -> None:
        self._data = v

    @property
    def offsets(self) -> Optional[np.ndarray]:
        if self._offsets is None and self.dict_enc is not None:
            self._materialize()
        return self._offsets

    @offsets.setter
    def offsets(self, v: Optional[np.ndarray]) -> None:
        self._offsets = v

    @property
    def is_lazy_dict(self) -> bool:
        """True while dictionary-encoded with no flat copy materialized —
        the state dict-aware fast paths (mask, to_arrow, take) look for."""
        return self.dict_enc is not None and self._data is None

    @property
    def n_rows(self) -> int:
        if self.dict_enc is not None and self._offsets is None:
            return len(self.dict_enc.indices)
        if self._offsets is not None:
            return len(self._offsets) - 1
        return len(self._data)

    def nbytes(self) -> int:
        if self.is_lazy_dict:
            n = self.dict_enc.nbytes()
        else:
            n = self._data.nbytes
            if self._offsets is not None:
                n += self._offsets.nbytes
        if self.validity is not None:
            n += self.validity.nbytes
        return n

    def is_valid(self, i: int) -> bool:
        return self.validity is None or bool(self.validity[i])

    # -- row access ---------------------------------------------------------
    def value(self, i: int) -> Any:
        """Python value at row i (None when invalid)."""
        if not self.is_valid(i):
            return None
        if self.is_lazy_dict:
            raw = self.dict_enc.value_bytes(int(self.dict_enc.indices[i]))
            return _decode_varwidth(self.ctype, raw)
        if self.offsets is not None:
            raw = bytes(self.data[self.offsets[i]:self.offsets[i + 1]])
            return _decode_varwidth(self.ctype, raw)
        v = self.data[i]
        if self.ctype == CanonicalType.BOOLEAN:
            return bool(v)
        if self.ctype.is_integer or self.ctype in (
            CanonicalType.DATE, CanonicalType.DATETIME,
            CanonicalType.TIMESTAMP, CanonicalType.INTERVAL,
        ):
            return int(v)
        return float(v)

    def to_pylist(self) -> list[Any]:
        return [self.value(i) for i in range(self.n_rows)]

    def renamed(self, name: str) -> "Column":
        """Copy under a new name (laziness and buffers preserved)."""
        return Column(name, self.ctype, self._data, self._offsets,
                      self.validity, self.dict_enc)

    # -- functional ops -----------------------------------------------------
    def take(self, indices: np.ndarray) -> "Column":
        """Gather rows (host-side; device path uses ops.strings.take_bytes).

        Fast paths: a contiguous ascending index range (what slice() and
        prefix/suffix filters produce) returns buffer VIEWS — no copy at
        all; non-contiguous fixed-width gathers route through the native
        width-specialized hostops loop when the library is present."""
        span = _contiguous_span(indices)
        if span is not None and span[1] <= self.n_rows:
            # (out-of-range spans fall through so the gather raises the
            # same IndexError numpy always did instead of clamping)
            return self._take_contiguous(*span)
        validity = (_gather_fixed(self.validity, indices)
                    if self.validity is not None else None)
        if self.is_lazy_dict:
            # dictionary stays shared; only the int32 codes gather —
            # through the native width-specialized loop, so a filter on
            # a DictEnc column never materializes the pool and never
            # pays numpy's generic fancy-indexing path
            enc = self.dict_enc
            return Column(
                self.name, self.ctype, validity=validity,
                dict_enc=DictEnc(_gather_fixed(enc.indices, indices),
                                 pool=enc.pool))
        if self.offsets is None:
            return Column(self.name, self.ctype,
                          _gather_fixed(self.data, indices), None, validity)
        out, new_offsets = _gather_varwidth(
            self.data, self.offsets,
            np.ascontiguousarray(indices, dtype=np.int64))
        return Column(self.name, self.ctype, out, new_offsets, validity)

    def _take_contiguous(self, lo: int, hi: int) -> "Column":
        """take() of [lo, hi) as views over the existing buffers."""
        validity = self.validity[lo:hi] if self.validity is not None else None
        if self.is_lazy_dict:
            enc = self.dict_enc
            return Column(
                self.name, self.ctype, validity=validity,
                dict_enc=DictEnc(enc.indices[lo:hi], pool=enc.pool))
        if self.offsets is None:
            return Column(self.name, self.ctype, self.data[lo:hi], None,
                          validity)
        off = self.offsets[lo:hi + 1]
        if off[0] == 0:
            # prefix range: offsets AND data are pure views
            return Column(self.name, self.ctype,
                          self.data[:off[-1]] if len(off) else self.data,
                          off, validity)
        # mid-range: data stays a view; only the small offsets rebase
        return Column(self.name, self.ctype,
                      self.data[off[0]:off[-1]],
                      off - off[0], validity)

    def filter(self, mask: np.ndarray) -> "Column":
        return self.take(np.nonzero(mask)[0])

    @staticmethod
    def from_pylist(name: str, ctype: CanonicalType,
                    values: Sequence[Any]) -> "Column":
        n = len(values)
        validity = np.fromiter(
            (v is not None for v in values), dtype=np.bool_, count=n
        )
        all_valid = bool(validity.all()) if n else True
        if ctype.is_variable_width:
            bufs = [
                _encode_varwidth(ctype, v) if v is not None else b""
                for v in values
            ]
            offsets = _offsets_from_lengths([len(b) for b in bufs])
            data = np.frombuffer(b"".join(bufs), dtype=np.uint8).copy() \
                if bufs else np.zeros(0, dtype=np.uint8)
            return Column(name, ctype, data, offsets,
                          None if all_valid else validity)
        dt = ctype.np_dtype
        data = np.zeros(n, dtype=dt)
        for i, v in enumerate(values):
            if v is not None:
                data[i] = v
        return Column(name, ctype, data, None, None if all_valid else validity)


def _encode_varwidth(ctype: CanonicalType, v: Any) -> bytes:
    if ctype == CanonicalType.STRING:
        if isinstance(v, bytes):
            return v
        return str(v).encode()
    if ctype in (CanonicalType.UTF8, CanonicalType.DECIMAL):
        return v.encode() if isinstance(v, str) else str(v).encode()
    # ANY: canonical JSON bytes
    if isinstance(v, bytes):
        return v
    return json.dumps(v, separators=(",", ":"), default=str).encode()


def _decode_varwidth(ctype: CanonicalType, raw: bytes) -> Any:
    if ctype == CanonicalType.STRING:
        return raw
    if ctype in (CanonicalType.UTF8, CanonicalType.DECIMAL):
        return raw.decode("utf-8", errors="replace")
    try:
        return json.loads(raw) if raw else None
    except (ValueError, UnicodeDecodeError):
        return raw


class ColumnBatch:
    """A columnar block of rows for one table.

    kinds is None for pure-insert (snapshot) blocks; otherwise an int8 array
    of KIND_CODES for mixed CDC blocks.  lsns/commit_times are optional
    per-row metadata carried through the pipeline for checkpointing.
    old_keys/txn_ids are host-side per-row sidecars (never shipped to the
    device) preserving CDC row identity for updates/deletes across the pivot.
    """

    __slots__ = ("table_id", "schema", "columns", "kinds", "lsns",
                 "commit_times", "part_id", "read_bytes", "old_keys",
                 "txn_ids")

    def __init__(self, table_id: TableID, schema: TableSchema,
                 columns: dict[str, Column],
                 kinds: Optional[np.ndarray] = None,
                 lsns: Optional[np.ndarray] = None,
                 commit_times: Optional[np.ndarray] = None,
                 part_id: str = "", read_bytes: int = 0,
                 old_keys: Optional[list[OldKeys]] = None,
                 txn_ids: Optional[list[str]] = None):
        self.table_id = table_id
        self.schema = schema
        self.columns = columns
        self.kinds = kinds
        self.lsns = lsns
        self.commit_times = commit_times
        self.part_id = part_id
        self.read_bytes = read_bytes
        self.old_keys = old_keys
        self.txn_ids = txn_ids
        self._check()

    def _check(self):
        n = self.n_rows
        for c in self.columns.values():
            if c.n_rows != n:
                raise ValueError(
                    f"ragged batch: column {c.name} has {c.n_rows} rows, "
                    f"expected {n}"
                )

    @property
    def n_rows(self) -> int:
        if not self.columns:
            return 0 if self.kinds is None else len(self.kinds)
        return next(iter(self.columns.values())).n_rows

    def __len__(self) -> int:
        return self.n_rows

    def nbytes(self) -> int:
        return sum(c.nbytes() for c in self.columns.values())

    def kind_at(self, i: int) -> Kind:
        if self.kinds is None:
            return Kind.INSERT
        return CODE_KINDS[int(self.kinds[i])]

    def column(self, name: str) -> Column:
        return self.columns[name]

    # -- constructors -------------------------------------------------------
    @staticmethod
    def from_pydict(table_id: TableID, schema: TableSchema,
                    data: dict[str, Sequence[Any]], **kw) -> "ColumnBatch":
        cols = {}
        for cs in schema:
            if cs.name in data:
                cols[cs.name] = Column.from_pylist(
                    cs.name, cs.data_type, data[cs.name]
                )
        return ColumnBatch(table_id, schema, cols, **kw)

    @staticmethod
    def from_rows(items: Sequence[ChangeItem]) -> "ColumnBatch":
        """Pivot a uniform-table row batch into a columnar block.

        All items must share table_id and table_schema; mixed kinds are
        captured in the kinds array.  This is the host-side pivot the
        BASELINE.json north star describes (ChangeItem rows -> column
        buffers).
        """
        from transferia_tpu.stats import trace

        sp = trace.span("pivot")
        if sp:
            sp.add(rows=len(items), direction="rows_to_columns")
        with sp:
            return ColumnBatch._from_rows_impl(items)

    @staticmethod
    def _from_rows_impl(items: Sequence[ChangeItem]) -> "ColumnBatch":
        if not items:
            raise ValueError("from_rows: empty batch")
        first = items[0]
        if first.table_schema is None:
            raise ValueError("from_rows: items must carry table_schema")
        schema = first.table_schema
        tid = first.table_id
        n = len(items)
        per_col: dict[str, list[Any]] = {c.name: [None] * n for c in schema}
        kinds = np.zeros(n, dtype=np.int8)
        lsns = np.zeros(n, dtype=np.int64)
        commit_times = np.zeros(n, dtype=np.int64)
        mixed = False
        old_keys: Optional[list[OldKeys]] = None
        txn_ids: Optional[list[str]] = None
        for i, it in enumerate(items):
            if it.table_id != tid:
                raise ValueError("from_rows: mixed tables in batch")
            if it.table_schema is not schema and it.table_schema != schema:
                raise ValueError(
                    "from_rows: mixed table schemas in batch (schema changed "
                    "mid-stream?) — split the batch on schema boundaries"
                )
            code = KIND_CODES.get(it.kind)
            if code is None:
                raise ValueError(f"from_rows: non-row kind {it.kind}")
            kinds[i] = code
            mixed = mixed or code != 0
            lsns[i] = it.lsn
            commit_times[i] = it.commit_time_ns
            if it.old_keys.key_names:
                if old_keys is None:
                    old_keys = [OldKeys()] * n
                old_keys[i] = it.old_keys
            if it.txn_id:
                if txn_ids is None:
                    txn_ids = [""] * n
                txn_ids[i] = it.txn_id
            for name, value in zip(it.column_names, it.column_values):
                if name in per_col:
                    per_col[name][i] = value
        cols = {
            c.name: Column.from_pylist(c.name, c.data_type, per_col[c.name])
            for c in schema
        }
        return ColumnBatch(
            tid, schema, cols,
            kinds=kinds if mixed else None,
            lsns=lsns if lsns.any() else None,
            commit_times=commit_times if commit_times.any() else None,
            part_id=first.part_id,
            read_bytes=sum(it.size_bytes for it in items),
            old_keys=old_keys,
            txn_ids=txn_ids,
        )

    # -- row view -----------------------------------------------------------
    def to_rows(self) -> list[ChangeItem]:
        """Unpivot to ChangeItems (row-oriented edges only)."""
        from transferia_tpu.stats import trace

        sp = trace.span("pivot")
        if sp:
            sp.add(rows=self.n_rows, direction="columns_to_rows")
        with sp:
            return self._to_rows_impl()

    def _to_rows_impl(self) -> list[ChangeItem]:
        names = tuple(self.columns.keys())
        cols = list(self.columns.values())
        out = []
        for i in range(self.n_rows):
            out.append(ChangeItem(
                kind=self.kind_at(i),
                schema=self.table_id.namespace,
                table=self.table_id.name,
                column_names=names,
                column_values=tuple(c.value(i) for c in cols),
                table_schema=self.schema,
                lsn=int(self.lsns[i]) if self.lsns is not None else 0,
                commit_time_ns=int(self.commit_times[i])
                if self.commit_times is not None else 0,
                part_id=self.part_id,
                old_keys=self.old_keys[i] if self.old_keys is not None
                else OldKeys(),
                txn_id=self.txn_ids[i] if self.txn_ids is not None else "",
            ))
        return out

    def to_pydict(self) -> dict[str, list[Any]]:
        return {name: c.to_pylist() for name, c in self.columns.items()}

    # -- functional ops -----------------------------------------------------
    def with_columns(self, columns: dict[str, Column],
                     schema: Optional[TableSchema] = None) -> "ColumnBatch":
        return ColumnBatch(
            self.table_id, schema or self.schema, columns,
            kinds=self.kinds, lsns=self.lsns, commit_times=self.commit_times,
            part_id=self.part_id, read_bytes=self.read_bytes,
            old_keys=self.old_keys, txn_ids=self.txn_ids,
        )

    def rename_table(self, table_id: TableID) -> "ColumnBatch":
        return ColumnBatch(
            table_id, self.schema, self.columns,
            kinds=self.kinds, lsns=self.lsns, commit_times=self.commit_times,
            part_id=self.part_id, read_bytes=self.read_bytes,
            old_keys=self.old_keys, txn_ids=self.txn_ids,
        )

    def project(self, names: Sequence[str]) -> "ColumnBatch":
        cols = {n: self.columns[n] for n in names if n in self.columns}
        return self.with_columns(cols, self.schema.project(list(cols)))

    def filter(self, mask: np.ndarray) -> "ColumnBatch":
        idx = np.nonzero(np.asarray(mask))[0]
        return self.take(idx)

    def take(self, indices: np.ndarray) -> "ColumnBatch":
        cols = {n: c.take(indices) for n, c in self.columns.items()}
        return ColumnBatch(
            self.table_id, self.schema, cols,
            kinds=self.kinds[indices] if self.kinds is not None else None,
            lsns=self.lsns[indices] if self.lsns is not None else None,
            commit_times=self.commit_times[indices]
            if self.commit_times is not None else None,
            part_id=self.part_id, read_bytes=self.read_bytes,
            old_keys=[self.old_keys[int(i)] for i in indices]
            if self.old_keys is not None else None,
            txn_ids=[self.txn_ids[int(i)] for i in indices]
            if self.txn_ids is not None else None,
        )

    def slice(self, start: int, stop: int) -> "ColumnBatch":
        return self.take(np.arange(start, min(stop, self.n_rows)))

    @staticmethod
    def concat(batches: Sequence["ColumnBatch"]) -> "ColumnBatch":
        if not batches:
            raise ValueError("concat: empty")
        if len(batches) == 1:
            return batches[0]
        first = batches[0]
        cols = {}
        for name, c0 in first.columns.items():
            parts = [b.columns[name] for b in batches]
            validity = None
            if any(p.validity is not None for p in parts):
                validity = np.concatenate([
                    p.validity if p.validity is not None
                    else np.ones(p.n_rows, dtype=np.bool_)
                    for p in parts
                ])
            if (c0.is_lazy_dict and all(p.is_lazy_dict for p in parts)
                    and all(p.dict_enc.pool is c0.dict_enc.pool
                            for p in parts)):
                # bufferer flushes concat batch slices of one row group:
                # they share one DictPool, so the concat is a pure int32
                # code concat and the column stays encoded end-to-end
                # (touching .offsets below would flatten every part)
                from transferia_tpu.stats.trace import TELEMETRY

                TELEMETRY.record_dict_preserved()
                cols[name] = Column(
                    name, c0.ctype, validity=validity,
                    dict_enc=DictEnc(
                        np.concatenate([p.dict_enc.indices
                                        for p in parts]),
                        pool=c0.dict_enc.pool))
            elif c0.offsets is not None:
                data = np.concatenate([p.data for p in parts])
                lens = np.concatenate([
                    p.offsets[1:] - p.offsets[:-1] for p in parts
                ])
                offsets = _offsets_from_lengths(lens)
                cols[name] = Column(name, c0.ctype, data, offsets, validity)
            else:
                cols[name] = Column(
                    name, c0.ctype,
                    np.concatenate([p.data for p in parts]), None, validity,
                )
        def cat(attr, fill_dtype):
            arrs = [getattr(b, attr) for b in batches]
            if all(a is None for a in arrs):
                return None
            return np.concatenate([
                a if a is not None else np.zeros(b.n_rows, dtype=fill_dtype)
                for a, b in zip(arrs, batches)
            ])
        def cat_list(attr, fill):
            vals = [getattr(b, attr) for b in batches]
            if all(v is None for v in vals):
                return None
            out = []
            for v, b in zip(vals, batches):
                out.extend(v if v is not None else [fill] * b.n_rows)
            return out

        return ColumnBatch(
            first.table_id, first.schema, cols,
            kinds=cat("kinds", np.int8),
            lsns=cat("lsns", np.int64),
            commit_times=cat("commit_times", np.int64),
            part_id=first.part_id,
            read_bytes=sum(b.read_bytes for b in batches),
            old_keys=cat_list("old_keys", OldKeys()),
            txn_ids=cat_list("txn_ids", ""),
        )

    # -- arrow interop ------------------------------------------------------
    def to_arrow(self):
        """Convert to a pyarrow.RecordBatch (for parquet sinks etc.)."""
        import pyarrow as pa

        arrays, fields = [], []
        for cs in self.schema:
            c = self.columns.get(cs.name)
            if c is None:
                continue
            pa_type = _ARROW_TYPES[cs.data_type]
            if c.is_lazy_dict:
                # dictionary-encoded end-to-end: parquet sinks write dict
                # pages straight from the pool, no flat materialization;
                # the arrow pool array memoizes on the shared DictPool so
                # batch slices of one row group serialize it once
                from transferia_tpu.stats.trace import TELEMETRY

                TELEMETRY.record_dict_preserved()
                enc = c.dict_enc
                memo_key = ("arrow_pool", str(pa_type))
                pool = enc.pool.memo_get(memo_key)
                if pool is None:
                    pool = pa.Array.from_buffers(
                        pa_type, enc.n_values,
                        [None,
                         pa.py_buffer(
                             enc.values_offsets.astype(np.int32)
                             .tobytes()),
                         pa.py_buffer(enc.values_data.tobytes())])
                    enc.pool.memo_set(memo_key, pool)
                mask = (~c.validity) if c.validity is not None else None
                idx = pa.array(enc.indices, type=pa.int32(), mask=mask)
                arrays.append(pa.DictionaryArray.from_arrays(idx, pool))
                fields.append(pa.field(
                    cs.name, pa.dictionary(pa.int32(), pa_type),
                    nullable=not cs.required))
                continue
            if c.offsets is not None:
                buf_data = pa.py_buffer(c.data.tobytes())
                buf_off = pa.py_buffer(c.offsets.astype(np.int32).tobytes())
                mask_buf = _arrow_validity(c.validity, c.n_rows)
                arr = pa.Array.from_buffers(
                    pa_type, c.n_rows, [mask_buf, buf_off, buf_data]
                )
            else:
                arr = pa.array(c.data, type=pa_type,
                               mask=(~c.validity) if c.validity is not None else None)
            arrays.append(arr)
            fields.append(pa.field(cs.name, pa_type, nullable=not cs.required))
        return pa.RecordBatch.from_arrays(arrays, schema=pa.schema(fields))

    @staticmethod
    def from_arrow(rb, table_id: TableID,
                   schema: Optional[TableSchema] = None) -> "ColumnBatch":
        """Zero-ish-copy import from a pyarrow RecordBatch.

        Parquet/Arrow sources land here directly — no row pivot, per the
        north star ("never re-row the data between source and sink").
        """
        import pyarrow as pa
        import pyarrow.compute as pc

        if schema is None:
            schema = arrow_to_table_schema(rb.schema)
        cols: dict[str, Column] = {}
        for cs in schema:
            idx = rb.schema.get_field_index(cs.name)
            if idx < 0:
                continue
            arr = rb.column(idx)
            if isinstance(arr, pa.ChunkedArray):
                arr = arr.combine_chunks()
            cols[cs.name] = _arrow_to_column(cs, arr)
        return ColumnBatch(table_id, schema, cols)


_ARROW_TYPES: dict[CanonicalType, Any] = {}


def _init_arrow_types():
    import pyarrow as pa

    _ARROW_TYPES.update({
        CanonicalType.INT8: pa.int8(),
        CanonicalType.INT16: pa.int16(),
        CanonicalType.INT32: pa.int32(),
        CanonicalType.INT64: pa.int64(),
        CanonicalType.UINT8: pa.uint8(),
        CanonicalType.UINT16: pa.uint16(),
        CanonicalType.UINT32: pa.uint32(),
        CanonicalType.UINT64: pa.uint64(),
        CanonicalType.FLOAT: pa.float32(),
        CanonicalType.DOUBLE: pa.float64(),
        CanonicalType.BOOLEAN: pa.bool_(),
        CanonicalType.DATE: pa.date32(),
        CanonicalType.DATETIME: pa.timestamp("s"),
        CanonicalType.TIMESTAMP: pa.timestamp("us"),
        CanonicalType.INTERVAL: pa.duration("us"),
        CanonicalType.STRING: pa.binary(),
        CanonicalType.UTF8: pa.string(),
        CanonicalType.ANY: pa.string(),
        CanonicalType.DECIMAL: pa.string(),
    })


try:  # pyarrow is present in the baked image; soft-fail for minimal envs
    _init_arrow_types()
except ImportError:  # pragma: no cover
    pass


def _arrow_validity(validity: Optional[np.ndarray], n: int):
    import pyarrow as pa

    if validity is None:
        return None
    bits = np.packbits(validity, bitorder="little")
    return pa.py_buffer(bits.tobytes())


def _adopt_string_buffers(arr) -> tuple[np.ndarray, np.ndarray]:
    """Zero-ish-copy adoption of a pyarrow string/binary array's buffers."""
    bufs = arr.buffers()
    off = np.frombuffer(bufs[1], dtype=np.int32,
                        count=len(arr) + 1 + arr.offset)
    data = np.frombuffer(bufs[2], dtype=np.uint8) if bufs[2] is not None \
        else np.zeros(0, dtype=np.uint8)
    if arr.offset:
        off = off[arr.offset:]
    # a slice of a longer array (pyarrow's to_batches) shares its
    # buffers: the bytes are those its own offsets span, no more - a
    # concat of two such columns reads data by length
    if off[0] != 0 or off[-1] != len(data):
        data = data[off[0]:off[-1]]
        off = off - off[0]
    return np.ascontiguousarray(data), np.ascontiguousarray(off)


def _adopt_dict_pool(pool_arr, vt, pt, pa,
                     scope: Optional[tuple] = None,
                     ) -> tuple[DictPool, Optional[np.ndarray]]:
    """Adopt an arrow dictionary as a shared DictPool.

    Keyed by buffer identity: all batch slices of one row group reference
    the same dictionary buffers, so they get one DictPool object — and one
    set of memos (the HMAC mask hashes a shared pool exactly once).  The
    cache entry pins the arrow array, keeping the address a valid key.
    An empty-bytes sentinel entry is appended for null rows (null_code).

    When `scope` is given and pool sharing is on, a pool that carries the
    same VALUE SET as the scope's canonical pool in a different order is
    not re-interned: `remap_codes_onto` prices the permutation and the
    caller rewrites codes through the returned table — order-insensitive
    convergence, mirroring `_adopt_dict_page` on the parquet path.
    Returns (pool, remap) where remap is None when codes pass through.
    """
    # key on the ORIGINAL array's buffers: casting large_string allocates
    # fresh buffers each call, which would make the key never repeat
    orig = pool_arr
    bufs = orig.buffers()
    key = (
        bufs[2].address if bufs[2] is not None else 0,
        bufs[1].address if bufs[1] is not None else 0,
        len(orig), orig.offset, str(orig.type),
    )
    with _POOL_CACHE_LOCK:
        hit = _POOL_CACHE.get(key)
        if hit is not None:
            return hit[0], hit[2]
    if pt.is_large_string(vt) or pt.is_large_binary(vt):
        pool_arr = pool_arr.cast(
            pa.string() if pt.is_large_string(vt) else pa.binary())
    pool_data, pool_off = _adopt_string_buffers(pool_arr)
    # append the null sentinel (empty bytes) at index n_values
    pool_off = np.append(pool_off, pool_off[-1]).astype(np.int32)
    dpool, remap = None, None
    if scope is not None and pool_sharing_enabled():
        canon = intern_peek(scope)
        if canon is not None:
            remap = remap_codes_onto(canon, pool_data, pool_off,
                                     len(pool_arr))
            if remap is not None:
                from transferia_tpu.stats.trace import TELEMETRY

                TELEMETRY.record_pool_share_hit()
                if np.array_equal(remap,
                                  np.arange(len(pool_arr) + 1,
                                            dtype=np.int32)):
                    remap = None  # same order: codes pass through
                dpool = canon
    if dpool is None:
        # content interning: arrow dictionaries re-read per row group
        # carry identical bytes in fresh buffers — converge them on one
        # DictPool so memos amortize across row groups exactly as on the
        # native path.  The INTERNED pool owns copied buffers (finalize):
        # a pool view into an IPC message / shm segment would otherwise
        # pin the whole mapping for the cache entry's lifetime
        dpool = intern_pool(
            scope, pool_data, pool_off, null_code=len(pool_arr),
            finalize=lambda d, o: (np.ascontiguousarray(d).copy(),
                                   np.ascontiguousarray(o).copy()))
    with _POOL_CACHE_LOCK:
        hit = _POOL_CACHE.get(key)
        if hit is not None:
            return hit[0], hit[2]
        while len(_POOL_CACHE) >= _POOL_CACHE_MAX:
            _POOL_CACHE.pop(next(iter(_POOL_CACHE)), None)
        # pin the ORIGINAL array: its buffer addresses are the key
        _POOL_CACHE[key] = (dpool, orig, remap)
    return dpool, remap


def _arrow_to_column(cs: ColSchema, arr) -> Column:
    import pyarrow as pa
    import pyarrow.types as pt

    validity = None
    if arr.null_count:
        validity = np.asarray(arr.is_valid())
    t = arr.type
    if pt.is_dictionary(t):
        vt = t.value_type
        if cs.data_type.is_variable_width and (
                pt.is_string(vt) or pt.is_large_string(vt)
                or pt.is_binary(vt) or pt.is_large_binary(vt)):
            pool_arr = arr.dictionary
            if pool_arr.null_count == 0:
                dpool, remap = _adopt_dict_pool(
                    pool_arr, vt, pt, pa,
                    scope=("arrow", cs.name, str(vt)))
                idx = arr.indices
                if idx.null_count:
                    idx = idx.fill_null(0)
                codes = np.asarray(idx.cast(pa.int32()))
                if remap is not None:
                    # permuted pool adopted onto the canonical one: the
                    # codes change basis (remap has n_pool+1 slots; real
                    # codes stay < n_pool, slot n_pool is the sentinel)
                    codes = remap[codes]
                if validity is not None:
                    # canonical null representation is empty bytes (matches
                    # the flat path): null rows point at the pool's empty
                    # sentinel so lazy materialization is byte-identical
                    codes = np.where(validity, codes,
                                     dpool.null_code).astype(np.int32)
                return Column(cs.name, cs.data_type, validity=validity,
                              dict_enc=DictEnc(codes, pool=dpool))
        # non-string pool or pool nulls: decode in arrow C++ and re-enter
        return _arrow_to_column(cs, arr.cast(t.value_type))
    if pt.is_string(t) or pt.is_large_string(t) or pt.is_binary(t) \
            or pt.is_large_binary(t):
        if pt.is_large_string(t) or pt.is_large_binary(t):
            arr = arr.cast(pa.string() if pt.is_large_string(t) else pa.binary())
        data, off = _adopt_string_buffers(arr)
        return Column(cs.name, cs.data_type, data, off, validity)
    if cs.data_type.is_variable_width:
        # canonical var-width but arrow gave a non-string type: stringify
        vals = arr.to_pylist()
        col = Column.from_pylist(cs.name, cs.data_type, vals)
        return col
    if pt.is_timestamp(t):
        unit_scale = {"s": 1_000_000, "ms": 1_000, "us": 1, "ns": 1}[t.unit]
        vals = np.asarray(arr.cast(pa.int64()).fill_null(0))
        if cs.data_type == CanonicalType.DATETIME:
            div = {"s": 1, "ms": 1_000, "us": 1_000_000, "ns": 1_000_000_000}[t.unit]
            data = (vals // div).astype(np.int64)
        else:
            data = (vals * unit_scale if t.unit in ("s", "ms")
                    else vals // (1000 if t.unit == "ns" else 1)).astype(np.int64)
        return Column(cs.name, cs.data_type, data, None, validity)
    if pt.is_date32(t):
        data = np.asarray(arr.cast(pa.int32()).fill_null(0))
        return Column(cs.name, cs.data_type, data.astype(np.int32), None, validity)
    if pt.is_boolean(t):
        data = np.asarray(arr.fill_null(False))
        return Column(cs.name, cs.data_type, data.astype(np.bool_), None, validity)
    data = np.asarray(arr.fill_null(0)).astype(cs.data_type.np_dtype, copy=False)
    return Column(cs.name, cs.data_type, np.ascontiguousarray(data), None, validity)


def arrow_to_table_schema(pa_schema) -> TableSchema:
    """Infer a canonical TableSchema from an arrow schema."""
    import pyarrow.types as pt

    cols = []
    for f in pa_schema:
        t = f.type
        if pt.is_dictionary(t):
            t = t.value_type  # canonical type is the value type;
            # the encoding itself travels as Column.dict_enc
        if pt.is_int8(t):
            ct = CanonicalType.INT8
        elif pt.is_int16(t):
            ct = CanonicalType.INT16
        elif pt.is_int32(t):
            ct = CanonicalType.INT32
        elif pt.is_int64(t):
            ct = CanonicalType.INT64
        elif pt.is_uint8(t):
            ct = CanonicalType.UINT8
        elif pt.is_uint16(t):
            ct = CanonicalType.UINT16
        elif pt.is_uint32(t):
            ct = CanonicalType.UINT32
        elif pt.is_uint64(t):
            ct = CanonicalType.UINT64
        elif pt.is_float32(t):
            ct = CanonicalType.FLOAT
        elif pt.is_float64(t):
            ct = CanonicalType.DOUBLE
        elif pt.is_boolean(t):
            ct = CanonicalType.BOOLEAN
        elif pt.is_date32(t) or pt.is_date64(t):
            ct = CanonicalType.DATE
        elif pt.is_timestamp(t):
            ct = CanonicalType.TIMESTAMP if t.unit in ("us", "ns") \
                else CanonicalType.DATETIME
        elif pt.is_string(t) or pt.is_large_string(t):
            ct = CanonicalType.UTF8
        elif pt.is_binary(t) or pt.is_large_binary(t):
            ct = CanonicalType.STRING
        elif pt.is_decimal(t):
            ct = CanonicalType.DECIMAL
        else:
            ct = CanonicalType.ANY
        cols.append(ColSchema(
            name=f.name, data_type=ct, required=not f.nullable,
            original_type=f"arrow:{t}",
        ))
    return TableSchema(cols)
