"""Zero-copy ColumnBatch ⇄ pyarrow.RecordBatch converters.

`ColumnBatch` columns are already Arrow-shaped (flat numpy buffers,
int32 offsets, boolean validity), so conversion is buffer *wrapping*,
not rewriting:

- ColumnBatch → Arrow: `pa.py_buffer(<numpy array>)` wraps each data /
  offsets buffer in place (pyarrow pins the array through the buffer
  protocol — no memcpy, no per-row Python).  The only materializations
  are bitmaps: validity packs bool→bits and BOOLEAN columns pack their
  byte-per-value data the same way (Arrow's bool layout is bit-packed).
- Arrow → ColumnBatch: `np.frombuffer(<pa.Buffer>)` views each buffer
  in place (numpy pins the pa.Buffer as `.base`, which pins the IPC
  message / shm segment it came from).  Arrays adopted this way are
  READ-ONLY views — the pipeline treats column buffers as immutable
  (transforms replace columns, never mutate), so this is safe; anything
  that must write takes a copy at that point.

Canonical-schema fidelity: the Arrow schema's metadata carries the full
`TableSchema` (`trtpu:schema`, TableSchema.to_json) plus the table
identity and CDC sidecars, so ANY/DECIMAL/STRING round-trip exactly
instead of degrading to UTF8 through arrow-type inference.  Foreign
Arrow data without the metadata falls back to `arrow_to_table_schema`.

CDC sidecars (kinds/lsns/commit_times) travel as extra `__trtpu_*`
columns — wrapped zero-copy like any other fixed-width buffer and
stripped on import.  Host-only sidecars (old_keys, txn_ids) do NOT
cross the wire, same as they never ship to the device.

Every buffer adoption is tallied in `telemetry.TELEMETRY`
(`zero_copy_buffers` vs `copied_buffers`) — the plane's honesty metric.
"""

from __future__ import annotations

import json
from typing import Any, Optional

import numpy as np

from transferia_tpu.abstract.schema import (
    CanonicalType,
    TableID,
    TableSchema,
)
from transferia_tpu.columnar.batch import (
    _ARROW_TYPES,
    Column,
    ColumnBatch,
    _arrow_to_column,
    arrow_to_table_schema,
)
from transferia_tpu.interchange._pyarrow import pyarrow
from transferia_tpu.interchange.telemetry import TELEMETRY
from transferia_tpu.runtime import knobs

SCHEMA_KEY = b"trtpu:schema"
TABLE_KEY = b"trtpu:table"
PART_KEY = b"trtpu:part_id"
# field-level markers of the encoded wire:
# - FOR_KEY marks a binary column carrying a frame-of-reference payload
#   (value = the canonical type name the decode reconstructs);
# - DICTREF_KEY marks an int32 codes-only column whose dictionary ships
#   on substream 0 of the same part (value = the pool's arrow type) —
#   `rebind_dict_columns` reattaches it before adoption.
FOR_KEY = b"trtpu:forenc"
DICTREF_KEY = b"trtpu:dictref"
_FOR_MAGIC = 0x464F5231  # "FOR1" LE
_FOR_HEADER_WORDS = 7    # magic, n_rows, bit_width, frame, n_mins,
#                          n_words, n_validity_bytes
_SIDECAR_KINDS = "__trtpu_kinds"
_SIDECAR_LSNS = "__trtpu_lsns"
_SIDECAR_COMMIT = "__trtpu_commit_times"
_SIDECARS = (_SIDECAR_KINDS, _SIDECAR_LSNS, _SIDECAR_COMMIT)


_encoded_wire_cached: Optional[bool] = None
_for_wire_cached: Optional[bool] = None


def for_wire_enabled() -> bool:
    """TRANSFERIA_TPU_FOR_WIRE=0 forces int columns RAW on the Arrow
    wire; default on — list-framed streams (Flight parts, shm segments,
    IPC files) FOR-encode clustered integer columns with sidecar frame
    mins when every batch of the column passes the `ops/dispatch`
    `_for_plan` guard chain (byte-identical round trip)."""
    global _for_wire_cached
    if _for_wire_cached is None:
        _for_wire_cached = knobs.env_str(
            "TRANSFERIA_TPU_FOR_WIRE", "1") != "0"
    return _for_wire_cached


def set_for_wire(on: Optional[bool]) -> None:
    """Force the FOR wire on/off (None = re-read the env)."""
    global _for_wire_cached
    _for_wire_cached = on


def encoded_wire_enabled() -> bool:
    """TRANSFERIA_TPU_ENCODED_FLIGHT=0 forces dict columns FLAT on the
    Arrow wire (the reference tests/unit/test_encoded_wire.py holds the
    encoded wire's rows to; nothing else turns it off: ROADMAP D3);
    default on — dict columns cross as DictionaryArrays, and the
    IPC/Flight framing ships each dictionary (pool) once per stream
    followed by codes-only record batches."""
    global _encoded_wire_cached
    if _encoded_wire_cached is None:
        _encoded_wire_cached = knobs.env_str(
            "TRANSFERIA_TPU_ENCODED_FLIGHT", "1") != "0"
    return _encoded_wire_cached


def set_encoded_wire(on: Optional[bool]) -> None:
    """Force the encoded Arrow wire on/off (None = re-read the env)."""
    global _encoded_wire_cached
    _encoded_wire_cached = on


class EncodedWireState:
    """Per-STREAM accounting of the pool-once encoded wire.

    One instance lives for the life of one IPC/Flight/shm stream; the
    Arrow framing ships a stream's dictionary exactly once (and again
    only on replacement), so `account()` tallies a pool's bytes the
    first time a batch references it and codes-only bytes every batch —
    the telemetry that lets tests/bench ASSERT "each pool shipped at
    most once per stream" instead of trusting the framing.  Also counts
    what the flat wire would have shipped (`flat_equiv`), the input to
    the encoded_wire_ratio honesty gauge.

    Tallies accumulate as PENDING and publish only on `commit()` —
    called after the bytes actually reach the wire.  A failed put
    drops its pending tallies with the state, so a retried stream
    (fresh state) never double-counts a pool that never crossed."""

    __slots__ = ("seen_pools", "_pool_b", "_codes_b", "_flat_b",
                 "_new_pools")

    def __init__(self):
        self.seen_pools: set[int] = set()
        self._pool_b = self._codes_b = self._flat_b = 0
        self._new_pools = 0

    def account(self, batch: "ColumnBatch") -> int:
        """Stage one batch's tallies; returns how many pools NEWLY
        ship with it (0 for a codes-only batch)."""
        new_pools = 0
        for c in batch.columns.values():
            if not (c.is_lazy_dict and encoded_wire_enabled()):
                continue
            enc = c.dict_enc
            self._codes_b += int(enc.indices.nbytes)
            offs = enc.pool.values_offsets
            lens = offs[1:] - offs[:-1]
            self._flat_b += int(lens[enc.indices].sum()) \
                + (len(enc.indices) + 1) * 4
            if id(enc.pool) not in self.seen_pools:
                self.seen_pools.add(id(enc.pool))
                new_pools += 1
                self._pool_b += enc.pool.nbytes()
        self._new_pools += new_pools
        return new_pools

    def account_payload(self, shipped_bytes: int, flat_bytes: int) -> None:
        """Stage a non-dict encoded column's wire bytes (FOR frames):
        the packed payload counts like codes, the raw dtype bytes like
        flat — same pending/commit discipline as `account()`."""
        self._codes_b += int(shipped_bytes)
        self._flat_b += int(flat_bytes)

    def commit(self) -> None:
        """Publish the staged tallies (the stream's bytes landed)."""
        from transferia_tpu.stats.ledger import LEDGER

        if not (self._pool_b or self._codes_b):
            return
        TELEMETRY.add(pool_bytes_shipped=self._pool_b,
                      codes_bytes_shipped=self._codes_b,
                      flat_equiv_bytes=self._flat_b,
                      pools_shipped=self._new_pools)
        LEDGER.add(pool_bytes_shipped=self._pool_b,
                   codes_bytes_shipped=self._codes_b)
        self._pool_b = self._codes_b = self._flat_b = 0
        self._new_pools = 0


def plan_for_wire(batches, wire: Optional[EncodedWireState] = None
                  ) -> dict[str, list]:
    """Decide which integer columns of a batch LIST cross as FOR frames.

    An Arrow stream's schema is fixed at open, so a column either
    FOR-encodes in EVERY batch of the stream or crosses raw — the plan
    runs `ops/dispatch._for_plan` (the exact device guard chain:
    frame-divisible row count, int32-exact values, genuine shrink) over
    all batches up front and keeps only all-or-nothing winners.
    Returns {column name: [per-batch (mins, rel, bw, frame)]} with the
    remainders still UNPACKED — the expensive bit-pack happens in
    `_for_array` at conversion time, which a multi-stream put runs on
    its substream threads (packing here would serialize it on the
    spawning thread).  Pass each batch's entry to
    `batch_to_arrow(for_enc=...)`.  With `wire`, stages payload-vs-flat
    bytes into the stream's EncodedWireState."""
    if not batches or not for_wire_enabled():
        return {}
    from transferia_tpu.ops.dispatch import _for_plan

    out: dict[str, list] = {}
    for cs in batches[0].schema:
        if cs.data_type.is_variable_width \
                or np.dtype(cs.data_type.np_dtype).kind not in "iu":
            continue
        encs, shipped, flat = [], 0, 0
        for b in batches:
            c = b.columns.get(cs.name)
            if c is None or c.is_lazy_dict:
                encs = []
                break
            plan = _for_plan(c.data.reshape(1, -1)) \
                if c.data.ndim == 1 else None
            if plan is None:
                encs = []
                break
            mins, rel, bw, frame = plan
            encs.append((mins[0], rel[0], bw, frame))
            flat += int(c.data.nbytes)
            # packed size without packing: bw bits per value, byte-
            # rounded then padded to whole uint32 words (pack_bits_host)
            words_nb = -4 * (-((c.n_rows * bw + 7) // 8) // 4)
            shipped += _FOR_HEADER_WORDS * 4 + mins[0].nbytes + words_nb
            if c.validity is not None:
                shipped += (c.n_rows + 7) // 8
        if encs:
            out[cs.name] = encs
            if wire is not None:
                wire.account_payload(shipped, flat)
    return out


def _for_array(pa, c: Column, enc) -> Any:
    """One FOR-encoded column → a binary Arrow array whose ROW 0 holds
    the whole payload (header + frame mins + packed remainders + packed
    validity) and rows 1..n-1 are empty — a RecordBatch column must be
    n_rows long, and this shape keeps the payload in-band in the data
    buffer where per-batch variance is allowed (schema/field metadata
    ship once per stream and must stay constant)."""
    from transferia_tpu.ops.dispatch import pack_bits_host

    mins, rel, bw, frame = enc
    words = pack_bits_host(rel, bw)
    n = c.n_rows
    vbytes = (np.packbits(c.validity, bitorder="little").tobytes()
              if c.validity is not None else b"")
    header = np.array([_FOR_MAGIC, n, bw, frame, len(mins), len(words),
                       len(vbytes)], dtype=np.uint32)
    payload = header.tobytes() + mins.tobytes() + words.tobytes() + vbytes
    offsets = np.zeros(n + 1, dtype=np.int32)
    offsets[1:] = len(payload)
    TELEMETRY.add(copied_buffers=1)  # the pack is a materialization
    return pa.Array.from_buffers(
        pa.binary(), n,
        [None, pa.py_buffer(offsets), pa.py_buffer(payload)])


def _decode_for_column(cs, arr) -> Column:
    """Inverse of `_for_array`: unpack the row-0 payload back into the
    canonical integer column, byte-identical (values and validity)."""
    bufs = arr.buffers()
    off = np.frombuffer(bufs[1], dtype=np.int32,
                        count=len(arr) + 1 + arr.offset)[arr.offset:]
    payload = np.frombuffer(bufs[2], dtype=np.uint8)[off[0]:off[1]]
    payload = np.ascontiguousarray(payload)
    hdr = np.frombuffer(payload, dtype=np.uint32,
                        count=_FOR_HEADER_WORDS)
    magic, n, bw, frame, n_mins, n_words, n_vbytes = (int(x) for x in hdr)
    if magic != _FOR_MAGIC:
        raise ValueError(f"FOR wire column {cs.name!r}: bad magic "
                         f"{magic:#x}")
    pos = _FOR_HEADER_WORDS * 4
    mins = np.frombuffer(payload, dtype=np.int32, count=n_mins,
                         offset=pos)
    pos += 4 * n_mins
    words = np.frombuffer(payload, dtype=np.uint32, count=n_words,
                          offset=pos)
    pos += 4 * n_words
    bits = np.unpackbits(words.view(np.uint8), bitorder="little")
    weights = (np.uint64(1) << np.arange(bw, dtype=np.uint64))
    rel = (bits[:n * bw].reshape(n, bw).astype(np.uint64) * weights) \
        .sum(axis=1).astype(np.int64)
    values = np.repeat(mins.astype(np.int64), frame)[:n] + rel
    data = values.astype(cs.data_type.np_dtype)
    validity = None
    if n_vbytes:
        vb = np.frombuffer(payload, dtype=np.uint8, count=n_vbytes,
                           offset=pos)
        validity = np.unpackbits(vb, bitorder="little")[:n] \
            .astype(np.bool_)
    TELEMETRY.add(copied_buffers=1)  # the unpack materializes
    return Column(cs.name, cs.data_type, data, None, validity)


def dict_columns_of(rb) -> dict:
    """{column name: dictionary array} for each DictionaryArray column
    of a RecordBatch — the pools substream 0 carries for the part."""
    pa = pyarrow("Arrow dictionary extraction")
    out = {}
    for i, field in enumerate(rb.schema):
        if pa.types.is_dictionary(field.type):
            out[field.name] = rb.column(i).dictionary
    return out


def rebind_dict_columns(rb, dictionaries: dict):
    """Codes-only batch (DICTREF-marked int32 columns) + the pools from
    substream 0 → a batch whose dict columns are DictionaryArrays again
    (a zero-copy rebind: the codes and pool buffers are reused as-is).
    Batches without DICTREF markers pass through untouched."""
    pa = pyarrow("Arrow dictionary rebind")
    arrays, fields, changed = [], [], False
    for i, field in enumerate(rb.schema):
        fmd = field.metadata or {}
        pool = dictionaries.get(field.name)
        if DICTREF_KEY in fmd and pool is not None:
            arr = pa.DictionaryArray.from_arrays(rb.column(i), pool)
            fields.append(pa.field(
                field.name, pa.dictionary(pa.int32(), pool.type),
                nullable=field.nullable))
            arrays.append(arr)
            changed = True
        else:
            arrays.append(rb.column(i))
            fields.append(field)
    if not changed:
        return rb
    return pa.RecordBatch.from_arrays(
        arrays, schema=pa.schema(fields, metadata=rb.schema.metadata))


def _validity_buffer(pa, validity: Optional[np.ndarray]):
    """Bool validity → Arrow bitmap buffer (the permitted materialization)."""
    if validity is None:
        return None
    return pa.py_buffer(np.packbits(validity, bitorder="little").tobytes())


def _wrap(pa, arr: np.ndarray):
    """Wrap a numpy buffer as an Arrow buffer without copying.

    Non-contiguous inputs (rare: sliced views with strides) compact
    first and are tallied as copies."""
    if not arr.flags.c_contiguous:
        arr = np.ascontiguousarray(arr)
        TELEMETRY.add(copied_buffers=1)
    else:
        TELEMETRY.add(zero_copy_buffers=1)
    return pa.py_buffer(arr)


def _column_to_arrow(pa, c: Column, pa_type) -> tuple[Any, Any]:
    """One column → (pa.Array, pa field type); zero-copy where the
    layouts already agree."""
    n = c.n_rows
    validity = _validity_buffer(pa, c.validity)
    if c.is_lazy_dict and not encoded_wire_enabled():
        # encoded wire forced off: serialize the gathered flat form
        # (a LOCAL gather — the shared column object stays lazy-dict)
        data, offsets = c.dict_enc.materialize()
        arr = pa.Array.from_buffers(
            pa_type, n,
            [validity, _wrap(pa, offsets), _wrap(pa, data)])
        TELEMETRY.add(copied_buffers=1)
        return arr, pa_type
    if c.is_lazy_dict:
        # dictionary-encoded end-to-end: wrap the shared pool's buffers
        # once (memoized on the DictPool so batch slices of one row
        # group serialize one pool) and the int32 codes per batch
        enc = c.dict_enc
        memo_key = ("interchange_pool", str(pa_type))
        pool = enc.pool.memo_get(memo_key)
        if pool is None:
            pool = pa.Array.from_buffers(
                pa_type, enc.n_values,
                [None, _wrap(pa, enc.values_offsets),
                 _wrap(pa, enc.values_data)])
            enc.pool.memo_set(memo_key, pool)
        idx = pa.Array.from_buffers(
            pa.int32(), n, [validity, _wrap(pa, enc.indices)])
        arr = pa.DictionaryArray.from_arrays(idx, pool)
        return arr, pa.dictionary(pa.int32(), pa_type)
    if c.ctype.is_variable_width:
        arr = pa.Array.from_buffers(
            pa_type, n,
            [validity, _wrap(pa, c.offsets), _wrap(pa, c.data)])
        return arr, pa_type
    if c.ctype == CanonicalType.BOOLEAN:
        # Arrow bools are bit-packed: the data bitmap is the second (and
        # last) permitted materialization next to validity
        bits = pa.py_buffer(
            np.packbits(c.data, bitorder="little").tobytes())
        TELEMETRY.add(copied_buffers=1)
        arr = pa.Array.from_buffers(pa_type, n, [validity, bits])
        return arr, pa_type
    arr = pa.Array.from_buffers(pa_type, n, [validity, _wrap(pa, c.data)])
    return arr, pa_type


def batch_to_arrow(batch: ColumnBatch,
                   for_enc: Optional[dict] = None,
                   strip_pools: Optional[set] = None):
    """ColumnBatch → pyarrow.RecordBatch, wrapping the existing numpy
    buffers (no per-row path, no memcpy for fixed-width columns).

    `for_enc` ({name: (mins, words, bw, frame)} from `plan_for_wire`)
    ships those integer columns as FOR frames.  `strip_pools` (column
    names) ships those dict columns CODES-ONLY with a DICTREF marker —
    the multi-stream put uses it on substreams ≥ 1 so the pool crosses
    once per PART (on substream 0), not once per substream."""
    pa = pyarrow("ColumnBatch→Arrow conversion")
    arrays, fields = [], []
    for cs in batch.schema:
        c = batch.columns.get(cs.name)
        if c is None:
            continue
        if for_enc and cs.name in for_enc:
            arrays.append(_for_array(pa, c, for_enc[cs.name]))
            fields.append(pa.field(
                cs.name, pa.binary(), nullable=not cs.required,
                metadata={FOR_KEY: cs.data_type.name.encode()}))
            continue
        if (strip_pools and cs.name in strip_pools and c.is_lazy_dict
                and encoded_wire_enabled()):
            enc = c.dict_enc
            idx = pa.Array.from_buffers(
                pa.int32(), c.n_rows,
                [_validity_buffer(pa, c.validity), _wrap(pa, enc.indices)])
            arrays.append(idx)
            fields.append(pa.field(
                cs.name, pa.int32(), nullable=not cs.required,
                metadata={DICTREF_KEY:
                          str(_ARROW_TYPES[cs.data_type]).encode()}))
            continue
        arr, ftype = _column_to_arrow(pa, c, _ARROW_TYPES[cs.data_type])
        arrays.append(arr)
        fields.append(pa.field(cs.name, ftype, nullable=not cs.required))
    for name, data in (
        (_SIDECAR_KINDS, batch.kinds),
        (_SIDECAR_LSNS, batch.lsns),
        (_SIDECAR_COMMIT, batch.commit_times),
    ):
        if data is None:
            continue
        pa_type = pa.int8() if data.dtype == np.int8 else pa.int64()
        arrays.append(pa.Array.from_buffers(
            pa_type, len(data), [None, _wrap(pa, data)]))
        fields.append(pa.field(name, pa_type, nullable=False))
    metadata = {
        SCHEMA_KEY: json.dumps(batch.schema.to_json()).encode(),
        TABLE_KEY: json.dumps({
            "namespace": batch.table_id.namespace,
            "name": batch.table_id.name,
        }).encode(),
    }
    if batch.part_id:
        metadata[PART_KEY] = batch.part_id.encode()
    rb = pa.RecordBatch.from_arrays(
        arrays, schema=pa.schema(fields, metadata=metadata))
    TELEMETRY.add(batches_out=1, bytes_out=rb.nbytes)
    return rb


def _adopt_fixed(c_name: str, ctype: CanonicalType, arr,
                 validity: Optional[np.ndarray]) -> Column:
    """View a primitive Arrow array's data buffer in place."""
    bufs = arr.buffers()
    n = len(arr)
    dt = ctype.np_dtype
    if bufs[1] is None or n == 0:
        data = np.zeros(0, dtype=dt)
        TELEMETRY.add(zero_copy_buffers=1)  # nothing to copy either way
    else:
        data = np.frombuffer(bufs[1], dtype=dt,
                             count=n + arr.offset)[arr.offset:]
        TELEMETRY.add(zero_copy_buffers=1)
    return Column(c_name, ctype, data, None, validity)


def _adopt_varwidth(c_name: str, ctype: CanonicalType, arr,
                    validity: Optional[np.ndarray]) -> Column:
    """View a binary/string Arrow array's offsets+data buffers in place.

    Sliced arrays (nonzero offset / nonzero first offset) rebase the
    small offsets array; the data buffer stays a view either way."""
    bufs = arr.buffers()
    n = len(arr)
    if bufs[1] is None:
        return Column(c_name, ctype, np.zeros(0, dtype=np.uint8),
                      np.zeros(1, dtype=np.int32), validity)
    off = np.frombuffer(bufs[1], dtype=np.int32,
                        count=n + 1 + arr.offset)[arr.offset:]
    data = (np.frombuffer(bufs[2], dtype=np.uint8)
            if bufs[2] is not None else np.zeros(0, dtype=np.uint8))
    if off[0] != 0:
        data = data[off[0]:off[-1]]
        off = off - off[0]  # small rebase copy; data stays a view
        TELEMETRY.add(copied_buffers=1, zero_copy_buffers=1)
    else:
        TELEMETRY.add(zero_copy_buffers=2)
    return Column(c_name, ctype, data, off, validity)


def _canonical_pa_type(pa, ctype: CanonicalType, t) -> bool:
    """Does the arrow array's physical layout already match the
    canonical device layout for ctype (no cast needed)?"""
    return t.equals(_ARROW_TYPES[ctype])


def arrow_to_batch(rb, table_id: Optional[TableID] = None,
                   schema: Optional[TableSchema] = None) -> ColumnBatch:
    """pyarrow.RecordBatch → ColumnBatch, viewing the Arrow buffers in
    place (`np.frombuffer`); the Arrow side stays pinned via numpy
    `.base` chains, so IPC messages / shm segments outlive the batch."""
    pa = pyarrow("Arrow→ColumnBatch conversion")
    md = rb.schema.metadata or {}
    if schema is None:
        if SCHEMA_KEY in md:
            schema = TableSchema.from_json(json.loads(md[SCHEMA_KEY]))
        else:
            names = [f.name for f in rb.schema if f.name not in _SIDECARS]
            schema = arrow_to_table_schema(
                pa.schema([rb.schema.field(nm) for nm in names]))
    if table_id is None:
        if TABLE_KEY in md:
            t = json.loads(md[TABLE_KEY])
            table_id = TableID(t["namespace"], t["name"])
        else:
            table_id = TableID("arrow", "batch")
    cols: dict[str, Column] = {}
    for cs in schema:
        idx = rb.schema.get_field_index(cs.name)
        if idx < 0:
            continue
        arr = rb.column(idx)
        t = arr.type
        fmd = rb.schema.field(idx).metadata or {}
        if FOR_KEY in fmd:
            cols[cs.name] = _decode_for_column(cs, arr)
            continue
        validity = np.asarray(arr.is_valid()) if arr.null_count else None
        if pa.types.is_dictionary(t):
            # shared-pool adoption (zero-copy, pool memoized) lives in
            # columnar/batch.py — reuse it rather than fork the cache
            cols[cs.name] = _arrow_to_column(cs, arr)
            TELEMETRY.add(**({"copied_buffers": 1} if arr.null_count
                             else {"zero_copy_buffers": 3}))
            continue
        if cs.data_type.is_variable_width \
                and _canonical_pa_type(pa, cs.data_type, t):
            cols[cs.name] = _adopt_varwidth(cs.name, cs.data_type, arr,
                                            validity)
            continue
        if (not cs.data_type.is_variable_width
                and cs.data_type != CanonicalType.BOOLEAN
                and _canonical_pa_type(pa, cs.data_type, t)):
            cols[cs.name] = _adopt_fixed(cs.name, cs.data_type, arr,
                                         validity)
            continue
        # layout mismatch (foreign units, large_string, bool bitmaps):
        # the normalizing importer copies into canonical form
        cols[cs.name] = _arrow_to_column(cs, arr)
        TELEMETRY.add(copied_buffers=1)
    kinds = lsns = commit_times = None
    for name in _SIDECARS:
        idx = rb.schema.get_field_index(name)
        if idx < 0:
            continue
        arr = rb.column(idx)
        bufs = arr.buffers()
        dt = np.int8 if name == _SIDECAR_KINDS else np.int64
        data = (np.frombuffer(bufs[1], dtype=dt,
                              count=len(arr) + arr.offset)[arr.offset:]
                if bufs[1] is not None else np.zeros(0, dtype=dt))
        TELEMETRY.add(zero_copy_buffers=1)
        if name == _SIDECAR_KINDS:
            kinds = data
        elif name == _SIDECAR_LSNS:
            lsns = data
        else:
            commit_times = data
    batch = ColumnBatch(
        table_id, schema, cols,
        kinds=kinds, lsns=lsns, commit_times=commit_times,
        part_id=md.get(PART_KEY, b"").decode(),
        read_bytes=rb.nbytes,
    )
    TELEMETRY.add(batches_in=1, bytes_in=rb.nbytes)
    return batch
