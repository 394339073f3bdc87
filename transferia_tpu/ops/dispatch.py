"""Compressed device dispatch: ship encoded columns, decode on device.

The device plane pays for every byte it moves across the host↔device
link, both ways, on every batch.  Fewer bytes is the lever a faster
kernel cannot replace: columns cross the link in their compact
encodings and the decode kernels in ops/decode.py reconstruct them on
device, byte-identical to host decode.

Encodings (selection is per column, per batch, host-side):

- **dict pools** — a `DictEnc` masked column never ships row bytes at
  all: the value pool uploads ONCE per (pool, HMAC key), hashes on
  device, and the (k, 8) digest matrix comes back to become a hexed
  `DictPool` memoized on the shared pool (`device_hmac_dict_pool`).
  Batches slicing the same row group then mask for free — the row
  codes never leave the host.
- **validity bitmaps** — bit-packed to n/8 bytes (`encode_validity` /
  `ops.decode.unpack_validity`); the keep mask returns the same way
  (`ops.decode.pack_mask_words`), shrinking the predicate's D2H 8x.
- **delta + bit-pack integers** — predicate columns whose zigzag'd
  deltas fit <= 30 bits ship as base + packed deltas and reconstruct
  via `ops.decode.delta_prefix_sum` (sorted ids, timestamps, dates).
- **bool data** — bit-packed like validity.

`TRANSFERIA_TPU_DISPATCH_ENCODING` picks the mode: `auto` (default —
encode whenever it shrinks) or `raw` (every column as flat arrays: what
`auto` itself ships for an array the encoders reject, and the reference
the tests hold the encoded results to, byte for byte.  Nothing but
tests sets the whole process to `raw`: ROADMAP D3).

Grounding: Zerrow (PAPERS.md) keeps data in its compact columnar
encoding across plane boundaries; Thallus shows transport cost, not
compute, is what columnar pipelines must engineer around.  This module
is host-side only (numpy packers + staging); the traced decode lives
in ops/decode.py, and ops/fused.py composes both into the fused
program.
"""

from __future__ import annotations

import os
import threading
from dataclasses import dataclass
from typing import Optional

import numpy as np

from transferia_tpu.runtime import knobs
from transferia_tpu.stats import trace
from transferia_tpu.stats.trace import TELEMETRY

_mode_cached: Optional[str] = None

# serializes dict-pool device hashing: concurrent part threads sharing
# one DictPool must not each pay the pool upload the memo exists to
# amortize.  One process-wide lock is enough — uploads happen once per
# (pool, key), so contention is a startup transient, not steady state.
_pool_hash_lock = threading.Lock()

# zigzag'd deltas wider than this fall back to raw: the device prefix
# sum runs in int32 and must never wrap (30 bits of |delta| keeps every
# partial sum an exact int32), and past ~30 bits the shrink is gone
_DELTA_MAX_BITS = 30
# below this many rows the encode/decode round trip costs more than the
# handful of saved bytes
_DELTA_MIN_ROWS = 256

_for_frame_cached: Optional[int] = None


def for_frame() -> int:
    """Frame size of the frame-of-reference integer encoding
    (TRANSFERIA_TPU_FOR_FRAME; default 256 — every row bucket is a
    multiple; 0 disables FOR).  Static for jit: one frame size -> one
    compiled decode."""
    global _for_frame_cached
    if _for_frame_cached is None:
        _for_frame_cached = max(
            0, knobs.env_int("TRANSFERIA_TPU_FOR_FRAME", 256))
    return _for_frame_cached


def set_for_frame(n: Optional[int]) -> None:
    """Force the FOR frame size (None = re-read the env)."""
    global _for_frame_cached
    _for_frame_cached = n


def dispatch_encoding() -> str:
    """auto (encode whenever it shrinks, default) | raw."""
    global _mode_cached
    if _mode_cached is None:
        mode = knobs.env_str(
            "TRANSFERIA_TPU_DISPATCH_ENCODING", "auto").lower()
        _mode_cached = mode if mode in ("auto", "raw") else "auto"
    return _mode_cached


def set_dispatch_encoding(mode: Optional[str]) -> None:
    """Force the dispatch encoding mode (None = re-read the env)."""
    global _mode_cached
    _mode_cached = mode


def encoding_enabled() -> bool:
    return dispatch_encoding() != "raw"


# -- host-side packers -------------------------------------------------------

def pack_bits_host(values: np.ndarray, bit_width: int) -> np.ndarray:
    """Non-negative values -> the little-endian packed uint32 word
    stream ops/decode.unpack_bits consumes (value i occupies bits
    [i*bw, (i+1)*bw) of the stream)."""
    n = len(values)
    if n == 0:
        return np.zeros(0, dtype=np.uint32)
    shifts = np.arange(bit_width, dtype=np.uint64)
    bits = ((values.astype(np.uint64)[:, None] >> shifts) & 1).astype(
        np.uint8)
    packed = np.packbits(bits.reshape(-1), bitorder="little")
    pad = (-len(packed)) % 4
    if pad:
        packed = np.pad(packed, (0, pad))
    return packed.view(np.uint32)


def encode_validity(validity: np.ndarray) -> np.ndarray:
    """(n,) bool -> packed little-endian uint32 bitmap words."""
    packed = np.packbits(np.ascontiguousarray(validity, dtype=np.uint8),
                         bitorder="little")
    pad = (-len(packed)) % 4
    if pad:
        packed = np.pad(packed, (0, pad))
    return packed.view(np.uint32)


def unpack_mask_host(words: np.ndarray, n: int) -> np.ndarray:
    """Packed uint32 keep-mask words (D2H) -> (n,) bool, host side."""
    bits = np.unpackbits(np.ascontiguousarray(words).view(np.uint8),
                         bitorder="little")
    return bits[:n].astype(np.bool_)


def _delta_plan(values: np.ndarray
                ) -> Optional[tuple[np.ndarray, np.ndarray, int]]:
    """THE delta-encoding guard chain, shared by the single-device and
    mesh wires (values: (n_shards, per) — one row per independently
    decoded chunk).  Returns (bases int32 (n_shards,), zigzag'd deltas
    uint64 (n_shards, per), bit_width) or None when any guard rejects.

    Guards: the device prefix sum reconstructs VALUES in int32, not
    just deltas — every value (and the base) must fit int32 exactly,
    or a 64-bit column would decode wrapped; zigzag widths past
    _DELTA_MAX_BITS could wrap a partial sum; and the packed form must
    actually shrink the raw dtype.  Keep these HERE only — a guard
    tweaked in one wire but not the other would silently diverge the
    single-device and mesh decodes."""
    n_shards, per = values.shape
    if values.dtype.kind not in "iu" or per < _DELTA_MIN_ROWS:
        return None
    v = values.astype(np.int64)
    if int(v.min()) < -2**31 or int(v.max()) > 2**31 - 1:
        return None
    bases = v[:, :1]
    deltas = np.diff(v, axis=1, prepend=bases)
    zz = ((deltas << 1) ^ (deltas >> 63)).astype(np.uint64)
    bw = max(1, int(zz.max()).bit_length())
    if bw > _DELTA_MAX_BITS:
        return None
    if bw * per >= values.dtype.itemsize * 8 * per:
        return None  # no shrink over the raw dtype
    return bases[:, 0].astype(np.int32), zz, bw


def encode_delta(data: np.ndarray
                 ) -> Optional[tuple[int, np.ndarray, int]]:
    """Delta+bit-pack an integer array: (base, packed words, bit_width),
    or None when the encoding would not shrink the transfer."""
    if data.ndim != 1:
        return None
    plan = _delta_plan(data.reshape(1, -1))
    if plan is None:
        return None
    bases, zz, bw = plan
    return int(bases[0]), pack_bits_host(zz[0], bw), bw


def _for_plan(values: np.ndarray
              ) -> Optional[tuple[np.ndarray, np.ndarray, int, int]]:
    """THE frame-of-reference guard chain, shared by the single-device
    and mesh wires (values: (n_shards, per)).  Returns (mins int32
    (n_shards, n_frames), rel uint64 (n_shards, per), bit_width, frame)
    or None when any guard rejects.

    FOR closes the delta wire's "unprofitable reject" gap for
    clustered-but-unsorted ints: per static-size frame, subtract the
    frame minimum and bit-pack the non-negative remainders with ONE
    uniform width (the max across frames/shards — the decode program
    stays static).  Guards: every value must fit int32 exactly (the
    device reconstructs in int32; wraparound adds are exact only
    then), the frame size must divide the padded row count (buckets
    are multiples of every power-of-two frame <= 256), and packed
    remainders + per-frame mins must genuinely shrink the raw dtype."""
    frame = for_frame()
    n_shards, per = values.shape
    if (frame <= 0 or values.dtype.kind not in "iu"
            or per < _DELTA_MIN_ROWS or per % frame):
        return None
    v = values.astype(np.int64)
    if int(v.min()) < -2**31 or int(v.max()) > 2**31 - 1:
        return None
    framed = v.reshape(n_shards, per // frame, frame)
    mins = framed.min(axis=2)
    rel = (framed - mins[:, :, None]).reshape(n_shards, per) \
        .astype(np.uint64)
    bw = max(1, int(rel.max()).bit_length())
    if bw > 32:
        return None
    n_frames = per // frame
    if bw * per + n_frames * 32 >= values.dtype.itemsize * 8 * per:
        return None  # no shrink over the raw dtype
    return mins.astype(np.int32), rel, bw, frame


def encode_for(data: np.ndarray
               ) -> Optional[tuple[np.ndarray, np.ndarray, int, int]]:
    """FOR-encode an integer array: (mins (n_frames,) int32, packed
    words, bit_width, frame), or None when the guards reject."""
    if data.ndim != 1:
        return None
    plan = _for_plan(data.reshape(1, -1))
    if plan is None:
        return None
    mins, rel, bw, frame = plan
    return mins[0], pack_bits_host(rel[0], bw), bw, frame


def narrow_pred_i32(values: np.ndarray) -> Optional[np.ndarray]:
    """A predicate column whose values arrive wider than the chip
    compares in (a DECIMAL column's int64 integers at its scale), in
    int32 - or None where the batch's range does not fit it: the same
    guard `_delta_plan` and `_for_plan` apply before they carry a column,
    made here for the whole batch so that nothing is truncated under x32
    on any wire.  The int32 column then encodes (delta / FOR / raw) like
    any other."""
    if len(values) and (int(values.min()) < -2**31
                        or int(values.max()) > 2**31 - 1):
        return None
    return values.astype(np.int32)


# -- per-column dispatch encodings ------------------------------------------

@dataclass(frozen=True)
class PredEnc:
    """Static half of one predicate column's dispatch encoding (a jit
    static argument — the traced program's structure hangs off it).

    kind: raw (dtype bytes as-is) | delta (base + packed zigzag deltas,
    sorted-ish integer dtypes) | for (per-frame mins + packed
    remainders, clustered-but-unsorted integers) | bits (bit-packed
    boolean data).
    valid_mode: none (all-valid, synthesized on device) | bits
    (bit-packed bitmap) | raw (bool bytes, the uncompressed wire).
    frame: FOR frame size (0 for every other kind).
    """

    name: str
    dtype: str
    kind: str
    bit_width: int
    valid_mode: str
    frame: int = 0


def encode_pred_column(name: str, data: np.ndarray,
                       validity: Optional[np.ndarray], n_rows: int,
                       bucket: int, encoded: bool
                       ) -> tuple[PredEnc, tuple, int]:
    """Encode one predicate column for dispatch.

    Returns (spec, host arrays ready for H2D, raw_equiv_bytes — what the
    uncompressed wire would have shipped).  Data pads to the bucket with
    its edge value (keeps delta widths narrow); validity pads False, so
    padded rows never pass the predicate regardless of data padding.
    """
    raw_equiv = bucket * data.dtype.itemsize + bucket  # data + bool bitmap
    if bucket != n_rows:
        data = np.pad(data, (0, bucket - n_rows),
                      mode="edge" if n_rows else "constant")
        if validity is not None:
            validity = np.pad(validity, (0, bucket - n_rows))
    if not encoded:
        if validity is None:
            validity = np.ones(bucket, dtype=np.bool_)
        spec = PredEnc(name, str(data.dtype), "raw", 0, "raw")
        return spec, (data, validity), raw_equiv
    if validity is None:
        valid_mode, val_arrays = "none", ()
    else:
        valid_mode, val_arrays = "bits", (encode_validity(validity),)
    if data.dtype == np.bool_:
        spec = PredEnc(name, str(data.dtype), "bits", 1, valid_mode)
        return (spec, (encode_validity(data),) + val_arrays, raw_equiv)
    delta = encode_delta(data)
    if delta is not None:
        base, words, bw = delta
        spec = PredEnc(name, str(data.dtype), "delta", bw, valid_mode)
        return (spec, (words, np.int32(base)) + val_arrays, raw_equiv)
    forenc = encode_for(data)
    if forenc is not None:
        mins, words, bw, frame = forenc
        spec = PredEnc(name, str(data.dtype), "for", bw, valid_mode,
                       frame)
        return (spec, (words, mins) + val_arrays, raw_equiv)
    spec = PredEnc(name, str(data.dtype), "raw", 0, valid_mode)
    return spec, (data,) + val_arrays, raw_equiv


def decode_pred_device(spec: PredEnc, arrays, bucket: int):
    """Traced device decode of one encoded predicate column — runs
    INSIDE the fused jitted program (spec is static there), emitting
    the (data, validity) pair predicate/device.compile_mask_jnp eats."""
    import jax.numpy as jnp

    from transferia_tpu.ops.decode import (
        delta_prefix_sum,
        for_frame_decode,
        unpack_validity,
    )

    if spec.kind == "raw":
        data = arrays[0]
    elif spec.kind == "bits":
        data = unpack_validity(arrays[0], bucket)
    elif spec.kind == "for":
        data = for_frame_decode(arrays[0], arrays[1], spec.bit_width,
                                spec.frame, bucket
                                ).astype(np.dtype(spec.dtype))
    else:  # delta
        data = delta_prefix_sum(arrays[0], arrays[1], spec.bit_width,
                                bucket).astype(np.dtype(spec.dtype))
    if spec.valid_mode == "none":
        valid = jnp.ones(bucket, dtype=jnp.bool_)
    elif spec.valid_mode == "bits":
        valid = unpack_validity(arrays[-1], bucket)
    else:
        valid = arrays[-1]
    return data, valid


# -- per-shard (mesh) dispatch encodings -------------------------------------
#
# The mesh wire ships every array with a leading device axis: each
# device's contiguous row chunk encodes INDEPENDENTLY (a delta prefix
# sum or a packed bitmap cannot span a shard boundary — each shard
# decodes alone inside shard_map), with one uniform bit width across
# shards so the decode program stays static.  parallel/fusedmesh.py is
# the only consumer.

def encode_validity_sharded(valid2d: np.ndarray) -> np.ndarray:
    """(n_dev, per_dev) bool -> (n_dev, W) packed little-endian uint32
    bitmap words, each shard packed independently."""
    packed = np.packbits(np.ascontiguousarray(valid2d, dtype=np.uint8),
                         axis=1, bitorder="little")
    pad = (-packed.shape[1]) % 4
    if pad:
        packed = np.pad(packed, ((0, 0), (0, pad)))
    return np.ascontiguousarray(packed).view(np.uint32)


def _encode_delta_sharded(d2: np.ndarray
                          ) -> Optional[tuple[np.ndarray, np.ndarray,
                                              int]]:
    """Per-shard delta+bit-pack: (bases (n_dev,) int32, words (n_dev,
    W), bit_width) or None when the shared `_delta_plan` guards reject
    (int32-exact values, <= 30-bit zigzag deltas, must shrink) — one
    uniform bit width across shards keeps the decode program static."""
    plan = _delta_plan(d2)
    if plan is None:
        return None
    bases, zz, bw = plan
    words = np.stack([pack_bits_host(row, bw) for row in zz])
    return bases, words, bw


def _encode_for_sharded(d2: np.ndarray
                        ) -> Optional[tuple[np.ndarray, np.ndarray,
                                            int, int]]:
    """Per-shard frame-of-reference pack: (mins (n_dev, n_frames)
    int32, words (n_dev, W), bit_width, frame) or None when the shared
    `_for_plan` guards reject — frames never span a shard boundary
    (per_dev is a bucket, a multiple of the frame size) and one
    uniform bit width across shards keeps the decode static."""
    plan = _for_plan(d2)
    if plan is None:
        return None
    mins, rel, bw, frame = plan
    words = np.stack([pack_bits_host(row, bw) for row in rel])
    return mins, words, bw, frame


def encode_pred_column_sharded(name: str, data: np.ndarray,
                               validity: Optional[np.ndarray],
                               n_rows: int, n_dev: int, per_dev: int,
                               encoded: bool
                               ) -> tuple[PredEnc, tuple, int]:
    """Encode one predicate column for the mesh wire.

    Returns (spec, arrays each with a leading (n_dev, ...) device
    axis, raw_equiv_bytes).  Padding matches encode_pred_column: data
    pads with its edge value (keeps delta widths narrow), validity
    pads False so padded rows never pass the predicate."""
    total = n_dev * per_dev
    raw_equiv = total * data.dtype.itemsize + total  # data + bool map
    if total != n_rows:
        data = np.pad(data, (0, total - n_rows),
                      mode="edge" if n_rows else "constant")
        if validity is not None:
            validity = np.pad(validity, (0, total - n_rows))
    d2 = data.reshape(n_dev, per_dev)
    v2 = (validity.reshape(n_dev, per_dev)
          if validity is not None else None)
    if not encoded:
        if v2 is None:
            v2 = np.ones((n_dev, per_dev), dtype=np.bool_)
        return (PredEnc(name, str(data.dtype), "raw", 0, "raw"),
                (d2, v2), raw_equiv)
    if v2 is None:
        valid_mode: str = "none"
        val_arrays: tuple = ()
    else:
        valid_mode = "bits"
        val_arrays = (encode_validity_sharded(v2),)
    if data.dtype == np.bool_:
        spec = PredEnc(name, str(data.dtype), "bits", 1, valid_mode)
        return spec, (encode_validity_sharded(d2),) + val_arrays, \
            raw_equiv
    delta = _encode_delta_sharded(d2)
    if delta is not None:
        bases, words, bw = delta
        spec = PredEnc(name, str(data.dtype), "delta", bw, valid_mode)
        return spec, (words, bases) + val_arrays, raw_equiv
    forenc = _encode_for_sharded(d2)
    if forenc is not None:
        mins, words, bw, frame = forenc
        spec = PredEnc(name, str(data.dtype), "for", bw, valid_mode,
                       frame)
        return spec, (words, mins) + val_arrays, raw_equiv
    spec = PredEnc(name, str(data.dtype), "raw", 0, valid_mode)
    return spec, (d2,) + val_arrays, raw_equiv


def decode_pred_device_sharded(spec: PredEnc, arrays, bucket: int):
    """Per-device decode of one mesh-encoded predicate column — runs
    inside shard_map, where every array arrives as the local (1, ...)
    shard; strip the shard axis and reuse the single-device decode."""
    return decode_pred_device(spec, tuple(a[0] for a in arrays), bucket)


# -- H2D staging -------------------------------------------------------------

def stage_h2d(arrays, raw_equiv_bytes: int, what: str = "batch",
              put: bool = True):
    """Stage a pytree of host arrays for upload — THE single H2D point
    of the compressed dispatch plane: chaos's `dispatch.h2d` failpoint
    and the encoded-vs-raw byte accounting both live here, so every
    encoded transfer is injectable and audited.

    put=True (default) device_puts eagerly (async) so a pipelined
    caller controls when the transfer enqueues; put=False returns the
    host arrays unchanged for callers whose jit does its own placement
    (the mesh-sharded program: an eager put would land everything on
    one device and force a reshard hop)."""
    import jax

    from transferia_tpu.chaos.failpoints import failpoint

    failpoint("dispatch.h2d")
    leaves = jax.tree_util.tree_leaves(arrays)
    encoded = sum(int(getattr(a, "nbytes", 0)) for a in leaves)
    with trace.span("device_decode", encoded_bytes=encoded,
                    raw_equiv_bytes=int(raw_equiv_bytes), what=what):
        dev = (jax.tree_util.tree_map(jax.device_put, arrays)
               if put else arrays)
    TELEMETRY.record_dispatch(encoded, int(raw_equiv_bytes))
    return dev


# -- device-resident dict-pool masking --------------------------------------

def device_hmac_dict_pool(key: bytes, pool, n_rows: int):
    """HMAC a DictPool's values ON DEVICE, once per (pool, key).

    Returns the hexed pool (a DictPool of 64-char hex digests with the
    null sentinel emptied), memoized on the shared pool under the SAME
    memo key as the host path (transform/plugins/mask.mask_dict_column)
    — whichever strategy touches a pool first pays; the other rides the
    memo.  Row codes never cross the link: the caller keeps them and
    rebinds them to the hexed pool.

    Returns None when the pool is too large to pay for itself on this
    batch (mirrors the host-path economics) — the caller then hashes
    the referenced value subset on the HOST (mask_dict_column), still
    dict-encoded: zero link bytes either way, which is exactly what
    DeviceFusedStep._estimate_link_bytes charges for a rejected pool.
    """
    memo_key = ("hmac_hex", key)
    hexed = pool.memo_get(memo_key)
    if hexed is not None:
        TELEMETRY.record_pool_hit()
        _record_avoided_batch_bytes(pool, n_rows)
        return hexed
    if pool.n_values > 2 * max(n_rows, 1):
        return None
    with _pool_hash_lock:
        return _hash_pool_locked(key, pool, n_rows, memo_key)


def _hash_pool_locked(key: bytes, pool, n_rows: int, memo_key):
    # double-checked: a racing part thread may have hashed this pool
    # while we waited on the lock
    hexed = pool.memo_get(memo_key)
    if hexed is not None:
        TELEMETRY.record_pool_hit()
        _record_avoided_batch_bytes(pool, n_rows)
        return hexed
    from transferia_tpu.columnar.hexcol import (
        digests_to_hex,
        hex_to_varwidth,
    )
    from transferia_tpu.transform.plugins.mask import hexed_pool_from_flat

    digest_rows = _pool_digest_rows_locked(key, pool)
    hex_mat = digests_to_hex(digest_rows)
    flat, flat_off = hex_to_varwidth(hex_mat, None)
    hexed = hexed_pool_from_flat(pool, flat, flat_off)
    pool.memo_set(memo_key, hexed)
    _record_avoided_batch_bytes(pool, n_rows)
    return hexed


def _pool_digest_rows_locked(key: bytes, pool) -> np.ndarray:
    """The (n_values, 8) uint32 HMAC digest matrix of a pool's values,
    computed ON DEVICE once per (pool, key) and memoized on the shared
    pool — the common substrate of the hexed pool (single-device mask
    route) and the mesh dict route's per-device digest gather.  Caller
    holds `_pool_hash_lock`."""
    memo_key = ("hmac_digest_rows", bytes(key))
    rows = pool.memo_get(memo_key)
    if rows is not None:
        return rows
    import jax.numpy as jnp

    from transferia_tpu.columnar.batch import bucket_rows
    from transferia_tpu.ops.fused import pack_hmac_blocks, pow2_blocks
    from transferia_tpu.ops.sha256 import (
        _hmac_inner_outer,
        _hmac_key_states,
    )

    n_vals = pool.n_values
    offsets = pool.values_offsets
    lens = offsets[1:] - offsets[:-1]
    max_len = int(lens.max()) if n_vals else 0
    mb = pow2_blocks(max_len)
    blocks, n_blocks = pack_hmac_blocks(pool.values_data, offsets, mb)
    bucket = bucket_rows(max(n_vals, 1))
    if bucket != n_vals:
        blocks = np.pad(blocks, ((0, bucket - n_vals), (0, 0)))
        n_blocks = np.pad(n_blocks, (0, bucket - n_vals))
    inner, outer = _hmac_key_states(bytes(key))
    with trace.span("pool_upload", values=n_vals,
                    bytes=int(blocks.nbytes)):
        dev_blocks, dev_nblocks = stage_h2d(
            (blocks, n_blocks),
            raw_equiv_bytes=int(blocks.nbytes) + int(n_blocks.nbytes),
            what="dict_pool")
        TELEMETRY.record_h2d(int(blocks.nbytes) + int(n_blocks.nbytes))
        digests = _hmac_inner_outer(
            dev_blocks, dev_nblocks,
            (jnp.asarray(inner[0]), jnp.asarray(outer[0])), mb)
        TELEMETRY.record_launch()
        digest_rows = np.ascontiguousarray(np.asarray(digests)[:n_vals])
    TELEMETRY.record_d2h(int(digests.nbytes))
    TELEMETRY.record_pool_upload()
    pool.memo_set(memo_key, digest_rows)
    return digest_rows


def device_hmac_pool_digests(key: bytes, pool, n_rows: int
                             ) -> Optional[np.ndarray]:
    """The memoized (n_values, 8) uint32 digest matrix for the MESH
    dict route (parallel/fusedmesh.py): the sharded program gathers
    per-row digest words from it by int32 code — byte-identical to
    HMAC'ing each row's flat bytes, because equal bytes hash equal and
    the pool's null sentinel is the same empty-bytes entry the flat
    wire ships for null rows.  None when the pool is too large to pay
    for itself on this batch (same economics as the hexed-pool route:
    the caller falls back to the flat block wire)."""
    memo_key = ("hmac_digest_rows", bytes(key))
    rows = pool.memo_get(memo_key)
    if rows is not None:
        TELEMETRY.record_pool_hit()
        return rows
    if pool.n_values > 2 * max(n_rows, 1):
        return None
    with _pool_hash_lock:
        return _pool_digest_rows_locked(bytes(key), pool)


def _record_avoided_batch_bytes(pool, n_rows: int) -> None:
    """Credit the dispatch accounting with the per-batch bytes the raw
    wire WOULD have shipped for a pool-routed column: the bucket-padded
    SHA block matrix plus per-row block counts.  (Block width estimated
    from the pool's longest value — the per-row materialized max the
    raw path would use is bounded by it.)"""
    from transferia_tpu.columnar.batch import bucket_rows
    from transferia_tpu.ops.fused import pow2_blocks

    offs = pool.values_offsets
    lens = offs[1:] - offs[:-1]
    max_len = int(lens.max()) if pool.n_values else 0
    mb = pow2_blocks(max_len)
    bucket = bucket_rows(max(n_rows, 1))
    TELEMETRY.record_dispatch(0, (mb * 64 + 4) * bucket)
