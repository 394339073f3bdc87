"""Device-side columnar decode kernels.

BASELINE.json config 3's north star is literally "columnar decode on
TPU": the hot shape of parquet decode is bit-unpack of RLE_DICTIONARY
codes followed by a dictionary gather, and both map cleanly onto the
chip — unpack is pure vectorized shift/mask arithmetic (VPU), the gather
rides HBM bandwidth.  The END-TO-END parquet decode stays on the host
today (the native reader decodes dict pages, ops/dispatch.py re-encodes
for the link); this module holds the kernels the fused program decodes
its encoded inputs with.  decode_dict_run has no caller outside
tests/unit/test_device_decode.py (ROADMAP D10).

Scope mirrors the native decoder's hot path (native/parquetdec.cpp
RleDecoder + dict gather):
  - unpack_bits: n fixed-width (<=32 bit) values from a little-endian
    packed uint32 word stream — the body of a bit-packed RLE run
  - decode_dict_run: unpack + jnp.take through the dictionary pool

Run HEADERS (varint framing) stay on the host: they are a sequential
byte-stream parse of a few bytes per ~hundreds of values, the opposite
of device-shaped work.  The host splits runs; the device does the
per-value arithmetic.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnums=(1, 2))
def unpack_bits(words: jax.Array, bit_width: int, n: int) -> jax.Array:
    """values[i] = bits [i*bw, (i+1)*bw) of the packed little-endian
    stream, as int32.  bit_width must be 0 < bw <= 32; a value spans at
    most two 32-bit words ((hi:lo) >> off, shift-by-32 guarded)."""
    if not 0 < bit_width <= 32:
        raise ValueError(f"bit_width {bit_width} outside (0, 32]")
    return _unpack_core(words, bit_width, n)


@functools.partial(jax.jit, static_argnums=(2, 3))
def decode_dict_run(words: jax.Array, pool: jax.Array, bit_width: int,
                    n: int) -> jax.Array:
    """Bit-unpack n dictionary codes and gather their pool values —
    the device half of an RLE_DICTIONARY data page."""
    codes = unpack_bits(words, bit_width, n)
    with jax.named_scope("dict_gather"):
        return jnp.take(pool, codes, axis=0, mode="clip")


@jax.named_scope("pool_acc_gather")
def gather_pool_accumulators(accs: jax.Array,
                             codes: jax.Array) -> jax.Array:
    """Dict-native fingerprint gather (ops/rowhash.py device backend):
    per-row lane accumulators from per-POOL-ENTRY accumulators by int32
    code — the reduction plane consuming dict codes directly, the same
    HBM-bandwidth shape as decode_dict_run's value gather.  Traceable
    inline; codes padded past the pool clip to entry 0 (the caller's
    rowmask zeroes those lanes)."""
    return jnp.take(accs, codes, mode="clip")


@functools.partial(jax.jit, static_argnums=(1,))
def unpack_validity(words: jax.Array, n: int) -> jax.Array:
    """Packed little-endian validity bitmap -> (n,) bool.

    The width-1 specialization of unpack_bits: a batch's null bitmap
    ships as n/8 bytes instead of n bool bytes (the compressed-dispatch
    plane's cheapest win — ops/dispatch.py packs, this unpacks)."""
    return _unpack_core(words, 1, n).astype(jnp.bool_)


@jax.named_scope("delta_decode")
def delta_prefix_sum(words: jax.Array, base: jax.Array, bit_width: int,
                     n: int) -> jax.Array:
    """Zigzag-delta decode: values[i] = base + Σ deltas[0..i], int32.

    The device half of the delta+bit-pack integer encoding
    (ops/dispatch.encode_delta): deltas arrive zigzag-encoded so the
    unpacked codes are non-negative; the prefix sum reconstructs the
    column exactly (encode rejects widths > 30 bits, so every partial
    sum fits int32 with no wraparound).  Traceable inline — callers
    inside larger jitted programs use this form directly."""
    zz = _unpack_core(words, bit_width, n)
    deltas = (zz >> 1) ^ -(zz & 1)
    return base.astype(jnp.int32) + jnp.cumsum(deltas, dtype=jnp.int32)


delta_decode = jax.jit(delta_prefix_sum, static_argnums=(2, 3))


@jax.named_scope("for_decode")
def for_frame_decode(words: jax.Array, mins: jax.Array, bit_width: int,
                     frame: int, n: int) -> jax.Array:
    """Frame-of-reference decode: values[i] = mins[i // frame] + rel[i].

    The device half of the FOR integer encoding
    (ops/dispatch.encode_for): clustered-but-unsorted ids whose zigzag
    deltas are too wide for the delta wire still pack tightly once each
    static-size frame subtracts its own minimum.  rel values arrive
    bit-packed; int32 adds wrap two's-complement, so a 32-bit rel span
    reconstructs exactly for any value that fits int32 (the encoder
    guards that).  Traceable inline — callers inside larger jitted
    programs use this form directly; n must be a multiple of `frame`
    (row buckets are, for the power-of-two frame sizes the encoder
    emits)."""
    rel = _unpack_core(words, bit_width, n)
    base = jnp.repeat(mins.astype(jnp.int32), frame,
                      total_repeat_length=n)
    return base + rel


for_decode = jax.jit(for_frame_decode, static_argnums=(2, 3, 4))


@jax.named_scope("keep_pack")
def pack_mask_words(bits: jax.Array, n: int) -> jax.Array:
    """(n,) bool -> packed little-endian uint32 words (device side).

    The D2H twin of unpack_validity: the fused program returns its keep
    mask as n/8 bytes instead of n bool bytes.  n must be a multiple of
    32 (row buckets are — columnar.batch._BUCKETS).  Traceable inline."""
    b = bits.reshape(n // 32, 32).astype(jnp.uint32)
    weights = jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32)
    return (b * weights).sum(axis=1).astype(jnp.uint32)


@jax.named_scope("unpack_bits")
def _unpack_core(w: jax.Array, bit_width: int, n: int) -> jax.Array:
    """unpack_bits body without the jit wrapper (traced inline).

    TPU gathers run orders of magnitude below HBM speed, so the hot
    shape (n a multiple of 32) avoids them entirely: every group of 32
    values consumes exactly bit_width words, so reshaping to
    (n/32, bit_width) makes each lane's word indices STATIC — the
    unpack becomes column slices + shifts, pure VPU work.  Ragged n
    falls back to the gather form."""
    w = w.astype(jnp.uint32)
    bw = bit_width
    if n % 32 == 0 and len(w.shape) == 1:
        g = n // 32
        need = g * bw
        wg = w[:need].reshape(g, bw)
        mask = (jnp.uint32((1 << bw) - 1) if bw < 32
                else jnp.uint32(0xFFFFFFFF))
        lanes = []
        for j in range(32):
            k, off = divmod(j * bw, 32)
            lo = wg[:, k] >> jnp.uint32(off)
            if off + bw > 32:
                lo = lo | (wg[:, k + 1] << jnp.uint32(32 - off))
            lanes.append(lo & mask)
        return jnp.stack(lanes, axis=1).reshape(n).astype(jnp.int32)
    starts = jnp.arange(n, dtype=jnp.uint32) * jnp.uint32(bw)
    wi = (starts >> 5).astype(jnp.int32)
    off = (starts & 31).astype(jnp.uint32)
    lo = jnp.take(w, wi, mode="clip")
    hi = jnp.take(w, wi + 1, mode="clip")
    upper = jnp.where(off > 0,
                      hi << ((jnp.uint32(32) - off) & jnp.uint32(31)),
                      jnp.uint32(0))
    v = (lo >> off) | upper
    if bw < 32:
        v = v & jnp.uint32((1 << bw) - 1)
    return v.astype(jnp.int32)
