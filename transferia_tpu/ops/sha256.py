"""Batched SHA-256 / HMAC-SHA256 on device (JAX).

The PII mask transformer's device backend (reference:
pkg/transformer/registry/mask/hmac_hasher.go does this per-row on CPU).
Here the whole column hashes in one XLA program: rows are padded to a
static max-block count and a lax.scan over message blocks updates each
row's hash state in parallel on the VPU (SHA-256 is pure uint32
arithmetic — rotations, xors, adds — which vectorizes over the row
dimension; there is no MXU work in this op).

Layout: messages arrive as a (N, max_blocks*64) uint8 matrix (padding
pre-applied, see prepare_padded_blocks) plus a per-row block count.  Rows
whose blocks are exhausted stop updating state via a select — the scan
length is the bucket's max blocks, keeping the compiled shape static.

Output parity with hashlib is pinned by tests (canon contract: the CPU and
TPU mask paths must produce byte-identical digests).
"""

from __future__ import annotations

import functools
from typing import Optional

import numpy as np

# jax is a hard dependency of the device kernels; host-only deployments use
# the hashlib path in transform/plugins/mask.py and never import this module
import jax
import jax.numpy as jnp

_K = np.array([
    0x428a2f98, 0x71374491, 0xb5c0fbcf, 0xe9b5dba5,
    0x3956c25b, 0x59f111f1, 0x923f82a4, 0xab1c5ed5,
    0xd807aa98, 0x12835b01, 0x243185be, 0x550c7dc3,
    0x72be5d74, 0x80deb1fe, 0x9bdc06a7, 0xc19bf174,
    0xe49b69c1, 0xefbe4786, 0x0fc19dc6, 0x240ca1cc,
    0x2de92c6f, 0x4a7484aa, 0x5cb0a9dc, 0x76f988da,
    0x983e5152, 0xa831c66d, 0xb00327c8, 0xbf597fc7,
    0xc6e00bf3, 0xd5a79147, 0x06ca6351, 0x14292967,
    0x27b70a85, 0x2e1b2138, 0x4d2c6dfc, 0x53380d13,
    0x650a7354, 0x766a0abb, 0x81c2c92e, 0x92722c85,
    0xa2bfe8a1, 0xa81a664b, 0xc24b8b70, 0xc76c51a3,
    0xd192e819, 0xd6990624, 0xf40e3585, 0x106aa070,
    0x19a4c116, 0x1e376c08, 0x2748774c, 0x34b0bcb5,
    0x391c0cb3, 0x4ed8aa4a, 0x5b9cca4f, 0x682e6ff3,
    0x748f82ee, 0x78a5636f, 0x84c87814, 0x8cc70208,
    0x90befffa, 0xa4506ceb, 0xbef9a3f7, 0xc67178f2,
], dtype=np.uint32)

_H0 = np.array([
    0x6a09e667, 0xbb67ae85, 0x3c6ef372, 0xa54ff53a,
    0x510e527f, 0x9b05688c, 0x1f83d9ab, 0x5be0cd19,
], dtype=np.uint32)


def _rotr(x, n):
    return (x >> n) | (x << (32 - n))


def _compress_batch(h, block_words):
    """One SHA-256 compression over a batch.

    h: (N, 8) uint32 states; block_words: (N, 16) uint32 big-endian words.
    Returns new (N, 8) states.  The rounds are lax loops with bounded
    unrolling — a fully unrolled 64-round body makes the XLA graph explode
    under vmap/shard_map (minutes of compile on CPU); fori_loop keeps the
    graph compact while unroll=8 still gives the VPU straight-line work.
    """
    # message schedule: w has shape (64, N)
    w = jnp.zeros((64,) + block_words.shape[:1], dtype=jnp.uint32)
    w = w.at[:16].set(jnp.transpose(block_words))

    def sched(i, w):
        x15 = w[i - 15]
        x2 = w[i - 2]
        s0 = _rotr(x15, 7) ^ _rotr(x15, 18) ^ (x15 >> 3)
        s1 = _rotr(x2, 17) ^ _rotr(x2, 19) ^ (x2 >> 10)
        return w.at[i].set(w[i - 16] + s0 + w[i - 7] + s1)

    with jax.named_scope("sha256_schedule"):
        w = jax.lax.fori_loop(16, 64, sched, w, unroll=8)
    k = jnp.asarray(_K)

    def round_fn(i, state):
        a, b, c, d, e, f, g, hh = state
        s1 = _rotr(e, 6) ^ _rotr(e, 11) ^ _rotr(e, 25)
        ch = (e & f) ^ (~e & g)
        t1 = hh + s1 + ch + k[i] + w[i]
        s0 = _rotr(a, 2) ^ _rotr(a, 13) ^ _rotr(a, 22)
        maj = (a & b) ^ (a & c) ^ (b & c)
        t2 = s0 + maj
        return (t1 + t2, a, b, c, d + t1, e, f, g)

    state = tuple(h[:, i] for i in range(8))
    with jax.named_scope("sha256_rounds"):
        a, b, c, d, e, f, g, hh = jax.lax.fori_loop(
            0, 64, round_fn, state, unroll=8
        )
    return jnp.stack([
        h[:, 0] + a, h[:, 1] + b, h[:, 2] + c, h[:, 3] + d,
        h[:, 4] + e, h[:, 5] + f, h[:, 6] + g, h[:, 7] + hh,
    ], axis=1)


@jax.named_scope("sha256_words")
def _bytes_to_words(blocks_u8):
    """(N, n_blocks, 64) uint8 -> (N, n_blocks, 16) uint32 big-endian.

    Uses bitcast + byteswap instead of strided byte gathers: slicing the
    minor dim of a uint8 tensor fights the TPU's (32,128) tiling and is
    ~400x slower than a bitcast to u32 followed by elementwise swaps.
    """
    n, nb = blocks_u8.shape[0], blocks_u8.shape[1]
    u32 = jax.lax.bitcast_convert_type(
        blocks_u8.reshape(n, nb, 16, 4), jnp.uint32
    )  # narrowing->wide bitcast consumes the trailing dim: (N, nb, 16)
    # little-endian load -> big-endian SHA word
    return (((u32 & 0xFF) << 24) | ((u32 & 0xFF00) << 8)
            | ((u32 >> 8) & 0xFF00) | (u32 >> 24))


@functools.partial(jax.jit, static_argnums=(2,))
def _sha256_padded(blocks_u8, n_blocks_per_row, max_blocks: int):
    """Hash pre-padded messages.

    blocks_u8: (N, max_blocks*64) uint8; n_blocks_per_row: (N,) int32.
    Returns (N, 8) uint32 digests.
    """
    n = blocks_u8.shape[0]
    words = _bytes_to_words(
        blocks_u8.reshape(n, max_blocks, 64)
    )  # (N, max_blocks, 16)
    h = jnp.broadcast_to(jnp.asarray(_H0), (n, 8))

    def step(h, inputs):
        block_words, idx = inputs
        new_h = _compress_batch(h, block_words)
        active = (idx < n_blocks_per_row)[:, None]
        return jnp.where(active, new_h, h), None

    with jax.named_scope("sha256_blocks"):
        h, _ = jax.lax.scan(
            step, h,
            (jnp.moveaxis(words, 1, 0), jnp.arange(max_blocks)),
        )
    return h


def prepare_padded_blocks(data: np.ndarray, offsets: np.ndarray,
                          prefix_len: int = 0,
                          max_blocks: Optional[int] = None,
                          ) -> tuple[np.ndarray, np.ndarray, int]:
    """Host-side: flat bytes+offsets -> padded SHA-256 block matrix.

    prefix_len: bytes of a (virtual) prefix already fed to the state — used
    by HMAC where the 64-byte ipad block is compressed separately; lengths
    in the padding must include it.  max_blocks: force the block bucket
    (callers sharing a compiled program across batches); None = derive.

    Returns (blocks (N, max_blocks*64) uint8, n_blocks (N,) int32,
    max_blocks).  Vectorized with numpy gathers — no per-row Python.
    """
    n = len(offsets) - 1
    lens = (offsets[1:] - offsets[:-1]).astype(np.int64)
    total_lens = lens + prefix_len
    # message + 0x80 + 8-byte length, rounded up to 64
    n_blocks = ((lens + 9 + 63) // 64).astype(np.int32)
    needed = int(n_blocks.max()) if n else 1
    if max_blocks is None:
        # bucket to powers of two so XLA compiles once per (rows, block
        # bucket), not once per batch-specific max length
        max_blocks = 1 << (needed - 1).bit_length() if needed > 1 else 1
    elif needed > max_blocks:
        raise ValueError(
            f"rows need {needed} SHA blocks > forced bucket {max_blocks}"
        )
    width = max_blocks * 64
    out = np.zeros((n, width), dtype=np.uint8)
    total = int(lens.sum())
    if total:
        # one flat scatter (no (N, W) index matrices): rows are contiguous
        # in the flat buffer, so source bytes in order are one slice; the
        # destination index of byte k of row i is i*width + k
        row_of = np.repeat(np.arange(n, dtype=np.int64), lens)
        cum = (offsets[:-1] - offsets[0]).astype(np.int64)
        intra = np.arange(total, dtype=np.int64) - np.repeat(cum, lens)
        out.reshape(-1)[row_of * width + intra] = \
            data[offsets[0]:offsets[0] + total]

    # 0x80 terminator
    rows = np.arange(n)
    out[rows, lens] = 0x80
    # 8-byte big-endian bit length at the end of the last block
    bit_lens = (total_lens * 8).astype(np.uint64)
    last = (n_blocks.astype(np.int64) * 64) - 8
    for k in range(8):
        out[rows, last + k] = ((bit_lens >> (8 * (7 - k))) & 0xFF
                               ).astype(np.uint8)
    return out, n_blocks, max_blocks


def sha256_batch(data: np.ndarray, offsets: np.ndarray) -> np.ndarray:
    """SHA-256 of each row in a flat bytes+offsets column.

    Returns (N, 32) uint8 digests.  Parity with hashlib pinned by tests.
    """
    blocks, n_blocks, max_blocks = prepare_padded_blocks(data, offsets)
    h = _sha256_padded(jnp.asarray(blocks), jnp.asarray(n_blocks),
                       max_blocks)
    return _words_to_bytes(np.asarray(h))


def _words_to_bytes(h: np.ndarray) -> np.ndarray:
    out = np.zeros((h.shape[0], 32), dtype=np.uint8)
    for i in range(8):
        out[:, 4 * i + 0] = (h[:, i] >> 24) & 0xFF
        out[:, 4 * i + 1] = (h[:, i] >> 16) & 0xFF
        out[:, 4 * i + 2] = (h[:, i] >> 8) & 0xFF
        out[:, 4 * i + 3] = h[:, i] & 0xFF
    return out


@functools.lru_cache(maxsize=64)
def _hmac_key_states(key: bytes) -> tuple[np.ndarray, np.ndarray]:
    """Precompute the per-key inner/outer states (one compression each)."""
    import hashlib

    if len(key) > 64:
        key = hashlib.sha256(key).digest()
    k = np.zeros(64, dtype=np.uint8)
    k[:len(key)] = np.frombuffer(key, dtype=np.uint8)
    ipad = (k ^ 0x36)[None, :]
    opad = (k ^ 0x5C)[None, :]

    @jax.jit
    def one_compress(block):
        # jitted even for this 1-row call: op-by-op execution would
        # compile each lax primitive of the 64 rounds separately
        words = _bytes_to_words(block.reshape(1, 1, 64))
        h = jnp.broadcast_to(jnp.asarray(_H0), (1, 8))
        return _compress_batch(h, words[:, 0])

    return (np.asarray(one_compress(jnp.asarray(ipad))),
            np.asarray(one_compress(jnp.asarray(opad))))


def hmac_device_core(blocks_u8, n_blocks_per_row, inner_state, outer_state,
                     max_blocks: int):
    """Pure-JAX HMAC core (composable inside larger jitted programs —
    the graft entry and the sharded transform step build on this)."""
    return _hmac_inner_outer_impl(
        blocks_u8, n_blocks_per_row, (inner_state, outer_state), max_blocks
    )


def _hmac_inner_outer_impl(blocks_u8, n_blocks_per_row, states,
                           max_blocks: int):
    inner_state, outer_state = states
    n = blocks_u8.shape[0]
    words = _bytes_to_words(blocks_u8.reshape(n, max_blocks, 64))
    h = jnp.broadcast_to(inner_state, (n, 8))

    def step(h, inputs):
        block_words, idx = inputs
        new_h = _compress_batch(h, block_words)
        active = (idx < n_blocks_per_row)[:, None]
        return jnp.where(active, new_h, h), None

    with jax.named_scope("hmac_inner"):
        h, _ = jax.lax.scan(
            step, h, (jnp.moveaxis(words, 1, 0), jnp.arange(max_blocks))
        )
    # outer: H(K^opad || inner_digest); inner digest is 32 bytes -> 1 block.
    # Built by concat, not .at[].set — column scatters lower terribly on TPU.
    pad_words = np.zeros(8, dtype=np.uint32)
    pad_words[0] = 0x80000000
    pad_words[7] = (64 + 32) * 8
    outer_block = jnp.concatenate(
        [h, jnp.broadcast_to(jnp.asarray(pad_words), (n, 8))], axis=1
    )
    with jax.named_scope("hmac_outer"):
        return _compress_batch(jnp.broadcast_to(outer_state, (n, 8)),
                               outer_block)


_hmac_inner_outer = functools.partial(
    jax.jit, static_argnums=(3,)
)(_hmac_inner_outer_impl)


def hmac_sha256_hex_batch(key: bytes, data: np.ndarray,
                          offsets: np.ndarray,
                          validity: Optional[np.ndarray] = None
                          ) -> tuple[np.ndarray, np.ndarray]:
    """Device backend for the mask transformer (mask.HashBackend signature).

    Returns (hex_data (uint8 flat), hex_offsets (int32)): 64-byte hex
    digests per valid row, empty for invalid rows.
    """
    n = len(offsets) - 1
    if n == 0:
        return np.zeros(0, dtype=np.uint8), np.zeros(1, dtype=np.int32)
    inner, outer = _hmac_key_states(key)
    blocks, n_blocks, max_blocks = prepare_padded_blocks(
        data, offsets, prefix_len=64
    )
    # bucket the row count so partial tail batches reuse the compiled
    # program (pad rows carry n_blocks=0 and never update state)
    from transferia_tpu.columnar.batch import bucket_rows

    bucket = bucket_rows(n)
    if bucket != n:
        blocks = np.pad(blocks, ((0, bucket - n), (0, 0)))
        n_blocks = np.pad(n_blocks, (0, bucket - n))
    h = _hmac_inner_outer(
        jnp.asarray(blocks), jnp.asarray(n_blocks),
        (jnp.asarray(inner), jnp.asarray(outer)), max_blocks,
    )
    from transferia_tpu.columnar.hexcol import (
        digests_to_hex,
        hex_to_varwidth,
    )

    hexes = digests_to_hex(np.asarray(h)[:n])  # (N, 64)

    return hex_to_varwidth(hexes, validity)


def enable_device_mask_backend() -> None:
    """Route MaskField hashing through the device kernel."""
    from transferia_tpu.transform.plugins.mask import set_hash_backend

    set_hash_backend(hmac_sha256_hex_batch)
