"""Device-side ragged pack: var-width rows -> padded SHA block matrices.

The fused mask program (ops/fused.py) consumes (N, max_blocks*64) padded
message matrices.  The portable feed path packs them on the host (C++
pack_sha_blocks) and ships the padded matrix to the device — ~2.5x the
bytes of the raw ragged column for short strings.  This module moves the
pack onto the device: the host ships the *flat* byte buffer + offsets,
and the device builds the padded matrix (row gather + SHA padding
arithmetic: 0x80 terminator, big-endian bit length with the virtual HMAC
ipad prefix block accounted).

History, because it is instructive: this was first written as a Pallas
kernel (per-row async DMAs HBM->VMEM, grid over 32-row tiles) on the
theory that XLA lowers ragged byte gathers poorly.  Profiling on a real
v5e falsified both halves: (a) Mosaic cannot express the kernel at all —
rank-1 SMEM blocks must be 128-multiples, and per-row `width`-byte
slices of a 1D buffer violate the (1024)(128) tiling ("Slice shape along
dimension 0 must be aligned to tiling (1024), but is 128"); (b) the
plain XLA formulation below — one `jnp.take` with a computed (N, width)
index matrix plus vectorized padding — runs at sub-millisecond per 131k
rows on the same chip, i.e. at HBM-bandwidth, with byte parity against
the C++ host pack.  Hand-scheduling lost to the compiler; keep the
compiler (it replaced ops/ragged_pallas.py outright).

Opt-in (TRANSFERIA_TPU_PALLAS_PACK=1, historical name): it halves H2D
traffic for short strings at the cost of one extra launch per batch.
Which side wins end to end on the chip is not measured (ROADMAP D3).
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnums=(3,))
def _pack_xla(flat, starts, lens, width: int):
    col = jnp.arange(width, dtype=jnp.int32)[None, :]
    idx = starts[:, None] + col
    raw = jnp.take(flat, idx, axis=0)            # (N, width) row gather
    lens2 = lens[:, None]
    msg = jnp.where(col < lens2, raw, 0)
    msg = jnp.where(col == lens2, jnp.uint8(0x80), msg)
    nb = (lens + 9 + 63) // 64
    pos = (nb * 64 - 8)[:, None]                 # length field start
    k = col - pos
    bits = ((lens + 64) * 8)[:, None]            # +64: HMAC ipad prefix
    shift = 8 * (7 - k)
    lenbyte = jnp.where(
        (k >= 0) & (k < 8) & (shift < 32),
        jax.lax.shift_right_logical(
            jnp.broadcast_to(bits, k.shape), jnp.clip(shift, 0, 31),
        ) & 0xFF,
        0,
    )
    msg = jnp.where((k >= 0) & (k < 8), lenbyte.astype(jnp.uint8), msg)
    return msg.astype(jnp.uint8), nb


def pack_blocks_device(flat_padded: np.ndarray, offsets: np.ndarray,
                       n_rows_bucket: int, max_blocks: int):
    """Pack ragged rows into padded SHA blocks on the device.

    flat_padded: (B + >=width slack,) uint8 — row gathers may overread up
    to width bytes past the last row; offsets: (n+1,) int32 for the true
    rows.  Returns device arrays (blocks (bucket, width) uint8, n_blocks
    (bucket,) int32); pad rows' content is garbage-but-valid (they re-read
    the final offset) and must be masked or sliced by the caller.
    """
    width = max_blocks * 64
    n = len(offsets) - 1
    starts = np.empty(n_rows_bucket, dtype=np.int32)
    lens = np.zeros(n_rows_bucket, dtype=np.int32)
    starts[:n] = offsets[:-1]
    starts[n:] = offsets[-1]
    lens[:n] = offsets[1:] - offsets[:-1]
    if n and int(lens[:n].max()) + 9 > width:
        # same contract as the host pack (prepare_padded_blocks): a row
        # that needs more blocks than max_blocks must fail loudly — the
        # padding arithmetic would silently truncate it otherwise
        raise ValueError(
            f"row of {int(lens[:n].max())} bytes needs more than "
            f"{max_blocks} SHA blocks")
    assert len(flat_padded) >= int(offsets[-1]) + width, \
        "flat buffer needs >= width slack bytes for row overreads"
    return _pack_xla(jnp.asarray(flat_padded), jnp.asarray(starts),
                     jnp.asarray(lens), width)
