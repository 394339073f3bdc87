"""Fused device transform program: HMAC mask + row predicate in one launch.

Round-1 shape of the device path was one kernel per transformer (mask only)
with a host hop between steps.  This module compiles the whole device-able
run of a transformer plan — every HMAC-SHA256 masked column, hex encoding,
and the row-filter predicate — into ONE jitted XLA program per
(schema fingerprint, row bucket, width buckets), so a mask+filter transfer
does a single H2D/compute/D2H round-trip per batch.

Reference hot loops being displaced: pkg/transformer/transformation.go:22-70
(chain apply) and pkg/transformer/registry/mask/hmac_hasher.go +
registry/filter_rows (per-row Go).

Host side: rows pack into padded SHA block matrices via the C++ hostops
kernel (pack_sha_blocks — memcpy-bound, GIL-free) with a vectorized numpy
fallback; the device side is pure jnp (ops/sha256.py core), so the same
program runs on TPU and on the CPU backend (tests pin byte-parity against
hashlib on the virtual mesh).
"""

from __future__ import annotations

import functools
import time as _time
from typing import Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from transferia_tpu.columnar.batch import bucket_rows
from transferia_tpu.ops.decode import pack_mask_words
from transferia_tpu.runtime import knobs
from transferia_tpu.ops.dispatch import (
    decode_pred_device,
    encode_pred_column,
    encoding_enabled,
    stage_h2d,
    unpack_mask_host,
)
from transferia_tpu.ops.sha256 import (
    _hmac_key_states,
    hmac_device_core,
    prepare_padded_blocks,
)
from transferia_tpu.stats import trace
from transferia_tpu.stats.trace import TELEMETRY

trace.install_jit_hooks()  # compile-event telemetry rides jax monitoring

_chunk_rows_cached: Optional[int] = None


def _chunk_rows() -> int:
    """Chunk size for pipelined dispatch; 0 disables chunking.

    Defaults to 32768 rows on an accelerator backend (enough work per
    launch to amortize it, small enough for >=4 chunks per 131k batch);
    0 on the CPU backend, where "device" compute shares the host cores
    and pipelining only adds overhead.  TRANSFERIA_TPU_CHUNK_ROWS
    overrides (0 = off).
    """
    global _chunk_rows_cached
    if _chunk_rows_cached is None:
        env = knobs.env_raw("TRANSFERIA_TPU_CHUNK_ROWS")
        if env is not None:
            _chunk_rows_cached = max(0, int(env))
        else:
            from transferia_tpu.ops.linkprobe import probe_link

            link = probe_link()
            if link.backend == "cpu":
                _chunk_rows_cached = 0
            elif link.launch_overhead_s > 0.005:
                # launches this expensive cost more per chunk than the
                # overlap buys — one launch per batch, overlap rides
                # across batches instead
                _chunk_rows_cached = 0
            else:
                _chunk_rows_cached = 32768
    return _chunk_rows_cached


def set_chunk_rows(n: Optional[int]) -> None:
    """Force the pipelined-dispatch chunk size (None = re-detect)."""
    global _chunk_rows_cached
    _chunk_rows_cached = n


def _dispatch_depth() -> int:
    """Launches kept in flight by the pipelined path (H2D of chunk g+1
    staged while chunk g computes and g-1 drains).
    TRANSFERIA_TPU_DISPATCH_DEPTH overrides; floor 1."""
    return max(1, knobs.env_int("TRANSFERIA_TPU_DISPATCH_DEPTH", 2))


def _pallas_pack_enabled() -> bool:
    """Opt-in device-side ragged pack (ops/raggedpack.py; the env name
    is historical — the implementation is XLA after hardware profiling
    retired the Pallas kernel, see that module's docstring).

    Off by default: it halves H2D bytes for short strings but costs an
    extra launch; which side wins on the chip is not measured.
    """
    if knobs.env_str("TRANSFERIA_TPU_PALLAS_PACK", "") != "1":
        return False
    return jax.default_backend() == "tpu"


def pow2_blocks(max_len: int) -> int:
    """Block count bucket for a max row length (bytes, before padding)."""
    nb = (max_len + 9 + 63) // 64
    return 1 << (nb - 1).bit_length() if nb > 1 else 1


def pack_hmac_blocks(data: np.ndarray, offsets: np.ndarray,
                     max_blocks: int) -> tuple[np.ndarray, np.ndarray]:
    """Flat bytes+offsets -> (N, max_blocks*64) padded HMAC message blocks.

    The 64-byte ipad prefix block is virtual (compressed separately from the
    cached key state); lengths in the SHA padding include it (prefix_len=64).
    C++ fast path releases the GIL so part threads overlap pack with device
    compute; numpy fallback is prepare_padded_blocks.
    """
    from transferia_tpu.native import lib as native_lib

    n = len(offsets) - 1
    width = max_blocks * 64
    cdll = native_lib()
    if cdll is not None and n:
        out = np.empty((n, width), dtype=np.uint8)
        n_blocks = np.empty(n, dtype=np.int32)
        cdll.pack_sha_blocks(
            np.ascontiguousarray(data),
            np.ascontiguousarray(offsets, dtype=np.int32),
            n, width, 64, out, n_blocks,
        )
        return out, n_blocks
    blocks, n_blocks, mb = prepare_padded_blocks(
        data, offsets, prefix_len=64, max_blocks=max_blocks
    )
    return blocks, n_blocks


from transferia_tpu.columnar.hexcol import hex_to_varwidth  # noqa: F401
# (re-exported: the fused step builds its output columns with it)


class FusedMaskFilterProgram:
    """One jitted program: HMAC+hex every masked column, evaluate the keep
    predicate — recompiled only when a bucket (rows / block width) changes.

    mask_keys: HMAC key per masked column (parallel to the blocks the caller
    passes); pred_node: predicate AST or None; the caller supplies the
    predicate columns as (data, validity) arrays.
    """

    # jitted wrappers shared across instances, keyed by the predicate
    # AST repr (frozen dataclasses — the repr is the full content).  The
    # HMAC key states are traced ARGUMENTS, not closure constants, so
    # every per-part sink chain (the snapshot loader builds one chain
    # per part) reuses the same compiled program instead of paying an
    # XLA compile per part.  Bounded FIFO: a long-lived worker cycling
    # through transfers with distinct predicate constants must not pin
    # an executable per constant forever.
    _jit_cache: dict = {}
    _JIT_CACHE_MAX = 64

    def __init__(self, mask_keys: Sequence[bytes], pred_node=None):
        self._states = []
        for key in mask_keys:
            inner, outer = _hmac_key_states(bytes(key))
            self._states.append((jnp.asarray(inner[0]),
                                 jnp.asarray(outer[0])))
        self._pred_fn = None
        if pred_node is not None:
            from transferia_tpu.predicate.device import compile_mask_jnp

            self._pred_fn = compile_mask_jnp(pred_node)
        cache_key = repr(pred_node)
        cached = FusedMaskFilterProgram._jit_cache.get(cache_key)
        if cached is not None:
            self._jit = cached
            return
        # bind the closure to THIS instance's pred_fn (an equal AST
        # compiles to an identical mask fn, so cache sharing is sound)
        pred_fn = self._pred_fn

        def program(blocks_t, nblocks_t, states_t, pred_arrays, spec):
            # spec (static): (bucket, max_blocks per column, PredEnc per
            # predicate column, pack_keep).  Raw (N, 8) u32 digests leave
            # the device — 32 bytes/row vs 64 for hex; the host
            # LUT-expands (columnar/hexcol.py).  On bandwidth-starved
            # links (see ops/linkprobe.py) the return payload is kept
            # minimal: with pack_keep the keep mask returns bit-packed.
            bucket, max_blocks_t, pred_specs, pack_keep = spec
            # the scopes name the program's parts in a profiler trace;
            # the function itself stays `program` (module `jit_program`)
            with jax.named_scope("mask_hmac"):
                digests = tuple(
                    hmac_device_core(b, nb, st[0], st[1], mb)
                    for b, nb, st, mb in zip(
                        blocks_t, nblocks_t, states_t, max_blocks_t
                    )
                )
            if pred_fn is not None:
                # predicate columns arrive in their dispatch encodings
                # (bit-packed validity, delta ints) and decode on device
                with jax.named_scope("pred_decode"):
                    cols = {
                        ps.name: decode_pred_device(ps, arrs, bucket)
                        for ps, arrs in zip(pred_specs, pred_arrays)
                    }
                with jax.named_scope("predicate"):
                    keep = pred_fn(cols, bucket)
                if pack_keep:
                    keep = pack_mask_words(keep, bucket)
            else:
                keep = jnp.zeros((0,), dtype=jnp.bool_)  # unused sentinel
            return digests, keep

        self._jit = jax.jit(program, static_argnums=(4,))
        cache = FusedMaskFilterProgram._jit_cache
        while len(cache) >= FusedMaskFilterProgram._JIT_CACHE_MAX:
            cache.pop(next(iter(cache)))
        cache[cache_key] = self._jit

    def run(self, mask_cols: Sequence[tuple[np.ndarray, np.ndarray]],
            pred_cols: dict[str, tuple[np.ndarray, Optional[np.ndarray]]],
            n_rows: int, states: Optional[list] = None
            ) -> tuple[list[np.ndarray], Optional[np.ndarray]]:
        """mask_cols: per masked column (flat uint8 data, int32 offsets).
        pred_cols: name -> (fixed-width data, validity or None).
        states: HMAC key states parallel to mask_cols (defaults to the
        constructor's full set — callers that peeled dict columns off to
        the pool route pass the surviving subset).
        Returns ([hex (n_rows, 64) per masked column], keep mask or None).

        On an accelerator backend, large batches run as a chunked
        double-buffered pipeline: the host packs+stages chunk k+1's H2D
        while the device computes chunk k and the host drains chunk k-1
        (D2H), so H2D / compute / D2H / pack overlap instead of
        serializing per batch.  One chunk size -> one compiled program.
        """
        from transferia_tpu.chaos.failpoints import failpoint

        failpoint("device.dispatch")
        # one parent span per batch run: pack / device_dispatch /
        # device_wait nest under it, so a chunked pipelined run reads
        # as one causally-grouped unit in the timeline
        with trace.span("fused_run", rows=n_rows):
            chunk = _chunk_rows()
            if chunk and n_rows > chunk and not _pallas_pack_enabled():
                return self._run_pipelined(mask_cols, pred_cols, n_rows,
                                           chunk, states=states)
            return self._run_single(mask_cols, pred_cols, n_rows, states)

    def _stage(self, mask_cols, pred_cols, n_rows, bucket, states=None):
        """Pack + encode on host and enqueue the (async) H2D for one
        chunk — compute does NOT launch here, so a pipelined caller can
        overlap this chunk's transfer with the previous chunk's
        kernels.  Returns the staged device handles."""
        states = self._states if states is None else list(states)
        use_pallas_pack = _pallas_pack_enabled()
        blocks_t, nblocks_t, mb_t = [], [], []
        with trace.span("pack"):
            self._pack_inputs(mask_cols, n_rows, bucket,
                              use_pallas_pack, blocks_t, nblocks_t, mb_t)
            pred_specs, pred_arrays, pred_raw = self._encode_pred(
                pred_cols, n_rows, bucket)
        blocks_raw = sum(int(b.nbytes) + int(nb.nbytes)
                         for b, nb in zip(blocks_t, nblocks_t))
        dev_blocks, dev_nblocks, dev_pred = stage_h2d(
            (tuple(blocks_t), tuple(nblocks_t), tuple(pred_arrays)),
            raw_equiv_bytes=blocks_raw + pred_raw)
        h2d = (blocks_raw
               + sum(int(a.nbytes) for arrs in pred_arrays for a in arrs))
        TELEMETRY.record_h2d(h2d)
        pack_keep = self._pred_fn is not None and encoding_enabled()
        spec = (bucket, tuple(mb_t), tuple(pred_specs), pack_keep)
        return (dev_blocks, dev_nblocks, dev_pred, tuple(states), spec,
                n_rows, h2d)

    def _launch(self, staged):
        """Launch the jitted program over a staged chunk (async);
        returns the device handles without blocking on the result."""
        dev_blocks, dev_nblocks, dev_pred, states, spec, rows, h2d = staged
        TELEMETRY.record_launch()
        with trace.span("device_dispatch", bytes=h2d, rows=rows):
            hexes_dev, keep_dev = self._jit(
                dev_blocks, dev_nblocks, states, dev_pred, spec,
            )
        return hexes_dev, keep_dev, rows, spec[3]

    def _dispatch(self, mask_cols, pred_cols, n_rows, bucket,
                  states=None):
        staged = self._stage(mask_cols, pred_cols, n_rows, bucket,
                             states)
        hexes_dev, keep_dev, _rows, packed = self._launch(staged)
        return hexes_dev, keep_dev, packed

    def _encode_pred(self, pred_cols, n_rows, bucket):
        """Per-column dispatch encodings (ops/dispatch.py): bit-packed
        validity + delta ints when they shrink, raw otherwise."""
        enc = encoding_enabled()
        specs, arrays, raw_total = [], [], 0
        for name, (data, validity) in pred_cols.items():
            spec, arrs, raw = encode_pred_column(
                name, data, validity, n_rows, bucket, enc)
            specs.append(spec)
            arrays.append(arrs)
            raw_total += raw
        return specs, arrays, raw_total

    def _pack_inputs(self, mask_cols, n_rows, bucket,
                     use_pallas_pack, blocks_t, nblocks_t, mb_t):
        for data, offsets in mask_cols:
            lens = offsets[1:] - offsets[:-1]
            max_len = int(lens.max()) if n_rows else 0
            mb = pow2_blocks(max_len)
            if use_pallas_pack:
                from transferia_tpu.ops.raggedpack import (
                    pack_blocks_device,
                )

                width = mb * 64
                flat = np.ascontiguousarray(data)
                total = int(offsets[-1])
                if len(flat) < total + width:
                    flat = np.pad(flat, (0, total + width - len(flat)))
                blocks_dev, nblocks_dev = pack_blocks_device(
                    flat, np.ascontiguousarray(offsets, dtype=np.int32),
                    bucket, mb,
                )
                # zero pad rows' block count so they never update state
                if bucket != n_rows:
                    row = jnp.arange(bucket, dtype=jnp.int32)
                    nblocks_dev = jnp.where(row < n_rows, nblocks_dev, 0)
                blocks_t.append(blocks_dev)
                nblocks_t.append(nblocks_dev)
                mb_t.append(mb)
                continue
            blocks, n_blocks = pack_hmac_blocks(data, offsets, mb)
            if bucket != n_rows:
                blocks = np.pad(blocks, ((0, bucket - n_rows), (0, 0)))
                n_blocks = np.pad(n_blocks, (0, bucket - n_rows))
            # numpy here — the H2D is staged explicitly by stage_h2d so
            # the pipelined path controls when the transfer enqueues
            blocks_t.append(blocks)
            nblocks_t.append(n_blocks)
            mb_t.append(mb)

    def _collect(self, digests_dev, keep_dev, n_rows, packed_keep=False
                 ) -> tuple[list[np.ndarray], Optional[np.ndarray]]:
        """Block on D2H, trim bucket padding, hex-expand on host."""
        from transferia_tpu.columnar.hexcol import digests_to_hex

        hexes = []
        t0 = _time.perf_counter()
        with trace.span("device_wait") as sp:
            for h in digests_dev:
                # digests_to_hex allocates fresh output, so the sliced
                # view never pins the bucket-padded transfer buffer
                arr = np.asarray(h)[:n_rows]
                hexes.append(digests_to_hex(arr))
            if self._pred_fn is None:
                keep = None
            elif packed_keep:
                keep = unpack_mask_host(np.asarray(keep_dev), n_rows)
            else:
                keep = np.asarray(keep_dev)[:n_rows]
            d2h = sum(int(h.nbytes) for h in digests_dev)
            if keep_dev is not None and self._pred_fn is not None:
                d2h += int(keep_dev.nbytes)
            if sp:  # args must attach before the span ends
                sp.add(bytes=d2h, rows=n_rows)
        TELEMETRY.record_d2h(d2h)
        TELEMETRY.record_device_wait(_time.perf_counter() - t0)
        return hexes, keep

    def _run_single(self, mask_cols, pred_cols, n_rows, states=None):
        hexes_dev, keep_dev, packed = self._dispatch(
            mask_cols, pred_cols, n_rows, bucket_rows(n_rows), states)
        return self._collect(hexes_dev, keep_dev, n_rows, packed)

    def _run_pipelined(self, mask_cols, pred_cols, n_rows, chunk,
                       depth: Optional[int] = None, states=None):
        """Split the batch into fixed-size chunks and keep `depth` device
        launches in flight, with one chunk's H2D always staged AHEAD of
        the compute launches: stage(k+1) overlaps compute(k) and
        D2H(k-1), so the link and the chip work simultaneously."""
        from collections import deque

        if depth is None:
            depth = _dispatch_depth()
        staged_q: deque = deque()
        inflight: deque = deque()
        hex_parts: list[list[np.ndarray]] = []
        keep_parts: list[np.ndarray] = []

        def launch_oldest():
            h_dev, k_dev, rows, packed = self._launch(staged_q.popleft())
            inflight.append((h_dev, k_dev, rows, packed))

        def drain_one():
            h_dev, k_dev, rows, packed = inflight.popleft()
            hexes, keep = self._collect(h_dev, k_dev, rows, packed)
            hex_parts.append(hexes)
            if keep is not None:
                keep_parts.append(keep)

        for lo in range(0, n_rows, chunk):
            hi = min(lo + chunk, n_rows)
            rows = hi - lo
            sub_mask = []
            for data, offsets in mask_cols:
                base = int(offsets[lo])
                sub_off = (offsets[lo:hi + 1] - base).astype(
                    offsets.dtype, copy=False)
                sub_mask.append(
                    (data[base:int(offsets[hi])], sub_off))
            sub_pred = {}
            for name, (data, validity) in pred_cols.items():
                sub_pred[name] = (
                    data[lo:hi],
                    validity[lo:hi] if validity is not None else None,
                )
            staged_q.append(self._stage(sub_mask, sub_pred, rows,
                                        bucket_rows(rows), states))
            # launch all but the freshest chunk: its H2D streams while
            # the previous chunk's kernels run (double-buffered H2D)
            while len(staged_q) > 1:
                launch_oldest()
            while len(inflight) > depth:
                drain_one()
        while staged_q:
            launch_oldest()
        while inflight:
            drain_one()
        n_mask = len(mask_cols)
        hexes = [
            np.concatenate([p[i] for p in hex_parts])
            if hex_parts else np.empty((0, 64), dtype=np.uint8)
            for i in range(n_mask)
        ]
        keep = (np.concatenate(keep_parts)
                if self._pred_fn is not None and keep_parts else
                (np.empty(0, dtype=np.bool_)
                 if self._pred_fn is not None else None))
        return hexes, keep
