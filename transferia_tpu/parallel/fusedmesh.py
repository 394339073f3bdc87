"""Mesh-sharded fused transform program for arbitrary schemas.

This is the multi-chip form of ops/fused.FusedMaskFilterProgram — the
PRODUCTION chain step, not a demo: N HMAC-masked var-width columns (each
with its own block width) + a compiled predicate over arbitrary numeric
columns, jitted once per (rows-per-device bucket, block widths) and
shard_map'd over the mesh.

Sharding layout (scaling-book recipe: pick a mesh, annotate shardings,
let XLA insert collectives):
- the ROW axis shards over every mesh axis (('data','model')) — the
  mask+filter step is row-parallel, so all chips contribute;
- per-column SHA block matrices stay per-device-local (no resharding);
- the only cross-chip traffic is two psums: the global kept-row count
  and the target-shard histogram (digest % n_shards) that a sharded
  ClickHouse writer uses to balance inserts (providers/clickhouse).
  On hardware these lower to ICI all-reduces.

Integration: transform/fused.DeviceFusedStep builds this program instead
of the single-device one when >1 jax device is visible (and the batch is
large enough to shard), so `build_chain` output is mesh-sharded with no
caller changes.  Byte parity with the host path is pinned by
tests/unit/test_parallel_fused.py and the multi-device e2e.
"""

from __future__ import annotations

import threading
from typing import Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from transferia_tpu.columnar.batch import bucket_rows
from transferia_tpu.columnar.hexcol import digests_to_hex
from transferia_tpu.ops.fused import (
    pack_hmac_blocks,
    pow2_blocks,
)
from transferia_tpu.ops.sha256 import _hmac_key_states, hmac_device_core
from transferia_tpu.stats import trace
from transferia_tpu.stats.trace import TELEMETRY


def default_mesh(devices=None) -> Mesh:
    """1×N row-parallel view is folded into the standard 2D mesh."""
    from transferia_tpu.parallel.mesh import make_mesh

    return make_mesh(devices=devices)


class DictMaskInput:
    """A dict-encoded masked column on the mesh wire: the row CODES
    shard over the row axis (4 bytes/row) and the pool's memoized HMAC
    digest matrix (ops/dispatch.device_hmac_pool_digests) replicates
    per device — the sharded program gathers per-row digest words by
    code instead of hashing per-row SHA block matrices, byte-identical
    because equal bytes hash equal and null rows carry the pool's
    empty-bytes sentinel code (exactly what the flat wire ships for a
    null row).  `raw_block_bytes_per_row` is what the flat route would
    have shipped for this column (the honesty number the compression
    accounting charges)."""

    __slots__ = ("codes", "digests", "raw_block_bytes_per_row")

    def __init__(self, codes: np.ndarray, digests: np.ndarray,
                 raw_block_bytes_per_row: int):
        self.codes = np.ascontiguousarray(codes, dtype=np.int32)
        self.digests = np.ascontiguousarray(digests, dtype=np.uint32)
        self.raw_block_bytes_per_row = int(raw_block_bytes_per_row)


def dict_mask_input(key: bytes, col) -> Optional[DictMaskInput]:
    """Build the mesh wire form of a lazy-dict masked column, or None
    when the pool's economics reject device hashing for this batch
    (the caller then falls back to the flat block wire)."""
    from transferia_tpu.ops.dispatch import device_hmac_pool_digests
    from transferia_tpu.ops.fused import pow2_blocks

    pool = col.dict_enc.pool
    digests = device_hmac_pool_digests(bytes(key), pool, col.n_rows)
    if digests is None:
        return None
    offs = pool.values_offsets
    lens = offs[1:] - offs[:-1]
    max_len = int(lens.max()) if pool.n_values else 0
    mb = pow2_blocks(max_len)
    return DictMaskInput(col.dict_enc.indices, digests, mb * 64 + 4)


class ShardedFusedProgram:
    """Row-sharded HMAC mask + predicate over a device mesh.

    Same host-side contract as FusedMaskFilterProgram.run(); adds two
    collective outputs kept as run() side-stats: global kept-row count
    and the digest shard histogram (`last_kept`, `last_shard_hist`).
    """

    # jitted programs shared across instances, like
    # FusedMaskFilterProgram._jit_cache and for the same reason: the
    # snapshot loader builds one chain per part, and a per-instance jit
    # made every part of a multi-chip snapshot pay a full XLA compile.
    # Keyed by everything the traced program closes over (predicate AST
    # repr, mesh devices and shape, shard count) plus the per-call
    # statics; the HMAC key states are traced ARGUMENTS.  Bounded FIFO.
    _jit_cache: dict = {}
    _JIT_CACHE_MAX = 64
    # signatures (jit + statics + argument shapes) that have compiled.
    # A snapshot's part threads reach a new signature together; left to
    # themselves each compiles it (jax.jit does not share a compile in
    # flight), so the first call of a signature is made under a lock
    _compiled_sigs: set = set()
    _compile_lock = threading.Lock()

    def __init__(self, mask_keys: Sequence[bytes], pred_node,
                 mesh: Optional[Mesh] = None, n_shards: int = 16):
        self.mesh = mesh or default_mesh()
        self._program_key = (
            repr(pred_node),
            tuple(d.id for d in self.mesh.devices.flat),
            tuple(self.mesh.shape.items()),
            n_shards,
        )
        self.n_dev = int(np.prod(list(self.mesh.shape.values())))
        self.n_shards = n_shards
        self._states = []
        for key in mask_keys:
            inner, outer = _hmac_key_states(bytes(key))
            self._states.append((jnp.asarray(inner[0]),
                                 jnp.asarray(outer[0])))
        self._pred_fn = None
        if pred_node is not None:
            from transferia_tpu.predicate.device import compile_mask_jnp

            self._pred_fn = compile_mask_jnp(pred_node)
        self.last_kept: int = 0
        self.last_shard_hist: Optional[np.ndarray] = None
        self._lock = threading.Lock()
        self._compiled: dict = {}

        row_axes = tuple(self.mesh.axis_names)  # rows over the full mesh

        def per_device(blocks_t, nblocks_t, states_t, codes_t, digs_t,
                       pred_arrays, valid_in, max_blocks_t, pred_specs,
                       valid_mode, bucket, routes):
            from transferia_tpu.ops.decode import unpack_validity
            from transferia_tpu.ops.dispatch import (
                decode_pred_device_sharded,
            )

            # encoded wire: predicate columns and the run-validity mask
            # arrive per-shard encoded (leading device axis of 1 locally)
            # and reconstruct HERE, on device, before the predicate runs
            if valid_mode == "raw":
                valid = valid_in[0]
            else:
                valid = unpack_validity(valid_in[0], bucket)
            with jax.named_scope("pred_decode"):
                pred_cols = {
                    name: decode_pred_device_sharded(
                        spec, pred_arrays[name], bucket)
                    for name, spec in pred_specs
                }
            rows_local = bucket
            # raw digest words leave the device (32 B/row, host LUT hex
            # expansion — same contract as FusedMaskFilterProgram).
            # Flat columns hash their sharded SHA block matrices; dict
            # columns GATHER per-row digest words from the replicated
            # pool digest matrix by their sharded int32 codes — equal
            # bytes hash equal, so the outputs are byte-identical
            with jax.named_scope("mask_hmac"):
                flat_digests = [
                    hmac_device_core(b, nb, st[0], st[1], mb)
                    for b, nb, st, mb in zip(
                        blocks_t, nblocks_t, states_t, max_blocks_t
                    )
                ]
            with jax.named_scope("dict_digest_gather"):
                dict_digests = [
                    jnp.take(dg, cd, axis=0, mode="clip")
                    for cd, dg in zip(codes_t, digs_t)
                ]
            fi = di = 0
            ordered = []
            for r in routes:  # reassemble the caller's column order
                if r == "dict":
                    ordered.append(dict_digests[di])
                    di += 1
                else:
                    ordered.append(flat_digests[fi])
                    fi += 1
            digests = tuple(ordered)
            if self._pred_fn is not None:
                with jax.named_scope("predicate"):
                    keep = self._pred_fn(pred_cols, rows_local) & valid
            else:
                keep = valid
            # cross-chip collectives: global kept count + target-shard
            # histogram over the first masked column's digest words
            # (digests[0] is already computed above — XLA CSEs the reuse)
            with jax.named_scope("shard_hist"):
                if digests:
                    shard = (digests[0][:, 0]
                             % jnp.uint32(self.n_shards)).astype(jnp.int32)
                else:
                    # a run of filters alone has no digest to spread
                    # rows by: the kept rows count under shard 0
                    shard = jnp.zeros(keep.shape, dtype=jnp.int32)
                hist = jnp.zeros((self.n_shards,), dtype=jnp.int32).at[
                    shard].add(keep.astype(jnp.int32))
            with jax.named_scope("mesh_psum"):
                hist = jax.lax.psum(hist, axis_name=row_axes)
                kept = jax.lax.psum(keep.sum(), axis_name=row_axes)
            out_keep = (keep if self._pred_fn is not None
                        else jnp.zeros((0,), dtype=jnp.bool_))
            return digests, out_keep, hist, kept

        self._per_device = per_device

    def _get_compiled(self, routes: tuple, pred_key: tuple,
                      valid_mode: str):
        """routes: "flat"/"dict" per masked column in caller order;
        pred_key: ((name, PredEnc, n_arrays), ...) sorted by name —
        both shape the traced program, so they key the cache."""
        key = (routes, pred_key, valid_mode)
        fn = self._compiled.get(key)
        if fn is not None:
            return fn
        n_mask = len(routes)
        n_flat = sum(1 for r in routes if r == "flat")
        n_dict = n_mask - n_flat
        shared = ShardedFusedProgram._jit_cache
        with self._lock:
            fn = self._compiled.get(key)
            if fn is None:
                # an equal (predicate, mesh, shard count) traces to an
                # identical program, so another instance's jit is ours
                fn = shared.get(self._program_key + key)
                if fn is not None:
                    self._compiled[key] = fn
            if fn is None:
                row_axes = tuple(self.mesh.axis_names)
                rows = P(row_axes)
                pred_specs = tuple((name, spec)
                                   for name, spec, _n in pred_key)
                in_specs = (
                    (P(row_axes, None),) * n_flat,   # blocks per column
                    (rows,) * n_flat,                # n_blocks per column
                    tuple((P(), P()) for _ in range(n_flat)),  # key states
                    (rows,) * n_dict,                # dict codes (total,)
                    (P(),) * n_dict,                 # digest matrices,
                    # replicated: every device holds the whole (small)
                    # pool digest table its local codes gather from
                    # encoded pred arrays carry a leading device axis;
                    # sharding it hands each device its own shard's words
                    {name: tuple(rows for _ in range(n_arr))
                     for name, _spec, n_arr in pred_key},
                    rows,                            # valid (2-D / words)
                )
                out_specs = (
                    (P(row_axes, None),) * n_mask,
                    rows if self._pred_fn is not None else P(row_axes),
                    P(),                             # histogram
                    P(),                             # kept count
                )
                # max_blocks + bucket must stay static: strip them from
                # specs and close over them per call instead.  Named as
                # ops/fused.py names its own: the profiler shows one
                # module name, jit_program, on one chip and on a mesh
                def program(blocks_t, nblocks_t, states_t, codes_t,
                            digs_t, pred_arrays, valid_arr,
                            max_blocks_t, bucket):
                    body = shard_map(
                        lambda b, nb, st, cd, dg, pa, v:
                        self._per_device(
                            b, nb, st, cd, dg, pa, v, max_blocks_t,
                            pred_specs, valid_mode, bucket, routes),
                        mesh=self.mesh,
                        in_specs=in_specs,
                        out_specs=out_specs,
                        check_vma=False,
                    )
                    return body(blocks_t, nblocks_t, states_t, codes_t,
                                digs_t, pred_arrays, valid_arr)

                fn = jax.jit(program, static_argnums=(7, 8))
                self._compiled[key] = fn
                while len(shared) >= ShardedFusedProgram._JIT_CACHE_MAX:
                    shared.pop(next(iter(shared)), None)
                shared[self._program_key + key] = fn
        return fn

    def run(self, mask_cols: Sequence,
            pred_cols: dict[str, tuple[np.ndarray, Optional[np.ndarray]]],
            n_rows: int) -> tuple[list[np.ndarray], Optional[np.ndarray]]:
        """Same contract as FusedMaskFilterProgram.run().  mask_cols
        entries are either (data, offsets) flat pairs or DictMaskInput
        (the dict-aware wire: codes shard, the pool digest matrix
        replicates — see dict_mask_input)."""
        from transferia_tpu.chaos.failpoints import failpoint
        from transferia_tpu.ops.dispatch import (
            encode_pred_column_sharded,
            encode_validity_sharded,
            encoding_enabled,
            stage_h2d,
        )

        failpoint("device.mesh_dispatch")
        # pad the global row count to n_dev * per-device bucket so every
        # shard is equal-sized and the per-device program is shape-stable
        per_dev = bucket_rows(max(1, -(-n_rows // self.n_dev)))
        total = per_dev * self.n_dev
        encoded = encoding_enabled()
        blocks_t, nblocks_t, mb_t, flat_states = [], [], [], []
        codes_t, digs_t, routes = [], [], []
        import time as _time

        raw_equiv = 0
        for i, entry in enumerate(mask_cols):
            if isinstance(entry, DictMaskInput):
                codes = entry.codes
                if total != n_rows:
                    codes = np.pad(codes, (0, total - n_rows))
                codes_t.append(codes)
                # the matrix's row count is a shape of the traced
                # program: padded to a bucket, or every pool size (one a
                # column a part file) compiles a program of its own.  No
                # code points at a padding row
                digs = entry.digests
                pad = bucket_rows(len(digs)) - len(digs)
                if pad:
                    digs = np.pad(digs, ((0, pad), (0, 0)))
                digs_t.append(digs)
                routes.append("dict")
                # honesty: charge what the flat wire would have shipped
                # (bucket-padded SHA block matrix + per-row counts)
                raw_equiv += entry.raw_block_bytes_per_row * total
                continue
            data, offsets = entry
            lens = offsets[1:] - offsets[:-1]
            max_len = int(lens.max()) if n_rows else 0
            mb = pow2_blocks(max_len)
            blocks, n_blocks = pack_hmac_blocks(data, offsets, mb)
            if total != n_rows:
                blocks = np.pad(blocks, ((0, total - n_rows), (0, 0)))
                n_blocks = np.pad(n_blocks, (0, total - n_rows))
            blocks_t.append(blocks)
            nblocks_t.append(n_blocks)
            mb_t.append(mb)
            flat_states.append(self._states[i])
            routes.append("flat")
            raw_equiv += int(blocks.nbytes) + int(n_blocks.nbytes)
        # flat SHA block matrices ship as-is (they are the payload being
        # hashed); dict columns ship codes + one replicated digest
        # table; the predicate columns and both validity planes cross
        # the mesh wire per-shard ENCODED — bit-packed bitmaps/bools,
        # delta/FOR-packed ints — and reconstruct inside the sharded
        # program (ops/dispatch.py sharded encoders, decode on device)
        pred_key = []
        pred_arrays: dict = {}
        for name in sorted(pred_cols):
            data, validity = pred_cols[name]
            spec, arrays, req = encode_pred_column_sharded(
                name, data, validity, n_rows, self.n_dev, per_dev,
                encoded)
            pred_key.append((name, spec, len(arrays)))
            pred_arrays[name] = arrays
            raw_equiv += req
        valid_bool = np.zeros(total, dtype=np.bool_)
        valid_bool[:n_rows] = True
        v2 = valid_bool.reshape(self.n_dev, per_dev)
        valid_arr = encode_validity_sharded(v2) if encoded else v2
        valid_mode = "bits" if encoded else "raw"
        raw_equiv += total  # the flat bool run-validity mask
        fn = self._get_compiled(tuple(routes), tuple(pred_key),
                                valid_mode)
        stage_tree = (tuple(blocks_t), tuple(nblocks_t),
                      tuple(codes_t), tuple(digs_t), pred_arrays,
                      valid_arr)
        h2d = sum(int(leaf.nbytes)
                  for leaf in jax.tree_util.tree_leaves(stage_tree))
        TELEMETRY.record_h2d(h2d)
        # put=False: the sharded jit places each shard itself; an eager
        # device_put would land everything on one device and pay a
        # reshard hop.  The shared staging site keeps the chaos
        # failpoint and the encoded-vs-raw byte accounting honest.
        blocks_s, nblocks_s, codes_s, digs_s, pred_s, valid_s = \
            stage_h2d(stage_tree, raw_equiv_bytes=raw_equiv,
                      what="mesh", put=False)
        TELEMETRY.record_launch()
        sig = (fn, tuple(mb_t), per_dev, tuple(
            leaf.shape for leaf in jax.tree_util.tree_leaves(stage_tree)))
        with trace.span("device_dispatch", bytes=h2d, rows=n_rows,
                        mesh=self.n_dev):
            args = (blocks_s, nblocks_s, tuple(flat_states), codes_s,
                    digs_s, pred_s, valid_s, tuple(mb_t), per_dev)
            if sig in ShardedFusedProgram._compiled_sigs:
                digests_dev, keep_dev, hist, kept = fn(*args)
            else:
                with ShardedFusedProgram._compile_lock:
                    digests_dev, keep_dev, hist, kept = fn(*args)
                    ShardedFusedProgram._compiled_sigs.add(sig)
        t_wait0 = _time.perf_counter()
        with trace.span("device_wait") as sp:
            hexes = [digests_to_hex(np.asarray(h)[:n_rows])
                     for h in digests_dev]
            keep = (np.asarray(keep_dev)[:n_rows]
                    if self._pred_fn is not None else None)
            self.last_shard_hist = np.asarray(hist)
            self.last_kept = int(kept)
            d2h = (sum(int(h.nbytes) for h in digests_dev)
                   + int(hist.nbytes))
            if keep_dev is not None and self._pred_fn is not None:
                d2h += int(keep_dev.nbytes)
            if sp:  # args must attach before the span ends
                sp.add(bytes=d2h, rows=n_rows)
        TELEMETRY.record_d2h(d2h)
        TELEMETRY.record_device_wait(_time.perf_counter() - t_wait0)
        return hexes, keep
