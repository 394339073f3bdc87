"""Device mesh + sharded columnar transform step.

The full device-side "step" of this framework is: HMAC-mask the PII
columns, evaluate the row predicate, cast numerics, and reduce global
per-shard row histograms (the ClickHouse sharded-insert fan-out statistic).
`sharded_transform_step` jits that step over a 2D mesh:

    rows    -> 'data'  axis (partition fan-in / dp)
    columns -> 'model' axis (column-parallel masking / tp-analogue)

Collectives: the shard histogram is a psum over 'data' — XLA lowers it to
an ICI all-reduce on real hardware.  Sequence-level parallelism (huge
single tables) stays host-side via intra-table part sharding
(tasks/table_splitter.py), and pipeline parallelism is the parsequeue's
parse/push/ack stages — matching how the reference distributes
(SURVEY.md §2.4), not an ML-training topology.
"""

from __future__ import annotations

import functools
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from transferia_tpu.ops.sha256 import (
    _H0,
    _compress_batch,
    _hmac_key_states,
    hmac_device_core,
)


def make_mesh(n_devices: Optional[int] = None,
              devices=None) -> Mesh:
    """Build a 2D ('data', 'model') mesh over the available devices.

    'model' gets the largest power-of-two divisor <= 2 by default (column
    parallelism is typically narrow); the rest goes to 'data'.
    """
    if devices is None:
        devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    n = len(devices)
    model = 2 if n % 2 == 0 and n >= 4 else 1
    data = n // model
    dev_array = np.array(devices[:data * model]).reshape(data, model)
    return Mesh(dev_array, ("data", "model"))


def _transform_core(blocks, n_blocks, inner, outer, ages, scores,
                    max_blocks: int, n_shards: int):
    """The per-device transform step.

    blocks: (C, N, max_blocks*64) uint8 — C masked columns x N rows
    n_blocks: (C, N) int32; ages: (N,) int32; scores: (N,) float64/32
    Returns (digests (C, N, 8) uint32, keep_mask (N,) bool,
             scores_f32 (N,), shard_hist (n_shards,) int32)
    """
    digests = jax.vmap(
        lambda b, nb: hmac_device_core(b, nb, inner, outer, max_blocks)
    )(blocks, n_blocks)
    keep = (ages >= 0) & jnp.isfinite(scores)
    scores_f32 = scores.astype(jnp.float32)
    # shard fan-out histogram over every local masked column's digest, so
    # the psum'd global histogram is layout-independent
    shard = (digests[:, :, 0] % jnp.uint32(n_shards)).astype(jnp.int32)
    hist = jnp.zeros((n_shards,), dtype=jnp.int32).at[shard.reshape(-1)].add(
        jnp.broadcast_to(keep.astype(jnp.int32), shard.shape).reshape(-1)
    )
    return digests, keep, scores_f32, hist


def sharded_transform_step(mesh: Mesh, max_blocks: int = 2,
                           n_shards: int = 16, key: bytes = b"mask-key"):
    """Build the jitted multi-chip transform step.

    Row axis shards over 'data', masked-column axis over 'model'; the
    histogram psum crosses 'data' so every device sees global shard counts
    (what a sharded CH writer needs to balance inserts).
    """
    inner_np, outer_np = _hmac_key_states(key)
    inner = jnp.asarray(inner_np[0])
    outer = jnp.asarray(outer_np[0])

    def per_device(blocks, n_blocks, ages, scores):
        digests, keep, scores_f32, hist = _transform_core(
            blocks, n_blocks, inner, outer, ages, scores,
            max_blocks, n_shards,
        )
        # global histogram across row shards AND column shards (each model
        # shard contributes its local columns' histogram)
        hist = jax.lax.psum(hist, axis_name=("data", "model"))
        total_kept = jax.lax.psum(keep.sum(), axis_name="data")
        return digests, keep, scores_f32, hist, total_kept

    in_specs = (
        P("model", "data", None),   # blocks: columns x rows x bytes
        P("model", "data"),         # n_blocks
        P("data"),                  # ages
        P("data"),                  # scores
    )
    out_specs = (
        P("model", "data", None),   # digests
        P("data"),                  # keep mask (replicated over model)
        P("data"),                  # scores
        P(),                        # histogram (fully replicated)
        P(),                        # total kept
    )
    fn = shard_map(per_device, mesh=mesh, in_specs=in_specs,
                   out_specs=out_specs, check_vma=False)
    return jax.jit(fn)


def example_step_args(mesh: Mesh, rows_per_device: int = 128,
                      n_columns: Optional[int] = None,
                      max_blocks: int = 2):
    """Tiny sharded example inputs matching sharded_transform_step specs."""
    data_n = mesh.shape["data"]
    model_n = mesh.shape["model"]
    n_rows = rows_per_device * data_n
    n_cols = n_columns or model_n
    rng = np.random.default_rng(0)
    blocks = rng.integers(
        0, 255, (n_cols, n_rows, max_blocks * 64), dtype=np.uint8
    )
    n_blocks = np.full((n_cols, n_rows), max_blocks, dtype=np.int32)
    ages = rng.integers(0, 99, n_rows).astype(np.int32)
    scores = rng.uniform(0, 100, n_rows)
    shardings = [
        NamedSharding(mesh, spec) for spec in (
            P("model", "data", None), P("model", "data"),
            P("data"), P("data"),
        )
    ]
    arrays = [
        jax.device_put(a, s)
        for a, s in zip((blocks, n_blocks, ages, scores), shardings)
    ]
    return tuple(arrays)
