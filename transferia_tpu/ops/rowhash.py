"""Order-independent table fingerprints (device-reducible checksums).

The checksum task (tasks/checksum.py; reference
pkg/worker/tasks/checksum.go) compares tables by sampling rows and
comparing values host-side.  This module adds the complementary
*fingerprint* method: every row hashes to two 32-bit lanes, and a table's
fingerprint is the order-independent reduction (per-lane sum mod 2^32,
per-lane xor, row count) — O(1) state per table, mergeable across
snapshot shards (each worker fingerprints its parts; the coordinator
merge is `FingerprintAggregate.merge`), and reduction-shaped for the
device: the whole batch ships H2D once and 20 bytes come back.

The hash is NOT cryptographic — it is a table-equality witness, like
pt-table-checksum's CRC aggregation, not a defense against adversarial
collisions.  Two independent lanes (different polynomial bases and
finalizers) put an accidental-collision floor around 2^-64 per table
pair.

Canonicalization (identical in both backends, pinned by parity tests):
- var-width columns: the SHA-style padded block matrix from
  native/hostops.cpp pack_sha_blocks(prefix_len=0) — zero fill, 0x80
  terminator, big-endian bit length — an injective fixed-width encoding;
- fixed-width columns: the 64-bit bit pattern, with -0.0 normalized to
  +0.0 and NaNs to the canonical quiet NaN first (value semantics, not
  representation semantics, for floats);
- NULL values hash to a per-column constant (validity is part of the
  fingerprint);
- each column is seeded by crc32(name) so column swaps change the
  fingerprint even between same-typed columns.

Dict-native reduction (ARCHITECTURE.md "Dict-native reductions"): a
dictionary-encoded column's per-row polynomial accumulator depends only
on the row's own bytes — zero padding contributes nothing and the
column seed mixes in AFTER the accumulator — so the accumulators are
computed once per POOL ENTRY (O(pool bytes), memoized on the shared
DictPool) and per-row lanes are an O(n_rows) int32 gather of
accumulators by code.  Work drops from O(total row bytes) to
O(pool bytes + n_rows), the digests stay byte-identical to the flat
path (pinned by tests), and the column never flattens.
"""

from __future__ import annotations

import functools
import zlib
from dataclasses import dataclass, field
from typing import Optional, Sequence

import numpy as np

from transferia_tpu.columnar.batch import ColumnBatch

M32 = np.uint32(0xFFFFFFFF)
# lane polynomial bases (odd => invertible mod 2^32) and null sentinels
_P1 = np.uint32(0x01000193)   # FNV-1a prime
_P2 = np.uint32(0x8DA6B343)
_NULL1 = np.uint32(0xA5A5A5A5)
_NULL2 = np.uint32(0x5A5A5A5A)


def _mix32_np(x: np.ndarray) -> np.ndarray:
    """xorshift-multiply avalanche (lowbias32); exact u32 wraparound."""
    x = x.astype(np.uint32, copy=True)
    x ^= x >> np.uint32(16)
    x *= np.uint32(0x7FEB352D)
    x ^= x >> np.uint32(15)
    x *= np.uint32(0x846CA68B)
    x ^= x >> np.uint32(16)
    return x


def _col_seed(name: str, lane: int) -> np.uint32:
    crc = zlib.crc32(name.encode("utf-8", errors="surrogatepass"))
    return np.uint32((crc + 0x9E3779B9 * (lane + 1)) & 0xFFFFFFFF)


@functools.lru_cache(maxsize=64)
def _powers(width: int, base: int) -> np.ndarray:
    """P^j mod 2^32 table; cached per (width, base) — do not mutate."""
    out = np.empty(width, dtype=np.uint32)
    acc = 1
    for j in range(width):
        out[j] = acc
        acc = (acc * base) & 0xFFFFFFFF
    out.setflags(write=False)
    return out


@dataclass
class FingerprintAggregate:
    """Mergeable order-independent table digest."""

    sum1: int = 0
    sum2: int = 0
    xor1: int = 0
    xor2: int = 0
    count: int = 0

    def merge(self, other: "FingerprintAggregate") -> None:
        self.sum1 = (self.sum1 + other.sum1) & 0xFFFFFFFF
        self.sum2 = (self.sum2 + other.sum2) & 0xFFFFFFFF
        self.xor1 ^= other.xor1
        self.xor2 ^= other.xor2
        self.count += other.count

    def digest(self) -> str:
        return (f"{self.sum1:08x}{self.sum2:08x}"
                f"{self.xor1:08x}{self.xor2:08x}:{self.count}")

    @classmethod
    def parse(cls, digest: str) -> "FingerprintAggregate":
        """Inverse of digest() — lets per-part digests stored as strings
        (coordinator part records) merge at read time."""
        hexes, _, count = digest.partition(":")
        if len(hexes) != 32 or not count:
            raise ValueError(f"malformed fingerprint digest: {digest!r}")
        return cls(
            sum1=int(hexes[0:8], 16), sum2=int(hexes[8:16], 16),
            xor1=int(hexes[16:24], 16), xor2=int(hexes[24:32], 16),
            count=int(count),
        )

    def __eq__(self, other) -> bool:
        if not isinstance(other, FingerprintAggregate):
            return NotImplemented
        return self.digest() == other.digest()


@dataclass
class _PreppedColumn:
    """Backend-neutral canonical form of one column for one batch.

    Var-width columns keep their (data, offsets) — the host backend
    hashes them in place (native polyhash_varcol never materializes the
    padded matrix); the device backend packs lazily via ensure_blocks().
    Dict-encoded columns keep their int32 codes plus the POOL's per-entry
    accumulators (memoized on the shared DictPool): both backends gather
    accumulators by code instead of touching row bytes.
    """

    name: str
    kind: str                      # "fixed" | "var" | "dict"
    lo: Optional[np.ndarray] = None     # fixed: (N,) u32
    hi: Optional[np.ndarray] = None     # fixed: (N,) u32
    data: Optional[np.ndarray] = None    # var: flat u8
    offsets: Optional[np.ndarray] = None  # var: (N+1,) i32
    blocks: Optional[np.ndarray] = None  # var: (N, W) u8 (lazy)
    width: int = 0
    validity: Optional[np.ndarray] = None
    codes: Optional[np.ndarray] = None   # dict: (N,) i32
    acc1: Optional[np.ndarray] = None    # dict: (n_values,) u32
    acc2: Optional[np.ndarray] = None    # dict: (n_values,) u32

    def ensure_blocks(self) -> np.ndarray:
        if self.blocks is None:
            self.blocks = _pack_var(self.data, self.offsets, self.width)
        return self.blocks


def _pow2_width(max_len: int) -> int:
    """Padded row width for var-width data (>= len + 9, pow2 of 64s)."""
    nb = (max_len + 9 + 63) // 64
    nb = 1 << (nb - 1).bit_length() if nb > 1 else 1
    return nb * 64


def _pack_var(data: np.ndarray, offsets: np.ndarray,
              width: int) -> np.ndarray:
    n = len(offsets) - 1
    from transferia_tpu.native import lib as native_lib

    cdll = native_lib()
    out = np.empty((n, width), dtype=np.uint8)
    nb = np.empty(n, dtype=np.int32)
    if cdll is not None and n:
        cdll.pack_sha_blocks(
            np.ascontiguousarray(data),
            np.ascontiguousarray(offsets, dtype=np.int32),
            n, width, 0, out, nb,
        )
        return out
    # numpy fallback: same layout as the C++ packer
    out[:] = 0
    for i in range(n):
        row = data[offsets[i]:offsets[i + 1]]
        ln = len(row)
        out[i, :ln] = row
        out[i, ln] = 0x80
        blocks = (ln + 9 + 63) // 64
        bits = ln * 8
        out[i, blocks * 64 - 8:blocks * 64] = np.frombuffer(
            int(bits).to_bytes(8, "big"), dtype=np.uint8)
    return out


# per-pool accumulator memo key: the accumulators depend only on the
# pool BYTES (the column seed mixes in after the gather), so one pair
# serves every column, batch, and HMAC key sharing the pool
_ACC_MEMO_KEY = ("rowhash_accs",)


def pool_accumulators(pool) -> tuple[np.ndarray, np.ndarray]:
    """Both lanes' polynomial accumulators, one per pool ENTRY.

    Identical to what `_var_accs_host` computes for a flat row carrying
    the same bytes (zero padding of the canonical block matrix
    contributes nothing to the sum, and the power-table indices a row
    touches depend only on its own length — never on the batch-wide
    padded width).  Memoized on the shared DictPool: batches slicing one
    row group's dictionary hash its values exactly once."""
    memo = pool.memo_get(_ACC_MEMO_KEY)
    if memo is not None:
        return memo
    from transferia_tpu.chaos.failpoints import failpoint
    from transferia_tpu.stats import trace

    failpoint("rowhash.pool_accs")
    # once per shared pool: worth a point event (a chaos fire at the
    # `rowhash.pool_accs` site lands next to it on the active span)
    trace.instant("rowhash_pool_accs", values=pool.n_values)
    n_vals = pool.n_values
    offs = np.ascontiguousarray(pool.values_offsets, dtype=np.int32)
    lens = offs[1:] - offs[:-1]
    width = _pow2_width(int(lens.max()) if n_vals else 0)
    tmp = _PreppedColumn(
        name="", kind="var",
        data=np.ascontiguousarray(pool.values_data),
        offsets=offs, width=width)
    accs = _var_accs_host(tmp, n_vals)
    pool.memo_set(_ACC_MEMO_KEY, accs)
    return accs


def prep_batch(batch: ColumnBatch) -> tuple[list[_PreppedColumn], int]:
    """Canonicalize a batch for either fingerprint backend."""
    from transferia_tpu.stats.trace import TELEMETRY

    cols: list[_PreppedColumn] = []
    for name in batch.schema.names():
        col = batch.column(name)
        if col.is_lazy_dict:
            # dict-native: never touch col.data/col.offsets (that would
            # flatten the pool per row); hash the pool once, gather by
            # code.  Null rows are overridden by the validity constant
            # in _col_lanes_host exactly as on the flat path, so the
            # sentinel entry's accumulator (empty bytes) is only ever a
            # don't-care placeholder there.
            pool = col.dict_enc.pool
            codes = np.ascontiguousarray(col.dict_enc.indices,
                                         dtype=np.int32)
            if len(codes):
                # both backends gather UNCHECKED (native loop / device
                # clip): a corrupt code must raise here, not hash
                # stray memory into a plausible-looking digest
                cmin, cmax = int(codes.min()), int(codes.max())
                if cmin < 0 or cmax >= pool.n_values:
                    raise IndexError(
                        f"column {name}: dict codes [{cmin}, {cmax}] "
                        f"out of range for pool of {pool.n_values} "
                        f"values")
            a1, a2 = pool_accumulators(pool)
            TELEMETRY.record_dict_preserved()
            cols.append(_PreppedColumn(
                name=name, kind="dict",
                codes=codes, acc1=a1, acc2=a2, validity=col.validity))
            continue
        if col.offsets is not None:
            lens = col.offsets[1:] - col.offsets[:-1]
            width = _pow2_width(int(lens.max()) if batch.n_rows else 0)
            cols.append(_PreppedColumn(
                name=name, kind="var",
                data=np.ascontiguousarray(col.data),
                offsets=np.ascontiguousarray(col.offsets,
                                             dtype=np.int32),
                width=width, validity=col.validity))
            continue
        data = col.data
        if data.dtype.kind == "f":
            data = data.astype(np.float64, copy=True)
            data[data == 0.0] = 0.0          # -0.0 -> +0.0
            data[np.isnan(data)] = np.nan    # canonical quiet NaN
            bits = data.view(np.uint64)
        elif data.dtype.kind == "b":
            bits = data.astype(np.uint64)
        else:
            bits = data.astype(np.int64, copy=False).view(np.uint64)
        cols.append(_PreppedColumn(
            name=name, kind="fixed",
            lo=(bits & np.uint64(0xFFFFFFFF)).astype(np.uint32),
            hi=(bits >> np.uint64(32)).astype(np.uint32),
            validity=col.validity))
    return cols, batch.n_rows


def _var_accs_host(col: _PreppedColumn,
                   n_rows: int) -> tuple[np.ndarray, np.ndarray]:
    """Both lanes' polynomial accumulators for a var-width column.

    Native path: one C++ pass over the real bytes (zero padding of the
    canonical block layout contributes nothing to the sum, so it is
    never materialized).  Numpy fallback hashes the packed matrix — the
    identical value, pinned by tests.
    """
    from transferia_tpu.native import lib as native_lib

    cdll = native_lib()
    if cdll is not None and n_rows:
        pw1 = _powers(col.width, int(_P1))
        pw2 = _powers(col.width, int(_P2))
        a1 = np.empty(n_rows, dtype=np.uint32)
        a2 = np.empty(n_rows, dtype=np.uint32)
        cdll.polyhash_varcol(col.data, col.offsets, n_rows, pw1, pw2,
                             a1, a2)
        return a1, a2
    blocks = col.ensure_blocks().astype(np.uint32)
    a1 = (blocks * _powers(col.width, int(_P1))[None, :]).sum(
        axis=1, dtype=np.uint32)
    a2 = (blocks * _powers(col.width, int(_P2))[None, :]).sum(
        axis=1, dtype=np.uint32)
    return a1, a2


def _lanes_lib():
    """The native lib (fused lane kernels), or None when
    TRANSFERIA_TPU_NO_NATIVE=1 keeps the numpy chain."""
    from transferia_tpu.native import lib as native_lib

    return native_lib()


def _col_lanes_host(col: _PreppedColumn, n_rows: int
                    ) -> tuple[np.ndarray, np.ndarray]:
    seed1, seed2 = _col_seed(col.name, 0), _col_seed(col.name, 1)
    cdll = _lanes_lib() if n_rows else None
    if cdll is not None:
        # fused C++ lane chains: one pass per column instead of the
        # ~30 numpy temporaries the mix cascade walks (byte-identical;
        # pinned by the fallback-parity tests)
        h1 = np.empty(n_rows, dtype=np.uint32)
        h2 = np.empty(n_rows, dtype=np.uint32)
        s1, s2 = int(seed1), int(seed2)
        if col.kind == "fixed":
            cdll.rowhash_mix_fixed(
                np.ascontiguousarray(col.lo),
                np.ascontiguousarray(col.hi), n_rows, s1, s2, h1, h2)
        elif col.kind == "dict":
            cdll.rowhash_dict_lanes(
                np.ascontiguousarray(col.acc1),
                np.ascontiguousarray(col.acc2),
                col.codes, n_rows, s1, s2, h1, h2)
        else:
            a1, a2 = _var_accs_host(col, n_rows)
            cdll.rowhash_mix_var(
                np.ascontiguousarray(a1), np.ascontiguousarray(a2),
                n_rows, s1, s2, h1, h2)
    elif col.kind == "fixed":
        out = []
        for seed in (seed1, seed2):
            h = _mix32_np(col.lo ^ seed)
            h = _mix32_np(h + _mix32_np(
                col.hi ^ np.uint32(~int(seed) & 0xFFFFFFFF)))
            out.append(h)
        h1, h2 = out
    elif col.kind == "dict":
        # O(n_rows) gather of the memoized pool accumulators by code —
        # byte-identical to hashing the materialized rows because the
        # accumulator of a row IS the accumulator of its pool entry
        from transferia_tpu.columnar.batch import _gather_fixed

        h1 = _mix32_np(_gather_fixed(col.acc1, col.codes) ^ seed1)
        h2 = _mix32_np(_gather_fixed(col.acc2, col.codes) ^ seed2)
    else:
        a1, a2 = _var_accs_host(col, n_rows)
        h1 = _mix32_np(a1 ^ seed1)
        h2 = _mix32_np(a2 ^ seed2)
    if col.validity is not None:
        h1 = np.where(col.validity, h1, _NULL1 ^ seed1)
        h2 = np.where(col.validity, h2, _NULL2 ^ seed2)
    return h1, h2


def row_lanes(cols: Sequence[_PreppedColumn],
              n_rows: int) -> tuple[np.ndarray, np.ndarray]:
    """Finalized per-row lane values (u32 each) — the pre-reduction state
    of `fingerprint_host`.  `(r1[i] << 32) | r2[i]` is a 64-bit content
    key for row i under the same canonicalization as the table
    fingerprint; the chaos delivery auditor (chaos/invariants.py) uses
    it to count per-row delivery multiplicities where the aggregate
    alone can only witness set equality."""
    r1 = np.zeros(n_rows, dtype=np.uint32)
    r2 = np.zeros(n_rows, dtype=np.uint32)
    cdll = _lanes_lib() if n_rows else None
    for col in cols:
        h1, h2 = _col_lanes_host(col, n_rows)
        if cdll is not None:
            cdll.rowhash_accum(np.ascontiguousarray(h1),
                               np.ascontiguousarray(h2), n_rows, r1, r2)
        else:
            r1 += _mix32_np(h1)
            r2 += _mix32_np(h2)
    return _mix32_np(r1), _mix32_np(r2)


def fingerprint_host(cols: Sequence[_PreppedColumn],
                     n_rows: int) -> FingerprintAggregate:
    """Host backend (exact twin of the device program)."""
    r1, r2 = row_lanes(cols, n_rows)
    return FingerprintAggregate(
        sum1=int(r1.sum(dtype=np.uint64) & 0xFFFFFFFF),
        sum2=int(r2.sum(dtype=np.uint64) & 0xFFFFFFFF),
        xor1=int(np.bitwise_xor.reduce(r1)) if n_rows else 0,
        xor2=int(np.bitwise_xor.reduce(r2)) if n_rows else 0,
        count=n_rows,
    )


def _device_keys_requested(environ=None) -> bool:
    """TRANSFERIA_TPU_DEDUP_KEYS=device routes dedup-window keys
    through the device program (profitable exactly when the part's
    batches already ride the device — fused chains, device
    fingerprinting — so codes/blocks are hot and the link cost is
    amortized by the reduction plane)."""
    from transferia_tpu.runtime import knobs

    return knobs.env_str("TRANSFERIA_TPU_DEDUP_KEYS", "",
                         environ=environ).lower() == "device"


def _batch_device_resident(batch: ColumnBatch) -> bool:
    """True when the batch's column buffers are already device arrays
    (a jax.Array survived a device-side transform): keys then compute
    where the data lives instead of gathering host-side."""
    try:
        cols = batch.columns.values()
    except AttributeError:
        return False
    for c in cols:
        data = getattr(c, "_data", None)
        # jax arrays report module "jaxlib.xla_extension" (ArrayImpl),
        # tracer types "jax...." — accept either root package
        if data is not None and \
                type(data).__module__.split(".")[0] in ("jax", "jaxlib"):
            return True
    return False


def batch_row_keys(batch: ColumnBatch, backend: str = "auto"
                   ) -> np.ndarray:
    """64-bit content key per row: `(r1 << 32) | r2` of the finalized
    lanes, under the same canonicalization as the table fingerprint.
    Dict columns key code-natively (pool-accumulator gather, no flat
    materialization).  Shared by the chaos delivery auditor (row
    delivery multiplicities) and the staged-commit dedup window
    (providers/staging.py: replayed torn-write prefixes are dropped
    before publish by these keys).

    `backend="device"` (or `auto` with TRANSFERIA_TPU_DEDUP_KEYS=device
    / a device-resident batch) computes the lanes on device through the
    fingerprint plane's kernel family — byte-identical to the host
    path (pinned by tests/unit/test_dict_reduction.py)."""
    if batch.n_rows == 0:
        return np.empty(0, dtype=np.uint64)
    if backend == "auto" and (_device_keys_requested()
                              or _batch_device_resident(batch)):
        backend = "device"
    if backend == "device":
        try:
            return batch_row_keys_device(batch)
        except ImportError:
            pass  # no jax: the host path is always correct
    cols, n = prep_batch(batch)
    r1, r2 = row_lanes(cols, n)
    return (r1.astype(np.uint64) << np.uint64(32)) | r2.astype(np.uint64)


# per-signature jitted row-keys programs (module-global like the
# fingerprint cache: identical schemas re-trace once per process)
_keys_jit_cache: dict = {}


def batch_row_keys_device(batch: ColumnBatch) -> np.ndarray:
    """Device twin of the host key path: the SAME traced lane body as
    DeviceFingerprintProgram (`_device_row_lanes` — one shared
    implementation, so the two entry points cannot drift) but
    returning the finalized per-row lanes instead of their reduction —
    one launch, two u32 vectors D2H, keys assembled host-side
    (padding rows trimmed)."""
    import jax

    cols, n_rows = prep_batch(batch)
    sig = tuple(
        (c.kind, c.width if c.kind == "var" else 0) for c in cols)

    fn = _keys_jit_cache.get(sig)
    if fn is None:
        sig_kinds = [k for k, _ in sig]

        def program(fixed_lo, fixed_hi, var_blocks, dict_codes,
                    dict_accs1, dict_accs2, validities,
                    seeds1, seeds2, nulls1, nulls2, powers1, powers2,
                    n):
            return _device_row_lanes(
                sig_kinds, fixed_lo, fixed_hi, var_blocks, dict_codes,
                dict_accs1, dict_accs2, validities, seeds1, seeds2,
                nulls1, nulls2, powers1, powers2, n)

        fn = jax.jit(program, static_argnames=("n",))
        _keys_jit_cache[sig] = fn

    args, bucket = _pack_device_lane_args(cols, n_rows)
    r1, r2 = fn(*args, bucket)
    r1 = np.asarray(r1)[:n_rows]
    r2 = np.asarray(r2)[:n_rows]
    return (r1.astype(np.uint64) << np.uint64(32)) | r2.astype(np.uint64)


def _device_row_lanes(sig_kinds, fixed_lo, fixed_hi, var_blocks,
                      dict_codes, dict_accs1, dict_accs2, validities,
                      seeds1, seeds2, nulls1, nulls2, powers1, powers2,
                      n):
    """Traced per-row lane body shared by the fingerprint reduction
    program and the dedup-key program — ONE implementation of the
    device lane math (mix chains, var-width polynomial blocks, dict
    accumulator gathers, null constants), so the two entry points
    cannot drift apart.  Returns the finalized (r1, r2) u32 vectors."""
    import jax
    import jax.numpy as jnp

    def mix(x):
        x = x ^ (x >> jnp.uint32(16))
        x = x * jnp.uint32(0x7FEB352D)
        x = x ^ (x >> jnp.uint32(15))
        x = x * jnp.uint32(0x846CA68B)
        return x ^ (x >> jnp.uint32(16))

    r1 = jnp.zeros(n, dtype=jnp.uint32)
    r2 = jnp.zeros(n, dtype=jnp.uint32)
    fi = vi = di = 0
    for idx, kind in enumerate(sig_kinds):
        for lane in (0, 1):
            seed = (seeds1 if lane == 0 else seeds2)[idx]
            null = (nulls1 if lane == 0 else nulls2)[idx]
            if kind == "fixed":
                lo, hi = fixed_lo[fi], fixed_hi[fi]
                with jax.named_scope("rowhash_fixed"):
                    h = mix(lo ^ seed)
                    h = mix(h + mix(hi ^ (~seed)))
            elif kind == "dict":
                # codes + per-pool-entry accumulators crossed the
                # link (4 + 4·k/n bytes/row, not the padded block
                # matrix); the reduction consumes codes directly via
                # an HBM-speed gather
                from transferia_tpu.ops.decode import (
                    gather_pool_accumulators,
                )

                acc = (dict_accs1 if lane == 0 else dict_accs2)[di]
                with jax.named_scope("rowhash_dict"):
                    h = mix(gather_pool_accumulators(
                        acc, dict_codes[di]) ^ seed)
            else:
                pw = (powers1 if lane == 0 else powers2)[vi]
                with jax.named_scope("rowhash_var"):
                    b = var_blocks[vi].astype(jnp.uint32)
                    h = mix((b * pw[None, :]).sum(
                        axis=1, dtype=jnp.uint32) ^ seed)
            v = validities[idx]
            if v is not None:
                h = jnp.where(v, h, null ^ seed)
            if lane == 0:
                r1 = r1 + mix(h)
            else:
                r2 = r2 + mix(h)
        if kind == "fixed":
            fi += 1
        elif kind == "dict":
            di += 1
        else:
            vi += 1
    return mix(r1), mix(r2)


def _pack_device_lane_args(cols: Sequence[_PreppedColumn],
                           n_rows: int):
    """Host-side argument packing shared by both device entry points:
    bucket-padded column arrays + per-column seed/null/power vectors.
    Returns (args tuple in _device_row_lanes order minus n, bucket)."""
    import jax.numpy as jnp

    from transferia_tpu.columnar.batch import bucket_rows

    bucket = bucket_rows(n_rows)
    fixed_lo, fixed_hi, var_blocks, validities = [], [], [], []
    dict_codes, dict_accs1, dict_accs2 = [], [], []
    seeds1, seeds2, nulls1, nulls2 = [], [], [], []
    powers1, powers2 = [], []
    pad = bucket - n_rows

    def padded(a, fill=0):
        if pad:
            return np.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1),
                          constant_values=fill)
        return a

    for c in cols:
        seeds1.append(_col_seed(c.name, 0))
        seeds2.append(_col_seed(c.name, 1))
        nulls1.append(_NULL1)
        nulls2.append(_NULL2)
        if c.kind == "fixed":
            fixed_lo.append(jnp.asarray(padded(c.lo)))
            fixed_hi.append(jnp.asarray(padded(c.hi)))
        elif c.kind == "dict":
            # accumulators pad to a row bucket too, so pool-size
            # jitter re-traces per bucket, not per distinct pool; pad
            # codes index entry 0 (their lanes are masked or trimmed)
            dict_codes.append(jnp.asarray(padded(c.codes)))
            ab = bucket_rows(max(len(c.acc1), 1))
            apad = ab - len(c.acc1)

            def padded_acc(a):
                return np.pad(a, (0, apad)) if apad else a

            dict_accs1.append(jnp.asarray(padded_acc(c.acc1)))
            dict_accs2.append(jnp.asarray(padded_acc(c.acc2)))
        else:
            var_blocks.append(jnp.asarray(padded(c.ensure_blocks())))
            powers1.append(jnp.asarray(_powers(c.width, int(_P1))))
            powers2.append(jnp.asarray(_powers(c.width, int(_P2))))
        validities.append(
            jnp.asarray(padded(c.validity))
            if c.validity is not None else None)
    args = (tuple(fixed_lo), tuple(fixed_hi), tuple(var_blocks),
            tuple(dict_codes), tuple(dict_accs1), tuple(dict_accs2),
            tuple(validities),
            jnp.asarray(np.array(seeds1, dtype=np.uint32)),
            jnp.asarray(np.array(seeds2, dtype=np.uint32)),
            jnp.asarray(np.array(nulls1, dtype=np.uint32)),
            jnp.asarray(np.array(nulls2, dtype=np.uint32)),
            tuple(powers1), tuple(powers2))
    return args, bucket


class DeviceFingerprintProgram:
    """Jitted device twin of fingerprint_host.

    One launch per (buffered) batch run: H2D moves the canonical columns,
    the reduction happens on device, and 5 scalars come back — the
    profitable shape for high-latency links (ops/linkprobe.py).  Launches
    are dispatched asynchronously; collect() blocks once at the end.
    """

    # compiled programs keyed by column signature — module-global so a
    # fresh instance (one per table scan) reuses prior compilations
    # instead of re-tracing identical schemas
    _jit_cache: dict = {}

    def __init__(self):
        import jax  # presence check at construction, not first dispatch

        self._pending: list = []

    def _program_for(self, sig: tuple):
        fn = self._jit_cache.get(sig)
        if fn is not None:
            return fn
        import jax
        import jax.numpy as jnp

        def program(fixed_lo, fixed_hi, var_blocks, dict_codes,
                    dict_accs1, dict_accs2, validities, rowmask,
                    seeds1, seeds2, nulls1, nulls2, powers1, powers2):
            r1, r2 = _device_row_lanes(
                sig_kinds, fixed_lo, fixed_hi, var_blocks, dict_codes,
                dict_accs1, dict_accs2, validities, seeds1, seeds2,
                nulls1, nulls2, powers1, powers2, rowmask.shape[0])
            with jax.named_scope("rowhash_reduce"):
                r1 = jnp.where(rowmask, r1, 0)
                r2 = jnp.where(rowmask, r2, 0)
                return (r1.sum(dtype=jnp.uint32),
                        r2.sum(dtype=jnp.uint32),
                        jnp.bitwise_xor.reduce(r1),
                        jnp.bitwise_xor.reduce(r2),
                        rowmask.sum(dtype=jnp.int32))

        sig_kinds = [k for k, _ in sig]
        fn = jax.jit(program)
        DeviceFingerprintProgram._jit_cache[sig] = fn
        return fn

    def dispatch(self, cols: Sequence[_PreppedColumn],
                 n_rows: int) -> None:
        """Async-launch one batch; result lands in collect()."""
        import jax.numpy as jnp

        sig = tuple(
            (c.kind, c.width if c.kind == "var" else 0) for c in cols)
        args, bucket = _pack_device_lane_args(cols, n_rows)
        rowmask = np.zeros(bucket, dtype=np.bool_)
        rowmask[:n_rows] = True
        fn = self._program_for(sig)
        (fixed_lo, fixed_hi, var_blocks, dict_codes, dict_accs1,
         dict_accs2, validities, seeds1, seeds2, nulls1, nulls2,
         powers1, powers2) = args
        out = fn(fixed_lo, fixed_hi, var_blocks, dict_codes,
                 dict_accs1, dict_accs2, validities,
                 jnp.asarray(rowmask), seeds1, seeds2, nulls1, nulls2,
                 powers1, powers2)
        self._pending.append(out)

    def collect(self) -> FingerprintAggregate:
        """Block on every dispatched launch and merge the partials."""
        agg = FingerprintAggregate()
        for out in self._pending:
            s1, s2, x1, x2, cnt = (np.asarray(o) for o in out)
            agg.merge(FingerprintAggregate(
                sum1=int(s1), sum2=int(s2), xor1=int(x1), xor2=int(x2),
                count=int(cnt)))
        self._pending.clear()
        return agg


# The backend model's compute term: rows/s the chip sustains on the
# fingerprint reduction with data resident (one int64 + one 64-byte
# var-width column, 1M-row launches).  A v5e ("TPU v5 lite") figure from
# the builder's own 2026-07 runs, never in a driver record;
# chip_smoke.py prints the rate it measures beside it.
DEVICE_FINGERPRINT_ROWS_PER_S = 20e6


class TableFingerprinter:
    """Streaming fingerprint over batches, backend chosen by measurement.

    backend="auto" times the host path on the first batch and predicts
    the device path from the link profile (same gating idea as
    transform/fused.py): reduction output is tiny, so the device is
    profitable whenever H2D keeps up and batches amortize the launch.
    """

    # re-evaluate the backend decision periodically: links drift, and a
    # decision pinned off one skewed sample would fix a bad backend for
    # a whole table scan (same policy as DeviceFusedStep.REPROBE_EVERY)
    REPROBE_EVERY = 256

    def __init__(self, backend: str = "auto"):
        self.backend = backend
        self._agg = FingerprintAggregate()
        self._device: Optional[DeviceFingerprintProgram] = None
        self._host_ns_row = -1.0
        self._host_samples = 0
        self._batch_no = 0
        self._decided: Optional[str] = None

    def _accel_available(self) -> bool:
        """A device backend only pays when it is a real accelerator —
        jax-on-CPU shares the host cores and adds jit overhead."""
        try:
            import jax
        except ImportError:  # host-only install: jax is an extra
            return False
        return jax.default_backend() != "cpu"

    def _choose(self, n_rows: int, row_bytes: int) -> str:
        if self.backend in ("host", "device"):
            return self.backend
        if (self._decided is not None
                and self._batch_no % self.REPROBE_EVERY != 0):
            return self._decided
        # need >=2 host samples: the first carries one-off warmup (native
        # lib build, cold caches) and is never recorded
        if self._host_samples < 2 or not self._accel_available():
            return "host"
        from transferia_tpu.ops.linkprobe import probe_link

        link = probe_link()
        pred_s = (2 * link.launch_overhead_s
                  + n_rows * row_bytes / link.h2d_bytes_per_s
                  + n_rows / DEVICE_FINGERPRINT_ROWS_PER_S)
        pred_ns = pred_s * 1e9 / max(n_rows, 1)
        self._decided = ("device" if pred_ns < self._host_ns_row
                         else "host")
        return self._decided

    def push(self, batch: ColumnBatch) -> None:
        if batch.n_rows == 0:
            return
        import time as _time

        cols, n = prep_batch(batch)
        row_bytes = sum(
            (c.width if c.kind == "var" else 8) for c in cols)
        self._batch_no += 1
        choice = self._choose(n, row_bytes)
        if choice == "device":
            if self._device is None:
                self._device = DeviceFingerprintProgram()
            self._device.dispatch(cols, n)
            return
        t0 = _time.perf_counter()
        self._agg.merge(fingerprint_host(cols, n))
        ns = (_time.perf_counter() - t0) * 1e9 / n
        self._host_samples += 1
        if self._host_samples == 1:
            return  # warmup-contaminated: measure, don't record
        self._host_ns_row = (ns if self._host_ns_row < 0
                             else 0.7 * self._host_ns_row + 0.3 * ns)

    def result(self) -> FingerprintAggregate:
        if self._device is not None:
            self._agg.merge(self._device.collect())
        return self._agg
