"""Compressed dispatch plane: decode kernels, device-resident dict
masking, placement under encoded link costs, double-buffered dispatch.

Contract under test (ops/dispatch.py + ops/decode.py + the fused
program): every encoding that crosses the link decodes on device
byte-identical to the host decode, the device HMAC of a dict pool
equals the host hashlib path bit-for-bit, and the placement model
judges the ENCODED wire — so a pinned slow link flips the fused chain
to `device` exactly when compression makes the transfer affordable.
"""

import hashlib
import hmac as hmac_mod

import numpy as np
import pytest

import jax.numpy as jnp

from transferia_tpu.abstract import TableID
from transferia_tpu.abstract.schema import new_table_schema
from transferia_tpu.columnar.batch import (
    Column,
    ColumnBatch,
    DictEnc,
    DictPool,
    _offsets_from_lengths,
)
from transferia_tpu.ops import dispatch as dsp
from transferia_tpu.ops import linkprobe
from transferia_tpu.ops.decode import (
    decode_dict_run,
    delta_decode,
    pack_mask_words,
    unpack_bits,
    unpack_validity,
)
from transferia_tpu.predicate import parse
from transferia_tpu.transform import build_chain
from transferia_tpu.transform.fused import (
    DeviceFusedStep,
    set_device_fusion,
    set_placement,
)

TID = TableID("web", "hits")
SCHEMA = new_table_schema([("url", "utf8"), ("region", "int32")])


@pytest.fixture(autouse=True)
def _reset_modes():
    yield
    set_placement(None)
    set_device_fusion(None)
    dsp.set_dispatch_encoding(None)
    linkprobe.reset_link_cache()


def _host_unpack(words: np.ndarray, bw: int, n: int) -> np.ndarray:
    """Reference bit-unpack in pure python/numpy."""
    bits = np.unpackbits(np.ascontiguousarray(words).view(np.uint8),
                         bitorder="little")
    out = np.zeros(n, dtype=np.int64)
    for k in range(bw):
        out |= bits[k:n * bw:bw].astype(np.int64) << k
    return out


# -- kernel round trips ------------------------------------------------------

@pytest.mark.parametrize("bw", list(range(1, 33)))
def test_unpack_bits_all_widths(bw):
    rng = np.random.default_rng(bw)
    for n in (0, 1, 31, 32, 64, 100, 257):  # pow2 lanes + ragged tails
        hi = (1 << bw) - 1
        vals = (rng.integers(0, 2**63, size=n, dtype=np.uint64)
                & np.uint64(hi))
        words = dsp.pack_bits_host(vals, bw)
        out = np.asarray(unpack_bits(jnp.asarray(words), bw, n))
        expect = _host_unpack(words, bw, n)
        assert (out.astype(np.uint64) & np.uint64(hi)
                == expect.astype(np.uint64) & np.uint64(hi)).all(), \
            (bw, n)
        assert (out.astype(np.uint64) & np.uint64(hi) == vals).all()


def test_unpack_bits_rejects_bad_width():
    with pytest.raises(ValueError):
        unpack_bits(jnp.zeros(1, dtype=jnp.uint32), 0, 4)
    with pytest.raises(ValueError):
        unpack_bits(jnp.zeros(2, dtype=jnp.uint32), 33, 4)


@pytest.mark.parametrize("n", [0, 1, 5, 31, 32, 64, 257, 1000])
def test_validity_bitmap_round_trip(n):
    rng = np.random.default_rng(n)
    for v in (rng.random(n) > 0.5,
              np.zeros(n, dtype=np.bool_),   # all-null
              np.ones(n, dtype=np.bool_)):
        out = np.asarray(unpack_validity(
            jnp.asarray(dsp.encode_validity(v)), n))
        assert out.dtype == np.bool_
        assert (out == v).all()


@pytest.mark.parametrize("dtype", [np.int32, np.int16, np.uint16])
def test_delta_decode_matches_host(dtype):
    rng = np.random.default_rng(3)
    n = 777
    top = 10**6 if dtype == np.int32 else 30000
    cases = [
        np.sort(rng.integers(0, top, size=n).astype(dtype)),
        np.full(n, 42, dtype=dtype),
        (np.arange(n) * 3 + 7).astype(dtype),
    ]
    if np.issubdtype(dtype, np.signedinteger):
        cases.append(rng.integers(-100, 100, size=n).astype(dtype))
    for arr in cases:
        enc = dsp.encode_delta(arr)
        assert enc is not None, arr.dtype
        base, words, bw = enc
        out = np.asarray(delta_decode(jnp.asarray(words),
                                      jnp.int32(base), bw, n))
        assert (out == arr.astype(np.int64)).all(), (arr.dtype, bw)
        # and the encoding really shrank the transfer
        assert words.nbytes < arr.nbytes


def test_delta_rejects_unprofitable():
    rng = np.random.default_rng(5)
    # full-range random int32: deltas need > 30 bits
    assert dsp.encode_delta(
        rng.integers(-2**31, 2**31, size=1000).astype(np.int32)) is None
    # tiny arrays are not worth the round trip
    assert dsp.encode_delta(np.arange(8, dtype=np.int32)) is None
    # floats never delta-encode
    assert dsp.encode_delta(rng.random(1000).astype(np.float32)) is None
    # values outside int32 must reject even with narrow deltas — the
    # device prefix sum reconstructs VALUES in int32 (an int64 ns-epoch
    # timestamp column would otherwise decode wrapped)
    ts = np.arange(1000, dtype=np.int64) * 1000 + 1_700_000_000 * 10**9
    assert dsp.encode_delta(ts) is None
    assert dsp.encode_delta(np.arange(512, dtype=np.int64) * 2**28) \
        is None


def test_dict_gather_kernel_matches_host():
    rng = np.random.default_rng(9)
    pool = rng.integers(0, 2**31, size=100).astype(np.int32)
    for n in (32, 100, 257):
        codes = rng.integers(0, 100, size=n).astype(np.uint64)
        bw = 7
        words = dsp.pack_bits_host(codes, bw)
        out = np.asarray(decode_dict_run(
            jnp.asarray(words), jnp.asarray(pool), bw, n))
        assert (out == pool[codes.astype(np.int64)]).all()


@pytest.mark.parametrize("n", [32, 256, 4096])
def test_keep_mask_pack_round_trip(n):
    rng = np.random.default_rng(n)
    bits = rng.random(n) > 0.3
    words = np.asarray(pack_mask_words(jnp.asarray(bits), n))
    assert words.nbytes == n // 8
    assert (dsp.unpack_mask_host(words, n) == bits).all()


# -- device-resident dict HMAC ----------------------------------------------

def _fresh_pool(k=50, null_sentinel=True):
    vals = [f"https://e{i}.com/p/{i * 31 % 17}" for i in range(k)]
    bufs = [v.encode() for v in vals]
    data = np.frombuffer(b"".join(bufs), dtype=np.uint8).copy()
    lens = [len(b) for b in bufs] + ([0] if null_sentinel else [])
    off = _offsets_from_lengths(lens)
    return DictPool(data, off, null_code=k if null_sentinel else None)


def _dict_batch(pool, n=600, seed=1, nulls=True):
    rng = np.random.default_rng(seed)
    k = pool.n_values - (1 if pool.null_code is not None else 0)
    codes = rng.integers(0, k, size=n).astype(np.int32)
    validity = None
    if nulls and pool.null_code is not None:
        validity = rng.random(n) > 0.1
        codes = np.where(validity, codes,
                         pool.null_code).astype(np.int32)
    url = Column("url", SCHEMA.find("url").data_type, validity=validity,
                 dict_enc=DictEnc(codes, pool=pool))
    region = Column("region", SCHEMA.find("region").data_type,
                    rng.integers(0, 500, size=n).astype(np.int32))
    return ColumnBatch(TID, SCHEMA, {"url": url, "region": region})


def test_device_pool_hmac_equals_host_hashlib():
    """The device-hashed pool must be bit-identical to hashlib HMAC."""
    pool = _fresh_pool()
    hexed = dsp.device_hmac_dict_pool(b"s3cr3t", pool, n_rows=600)
    assert hexed is not None
    for code in range(pool.n_values):
        raw = pool.value_bytes(code)
        got = hexed.value_bytes(code)
        if code == pool.null_code:
            assert got == b""  # sentinel hexes to empty, not HMAC("")
        else:
            expect = hmac_mod.new(b"s3cr3t", raw,
                                  hashlib.sha256).hexdigest().encode()
            assert got == expect, code


def test_device_pool_hmac_shares_host_memo():
    from transferia_tpu.transform.plugins.mask import mask_dict_column

    pool = _fresh_pool()
    batch = _dict_batch(pool)
    # host path hashes first; the device route must ride its memo
    host_col = mask_dict_column(b"k", batch.column("url"))
    assert host_col is not None
    from transferia_tpu.stats.trace import TELEMETRY

    TELEMETRY.reset()
    hexed = dsp.device_hmac_dict_pool(b"k", pool, n_rows=600)
    assert hexed is host_col.dict_enc.pool
    assert TELEMETRY.snapshot()["dict_pool_hits"] == 1
    assert TELEMETRY.snapshot()["dict_pool_uploads"] == 0


def test_device_pool_hmac_single_upload_under_races():
    """Concurrent part threads sharing one pool must pay ONE upload."""
    import threading

    from transferia_tpu.stats.trace import TELEMETRY

    pool = _fresh_pool()
    TELEMETRY.reset()
    results = []
    barrier = threading.Barrier(4)

    def worker():
        barrier.wait()
        results.append(dsp.device_hmac_dict_pool(b"race", pool, 600))

    threads = [threading.Thread(target=worker) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(results) == 4
    assert all(r is results[0] for r in results)  # one shared hexed pool
    snap = TELEMETRY.snapshot()
    assert snap["dict_pool_uploads"] == 1
    assert snap["dict_pool_hits"] == 3


def test_device_pool_hmac_economics_guard():
    pool = _fresh_pool(k=50)
    # pool much larger than the batch and no memo: refuse (the caller
    # falls back to the flat wire, exactly like the host path)
    assert dsp.device_hmac_dict_pool(b"k", pool, n_rows=10) is None


def test_dict_chain_device_parity_with_host():
    """Fused device chain over a dict column (pool route) must equal
    the plain host chain — including nulls and the row filter."""
    cfg = {"transformers": [
        {"mask_field": {"columns": ["url"], "salt": "s3cr3t"}},
        {"filter_rows": {"filter": "region < 400"}},
    ]}
    dev_batch = _dict_batch(_fresh_pool(), seed=2)
    host_batch = _dict_batch(_fresh_pool(), seed=2)  # fresh pool: no
    # shared memo, so the two strategies hash independently
    set_device_fusion(True)
    set_placement("device")
    dev = build_chain(cfg).apply(dev_batch)
    set_device_fusion(False)
    set_placement(None)
    host = build_chain(cfg).apply(host_batch)
    assert dev.n_rows == host.n_rows
    assert dev.column("url").to_pylist() == host.column("url").to_pylist()
    assert (dev.column("region").to_pylist()
            == host.column("region").to_pylist())
    # the device output stays dictionary-encoded (codes never shipped)
    assert dev.column("url").is_lazy_dict


def test_dict_chain_all_null_column():
    cfg = {"transformers": [
        {"mask_field": {"columns": ["url"], "salt": "k"}},
    ]}
    pool = _fresh_pool()
    n = 40
    codes = np.full(n, pool.null_code, dtype=np.int32)
    validity = np.zeros(n, dtype=np.bool_)
    url = Column("url", SCHEMA.find("url").data_type, validity=validity,
                 dict_enc=DictEnc(codes, pool=pool))
    region = Column("region", SCHEMA.find("region").data_type,
                    np.arange(n, dtype=np.int32))
    batch = ColumnBatch(TID, SCHEMA, {"url": url, "region": region})
    set_device_fusion(True)
    set_placement("device")
    out = build_chain(cfg).apply(batch)
    assert out.column("url").to_pylist() == [None] * n


def test_varwidth_digests_device_vs_hashlib():
    """Flat var-width columns through the ENCODED program: digest bytes
    must still equal hashlib HMAC row by row."""
    cfg = {"transformers": [
        {"mask_field": {"columns": ["url"], "salt": "vw-salt"}},
        {"filter_rows": {"filter": "region < 450"}},
    ]}
    n = 300
    rng = np.random.default_rng(8)
    urls = [None if i % 9 == 0 else f"https://x{i}.org/{i}"
            for i in range(n)]
    batch = ColumnBatch.from_pydict(TID, SCHEMA, {
        "url": urls,
        "region": [int(x) for x in rng.integers(0, 500, size=n)],
    })
    dsp.set_dispatch_encoding("auto")
    set_device_fusion(True)
    set_placement("device")
    out = build_chain(cfg).apply(batch)
    regions = batch.column("region").to_pylist()
    expect = [
        (None if u is None else
         hmac_mod.new(b"vw-salt", u.encode(),
                      hashlib.sha256).hexdigest())
        for u, r in zip(urls, regions) if r < 450
    ]
    assert out.column("url").to_pylist() == expect


def test_encoded_vs_raw_program_identical():
    """The dispatch encoding must be invisible in the output: raw and
    auto modes produce byte-identical batches (nullable predicate
    column exercises the bitmap; sorted ints exercise delta)."""
    schema = new_table_schema([
        ("url", "utf8"), ("region", "int32"), ("seq", "int32"),
    ])
    n = 500
    rng = np.random.default_rng(4)
    batch = ColumnBatch.from_pydict(TID, schema, {
        "url": [f"u{i}" for i in range(n)],
        "region": [None if i % 7 == 0 else int(rng.integers(0, 500))
                   for i in range(n)],
        "seq": sorted(int(x) for x in rng.integers(0, 10**6, size=n)),
    })
    cfg = {"transformers": [
        {"mask_field": {"columns": ["url"], "salt": "k"}},
        {"filter_rows": {"filter": "region < 300 AND seq >= 1000"}},
    ]}
    set_device_fusion(True)
    set_placement("device")
    dsp.set_dispatch_encoding("raw")
    raw = build_chain(cfg).apply(batch)
    dsp.set_dispatch_encoding("auto")
    enc = build_chain(cfg).apply(batch)
    assert raw.n_rows == enc.n_rows
    for name in ("url", "region", "seq"):
        assert (raw.column(name).to_pylist()
                == enc.column(name).to_pylist()), name


# -- placement under the encoded link model ---------------------------------

def _planned_step(monkeypatch):
    monkeypatch.setenv("TRANSFERIA_TPU_LINK", "70,21,21")
    linkprobe.reset_link_cache()
    cfg = {"transformers": [
        {"mask_field": {"columns": ["url"], "salt": "s"}},
        {"filter_rows": {"filter": "region < 400"}},
    ]}
    set_device_fusion(True)
    set_placement("auto")
    chain = build_chain(cfg)
    step = chain.plan_for(TID, SCHEMA).steps[0]
    assert isinstance(step, DeviceFusedStep)
    return step


def test_placement_flips_to_device_on_slow_link_with_encoding(
        monkeypatch):
    """On the measured slow link (~70ms rtt, 21MB/s) a dict-heavy batch
    is affordable ENCODED (pool upload + codes-free masking + bitmap
    pred) but hopeless RAW — auto placement must flip accordingly.

    Pinned to the SINGLE-device program: the pool route (and so the
    encoded-wire estimate) does not apply on the mesh route, and the
    virtual 8-device test env would otherwise take it at this size."""
    step = _planned_step(monkeypatch)
    step.sharded_program = None
    step._ns_row = {"host": 600.0, "device": -1.0}
    batch = _dict_batch(_fresh_pool(k=4096, null_sentinel=True),
                        n=131072, nulls=False)
    dsp.set_dispatch_encoding("auto")
    assert step._pick_strategy(batch.n_rows, batch) == "device"
    assert not step._device_gated
    # the same batch over the same link with the raw wire: gated to host
    step2 = _planned_step(monkeypatch)
    step2._ns_row = {"host": 600.0, "device": -1.0}
    dsp.set_dispatch_encoding("raw")
    assert step2._pick_strategy(batch.n_rows, batch) == "host"
    assert step2._device_gated


def test_placement_memoized_pool_is_free(monkeypatch):
    """Once the hexed pool is device-resident the link model charges
    ZERO mask bytes — an even smaller batch stays device-eligible.
    (Single-device program: the pool route does not exist on the mesh
    route, so the virtual 8-device env must not shadow it.)"""
    step = _planned_step(monkeypatch)
    step.sharded_program = None
    step._ns_row = {"host": 600.0, "device": -1.0}
    pool = _fresh_pool(k=4096)
    batch = _dict_batch(pool, n=131072, nulls=False)
    dsp.set_dispatch_encoding("auto")
    h2d_cold, _ = step._estimate_link_bytes(batch.n_rows, batch)
    pool.memo_set(("hmac_hex", b"s"), _fresh_pool(k=4096))
    h2d_warm, _ = step._estimate_link_bytes(batch.n_rows, batch)
    assert h2d_warm < h2d_cold
    assert step._pick_strategy(batch.n_rows, batch) == "device"


def test_placement_mesh_route_charges_dict_wire(monkeypatch):
    """A batch big enough for the MESH program takes the dict-aware
    mesh route: sharded int32 codes (4 B/row) + one pool digest upload
    instead of the per-row block matrix — the link estimate must charge
    the codes wire, far below the flat wire's 128 B/row."""
    step = _planned_step(monkeypatch)
    if step.sharded_program is None:
        pytest.skip("needs the virtual multi-device mesh")
    pool = _fresh_pool(k=4096)
    n = max(step._sharded_min_rows, 131072)
    batch = _dict_batch(pool, n=n, nulls=False)
    dsp.set_dispatch_encoding("auto")
    h2d_cold, d2h_cold = step._estimate_link_bytes(batch.n_rows, batch)
    # cold pool: one upload (128 B/value) + the codes, never 128 B/row
    assert h2d_cold < 64.0 * n
    assert d2h_cold >= 32.0 * n  # gathered digest words still return
    # digest matrix memoized: the pool upload term disappears
    pool.memo_set(("hmac_digest_rows", b"s"),
                  np.zeros((pool.n_values, 8), dtype=np.uint32))
    h2d_warm, _ = step._estimate_link_bytes(batch.n_rows, batch)
    assert h2d_warm < h2d_cold


def test_placement_mesh_route_rejected_pool_charges_flat(monkeypatch):
    """An economics-rejected pool (bigger than 2x the batch, no memo)
    still flattens onto the mesh block wire — the estimate must charge
    the full per-row block matrix for it."""
    step = _planned_step(monkeypatch)
    if step.sharded_program is None:
        pytest.skip("needs the virtual multi-device mesh")
    n = max(step._sharded_min_rows, 8192)
    pool = _fresh_pool(k=4 * n)
    batch = _dict_batch(pool, n=n, nulls=False)
    dsp.set_dispatch_encoding("auto")
    h2d, _ = step._estimate_link_bytes(batch.n_rows, batch)
    assert h2d >= 128.0 * n  # full block matrix, not the codes wire


# -- double-buffered pipelined dispatch -------------------------------------

@pytest.mark.parametrize("depth", [1, 2, 4])
def test_double_buffer_ordering_deterministic(depth):
    """Chunk results must reassemble in row order at every pipeline
    depth, byte-identical to the single-launch program."""
    from transferia_tpu.ops.fused import FusedMaskFilterProgram

    n = 1000
    rng = np.random.default_rng(6)
    urls = [f"https://d{i}.io/{int(rng.integers(10**6))}"
            for i in range(n)]
    bufs = [u.encode() for u in urls]
    data = np.frombuffer(b"".join(bufs), dtype=np.uint8).copy()
    offsets = _offsets_from_lengths([len(b) for b in bufs])
    region = rng.integers(0, 500, size=n).astype(np.int32)
    node = parse("region < 400")
    prog = FusedMaskFilterProgram([b"db-salt"], node)
    mask_cols = [(data, offsets)]
    pred_cols = {"region": (region, None)}
    ref_hexes, ref_keep = prog._run_single(mask_cols, pred_cols, n)
    hexes, keep = prog._run_pipelined(mask_cols, pred_cols, n,
                                      chunk=256, depth=depth)
    assert (keep == ref_keep).all()
    assert len(hexes) == 1
    assert bytes(hexes[0].reshape(-1)) == bytes(ref_hexes[0].reshape(-1))


def test_pipelined_stage_overlaps_launches():
    """The staging queue really holds one chunk's H2D ahead of the
    launches: launch order must equal chunk order (determinism) while
    every stage happens no later than the launch that consumes it."""
    from transferia_tpu.ops import fused as ops_fused

    events = []
    prog = ops_fused.FusedMaskFilterProgram([b"k"], None)
    orig_stage = prog._stage
    orig_launch = prog._launch

    def spy_stage(*a, **kw):
        st = orig_stage(*a, **kw)
        events.append(("stage", st[5]))
        return st

    def spy_launch(st):
        events.append(("launch", st[5]))
        return orig_launch(st)

    prog._stage = spy_stage
    prog._launch = spy_launch
    n = 1024
    bufs = [f"r{i}".encode() for i in range(n)]
    data = np.frombuffer(b"".join(bufs), dtype=np.uint8).copy()
    offsets = _offsets_from_lengths([len(b) for b in bufs])
    prog._run_pipelined([(data, offsets)], {}, n, chunk=256, depth=2)
    stages = [e for e in events if e[0] == "stage"]
    launches = [e for e in events if e[0] == "launch"]
    assert len(stages) == len(launches) == 4
    # chunk g+1 stages before chunk g launches (double buffering), and
    # launches retire in chunk order
    assert events[0] == ("stage", 256)
    assert events[1] == ("stage", 256)
    assert events[2] == ("launch", 256)


# -- link probe failure ------------------------------------------------------

def test_failed_link_probe_on_accelerator_raises(monkeypatch):
    """No made-up profile: a probe that raises on an accelerator backend
    surfaces, and nothing is cached for the next caller to trust."""
    import jax

    def broken(backend):
        raise RuntimeError("device lost")

    monkeypatch.setattr(linkprobe, "_measure", broken)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("TRANSFERIA_TPU_LINK", raising=False)
    linkprobe.reset_link_cache()
    try:
        with pytest.raises(RuntimeError, match="device lost"):
            linkprobe.probe_link()
        assert linkprobe._cached is None
    finally:
        linkprobe.reset_link_cache()


# -- telemetry + chaos -------------------------------------------------------

def test_dispatch_compression_counters_fold():
    from transferia_tpu.stats.registry import Metrics
    from transferia_tpu.stats.trace import TELEMETRY

    TELEMETRY.reset()
    TELEMETRY.record_dispatch(100, 1000)
    TELEMETRY.record_pool_hit()
    TELEMETRY.record_pool_upload()
    snap = TELEMETRY.snapshot()
    assert snap["h2d_encoded_bytes"] == 100
    assert snap["h2d_raw_equiv_bytes"] == 1000
    assert snap["dispatch_compression_ratio"] == 10.0
    m = Metrics()
    TELEMETRY.fold_into(m)
    assert m.value("h2d_encoded_bytes") == 100
    assert m.value("h2d_raw_equiv_bytes") == 1000
    assert m.value("dispatch_compression_ratio") == 10.0
    assert m.value("dict_pool_device_hits") == 1
    assert m.value("dict_pool_device_uploads") == 1
    TELEMETRY.fold_into(m)  # fold is delta-safe
    assert m.value("h2d_encoded_bytes") == 100


def test_dispatch_h2d_failpoint_fires():
    from transferia_tpu.chaos import failpoints as fp
    from transferia_tpu.ops.fused import FusedMaskFilterProgram

    n = 64
    bufs = [f"v{i}".encode() for i in range(n)]
    data = np.frombuffer(b"".join(bufs), dtype=np.uint8).copy()
    offsets = _offsets_from_lengths([len(b) for b in bufs])
    prog = FusedMaskFilterProgram([b"k"], None)
    fp.configure("dispatch.h2d=raise:IOError", seed=1)
    try:
        with pytest.raises(IOError):
            prog.run([(data, offsets)], {}, n)
    finally:
        fp.reset()


# -- take fast path ----------------------------------------------------------

def test_take_dict_codes_gather_stays_lazy():
    pool = _fresh_pool()
    batch = _dict_batch(pool, n=200, seed=7)
    idx = np.array([5, 3, 199, 0, 77, 3], dtype=np.int64)
    out = batch.column("url").take(idx)
    assert out.is_lazy_dict  # pool never materialized
    assert out.dict_enc.pool is pool
    expect = [batch.column("url").value(int(i)) for i in idx]
    assert out.to_pylist() == expect


# -- stable names in a profiler trace ----------------------------------------

def _op_names(lowered) -> set:
    import re

    return set(re.findall(r'op_name="([^"]*)"', lowered.compile().as_text()))


@pytest.mark.parametrize("mask_keys", [[b"salt"], []],
                         ids=["mask_and_filter", "filter_alone"])
def test_fused_program_carries_named_scopes_under_its_old_name(mask_keys):
    """The parts of the fused program are named in the compiled HLO's
    op metadata (what a profiler trace shows beside `%while.220`); the
    module keeps its name, which the benchmark's rooflines match - with
    masks, and for a run of filters alone (predicate inputs only)."""
    from transferia_tpu.columnar.batch import bucket_rows
    from transferia_tpu.ops.fused import FusedMaskFilterProgram

    n = 300
    bufs = [f"https://d{i}.io/x".encode() for i in range(n)]
    data = np.frombuffer(b"".join(bufs), dtype=np.uint8).copy()
    offsets = _offsets_from_lengths([len(b) for b in bufs])
    region = np.arange(n, dtype=np.int32)
    prog = FusedMaskFilterProgram(mask_keys, parse("region < 400"))
    blocks, nblocks, pred, states, spec, _rows, _h2d = prog._stage(
        [(data, offsets)] if mask_keys else [],
        {"region": (region, None)}, n, bucket_rows(n))
    lowered = prog._jit.lower(blocks, nblocks, states, pred, spec)
    names = _op_names(lowered)
    assert all(nm.startswith("jit(program)/") for nm in names
               if "/" in nm)
    scopes = ("pred_decode/", "predicate/", "keep_pack/")
    if mask_keys:
        scopes += ("mask_hmac/hmac_inner/", "mask_hmac/hmac_outer/",
                   "sha256_rounds/", "sha256_schedule/", "sha256_words/")
    for scope in scopes:
        assert any(scope in nm for nm in names), scope


@pytest.mark.parametrize("fn,args,scope", [
    ("unpack_bits", (np.zeros(4, np.uint32), 4, 32), "unpack_bits/"),
    ("delta_decode", (np.zeros(4, np.uint32), np.int32(0), 4, 32),
     "delta_decode/"),
    ("for_decode", (np.zeros(4, np.uint32), np.zeros(1, np.int32), 4, 32,
                    32), "for_decode/"),
    ("decode_dict_run", (np.zeros(4, np.uint32), np.zeros(16, np.int32),
                         4, 32), "dict_gather/"),
])
def test_decode_kernels_carry_named_scopes(fn, args, scope):
    from transferia_tpu.ops import decode

    names = _op_names(getattr(decode, fn).lower(*args))
    assert any(scope in nm for nm in names), names
