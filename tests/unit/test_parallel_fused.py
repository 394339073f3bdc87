"""Mesh-sharded fused chain: byte parity with the host path on the
virtual 8-device CPU mesh (conftest).

This exercises the PRODUCTION path end to end: build_chain plans a
DeviceFusedStep, which (with >1 device visible) routes large batches
through parallel/fusedmesh.ShardedFusedProgram — rows sharded over the
whole mesh, kept-count + shard-histogram psums crossing it.
"""

import numpy as np
import pytest

import jax

from tests.unit.test_decode_kernels import _op_names
from tests.unit.test_fused_device import (
    CONFIG,
    TID,
    batches_equal,
    make_batch,
    run_chain,
)
from transferia_tpu.parallel.fusedmesh import ShardedFusedProgram
from transferia_tpu.predicate import parse
from transferia_tpu.transform.fused import DeviceFusedStep, set_device_fusion
from transferia_tpu.transform import build_chain


def test_virtual_mesh_present():
    assert len(jax.devices()) == 8


def test_sharded_chain_parity_large_batch():
    # 16384 rows >= sharded_min_rows (1024 * 8): the sharded program runs
    batch = make_batch(16384)
    host = run_chain(CONFIG, batch, fused=False)
    dev = run_chain(CONFIG, batch, fused=True)
    batches_equal(host, dev)


def test_sharded_program_selected_for_large_batches():
    set_device_fusion(True)
    try:
        chain = build_chain(CONFIG)
        plan = chain.plan_for(TID, make_batch(4).schema)
        step = plan.steps[0]
        assert isinstance(step, DeviceFusedStep)
        assert step.sharded_program is not None
        assert step._sharded_min_rows == 1024 * 8
    finally:
        set_device_fusion(None)


def test_sharded_program_ragged_padding_parity():
    """A row count that is NOT a multiple of the device count: padding
    rows must not leak into keep, hexes, or the collective stats."""
    prog = ShardedFusedProgram([b"k"], parse("region < 400"))
    n = 8 * 1024 + 37
    rng = np.random.default_rng(3)
    vals = [f"v{i}".encode() for i in range(n)]
    data = np.frombuffer(b"".join(vals), dtype=np.uint8)
    offsets = np.zeros(n + 1, dtype=np.int32)
    np.cumsum([len(v) for v in vals], out=offsets[1:])
    region = rng.integers(0, 500, n).astype(np.int32)
    hexes, keep = prog.run(
        [(data, offsets)], {"region": (region, None)}, n)
    assert hexes[0].shape == (n, 64)
    assert keep.shape == (n,)
    np.testing.assert_array_equal(keep, region < 400)
    # collectives agree with the local truth
    assert prog.last_kept == int((region < 400).sum())
    assert prog.last_shard_hist is not None
    assert int(prog.last_shard_hist.sum()) == prog.last_kept
    # hex output matches hashlib on a sample of rows
    import hashlib
    import hmac as hmac_mod

    for i in (0, 1, n - 2, n - 1, 4321):
        expect = hmac_mod.new(b"k", vals[i], hashlib.sha256).hexdigest()
        assert bytes(hexes[0][i]).decode() == expect


def test_sharded_program_no_predicate():
    prog = ShardedFusedProgram([b"key"], None)
    n = 8192
    vals = [f"row-{i}".encode() for i in range(n)]
    data = np.frombuffer(b"".join(vals), dtype=np.uint8)
    offsets = np.zeros(n + 1, dtype=np.int32)
    np.cumsum([len(v) for v in vals], out=offsets[1:])
    hexes, keep = prog.run([(data, offsets)], {}, n)
    assert keep is None
    assert prog.last_kept == n  # no predicate: every real row kept


def test_sharded_program_steady_state_never_recompiles():
    """Round-4 review flagged mesh1 overhead swinging 0.3%..18.6% with
    re-jit as a suspect.  Pin the steady state: repeated runs — and any
    row count landing in the same per-device bucket — must hit the one
    compiled executable; only a bucket change may compile again."""
    prog = ShardedFusedProgram([b"k"], parse("region < 400"))

    def run(n):
        rng = np.random.default_rng(n)
        vals = [f"v{i}".encode() for i in range(n)]
        data = np.frombuffer(b"".join(vals), dtype=np.uint8)
        offsets = np.zeros(n + 1, dtype=np.int32)
        np.cumsum([len(v) for v in vals], out=offsets[1:])
        region = rng.integers(0, 500, n).astype(np.int32)
        prog.run([(data, offsets)], {"region": (region, None)}, n)

    run(8 * 1024)
    assert len(prog._compiled) == 1
    fn = next(iter(prog._compiled.values()))
    first = fn._cache_size()
    # repeated runs and any row count in the SAME per-device bucket pad
    # to identical shapes: zero new traces
    for n in (8 * 1024, 8 * 1024, 8 * 1024 - 100):
        run(n)
    assert fn._cache_size() == first, "steady-state call recompiled"
    # a different bucket may trace once more, never per call
    run(2 * 8 * 1024)
    grown = fn._cache_size()
    assert grown <= first + 1
    run(2 * 8 * 1024)
    assert fn._cache_size() == grown


# -- encoded per-shard staging (ISSUE 8 satellite) ---------------------------
#
# The mesh wire ships predicate columns and both validity planes in
# their dispatch encodings (per-shard bit-packed bitmaps/bools, delta
# ints) and reconstructs them inside the sharded program.  Parity with
# the raw wire is the contract; the byte accounting must show a >1.0
# ratio exactly when encoding engages.

def _varwidth(n, prefix="v"):
    vals = [f"{prefix}{i}".encode() for i in range(n)]
    data = np.frombuffer(b"".join(vals), dtype=np.uint8)
    offsets = np.zeros(n + 1, dtype=np.int32)
    np.cumsum([len(v) for v in vals], out=offsets[1:])
    return vals, data, offsets


def _run_mode(mode, pred_cols, n, pred="region < 400"):
    from transferia_tpu.ops import dispatch as dsp

    _, data, offsets = _varwidth(n)
    dsp.set_dispatch_encoding(mode)
    try:
        prog = ShardedFusedProgram([b"k"], parse(pred))
        hexes, keep = prog.run([(data, offsets)], pred_cols, n)
        return np.asarray(hexes[0]), np.asarray(keep), prog
    finally:
        dsp.set_dispatch_encoding(None)


def test_encoded_mesh_parity_int_with_nulls():
    n = 8 * 1024 + 123  # ragged: padding must stay invisible
    rng = np.random.default_rng(5)
    region = rng.integers(0, 500, n).astype(np.int32)
    validity = rng.random(n) > 0.15
    cols = {"region": (region, validity)}
    hx_raw, keep_raw, _ = _run_mode("raw", cols, n)
    hx_enc, keep_enc, _ = _run_mode("auto", cols, n)
    np.testing.assert_array_equal(hx_raw, hx_enc)
    np.testing.assert_array_equal(keep_raw, keep_enc)
    np.testing.assert_array_equal(keep_enc,
                                  (region < 400) & validity)


def test_encoded_mesh_parity_bool_column():
    n = 8 * 1024
    rng = np.random.default_rng(6)
    flag = rng.random(n) > 0.5
    cols = {"flag": (flag, None)}
    hx_raw, keep_raw, _ = _run_mode("raw", cols, n, pred="flag = true")
    hx_enc, keep_enc, _ = _run_mode("auto", cols, n, pred="flag = true")
    np.testing.assert_array_equal(hx_raw, hx_enc)
    np.testing.assert_array_equal(keep_raw, keep_enc)
    np.testing.assert_array_equal(keep_enc, flag)


def test_encoded_mesh_parity_monotonic_int64():
    """Sorted 64-bit ids: the per-shard delta path (narrow deltas,
    int32-exact values) must reconstruct exactly."""
    n = 8 * 1024
    ids = (np.arange(n, dtype=np.int64) * 3 + 100)
    cols = {"event_id": (ids, None)}
    hx_raw, keep_raw, _ = _run_mode("raw", cols, n,
                                    pred="event_id >= 103")
    hx_enc, keep_enc, _ = _run_mode("auto", cols, n,
                                    pred="event_id >= 103")
    np.testing.assert_array_equal(hx_raw, hx_enc)
    np.testing.assert_array_equal(keep_raw, keep_enc)
    assert int(keep_enc.sum()) == n - 1


def test_encoded_mesh_compresses_the_wire():
    """auto must report encoded < raw-equivalent bytes; raw must stay
    exactly 1.0 (the honesty gauge)."""
    from transferia_tpu.stats.trace import TELEMETRY

    n = 8 * 2048
    rng = np.random.default_rng(7)
    region = rng.integers(0, 500, n).astype(np.int32)
    validity = rng.random(n) > 0.1
    cols = {"region": (region, validity)}
    TELEMETRY.reset()
    _run_mode("raw", cols, n)
    snap = TELEMETRY.snapshot()
    assert snap["h2d_encoded_bytes"] == snap["h2d_raw_equiv_bytes"]
    TELEMETRY.reset()
    _run_mode("auto", cols, n)
    snap = TELEMETRY.snapshot()
    assert snap["h2d_encoded_bytes"] < snap["h2d_raw_equiv_bytes"]


def test_sharded_encoders_roundtrip_host():
    """Host-side unit check of the per-shard encoders against their
    device decoders (no mesh): validity bitmaps and delta words."""
    import jax.numpy as jnp

    from transferia_tpu.ops.decode import unpack_validity
    from transferia_tpu.ops.dispatch import (
        _encode_delta_sharded,
        decode_pred_device_sharded,
        encode_pred_column_sharded,
        encode_validity_sharded,
    )

    rng = np.random.default_rng(8)
    v2 = rng.random((4, 512)) > 0.3
    words = encode_validity_sharded(v2)
    assert words.shape[0] == 4
    for d in range(4):
        got = np.asarray(unpack_validity(jnp.asarray(words[d]), 512))
        np.testing.assert_array_equal(got, v2[d])

    d2 = np.cumsum(rng.integers(0, 9, (4, 512)), axis=1).astype(
        np.int64)
    enc = _encode_delta_sharded(d2)
    assert enc is not None
    bases, dwords, bw = enc
    assert bases.dtype == np.int32 and dwords.shape[0] == 4

    # full column round trip through the public encoder
    data = d2.reshape(-1)
    validity = rng.random(data.size) > 0.2
    spec, arrays, raw_equiv = encode_pred_column_sharded(
        "c", data, validity, data.size, 4, 512, True)
    assert spec.kind == "delta" and spec.valid_mode == "bits"
    assert raw_equiv == data.size * 8 + data.size
    for d in range(4):
        local = tuple(jnp.asarray(a[d:d + 1]) for a in arrays)
        dd, vv = decode_pred_device_sharded(spec, local, 512)
        np.testing.assert_array_equal(
            np.asarray(dd), d2[d].astype(np.int64))
        np.testing.assert_array_equal(
            np.asarray(vv), validity.reshape(4, 512)[d])


@pytest.mark.parametrize("mask_keys,scopes", [
    ([b"k"], ("mask_hmac/hmac_inner/", "pred_decode/", "predicate/",
              "shard_hist/", "mesh_psum/")),
    # a run of filters alone: predicate inputs only, the same names
    ([], ("pred_decode/", "predicate/", "shard_hist/", "mesh_psum/")),
], ids=["mask_and_filter", "filter_alone"])
def test_sharded_program_names_its_body_and_its_psum(mask_keys, scopes):
    """The sharded body's parts and the two psums carry named scopes in
    the compiled HLO's op metadata (what a profiler trace shows, and what
    a four-chip cell's roofline and a later collectives metric find the
    program by)."""
    import jax

    # an inner jit that an earlier test of this worker traced under
    # another scope keeps that trace's names (the driver's -n 6 run puts
    # other files' tests before this one): start from no trace
    jax.clear_caches()
    prog = ShardedFusedProgram(mask_keys, parse("region < 400"))
    lowered = {}
    get_compiled = prog._get_compiled

    def spy(*key):
        fn = get_compiled(*key)

        def call(*args):
            lowered["names"] = _op_names(fn.lower(*args))
            return fn(*args)

        return call

    prog._get_compiled = spy
    n = 8 * 1024
    vals = [f"v{i}".encode() for i in range(n)]
    data = np.frombuffer(b"".join(vals), dtype=np.uint8)
    offsets = np.zeros(n + 1, dtype=np.int32)
    np.cumsum([len(v) for v in vals], out=offsets[1:])
    region = (np.arange(n) % 500).astype(np.int32)
    prog.run([(data, offsets)] if mask_keys else [],
             {"region": (region, None)}, n)
    assert prog.last_kept == int((region < 400).sum())
    names = lowered["names"]
    for scope in scopes:
        assert any(scope in nm for nm in names), scope
    if not mask_keys:
        assert not any("mask_hmac/" in nm for nm in names)


# -- what a four-chip snapshot met (PERF.md section 6, PR 27) -------------------
#
# Every part file brings dictionary pools of its own sizes, and eight part
# threads reach a new signature together: neither may mean a compile each.

def _dict_input(n_values, n_rows, seed):
    from transferia_tpu.parallel.fusedmesh import DictMaskInput

    rng = np.random.default_rng(seed)
    digests = rng.integers(0, 2**32, (n_values, 8), dtype=np.uint64) \
        .astype(np.uint32)
    codes = rng.integers(0, n_values, n_rows).astype(np.int32)
    return DictMaskInput(codes, digests, 68), digests, codes


def test_sharded_program_pool_sizes_of_one_bucket_share_a_program():
    from transferia_tpu.columnar.hexcol import digests_to_hex

    prog = ShardedFusedProgram([b"k"], None)
    n = 8 * 1024
    sizes = []
    for n_values in (5, 7, 164, 168):
        entry, digests, codes = _dict_input(n_values, n, n_values)
        hexes, keep = prog.run([entry], {}, n)
        np.testing.assert_array_equal(
            np.asarray(hexes[0]), digests_to_hex(digests[codes]))
        fn = next(iter(prog._compiled.values()))
        sizes.append(fn._cache_size())
    assert len(prog._compiled) == 1
    assert sizes == [sizes[0]] * 4, "a pool size compiled a program"
    # another bucket is another shape, once
    entry, _d, _c = _dict_input(300, n, 300)
    prog.run([entry], {}, n)
    assert fn._cache_size() == sizes[0] + 1


def test_sharded_program_module_is_named_as_the_one_chip_program():
    """The profiler's module name is what `mask_program_roofline` finds the
    program by: jit_program, on one chip and on a mesh."""
    prog = ShardedFusedProgram([b"k"], None)
    fn = prog._get_compiled(("flat",), (), "bits")
    assert fn.__name__ == "program"


def test_sharded_program_threads_compile_a_signature_once(monkeypatch):
    import threading

    from transferia_tpu.parallel import fusedmesh

    traced = []
    core = fusedmesh.hmac_device_core

    def counting_core(*args):
        traced.append(1)        # runs while the program is traced
        return core(*args)

    monkeypatch.setattr(fusedmesh, "hmac_device_core", counting_core)
    n = 8 * 1024
    _, data, offsets = _varwidth(n, prefix="t")
    region = (np.arange(n) % 500).astype(np.int32)
    before = len(ShardedFusedProgram._compiled_sigs)
    gate = threading.Barrier(4)
    kept, errors = [], []

    def work():
        try:
            own = ShardedFusedProgram([b"k"], parse("region < 417"))
            gate.wait()
            own.run([(data, offsets)], {"region": (region, None)}, n)
            kept.append(own.last_kept)
        except Exception as e:  # surfaced below
            errors.append(e)

    threads = [threading.Thread(target=work) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    assert kept == [int((region < 417).sum())] * 4
    # four instances, one shared jit, one signature, traced once
    assert len(ShardedFusedProgram._compiled_sigs) == before + 1
    fns = [fn for k, fn in ShardedFusedProgram._jit_cache.items()
           if k[0] == repr(parse("region < 417"))]
    assert len(fns) == 1 and fns[0]._cache_size() == 1
    assert len(traced) == 1, "threads traced one signature more than once"
