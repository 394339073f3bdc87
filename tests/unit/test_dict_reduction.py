"""Dict-native reduction plane (ops/rowhash.py + the no-flatten
pipeline discipline).

Digest parity is the load-bearing contract: a dictionary-encoded
column's fingerprint/row_lanes/HMAC mask must be BYTE-IDENTICAL to the
flat path's, across every canonical var-width type, null shapes, and
sliced/taken code arrays — while the column never materializes flat
buffers (`dict_flat_materializations` stays zero end-to-end on a
dict-heavy snapshot).
"""

import numpy as np
import pytest

from transferia_tpu.abstract.schema import (
    CanonicalType,
    ColSchema,
    TableID,
    TableSchema,
)
from transferia_tpu.columnar.batch import (
    Column,
    ColumnBatch,
    DictEnc,
    DictPool,
    _gather_varwidth,
    _offsets_from_lengths,
)
from transferia_tpu.ops import rowhash
from transferia_tpu.ops.rowhash import (
    fingerprint_host,
    pool_accumulators,
    prep_batch,
    row_lanes,
)
from transferia_tpu.stats.trace import TELEMETRY

TID = TableID("d", "t")

VAR_TYPES = [
    CanonicalType.UTF8,
    CanonicalType.STRING,
    CanonicalType.ANY,
    CanonicalType.DECIMAL,
]


def _pool(values: list[bytes], sentinel: bool = True) -> DictPool:
    data = np.frombuffer(b"".join(values), dtype=np.uint8).copy()
    lens = [len(v) for v in values] + ([0] if sentinel else [])
    off = _offsets_from_lengths(lens)
    return DictPool(data, off,
                    null_code=len(values) if sentinel else None)


def _dict_col(name: str, ctype: CanonicalType, pool: DictPool,
              codes: np.ndarray,
              validity=None) -> Column:
    return Column(name, ctype, validity=validity,
                  dict_enc=DictEnc(codes.astype(np.int32), pool=pool))


def _flat_twin(col: Column) -> Column:
    """The flat column the dict column WOULD materialize to — built via
    DictEnc.materialize directly so Column._materialize (and its
    counter) never runs on the original."""
    data, off = col.dict_enc.materialize()
    return Column(col.name, col.ctype, data, off, col.validity)


def _batches(col: Column, extra_int: bool = True):
    schema_cols = [ColSchema(col.name, col.ctype)]
    cols_d = {col.name: col}
    cols_f = {col.name: _flat_twin(col)}
    if extra_int:
        ints = np.arange(col.n_rows, dtype=np.int64)
        schema_cols.append(ColSchema("i", CanonicalType.INT64))
        cols_d["i"] = Column("i", CanonicalType.INT64, ints)
        cols_f["i"] = Column("i", CanonicalType.INT64, ints.copy())
    schema = TableSchema(tuple(schema_cols))
    return (ColumnBatch(TID, schema, cols_d),
            ColumnBatch(TID, schema, cols_f))


def _assert_parity(dict_b: ColumnBatch, flat_b: ColumnBatch):
    fd = fingerprint_host(*prep_batch(dict_b))
    ff = fingerprint_host(*prep_batch(flat_b))
    assert fd.digest() == ff.digest()
    r1d, r2d = row_lanes(*prep_batch(dict_b))
    r1f, r2f = row_lanes(*prep_batch(flat_b))
    np.testing.assert_array_equal(r1d, r1f)
    np.testing.assert_array_equal(r2d, r2f)


class TestDigestParity:
    @pytest.mark.parametrize("ctype", VAR_TYPES)
    def test_all_var_types(self, ctype):
        pool = _pool([b"alpha", b"", b"gamma-longer-value" * 4, b"d"])
        rng = np.random.default_rng(3)
        codes = rng.integers(0, 4, 500)
        col = _dict_col("s", ctype, pool, codes)
        _assert_parity(*_batches(col))

    def test_null_code_rows(self):
        pool = _pool([b"v0", b"v1", b"v2"])
        rng = np.random.default_rng(5)
        codes = rng.integers(0, 3, 300)
        validity = rng.random(300) > 0.2
        codes = np.where(validity, codes, pool.null_code)
        col = _dict_col("s", CanonicalType.UTF8, pool,
                        codes, validity=validity)
        _assert_parity(*_batches(col))

    def test_all_null(self):
        pool = _pool([b"only"])
        n = 64
        codes = np.full(n, pool.null_code, dtype=np.int32)
        col = _dict_col("s", CanonicalType.UTF8, pool, codes,
                        validity=np.zeros(n, dtype=bool))
        _assert_parity(*_batches(col))

    def test_empty_pool_empty_batch(self):
        pool = _pool([], sentinel=False)
        col = _dict_col("s", CanonicalType.UTF8, pool,
                        np.zeros(0, dtype=np.int32))
        dict_b, flat_b = _batches(col, extra_int=False)
        assert fingerprint_host(*prep_batch(dict_b)).count == 0
        _assert_parity(dict_b, flat_b)

    def test_sentinel_less_pool_with_validity(self):
        pool = _pool([b"x", b"yy"], sentinel=False)
        codes = np.array([0, 1, 0, 1], dtype=np.int32)
        validity = np.array([True, False, True, True])
        col = _dict_col("s", CanonicalType.UTF8, pool, codes,
                        validity=validity)
        _assert_parity(*_batches(col))

    def test_sliced_and_taken_dict_columns(self):
        pool = _pool([b"aa", b"bbb", b"cccc", b""])
        rng = np.random.default_rng(9)
        codes = rng.integers(0, 4, 400)
        col = _dict_col("s", CanonicalType.UTF8, pool, codes)
        sliced = col._take_contiguous(37, 311)
        assert sliced.is_lazy_dict
        _assert_parity(*_batches(sliced))
        idx = rng.permutation(400)[:123]
        taken = col.take(idx)
        assert taken.is_lazy_dict
        _assert_parity(*_batches(taken))

    def test_device_backend_parity(self):
        pool = _pool([b"alpha", b"", b"gamma" * 10])
        rng = np.random.default_rng(11)
        codes = rng.integers(0, 3, 700)
        validity = rng.random(700) > 0.1
        codes = np.where(validity, codes, pool.null_code)
        col = _dict_col("s", CanonicalType.UTF8, pool, codes,
                        validity=validity)
        dict_b, flat_b = _batches(col)
        dev = rowhash.DeviceFingerprintProgram()
        cols, n = prep_batch(dict_b)
        assert any(c.kind == "dict" for c in cols)
        dev.dispatch(cols, n)
        assert (dev.collect().digest()
                == fingerprint_host(*prep_batch(flat_b)).digest())

    def test_numpy_fallback_parity(self, monkeypatch):
        """Digest with the native lib OFF == digest with it on: the
        fused lane kernels and the accumulator memo are byte-exact
        twins of the numpy chain."""
        pool = _pool([b"one", b"two-longer", b""])
        rng = np.random.default_rng(13)
        codes = rng.integers(0, 3, 300)
        col = _dict_col("s", CanonicalType.UTF8, pool, codes)
        dict_b, _ = _batches(col)
        with_native = fingerprint_host(*prep_batch(dict_b)).digest()
        from transferia_tpu import native as native_pkg

        monkeypatch.setattr(native_pkg, "_lib", None)
        monkeypatch.setenv("TRANSFERIA_TPU_NO_NATIVE", "1")
        pool2 = _pool([b"one", b"two-longer", b""])  # fresh: no memo
        col2 = _dict_col("s", CanonicalType.UTF8, pool2, codes)
        dict_b2, _ = _batches(col2)
        assert fingerprint_host(
            *prep_batch(dict_b2)).digest() == with_native


class TestPoolAccumulators:
    def test_memoized_once_per_pool(self):
        pool = _pool([b"aa", b"bb"])
        a = pool_accumulators(pool)
        b = pool_accumulators(pool)
        assert a[0] is b[0] and a[1] is b[1]

    def test_shared_across_columns_and_batches(self):
        pool = _pool([b"aa", b"bb"])
        c1 = _dict_col("x", CanonicalType.UTF8, pool,
                       np.array([0, 1], dtype=np.int32))
        c2 = _dict_col("y", CanonicalType.UTF8, pool,
                       np.array([1, 0], dtype=np.int32))
        schema = TableSchema((ColSchema("x", CanonicalType.UTF8),
                              ColSchema("y", CanonicalType.UTF8)))
        prep_batch(ColumnBatch(TID, schema, {"x": c1, "y": c2}))
        assert pool.memo_get(rowhash._ACC_MEMO_KEY) is not None

    def test_accumulator_equals_flat_rows(self):
        """The pool-entry accumulator IS the flat row accumulator."""
        values = [b"short", b"a-much-longer-value-here" * 3, b""]
        pool = _pool(values, sentinel=False)
        a1, a2 = pool_accumulators(pool)
        # flat column holding the same byte rows, via the var path
        flat = Column.from_pylist("v", CanonicalType.STRING, values)
        cols, n = prep_batch(
            ColumnBatch(TID, TableSchema(
                (ColSchema("v", CanonicalType.STRING),)), {"v": flat}))
        f1, f2 = rowhash._var_accs_host(cols[0], n)
        np.testing.assert_array_equal(a1, f1)
        np.testing.assert_array_equal(a2, f2)


class TestChaosAuditorEquivalence:
    def test_row_keys_same_either_route(self):
        from transferia_tpu.chaos.invariants import batch_row_keys

        pool = _pool([b"k1", b"k2", b"k3"])
        rng = np.random.default_rng(17)
        codes = rng.integers(0, 3, 256)
        validity = rng.random(256) > 0.15
        codes = np.where(validity, codes, pool.null_code)
        col = _dict_col("s", CanonicalType.UTF8, pool, codes,
                        validity=validity)
        dict_b, flat_b = _batches(col)
        np.testing.assert_array_equal(batch_row_keys(dict_b),
                                      batch_row_keys(flat_b))


class TestMaskSubsetRoute:
    def _big_pool_col(self, n_rows=20, with_nulls=True):
        values = [f"value-{i:05d}".encode() for i in range(300)]
        pool = _pool(values)
        rng = np.random.default_rng(19)
        codes = rng.integers(0, 300, n_rows)
        validity = None
        if with_nulls:
            validity = rng.random(n_rows) > 0.3
            codes = np.where(validity, codes, pool.null_code)
        return pool, _dict_col("s", CanonicalType.UTF8, pool, codes,
                               validity=validity)

    @pytest.mark.parametrize("with_nulls", [False, True])
    def test_subset_hash_matches_flat(self, with_nulls):
        from transferia_tpu.transform.plugins.mask import (
            _host_hmac_hex,
            mask_dict_column,
        )

        pool, col = self._big_pool_col(with_nulls=with_nulls)
        out = mask_dict_column(b"key", col)
        assert out.is_lazy_dict  # never fell through to flat hashing
        # the big pool itself was NOT hashed whole (no memo landed)
        assert pool.memo_get(("hmac_hex", b"key")) is None
        flat = _flat_twin(col)
        fd, fo = _host_hmac_hex(b"key", flat.data, flat.offsets,
                                col.validity)
        np.testing.assert_array_equal(out.data, fd)
        np.testing.assert_array_equal(out.offsets, fo)

    def test_fused_host_route_stays_encoded(self):
        """DeviceFusedStep's host strategy must keep a big-pool dict
        column encoded (subset route), never flatten it."""
        from transferia_tpu.transform.fused import DeviceFusedStep
        from transferia_tpu.transform.plugins.mask import MaskField

        jax = pytest.importorskip("jax")  # noqa: F841

        pool, col = self._big_pool_col(n_rows=24)
        ints = np.arange(24, dtype=np.int64)
        schema = TableSchema((ColSchema("s", CanonicalType.UTF8),
                              ColSchema("i", CanonicalType.INT64)))
        batch = ColumnBatch(TID, schema, {
            "s": col, "i": Column("i", CanonicalType.INT64, ints)})
        step = DeviceFusedStep([MaskField(columns=["s"], salt="x")],
                               [("s", b"x")], None)
        TELEMETRY.reset()
        out = step._apply_host(batch).transformed
        assert out.column("s").is_lazy_dict
        snap = TELEMETRY.snapshot()
        assert snap["dict_flat_materializations"] == 0


class TestConcatStaysEncoded:
    def _batch(self, pool, codes):
        schema = TableSchema((ColSchema("s", CanonicalType.UTF8),))
        return ColumnBatch(TID, schema, {
            "s": _dict_col("s", CanonicalType.UTF8, pool,
                           np.asarray(codes))})

    def test_shared_pool_concat_is_code_concat(self):
        pool = _pool([b"aa", b"bbb"])
        a = self._batch(pool, [0, 1, 0])
        b = self._batch(pool, [1, 1])
        TELEMETRY.reset()
        out = ColumnBatch.concat([a, b])
        col = out.column("s")
        assert col.is_lazy_dict
        assert col.dict_enc.pool is pool
        np.testing.assert_array_equal(col.dict_enc.indices,
                                      [0, 1, 0, 1, 1])
        snap = TELEMETRY.snapshot()
        assert snap["dict_flat_materializations"] == 0
        assert snap["lazy_dict_preserved"] >= 1

    def test_different_pools_fall_back_and_count(self):
        a = self._batch(_pool([b"aa", b"bbb"]), [0, 1])
        b = self._batch(_pool([b"aa", b"bbb"]), [1, 0])
        TELEMETRY.reset()
        out = ColumnBatch.concat([a, b])
        assert out.column("s").to_pylist() == ["aa", "bbb",
                                               "bbb", "aa"]
        assert TELEMETRY.snapshot()["dict_flat_materializations"] > 0


class TestGatherVarNative:
    def test_native_matches_numpy(self, monkeypatch):
        rng = np.random.default_rng(23)
        lens = rng.integers(0, 40, 200)
        data = rng.integers(0, 256, int(lens.sum())).astype(np.uint8)
        offsets = _offsets_from_lengths(lens)
        idx = rng.integers(0, 200, 500).astype(np.int64)
        got_d, got_o = _gather_varwidth(data, offsets, idx)
        from transferia_tpu import native as native_pkg

        monkeypatch.setattr(native_pkg, "_lib", None)
        monkeypatch.setenv("TRANSFERIA_TPU_NO_NATIVE", "1")
        want_d, want_o = _gather_varwidth(data, offsets, idx)
        np.testing.assert_array_equal(got_d, want_d)
        np.testing.assert_array_equal(got_o, want_o)

    def test_empty_gather(self):
        data = np.zeros(0, dtype=np.uint8)
        offsets = np.zeros(1, dtype=np.int32)
        out, off = _gather_varwidth(data, offsets,
                                    np.zeros(0, dtype=np.int64))
        assert len(out) == 0
        np.testing.assert_array_equal(off, [0])

    def test_out_of_range_keeps_numpy_semantics(self):
        """The unchecked C loops must never see bad indices: OOB
        raises IndexError, negatives wrap, exactly like numpy."""
        data = np.frombuffer(b"aabbbcccc", dtype=np.uint8).copy()
        offsets = np.array([0, 2, 5, 9], dtype=np.int32)
        with pytest.raises(IndexError):
            _gather_varwidth(data, offsets,
                             np.array([0, 100], dtype=np.int64))
        out, off = _gather_varwidth(data, offsets,
                                    np.array([-1, 0], dtype=np.int64))
        assert bytes(out) == b"ccccaa"
        np.testing.assert_array_equal(off, [0, 4, 6])


class TestCorruptCodesRaise:
    def test_prep_batch_rejects_out_of_range_codes(self):
        """A corrupt dict page's codes must raise, not gather stray
        memory into a plausible-looking digest (both backends gather
        unchecked after this gate)."""
        pool = _pool([b"aa", b"bb"])
        bad = _dict_col("s", CanonicalType.UTF8, pool,
                        np.array([0, 99], dtype=np.int32))
        schema = TableSchema((ColSchema("s", CanonicalType.UTF8),))
        with pytest.raises(IndexError, match="out of range"):
            prep_batch(ColumnBatch(TID, schema, {"s": bad}))
        neg = _dict_col("s", CanonicalType.UTF8, pool,
                        np.array([0, -2], dtype=np.int32))
        with pytest.raises(IndexError, match="out of range"):
            prep_batch(ColumnBatch(TID, schema, {"s": neg}))


class TestSnapshotNoFlatMaterializations:
    def test_dict_heavy_sample_to_memory(self):
        """A dict-encoded sample→memory snapshot (with fingerprint
        validation streaming every batch through rowhash) finishes
        with ZERO flat materializations — the acceptance criterion of
        the dict-native reduction plane."""
        from transferia_tpu.coordinator import MemoryCoordinator
        from transferia_tpu.models import Transfer
        from transferia_tpu.providers.memory import (
            MemoryTargetParams,
            get_store,
        )
        from transferia_tpu.providers.sample import SampleSourceParams
        from transferia_tpu.tasks import SnapshotLoader

        sid = "dictnative-snap"
        t = Transfer(
            id=sid,
            src=SampleSourceParams(preset="users", rows=2048,
                                   batch_rows=512, dict_encode=True),
            dst=MemoryTargetParams(sink_id=sid),
            validation={"fingerprint": True},
        )
        TELEMETRY.reset()
        SnapshotLoader(t, MemoryCoordinator(),
                       operation_id=f"op-{sid}").upload_tables()
        snap = TELEMETRY.snapshot()
        assert snap["dict_flat_materializations"] == 0, snap
        assert snap["lazy_dict_preserved"] > 0
        store = get_store(sid)
        assert len(store.rows()) == 2048

    def test_dict_sample_digest_equals_flat_sample(self):
        """Same seed, dict_encode on/off: identical table digests."""
        from transferia_tpu.ops.rowhash import TableFingerprinter
        from transferia_tpu.providers.sample import make_batch

        tid = TableID("sample", "users")
        fp_d = TableFingerprinter(backend="host")
        fp_f = TableFingerprinter(backend="host")
        for lo in range(0, 1000, 250):
            fp_d.push(make_batch("users", tid, lo, 250, seed=5,
                                 dict_encode=True))
            fp_f.push(make_batch("users", tid, lo, 250, seed=5))
        assert fp_d.result().digest() == fp_f.result().digest()


class TestPoolAccsFailpoint:
    def test_failpoint_fires_and_recovers(self):
        from transferia_tpu.chaos import failpoints

        pool = _pool([b"aa", b"bb"])
        failpoints.configure("rowhash.pool_accs=raise:IOError", seed=1)
        try:
            with pytest.raises(OSError):
                pool_accumulators(pool)
        finally:
            failpoints.reset()
        # no partial memo left behind; a retry computes cleanly
        assert pool.memo_get(rowhash._ACC_MEMO_KEY) is None
        a1, a2 = pool_accumulators(pool)
        assert len(a1) == pool.n_values == len(a2)


class TestDeviceRowKeys:
    """Device-side dedup-window keys (ROADMAP item 2 remainder): the
    jitted key program is byte-identical to the host gather for every
    column kind — fixed, var-width, dict-native — including nulls, so
    a dedup window fed by either backend recognizes the same torn-write
    prefixes."""

    def _tid(self):
        return TableID("sample", "events")

    @pytest.mark.parametrize("preset,n,dict_encode", [
        ("iot", 257, False),      # fixed + var mix, non-pow2 rows
        ("users", 512, False),
        ("iot", 300, True),       # dict-native accumulator gather
        ("users", 64, True),
        ("iot", 1, False),        # single row
    ])
    def test_device_keys_byte_identical(self, preset, n, dict_encode):
        from transferia_tpu.providers.sample import make_batch

        b = make_batch(preset, self._tid(), 0, n, 7,
                       dict_encode=dict_encode)
        host = rowhash.batch_row_keys(b)
        dev = rowhash.batch_row_keys_device(b)
        assert np.array_equal(host, dev)

    def test_device_keys_with_nulls(self):
        from transferia_tpu.abstract.schema import TableSchema

        schema = TableSchema([
            ColSchema("a", CanonicalType.INT64),
            ColSchema("s", CanonicalType.UTF8),
        ])
        b = ColumnBatch.from_pydict(self._tid(), schema, {
            "a": [1, None, 3, None, 5],
            "s": ["x", "y", None, None, "zz"],
        })
        assert np.array_equal(rowhash.batch_row_keys(b),
                              rowhash.batch_row_keys_device(b))

    def test_env_knob_routes_auto_to_device(self, monkeypatch):
        from transferia_tpu.providers.sample import make_batch

        b = make_batch("iot", self._tid(), 0, 128, 3)
        host = rowhash.batch_row_keys(b)
        monkeypatch.setenv("TRANSFERIA_TPU_DEDUP_KEYS", "device")
        assert rowhash._device_keys_requested()
        assert np.array_equal(rowhash.batch_row_keys(b), host)

    def test_explicit_backends(self):
        from transferia_tpu.providers.sample import make_batch

        b = make_batch("users", self._tid(), 0, 96, 5)
        assert np.array_equal(
            rowhash.batch_row_keys(b, backend="host"),
            rowhash.batch_row_keys(b, backend="device"))

    def test_dedup_window_agrees_across_backends(self, monkeypatch):
        """The staged-commit window behaves identically whichever
        backend computed the keys: an armed replay of a torn prefix
        drops either way."""
        from transferia_tpu.providers.sample import make_batch
        from transferia_tpu.providers.staging import DedupWindow

        b = make_batch("iot", self._tid(), 0, 96, 7)
        for device in (False, True):
            if device:
                monkeypatch.setenv("TRANSFERIA_TPU_DEDUP_KEYS",
                                   "device")
            w = DedupWindow()
            w.filter(b.slice(0, 64))
            w.arm_replay()
            out, dropped = w.filter(b)
            assert dropped == 64 and out.n_rows == 32
