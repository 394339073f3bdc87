"""Order-independent table fingerprints (ops/rowhash.py) and the
checksum task's fingerprint method.

The fingerprint is the device-reducible complement of the reference's
row-by-row checksum (pkg/worker/tasks/checksum.go): batches stream
through a two-lane hash whose reduction (sum/xor/count) is order- and
batching-independent and mergeable across snapshot shards.
"""

import numpy as np
import pytest

from transferia_tpu.abstract.schema import TableID, new_table_schema
from transferia_tpu.columnar.batch import ColumnBatch
from transferia_tpu.ops.rowhash import (
    DeviceFingerprintProgram,
    FingerprintAggregate,
    TableFingerprinter,
    fingerprint_host,
    prep_batch,
)

SCHEMA = new_table_schema([
    ("id", "int64", True), ("name", "utf8"), ("score", "double"),
    ("flag", "boolean"),
])
TID = TableID("db", "t")


def mk(rows=256, order=None, tweak_at=None):
    idx = list(order) if order is not None else list(range(rows))
    return ColumnBatch.from_pydict(TID, SCHEMA, {
        "id": idx,
        "name": [None if i % 7 == 0 else f"name-{i}" for i in idx],
        "score": [None if i % 5 == 0 else
                  i * 1.5 + (1.0 if i == tweak_at else 0.0) for i in idx],
        "flag": [i % 2 == 0 for i in idx],
    })


def test_host_device_parity():
    cols, n = prep_batch(mk(500))
    host = fingerprint_host(cols, n)
    dev = DeviceFingerprintProgram()
    dev.dispatch(cols, n)
    assert dev.collect().digest() == host.digest()


def test_order_and_batching_independence():
    whole = fingerprint_host(*prep_batch(mk(300)))
    rng = np.random.default_rng(0)
    shuffled = mk(300, order=rng.permutation(300))
    fp = TableFingerprinter(backend="host")
    for lo in range(0, 300, 71):
        fp.push(shuffled.slice(lo, min(lo + 71, 300)))
    assert fp.result().digest() == whole.digest()


def test_shard_merge_equals_whole():
    whole = fingerprint_host(*prep_batch(mk(200)))
    parts = [fingerprint_host(*prep_batch(mk(200).slice(lo, lo + 50)))
             for lo in range(0, 200, 50)]
    agg = FingerprintAggregate()
    for p in parts:
        agg.merge(p)
    assert agg == whole


def test_single_value_change_detected():
    a = fingerprint_host(*prep_batch(mk(300)))
    b = fingerprint_host(*prep_batch(mk(300, tweak_at=123)))
    assert a.digest() != b.digest()


def test_null_vs_value_distinct():
    s = new_table_schema([("x", "utf8")])
    a = ColumnBatch.from_pydict(TID, s, {"x": ["v", None]})
    b = ColumnBatch.from_pydict(TID, s, {"x": ["v", ""]})
    fa = fingerprint_host(*prep_batch(a))
    fb = fingerprint_host(*prep_batch(b))
    assert fa.digest() != fb.digest()


def test_float_canonicalization():
    s = new_table_schema([("x", "double")])
    a = ColumnBatch.from_pydict(TID, s, {"x": [0.0, float("nan")]})
    b = ColumnBatch.from_pydict(TID, s, {"x": [-0.0, float("nan")]})
    assert (fingerprint_host(*prep_batch(a)).digest()
            == fingerprint_host(*prep_batch(b)).digest())


def test_column_names_seed_the_hash():
    s1 = new_table_schema([("a", "int64"), ("b", "int64")])
    s2 = new_table_schema([("b", "int64"), ("a", "int64")])
    x = ColumnBatch.from_pydict(TID, s1, {"a": [1, 2], "b": [3, 4]})
    y = ColumnBatch.from_pydict(TID, s2, {"b": [1, 2], "a": [3, 4]})
    assert (fingerprint_host(*prep_batch(x)).digest()
            != fingerprint_host(*prep_batch(y)).digest())


def test_empty_table():
    fp = TableFingerprinter(backend="host")
    assert fp.result().count == 0
    assert fp.result().digest().endswith(":0")


def test_native_polyhash_matches_numpy_fallback(monkeypatch):
    """The C++ pass over real bytes == the packed-matrix numpy hash."""
    batch = mk(300)
    native = fingerprint_host(*prep_batch(batch))
    from transferia_tpu import native as native_pkg

    monkeypatch.setattr(native_pkg, "_lib", None)
    monkeypatch.setenv("TRANSFERIA_TPU_NO_NATIVE", "1")  # numpy path
    fallback = fingerprint_host(*prep_batch(batch))
    assert native.digest() == fallback.digest()


def test_device_backend_via_fingerprinter():
    rows = mk(200)
    host = TableFingerprinter(backend="host")
    host.push(rows)
    dev = TableFingerprinter(backend="device")
    dev.push(rows)
    assert dev.result().digest() == host.result().digest()


class TestChecksumFingerprintMethod:
    def _storage(self, sid, rows=120, corrupt_at=None):
        from transferia_tpu.factories import new_storage
        from transferia_tpu.models import Transfer
        from transferia_tpu.providers.memory import (
            MemorySourceParams,
            seed_source,
        )
        from transferia_tpu.providers.sample import make_batch

        tid = TableID("sample", "users")
        b = make_batch("users", tid, 0, rows, seed=3)
        if corrupt_at is not None:
            b.columns["score"].data[corrupt_at] += 0.5
        seed_source(sid, [b])
        return new_storage(Transfer(id=sid, src=MemorySourceParams(
            source_id=sid)))

    def test_match_short_circuits_row_compare(self):
        from transferia_tpu.tasks.checksum import (
            ChecksumParameters,
            compare_checksum,
        )

        src = self._storage("fp_src")
        dst = self._storage("fp_dst")
        report = compare_checksum(
            src, dst,
            params=ChecksumParameters(method="fingerprint"))
        assert report.ok, report.summary()
        t = report.tables[0]
        assert t.strategy == "fingerprint"
        assert t.source_fingerprint == t.target_fingerprint != ""
        assert t.compared_rows == 0  # no row-level pass ran

    def test_mismatch_falls_back_to_row_diagnosis(self):
        from transferia_tpu.tasks.checksum import (
            ChecksumParameters,
            compare_checksum,
        )

        src = self._storage("fp_src2")
        dst = self._storage("fp_dst2", corrupt_at=77)
        report = compare_checksum(
            src, dst,
            params=ChecksumParameters(method="fingerprint",
                                      keyset_chunk=16))
        assert not report.ok
        t = report.tables[0]
        assert t.source_fingerprint != t.target_fingerprint
        assert any("fingerprints differ" in m for m in t.mismatches)
        # the row-level pass ran and named the column
        assert any("score" in m for m in t.mismatches)
        assert t.strategy.startswith("fingerprint+")
