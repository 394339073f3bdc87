"""Decode-pipeline readahead (providers/readahead.py + the fs provider
wiring): ordering, error propagation, cancellation, memory caps, and the
end-to-end equivalence of the prefetched paths with serial decode."""

import threading
import time

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from transferia_tpu.providers import readahead as ra_mod
from transferia_tpu.providers.readahead import RowGroupReadahead


class _Gauge:
    """inc/dec recorder with the prometheus Gauge surface."""

    def __init__(self):
        self.v = 0.0
        self.max = 0.0
        self._lock = threading.Lock()

    def inc(self, amount=1.0):
        with self._lock:
            self.v += amount
            self.max = max(self.max, self.v)

    def dec(self, amount=1.0):
        with self._lock:
            self.v -= amount


def test_ordering_preserved_under_jitter():
    groups = list(range(12))

    def decode(g):
        time.sleep(0.001 * (g % 3))
        return g * 10

    with RowGroupReadahead(groups, decode, max_groups=3) as ra:
        got = list(ra)
    assert got == [(g, g * 10) for g in groups]


def test_consumer_stall_is_a_decode_wait_span_of_its_own():
    from transferia_tpu.stats import trace

    def decode(g):
        time.sleep(0.03)
        return g

    trace.reset()
    trace.enable(True)
    try:
        with trace.span("part"):
            with RowGroupReadahead(range(3), decode, max_groups=2) as ra:
                assert [g for g, _ in ra] == [0, 1, 2]
        rec = trace.spans()
    finally:
        trace.enable(False)
        trace.reset()
    waits = [s for s in rec if s[0] == "decode_wait"]
    part = next(s for s in rec if s[0] == "part")
    assert waits and sum(s[4] for s in waits) >= 0.03
    # recorded once it ended: on the part's trace, but the part keeps
    # all of its own time
    assert all(s[10] == part[9] and s[5] == s[4] for s in waits)
    assert part[5] == part[4]


def test_worker_error_propagates_to_consumer():
    def decode(g):
        if g == 3:
            raise ValueError("chunk rot")
        return g

    delivered = []
    with pytest.raises(ValueError, match="chunk rot"):
        with RowGroupReadahead(range(8), decode, max_groups=2) as ra:
            for g, item in ra:
                delivered.append(g)
    # everything decoded before the failure still flowed, in order
    assert delivered == [0, 1, 2]


def test_consumer_error_cancels_outstanding_decode():
    calls = []
    lock = threading.Lock()

    def decode(g):
        with lock:
            calls.append(g)
        time.sleep(0.002)
        return g

    with pytest.raises(RuntimeError):
        with RowGroupReadahead(range(100), decode, max_groups=2) as ra:
            for g, item in ra:
                raise RuntimeError("sink died")
    n_at_exit = len(calls)
    # the cap bounds how far the worker ran ahead: the handed group, one
    # queued, one mid-decode — nowhere near the 100-group list
    assert n_at_exit <= 4
    time.sleep(0.05)  # close() joined the worker: no decodes after exit
    assert len(calls) == n_at_exit


def test_memory_cap_bounds_inflight_bytes():
    ra_mod.reset_stats()
    item = b"x" * 100

    def decode(g):
        return item

    with RowGroupReadahead(range(50), decode, max_groups=50,
                           max_bytes=250, nbytes=len) as ra:
        for g, it in ra:
            time.sleep(0.001)  # slow consumer: the cap must do the work
    stats = ra_mod.snapshot_stats()
    assert stats["prefetched_groups"] == 50
    # the worker checks the cap before decoding, so the ceiling is
    # cap + one item — never the 5000 bytes an unbounded queue would hold
    assert stats["max_inflight_bytes"] <= 350


def test_group_cap_counts_handed_and_queued():
    ra_mod.reset_stats()
    with RowGroupReadahead(range(30), lambda g: g, max_groups=2,
                           nbytes=lambda _i: 1) as ra:
        for g, it in ra:
            time.sleep(0.001)
    # in-flight (handed + queued) never exceeds the group cap
    assert ra_mod.snapshot_stats()["max_depth"] <= 2
    assert ra_mod.snapshot_stats()["prefetched_groups"] == 30


def test_inline_mode_is_lazy_and_serial():
    calls = []

    def decode(g):
        calls.append(g)
        return g

    ra = RowGroupReadahead(range(5), decode, max_groups=0)
    assert ra._thread is None and calls == []  # no worker, no eager work
    it = iter(ra)
    assert next(it) == (0, 0) and calls == [0]
    assert list(it) == [(g, g) for g in range(1, 5)]
    ra.close()


def test_gauges_return_to_zero():
    depth, bytes_g = _Gauge(), _Gauge()
    with RowGroupReadahead(range(20), lambda g: g, max_groups=3,
                           nbytes=lambda _i: 7,
                           gauges=(depth, bytes_g)) as ra:
        consumed = sum(1 for _ in ra)
    assert consumed == 20
    assert depth.v == 0 and bytes_g.v == 0
    assert bytes_g.max >= 7  # something was actually in flight


def test_gauges_drain_on_cancel():
    depth, bytes_g = _Gauge(), _Gauge()
    with pytest.raises(RuntimeError):
        with RowGroupReadahead(range(50), lambda g: g, max_groups=4,
                               nbytes=lambda _i: 10,
                               gauges=(depth, bytes_g)) as ra:
            next(iter(ra))
            raise RuntimeError("pusher error")
    assert depth.v == 0 and bytes_g.v == 0


# -- fs provider wiring ------------------------------------------------------

@pytest.fixture
def hits_parquet(tmp_path):
    n = 40_000
    t = pa.table({
        "URL": pa.array([f"https://e.test/{i % 997}" for i in range(n)]),
        "RegionID": pa.array((np.arange(n) % 500).astype(np.int32)),
        "Score": pa.array(np.linspace(0, 1, n).astype(np.float64)),
    })
    path = str(tmp_path / "hits.parquet")
    pq.write_table(t, path, row_group_size=8192)
    return path, n


def _load_rows(path, monkeypatch, *, native: bool, readahead: int,
               decode_threads: int = 0):
    from transferia_tpu.abstract.schema import TableID
    from transferia_tpu.abstract.table import TableDescription
    from transferia_tpu.providers.file import FileSourceParams, FileStorage

    monkeypatch.setenv("TRANSFERIA_TPU_NATIVE_PARQUET",
                       "1" if native else "0")
    st = FileStorage(FileSourceParams(
        path=path, format="parquet", table="hits", batch_rows=4096,
        readahead_groups=readahead, decode_threads=decode_threads))
    out = []
    st.load_table(TableDescription(id=TableID("fs", "hits")), out.append)
    rows = []
    for b in out:
        rows.extend(zip(b.column("URL").to_pylist(),
                        b.column("RegionID").to_pylist(),
                        b.column("Score").to_pylist()))
    return rows


@pytest.mark.parametrize("native", [True, False])
def test_readahead_paths_match_serial(hits_parquet, monkeypatch, native):
    """Prefetched decode (native and arrow) must produce the exact batch
    stream serial decode does — values AND order."""
    path, n = hits_parquet
    serial = _load_rows(path, monkeypatch, native=native, readahead=0,
                        decode_threads=1)
    pipelined = _load_rows(path, monkeypatch, native=native, readahead=3,
                           decode_threads=4)
    assert len(serial) == n
    assert pipelined == serial


def test_worker_error_reaches_upload_tables(tmp_path, monkeypatch):
    """A decode failure on the readahead worker must surface from
    SnapshotLoader.upload_tables as a part failure, not hang or get
    swallowed."""
    from transferia_tpu.abstract.errors import FatalError, TableUploadError
    from transferia_tpu.coordinator import MemoryCoordinator
    from transferia_tpu.models import Transfer
    from transferia_tpu.providers.file import FileSourceParams
    from transferia_tpu.providers.stdout import NullTargetParams
    from transferia_tpu.tasks import SnapshotLoader

    n = 20_000
    t = pa.table({"A": pa.array(np.arange(n, dtype=np.int64))})
    path = str(tmp_path / "t.parquet")
    pq.write_table(t, path, row_group_size=4096)

    # arrow decode path (deterministic without the native lib), forced
    # readahead so the failure happens on the prefetch worker thread
    monkeypatch.setenv("TRANSFERIA_TPU_NATIVE_PARQUET", "0")
    monkeypatch.setenv("TRANSFERIA_TPU_READAHEAD_GROUPS", "2")

    real = pq.ParquetFile.read_row_group

    def boom(self, g, *a, **kw):
        if g >= 2:
            raise FatalError("decode worker blew up")
        return real(self, g, *a, **kw)

    monkeypatch.setattr(pq.ParquetFile, "read_row_group", boom)
    transfer = Transfer(
        id="ra-err",
        src=FileSourceParams(path=path, format="parquet", table="t",
                             batch_rows=2048, rowgroups_per_part=8),
        dst=NullTargetParams(),
    )
    loader = SnapshotLoader(transfer, MemoryCoordinator(),
                            operation_id="ra-err-op")
    with pytest.raises(TableUploadError, match="decode worker blew up"):
        loader.upload_tables()
