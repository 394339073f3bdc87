"""Middleware behaviors: bufferer triggers/ordering, error latch, retrier."""

import threading
import time

import pytest

from transferia_tpu.abstract import ChangeItem, Kind, TableID
from transferia_tpu.abstract.change_item import (
    done_table_load,
    init_table_load,
)
from transferia_tpu.abstract.interfaces import Sinker
from transferia_tpu.abstract.schema import new_table_schema
from transferia_tpu.columnar import ColumnBatch
from transferia_tpu.middlewares import (
    Bufferer,
    BuffererConfig,
    ErrorTracker,
    NonRowSeparator,
    Retrier,
    Statistician,
    Synchronizer,
)
from transferia_tpu.stats.registry import SinkerStats


SCHEMA = new_table_schema([("id", "int64", True), ("v", "utf8")])
TID = TableID("s", "t")


def cb(n=4, start=0):
    return ColumnBatch.from_pydict(TID, SCHEMA, {
        "id": list(range(start, start + n)),
        "v": [f"v{i}" for i in range(start, start + n)],
    })


class Capture(Sinker):
    def __init__(self, fail_times=0):
        self.pushes = []
        self.fail_times = fail_times
        self.lock = threading.Lock()

    def push(self, batch):
        with self.lock:
            if self.fail_times > 0:
                self.fail_times -= 1
                raise ConnectionError("boom")
            self.pushes.append(batch)


class TestBufferer:
    def test_row_trigger_merges_batches(self):
        cap = Capture()
        buf = Bufferer(cap, BuffererConfig(trigger_rows=8,
                                           trigger_interval=0))
        futs = [buf.async_push(cb(4, 0)), buf.async_push(cb(4, 4))]
        for f in futs:
            f.result(timeout=5)
        assert len(cap.pushes) == 1  # merged into one big push
        assert cap.pushes[0].n_rows == 8
        assert cap.pushes[0].to_pydict()["id"] == list(range(8))
        buf.close()

    def test_control_flushes_and_orders(self):
        cap = Capture()
        buf = Bufferer(cap, BuffererConfig(trigger_rows=1000,
                                           trigger_interval=0))
        f1 = buf.async_push(cb(4))
        f2 = buf.async_push([done_table_load(TID, SCHEMA)])
        f1.result(timeout=5)
        f2.result(timeout=5)
        assert len(cap.pushes) == 2
        assert cap.pushes[0].n_rows == 4          # data flushed first
        assert cap.pushes[1][0].kind == Kind.DONE_TABLE_LOAD
        buf.close()

    def test_close_flushes(self):
        cap = Capture()
        buf = Bufferer(cap, BuffererConfig(trigger_rows=1000,
                                           trigger_interval=0))
        f = buf.async_push(cb(3))
        buf.close()
        f.result(timeout=5)
        assert len(cap.pushes) == 1 and cap.pushes[0].n_rows == 3

    def test_interval_trigger(self):
        cap = Capture()
        buf = Bufferer(cap, BuffererConfig(trigger_rows=10**9,
                                           trigger_interval=0.05))
        f = buf.async_push(cb(2))
        f.result(timeout=5)
        assert cap.pushes and cap.pushes[0].n_rows == 2
        buf.close()

    def test_flush_error_fails_futures(self):
        cap = Capture(fail_times=1)
        buf = Bufferer(cap, BuffererConfig(trigger_rows=4,
                                           trigger_interval=0))
        f = buf.async_push(cb(4))
        with pytest.raises(ConnectionError):
            f.result(timeout=5)
        buf.close()


def test_error_tracker_latches():
    cap = Capture(fail_times=1)
    et = ErrorTracker(Synchronizer(cap))
    with pytest.raises(ConnectionError):
        et.async_push(cb()).result()
    # healthy inner now, but tracker stays failed
    with pytest.raises(ConnectionError):
        et.async_push(cb()).result()
    assert isinstance(et.failure, ConnectionError)


def test_retrier_retries_then_succeeds():
    cap = Capture(fail_times=2)
    r = Retrier(cap, attempts=3, base_delay=0.01)
    r.push(cb())
    assert len(cap.pushes) == 1


def test_retrier_gives_up():
    cap = Capture(fail_times=5)
    r = Retrier(cap, attempts=3, base_delay=0.01)
    with pytest.raises(ConnectionError):
        r.push(cb())


def test_nonrow_separator():
    cap = Capture()
    sep = NonRowSeparator(cap)
    items = [
        init_table_load(TID, SCHEMA),
        *cb(2).to_rows(),
        done_table_load(TID, SCHEMA),
    ]
    sep.push(items)
    assert len(cap.pushes) == 3
    assert cap.pushes[0][0].kind == Kind.INIT_TABLE_LOAD
    assert len(cap.pushes[1]) == 2
    assert cap.pushes[2][0].kind == Kind.DONE_TABLE_LOAD


def test_statistician_counts():
    cap = Capture()
    stats = SinkerStats()
    s = Statistician(cap, stats)
    s.push(cb(5))
    assert stats.m.value("sinker_pushed_rows") == 5.0
    assert stats.table_rows[str(TID)] == 5


def _flush_with_trigger(trigger):
    """Make the bufferer flush for one reason; returns the flush spans."""
    from transferia_tpu.stats import trace

    cap = Capture()
    cfg = {
        "rows": BuffererConfig(trigger_rows=4, trigger_interval=0),
        "bytes": BuffererConfig(trigger_rows=10**9, trigger_bytes=1,
                                trigger_interval=0),
        "interval": BuffererConfig(trigger_rows=10**9,
                                   trigger_interval=0.05),
    }.get(trigger, BuffererConfig(trigger_rows=10**9, trigger_interval=0))
    trace.reset()
    trace.enable(True)
    try:
        buf = Bufferer(cap, cfg)
        f = buf.async_push(cb(4))
        if trigger == "control":
            buf.async_push([done_table_load(TID, SCHEMA)]).result(timeout=5)
        if trigger == "close":
            buf.close()
        f.result(timeout=5)
        buf.close()
        return [s for s in trace.spans() if s[0] == "bufferer_flush"]
    finally:
        trace.enable(False)
        trace.reset()


@pytest.mark.parametrize(
    "trigger", ["rows", "bytes", "interval", "control", "close"])
def test_bufferer_flush_span_says_what_triggered_it(trigger):
    flushes = _flush_with_trigger(trigger)
    assert len(flushes) == 1
    args = flushes[0][7]
    assert args["trigger"] == trigger
    assert args["rows"] == 4 and args["units"] == 1 and args["bytes"] > 0
