"""What a snapshot's part thread waits on, as spans (tasks/snapshot.py).

Under `part`: `part_open`, `push_backpressure` (inside `batch`),
`part_drain`, `part_close` (phase `done`), `part_commit` (staged only);
after `part` has ended, on the same worker thread: `part_close` (phase
`close`) and `part_report`; between two parts: `part_claim`.  Their self
time is what `part` and `batch` self time was made of; what is left
there is the source iterator's own work.

Recorded tuple layout (trace.spans()):
  (name, tid, tname, t0, dur, self, depth, args,
   trace_id, span_id, parent_id, self_cpu)
"""

from __future__ import annotations

import concurrent.futures
import threading
import time

import pytest

from transferia_tpu.abstract import TableID
from transferia_tpu.abstract.interfaces import AsyncSink
from transferia_tpu.coordinator import MemoryCoordinator
from transferia_tpu.models import Transfer
from transferia_tpu.providers import memory
from transferia_tpu.providers.memory import (
    MemorySourceParams,
    MemoryTargetParams,
    get_store,
    seed_source,
)
from transferia_tpu.providers.sample import make_batch
from transferia_tpu.stats import trace
from transferia_tpu.tasks import SnapshotLoader
from transferia_tpu.tasks import snapshot as snapshot_mod

WAITS_UNDER_PART = ("part_open", "push_backpressure", "part_drain",
                    "part_close", "part_commit")
WAITS_OF_THE_WORKER = ("part_claim", "part_report")
TID = TableID("sample", "users")


def setup_function(_fn):
    trace.enable(False)
    trace.reset()


def teardown_function(_fn):
    trace.enable(False)
    trace.reset()


def _transfer(name, batches=8):
    seed_source(name, [make_batch("users", TID, lo, 250, seed=5)
                       for lo in range(0, 250 * batches, 250)])
    get_store(name).clear()
    return Transfer(id=name, src=MemorySourceParams(source_id=name),
                    dst=MemoryTargetParams(sink_id=name))


def _snapshot(transfer):
    trace.enable(True)
    trace.reset()
    try:
        SnapshotLoader(transfer, MemoryCoordinator()).upload_tables()
    finally:
        trace.enable(False)
    return [s for s in trace.spans() if s[6] >= 0]


def _named(spans, name, **args):
    return [s for s in spans if s[0] == name
            and all((s[7] or {}).get(k) == v for k, v in args.items())]


def _ancestors(span, by_id):
    names = []
    while span is not None and span[10]:
        span = by_id.get(span[10])
        if span is not None:
            names.append(span[0])
    return names


@pytest.mark.parametrize("staged", [True, False],
                         ids=["staged", "unstaged"])
def test_a_snapshot_records_every_wait_under_its_part_or_operation(
        staged, monkeypatch):
    if not staged:
        monkeypatch.setenv(snapshot_mod.ENV_STAGED_COMMIT, "off")
    name = f"wait-spans-{'staged' if staged else 'plain'}"
    spans = _snapshot(_transfer(name))
    assert get_store(name).row_count() == 2000
    by_id = {s[9]: s for s in spans}
    (part,) = _named(spans, "part")
    (op,) = _named(spans, "snapshot_op")
    # what the part thread waits on, inside the part
    assert len(_named(spans, "part_open")) == 1
    assert len(_named(spans, "part_drain")) == 1
    assert len(_named(spans, "part_close", phase="done")) == 1
    assert len(_named(spans, "part_commit")) == staged
    pushes = _named(spans, "push_backpressure")
    assert [s[7] for s in pushes] == [
        {"inflight": i, "cause": "push"} for i in range(8)]
    for s in pushes:
        assert by_id[s[10]][0] == "batch"
    for name_ in WAITS_UNDER_PART:
        for s in _named(spans, name_):
            if (s[7] or {}).get("phase") == "close":
                continue
            assert "part" in _ancestors(s, by_id), name_
            assert s[1] == part[1]
    # and after it, on the same thread, under the operation
    (closing,) = _named(spans, "part_close", phase="close")
    (report,) = _named(spans, "part_report")
    for s in (closing, report):
        assert s[1] == part[1] and s[10] == op[9]
        assert s[3] >= part[3] + part[4]
    # every worker thread asks for a part
    claims = _named(spans, "part_claim")
    assert {s[2] for s in claims} == {f"upload-{i}" for i in range(4)}
    assert all(s[10] == op[9] for s in claims)
    # the sink's own spans keep their self time under the waits
    assert any(by_id[s[10]][0] in ("push_backpressure", "sink")
               for s in _named(spans, "sink_stage")) == staged
    for s in spans:
        assert len(s) == 12 and s[4] >= 0


class _SlowAsyncSink(AsyncSink):
    """Every push takes 20 ms on a thread of the sink's own, as an
    asynchronizer's does: the caller is handed a future."""

    def __init__(self):
        self._pool = concurrent.futures.ThreadPoolExecutor(max_workers=1)
        self.pushed = 0

    def _land(self, batch):
        time.sleep(0.02)
        self.pushed += 1

    def async_push(self, batch):
        return self._pool.submit(self._land, batch)

    def close(self):
        self._pool.shutdown(wait=True)


def test_with_a_slow_sink_the_waits_hold_what_part_self_time_held(
        monkeypatch):
    sinks = []

    def make(*_a, **_kw):
        sinks.append(_SlowAsyncSink())
        return sinks[-1]

    monkeypatch.setattr(snapshot_mod, "make_async_sink", make)
    spans = _snapshot(_transfer("wait-spans-slow"))
    assert max(s.pushed for s in sinks) == 10   # init, 8 batches, done

    def self_s(*names):
        return sum(s[5] for s in spans if s[0] in names)

    waits = self_s(*WAITS_UNDER_PART, *WAITS_OF_THE_WORKER)
    assert waits >= 0.15                # ten pushes of 20 ms, in turn
    assert self_s("part", "batch") < 0.25 * waits
    # blocked, not working: the CPU clock stood nearly still
    cpu = sum(s[11] for s in spans if s[0] in WAITS_UNDER_PART)
    assert cpu < 0.25 * waits
    (drain,) = _named(spans, "part_drain")
    assert drain[5] >= 0.1


def test_a_source_that_fails_mid_part_still_closes_every_span(
        monkeypatch):
    monkeypatch.setattr(snapshot_mod, "PART_RETRIES", 1)
    transfer = _transfer("wait-spans-fail")
    load = memory.MemoryStorage.load_table
    threads = []

    def failing(self, table, pusher):
        threads.append(threading.current_thread())
        seen = 0

        def counting(batch):
            nonlocal seen
            seen += 1
            if seen == 4:
                raise ValueError("source went away")
            pusher(batch)

        load(self, table, counting)

    monkeypatch.setattr(memory.MemoryStorage, "load_table", failing)
    open_after = {}

    real_loop = snapshot_mod.SnapshotLoader._upload_part_with_retry

    def watched(self, storage, part, schemas):
        try:
            real_loop(self, storage, part, schemas)
        finally:
            open_after[threading.current_thread().name] = trace.current()

    monkeypatch.setattr(snapshot_mod.SnapshotLoader,
                        "_upload_part_with_retry", watched)
    trace.enable(True)
    trace.reset()
    try:
        with pytest.raises(Exception, match="source went away"):
            SnapshotLoader(transfer, MemoryCoordinator()).upload_tables()
    finally:
        trace.enable(False)
    spans = [s for s in trace.spans() if s[6] >= 0]
    assert threads and all(s[4] >= 0 and s[5] >= 0 for s in spans)
    # the part's span and its waits ended with the error
    assert len(_named(spans, "part")) == 1
    assert len(_named(spans, "push_backpressure")) == 3
    assert len(_named(spans, "part_close", phase="close")) == 1
    assert _named(spans, "part_drain") == []
    assert _named(spans, "part_report") == []
    # nothing is left open on the worker thread
    assert open_after == {threads[0].name: None}
