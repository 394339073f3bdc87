"""Causal trace propagation (stats/trace.py PR 10): span ids + parent
links, cross-thread adoption, Perfetto flow events, the wire format,
and the cross-boundary attribution contracts — readahead workers,
async sink middleware, fleet ticket lifecycle under a kill, the Flight
gRPC metadata hop, and the shm framing-metadata hop.

Recorded tuple layout (trace.spans()):
  (name, tid, tname, t0, dur, self, depth, args,
   trace_id, span_id, parent_id, self_cpu)
self_cpu: of `self`, the seconds on the thread's CPU clock; None for
instants, complete() records and platforms without the clock.
"""

from __future__ import annotations

import json
import threading
import time

import pytest

from transferia_tpu.stats import trace
from transferia_tpu.stats.ledger import LEDGER


def setup_function(_fn):
    trace.enable(False)
    trace.reset()
    LEDGER.reset()


def teardown_function(_fn):
    trace.enable(False)
    trace.reset()
    LEDGER.reset()


def _args(rec) -> dict:
    return rec[7] or {}


def _by_name(name):
    return [s for s in trace.spans() if s[0] == name]


# -- ids and links -----------------------------------------------------------

def test_nested_spans_share_trace_and_link_parent():
    trace.enable(True)
    with trace.span("outer"):
        with trace.span("inner"):
            pass
    outer = _by_name("outer")[0]
    inner = _by_name("inner")[0]
    o_trace, o_span, o_parent = outer[8:11]
    i_trace, i_span, i_parent = inner[8:11]
    assert o_parent == 0, "root span has no parent"
    assert o_trace == o_span, "a root starts its own trace"
    assert i_trace == o_trace, "child stays on the parent's trace"
    assert i_parent == o_span
    assert i_span != o_span


def test_sibling_roots_get_distinct_traces():
    trace.enable(True)
    with trace.span("a"):
        pass
    with trace.span("b"):
        pass
    a, b = _by_name("a")[0], _by_name("b")[0]
    assert a[8] != b[8]


def test_instant_lands_on_active_span():
    trace.enable(True)
    with trace.span("host") as sp:
        trace.instant("fired", detail=1)
    host = _by_name("host")[0]
    inst = _by_name("fired")[0]
    assert inst[6] == -1  # instant marker depth
    assert inst[8] == host[8]  # same trace
    assert inst[10] == host[9]  # parent = the span it fired on
    # explicit ctx override
    trace.instant("routed", ctx=trace.SpanContext(42, 7))
    routed = _by_name("routed")[0]
    assert routed[8] == 42 and routed[10] == 7


def test_complete_records_retroactive_span_with_parent():
    trace.enable(True)
    with trace.span("root") as sp:
        ctx = sp.context()
    t0 = time.perf_counter() - 1.0
    trace.complete("queue_wait", t0=t0, dur=0.5, parent=ctx, attempt=1)
    root = _by_name("root")[0]
    qw = _by_name("queue_wait")[0]
    assert qw[4] == pytest.approx(0.5)
    assert qw[8] == root[8]
    assert qw[10] == root[9]
    assert _args(qw)["attempt"] == 1


# -- cross-thread adoption ---------------------------------------------------

def test_adopted_parents_worker_spans_and_exports_flow():
    trace.enable(True)
    with trace.span("submit") as sp:
        ctx = trace.current_context()
        assert ctx == sp.context()

    def worker():
        with trace.adopted(ctx):
            with trace.span("decode"):
                pass
        # adoption is scoped: nothing leaks onto the worker thread
        assert trace.current_context() is None

    t = threading.Thread(target=worker, name="ra-worker")
    t.start()
    t.join()
    submit = _by_name("submit")[0]
    decode = _by_name("decode")[0]
    assert decode[8] == submit[8]
    assert decode[10] == submit[9]
    assert decode[1] != submit[1], "spans live on different threads"
    # the export draws the cross-thread link as an s/f flow pair
    doc = trace.export_chrome_trace()
    flows = [e for e in doc["traceEvents"] if e.get("cat") == "flow"]
    starts = [e for e in flows if e["ph"] == "s"]
    finishes = [e for e in flows if e["ph"] == "f"]
    assert len(starts) == 1 and len(finishes) == 1
    assert starts[0]["id"] == finishes[0]["id"] == decode[9]
    assert starts[0]["tid"] == submit[1]
    assert finishes[0]["tid"] == decode[1]
    # same-thread nesting draws NO arrow
    ids = {e["id"] for e in flows}
    assert submit[9] not in ids


def test_adopted_none_is_noop():
    trace.enable(True)
    with trace.adopted(None):
        with trace.span("root"):
            pass
    root = _by_name("root")[0]
    assert root[10] == 0


# -- wire format -------------------------------------------------------------

def test_wire_format_round_trip_and_junk_tolerance():
    ctx = trace.SpanContext(123456789, 987654321)
    wire = trace.wire_format(ctx)
    assert trace.parse_wire(wire) == ctx
    assert trace.parse_wire(wire.encode()) == ctx
    assert trace.wire_format(None) == ""
    for junk in ("", None, "abc", "12:", ":34", "x:y", b"\xff\xfe"):
        assert trace.parse_wire(junk) is None


# -- capture helper-thread deadline ------------------------------------------

def test_capture_seconds_deadline_raises_timeout():
    # a stuck capture (here: the lock held by a concurrent capture that
    # never finishes) must bound the caller's wait, not pin it forever
    acquired = trace._capture_lock.acquire()
    assert acquired
    try:
        with pytest.raises(TimeoutError):
            trace.capture_seconds(0.05, deadline_grace=0.2)
    finally:
        trace._capture_lock.release()


def test_iter_chrome_trace_chunks_streams_equivalent_json():
    trace.enable(True)
    with trace.span("part", table="ns.t"):
        trace.instant("tick")
    doc = trace.export_chrome_trace()
    streamed = json.loads("".join(trace.iter_chrome_trace_chunks(doc)))
    assert streamed["traceEvents"] == json.loads(
        json.dumps(doc["traceEvents"]))
    assert streamed["displayTimeUnit"] == doc["displayTimeUnit"]
    assert "otherData" in streamed


# -- readahead worker hop ----------------------------------------------------

def test_readahead_worker_spans_parent_to_submitting_span():
    from transferia_tpu.providers.readahead import RowGroupReadahead

    trace.enable(True)
    with trace.span("part_submit"):
        with LEDGER.context(transfer_id="t-ra", tenant="acme"):
            with RowGroupReadahead(list(range(4)), lambda g: g * 10,
                                   max_groups=2) as ra:
                got = [item for _g, item in ra]
    assert got == [0, 10, 20, 30]
    submit = _by_name("part_submit")[0]
    # consumer-side stall handoffs may decode some groups inline; every
    # group the WORKER decoded must still parent across the thread hop
    decodes = _by_name("decode_readahead")
    assert decodes, "no worker-side decode spans recorded"
    for d in decodes:
        assert d[8] == submit[8], "decode span must ride the trace"
        assert d[10] == submit[9], "decode parents to the submitter"
        assert d[1] != submit[1], "decode ran on the worker thread"


# -- async sink middleware hop -----------------------------------------------

def test_asynchronizer_push_parents_to_submitting_span():
    from transferia_tpu.middlewares.asynchronizer import Asynchronizer

    pushed = []

    class _Sink:
        def push(self, batch):
            pushed.append(batch)

        def close(self):
            pass

    trace.enable(True)
    sink = Asynchronizer(_Sink())
    try:
        with trace.span("batch_submit"):
            with LEDGER.context(transfer_id="t-async", tenant="acme"):
                sink.async_push([1, 2, 3]).result(timeout=10)
    finally:
        sink.close()
    assert pushed == [[1, 2, 3]]
    submit = _by_name("batch_submit")[0]
    push = _by_name("sink_push")[0]
    assert push[8] == submit[8]
    assert push[10] == submit[9]
    assert push[1] != submit[1]


# -- fleet ticket lifecycle --------------------------------------------------

def test_fleet_ticket_kill_rebalance_stays_one_trace():
    from transferia_tpu.chaos import failpoints
    from transferia_tpu.fleet.scheduler import (
        FleetScheduler,
        FleetTransfer,
        QosClass,
    )
    from transferia_tpu.stats.registry import Metrics

    trace.enable(True)
    with failpoints.active(
            "fleet.dispatch=after:2,times:1,raise:WorkerKilledError",
            seed=1):
        sched = FleetScheduler(workers=2, max_inflight_per_worker=1,
                               metrics=Metrics(), name="trace-test")
        for i in range(8):
            sched.submit(FleetTransfer(
                transfer_id=f"tr{i:03d}", tenant=f"tn{i % 2}",
                qos=QosClass.BATCH, run=lambda: None))
        sched.start()
        try:
            assert sched.drain(timeout=30.0)
        finally:
            sched.shutdown()
    assert len(sched.rebalance_log) == 1
    victim = sched.rebalance_log[0][0]

    related = [s for s in trace.spans()
               if _args(s).get("transfer_id") == victim]
    names = {s[0] for s in related}
    # the full lifecycle is visible...
    assert {"fleet_admit", "fleet_queue_wait", "fleet_dispatch",
            "fleet_run", "fleet_worker_kill",
            "fleet_rebalance"} <= names
    # ...and rides ONE trace id across the kill + re-dispatch
    adm = [s for s in related if s[0] == "fleet_admit"][0]
    assert {s[8] for s in related} == {adm[8]}, related
    # the kill landed at the dispatch decision, so the surviving run
    # carries the post-rebalance attempt count — on the same trace
    runs = [s for s in related if s[0] == "fleet_run"]
    assert runs, "the rebalanced ticket still ran"
    assert max(_args(r)["attempt"] for r in runs) == 2
    # the rebalance billed a retry to the ticket's ledger entry
    assert LEDGER.snapshot()["transfers"][victim]["retries"] == 1


def test_fleet_run_scopes_ledger_to_ticket():
    from transferia_tpu.fleet.scheduler import (
        FleetScheduler,
        FleetTransfer,
        QosClass,
    )
    from transferia_tpu.stats.registry import Metrics

    def burn():
        LEDGER.add(rows_out=11)

    sched = FleetScheduler(workers=1, max_inflight_per_worker=1,
                           metrics=Metrics(), name="ledger-test")
    sched.submit(FleetTransfer(transfer_id="tL", tenant="acme",
                               qos=QosClass.BATCH, run=burn))
    sched.start()
    try:
        assert sched.drain(timeout=30.0)
    finally:
        sched.shutdown()
    snap = LEDGER.snapshot()
    entry = snap["transfers"]["tL"]
    assert entry["rows_out"] == 11
    assert entry["tenant"] == "acme"
    assert entry["queue_wait_seconds"] >= 0.0


# -- flight wire hop ---------------------------------------------------------

@pytest.mark.requires_pyarrow
def test_flight_do_put_links_server_span_to_client_trace():
    pytest.importorskip("pyarrow.flight")
    from transferia_tpu.abstract.schema import (
        CanonicalType,
        ColSchema,
        TableID,
        TableSchema,
    )
    from transferia_tpu.columnar.batch import ColumnBatch
    from transferia_tpu.interchange.flight import (
        FlightShardClient,
        make_server,
    )

    schema = TableSchema([ColSchema("id", CanonicalType.INT64,
                                    primary_key=True)])
    batch = ColumnBatch.from_pydict(TableID("ns", "t"), schema,
                                    {"id": [1, 2, 3]})
    server = make_server()
    client = FlightShardClient(server.location)
    trace.enable(True)
    try:
        with trace.span("client_root") as sp:
            client.put_part("ns.t/0", [batch])
            client.get_part("ns.t/0")
    finally:
        client.close()
        server.close()
    root = _by_name("client_root")[0]
    put_client = _by_name("flight_put")[0]
    put_server = _by_name("flight_do_put")[0]
    get_server = _by_name("flight_do_get")[0]
    assert put_client[8] == root[8]
    # the server-side spans joined the CLIENT's trace via the gRPC
    # metadata header, across the (loopback) wire
    assert put_server[8] == root[8], "DoPut server span left the trace"
    assert get_server[8] == root[8], "DoGet server span left the trace"
    assert put_server[10] == put_client[9], \
        "server span parents to the client-side put span"


# -- shm framing-metadata hop ------------------------------------------------

@pytest.mark.requires_pyarrow
def test_shm_reader_span_links_to_writer_context():
    from transferia_tpu.abstract.schema import (
        CanonicalType,
        ColSchema,
        TableID,
        TableSchema,
    )
    from transferia_tpu.columnar.batch import ColumnBatch
    from transferia_tpu.interchange import shm

    schema = TableSchema([ColSchema("id", CanonicalType.INT64,
                                    primary_key=True)])
    batch = ColumnBatch.from_pydict(TableID("ns", "t"), schema,
                                    {"id": [1, 2, 3, 4]})
    trace.enable(True)
    with trace.span("writer") as sp:
        handle = shm.write_segment([batch])
    got = {}

    def reader():
        att = shm.attach(handle)
        try:
            got["batches"] = att.batches()
        finally:
            got["batches"] = None  # release views before close
            att.close()

    try:
        t = threading.Thread(target=reader, name="shm-reader")
        t.start()
        t.join()
    finally:
        shm.unlink_segment(handle)
    writer = _by_name("writer")[0]
    smap = _by_name("shm_map")[0]
    assert smap[8] == writer[8], \
        "shm_map must join the writer's trace via framing metadata"
    assert smap[10] == writer[9]
    assert smap[1] != writer[1]
