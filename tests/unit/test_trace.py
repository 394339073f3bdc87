"""Pipeline tracing (stats/trace.py): span recorder, Chrome trace
export, device telemetry, and the /debug/trace endpoint.

The contracts that matter:
- disabled tracing is free: span() returns a shared no-op singleton
  (no allocation, nothing recorded) — the hot path pays one bool
  check per site;
- span nesting works across threads (per-thread stacks, self-time
  attribution);
- the export is valid Chrome trace-event JSON (Perfetto-loadable);
- the fused transform path wires nonzero device launch + H2D/D2H byte
  counters on the CPU backend (same code path as TPU);
- /debug/trace?seconds=N round-trips over the health port.
"""

import json
import threading
import time
import urllib.request

import numpy as np
import pytest

from transferia_tpu.stats import trace


def setup_function(_fn):
    trace.enable(False)
    trace.reset()


def teardown_function(_fn):
    trace.enable(False)
    trace.reset()


# -- disabled path -----------------------------------------------------------

def test_disabled_span_is_shared_noop_singleton():
    assert not trace.enabled()
    s1 = trace.span("a")
    s2 = trace.span("b")
    assert s1 is s2, "disabled span() must return the shared singleton"
    assert not s1  # falsy: sites guard arg-building with `if sp:`
    with s1:
        s1.add(bytes=123)  # must be a silent no-op
    assert trace.spans() == []


def test_disabled_path_records_nothing_and_allocates_nothing():
    import tracemalloc

    # warm any lazy state before measuring
    with trace.span("warm"):
        pass
    tracemalloc.start()
    before = tracemalloc.take_snapshot()
    for _ in range(1000):
        with trace.span("hot"):
            pass
    after = tracemalloc.take_snapshot()
    tracemalloc.stop()
    growth = sum(s.size_diff for s in after.compare_to(before, "lineno")
                 if s.size_diff > 0)
    # tracemalloc bookkeeping itself shows up; the loop must not leave
    # per-iteration allocations behind (1000 spans would be >50KB)
    assert growth < 20_000, f"disabled spans allocated {growth}B"
    assert trace.spans() == []


# -- enabled recording -------------------------------------------------------

def test_span_nesting_and_self_time():
    trace.enable(True)
    with trace.span("outer"):
        assert trace.current() == "outer"
        time.sleep(0.02)
        with trace.span("inner"):
            assert trace.current() == "inner"
            time.sleep(0.02)
    assert trace.current() is None
    rec = {s[0]: s for s in trace.spans()}
    assert set(rec) == {"outer", "inner"}
    # depth: inner nested under outer
    assert rec["outer"][6] == 0
    assert rec["inner"][6] == 1
    # self time: outer's self excludes inner's duration
    outer_dur, outer_self = rec["outer"][4], rec["outer"][5]
    inner_dur = rec["inner"][4]
    assert outer_dur >= inner_dur
    assert outer_self <= outer_dur - inner_dur + 0.005


def test_span_stacks_are_per_thread():
    trace.enable(True)
    seen = {}
    barrier = threading.Barrier(2)

    def worker(name):
        with trace.span(name):
            barrier.wait()  # both threads inside their span at once
            seen[name] = trace.current()
            barrier.wait()

    threads = [threading.Thread(target=worker, args=(f"t{i}",))
               for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    # each thread saw only ITS innermost span
    assert seen == {"t0": "t0", "t1": "t1"}
    rec = trace.spans()
    assert len(rec) == 2
    tids = {s[1] for s in rec}
    assert len(tids) == 2, "spans must carry their own thread ids"
    # both are roots on their own stacks, never nested cross-thread
    assert all(s[6] == 0 for s in rec)


def test_ring_buffer_is_bounded():
    trace.enable(True, capacity=64)
    try:
        for i in range(200):
            with trace.span("s"):
                pass
        assert len(trace.spans()) == 64
    finally:
        trace.enable(False, capacity=trace.DEFAULT_CAPACITY)


# -- chrome export -----------------------------------------------------------

def test_chrome_trace_schema():
    trace.enable(True)
    with trace.span("part", table="ns.t", part="0"):
        with trace.span("transform", rows=10):
            pass
    trace.instant("xla_compile", seconds=0.5)
    doc = trace.export_chrome_trace()
    # round-trips through json (the endpoint/file contract)
    doc = json.loads(json.dumps(doc))
    events = doc["traceEvents"]
    assert doc["displayTimeUnit"] == "ms"
    phases = {e["ph"] for e in events}
    assert phases <= {"X", "M", "i"}
    complete = [e for e in events if e["ph"] == "X"]
    assert {e["name"] for e in complete} == {"part", "transform"}
    for e in complete:
        assert isinstance(e["ts"], (int, float))
        assert isinstance(e["dur"], (int, float))
        assert e["pid"] == 1 and isinstance(e["tid"], int)
    by_name = {e["name"]: e for e in complete}
    # child nested within parent on the same tid
    p, c = by_name["part"], by_name["transform"]
    assert c["tid"] == p["tid"]
    assert p["ts"] <= c["ts"]
    assert c["ts"] + c["dur"] <= p["ts"] + p["dur"] + 1
    assert p["args"]["table"] == "ns.t"
    # thread-name metadata present for the recording thread
    assert any(e["ph"] == "M" and e["name"] == "thread_name"
               and e["tid"] == p["tid"] for e in events)
    # instants render as "i"
    assert any(e["ph"] == "i" and e["name"] == "xla_compile"
               for e in events)


def test_stage_summary_percentiles_and_bytes():
    trace.enable(True)
    for i in range(10):
        with trace.span("sink", bytes=100):
            time.sleep(0.002)
    s = trace.stage_summary()
    st = s["stages"]["sink"]
    assert st["calls"] == 10
    assert st["bytes"] == 1000
    assert 0 < st["p50_ms"] <= st["p99_ms"]
    assert s["overlap_factor"] > 0


# -- device telemetry --------------------------------------------------------

def test_device_telemetry_wired_in_fused_path():
    from transferia_tpu.abstract import TableID
    from transferia_tpu.abstract.schema import new_table_schema
    from transferia_tpu.columnar import ColumnBatch
    from transferia_tpu.transform import build_chain
    from transferia_tpu.transform.fused import (
        set_device_fusion,
        set_placement,
    )

    schema = new_table_schema([
        ("id", "int32", True), ("url", "utf8"), ("region", "int32"),
    ])
    tid = TableID("web", "hits")
    n = 123
    batch = ColumnBatch.from_pydict(tid, schema, {
        "id": list(range(n)),
        "url": [f"https://e{i}.com" for i in range(n)],
        "region": [i % 500 for i in range(n)],
    })
    cfg = {"transformers": [
        {"mask_field": {"columns": ["url"], "salt": "s"}},
        {"filter_rows": {"filter": "region < 400"}},
    ]}
    trace.TELEMETRY.reset()
    trace.enable(True)
    set_device_fusion(True)
    set_placement("device")  # force the XLA strategy on the CPU backend
    try:
        out = build_chain(cfg).apply(batch)
    finally:
        set_device_fusion(None)
        set_placement(None)
        trace.enable(False)
    assert out.n_rows == sum(1 for i in range(n) if i % 500 < 400)
    tel = trace.TELEMETRY.snapshot()
    assert tel["device_launches"] > 0
    assert tel["h2d_bytes"] > 0 and tel["h2d_transfers"] > 0
    assert tel["d2h_bytes"] > 0 and tel["d2h_transfers"] > 0
    assert tel["device_wait_seconds"] > 0
    # the timeline carries the matching spans with byte args (chain
    # applied directly here, so no middleware "transform" span)
    names = {s[0] for s in trace.spans()}
    assert {"pack", "device_dispatch", "device_wait",
            "host_post"} <= names
    disp = [s for s in trace.spans() if s[0] == "device_dispatch"]
    assert any((s[7] or {}).get("bytes", 0) > 0 for s in disp)
    waits = [s for s in trace.spans() if s[0] == "device_wait"]
    assert any((s[7] or {}).get("bytes", 0) > 0 for s in waits)


def test_snapshot_operation_counts_the_process_cpu_and_faults():
    from transferia_tpu.abstract import TableID
    from transferia_tpu.coordinator import MemoryCoordinator
    from transferia_tpu.models import Transfer
    from transferia_tpu.providers.memory import (
        MemorySourceParams,
        MemoryTargetParams,
        get_store,
        seed_source,
    )
    from transferia_tpu.providers.sample import make_batch
    from transferia_tpu.tasks import SnapshotLoader

    names = ("proc_cpu_ms", "proc_cpu_sys_ms")
    tid = TableID("sample", "users")
    seed_source("proc_usage", [make_batch("users", tid, lo, 500, seed=5)
                               for lo in range(0, 4000, 500)])
    t = Transfer(id="proc_usage",
                 src=MemorySourceParams(source_id="proc_usage"),
                 dst=MemoryTargetParams(sink_id="proc_usage"))
    trace.TELEMETRY.reset()
    assert [trace.TELEMETRY.snapshot()[k] for k in names] == [0, 0]
    SnapshotLoader(t, MemoryCoordinator()).upload_tables()
    assert get_store("proc_usage").row_count() == 4000
    tel = trace.TELEMETRY.snapshot()
    assert tel["proc_cpu_ms"] > 0
    assert 0 <= tel["proc_cpu_sys_ms"] <= tel["proc_cpu_ms"]
    assert "proc_minor_faults" not in tel
    # a second operation adds to the first; reset clears both
    SnapshotLoader(t, MemoryCoordinator()).upload_tables()
    assert trace.TELEMETRY.snapshot()["proc_cpu_ms"] > tel["proc_cpu_ms"]
    trace.TELEMETRY.reset()
    assert [trace.TELEMETRY.snapshot()[k] for k in names] == [0, 0]


def test_telemetry_folds_into_metrics_facade():
    from transferia_tpu.stats.registry import Metrics

    trace.TELEMETRY.reset()
    trace.TELEMETRY.record_h2d(1000)
    trace.TELEMETRY.record_d2h(500)
    trace.TELEMETRY.record_launch()
    trace.TELEMETRY.record_compile(0.25)
    m = Metrics()
    trace.TELEMETRY.fold_into(m)
    assert m.value("device_h2d_bytes") == 1000
    assert m.value("device_d2h_bytes") == 500
    assert m.value("device_launches") == 1
    assert m.value("device_xla_compiles") == 1
    # folds carry deltas: a second fold with no new activity adds nothing
    trace.TELEMETRY.fold_into(m)
    assert m.value("device_h2d_bytes") == 1000
    trace.TELEMETRY.record_h2d(24)
    trace.TELEMETRY.fold_into(m)
    assert m.value("device_h2d_bytes") == 1024


def test_concurrent_folds_one_metrics_never_duplicate_timeseries():
    """One Metrics is shared by a loader's parallel part-upload threads;
    each fold constructs a DeviceStats bundle, so the facade's
    get-or-create must be atomic — a lost race re-registers a collector
    and prometheus raises "Duplicated timeseries", failing the part."""
    import sys

    from transferia_tpu.stats.registry import Metrics

    trace.TELEMETRY.reset()
    trace.TELEMETRY.record_h2d(64)
    prev_switch = sys.getswitchinterval()
    # the unlocked facade loses this race ~96% of runs at this switch
    # interval (vs ~never at the default 5ms — creation is microseconds)
    sys.setswitchinterval(1e-6)
    try:
        for _ in range(20):
            m = Metrics()
            barrier = threading.Barrier(4)
            errors = []

            def fold():
                try:
                    barrier.wait(timeout=5)
                    trace.TELEMETRY.fold_into(m)
                except Exception as exc:  # noqa: BLE001 - collected
                    errors.append(exc)

            threads = [threading.Thread(target=fold) for _ in range(4)]
            for th in threads:
                th.start()
            for th in threads:
                th.join(timeout=10)
            assert not errors, errors
            assert m.value("device_h2d_bytes") == 64
    finally:
        sys.setswitchinterval(prev_switch)


# -- endpoint ----------------------------------------------------------------

def test_debug_trace_endpoint_round_trip():
    from transferia_tpu.cli.main import _start_health_server

    port = _start_health_server(0)
    stop = threading.Event()

    def busy():
        while not stop.is_set():
            with trace.span("transform", rows=1):
                np.dot(np.ones((64, 64)), np.ones((64, 64)))

    th = threading.Thread(target=busy, daemon=True)
    th.start()
    try:
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/debug/trace?seconds=0.4",
            timeout=10).read()
    finally:
        stop.set()
        th.join(timeout=5)
    doc = json.loads(body)
    names = {e["name"] for e in doc["traceEvents"] if e["ph"] == "X"}
    assert "transform" in names
    assert "device_telemetry" in doc["otherData"]
    # the endpoint restores the previous (disabled) state
    assert not trace.enabled()


def test_capture_seconds_preserves_a_live_session():
    # a /debug/trace hit must not destroy an in-progress capture
    trace.enable(True)
    with trace.span("precious"):
        pass
    doc = trace.capture_seconds(0.05)
    assert trace.enabled(), "live session must stay enabled"
    assert any(e["name"] == "precious" for e in doc["traceEvents"]
               if e["ph"] == "X"), "pre-capture spans must survive"
    assert any(s[0] == "precious" for s in trace.spans())


# -- the profiler's clock ----------------------------------------------------

class _FakeAnnotation:
    """Stands in for jax.profiler.TraceAnnotation: records what the
    spans do to it, on which thread."""

    made: list = []

    def __init__(self, name):
        self.name = name
        self.events = []
        _FakeAnnotation.made.append(self)

    def __enter__(self):
        self.events.append(("enter", threading.get_ident()))
        return self

    def __exit__(self, *exc):
        self.events.append(("exit", threading.get_ident()))
        return False


def test_spans_enter_a_profiler_annotation_when_tracing_is_on(
        monkeypatch):
    _FakeAnnotation.made = []
    trace.enable(True)
    monkeypatch.setattr(trace, "_annotation", _FakeAnnotation)
    with trace.span("outer"):
        with trace.span("inner", rows=3):
            # the annotation is open while the span's body runs
            assert [a.events[0][0] for a in _FakeAnnotation.made] == \
                ["enter", "enter"]
            assert all(len(a.events) == 1 for a in _FakeAnnotation.made)
    me = threading.get_ident()
    assert [a.name for a in _FakeAnnotation.made] == ["outer", "inner"]
    for a in _FakeAnnotation.made:
        assert a.events == [("enter", me), ("exit", me)]
    # the span's own record is unchanged by the mirror
    assert [s[0] for s in trace.spans()] == ["inner", "outer"]


def test_no_annotation_is_constructed_when_tracing_is_off(monkeypatch):
    _FakeAnnotation.made = []
    monkeypatch.setattr(trace, "_annotation", _FakeAnnotation)
    assert not trace.enabled()
    with trace.span("hot", rows=1):
        pass
    trace.instant("placement", reason="pinned")
    trace.complete("queue_wait", time.perf_counter(), 0.01)
    assert _FakeAnnotation.made == []
    assert trace.spans() == []


def test_enable_finds_the_real_annotation_class():
    import jax

    trace.enable(True)
    assert trace._annotation is jax.profiler.TraceAnnotation
    with trace.span("real"):   # the real class takes a bare name
        pass
    assert [s[0] for s in trace.spans()] == ["real"]


# -- compile or load ---------------------------------------------------------

_HIT = "/jax/compilation_cache/cache_hits"
_COMPILE = "/jax/core/compile/backend_compile_duration"


def _compile_counts():
    tel = trace.TELEMETRY.snapshot()
    return (tel["compile_events"], tel["compile_cache_hits"],
            tel["compile_seconds"], tel["compile_cache_seconds"])


def test_cache_hit_then_duration_counts_one_load_and_one_event():
    from jax import monitoring

    trace.enable(True)   # installs the hooks
    trace.TELEMETRY.reset()
    monitoring.record_event(_HIT)
    monitoring.record_event_duration_secs(_COMPILE, 0.25)
    assert _compile_counts() == (1, 1, 0.25, 0.25)
    inst = [s for s in trace.spans() if s[0] == "xla_compile"]
    assert len(inst) == 1 and inst[0][7]["cache_hit"] is True


def test_duration_alone_counts_a_compile_and_no_load():
    from jax import monitoring

    trace.enable(True)
    trace.TELEMETRY.reset()
    monitoring.record_event_duration_secs(_COMPILE, 1.5)
    # the flag of an earlier load does not leak into the next compile
    monitoring.record_event(_HIT)
    monitoring.record_event_duration_secs(_COMPILE, 0.5)
    monitoring.record_event_duration_secs(_COMPILE, 2.0)
    assert _compile_counts() == (3, 1, 4.0, 0.5)
    hits = [s[7]["cache_hit"] for s in trace.spans()
            if s[0] == "xla_compile"]
    assert hits == [False, True, False]


def test_a_load_on_one_thread_is_not_a_load_on_another():
    from jax import monitoring

    trace.enable(True)
    trace.TELEMETRY.reset()
    step = threading.Barrier(2)

    def loader():
        monitoring.record_event(_HIT)        # inside its interval ...
        step.wait(timeout=5)
        step.wait(timeout=5)                 # ... while the other ends
        monitoring.record_event_duration_secs(_COMPILE, 0.2)

    def compiler():
        step.wait(timeout=5)
        monitoring.record_event_duration_secs(_COMPILE, 17.0)
        step.wait(timeout=5)

    threads = [threading.Thread(target=loader),
               threading.Thread(target=compiler)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=10)
    assert _compile_counts() == (2, 1, 17.2, 0.2)


def test_new_counters_are_present_at_zero_from_the_start():
    trace.TELEMETRY.reset()
    tel = trace.TELEMETRY.snapshot()
    for key in ("compile_cache_hits", "compile_cache_seconds",
                "device_wait_seconds",
                *(f"placement_{r}" for r in trace.PLACEMENT_REASONS)):
        assert tel[key] == 0, key
    assert "kernel_seconds" not in tel


# -- inside the ClickHouse sink ----------------------------------------------

def _ch_batch(n=64):
    from transferia_tpu.abstract.schema import TableID
    from transferia_tpu.providers.sample import make_batch

    return make_batch("iot", TableID("sample", "events"), 0, n, 7)


def _sink_tree(recorded):
    """(the `sink` span, its direct children) of one recorded push."""
    sink = [s for s in recorded if s[0] == "sink"]
    assert len(sink) == 1
    kids = [s for s in recorded if s[6] >= 0 and s[10] == sink[0][9]]
    return sink[0], kids


@pytest.mark.parametrize("path", ["native", "numpy"])
@pytest.mark.parametrize("staged", [False, True],
                         ids=["push", "stage_push"])
def test_ch_sink_splits_into_serialize_and_sink_push(staged, path,
                                                     monkeypatch):
    if path == "numpy":  # the repo's switch; lib() caches the library
        from transferia_tpu import native

        monkeypatch.setattr(native, "_lib", None)
        monkeypatch.setenv("TRANSFERIA_TPU_NO_NATIVE", "1")
    from tests.recipes.fake_clickhouse import FakeCH
    from transferia_tpu.middlewares.sync import Statistician
    from transferia_tpu.providers.clickhouse.provider import (
        CHSinker,
        CHTargetParams,
    )
    from transferia_tpu.stats.registry import SinkerStats

    srv = FakeCH().start()
    inner = CHSinker(CHTargetParams(host="127.0.0.1", port=srv.port,
                                    bufferer=None))
    sink = Statistician(inner, SinkerStats())
    batch = _ch_batch()
    try:
        if staged:
            inner.begin_part("op/sample.events/0", 1)
        trace.enable(True)
        trace.reset()
        sink.push(batch)
        trace.enable(False)
        recorded = trace.spans()
        if staged:
            assert inner.publish_part("op/sample.events/0", 1) == 64
        assert srv.total_rows() >= 64
    finally:
        sink.close()
        srv.stop()
    top, kids = _sink_tree(recorded)
    by_name = {s[0]: s for s in kids}
    # two spans an insert, both directly under `sink`, nothing per column
    assert [s[0] for s in recorded].count("serialize") == 1
    assert [s[0] for s in recorded].count("sink_push") == 1
    ser, push = by_name["serialize"], by_name["sink_push"]
    assert ser[6] == push[6] == top[6] + 1
    assert ser[7]["format"] == "rowbinary" and ser[7]["rows"] == 64
    assert ser[7]["path"] == path
    assert ser[7]["columns"] == len(batch.columns)
    assert push[7] == {"direction": "clickhouse_http",
                       "bytes": ser[7]["bytes"]}
    assert ser[7]["bytes"] > 64
    # what `sink` alone read before the split: its duration less the
    # children it had then (everything but the two new spans)
    others = sum(s[4] for s in kids
                 if s[0] not in ("serialize", "sink_push"))
    old_sink_self = top[4] - others
    assert top[5] + ser[5] + push[5] == pytest.approx(old_sink_self,
                                                      abs=1e-6)
    assert ser[5] > 0 and push[5] > 0


# -- the push loop's queue ---------------------------------------------------

class _SlowSink:
    def __init__(self, seconds):
        self.seconds = seconds
        self.pushed = []

    def async_push(self, batch):
        import concurrent.futures

        time.sleep(self.seconds)
        self.pushed.append(batch)
        fut = concurrent.futures.Future()
        fut.set_result(None)
        return fut

    def close(self):
        pass


def _run_parsequeue(n_items, seconds=0.03):
    from transferia_tpu.parsequeue.queue import ParseQueue

    sink = _SlowSink(seconds)
    acked = []
    q = ParseQueue(2, sink, parse_fn=tuple,
                   ack_fn=lambda raw, err: acked.append((raw, err)))
    for i in range(n_items):
        q.add([i] * (i + 1))
    q.wait()
    q.close()
    assert [e for _r, e in acked] == [None] * n_items
    return sink


def test_queue_wait_is_recorded_behind_a_slow_sink():
    trace.enable(True)
    _run_parsequeue(4)
    rec = trace.spans()
    waits = [s for s in rec if s[0] == "queue_wait"]
    assert len(waits) == 4
    assert [s[7]["rows"] for s in waits] == [1, 2, 3, 4]
    # item k waits for the k pushes before it
    durs = [s[4] for s in waits]
    assert durs[3] >= 2 * 0.03 and durs[3] > durs[0]
    # recorded after the fact: a root with all its time its own, so it
    # takes self time from no span - `sink_wait` least of all
    assert all(s[6] == trace.WAIT_DEPTH and s[5] == s[4] and s[10] == 0
               for s in waits)
    pushes = [s for s in rec if s[0] == "sink_wait"]
    assert len(pushes) == 4
    assert all(s[5] == s[4] and s[4] >= 0.03 for s in pushes)


def test_waits_stay_out_of_the_stage_shares_and_the_overlap_factor():
    trace.enable(True)
    with trace.span("sink"):
        time.sleep(0.01)
    before = trace.stage_summary(1.0)
    # a consumer stall and a queued item, each longer than the wall
    trace.complete("decode_wait", time.perf_counter() - 3.0, 3.0)
    trace.complete("queue_wait", time.perf_counter() - 9.0, 9.0, rows=7)
    after = trace.stage_summary(1.0)
    assert after["overlap_factor"] == before["overlap_factor"]
    assert after["stages"] == before["stages"] and before["waits"] == {}
    assert list(after["waits"]) == ["queue_wait", "decode_wait"]
    assert after["waits"]["queue_wait"]["self_s"] == pytest.approx(9.0)
    # a span reader that filters on depth >= 0 still finds them
    assert sum(1 for s in trace.spans() if s[6] >= 0) == 3


def test_summary_table_reads_recorded_spans_and_marks_waits():
    trace.enable(True)
    with trace.span("batch"):
        with trace.span("transform"):
            time.sleep(0.02)
        # a passive wait far longer than the wall: no stage share
        trace.complete("queue_wait", time.perf_counter() - 5.0, 5.0)
    trace.enable(False)
    stages = trace.stage_summary(0.04)["stages"]
    assert list(stages) == ["transform", "batch"]
    assert stages["transform"]["self_s"] >= 0.02
    rows = trace.format_summary(0.04).splitlines()
    names = [ln.split()[0] for ln in rows[2:5]]
    assert names == ["transform", "batch", "~queue_wait"]
    assert rows[0].startswith("wall=0.04s overlap_factor=")


def test_queue_wait_costs_nothing_when_tracing_is_off():
    sink = _run_parsequeue(3, seconds=0.0)
    assert len(sink.pushed) == 3
    assert trace.spans() == []


# -- the kafka client's decode of a fetch response ---------------------------

def test_kafka_decode_is_recorded_with_what_it_decoded():
    """`kafka_decode` covers the decode and offset filter of one
    partition's blob in `fetch_multi`, beside `kafka_roundtrip` and not
    under it; an empty long poll records none."""
    from tests.recipes.fake_kafka import FakeKafka
    from transferia_tpu.providers.kafka.client import KafkaClient
    from transferia_tpu.providers.kafka.protocol import Record
    from transferia_tpu.stats import critpath

    srv = FakeKafka(n_partitions=2).start()
    client = KafkaClient([f"127.0.0.1:{srv.port}"])
    try:
        client.metadata(["td"])
        client.produce("td", 1, [Record(key=b"", value=b"x" * 100)
                                 for _ in range(7)])
        trace.enable(True)
        got = client.fetch_multi("td", {0: 0, 1: 2})
        recorded = trace.spans()
    finally:
        client.close()
        srv.stop()
    assert [r.offset for r in got[1][0]] == [2, 3, 4, 5, 6]
    decode = [s for s in recorded if s[0] == "kafka_decode"]
    assert [s[7] for s in decode] == [
        {"partition": 1, "bytes": decode[0][7]["bytes"], "records": 5}]
    assert decode[0][7]["bytes"] > 7 * 100     # the blob's, untrimmed
    trip = [s for s in recorded if s[0] == "kafka_roundtrip"][-1]
    assert decode[0][10] != trip[9]            # a sibling, not a child
    assert critpath.stage_of("kafka_decode") == "decode"


def test_kafka_handout_counters_are_in_the_snapshot_and_reset():
    trace.TELEMETRY.reset()
    assert trace.TELEMETRY.snapshot()["kafka_handouts"] == 0
    assert trace.TELEMETRY.snapshot()["kafka_handouts_buffered"] == 0
    for buffered in (False, True, True):
        trace.TELEMETRY.record_kafka_handout(buffered)
    tel = trace.TELEMETRY.snapshot()
    assert (tel["kafka_handouts"], tel["kafka_handouts_buffered"]) == (3, 2)
    trace.TELEMETRY.reset()
    tel = trace.TELEMETRY.snapshot()
    assert (tel["kafka_handouts"], tel["kafka_handouts_buffered"]) == (0, 0)


# -- the thread's CPU clock beside the wall clock -----------------------------

def _spin_cpu(seconds):
    """Burn `seconds` of this thread's CPU clock (not the wall clock:
    the suite runs several workers wide)."""
    end = time.thread_time() + seconds
    while time.thread_time() < end:
        pass


def _one(name):
    found = [s for s in trace.spans() if s[0] == name]
    assert len(found) == 1, found
    return found[0]


def test_a_span_that_works_reads_its_cpu_seconds():
    trace.enable(True)
    with trace.span("spin"):
        _spin_cpu(0.03)
    rec = _one("spin")
    assert len(rec) == 12
    assert rec[11] >= 0.025
    # both clocks cover one interval: a thread is on a core for no
    # longer than the wall clock ran
    assert rec[11] <= rec[5] + 0.002


def test_a_span_that_sleeps_reads_next_to_no_cpu():
    trace.enable(True)
    with trace.span("sleep"):
        time.sleep(0.03)
    rec = _one("sleep")
    assert rec[5] >= 0.03
    assert 0.0 <= rec[11] < 0.01


def test_a_parent_keeps_its_childs_cpu_out_of_its_own():
    trace.enable(True)
    with trace.span("parent"):
        with trace.span("child"):
            _spin_cpu(0.03)
    parent, child = _one("parent"), _one("child")
    assert child[11] >= 0.025
    assert 0.0 <= parent[11] < 0.01


class _CostlyCpuClock:
    """Both clocks of a thread that never leaves its core, where a read
    of the CPU clock takes 6 units (a system call: the value is taken
    half way through) and a read of the wall clock none."""

    def __init__(self):
        self.now = 0.0

    def perf_counter(self):
        return self.now

    def thread_time(self):
        self.now += 3.0
        value = self.now
        self.now += 3.0
        return value


def test_the_two_clocks_of_a_span_cover_the_same_interval(monkeypatch):
    """A parent of many short children must not read more CPU than wall
    seconds (nor a child fewer) for the clock reads themselves: on a
    thread that is always on a core every span reads 100%, unclipped."""
    import types

    clock = _CostlyCpuClock()
    trace.enable(True)
    monkeypatch.setattr(trace, "time", types.SimpleNamespace(
        **{**vars(time), "perf_counter": clock.perf_counter,
           "thread_time": clock.thread_time}))
    with trace.span("parent"):
        clock.now += 10.0
        for _ in range(50):
            with trace.span("child"):
                clock.now += 1.0
    monkeypatch.undo()
    parent = _one("parent")
    children = [s for s in trace.spans() if s[0] == "child"]
    assert len(children) == 50
    for rec in (parent, *children):
        assert rec[5] > 0 and rec[11] == pytest.approx(rec[5]), rec
    # a child holds its work and one clock read, the parent the other
    assert children[0][5] == pytest.approx(1.0 + 6.0)
    assert parent[5] == pytest.approx(10.0 + 6.0 + 50 * 6.0)


def test_instant_and_complete_records_carry_no_cpu_clock():
    trace.enable(True)
    trace.instant("retry", attempt=1)
    trace.complete("queue_wait", time.perf_counter() - 1.0, 1.0)
    for name in ("retry", "queue_wait"):
        rec = _one(name)
        assert len(rec) == 12 and rec[11] is None


def test_summary_has_a_cpu_column_beside_self_seconds():
    trace.enable(True)
    with trace.span("transform"):
        _spin_cpu(0.03)
    with trace.span("sink_push"):
        time.sleep(0.03)
    trace.complete("queue_wait", time.perf_counter() - 1.0, 1.0)
    s = trace.stage_summary()
    assert s["stages"]["transform"]["cpu_s"] >= 0.025
    assert s["stages"]["sink_push"]["cpu_s"] < 0.01
    assert s["stages"]["sink_push"]["self_s"] >= 0.03
    # a wait recorded once it ended ran on no thread
    assert s["waits"]["queue_wait"]["cpu_s"] is None
    rows = trace.format_summary().splitlines()
    head = rows[1].split()
    assert head[head.index("self_s") + 1] == "cpu_s"
    col = head.index("cpu_s")
    by_name = {ln.split()[0]: ln.split() for ln in rows[2:5]}
    assert float(by_name["transform"][col]) >= 0.02
    assert by_name["~queue_wait"][col] == "-"


def test_chrome_export_carries_the_thread_clock_duration():
    trace.enable(True)
    with trace.span("part"):
        with trace.span("transform"):
            _spin_cpu(0.03)
        time.sleep(0.02)
    trace.instant("xla_compile", seconds=0.5)
    trace.complete("queue_wait", time.perf_counter() - 1.0, 1.0)
    doc = json.loads(json.dumps(trace.export_chrome_trace()))
    by_name = {e["name"]: e for e in doc["traceEvents"]
               if e["ph"] in ("X", "i")}
    part, tf = by_name["part"], by_name["transform"]
    assert tf["tdur"] >= 25_000
    # a slice's CPU time holds its children's, as its `dur` does
    assert tf["tdur"] <= part["tdur"] <= part["dur"] - 15_000
    assert "tdur" not in by_name["xla_compile"]
    assert "tdur" not in by_name["queue_wait"]


def test_the_cpu_clock_is_not_read_when_tracing_is_off(monkeypatch):
    def boom():
        raise AssertionError("thread_time read with tracing off")

    monkeypatch.setattr(time, "thread_time", boom)
    sp = trace.span("hot")
    assert sp is trace.span("other") and not sp
    with sp:
        sp.add(rows=1)
    trace.instant("x")
    trace.complete("queue_wait", 0.0, 1.0)
    assert trace.spans() == []


def test_a_platform_without_the_clock_records_none(monkeypatch):
    monkeypatch.setattr(trace, "_HAS_THREAD_TIME", False)
    trace.enable(True)
    with trace.span("parent"):
        with trace.span("child"):
            pass
    assert _one("parent")[11] is None and _one("child")[11] is None
    assert trace.stage_summary()["stages"]["parent"]["cpu_s"] is None


# -- the poll thread's wait for a free slot ----------------------------------

class _HoldingSink:
    """Hands out futures that resolve when the test says so."""

    def __init__(self):
        self.futures = []

    def async_push(self, batch):
        import concurrent.futures

        fut = concurrent.futures.Future()
        self.futures.append(fut)
        return fut

    def close(self):
        pass


def test_a_full_window_records_one_inflight_wait_as_long_as_the_hold():
    from transferia_tpu.parsequeue.queue import ParseQueue

    trace.enable(True)
    sink = _HoldingSink()
    q = ParseQueue(1, sink, parse_fn=tuple, ack_fn=lambda raw, err: None,
                   max_inflight=1)
    q.add([1])
    deadline = time.time() + 5.0
    while not sink.futures and time.time() < deadline:
        time.sleep(0.005)
    hold = 0.08
    threading.Timer(hold, lambda: sink.futures[0].set_result(None)).start()
    t0 = time.perf_counter()
    q.add([2])       # no slot until the first unit is acked
    blocked = time.perf_counter() - t0
    while len(sink.futures) < 2 and time.time() < deadline:
        time.sleep(0.005)
    sink.futures[1].set_result(None)
    q.wait()
    q.close()
    rec = _one("inflight_wait")
    assert 0 <= rec[6] < trace.WAIT_DEPTH     # a thread was blocked there
    assert hold * 0.8 <= rec[4] <= blocked + 0.001
    assert rec[11] < 0.02                      # and did not work


def test_a_free_slot_records_no_inflight_wait():
    trace.enable(True)
    _run_parsequeue(4, seconds=0.0)
    assert [s for s in trace.spans() if s[0] == "inflight_wait"] == []
    assert len([s for s in trace.spans() if s[0] == "queue_wait"]) == 4
