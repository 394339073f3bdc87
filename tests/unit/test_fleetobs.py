"""Fleet observability plane (stats/fleetobs.py + stats/hdr.py):
mergeable histogram properties (merge == concat, exactly), obs-segment
coordinator conformance across memory / filestore / s3 / s3-lww
(mirroring the ticket-queue conformance suite), export/merge semantics
(per-process cumulative latest, torn-segment tolerance, cross-process
conservation), and the panes (`trtpu top --fleet`, `trtpu trace
--fleet`, `GET /debug/fleet/obs`)."""

import json
import os
import random
import time
import urllib.request

import pytest

from transferia_tpu.chaos import failpoints
from transferia_tpu.coordinator import (
    FileStoreCoordinator,
    MemoryCoordinator,
    S3Coordinator,
)
from transferia_tpu.stats import fleetobs, hdr, trace
from transferia_tpu.stats.fleetobs import (
    ObsExporter,
    export_fleet_chrome_trace,
    format_fleet_top,
    merge_segments,
)
from transferia_tpu.stats.hdr import LogHistogram
from transferia_tpu.stats.ledger import FIELDS


# -- histograms ---------------------------------------------------------------

class TestLogHistogram:
    def test_merge_equals_concat(self):
        """The mergeability contract: bucket-wise merge of two
        histograms is EXACTLY the histogram of the concatenated
        samples — counts, totals, quantiles, max."""
        rng = random.Random(42)
        for trial in range(5):
            a = [rng.expovariate(1.0 / 0.01) for _ in range(400)]
            b = [rng.lognormvariate(-5, 2) for _ in range(250)]
            ha, hb, hc = LogHistogram(), LogHistogram(), LogHistogram()
            for v in a:
                ha.observe(v)
            for v in b:
                hb.observe(v)
            for v in a + b:
                hc.observe(v)
            ha.merge(hb)
            assert ha.counts == hc.counts
            assert ha.count == hc.count
            assert ha.max_value == hc.max_value
            for q in (0.5, 0.9, 0.99, 0.999):
                assert ha.quantile(q) == hc.quantile(q)

    def test_merge_is_associative_and_commutative_on_buckets(self):
        rng = random.Random(3)
        parts = [[rng.expovariate(100) for _ in range(50)]
                 for _ in range(4)]
        hs = []
        for p in parts:
            h = LogHistogram()
            for v in p:
                h.observe(v)
            hs.append(h)
        left = LogHistogram()
        for h in hs:
            left.merge(h)
        right = LogHistogram()
        for h in reversed(hs):
            right.merge(h)
        assert left.counts == right.counts
        assert left.count == right.count

    def test_edge_values(self):
        h = LogHistogram()
        for v in (0.0, -1.0, 1e-12, 1e-7, 1.0, 3600.0):
            h.observe(v)
        assert h.count == 6
        assert h.quantile(1.0) == 3600.0
        # negatives/zeros clamp into the smallest bucket, never raise
        assert min(h.counts) == 0

    def test_quantile_relative_error_bound(self):
        """SUB=16 sub-buckets per octave: any quantile read-back is
        within ~1/(2*16) relative error of a true sample value."""
        rng = random.Random(11)
        samples = sorted(rng.uniform(0.001, 10.0) for _ in range(2000))
        h = LogHistogram()
        for v in samples:
            h.observe(v)
        for q in (0.5, 0.9, 0.99):
            true = samples[int(q * len(samples)) - 1]
            got = h.quantile(q)
            assert abs(got - true) / true < 0.08, (q, true, got)

    def test_top_bucket_reads_exact_max(self):
        h = LogHistogram()
        h.observe(0.010)
        h.observe(0.7654321)
        assert h.quantile(0.999) == 0.7654321

    def test_exemplar_rides_the_max(self):
        h = LogHistogram()
        h.observe(0.01, trace_id=111)
        h.observe(0.5, trace_id=222)
        h.observe(0.02, trace_id=333)
        assert h.max_trace == 222
        other = LogHistogram()
        other.observe(0.9, trace_id=999)
        h.merge(other)
        assert h.max_trace == 999
        # merging a smaller-max histogram keeps the exemplar
        small = LogHistogram()
        small.observe(0.001, trace_id=1)
        h.merge(small)
        assert h.max_trace == 999

    def test_json_round_trip(self):
        h = LogHistogram()
        for v in (0.001, 0.01, 0.5, 0.5, 2.0):
            h.observe(v, trace_id=7)
        back = LogHistogram.from_json(
            json.loads(json.dumps(h.to_json())))
        assert back.counts == h.counts
        assert back.count == h.count
        assert back.max_value == h.max_value
        assert back.max_trace == h.max_trace
        assert back.quantile(0.99) == h.quantile(0.99)

    def test_from_json_tolerates_junk(self):
        for junk in (None, [], "x", {"counts": "nope"},
                     {"counts": {"a": "b", "3": -1}, "count": "x"}):
            h = LogHistogram.from_json(junk)
            assert h.count == sum(h.counts.values())
        # torn header vs buckets: buckets win
        torn = LogHistogram.from_json(
            {"counts": {"100": 3}, "count": 999})
        assert torn.count == 3

    def test_diff_window(self):
        h = LogHistogram()
        h.observe(0.01)
        base = LogHistogram.from_json(h.to_json())
        h.observe(0.02)
        h.observe(0.04)
        win = h.diff(base)
        assert win.count == 2
        assert sum(win.counts.values()) == 2

    def test_stage_registry_and_merge_maps(self):
        reg = hdr.StageHistograms()
        reg.observe("s1", 0.01, trace_id=5)
        reg.observe("s1", 0.02, trace_id=6)
        reg.observe("s2", 0.5, trace_id=9)
        snap = reg.snapshot()
        assert set(snap) == {"s1", "s2"}
        merged = hdr.merge_stage_maps([snap, snap, {"junk": None}, None])
        assert merged["s1"].count == 4
        assert merged["s2"].count == 2
        assert merged["s2"].max_trace == 9


# -- coordinator conformance --------------------------------------------------

def make_segment(worker="w0.1", pid=1, seq=1, ts=None, kind="periodic",
                 totals=None, transfers=None, tenants=None,
                 hists=None, spans=None, epoch=1000.0,
                 conservation_ok=True):
    base = dict.fromkeys(FIELDS, 0)
    if totals:
        base.update(totals)
    return {
        "v": 1, "worker": worker, "pid": pid, "seq": seq,
        "ts": time.time() if ts is None else ts, "kind": kind,
        "host": "h", "epoch_unix": epoch,
        "spans": spans or [], "spans_dropped": 0,
        "ledger": {"totals": base, "transfers": transfers or {},
                   "tenants": tenants or {},
                   "conservation_ok": conservation_ok},
        "telemetry": {"device_launches": 1},
        "hists": hists or {},
    }


@pytest.fixture(params=["memory", "filestore", "s3", "s3-lww"])
def cp(request, tmp_path):
    if request.param == "memory":
        yield MemoryCoordinator()
        return
    if request.param == "filestore":
        yield FileStoreCoordinator(root=str(tmp_path / "cp"))
        return
    from tests.recipes.fake_s3 import FakeS3

    fake = FakeS3(
        conditional_writes=(request.param == "s3"), page_size=3,
    ).start()
    try:
        yield S3Coordinator(
            bucket="cp-bucket", endpoint=fake.endpoint,
            access_key="test-ak", secret_key="test-sk",
        )
    finally:
        fake.stop()


class TestObsSegmentConformance:
    def test_supports_obs_segments(self, cp):
        assert cp.supports_obs_segments()

    def test_put_list_round_trip_ordered(self, cp):
        cp.put_obs_segment("s", make_segment(worker="w1", seq=2))
        cp.put_obs_segment("s", make_segment(worker="w0", seq=1))
        cp.put_obs_segment("s", make_segment(worker="w0", seq=2))
        got = cp.list_obs_segments("s")
        assert [(g["worker"], g["seq"]) for g in got] == \
            [("w0", 1), ("w0", 2), ("w1", 2)]
        assert got[0]["ledger"]["conservation_ok"] is True

    def test_reput_same_seq_replaces(self, cp):
        cp.put_obs_segment("s", make_segment(seq=1, kind="periodic"))
        cp.put_obs_segment("s", make_segment(seq=1, kind="final"))
        got = cp.list_obs_segments("s")
        assert len(got) == 1
        assert got[0]["kind"] == "final"

    def test_scopes_isolated(self, cp):
        cp.put_obs_segment("a", make_segment(worker="wa"))
        cp.put_obs_segment("b", make_segment(worker="wb"))
        assert [g["worker"] for g in cp.list_obs_segments("a")] == ["wa"]
        assert [g["worker"] for g in cp.list_obs_segments("b")] == ["wb"]

    def test_gc_prunes_by_age(self, cp):
        old = make_segment(worker="w0", seq=1, ts=time.time() - 9999)
        cp.put_obs_segment("s", old)
        cp.put_obs_segment("s", make_segment(worker="w0", seq=2))
        pruned = cp.gc_obs_segments("s", retention_seconds=3600)
        assert pruned == 1
        assert [g["seq"] for g in cp.list_obs_segments("s")] == [2]

    def test_gc_per_worker_bound(self, cp, monkeypatch):
        monkeypatch.setenv("TRANSFERIA_TPU_OBS_SEGMENTS_PER_WORKER",
                           "2")
        for seq in range(1, 6):
            cp.put_obs_segment("s", make_segment(worker="w0", seq=seq))
        cp.put_obs_segment("s", make_segment(worker="w1", seq=1))
        cp.gc_obs_segments("s", retention_seconds=999999)
        got = cp.list_obs_segments("s")
        w0 = [g["seq"] for g in got if g["worker"] == "w0"]
        assert w0 == [4, 5]          # newest two kept
        assert [g["seq"] for g in got if g["worker"] == "w1"] == [1]

    def test_memory_put_bounds_per_worker_without_gc(self):
        """The in-process backend trims at put time — a forgotten GC
        can't grow a long-lived coordinator without bound."""
        cp = MemoryCoordinator()
        for seq in range(1, 40):
            cp.put_obs_segment("s", make_segment(worker="w0", seq=seq))
        from transferia_tpu.coordinator.interface import (
            obs_segments_per_worker,
        )

        assert len(cp.list_obs_segments("s")) <= \
            obs_segments_per_worker()

    def test_torn_stored_segment_skipped(self, cp, tmp_path):
        """A crashed writer's torn file/object is skipped by list, and
        the merge still renders from the survivors."""
        cp.put_obs_segment("s", make_segment(worker="w0", seq=1))
        if isinstance(cp, FileStoreCoordinator):
            with open(os.path.join(cp.root, "obs", "s",
                                   "torn-00000099.json"), "w") as fh:
                fh.write('{"worker": "torn", "seq": 99, "led')
        elif isinstance(cp, S3Coordinator):
            cp.client.put(cp._obs_key("s", "torn", 99),
                          b'{"worker": "torn", "seq": 99, "led')
        else:
            pytest.skip("memory backend cannot store torn JSON")
        got = cp.list_obs_segments("s")
        assert [g["worker"] for g in got] == ["w0"]
        assert merge_segments(got)["segments"] == 1


# -- exporter -----------------------------------------------------------------

class TestObsExporter:
    def test_export_carries_cumulative_payloads(self):
        cp = MemoryCoordinator()
        exp = ObsExporter(cp, worker="wx.1", scope="sc")
        assert exp.enabled
        hdr.observe("t_stage", 0.01)
        assert exp.export("final")
        seg = cp.list_obs_segments("sc")[0]
        assert seg["worker"] == "wx.1"
        assert seg["seq"] == 1
        assert seg["pid"] == os.getpid()
        assert "t_stage" in seg["hists"]
        assert set(seg["ledger"]) >= {"totals", "transfers", "tenants",
                                      "conservation_ok"}
        assert "device_launches" in seg["telemetry"]

    def test_span_delta_not_duplicated_across_exports(self):
        cp = MemoryCoordinator()
        exp = ObsExporter(cp, worker="wd.1", scope="sc")
        trace.enable(True)
        try:
            trace.reset()
            with trace.span("alpha"):
                pass
            assert exp.export("final")
            with trace.span("beta"):
                pass
            assert exp.export("final")
        finally:
            trace.enable(False)
        segs = cp.list_obs_segments("sc")
        names = [[r[0] for r in s["spans"]] for s in segs]
        assert "alpha" in names[0] and "alpha" not in names[1]
        assert "beta" in names[1]

    def test_export_failure_is_absorbed_and_window_resent(self):
        cp = MemoryCoordinator()
        exp = ObsExporter(cp, worker="wf.1", scope="sc")
        trace.enable(True)
        try:
            trace.reset()
            with trace.span("survives"):
                pass
            with failpoints.active(
                    "obs.export=times:1,raise:ChaosInjectedError",
                    seed=1):
                assert exp.export("final") is False
                assert exp.export_failures == 1
                # the failed window re-sends under the SAME seq
                assert exp.export("final") is True
        finally:
            trace.enable(False)
        segs = cp.list_obs_segments("sc")
        assert [s["seq"] for s in segs] == [1]
        assert "survives" in [r[0] for r in segs[0]["spans"]]

    def test_non_final_exports_coalesce(self, monkeypatch):
        monkeypatch.setenv("TRANSFERIA_TPU_OBS_INTERVAL", "30")
        cp = MemoryCoordinator()
        exp = ObsExporter(cp, worker="wc.1", scope="sc")
        assert exp.export("periodic") is True
        assert exp.export("part") is False        # throttled
        assert exp.export("final") is True        # final bypasses

    def test_disabled_without_backend_support(self):
        class NoObs:
            pass

        exp = ObsExporter(NoObs(), worker="w", scope="sc")
        assert not exp.enabled
        assert exp.export("final") is False

    def test_kill_switch_env(self, monkeypatch):
        monkeypatch.setenv("TRANSFERIA_TPU_OBS_EXPORT", "0")
        exp = ObsExporter(MemoryCoordinator(), worker="w", scope="sc")
        assert not exp.enabled

    def test_filestore_export_leaves_no_lock_or_tmp_files(self,
                                                          tmp_path):
        """One export = one segment file.  A lock file per (worker,
        seq) would grow the obs dir O(history) — seq never repeats."""
        cp = FileStoreCoordinator(root=str(tmp_path / "cp"))
        exp = ObsExporter(cp, worker="wl.1", scope="sc")
        for _ in range(3):
            assert exp.export("final")
        d = os.path.join(cp.root, "obs", "sc")
        names = os.listdir(d)
        assert all(n.endswith(".json") for n in names), names
        # and GC sweeps any stray lock/tmp debris from crashed writers
        open(os.path.join(d, "x.json.lock"), "w").close()
        open(os.path.join(d, "y.json.tmp.123"), "w").close()
        cp.gc_obs_segments("sc", retention_seconds=999999)
        assert all(n.endswith(".json")
                   for n in os.listdir(d)), os.listdir(d)

    def test_s3_gc_prunes_torn_segments(self):
        """A crashed writer's unparsable object must not survive GC
        forever (no per-worker trim can ever reach a dead label)."""
        from tests.recipes.fake_s3 import FakeS3

        fake = FakeS3(conditional_writes=True, page_size=3).start()
        try:
            cp = S3Coordinator(bucket="cp-bucket",
                               endpoint=fake.endpoint,
                               access_key="test-ak",
                               secret_key="test-sk")
            cp.put_obs_segment("s", make_segment(worker="ok", seq=1))
            cp.client.put(cp._obs_key("s", "torn", 9),
                          b'{"worker": "torn", "seq": 9, "led')
            pruned = cp.gc_obs_segments("s", retention_seconds=999999)
            assert pruned == 1
            assert [g["worker"] for g in cp.list_obs_segments("s")] \
                == ["ok"]
        finally:
            fake.stop()

    def test_registry_does_not_pin_coordinators(self):
        """The exporter holds its coordinator weakly: a dropped
        coordinator (per-trial chaos runs, test churn) must be
        collectable despite living as a registry key."""
        import gc as _gc

        cp = MemoryCoordinator()
        exp = fleetobs.exporter_for(cp, worker="wgc.1")
        assert exp.export("final")
        ref = __import__("weakref").ref(cp)
        del cp
        _gc.collect()
        assert ref() is None, "exporter registry pinned the coordinator"
        assert exp.export("final") is False    # dead backend: no-op

    def test_exporter_registry_shares_streams(self):
        cp = MemoryCoordinator()
        a = fleetobs.exporter_for(cp, worker="wr.1", scope=None)
        b = fleetobs.exporter_for(cp, worker="wr.1", scope=None)
        assert a is b
        c = fleetobs.exporter_for(cp, worker="other.1", scope=None)
        assert c is not a
        # the ambient exporter wins over a fresh label for the SAME
        # coordinator (a loader inside a fleet worker's ticket run
        # joins the worker's stream)
        with fleetobs.ambient_exporter(a):
            d = fleetobs.exporter_for(cp, worker="snap.w0.123")
            assert d is a
            other_cp = MemoryCoordinator()
            e = fleetobs.exporter_for(other_cp, worker="snap.w0.123")
            assert e is not a


# -- merge --------------------------------------------------------------------

class TestMerge:
    def test_latest_per_process_no_double_count(self):
        """Two segments from ONE process: cumulative payloads take the
        newest only (totals are process-cumulative — summing both
        would double-bill)."""
        segs = [
            make_segment(worker="w0", pid=10, seq=1, ts=100.0,
                         totals={"rows_in": 50}),
            make_segment(worker="w0", pid=10, seq=2, ts=200.0,
                         totals={"rows_in": 80}),
        ]
        view = merge_segments(segs, now=210.0)
        assert view["totals"]["rows_in"] == 80
        assert view["processes"] == 1

    def test_sum_across_processes_and_conservation(self):
        tr_a = {"t1": {"tenant": "ta", "parts": 1, "rows_in": 30,
                       **{f: 0 for f in FIELDS if f != "rows_in"}}}
        tr_b = {"t1": {"tenant": "ta", "parts": 1, "rows_in": 12,
                       **{f: 0 for f in FIELDS if f != "rows_in"}}}
        segs = [
            make_segment(worker="a", pid=1, seq=3,
                         totals={"rows_in": 30}, transfers=tr_a),
            make_segment(worker="b", pid=2, seq=5,
                         totals={"rows_in": 12}, transfers=tr_b),
        ]
        view = merge_segments(segs)
        assert view["totals"]["rows_in"] == 42
        assert view["transfers"]["t1"]["rows_in"] == 42
        assert sorted(view["transfers"]["t1"]["workers"]) == ["a", "b"]
        assert view["conservation"]["ok"]
        assert view["conservation"]["per_process_totals"]["h:1"][
            "rows_in"] == 30

    def test_same_pid_different_hosts_both_counted(self):
        """Containerized fleets: every worker is pid 1.  Process
        identity is (host, pid) — a bare-pid merge would silently drop
        one host's cumulative state."""
        tr = lambda n: {"t1": {  # noqa: E731
            "tenant": "ta", "rows_in": n,
            **{f: 0 for f in FIELDS if f != "rows_in"}}}
        seg_a = make_segment(worker="w", pid=1, seq=1,
                             totals={"rows_in": 10}, transfers=tr(10))
        seg_a["host"] = "host-a"
        seg_b = make_segment(worker="w", pid=1, seq=1,
                             totals={"rows_in": 7}, transfers=tr(7))
        seg_b["host"] = "host-b"
        view = merge_segments([seg_a, seg_b])
        assert view["processes"] == 2
        assert view["totals"]["rows_in"] == 17
        assert view["conservation"]["ok"]
        assert set(view["conservation"]["per_process_totals"]) == \
            {"host-a:1", "host-b:1"}
        # same worker LABEL on two hosts renders as two workers
        assert set(view["workers"]) == {"w@host-a", "w@host-b"}
        # and the Perfetto export gives each host its own lane
        seg_a["spans"] = [_span_rec("x", 1, 0.0, 1.0, None, 3, 1, 0)]
        seg_b["spans"] = [_span_rec("y", 1, 0.0, 1.0, None, 4, 2, 0)]
        doc = export_fleet_chrome_trace([seg_a, seg_b])
        lanes = {e["pid"] for e in doc["traceEvents"]
                 if e.get("ph") == "X"}
        assert len(lanes) == 2

    def test_conservation_drift_detected(self):
        """A merge where the per-transfer aggregation disagrees with
        the per-process totals (torn data, merge bug) must report
        DRIFT, not silently lie."""
        segs = [make_segment(worker="a", pid=1, seq=1,
                             totals={"rows_in": 100}, transfers={})]
        view = merge_segments(segs)
        assert not view["conservation"]["ok"]
        assert view["conservation"]["drift"]["rows_in"] == 100

    def test_per_worker_liveness_ages(self):
        segs = [
            make_segment(worker="w0", pid=1, seq=1, ts=1000.0),
            make_segment(worker="w1", pid=2, seq=4, ts=1090.0,
                         kind="final"),
        ]
        view = merge_segments(segs, now=1100.0)
        assert view["workers"]["w0"]["age_seconds"] == 100.0
        assert view["workers"]["w1"]["age_seconds"] == 10.0
        assert view["workers"]["w1"]["kind"] == "final"

    def test_torn_segments_skipped_and_counted(self):
        segs = [
            make_segment(worker="ok", pid=1, seq=1),
            {"worker": "torn", "seq": "x", "ts": "y", "pid": "z"},
            "not even a dict",
            {"no_worker": True},
        ]
        view = merge_segments(segs)
        assert view["segments"] == 1
        assert view["corrupt_segments"] == 3
        assert list(view["workers"]) == ["ok"]

    def test_obs_merge_failpoint_treated_as_torn(self):
        segs = [make_segment(worker="a", pid=1, seq=1),
                make_segment(worker="b", pid=2, seq=1)]
        with failpoints.active(
                "obs.merge=times:1,raise:ChaosInjectedError", seed=1):
            view = merge_segments(segs)
        assert view["segments"] == 1
        assert view["corrupt_segments"] == 1

    def test_histograms_merge_across_processes(self):
        h1, h2 = LogHistogram(), LogHistogram()
        for v in (0.01, 0.02):
            h1.observe(v)
        h2.observe(0.5, trace_id=77)
        segs = [
            make_segment(worker="a", pid=1, seq=1,
                         hists={"st": h1.to_json()}),
            make_segment(worker="b", pid=2, seq=1,
                         hists={"st": h2.to_json()}),
        ]
        view = merge_segments(segs)
        st = view["hists"]["st"]
        assert st["count"] == 3
        assert st["max_trace"] == 77
        assert st["p999_ms"] == 500.0

    def test_format_fleet_top_renders(self):
        tr = {"t1": {"tenant": "ta", "rows_in": 10, "rows_out": 10,
                     **{f: 0 for f in FIELDS
                        if f not in ("rows_in", "rows_out")}}}
        view = merge_segments([
            make_segment(worker="w0", pid=1, seq=1,
                         totals={"rows_in": 10, "rows_out": 10},
                         transfers=tr)])
        text = format_fleet_top(view)
        assert "fleet obs: 1 segment(s)" in text
        assert "conservation OK" in text
        assert "t1" in text


# -- merged Perfetto export ---------------------------------------------------

def _span_rec(name, tid, t0, dur, args, trace_id, span_id, parent_id,
              depth=0):
    return [name, tid, f"T{tid}", t0, dur, dur, depth, args, trace_id,
            span_id, parent_id]


class TestFleetChromeTrace:
    def test_two_processes_one_timeline_with_flow(self):
        # scheduler process: admission span (trace 9, span 1) at
        # wall epoch 1000; worker process: run span parented on it at
        # wall epoch 1002
        seg_sched = make_segment(
            worker="sched", pid=100, seq=1, epoch=1000.0,
            spans=[_span_rec("fleet_dist_admit", 1, 0.5, 0.01,
                             {"ticket_id": "tk-0"}, 9, 1, 0)])
        seg_worker = make_segment(
            worker="fleet.w1", pid=200, seq=1, epoch=1002.0,
            spans=[_span_rec("fleet_ticket_run", 7, 0.25, 1.0,
                             {"transfer_id": "tr-0"}, 9, 2, 1)])
        doc = export_fleet_chrome_trace([seg_sched, seg_worker])
        evs = doc["traceEvents"]
        pids = {e["pid"] for e in evs if e.get("ph") == "X"}
        assert pids == {100, 200}
        # wall-clock alignment: the worker span (epoch 1002 + 0.25s)
        # lands AFTER the scheduler span (epoch 1000 + 0.5s)
        by_name = {e["name"]: e for e in evs if e.get("ph") == "X"}
        assert by_name["fleet_ticket_run"]["ts"] > \
            by_name["fleet_dist_admit"]["ts"]
        # the cross-process parent link renders as one s/f flow pair
        flows = [e for e in evs if e.get("cat") == "flow"]
        assert {f["ph"] for f in flows} == {"s", "f"}
        assert {f["pid"] for f in flows} == {100, 200}
        # process lanes carry the worker labels
        names = [e for e in evs if e["name"] == "process_name"]
        assert {e["args"]["name"] for e in names} == \
            {"trtpu sched", "trtpu fleet.w1"}

    def test_transfer_filter_keeps_whole_trace(self):
        match = make_segment(
            worker="a", pid=1, seq=1,
            spans=[_span_rec("snapshot_op", 1, 0.0, 1.0,
                             {"transfer_id": "tr-X"}, 5, 1, 0),
                   _span_rec("part", 1, 0.1, 0.5, None, 5, 2, 1)])
        other = make_segment(
            worker="b", pid=2, seq=1,
            spans=[_span_rec("snapshot_op", 1, 0.0, 1.0,
                             {"transfer_id": "tr-Y"}, 6, 3, 0)])
        doc = export_fleet_chrome_trace([match, other],
                                        transfer_id="tr-X")
        names = [e["name"] for e in doc["traceEvents"]
                 if e.get("ph") == "X"]
        assert "part" in names           # in-trace span with no args
        assert all(e.get("args", {}).get("trace_id") != 6
                   for e in doc["traceEvents"] if e.get("ph") == "X")

    def test_a_twelve_field_record_round_trips_and_an_older_one_reads(
            self):
        """The span tuple's twelfth field (self CPU seconds) rides the
        segment as it is; a segment written before the field has eleven
        and reads as before."""
        from transferia_tpu.stats import critpath

        cp = MemoryCoordinator()
        exp = ObsExporter(cp, worker="wt.1", scope="sc")
        trace.enable(True)
        try:
            trace.reset()
            with trace.span("snapshot_op", transfer_id="tr-12"):
                end = time.thread_time() + 0.01
                while time.thread_time() < end:
                    pass
            trace.complete("queue_wait", time.perf_counter() - 1.0, 1.0)
            assert exp.export("final")
        finally:
            trace.enable(False)
        seg = json.loads(json.dumps(cp.list_obs_segments("sc")[0]))
        by_name = {r[0]: r for r in seg["spans"]}
        assert len(by_name["snapshot_op"]) == 12
        assert by_name["snapshot_op"][11] >= 0.009
        assert by_name["queue_wait"][11] is None
        old = make_segment(
            worker="old", pid=7, seq=1,
            spans=[_span_rec("part", 1, 0.1, 0.5, None, 5, 2, 0)])
        assert len(old["spans"][0]) == 11
        doc = export_fleet_chrome_trace([seg, old])
        names = {e["name"] for e in doc["traceEvents"]
                 if e.get("ph") == "X"}
        assert names >= {"snapshot_op", "queue_wait", "part"}
        records = critpath.records_from_segments([seg, old])
        assert {r["name"] for r in records} >= {"snapshot_op", "part"}
        assert critpath.explain(records, transfer_id="tr-12")

    def test_overlapping_export_windows_dedup(self):
        rec = _span_rec("s", 1, 0.0, 1.0, None, 5, 1, 0)
        segs = [make_segment(worker="a", pid=1, seq=1, spans=[rec]),
                make_segment(worker="a", pid=1, seq=2, spans=[rec])]
        doc = export_fleet_chrome_trace(segs)
        assert sum(1 for e in doc["traceEvents"]
                   if e.get("ph") == "X") == 1


# -- end-to-end through the engine + debug surfaces ---------------------------

class TestEngineExport:
    def _run_snapshot(self, cp, transfer_id="obs-e2e"):
        from transferia_tpu.models import Transfer, TransferType
        from transferia_tpu.providers.memory import (
            MemoryTargetParams,
            get_store,
        )
        from transferia_tpu.providers.sample import SampleSourceParams
        from transferia_tpu.tasks.snapshot import SnapshotLoader

        get_store(transfer_id).clear()
        t = Transfer(
            id=transfer_id, type=TransferType.SNAPSHOT_ONLY,
            src=SampleSourceParams(preset="iot", table="events",
                                   rows=256, batch_rows=64,
                                   shard_parts=2),
            dst=MemoryTargetParams(sink_id=transfer_id))
        SnapshotLoader(t, cp).upload_tables()
        get_store(transfer_id).clear()

    def test_snapshot_exports_segments_and_pane_renders(self):
        cp = MemoryCoordinator()
        self._run_snapshot(cp)
        segs = cp.list_obs_segments(fleetobs.default_scope())
        assert segs, "snapshot loader exported no obs segments"
        assert any(s["kind"] == "final" for s in segs)
        view = merge_segments(segs)
        assert view["conservation"]["ok"]
        assert view["totals"]["rows_in"] >= 256
        assert "part_upload" in view["hists"]
        assert view["hists"]["part_upload"]["count"] >= 2
        assert "obs-e2e" in format_fleet_top(view)

    def test_debug_fleet_obs_endpoint_and_liveness(self):
        from transferia_tpu.cli.main import _start_health_server

        cp = MemoryCoordinator()
        self._run_snapshot(cp, transfer_id="obs-http")
        cp.operation_health("fleet:q", 3, {"state": "running",
                                           "ticket": "tk-9",
                                           "tickets_run": 2})
        fleetobs.register_runtime(cp, health_scope="fleet:q")
        try:
            port = _start_health_server(0)
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/debug/fleet/obs",
                    timeout=10) as resp:
                view = json.loads(resp.read())
            assert view["segments"] >= 1
            assert view["conservation"]["ok"]
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/debug/fleet",
                    timeout=10) as resp:
                fleet_view = json.loads(resp.read())
            workers = fleet_view["workers"]["workers"]
            assert workers["3"]["ticket"] == "tk-9"
            assert workers["3"]["age_seconds"] is not None
        finally:
            fleetobs.unregister_runtime()

    def test_debug_fleet_obs_without_runtime_503(self):
        from transferia_tpu.cli.main import _start_health_server

        fleetobs.unregister_runtime()
        port = _start_health_server(0)
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/debug/fleet/obs")
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=10)
        assert ei.value.code == 503

    def test_top_fleet_once_and_trace_fleet_cli(self, tmp_path,
                                                capsys):
        from transferia_tpu.cli.main import main

        root = str(tmp_path / "cp")
        cp = FileStoreCoordinator(root=root)
        trace.enable(True)
        try:
            trace.reset()
            self._run_snapshot(cp, transfer_id="obs-cli")
        finally:
            trace.enable(False)
        rc = main(["--coordinator", "filestore",
                   "--coordinator-dir", root, "top", "--fleet",
                   "--once"])
        out = capsys.readouterr().out
        assert rc == 0
        assert "fleet obs:" in out and "obs-cli" in out
        rc = main(["--coordinator", "filestore",
                   "--coordinator-dir", root, "top", "--fleet",
                   "--json"])
        out = capsys.readouterr().out
        assert rc == 0
        assert json.loads(out)["conservation"]["ok"]
        trace_out = str(tmp_path / "fleet_trace.json")
        rc = main(["--coordinator", "filestore",
                   "--coordinator-dir", root, "trace",
                   "--fleet", "obs-cli", "--out", trace_out])
        assert rc == 0
        with open(trace_out) as fh:
            doc = json.load(fh)
        names = [e["name"] for e in doc["traceEvents"]]
        assert "snapshot_op" in names and "part" in names
