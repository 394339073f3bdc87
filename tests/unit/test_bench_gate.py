"""bench.py --against: the perf regression gate.

Covers the three artifact shapes `load_bench_metrics` accepts (driver
wrapper with a `tail`, raw bench log, JSON lines), direction handling
(throughput vs latency metrics), tolerance bands (default + per-metric
overrides), and the gate's exit codes.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))

import bench  # noqa: E402  (repo-root module, not a package)


def _metric(name, value, **extra):
    return {"metric": name, "value": value, "unit": "rows/sec", **extra}


# -- artifact loading --------------------------------------------------------

def test_load_metrics_from_driver_wrapper(tmp_path):
    tail = "\n".join([
        "# some diagnostic line",
        f"# {json.dumps(_metric('checksum_fingerprint_rows_per_sec', 100))}",
        "# profile:   3.3s  49.7%  whatever (x.py:1)",
        json.dumps(_metric("clickbench_snapshot_rows_per_sec", 500)),
    ])
    p = tmp_path / "BENCH_rNN.json"
    p.write_text(json.dumps({"n": 5, "cmd": "python bench.py",
                             "rc": 0, "tail": tail}))
    got = bench.load_bench_metrics(str(p))
    assert got["clickbench_snapshot_rows_per_sec"]["value"] == 500
    assert got["checksum_fingerprint_rows_per_sec"]["value"] == 100


def test_load_metrics_from_raw_log_last_wins(tmp_path):
    p = tmp_path / "run.log"
    p.write_text("\n".join([
        f"# headline(early): {json.dumps(_metric('m', 1))}",
        f"{json.dumps(_metric('m', 2))}",
    ]))
    got = bench.load_bench_metrics(str(p))
    assert got["m"]["value"] == 2


def test_load_metrics_from_json_lines(tmp_path):
    p = tmp_path / "metrics.jsonl"
    p.write_text(json.dumps(_metric("a", 10)) + "\n"
                 + json.dumps(_metric("b", 20)) + "\n")
    got = bench.load_bench_metrics(str(p))
    assert set(got) == {"a", "b"}


# -- comparison --------------------------------------------------------------

def test_throughput_regression_beyond_band_trips():
    prior = {"x_rows_per_sec": _metric("x_rows_per_sec", 1000)}
    current = {"x_rows_per_sec": _metric("x_rows_per_sec", 700)}
    regs, _ = bench.compare_against(prior, current, tolerance=0.15)
    assert len(regs) == 1
    assert regs[0]["metric"] == "x_rows_per_sec"
    # within band: no trip
    current["x_rows_per_sec"]["value"] = 900
    regs, _ = bench.compare_against(prior, current, tolerance=0.15)
    assert regs == []


def test_latency_metric_direction_inverted():
    prior = {"y_p99_ms": _metric("y_p99_ms", 10.0)}
    # latency went UP (worse) by 2x: regression
    current = {"y_p99_ms": _metric("y_p99_ms", 20.0)}
    regs, _ = bench.compare_against(prior, current, tolerance=0.15)
    assert len(regs) == 1
    # latency went DOWN (better): never a regression
    current["y_p99_ms"]["value"] = 1.0
    regs, _ = bench.compare_against(prior, current, tolerance=0.15)
    assert regs == []


def test_per_metric_tolerance_override_widens_band():
    name = "device_mask_kernel_rows_per_sec"  # 0.5 override
    prior = {name: _metric(name, 1000)}
    current = {name: _metric(name, 600)}  # -40%: inside the 0.5 band
    regs, _ = bench.compare_against(prior, current, tolerance=0.15)
    assert regs == []
    current[name]["value"] = 400  # -60%: outside
    regs, _ = bench.compare_against(prior, current, tolerance=0.15)
    assert len(regs) == 1


def test_missing_and_non_numeric_metrics_skip_not_trip():
    prior = {
        "gone": _metric("gone", 5),
        "null_value": {"metric": "null_value", "value": None},
        "zero": _metric("zero", 0),
        "ok_rows_per_sec": _metric("ok_rows_per_sec", 100),
    }
    current = {
        "null_value": {"metric": "null_value", "value": None},
        "zero": _metric("zero", 0),
        "ok_rows_per_sec": _metric("ok_rows_per_sec", 100),
        "brand_new": _metric("brand_new", 1),
    }
    regs, lines = bench.compare_against(prior, current)
    assert regs == []
    joined = "\n".join(lines)
    assert "gone: SKIP" in joined
    assert "null_value: SKIP" in joined
    assert "zero: SKIP" in joined
    assert "brand_new: NEW" in joined


# -- the gate ----------------------------------------------------------------

def test_gate_exit_codes(tmp_path):
    prior = tmp_path / "prior.json"
    prior.write_text(json.dumps(_metric("m_rows_per_sec", 1000)))
    assert bench.run_regression_gate(
        str(prior), {"m_rows_per_sec": _metric("m_rows_per_sec",
                                               990)}) == 0
    assert bench.run_regression_gate(
        str(prior), {"m_rows_per_sec": _metric("m_rows_per_sec",
                                               10)}) == 1
    empty = tmp_path / "empty.json"
    empty.write_text("no metrics here\n")
    assert bench.run_regression_gate(str(empty), {}) == 2


def test_self_compare_of_committed_artifact_passes():
    """The verify-skill smoke: a bench artifact never regresses against
    itself.  The artifact is synthetic (driver-wrapper shape, made-up
    values): it pins the gate's wiring, not any recorded run."""
    artifact = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "fixtures", "bench_artifact.json")
    metrics = bench.load_bench_metrics(artifact)
    assert len(metrics) == 4, "the fixture carries four metric lines"
    regs, _ = bench.compare_against(metrics, metrics)
    assert regs == []


# -- the stages line (trace.format_summary's one-line mode) ------------------

def test_stage_note_is_self_time_share_of_wall_largest_first(monkeypatch):
    from transferia_tpu.stats import trace

    summary = {"wall_s": 10.0, "overlap_factor": 1.25, "waits": {},
               "stages": {"sink": {"self_s": 7.5},
                          "transform": {"self_s": 5.0}}}
    monkeypatch.setattr(trace, "stage_summary", lambda wall=None: summary)
    assert trace.format_summary(10.0, one_line=True) == \
        "sink=7.50s(75%) transform=5.00s(50%) overlap_factor=1.25"
    summary.update(wall_s=0.0, overlap_factor=0.0, stages={})
    assert trace.format_summary(one_line=True) == ""


def test_stage_note_reads_recorded_spans_and_leaves_waits_out():
    import time

    from transferia_tpu.stats import trace

    trace.reset()
    trace.enable(True)
    try:
        with trace.span("batch"):
            with trace.span("transform"):
                time.sleep(0.02)
            # a passive wait far longer than the wall: no stage share
            trace.complete("queue_wait", time.perf_counter() - 5.0, 5.0)
    finally:
        trace.enable(False)
    note = trace.format_summary(0.04, one_line=True)
    table = trace.format_summary(0.04)
    trace.reset()
    assert note.startswith("transform=0.02s(")
    assert " batch=0.00s(" in note and "overlap_factor=" in note
    assert "queue_wait" not in note
    assert "~queue_wait" in table
