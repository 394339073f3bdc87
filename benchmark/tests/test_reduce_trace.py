"""The trace reduction on a small trace recorded on the chip."""

import json
import os

import numpy as np

from benchmark import reduce_trace as rt
from benchmark.readers import device_trace

HERE = os.path.dirname(os.path.abspath(__file__))


def _doc():
    with open(os.path.join(HERE, "data", "trace_v5e_catchup.json")) as fh:
        return json.load(fh)


def _brute_busy_ns(events):
    lo = min(s for _n, s, _d in events)
    hi = max(s + d for _n, s, d in events)
    line = np.zeros(hi - lo, dtype=bool)
    for _n, s, d in events:
        line[s - lo:s + d - lo] = True
    return int(line.sum()), lo, hi


def test_busy_is_the_union_of_the_op_intervals():
    doc = _doc()
    ops = next(ln["events"] for ln in doc["planes"][0]["lines"]
               if ln["name"] == rt.OPS_LINE)
    busy_ns, lo, hi = _brute_busy_ns(ops)
    out = rt.reduce(doc, window_s=6.0)
    assert out["devices_busy"] == 1
    assert abs(out["busy_s"] * 1e9 - busy_ns) < 1
    assert out["busy_per_device_s"] == [out["busy_s"]]    # one chip
    assert out["busy_s"] < (hi - lo) / 1e9 < 6.0
    assert out["clock"] == "unknown"          # no wall clock was given
    assert out["breakdown"]["idle_gaps"][0][0] == "unattributed"
    assert len(out["breakdown"]["device_ops"]) <= rt.TOP
    assert all(len(name) <= 64 for name, _s in out["breakdown"]["device_ops"])
    total = sum(d for _n, _s, d in ops) / 1e9
    assert sum(s for _n, s in out["breakdown"]["device_ops"]) <= total + 1e-12


def test_modules_are_summed_by_name_without_the_fingerprint():
    doc = _doc()
    mods = next(ln["events"] for ln in doc["planes"][0]["lines"]
                if ln["name"] == rt.MODULES_LINE)
    out = rt.reduce(doc, window_s=6.0)
    assert set(out["modules"]) == {rt._module_name(n) for n, _s, _d in mods}
    assert "jit_program" in out["modules"]
    assert abs(sum(out["modules"].values())
               - sum(d for _n, _s, d in mods) / 1e9) < 1e-12


def test_gaps_are_attributed_on_the_trace_start_clock():
    doc = _doc()
    ops = next(ln["events"] for ln in doc["planes"][0]["lines"]
               if ln["name"] == rt.OPS_LINE)
    _busy, lo, hi = _brute_busy_ns(ops)
    t0 = 1_790_000_000 * 10**9                 # start_trace, wall clock
    window = (t0, t0 + 6 * 10**9)
    # a root span over the first half of the window, a short one over the
    # ops, a long one over everything: a gap goes to the span it is most
    # about, not to the one that covers most
    epoch = 1_790_000_000.0 - 5.0
    spans = [("replication_attempt", 1, "t", 5.0, 3.0, 0.0, 0),
             ("process", 1, "t", 0.0, 500.0, 0.0, 0),
             # (ends 10 us before the last op: a float holds the wall
             # clock to a quarter of a microsecond)
             ("device_wait", 1, "t", 5.0 + lo / 1e9, (hi - lo) / 1e9 - 1e-5,
              0.0, 1),
             ("instant", 1, "t", 5.0, 0.0, 0.0, -1)]
    out = rt.reduce(doc, 6.0, spans, epoch, window)
    assert out["clock"] == "trace_start"
    gaps = dict(out["breakdown"]["idle_gaps"])
    assert "device_wait" in gaps              # the gaps between the ops
    # window start .. first op (4.7 s): the attempt's 3 s overlap it most
    assert abs(gaps["replication_attempt"] - lo / 1e9) < 1e-6
    # after the last op only the process-long span is left
    assert abs(gaps["process"] - (6.0 - hi / 1e9)) < 1e-6
    assert "unattributed" not in gaps
    assert abs(sum(gaps.values()) + out["busy_s"] - 6.0) < 0.02


def test_busy_is_told_device_by_device():
    doc = _doc()
    one = rt.reduce(doc, window_s=6.0)["busy_s"]
    # a second chip that ran the first eight of the first one's
    # operations, and a third whose plane holds nothing
    some = [dict(ln, events=ln["events"][:8])
            for ln in doc["planes"][0]["lines"]]
    ops = next(ln["events"] for ln in some if ln["name"] == rt.OPS_LINE)
    doc["planes"] += [{"name": "/device:TPU:1", "lines": some},
                      {"name": "/device:TPU:2", "lines": []}]
    out = rt.reduce(doc, window_s=6.0)
    assert out["devices_busy"] == 2 and len(out["busy_per_device_s"]) == 2
    assert out["busy_per_device_s"][0] == one
    assert abs(out["busy_per_device_s"][1] * 1e9
               - _brute_busy_ns(ops)[0]) < 1
    assert 0 < out["busy_per_device_s"][1] < one
    assert out["busy_s"] == sum(out["busy_per_device_s"]) / 2
    assert rt.reduce({"planes": []}, 3.0)["busy_per_device_s"] == []


def test_an_items_wait_names_no_gap():
    from transferia_tpu.stats import trace

    assert rt.WAIT_DEPTH == trace.WAIT_DEPTH
    doc = _doc()
    t0 = 1_790_000_000 * 10**9
    epoch = 1_790_000_000.0
    # catch-up's window: the push loop waits for the flush (`sink_wait`, a
    # span a thread is in) while fetched batches queue (`queue_wait`,
    # recorded afterwards by `trace.complete()`, covering the same seconds
    # more closely)
    spans = [("sink_wait", 1, "t", 0.0, 6.5, 6.5, 1),
             ("queue_wait", 1, "t", 0.0, 6.0, 6.0, rt.WAIT_DEPTH)]
    out = rt.reduce(doc, 6.0, spans, epoch, (t0, t0 + 6 * 10**9))
    gaps = dict(out["breakdown"]["idle_gaps"])
    assert set(gaps) == {"sink_wait"}
    assert abs(gaps["sink_wait"] + out["busy_s"] - 6.0) < 0.02
    # with nothing else over a gap it is unattributed, not the wait's
    out = rt.reduce(doc, 6.0, spans[1:], epoch, (t0, t0 + 6 * 10**9))
    assert set(dict(out["breakdown"]["idle_gaps"])) == {"unattributed"}


def test_a_trace_in_which_nothing_ran_reads_the_chip_idle():
    out = rt.reduce({"planes": []}, 3.0)
    assert out["busy_s"] == 0.0 and out["breakdown"]["device_ops"] == []
    data = {"trace": out, "telemetry_traced": {}, "compared": {},
            "peaks": {"hbm_bytes_per_s": 819e9}}
    # `auto` kept the chain on the host: idle is the reading, a roofline
    # has nothing to read; no trace at all reads nothing
    assert device_trace.read({"what": "idle_share"}, data) == 100.0
    assert device_trace.read({"what": "idle_share"},
                             {**data, "trace": {**out, "window_s": 0.0}}) \
        is None
    assert device_trace.read({"what": "hbm_roofline", "modules": "x"},
                             data) is None


def test_roofline_counts_the_bytes_the_mask_needs():
    # 1,000 rows of one 100-byte column: two SHA blocks in, a digest out
    assert device_trace.needed_bytes(1000, {"URL": 128.0}) == 1000 * 160.0
    tr = {"busy_s": 0.5, "window_s": 2.0,
          "modules": {"jit_program": 1e-3, "jit_other": 5.0}}
    data = {"trace": tr, "telemetry_traced": {"mask_rows_device_flat": 1000},
            "compared": {"sha_block_bytes_per_row": {"URL": 128.0}},
            "peaks": {"hbm_bytes_per_s": 819e9}}
    got = device_trace.read({"what": "hbm_roofline",
                             "modules": "^jit_program"}, data)
    assert abs(got - 100 * (160000 / 819e9) / 1e-3) < 1e-12
    assert device_trace.read({"what": "idle_share"}, data) == 75.0
