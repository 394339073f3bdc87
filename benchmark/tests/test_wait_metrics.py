"""The metrics that tell a thread's waiting from its work: the reader
`span_cpu_share` on hand-made span tuples, the data files of the
part-thread and poll-thread waits against BENCHMARK.json and the program's
source, and a traced CPU rehearsal of one snapshot cell and of the
catch-up cell, in which every one of them that has something to read
prints.
"""

import functools
import json
import os
import re

import pytest
from test_rehearsal import SEED, fast_flush, shrink  # noqa: F401

from benchmark import run
from benchmark.readers import span_cpu_share, span_self_time

BENCH = json.load(open(os.path.join(run.ROOT, "BENCHMARK.json")))
RATE_CELLS = ["clickbench-snapshot", "tpch-lineitem-q6",
              "tpcc-snapshot-debezium", "clickbench-jsonl-snapshot"]
# suffix -> (the most cells a metric of it may list, what it moves)
SUFFIX = {
    "rate": (RATE_CELLS, "snapshot_rows_per_s"),
    "snapshot": (["clickbench-snapshot"], "snapshot_rows_per_s"),
    "pii": (["clickbench-snapshot-pii"], "snapshot_pass_p50_s"),
    "catchup": (["kafka2ch-catchup"], "replication_rows_per_s"),
}
FAMILIES = ("part_sink_wait_s_per_mrow", "part_coord_s_per_mrow",
            "part_unnamed_s_per_mrow", "decode_stall_s_per_mrow",
            "inflight_wait_s_per_mrow", "scan_filter_s_per_mrow",
            "decode_cpu_share",
            "sink_encode_cpu_share", "transform_host_cpu_share")
MINE = [m for m in BENCH["per_layer"]
        if m["name"].rsplit(".", 1)[0] in FAMILIES]
WAIT_DEPTH = 1 << 20


def span(name, self_s, cpu, depth=1, args=None, fields=12):
    rec = (name, 1, "T1", 0.0, self_s, self_s, depth, args, 7, 8, 0, cpu)
    return rec[:fields]


def share(spans, names=("serialize",), **where):
    params = {"spans": list(names)}
    if where:
        params["where"] = where
    return span_cpu_share.read(params, {"spans": spans})


def test_cpu_share_is_cpu_seconds_over_self_seconds():
    spans = [span("serialize", 2.0, 1.0), span("serialize", 2.0, 2.0),
             span("sink_push", 5.0, 0.0)]
    assert share(spans) == pytest.approx(75.0)
    assert share(spans, ("serialize", "sink_push")) == \
        pytest.approx(100.0 * 3.0 / 9.0)
    # as the clocks read: a coarse CPU clock gives one span 0 or a whole
    # tick, and a sum is right only if nothing is cut off
    assert share([span("serialize", 0.006, 0.01),
                  span("serialize", 0.006, 0.0),
                  span("serialize", 0.008, 0.01)]) == pytest.approx(100.0)
    assert share([span("serialize", 1.0, 1.3)]) == pytest.approx(130.0)


def test_cpu_share_reads_nothing_from_a_program_without_the_clock():
    assert share([span("serialize", 2.0, 1.0, fields=11)]) is None
    assert share([span("serialize", 2.0, None)]) is None
    assert share([]) is None
    assert share([span("sink", 2.0, 1.0)]) is None
    assert share([span("serialize", 0.0, 0.0)]) is None


def test_cpu_share_passes_over_what_ran_on_no_thread():
    spans = [span("serialize", 2.0, 1.0),
             span("serialize", 2.0, None),             # no clock there
             span("serialize", 9.0, None, depth=WAIT_DEPTH),
             span("serialize", 9.0, 9.0, depth=WAIT_DEPTH),
             span("serialize", 0.0, None, depth=-1)]   # an instant
    assert share(spans) == pytest.approx(50.0)


def test_cpu_share_keeps_only_the_spans_whose_args_match():
    spans = [span("serialize", 1.0, 1.0, args={"format": "rowbinary"}),
             span("serialize", 3.0, 0.0, args={"format": "debezium"}),
             span("serialize", 3.0, 0.0)]
    assert share(spans, format="rowbinary") == pytest.approx(100.0)
    assert share(spans, format="debezium") == pytest.approx(0.0)
    assert share(spans, format="csv") is None


def test_self_time_reads_a_record_of_either_length():
    spans = [span("part_drain", 2.0, 0.1), span("part_drain", 1.0, None,
                                                fields=11)]
    assert span_self_time.read({"spans": ["part_drain"]},
                               {"spans": spans, "rows": 1_000_000}) == \
        pytest.approx(3.0)


@functools.lru_cache(maxsize=1)
def _program_source() -> str:
    text = []
    for d, _dirs, files in os.walk(os.path.join(run.ROOT,
                                                "transferia_tpu")):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(d, f)) as fh:
                    text.append(fh.read())
    return "\n".join(text)


def test_there_are_metrics_of_every_family():
    assert {m["name"].rsplit(".", 1)[0] for m in MINE} <= set(FAMILIES)
    assert 1 <= len(MINE) <= 19
    assert BENCH["per_layer"][-len(MINE):] == MINE   # added at the end


@pytest.mark.parametrize("metric", MINE, ids=lambda m: m["name"])
def test_a_wait_metric_names_a_reader_its_cells_and_recorded_spans(metric):
    spec = run.load_json("metrics", metric["name"] + ".json")
    assert spec["name"] == metric["name"]
    assert os.path.exists(os.path.join(
        run.HERE, "readers", spec["reader"] + ".py"))
    cells, moves = SUFFIX[metric["name"].rsplit(".", 1)[1]]
    assert metric["moves"] == moves
    assert metric["workloads"] and set(metric["workloads"]) <= set(cells)
    assert metric["source"] == "program_span"
    assert set(spec["params"]) <= {"spans", "where", "per"}
    source = _program_source()
    for name in spec["params"]["spans"]:
        assert re.search(r'(span|complete)\(\s*"%s"' % name, source), name


def _has_something_to_read(spec, spans) -> bool:
    hits = [s for s in spans if s[0] in spec["params"]["spans"]
            and s[6] >= 0]
    if spec["reader"] == "span_cpu_share":
        hits = [s for s in hits if s[6] < WAIT_DEPTH and s[11] is not None]
    return sum(s[5] for s in hits) > 0


@pytest.mark.parametrize("cell,always", [
    ("clickbench-snapshot", {
        "part_sink_wait_s_per_mrow.rate", "part_coord_s_per_mrow.rate",
        "part_unnamed_s_per_mrow.rate", "decode_stall_s_per_mrow.snapshot",
        "scan_filter_s_per_mrow.snapshot", "decode_cpu_share.rate",
        "sink_encode_cpu_share.rate", "transform_host_cpu_share.rate"}),
    ("kafka2ch-catchup", {
        "decode_cpu_share.catchup", "sink_encode_cpu_share.catchup"}),
])
def test_a_traced_rehearsal_prints_every_wait_metric_it_can_read(
        cell, always, monkeypatch):
    seen = {}
    read_per_layer = run.read_per_layer

    def keep_the_data(bench, name, data):
        seen.update(data)
        return read_per_layer(bench, name, data)

    monkeypatch.setattr(run, "read_per_layer", keep_the_data)
    result = run.run_cell(cell, SEED, 1.5, 1, require_chip=False,
                          shrink=shrink)
    assert result["correct"], result["compared"]
    assert all(len(s) == 12 for s in seen["spans"])
    mine = [m for m in MINE if cell in m["workloads"]]
    for m in mine:
        spec = run.load_json("metrics", m["name"] + ".json")
        if _has_something_to_read(spec, seen["spans"]):
            value = result["metrics"][m["name"]]["value"]
            assert value >= 0, m["name"]
            if m["unit"] == "%":
                # not cut off at 100: the recorder's two clocks cover one
                # interval, so a thread is on a core for no longer than
                # the wall clock ran
                assert 0 <= value < 105, m["name"]
        else:
            assert m["name"] not in result["metrics"]
    listed = {m["name"] for m in mine}
    assert always & listed <= set(result["metrics"])
    names = {s[0] for s in seen["spans"]}
    if "snapshot" in cell:
        # the part thread's waits have names; what is left of `part` and
        # `batch` is less than what they hold
        assert {"part_open", "push_backpressure", "part_drain",
                "part_close", "part_claim", "part_report",
                "scan_filter"} <= names

        def self_s(*which):
            return sum(s[5] for s in seen["spans"]
                       if s[0] in which and s[6] >= 0)

        assert self_s("part", "batch") < self_s(
            "part_open", "push_backpressure", "part_drain", "part_close",
            "part_commit", "part_claim", "part_report")
