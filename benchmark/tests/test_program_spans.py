"""The spans and counters the program gained for the per-layer metrics
(the ClickHouse sink's `serialize` / `sink_push`, the push loop's
`queue_wait`, `auto`'s placement counters, compile-or-load): in a traced
rehearsal of each cell every metric that reads them reads a number, and
every metric that was there before reads what it would have read without
them - the same recorded spans, put back into the form they had before the
sink was split, go through the same readers.
"""

import importlib
import json
import os

import pytest
from test_rehearsal import SEED, fast_flush, shrink  # noqa: F401

from benchmark import run

NEW = {
    "sink_encode_s_per_mrow.snapshot", "sink_encode_s_per_mrow.catchup",
    "sink_wire_s_per_mrow.snapshot", "sink_wire_s_per_mrow.catchup",
    "compile_s_in_window.snapshot", "cache_loads_in_window.snapshot",
    "placement_explore_share.snapshot", "push_wait_s_per_mrow.catchup",
    "queue_wait_p95_ms.catchup", "cache_load_s_in_window.snapshot",
}
# the pii cell reads the snapshot family's quantities under names of its
# own: it reports another end-to-end metric (PERF.md section 2)
NEW |= {n[:-len("snapshot")] + "pii" for n in NEW if n.endswith(".snapshot")}
BENCH = json.load(open(os.path.join(run.ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in BENCH["workloads"]]
FAMILY = {"clickbench-snapshot": ".snapshot",
          "clickbench-snapshot-pii": ".pii", "kafka2ch-catchup": ".catchup"}


def before_the_split(spans: list) -> list:
    """The recorded spans as the program before this PR would have
    recorded them: the sink's two inner spans gone and their time back
    in the span they nest under; the after-the-fact waits gone."""
    inner = [s for s in spans if s[6] >= 0 and (
        (s[0] == "serialize" and (s[7] or {}).get("format") == "rowbinary")
        or (s[0] == "sink_push"
            and (s[7] or {}).get("direction") == "clickhouse_http"))]
    back = {}
    for s in inner:
        back[s[10]] = back.get(s[10], 0.0) + s[4]
    gone = {id(s) for s in inner}
    out = []
    for s in spans:
        if id(s) in gone or s[0] in ("queue_wait", "decode_wait"):
            continue
        if s[6] >= 0 and s[9] in back:
            s = s[:5] + (s[5] + back[s[9]],) + s[6:]
        out.append(s)
    return out


@pytest.mark.parametrize("cell", CELLS)
def test_new_metrics_read_and_old_ones_read_what_they_read(cell,
                                                           monkeypatch):
    seen = {}
    read_per_layer = run.read_per_layer

    def keep_the_data(bench, name, data):
        seen.update(data)
        return read_per_layer(bench, name, data)

    monkeypatch.setattr(run, "read_per_layer", keep_the_data)
    result = run.run_cell(cell, SEED, 1.5, 1, require_chip=False,
                          shrink=shrink)
    assert result["correct"], result["compared"]
    mine = [m["name"] for m in BENCH["per_layer"]
            if cell in m["workloads"]]
    assert NEW & set(mine)
    for name in NEW & set(mine):
        value = result["metrics"][name]["value"]
        assert isinstance(value, (int, float)) and value >= 0, name
    # the split adds up: what `sink` and its kin read is the encoding,
    # the wire and the rest of the sink together
    family = FAMILY[cell]
    whole = result["metrics"]["sink_s_per_mrow" + family]["value"]
    parts = sum(result["metrics"][n + family]["value"]
                for n in ("sink_encode_s_per_mrow", "sink_wire_s_per_mrow"))
    assert 0 < parts < whole
    names = {s[0] for s in seen["spans"]}
    assert {"serialize", "sink_push", "placement"} <= names
    if family == ".catchup":
        assert "queue_wait" in names
    # every metric that was there before this PR, on the old form
    old = dict(seen, spans=before_the_split(seen["spans"]))
    assert len(old["spans"]) < len(seen["spans"])
    checked = 0
    for name in set(mine) - NEW:
        spec = run.load_json("metrics", f"{name}.json")
        if not spec["reader"].startswith("span_"):
            continue    # counters and the device trace see no span
        reader = importlib.import_module(
            f"benchmark.readers.{spec['reader']}")
        then = reader.read(spec["params"], old)
        now = reader.read(spec["params"], seen)
        assert then == pytest.approx(now, rel=1e-9, abs=1e-12), name
        assert not {"queue_wait", "decode_wait"} & set(
            spec["params"].get("spans", [spec["params"].get("span")]))
        checked += 1
    assert checked >= 3
