"""The cell `clickbench-jsonl-snapshot`, end to end on the CPU at a tiny
size, as `test_rehearsal.py` does the older cells; its spans and counters
through the metrics that read them, as `test_program_spans.py` does; and
its control, where `correct` has to come out false.

`test_rehearsal.py`'s own `shrink` knows two kinds of cell and
`test_program_spans.py` three cells' families, so their cases for this
cell fail in the tests' own code until a `benchmark` PR edits them
(PERF.md section 7 (a)); these are the same checks with a shrink that
knows this kind.
"""

import json
import os

from benchmark import control_jsonl, run

SEED = 3_000_000_019
CELL = "clickbench-jsonl-snapshot"
BENCH = json.load(open(os.path.join(run.ROOT, "BENCHMARK.json")))


def shrink(cell, config):
    # four objects of one batch each: four parts a pass
    config["source_table"].update(rows=8192, file_rows=2048,
                                  batch_rows=2048)
    cell["params"].update(sample_one_in=4, min_passes=1)


def test_rehearsal_of_clickbench_jsonl_snapshot(capsys):
    result = run.run_cell(CELL, SEED, 1.0, 0, require_chip=False,
                          shrink=shrink)
    assert result["correct"], result["compared"]
    assert result["failed"] == 0 and result["attempted"] > 1000
    assert set(result["compared"]) >= {
        "rows_missing", "rows_extra", "sample_keys_missing",
        "sample_rows_unexpected", "sample_cells_mismatched",
        "ch_types_wrong", "activate_rc_nonzero", "sink_server_errors"}
    assert all(v == 0 and lim == 0
               for v, lim in result["compared"].values())
    assert set(result["metrics"]) == {"snapshot_rows_per_s", "setup_s"}
    assert all(v["value"] > 0 for v in result["metrics"].values())
    info, account = result["info"], result["account"]
    assert info["source_rows"] == account["source_rows_per_pass"] == 8192
    assert info["objects"] == 4 and info["text_fault"] is None
    assert 0.3 < info["kept_per_pass"] / info["source_rows"] < 0.6
    assert info["sample_rows_compared"] > 100 * info["passes"]
    assert 2000 < account["source_bytes_per_pass"] / 8192 < 2800
    assert account["warm_part_seconds"] > 0 < account["warm_pass_seconds"]
    # every row of every pass by the block path: the warm object, the warm
    # pass and the window's passes
    tel = result["telemetry"]
    assert tel["jsonl_rows"] == tel["jsonl_rows_block"] == \
        8192 * info["passes"]
    assert tel["jsonl_bytes"] == \
        account["source_bytes_per_pass"] * info["passes"]
    assert '"correct"' not in capsys.readouterr().out


def test_every_jsonl_metric_finds_what_it_reads(monkeypatch):
    seen = {}
    read_per_layer = run.read_per_layer

    def keep_the_data(bench, name, data):
        seen.update(data)
        return read_per_layer(bench, name, data)

    monkeypatch.setattr(run, "read_per_layer", keep_the_data)
    result = run.run_cell(CELL, SEED, 1.0, 1, require_chip=False,
                          shrink=shrink)
    assert result["correct"], result["compared"]
    mine = {m["name"] for m in BENCH["per_layer"]
            if CELL in m.get("workloads", [])}
    assert len(mine) == 17 and all(n.endswith(".jsonl") for n in mine)
    assert set(result["metrics"]) <= mine
    # what the CPU can show: all but the device's own numbers
    for name in mine - {"mask_program_roofline.jsonl",
                        "h2d_bytes_per_device_row.jsonl"}:
        value = result["metrics"][name]["value"]
        assert isinstance(value, (int, float)) and value >= 0, name
    m = {k: v["value"] for k, v in result["metrics"].items()}
    assert m["decode_block_share.jsonl"] == 100.0
    assert m["decode_s_per_mrow.jsonl"] > 0
    assert m["source_read_s_per_mrow.jsonl"] > 0
    assert 2000 < m["source_bytes_per_row.jsonl"] < 2800
    assert 0 < m["slowest_part_share.jsonl"] <= 100
    # the spans, and what their args add up to
    passes = len(seen["account"]["passes"])
    by_name = {}
    for s in seen["spans"]:
        if s[6] >= 0:
            by_name.setdefault(s[0], []).append(s[7] or {})
    decodes = [a for a in by_name["source_decode"]
               if a.get("format") == "jsonl"]
    assert sum(a["rows"] for a in decodes) == 8192 * passes
    assert {a["path"] for a in decodes} == {"block"}
    reads = by_name["file_read"]
    assert sum(a["bytes"] for a in reads) == sum(
        a["bytes"] for a in decodes) == \
        seen["account"]["source_bytes_per_pass"] * passes
    assert all(a["path"].endswith(".jsonl") for a in reads)
    # one part an object, and each part one batch: the activation's parts
    # go by the placement book, one host reading among them
    tel = result["telemetry"]
    assert len(by_name["part"]) == 4 * passes
    assert tel["placement_host_first"] == passes
    assert sum(v for k, v in tel.items()
               if k.startswith("placement_")) == 4 * passes


def test_the_control_reads_not_correct():
    result = control_jsonl.run_with_fault(
        CELL, SEED, 1.0, "CounterID", require_chip=False, shrink=shrink)
    assert not result["correct"]
    bad = {k: v for k, (v, lim) in result["compared"].items() if v > lim}
    assert set(bad) == {"sample_cells_mismatched"}
    assert bad["sample_cells_mismatched"] == result["info"]["passes"]
    row, before, after = result["info"]["text_fault"]
    assert before != after and len(before) == len(after)
