"""Each cell end to end on the CPU at a tiny size - the harness's look for
a chip skipped, no metric printed - and once more with the timed path
broken underneath, where `correct` has to come out false.

The kafka rehearsals flush every 50 ms where the deployment's bufferer
flushes every second: with the default, 64 queued fetched batches take 64 s
to land (PERF.md section 6, PR 24), which no test run can hold.
"""

import json
import os

import pytest
import yaml

from benchmark import control, run

SEED = 3_000_000_019
CELLS = [w["name"] for w in json.load(
    open(os.path.join(run.ROOT, "BENCHMARK.json")))["workloads"]]


def shrink(cell, config):
    if "table" in config:
        config["table"].update(rows=24000, file_rows=12000, batch_rows=4096)
        cell["params"].update(sample_one_in=4, min_passes=1)
    else:
        cell["params"].update(backlog=1500, chunk_events=4096, rate=500,
                              users=5000, trace_seconds=0.5,
                              drain_quiet_s=3.0,
                              warm_waves=cell["params"]["warm_waves"][:1])


@pytest.fixture(autouse=True)
def fast_flush(monkeypatch):
    render = run.render_transfer

    def render_fast(text, values, cell, out):
        render(text, values, cell, out)
        with open(out) as fh:
            doc = yaml.safe_load(fh)
        if doc["src"]["type"] == "kafka":
            doc["dst"]["params"]["bufferer"] = {
                "trigger_rows": 100000, "trigger_interval": 0.05}
            with open(out, "w") as fh:
                yaml.safe_dump(doc, fh)
        return out

    monkeypatch.setattr(run, "render_transfer", render_fast)


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_of_each_cell(cell, capsys):
    trace = 1 if cell in CELLS[:2] else 0   # one of each family traced
    result = run.run_cell(cell, SEED, 1.5, trace, require_chip=False,
                          shrink=shrink)
    assert result["correct"], result["compared"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["device"]["platform"] == "cpu"
    for name, (value, limit) in result["compared"].items():
        assert value <= limit, name
    bench = json.load(open(os.path.join(run.ROOT, "BENCHMARK.json")))
    if trace:
        names = {m["name"] for m in bench["per_layer"]
                 if cell in m["workloads"]}
        assert set(result["metrics"]) <= names and result["metrics"]
        assert result["device"]["window_s"] > 0
    else:
        names = {m["name"] for m in bench["end_to_end"]
                 if cell in m.get("workloads", [cell])}
        assert set(result["metrics"]) == names
        assert all(v["value"] > 0 for v in result["metrics"].values())
    if "snapshot" in cell:
        # set-up held a part pass and a whole one, and says what each
        # compiled or loaded; so does every pass of the window
        acc = result["account"]
        assert acc["warm_part_seconds"] > 0 and acc["warm_pass_seconds"] > 0
        assert acc["warm_part_telemetry"]["compile_events"] >= 0
        assert acc["warm_telemetry"]["compile_events"] >= 0
        assert len(acc["pass_compile_seconds"]) == len(acc["pass_seconds"])
    # a rehearsal prints no result line
    assert '"correct"' not in capsys.readouterr().out


FAULTS = [
    ("clickbench-snapshot", "duplicate_insert", "rows_extra"),
    ("clickbench-snapshot", "half_batch", "rows_missing"),
    ("clickbench-snapshot", "alter_answer", "sample_cells_mismatched"),
    ("kafka2ch-catchup", "drop_insert", "events_missing"),
    ("kafka2ch-catchup", "half_batch", "events_missing"),
    ("kafka2ch-catchup", "alter_answer", "rows_field_mismatch"),
    ("clickbench-snapshot-pii", "alter_answer", "sample_cells_mismatched"),
]


@pytest.mark.parametrize("cell,fault,number", FAULTS)
def test_a_fault_in_the_timed_path_comes_out_not_correct(cell, fault,
                                                         number):
    if cell not in CELLS:
        pytest.skip(f"{cell} is not in BENCHMARK.json")
    result, fired = control.run_with_fault(
        cell, SEED, 1.5, fault, nth=2, require_chip=False, shrink=shrink)
    assert fired == 1
    assert not result["correct"]
    value, limit = result["compared"][number]
    assert value > limit, result["compared"]
    assert result["failed"] > 0
