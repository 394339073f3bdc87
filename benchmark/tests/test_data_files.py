"""Every data file loads, every name is one the contract allows, and
BENCHMARK.json and the files under workloads/, configs/, metrics/ agree."""

import importlib
import json
import os
import re

import pytest
import yaml

from benchmark import run

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _bench():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


def _files(sub, ext=".json"):
    d = os.path.join(run.HERE, sub)
    return sorted(f for f in os.listdir(d) if f.endswith(ext))


@pytest.mark.parametrize("sub", ["workloads", "configs", "metrics"])
def test_every_data_file_loads(sub):
    for f in _files(sub):
        doc = run.load_json(sub, f)
        assert NAME.match(doc["name"]), (sub, f)
        assert doc["name"] == f[:-len(".json")]
    for f in _files("configs", ".yaml"):
        with open(os.path.join(run.HERE, "configs", f)) as fh:
            assert isinstance(yaml.safe_load(
                run._PARAM.sub("1", fh.read())), dict)


def test_benchmark_json_names_units_and_keys():
    b = _bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= b["run_seconds"] <= 51
    seen = set()
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        assert m["name"] not in seen
        seen.add(m["name"])
    for m in b["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert any(m["name"] == "setup_s" for m in b["end_to_end"])
    for w in b["workloads"]:
        assert len(w["why"]) <= 200 and "\n" not in w["why"]
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
    pairs = [(w["config"], w["traffic"]) for w in b["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert len(json.dumps(b)) < 64 * 1024


def test_cells_configs_metrics_are_files_found_by_name():
    b = _bench()
    cells = {w["name"] for w in b["workloads"]}
    e2e_cells = {m["name"]: set(m.get("workloads", cells))
                 for m in b["end_to_end"]}
    for w in b["workloads"]:
        _bench_, cell, config, text = run.load_cell(w["name"])
        importlib.import_module(f"benchmark.traffic.{cell['kind']}")
        assert config["name"] == w["config"]
        # which configuration, traffic and chips: BENCHMARK.json's alone
        assert not {"config", "traffic", "chips", "why"} & set(
            run.load_json("workloads", f"{w['name']}.json"))
        # every cell reports setup_s and one more end-to-end metric
        assert sum(w["name"] in c for c in e2e_cells.values()) >= 2
    for c in b["configs"]:
        assert any(w["config"] == c["name"] for w in b["workloads"])
        with open(os.path.join(run.ROOT, c["file"])) as fh:
            doc = json.load(fh)
        assert set(c["reduced"]) == set(doc["reduced"])
    layers = set()
    for m in b["per_layer"]:
        spec = run.load_json("metrics", f"{m['name']}.json")
        importlib.import_module(f"benchmark.readers.{spec['reader']}")
        assert set(spec) <= {"name", "reader", "params"}
        assert m["workloads"] and set(m["workloads"]) <= cells
        # a per-layer metric lists only cells that report what it moves
        assert set(m["workloads"]) <= e2e_cells[m["moves"]], m["name"]
        layers.add(m["layer"])
    with open(os.path.join(run.ROOT, "PERF.md")) as fh:
        perf = fh.read()
    for layer in layers:
        assert layer in perf, f"PERF.md's layer list lacks {layer!r}"
    for w in cells:
        assert any(w in m["workloads"] for m in b["per_layer"])


def test_peaks_name_their_source():
    with open(os.path.join(run.HERE, "peaks.json")) as fh:
        peaks = json.load(fh)
    assert peaks["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
    assert all("source" in p for p in peaks.values())
