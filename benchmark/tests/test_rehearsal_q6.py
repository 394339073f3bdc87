"""The cell `tpch-lineitem-q6`, end to end on the CPU at a tiny size, as
`test_rehearsal.py` does the others; its spans and counters through the
metrics that read them, as `test_program_spans.py` does; and its controls,
where `correct` has to come out false.

`test_rehearsal.py`'s own `shrink` knows two kinds of cell (a parquet table,
a kafka backlog) and `test_program_spans.py` knows three cells' families, so
their cases for the cells PR 29 adds fail in the tests' own code
(`KeyError: 'warm_waves'`, `KeyError` in `FAMILY`) until a `benchmark` PR
edits them (PERF.md section 7); these are the same checks with a shrink
that knows the third kind.
"""

import json
import os

import pytest
import yaml

from benchmark import control, control_pg, run

SEED = 3_000_000_019
BENCH = json.load(open(os.path.join(run.ROOT, "BENCHMARK.json")))


def shrink(cell, config):
    config["scale_factor"] = 0.006       # some 36,000 rows
    cell["params"].update(min_passes=1)


@pytest.fixture(autouse=True)
def small_parts(monkeypatch):
    """The provider's default 256 MiB a ctid part gives the tiny heap one
    part: here 160 pages a part give four or five, and batches of 2,048."""
    render = run.render_transfer

    def render_small(text, values, cell, out):
        render(text, values, cell, out)
        with open(out) as fh:
            doc = yaml.safe_load(fh)
        if doc["src"]["type"] == "pg":
            doc["src"]["params"].update(
                desired_part_size_bytes=160 * 8192, batch_rows=2048)
            with open(out, "w") as fh:
                yaml.safe_dump(doc, fh)
        return out

    monkeypatch.setattr(run, "render_transfer", render_small)


def test_rehearsal_of_tpch_lineitem_q6(capsys):
    result = run.run_cell("tpch-lineitem-q6", SEED, 1.0, 0,
                          require_chip=False, shrink=shrink)
    assert result["correct"], result["compared"]
    assert result["failed"] == 0 and result["attempted"] > 500
    assert set(result["compared"]) >= {
        "rows_missing", "rows_extra", "rows_duplicated", "cells_mismatched",
        "activate_rc_nonzero", "sink_server_errors"}
    assert all(v == 0 and lim == 0
               for v, lim in result["compared"].values())
    assert set(result["metrics"]) == {"snapshot_rows_per_s", "setup_s"}
    assert all(v["value"] > 0 for v in result["metrics"].values())
    info, account = result["info"], result["account"]
    assert info["rows_compared"] == info["kept_per_pass"] * info["passes"]
    assert 0.015 < info["kept_per_pass"] / info["source_rows"] < 0.023
    assert account["source_rows_per_pass"] == info["source_rows"]
    cost = account["standin_cost"]
    # several ctid parts a pass, one COPY each; the whole heap sent in the
    # whole warm pass and in each pass of the window, and before them one
    # COPY from the cursor's page on (here the whole tiny heap: the world
    # reckons the cursor by the provider's default part size)
    passes = info["passes"] + 1
    assert (cost["pg_copies"] - 1) % passes == 0
    assert (cost["pg_copies"] - 1) // passes >= 4
    assert cost["pg_copy_rows"] == info["source_rows"] * (passes + 1)
    assert account["warm_part_seconds"] > 0 < account["warm_pass_seconds"]
    assert account["warm_part_telemetry"]["compile_events"] >= 0
    assert len(account["pass_compile_seconds"]) == \
        len(account["pass_seconds"])
    assert 120 < info["copy_bytes_per_row"] < 160
    assert '"correct"' not in capsys.readouterr().out


def test_new_spans_and_counters_are_read_by_their_metrics(monkeypatch):
    seen = {}
    read_per_layer = run.read_per_layer

    def keep_the_data(bench, name, data):
        seen.update(data)
        return read_per_layer(bench, name, data)

    monkeypatch.setattr(run, "read_per_layer", keep_the_data)
    result = run.run_cell("tpch-lineitem-q6", SEED, 1.0, 1,
                          require_chip=False, shrink=shrink)
    assert result["correct"], result["compared"]
    mine = {m["name"] for m in BENCH["per_layer"]
            if "tpch-lineitem-q6" in m["workloads"]}
    assert set(result["metrics"]) <= mine
    assert len(mine) == 14
    for name in ("source_wire_s_per_mrow.q6", "decode_s_per_mrow.q6",
                 "filter_device_row_share.q6", "sink_s_per_mrow.q6",
                 "sink_encode_s_per_mrow.q6", "sink_wire_s_per_mrow.q6",
                 "transform_host_s_per_mrow.q6",
                 "placement_explore_share.q6", "compiles_in_window.q6",
                 "compile_s_in_window.q6", "device_idle_share.q6"):
        value = result["metrics"][name]["value"]
        assert isinstance(value, (int, float)) and value >= 0, name
    assert result["metrics"]["source_wire_s_per_mrow.q6"]["value"] > 0
    assert result["metrics"]["decode_s_per_mrow.q6"]["value"] > 0
    # a device metric prints a number or nothing, never a 0 for "no row"
    tel = result["telemetry"]
    for name in ("h2d_bytes_per_device_row.q6",
                 "filter_program_roofline.q6"):
        if not tel["filter_rows_device"]:
            assert name not in result["metrics"]
    # the spans, and what their args add up to
    rows = seen["account"]["source_rows_per_pass"] * len(
        seen["account"]["passes"])
    by_name = {}
    for s in seen["spans"]:
        if s[6] >= 0:
            by_name.setdefault(s[0], []).append(s[7] or {})
    decodes = [a for a in by_name["source_decode"]
               if a.get("format") == "pg_copy"]
    assert sum(a["rows"] for a in decodes) == rows
    assert sum(a["messages"] for a in by_name["pg_copy_read"]) == rows
    assert sum(a["bytes"] for a in by_name["pg_copy_read"]) == \
        sum(a["bytes"] for a in decodes)
    # two DECIMAL columns of the predicate, each batch's view made once
    assert sum(a["rows"] for a in by_name["decimal_view"]) == 2 * rows
    assert {"placement", "predicate_coerce"} <= {s[0] for s in seen["spans"]}
    # the counters: every source row's predicate ran somewhere, a filter-
    # only chain's batches were placed by `auto`, no batch was too wide
    assert tel["filter_rows_device"] + tel["filter_rows_host"] == rows
    assert tel["filter_batches_host_unsafe"] == 0
    placed = sum(v for k, v in tel.items() if k.startswith("placement_"))
    assert placed > 0 and tel["placement_pinned"] == 0


def test_a_program_without_the_new_counters_leaves_the_metrics_out():
    """The parent commit records none of this PR's spans and counters:
    every new metric's reader returns nothing there, and does not raise."""
    import importlib

    before = {"spans": [("transform", 1, "t", 0.0, 1.0, 1.0, 0, None,
                         1, 1, 0)],
              "telemetry": {"h2d_bytes": 10, "device_launches": 3},
              "telemetry_traced": {}, "rows": 1000, "account": {},
              "trace": {"window_s": 1.0, "busy_s": 0.1,
                        "modules": {"jit_program": 0.1}},
              "peaks": {"hbm_bytes_per_s": 819e9}, "compared": {}}
    for name in ("source_wire_s_per_mrow.q6", "decode_s_per_mrow.q6",
                 "filter_device_row_share.q6",
                 "h2d_bytes_per_device_row.q6",
                 "filter_program_roofline.q6"):
        spec = run.load_json("metrics", f"{name}.json")
        reader = importlib.import_module(
            f"benchmark.readers.{spec['reader']}")
        assert reader.read(spec["params"], before) is None, name


CONTROLS = [("drop_insert", "rows_missing"),
            ("served_discount_low", "rows_missing")]


@pytest.mark.parametrize("fault,number", CONTROLS)
def test_a_control_comes_out_not_correct(fault, number):
    if fault == "drop_insert":      # `faults.py`'s, through `control.py`
        result, fired = control.run_with_fault(
            "tpch-lineitem-q6", SEED, 1.0, fault, nth=2, require_chip=False,
            shrink=shrink)
    else:
        result, fired = control_pg.run_with_fault(
            "tpch-lineitem-q6", SEED, 1.0, fault, require_chip=False,
            shrink=shrink)
    assert fired == 1
    assert not result["correct"]
    value, limit = result["compared"][number]
    assert value > limit == 0, result["compared"]
    if fault == "served_discount_low":
        # one row a pass: served a hundredth under Q6's lower bound, the
        # program drops it where the generator's table keeps it
        assert value == result["info"]["passes"]
    assert result["failed"] > 0
    others = {k: v for k, (v, _l) in result["compared"].items()
              if k != number}
    assert all(v == 0 for v in others.values()), others
