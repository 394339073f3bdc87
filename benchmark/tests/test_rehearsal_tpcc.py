"""The cell `tpcc-snapshot-debezium`, end to end on the CPU at a small
size, as `test_rehearsal.py` does the first cells (its `shrink` knows no
MySQL table and a `benchmark` PR edits it, PERF.md section 7); its spans
and counters through the metrics that read them; and its controls, where
`correct` has to come out false."""

import importlib
import json
import os

import pytest
import yaml

from benchmark import control_tpcc, run

SEED = 3_000_000_033
CELL = "tpcc-snapshot-debezium"
BENCH = json.load(open(os.path.join(run.ROOT, "BENCHMARK.json")))


def shrink(cell, config):
    config["population"] = {
        "customers_per_district": 60, "orders_per_district": 60,
        "new_orders_per_district": 18, "stock": 2000, "items": 2000}
    cell["params"].update(min_passes=1, sample_one_in=8)


@pytest.fixture(autouse=True)
def small_parts(monkeypatch):
    """The provider's `_MIN_PART_BYTES` (1 MiB) would leave every table
    of this size whole: here 5,000 bytes a part cut the large ones, and
    batches of 512 rows make several a part."""
    from transferia_tpu.providers.mysql import provider

    monkeypatch.setattr(provider, "_MIN_PART_BYTES", 5000)
    render = run.render_transfer

    def render_small(text, values, cell, out):
        render(text, values, cell, out)
        with open(out) as fh:
            doc = yaml.safe_load(fh)
        if doc["src"]["type"] == "mysql":
            doc["src"]["params"].update(batch_rows=512)
            with open(out, "w") as fh:
                yaml.safe_dump(doc, fh)
        return out

    monkeypatch.setattr(run, "render_transfer", render_small)


def test_rehearsal_of_tpcc_snapshot_debezium(capsys):
    result = run.run_cell(CELL, SEED, 1.0, 0, require_chip=False,
                          shrink=shrink)
    assert result["correct"], result["compared"]
    assert set(result["compared"]) >= {
        "rows_missing", "rows_extra", "rows_duplicated",
        "sample_cells_mismatched", "sample_keys_missing",
        "records_unparsed", "partition_moved", "partitions_unwritten",
        "activate_rc_nonzero", "sink_server_errors"}
    assert all(v == 0 and lim == 0
               for v, lim in result["compared"].values())
    assert set(result["metrics"]) == {"snapshot_rows_per_s", "setup_s"}
    info, account = result["info"], result["account"]
    assert result["failed"] == 0
    assert result["attempted"] == info["source_rows"] * info["passes"]
    assert info["rows_compared"] == result["attempted"]
    assert info["tables"]["history"] == 600 and len(info["tables"]) == 9
    assert info["samples_compared"] > 11 * info["passes"]
    assert account["rows_skipped_by_offset"] == 0
    assert account["offset_statements"] == 0
    cost = account["standin_cost"]
    assert cost["records"] == info["source_rows"]
    assert cost["mysql_rows_sent"] == info["source_rows"]
    # the three large tables in ranges, one produce a part
    assert cost["mysql_result_sets"] == cost["produce_requests"] > 9
    assert cost["superseded_publishes"] == cost["refused_batches"] == 0
    assert account["warm_part_seconds"] > 0 < account["warm_pass_seconds"]
    assert '"correct"' not in capsys.readouterr().out


def test_spans_and_counters_are_read_by_their_metrics(monkeypatch):
    seen = {}
    read_per_layer = run.read_per_layer

    def keep_the_data(bench, name, data):
        seen.update(data)
        return read_per_layer(bench, name, data)

    monkeypatch.setattr(run, "read_per_layer", keep_the_data)
    result = run.run_cell(CELL, SEED, 1.0, 1, require_chip=False,
                          shrink=shrink)
    assert result["correct"], result["compared"]
    mine = {m["name"] for m in BENCH["per_layer"] if CELL in m["workloads"]}
    assert len(mine) == 19 and all(n.endswith(".tpcc") for n in mine)
    # (the profiler sees no device on the CPU: no roofline share here)
    assert set(result["metrics"]) == mine - {"mask_program_roofline.tpcc"}
    for name in ("source_wire_s_per_mrow.tpcc", "decode_s_per_mrow.tpcc",
                 "envelope_s_per_mrow.tpcc",
                 "produce_encode_s_per_mrow.tpcc",
                 "sink_wire_s_per_mrow.tpcc", "sink_s_per_mrow.tpcc",
                 "landed_bytes_per_row.tpcc", "rows_per_produce.tpcc"):
        assert result["metrics"][name]["value"] > 0, name
    assert result["metrics"]["envelope_fast_share.tpcc"]["value"] == 100.0
    assert 0 < result["metrics"]["slowest_part_share.tpcc"]["value"] < 100
    rows = seen["account"]["source_rows_per_pass"] * len(
        seen["account"]["passes"])
    by_name = {}
    for s in seen["spans"]:
        if s[6] >= 0:
            by_name.setdefault(s[0], []).append(s[7] or {})
    assert sum(a["rows"] for a in by_name["mysql_read"]) == rows
    assert {a["table"] for a in by_name["mysql_read"]} == set(
        result["info"]["tables"])
    decodes = [a for a in by_name["source_decode"]
               if a.get("format") == "mysql_text"]
    assert sum(a["rows"] for a in decodes) == rows
    assert sum(a["bytes"] for a in decodes) == \
        sum(a["bytes"] for a in by_name["mysql_read"])
    # (a part's two control batches pass the serializer with no row)
    envelopes = [a for a in by_name["serialize"]
                 if a.get("format") == "debezium" and a["rows"]]
    assert sum(a["rows"] for a in envelopes) == rows
    assert {a["path"] for a in envelopes} == {"fast"}
    pushes = [a for a in by_name["sink_push"]
              if a.get("direction") == "kafka_produce"]
    assert sum(a["records"] for a in pushes) == rows
    assert sum(a["records"] for a in by_name["kafka_encode"]) == rows
    tel = result["telemetry"]
    passes = len(seen["account"]["passes"])
    assert tel["debezium_rows"] == tel["debezium_rows_fast"] == rows
    assert tel["mysql_parts"] == len(pushes) > 9 * passes
    # eight of nine tables cost the chain nothing: every batch that is
    # not the customer's passes untouched
    customer_batches = sum(
        1 for s in seen["spans"] if s[0] == "batch" and s[6] >= 0
        and (s[7] or {}).get("table") == "tpcc.customer")
    all_batches = sum(1 for s in seen["spans"]
                      if s[0] == "batch" and s[6] >= 0)
    assert tel["chain_batches_untouched"] == all_batches - customer_batches
    assert customer_batches > 0
    # the customer's parts share the activation's host reading: one
    # batch a pass measures the host, every other part's first goes to
    # the device, so the chip sees masked values in every pass
    assert tel["placement_host_first"] == passes
    assert tel["placement_device_explore"] >= passes
    assert tel["mask_rows_device_flat"] > 0 < tel["device_launches"]


def test_a_program_without_the_new_spans_leaves_the_metrics_out():
    """The parent commit records none of this PR's spans and counters:
    every new metric's reader returns nothing there, and does not raise."""
    before = {"spans": [("transform", 1, "t", 0.0, 1.0, 1.0, 0, None,
                         1, 1, 0)],
              "telemetry": {"h2d_bytes": 10, "device_launches": 3},
              "telemetry_traced": {}, "rows": 1000, "account": {},
              "trace": {"window_s": 1.0, "busy_s": 0.1,
                        "busy_per_device_s": [0.1],
                        "modules": {"jit_program": 0.1}},
              "peaks": {"hbm_bytes_per_s": 819e9}, "compared": {}}
    for name in ("source_wire_s_per_mrow.tpcc", "decode_s_per_mrow.tpcc",
                 "envelope_s_per_mrow.tpcc", "envelope_fast_share.tpcc",
                 "produce_encode_s_per_mrow.tpcc",
                 "sink_wire_s_per_mrow.tpcc", "device_row_share.tpcc",
                 "landed_bytes_per_row.tpcc", "rows_per_produce.tpcc",
                 "slowest_part_share.tpcc"):
        spec = run.load_json("metrics", f"{name}.json")
        reader = importlib.import_module(
            f"benchmark.readers.{spec['reader']}")
        assert reader.read(spec["params"], before) is None, name


@pytest.mark.parametrize("fault,number", [
    ("served_balance_low", "sample_cells_mismatched"),
    ("dropped_acked_record", "rows_missing")])
def test_a_control_comes_out_not_correct(fault, number):
    result, fired = control_tpcc.run_with_fault(
        CELL, SEED, 1.0, fault, require_chip=False, shrink=shrink)
    assert fired == 1
    assert not result["correct"]
    value, limit = result["compared"][number]
    assert value > limit == 0, result["compared"]
    if fault == "served_balance_low":
        assert value == result["info"]["passes"]
    else:
        assert value == 1
    assert result["failed"] > 0
    others = {k: v for k, (v, _l) in result["compared"].items()
              if k not in (number, "sample_keys_missing")}
    assert all(v == 0 for v in others.values()), others
