"""The deployment `tpch-lineitem-pg2ch` through the normal entry, shrunk:
`trtpu activate` on the configuration's transfer YAML (20,000 rows of
TPC-H LINEITEM in 2 ctid parts, Q6's predicate as the filter_rows step),
the benchmark's stand-ins at both ends (benchmark/pgserver.py,
benchmark/chserver.py), placement forced to the host, to the device, and
left to `auto`: what lands equals the benchmark's reference
(benchmark/reference_lineitem.py), cell for cell, in all three.
"""

import json
import os

import pytest
import yaml

from benchmark import rowbinary, run
from benchmark.traffic import snapshot_passes_pg
from transferia_tpu.abstract.errors import AbortTransferError
from transferia_tpu.cli.main import main as trtpu
from transferia_tpu.stats.trace import TELEMETRY
from transferia_tpu.transform.fused import set_placement

ROWS = 20_000
SEED = 2_900_000_029


@pytest.fixture(scope="module")
def world(tmp_path_factory):
    rowbinary.build()
    _bench, cell, config, text = run.load_cell("tpch-lineitem-q6")
    config["scale_factor"] = ROWS / 6_001_215
    side = snapshot_passes_pg.World(cell, config, SEED, 1.0, "")
    work = tmp_path_factory.mktemp("tpch")
    path = run.render_transfer(text, side.endpoints(), cell,
                               str(work / "transfer.yaml"))
    with open(path) as fh:
        doc = yaml.safe_load(fh)
    # 358 heap pages in 2 ctid parts, batches of 4,096
    doc["src"]["params"].update(desired_part_size_bytes=180 * 8192,
                                batch_rows=4096)
    with open(path, "w") as fh:
        yaml.safe_dump(doc, fh)
    yield side, path
    side.close()


@pytest.mark.parametrize("placement", ["host", "device", None],
                         ids=["host", "device", "auto"])
def test_activate_lands_what_the_reference_expects(world, placement):
    side, path = world
    assert side.table["rows"] == ROWS
    side.passes.clear()
    set_placement(placement)
    before = TELEMETRY.snapshot()
    try:
        rc = trtpu(["--log-level", "warning", "activate",
                    "--transfer", path])
    finally:
        set_placement(None)
    after = TELEMETRY.snapshot()
    landed = side.cmd_pass_end(in_window=True)
    assert rc == 0 and not landed["server_errors"]
    # 2 ctid parts, one COPY each, every source row served once
    cost = landed["standin_cost"]
    assert cost["pg_copy_rows"] % ROWS == 0
    out = side.cmd_verify()
    assert all(v == 0 and limit == 0
               for v, limit in out["numbers"].values()), out["numbers"]
    assert out["failed"] == 0
    info = out["info"]
    assert info["rows_compared"] == info["kept_per_pass"] == landed["rows"]
    assert 0.015 < info["kept_per_pass"] / ROWS < 0.023
    on_device = after["filter_rows_device"] - before["filter_rows_device"]
    on_host = after["filter_rows_host"] - before["filter_rows_host"]
    assert on_device + on_host == ROWS
    if placement == "host":
        assert on_device == 0
    if placement == "device":
        assert on_host == 0
    assert after["filter_batches_host_unsafe"] == \
        before["filter_batches_host_unsafe"]
    # what landed is typed as the sink maps it
    types = side.passes[0]["ch_types"]
    assert types["l_shipdate"].startswith("Date32")
    assert types["l_discount"] == "String"
    assert types["l_orderkey"].startswith("Int32")


def test_a_bad_date_literal_fails_the_activation(world, tmp_path):
    side, path = world
    with open(path) as fh:
        doc = yaml.safe_load(fh)
    doc["transformation"] = {"transformers": [
        {"filter_rows": {"filter": "l_shipdate >= 'yesterday'"}}]}
    bad = tmp_path / "bad.yaml"
    with open(bad, "w") as fh:
        yaml.safe_dump(doc, fh)
    side.passes.clear()
    copies = side.pg.cost["copies"]
    with pytest.raises(AbortTransferError) as err:
        trtpu(["--log-level", "error", "activate", "--transfer", str(bad)])
    assert "l_shipdate" in str(err.value) and "yesterday" in str(err.value)
    # before a row was read, and nothing landed
    assert side.pg.cost["copies"] == copies
    assert side.cmd_pass_end(in_window=False)["rows"] == 0
