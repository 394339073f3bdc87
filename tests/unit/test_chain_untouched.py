"""A chain whose mask names one table: a batch of any other table passes
it as it came - the same object, no device batch, no placement decision."""

import pyarrow as pa
import pytest

from transferia_tpu.abstract.schema import (
    CanonicalType,
    ColSchema,
    TableID,
    TableSchema,
)
from transferia_tpu.columnar.batch import ColumnBatch
from transferia_tpu.stats.trace import TELEMETRY
from transferia_tpu.transform.chain import build_chain

CHAIN = {"transformers": [{"mask_field": {
    "columns": ["c_first", "c_phone"], "tables": ["customer"],
    "salt": "s"}}]}
DEVICE_COUNTERS = ("h2d_bytes", "h2d_transfers", "device_launches",
                   "d2h_bytes", "compile_events")


def batch(table, names):
    cols = [ColSchema(name=n, data_type=CanonicalType.UTF8) for n in names]
    rb = pa.record_batch({n: pa.array([f"{n}{i}" for i in range(50)])
                          for n in names})
    return ColumnBatch.from_arrow(rb, TableID("tpcc", table),
                                  TableSchema(cols))


def placements(snap):
    return sum(v for k, v in snap.items() if k.startswith("placement_"))


@pytest.mark.parametrize("table,names", [
    ("orders", ["o_id", "o_c_id"]),
    # the masked columns' names in another table are not the mask's
    ("supplier", ["c_first", "c_phone"]),
])
def test_a_batch_of_another_table_is_untouched(table, names):
    chain = build_chain(CHAIN)
    b = batch(table, names)
    before = TELEMETRY.snapshot()
    out = chain.apply(b)
    after = TELEMETRY.snapshot()
    assert out is b
    assert after["chain_batches_untouched"] \
        - before["chain_batches_untouched"] == 1
    assert all(after[k] == before[k] for k in DEVICE_COUNTERS)
    assert placements(after) == placements(before)
    assert all(after[k] == before[k] for k in after
               if k.startswith("mask_rows_"))


def test_the_named_table_is_masked():
    chain = build_chain(CHAIN)
    b = batch("customer", ["c_first", "c_phone", "c_city"])
    before = TELEMETRY.snapshot()["chain_batches_untouched"]
    out = chain.apply(b).to_pydict()
    assert TELEMETRY.snapshot()["chain_batches_untouched"] == before
    assert all(len(v) == 64 for v in out["c_first"] + out["c_phone"])
    assert out["c_city"] == [f"c_city{i}" for i in range(50)]
