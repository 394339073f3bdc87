"""Fused device transform step: parity with the host step-by-step path.

The canon contract of the device plane: for any plan, the fused
DeviceFusedStep output is byte-identical to running each transformer's host
implementation in order (hashlib HMAC, numpy predicate).  These tests run
on the virtual CPU mesh (conftest) — the same XLA program runs on TPU.
"""

import hashlib
import hmac

import numpy as np
import pytest

from transferia_tpu.abstract import TableID
from transferia_tpu.abstract.schema import CanonicalType, new_table_schema
from transferia_tpu.columnar import ColumnBatch
from transferia_tpu.predicate import parse
from transferia_tpu.predicate.device import device_compatible
from transferia_tpu.transform import build_chain
from transferia_tpu.transform.fused import (
    DeviceFusedStep,
    maybe_fuse_steps,
    set_device_fusion,
)

SCHEMA = new_table_schema([
    ("id", "int32", True),
    ("url", "utf8"),
    ("title", "utf8"),
    ("region", "int32"),
    ("width", "int32"),
    ("big", "int64"),
])
TID = TableID("web", "hits")


def make_batch(n=257):
    rng = np.random.default_rng(7)
    urls = [f"https://e{i}.com/p/{rng.integers(1e6)}" for i in range(n)]
    titles = [f"Title {i}" if i % 5 else "" for i in range(n)]
    batch = ColumnBatch.from_pydict(TID, SCHEMA, {
        "id": list(range(n)),
        "url": [None if i % 11 == 0 else urls[i] for i in range(n)],
        "title": titles,
        "region": [int(rng.integers(0, 500)) for _ in range(n)],
        "width": [int(rng.integers(300, 2600)) for _ in range(n)],
        "big": [2**61 + i for i in range(n)],
    })
    return batch


CONFIG = {"transformers": [
    {"mask_field": {"columns": ["url"], "salt": "s3cr3t"}},
    {"filter_rows": {"filter": "region < 400 AND width >= 390"}},
]}


def run_chain(config, batch, fused: bool, placement: str = "device"):
    # placement pinned to "device" so fused=True really exercises the XLA
    # program (auto would route the first batch to the host strategy)
    from transferia_tpu.transform.fused import set_placement

    set_device_fusion(fused)
    set_placement(placement)
    try:
        chain = build_chain(config)
        return chain.apply(batch)
    finally:
        set_device_fusion(None)
        set_placement(None)


def batches_equal(a: ColumnBatch, b: ColumnBatch):
    assert a.n_rows == b.n_rows
    assert a.schema.names() == b.schema.names()
    for name in a.schema.names():
        ca, cb = a.column(name), b.column(name)
        assert ca.ctype == cb.ctype, name
        assert ca.to_pylist() == cb.to_pylist(), name


def test_fused_parity_mask_filter():
    batch = make_batch()
    host = run_chain(CONFIG, batch, fused=False)
    dev = run_chain(CONFIG, batch, fused=True)
    batches_equal(host, dev)
    # and the mask really is HMAC-SHA256 hex of the raw value
    url_col = dev.column("url")
    raw = make_batch().column("url")
    i = 1  # a valid row
    expect = hmac.new(b"s3cr3t",
                      raw.value(i).encode(), hashlib.sha256).hexdigest()
    assert url_col.value(i) == expect


def test_fused_plan_contains_single_device_step():
    set_device_fusion(True)
    try:
        chain = build_chain(CONFIG)
        plan = chain.plan_for(TID, SCHEMA)
        assert len(plan.steps) == 1
        assert isinstance(plan.steps[0], DeviceFusedStep)
        assert plan.steps[0].describe().startswith("device[")
    finally:
        set_device_fusion(None)


def test_filter_before_mask_fuses_and_matches():
    config = {"transformers": [
        {"filter_rows": {"filter": "region >= 100"}},
        {"mask_field": {"columns": ["url", "title"], "salt": "k"}},
    ]}
    batch = make_batch(300)
    host = run_chain(config, batch, fused=False)
    dev = run_chain(config, batch, fused=True)
    batches_equal(host, dev)


def test_predicate_on_masked_column_not_fused_together():
    # filter reads url AFTER masking -> must not join the mask's fused run
    config = {"transformers": [
        {"mask_field": {"columns": ["url"], "salt": "k"}},
        {"filter_rows": {"filter": "region < 100"}},
    ]}
    # region predicate is fine; but url predicate after mask is not:
    config2 = {"transformers": [
        {"mask_field": {"columns": ["url"], "salt": "k"}},
        {"filter_rows": {"filter": "url = 'x'"}},
    ]}
    set_device_fusion(True)
    try:
        plan = build_chain(config2).plan_for(TID, SCHEMA)
        # mask fuses alone; string filter stays host
        assert len(plan.steps) == 2
        assert isinstance(plan.steps[0], DeviceFusedStep)
    finally:
        set_device_fusion(None)
    batch = make_batch(64)
    batches_equal(run_chain(config2, batch, fused=False),
                  run_chain(config2, batch, fused=True))


def test_int64_predicate_stays_on_host():
    node = parse("big > 5")
    assert not device_compatible(node, SCHEMA)
    config = {"transformers": [
        {"mask_field": {"columns": ["url"], "salt": "k"}},
        {"filter_rows": {"filter": "big >= 2305843009213693953"}},
    ]}
    batch = make_batch(40)
    host = run_chain(config, batch, fused=False)
    dev = run_chain(config, batch, fused=True)
    batches_equal(host, dev)


def test_double_mask_splits_runs():
    config = {"transformers": [
        {"mask_field": {"columns": ["url"], "salt": "a"}},
        {"mask_field": {"columns": ["url"], "salt": "b"}},
    ]}
    set_device_fusion(True)
    try:
        plan = build_chain(config).plan_for(TID, SCHEMA)
        assert len(plan.steps) == 2  # two runs, not one chained program
    finally:
        set_device_fusion(None)
    batch = make_batch(33)
    batches_equal(run_chain(config, batch, fused=False),
                  run_chain(config, batch, fused=True))


@pytest.mark.parametrize("pred", [
    "region IS NULL",
    "region IS NOT NULL",
    "region IN (1, 2, 3) OR width BETWEEN 400 AND 800",
    "NOT (region < 250)",
    "region != 7 AND NOT width = 0",
])
def test_device_predicate_3vl_parity(pred):
    schema = new_table_schema([
        ("url", "utf8"), ("region", "int32"), ("width", "int32"),
    ])
    n = 128
    rng = np.random.default_rng(3)
    batch = ColumnBatch.from_pydict(TID, schema, {
        "url": [f"u{i}" for i in range(n)],
        "region": [None if i % 7 == 0 else int(rng.integers(0, 500))
                   for i in range(n)],
        "width": [None if i % 13 == 0 else int(rng.integers(0, 900))
                  for i in range(n)],
    })
    config = {"transformers": [
        {"mask_field": {"columns": ["url"], "salt": "k"}},
        {"filter_rows": {"filter": pred}},
    ]}
    host = run_chain(config, batch, fused=False)
    dev = run_chain(config, batch, fused=True)
    batches_equal(host, dev)


def test_empty_batch_through_fused_step():
    batch = make_batch(5).slice(0, 0)
    dev = run_chain(CONFIG, batch, fused=True)
    assert dev.n_rows == 0
    assert dev.schema.find("url").data_type == CanonicalType.UTF8


def test_literal_dtype_eligibility():
    schema = new_table_schema([
        ("i32", "int32"), ("i16", "int16"), ("f32", "float"),
        ("b", "boolean"),
    ])
    # int literal out of int32 range -> host (jnp trace would overflow)
    assert not device_compatible(parse("i32 != 3000000000"), schema)
    # float literal vs int32 column -> host (2^24+1 collapses in f32)
    assert not device_compatible(parse("i32 > 16777216.5"), schema)
    assert not device_compatible(parse("i32 > 2.0"), schema)
    # float literal vs int16 column is exact in f32 -> device ok
    assert device_compatible(parse("i16 > 2.5"), schema)
    # f32 column: literal must round-trip float64 -> float32
    assert device_compatible(parse("f32 < 2.5"), schema)
    assert not device_compatible(parse("f32 < 2.1"), schema)
    # int literal vs f32 column exact below 2^24
    assert device_compatible(parse("f32 < 1000000"), schema)
    assert not device_compatible(parse("f32 < 16777217"), schema)
    # in-range int32 ok; bools only vs boolean columns
    assert device_compatible(parse("i32 >= -2147483648"), schema)
    assert device_compatible(parse("b = TRUE"), schema)
    assert not device_compatible(parse("i32 = TRUE"), schema)
    # and the silent-loss scenario stays host-path but correct:
    batch = ColumnBatch.from_pydict(TID, new_table_schema([
        ("url", "utf8"), ("i32", "int32"),
    ]), {
        "url": ["a", "b", "c"],
        "i32": [16777216, 16777217, 1],
    })
    config = {"transformers": [
        {"mask_field": {"columns": ["url"], "salt": "k"}},
        {"filter_rows": {"filter": "i32 > 16777216.5"}},
    ]}
    host = run_chain(config, batch, fused=False)
    dev = run_chain(config, batch, fused=True)
    batches_equal(host, dev)
    assert dev.column("i32").to_pylist() == [16777217]


def test_always_true_filter_joins_run_as_noop():
    from transferia_tpu.predicate.ast import TrueNode

    assert isinstance(parse(""), TrueNode)
    config = {"transformers": [
        {"mask_field": {"columns": ["url"], "salt": "k"}},
        {"filter_rows": {"filter": ""}},
    ]}
    batch = make_batch(20)
    host = run_chain(config, batch, fused=False)
    dev = run_chain(config, batch, fused=True)
    batches_equal(host, dev)
    assert dev.n_rows == 20


def test_fixed_width_mask_target_not_fused():
    config = {"transformers": [
        {"mask_field": {"columns": ["region"], "salt": "k"}},
    ]}
    steps = build_chain(config).transformers
    set_device_fusion(True)
    try:
        fused = maybe_fuse_steps(steps, TID, SCHEMA)
        assert not any(isinstance(s, DeviceFusedStep) for s in fused)
    finally:
        set_device_fusion(None)
    batch = make_batch(12)
    batches_equal(run_chain(config, batch, fused=False),
                  run_chain(config, batch, fused=True))


def test_pipelined_chunked_dispatch_parity():
    """Chunked double-buffered dispatch (ops/fused._run_pipelined) must be
    byte-identical to the single-launch path, including ragged chunk
    tails and empty keep results."""
    from transferia_tpu.ops.fused import set_chunk_rows

    batch = make_batch(1000)  # 1000 rows, chunk=256 -> 3 full + 1 tail
    host = run_chain(CONFIG, batch, fused=False)
    set_chunk_rows(256)
    try:
        dev = run_chain(CONFIG, batch, fused=True)
    finally:
        set_chunk_rows(None)
    batches_equal(host, dev)


def test_pipelined_chunk_exact_multiple():
    from transferia_tpu.ops.fused import set_chunk_rows

    batch = make_batch(512)
    host = run_chain(CONFIG, batch, fused=False)
    set_chunk_rows(128)
    try:
        dev = run_chain(CONFIG, batch, fused=True)
    finally:
        set_chunk_rows(None)
    batches_equal(host, dev)


FILTER_ONLY = {"transformers": [
    {"filter_rows": {"filter": "region < 400"}},
    {"filter_rows": {"filter": "width >= 390 AND region IS NOT NULL"}},
]}


def test_filter_only_chain_is_a_fused_step():
    set_device_fusion(True)
    try:
        plan = build_chain(FILTER_ONLY).plan_for(TID, SCHEMA)
        assert len(plan.steps) == 1
        step = plan.steps[0]
        assert isinstance(step, DeviceFusedStep)
        assert step.mask_entries == [] and step.pred_cols == [
            "region", "width"]
        # a filter the device cannot take (string, 64-bit) stays a host
        # step, and an always-true one alone is no run at all
        for text in ("url = 'x'", "big > 5", ""):
            plan = build_chain({"transformers": [
                {"filter_rows": {"filter": text}}]}).plan_for(TID, SCHEMA)
            assert not any(isinstance(s, DeviceFusedStep)
                           for s in plan.steps)
    finally:
        set_device_fusion(None)


@pytest.mark.parametrize("n", [300, 20000])   # one device; the mesh
@pytest.mark.parametrize("placement", ["host", "device"])
def test_filter_only_both_strategies_give_the_host_filters_rows(
        n, placement):
    batch = make_batch(n)
    host = run_chain(FILTER_ONLY, batch, fused=False)
    assert 0 < host.n_rows < n
    batches_equal(host, run_chain(FILTER_ONLY, batch, fused=True,
                                  placement=placement))


def test_filter_only_run_is_placed_by_what_auto_measures():
    """The run reaches `_pick_strategy` like any other: host first, then
    the link model's word on the device, counted under placement_*; and
    the model charges a run without a mask no mask compute."""
    from transferia_tpu.stats.trace import TELEMETRY
    from transferia_tpu.transform.fused import (
        DEVICE_MASK_ROWS_PER_S,
        set_placement,
    )

    set_device_fusion(True)
    set_placement("auto")
    try:
        chain = build_chain(FILTER_ONLY)
        step, = chain.plan_for(TID, SCHEMA).steps
        before = TELEMETRY.snapshot()
        batch = make_batch(4096)
        outs = [chain.apply(batch) for _ in range(4)]
        after = TELEMETRY.snapshot()
        reasons = {k: after[k] - before[k] for k in after
                   if k.startswith("placement_") and after[k] != before[k]}
        assert sum(reasons.values()) == 4
        assert reasons["placement_host_first"] == 1
        assert set(reasons) <= {
            "placement_host_first", "placement_link_gated",
            "placement_device_explore", "placement_winner_host",
            "placement_winner_device"}
        rows = (after["filter_rows_host"] - before["filter_rows_host"]
                + after["filter_rows_device"] - before["filter_rows_device"])
        assert rows == 4 * 4096
        host = run_chain(FILTER_ONLY, batch, fused=False)
        for out in outs:
            batches_equal(host, out)
        masked, = build_chain(CONFIG).plan_for(TID, SCHEMA).steps
        n = 131072
        link_only = step._predict_device_ns_row(n, None)
        with_mask = masked._predict_device_ns_row(n, None)
        assert with_mask - link_only > 1e9 / DEVICE_MASK_ROWS_PER_S
        h2d, d2h = step._estimate_link_bytes(n, None)
        assert h2d < 2 * 9 * n and d2h <= n
    finally:
        set_device_fusion(None)
        set_placement(None)
