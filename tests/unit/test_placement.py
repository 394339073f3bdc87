"""Link-aware placement of fused transform steps.

The fused mask+filter step has two byte-identical strategies — the XLA
device program and the host path (predicate pushdown + C++ SHA-NI).  The
auto placement mode measures both on real batches and keeps the winner
(transform/fused.py); the link profile (ops/linkprobe.py) informs device
chunk sizing.  No reference analogue: the reference assumes a local
accelerator; this framework measures the link it has.
"""

import binascii
import os

import numpy as np
import pytest

from tests.unit.test_fused_device import (
    CONFIG,
    TID,
    batches_equal,
    make_batch,
    run_chain,
)
from transferia_tpu.columnar.hexcol import digests_to_hex, hex_to_varwidth
from transferia_tpu.ops import linkprobe
from transferia_tpu.transform import build_chain
from transferia_tpu.transform.fused import (
    DeviceFusedStep,
    set_device_fusion,
    set_placement,
)


@pytest.fixture(autouse=True)
def _reset_placement():
    yield
    set_placement(None)
    set_device_fusion(None)


def test_host_strategy_parity():
    """Pushdown host strategy == plain host chain == device program."""
    batch = make_batch()
    plain = run_chain(CONFIG, batch, fused=False)
    host = run_chain(CONFIG, batch, fused=True, placement="host")
    dev = run_chain(CONFIG, batch, fused=True, placement="device")
    batches_equal(plain, host)
    batches_equal(plain, dev)


def test_auto_measures_both_then_sticks():
    set_device_fusion(True)
    set_placement("auto")
    chain = build_chain(CONFIG)
    plain = run_chain(CONFIG, make_batch(), fused=False)
    for _ in range(4):
        out = chain.apply(make_batch())
        batches_equal(plain, out)
    step = chain.plan_for(TID, make_batch(4).schema).steps[0]
    assert isinstance(step, DeviceFusedStep)
    # both strategies were measured; a winner exists
    assert step._ns_row["host"] > 0
    assert step._ns_row["device"] > 0
    assert step._pick_strategy() in ("host", "device")


def test_auto_reprobes_loser():
    set_device_fusion(True)
    set_placement("auto")
    chain = build_chain(CONFIG)
    step = chain.plan_for(TID, make_batch(4).schema).steps[0]
    # host wins but is slow enough that the link model allows a re-probe
    step._ns_row = {"host": 50_000.0, "device": 90_000.0}
    step._batch_no = DeviceFusedStep.REPROBE_EVERY - 1
    assert step._pick_strategy(4096) == "device"  # loser gets a re-probe
    step._batch_no = 1
    assert step._pick_strategy(4096) == "host"


def test_auto_weighs_a_strategy_by_rows_not_batches():
    """A flush tick's batch of a few rows costs what a batch costs: read
    as ns a row it must not make the strategy that took it the loser."""
    set_device_fusion(True)
    set_placement("auto")
    chain = build_chain(CONFIG)
    step = chain.plan_for(TID, make_batch(4).schema).steps[0]
    step._observe("host", 0.050, 100_000)        # 500 ns a row
    step._observe("device", 0.0, 100_000)        # carries the compile
    step._observe("device", 1.5, 100_000)        # 15,000 ns a row
    assert step._pick_strategy(100_000) == "host"
    step._observe("host", 0.005, 100)            # a tick: 50,000 ns a row
    assert step._ns_row["host"] < 600
    assert step._pick_strategy(100_000) == "host"
    # equal batches weigh as before: 0.7 of the old, 0.3 of the new
    step._observe("host", 0.100, 100_000)
    step._observe("host", 0.100, 100_000)
    assert 600 < step._ns_row["host"] < 1000


def test_auto_gates_device_probe_on_slow_link(monkeypatch):
    from transferia_tpu.ops import linkprobe as lp

    slow = lp.LinkProfile(backend="tpu", launch_overhead_s=0.07,
                          h2d_bytes_per_s=20e6, d2h_bytes_per_s=20e6,
                          measured=True)
    monkeypatch.setattr(lp, "probe_link", lambda force=False: slow)
    set_device_fusion(True)
    set_placement("auto")
    chain = build_chain(CONFIG)
    step = chain.plan_for(TID, make_batch(4).schema).steps[0]
    step._ns_row = {"host": 200.0, "device": -1.0}  # host measured, fast
    # a small batch through a 70ms-launch link: the device probe (which
    # would cost ~1s of p99) must be gated by the prediction
    assert step._pick_strategy(2048) == "host"
    assert step._device_gated
    # the re-probe path stays gated as well
    step._ns_row = {"host": 200.0, "device": 25_000.0}
    step._batch_no = DeviceFusedStep.REPROBE_EVERY - 1
    assert step._pick_strategy(2048) == "host"


def test_host_strategy_masks_only_surviving_rows(monkeypatch):
    """Pushdown: the host hash must run on the post-filter row count."""
    import transferia_tpu.transform.fused as fused_mod

    seen = []
    real = None
    from transferia_tpu.transform.plugins import mask as mask_mod

    real = mask_mod._host_hmac_hex

    def spy(key, data, offsets, validity):
        seen.append(len(offsets) - 1)
        return real(key, data, offsets, validity)

    monkeypatch.setattr(mask_mod, "_host_hmac_hex", spy)
    batch = make_batch(512)
    out = run_chain(CONFIG, batch, fused=True, placement="host")
    assert seen, "host strategy did not reach the native hash"
    assert seen[0] == out.n_rows
    assert out.n_rows < batch.n_rows  # the filter really dropped rows


def test_digests_to_hex_matches_binascii():
    rng = np.random.default_rng(7)
    words = rng.integers(0, 2**32, size=(17, 8), dtype=np.uint64).astype(
        np.uint32)
    out = digests_to_hex(words)
    assert out.shape == (17, 64)
    for i in range(17):
        raw = words[i].astype(">u4").tobytes()
        assert bytes(out[i]) == binascii.hexlify(raw)


def test_hex_to_varwidth_partial_validity_gather():
    hexes = np.arange(4 * 64, dtype=np.uint8).reshape(4, 64) % 16 + 97
    validity = np.array([True, False, True, False])
    data, offsets = hex_to_varwidth(hexes, validity)
    assert offsets.tolist() == [0, 64, 64, 128, 128]
    assert bytes(data[:64]) == bytes(hexes[0])
    assert bytes(data[64:]) == bytes(hexes[2])


def test_linkprobe_env_pin(monkeypatch):
    monkeypatch.setenv("TRANSFERIA_TPU_LINK", "70,1200,20")
    linkprobe.reset_link_cache()
    try:
        prof = linkprobe.probe_link()
        assert not prof.measured
        assert prof.launch_overhead_s == pytest.approx(0.070)
        assert prof.h2d_bytes_per_s == pytest.approx(1.2e9)
        assert prof.d2h_bytes_per_s == pytest.approx(20e6)
        assert "pinned" in prof.describe()
    finally:
        linkprobe.reset_link_cache()


def test_linkprobe_cpu_backend_is_inprocess():
    linkprobe.reset_link_cache()
    prof = linkprobe.probe_link()
    # conftest pins the virtual CPU mesh in unit tests
    assert prof.backend == "cpu"
    assert not prof.measured
    assert prof.launch_overhead_s < 0.001


@pytest.mark.parametrize("launch_ms,expect", [(70.0, 0), (0.2, 32768)])
def test_chunk_sizing_follows_launch_overhead(monkeypatch, launch_ms,
                                              expect):
    from transferia_tpu.ops import fused as ops_fused

    prof = linkprobe.LinkProfile(
        backend="tpu", launch_overhead_s=launch_ms / 1e3,
        h2d_bytes_per_s=1.2e9, d2h_bytes_per_s=20e6, measured=True)
    monkeypatch.setattr(linkprobe, "probe_link", lambda force=False: prof)
    monkeypatch.delenv("TRANSFERIA_TPU_CHUNK_ROWS", raising=False)
    ops_fused.set_chunk_rows(None)
    try:
        assert ops_fused._chunk_rows() == expect
    finally:
        ops_fused.set_chunk_rows(None)


# -- why a batch went where it went (DeviceTelemetry placement_*) -----------

def _placements():
    from transferia_tpu.stats import trace

    tel = trace.TELEMETRY.snapshot()
    return {r: tel[f"placement_{r}"] for r in trace.PLACEMENT_REASONS
            if tel[f"placement_{r}"]}


@pytest.fixture
def fast_link(monkeypatch):
    from transferia_tpu.ops import linkprobe as lp
    from transferia_tpu.stats import trace

    fast = lp.LinkProfile(backend="tpu", launch_overhead_s=1e-7,
                          h2d_bytes_per_s=1e13, d2h_bytes_per_s=1e13,
                          measured=True)
    monkeypatch.setattr(lp, "probe_link", lambda force=False: fast)
    trace.TELEMETRY.reset()
    set_device_fusion(True)
    set_placement("auto")


def test_first_four_batches_of_a_fresh_chain(fast_link):
    chain = build_chain(CONFIG)
    for _ in range(4):
        chain.apply(make_batch())
    got = _placements()
    winner = {k: v for k, v in got.items() if k.startswith("winner_")}
    # host first; the device twice, the first of the two carrying the
    # compile and so not measured; then whoever won
    assert got == {"host_first": 1, "device_explore": 2, **winner}
    assert sum(winner.values()) == 1 and len(winner) == 1


def test_pinned_gated_and_reprobed_batches_are_counted(monkeypatch,
                                                       fast_link):
    from transferia_tpu.ops import linkprobe as lp

    chain = build_chain(CONFIG)
    step = chain.plan_for(TID, make_batch(4).schema).steps[0]
    step._ns_row = {"host": 50_000.0, "device": 90_000.0}
    step._batch_no = DeviceFusedStep.REPROBE_EVERY - 1
    assert step._pick_strategy(4096) == "device"
    slow = lp.LinkProfile(backend="tpu", launch_overhead_s=0.07,
                          h2d_bytes_per_s=20e6, d2h_bytes_per_s=20e6,
                          measured=True)
    monkeypatch.setattr(lp, "probe_link", lambda force=False: slow)
    step._ns_row = {"host": 200.0, "device": -1.0}
    assert step._pick_strategy(2048) == "host"        # never explored
    step._ns_row = {"host": 200.0, "device": 25_000.0}
    assert step._pick_strategy(2048) == "host"        # re-probe refused
    set_placement("device")
    assert step._pick_strategy(2048) == "device"
    assert _placements() == {"reprobe": 1, "link_gated": 2, "pinned": 1}


def test_an_unlisted_reason_is_counted_and_never_raises(fast_link):
    import ast
    import inspect
    import textwrap

    from transferia_tpu.stats import trace

    # every reason _decide can return today is in the tuple ...
    tree = ast.parse(textwrap.dedent(
        inspect.getsource(DeviceFusedStep._decide)))
    reasons = set()
    for ret in (n for n in ast.walk(tree) if isinstance(n, ast.Return)):
        reason = ret.value.elts[1]
        if isinstance(reason, ast.Constant):
            reasons.add(reason.value)
        else:   # f"winner_{winner}"
            reasons |= {"winner_host", "winner_device"}
    assert reasons == set(trace.PLACEMENT_REASONS)
    # ... and one it adds later is counted, not a KeyError in the batch
    trace.TELEMETRY.record_placement("brand_new")
    assert trace.TELEMETRY.snapshot()["placement_brand_new"] == 1


def test_placement_instant_lands_on_the_transform_span(fast_link):
    from transferia_tpu.middlewares.sync import Transformation
    from transferia_tpu.stats import trace

    class Null:
        def push(self, batch):
            pass

        def close(self):
            pass

    trace.reset()
    trace.enable(True)
    try:
        Transformation(Null(), build_chain(CONFIG)).push(make_batch())
        rec = trace.spans()
    finally:
        trace.enable(False)
        trace.reset()
    transform = next(s for s in rec if s[0] == "transform")
    inst = [s for s in rec if s[0] == "placement"]
    assert len(inst) == 1 and inst[0][6] == -1
    assert inst[0][10] == transform[9]        # fired on that span
    assert inst[0][7] == {
        "strategy": "host", "reason": "host_first", "rows": 257,
        "host_ns_row": -1.0, "device_ns_row": -1.0,
        "predicted_device_ns_row": -1.0}


# -- an activation's chains share the first host reading (PlacementBook) --------

def _plain():
    return run_chain(CONFIG, make_batch(), fused=False)


def test_a_second_chain_of_the_activation_explores_with_its_first_batch(
        fast_link):
    from transferia_tpu.transform.fused import PlacementBook

    book, plain = PlacementBook(), _plain()
    first = build_chain(CONFIG, placement_book=book)
    batches_equal(plain, first.apply(make_batch()))
    assert _placements() == {"host_first": 1}
    # a part of one batch: its chain takes the first chain's reading and
    # asks the device at once
    for n in (1, 2):
        later = build_chain(CONFIG, placement_book=book)
        batches_equal(plain, later.apply(make_batch()))
        assert _placements() == {"host_first": 1, "device_explore": n}
        step = later.plan_for(TID, make_batch(4).schema).steps[0]
        read = first.plan_for(TID, make_batch(4).schema).steps[0]
        assert step._ns_row["host"] == read._ns_row["host"] > 0


def test_a_chain_that_starts_with_a_whole_batch_keeps_off_the_book(
        fast_link, monkeypatch):
    from transferia_tpu.transform.fused import PlacementBook

    book = PlacementBook()
    n = make_batch().n_rows
    monkeypatch.setattr(DeviceFusedStep, "SHARED_READING_MAX_ROWS", n - 1)
    for _ in range(2):
        build_chain(CONFIG, placement_book=book).apply(make_batch())
    assert _placements() == {"host_first": 2} and not book._readings
    # the small parts that follow share a reading of their own
    monkeypatch.setattr(DeviceFusedStep, "SHARED_READING_MAX_ROWS", n)
    for _ in range(2):
        build_chain(CONFIG, placement_book=book).apply(make_batch())
    assert _placements() == {"host_first": 3, "device_explore": 1}


def test_chains_without_a_book_measure_the_host_each(fast_link):
    for _ in range(2):
        build_chain(CONFIG).apply(make_batch())
    assert _placements() == {"host_first": 2}


def test_a_planned_chain_that_never_runs_claims_no_reading(fast_link):
    from transferia_tpu.transform.fused import PlacementBook

    book = PlacementBook()
    build_chain(CONFIG, placement_book=book).plan_for(
        TID, make_batch(4).schema)
    assert not book._readings
    build_chain(CONFIG, placement_book=book).apply(make_batch())
    assert _placements() == {"host_first": 1}


def test_a_failed_measuring_batch_leaves_the_others_to_measure(
        fast_link, monkeypatch):
    from transferia_tpu.transform.fused import PlacementBook

    book = PlacementBook()
    first = build_chain(CONFIG, placement_book=book)
    step = first.plan_for(TID, make_batch(4).schema).steps[0]

    def boom(batch):
        raise RuntimeError("host strategy failed")

    monkeypatch.setattr(step, "_apply_host", boom)
    with pytest.raises(RuntimeError):
        first.apply(make_batch())
    (reading,) = book._readings.values()
    assert reading.done.is_set() and reading.ns_row < 0
    build_chain(CONFIG, placement_book=book).apply(make_batch())
    assert _placements() == {"host_first": 2}


def test_a_chain_waits_out_a_measuring_batch_under_way(fast_link,
                                                       monkeypatch):
    import threading

    from transferia_tpu.transform.fused import PlacementBook

    book = PlacementBook()
    first = build_chain(CONFIG, placement_book=book)
    step = first.plan_for(TID, make_batch(4).schema).steps[0]
    entered, release = threading.Event(), threading.Event()
    host = step._apply_host

    def slow_host(batch):
        entered.set()
        assert release.wait(30)
        return host(batch)

    monkeypatch.setattr(step, "_apply_host", slow_host)
    t1 = threading.Thread(target=first.apply, args=(make_batch(),))
    t1.start()
    assert entered.wait(30)
    later = build_chain(CONFIG, placement_book=book)
    t2 = threading.Thread(target=later.apply, args=(make_batch(),))
    t2.start()
    t2.join(0.3)
    assert t2.is_alive() and _placements() == {"host_first": 1}
    release.set()
    t1.join(30)
    t2.join(30)
    assert not t1.is_alive() and not t2.is_alive()
    assert _placements() == {"host_first": 1, "device_explore": 1}


def test_a_snapshots_parts_get_the_activations_book(monkeypatch):
    import inspect

    from tests.unit.test_factories import make_transfer
    from transferia_tpu.factories import sink as sink_factory
    from transferia_tpu.tasks import snapshot
    from transferia_tpu.transform.fused import PlacementBook

    seen = []
    real = sink_factory.build_chain

    def spy(config, stats=None, placement_book=None):
        seen.append(placement_book)
        return real(config, stats, placement_book)

    monkeypatch.setattr(sink_factory, "build_chain", spy)
    transfer, _ = make_transfer("book", transformation=CONFIG)
    book = PlacementBook()
    sink_factory.make_async_sink(transfer, snapshot_stage=True,
                                 placement_book=book).close()
    sink_factory.make_async_sink(transfer, snapshot_stage=True).close()
    assert seen == [book, None]
    assert "placement_book=self._placement_book" in inspect.getsource(
        snapshot.SnapshotLoader._upload_part)
