"""Plan-time fusion of device-able transformer runs into one device step.

The transformer chain plans per (table, schema fingerprint)
(transform/chain.py).  At plan time this pass scans the chosen steps for
maximal runs of device-able transformers — HMAC mask (mask_field) and
row-filter predicates (filter_rows) — and replaces each run with a single
DeviceFusedStep whose apply() does ONE device round-trip per batch
(ops/fused.py), instead of one host pass (or one device launch) per step.
A run of filters alone is such a run too: whether its predicate is
evaluated on the chip or by numpy is `auto`'s to measure, as for every
other run (`_pick_strategy`).

Fusion preconditions (checked against the schema at that chain position):
- mask_field targets only variable-width columns (fixed-width masking
  stringifies per value on the host; that step stays unfused);
- a column is masked at most once per run (a second hash would need the
  first's output — runs split instead);
- filter_rows predicates are device-compatible (predicate/device.py) and
  never reference a column masked EARLIER in the run (the fused predicate
  evaluates on the run's input batch; filter-before-mask is fine because
  the mask+filter outputs commute when the predicate sees pre-mask bytes).

Default: ON when jax imports; kill switch TRANSFERIA_TPU_DEVICE=0 or
set_device_fusion(False).  CPU/TPU parity is pinned by canon tests — the
fused output is byte-identical to the host step-by-step path.
"""

from __future__ import annotations

import logging
import os
import threading
from typing import Optional, Sequence

from transferia_tpu.abstract.schema import (
    CanonicalType,
    TableID,
    TableSchema,
)
from transferia_tpu.runtime import knobs
from transferia_tpu.predicate.ast import TrueNode
from transferia_tpu.columnar.batch import Column, ColumnBatch
from transferia_tpu.stats import trace
from transferia_tpu.stats.trace import TELEMETRY
from transferia_tpu.transform.base import TransformResult, Transformer
from transferia_tpu.transform.plugins.filter import FilterRows
from transferia_tpu.transform.plugins.mask import MaskField

logger = logging.getLogger(__name__)

# The placement model's compute term: rows/s the chip sustains on the
# HMAC mask with data resident (two SHA blocks a row, 1M-row launches).
# Measured on device_kind "TPU v5 lite" (v5e), 2026-07-29, 12.5-14.3M
# rows/s; rounded down.  chip_smoke.py prints the rate it measures
# beside this figure — a different device_kind needs its own.
DEVICE_MASK_ROWS_PER_S = 10e6

_enabled: Optional[bool] = None


def device_fusion_enabled() -> bool:
    global _enabled
    if _enabled is None:
        if knobs.env_str("TRANSFERIA_TPU_DEVICE", "").lower() in (
                "0", "off", "false"):
            _enabled = False
        else:
            try:
                import jax  # noqa: F401 - presence probe only

                _enabled = True
            except ImportError:
                _enabled = False
    return _enabled


def set_device_fusion(on: Optional[bool]) -> None:
    """Force fusion on/off (None = re-detect from env/jax presence)."""
    global _enabled
    _enabled = on


_placement: Optional[str] = None


def placement_mode() -> str:
    """Execution strategy for fused steps: auto | device | host.

    auto (default) measures both strategies on real batches and keeps the
    winner (re-probing the loser periodically); which one wins depends on
    the link this process measured (ops/linkprobe.py) and on how much of
    the batch is dict-encoded.  The device program stays compiled either
    way, and both strategies produce byte-identical output (pinned by
    tests).
    """
    global _placement
    if _placement is None:
        mode = knobs.env_str("TRANSFERIA_TPU_PLACEMENT",
                             "auto").lower()
        _placement = mode if mode in ("auto", "device", "host") else "auto"
    return _placement


def set_placement(mode: Optional[str]) -> None:
    """Force the placement mode (None = re-read the env)."""
    global _placement
    _placement = mode


class _HostReading:
    """One fused step's first host measurement of an activation."""

    __slots__ = ("done", "ns_row", "rows")

    def __init__(self):
        self.done = threading.Event()
        self.ns_row = -1.0      # stays -1 where the measuring batch failed
        self.rows = 0.0


class PlacementBook:
    """What the small parts of one activation have measured of the host
    strategy, by fused step.  A snapshot builds a chain a part and a
    chain's first batch goes to the host to be measured, so a part of
    one batch never asked the device.  A chain whose first batch is small
    (DeviceFusedStep.SHARED_READING_MAX_ROWS: a source hands out whole
    batches first, so it is most likely the part's only one) goes by the
    book: the first such chain of the activation measures the host, every
    other takes that reading for its own and spends its batch on the
    device (link-gated as ever), waiting out the measuring batch where it
    is still under way.  A chain that starts with a whole batch measures
    for itself as it always did."""

    def __init__(self):
        self._lock = threading.Lock()
        self._readings: dict = {}

    def reading(self, key) -> tuple[_HostReading, bool]:
        """(the step's reading, whether the caller is the one to take it)"""
        with self._lock:
            r = self._readings.get(key)
            if r is not None:
                return r, False
            r = self._readings[key] = _HostReading()
            return r, True


class DeviceFusedStep(Transformer):
    """A fused run of mask_field/filter_rows steps, one device launch."""

    TYPE = "device_fused"

    # auto placement: re-probe the losing strategy every this many batches
    REPROBE_EVERY = 256
    # a first batch of up to this many rows (one chunk of the device
    # pipeline on an accelerator, ops/fused.py) goes by the activation's
    # PlacementBook
    SHARED_READING_MAX_ROWS = 32768

    def __init__(self, members: Sequence[Transformer],
                 mask_entries: Sequence[tuple[str, bytes]],
                 pred_node, device_pred=None,
                 decimal_scales: Optional[dict[str, int]] = None,
                 shared: Optional[tuple[PlacementBook, tuple]] = None):
        """pred_node is the predicate as written (the host strategy
        compiles it); device_pred is the same predicate bound to the
        schema for the chip (predicate/exact.py bind_device: day numbers
        and scaled integers), and decimal_scales the scale of every
        DECIMAL column it reads; shared is the activation's
        PlacementBook and this step's key in it."""
        from transferia_tpu.ops.fused import FusedMaskFilterProgram

        self.members = list(members)
        self.mask_entries = list(mask_entries)
        self.pred_node = pred_node
        self.pred_cols = sorted(pred_node.columns()) if pred_node else []
        self.decimal_scales = dict(decimal_scales or {})
        if device_pred is None:
            device_pred = pred_node
        keys = [key for _, key in mask_entries]
        self.program = FusedMaskFilterProgram(keys, device_pred)
        # >1 visible device: also build the mesh-sharded program and
        # route large batches through it (parallel/fusedmesh.py)
        self.sharded_program = None
        self._sharded_min_rows = 0
        if _mesh_devices() > 1:
            from transferia_tpu.parallel.fusedmesh import (
                ShardedFusedProgram,
            )

            self.sharded_program = ShardedFusedProgram(keys, device_pred)
            # below ~1k rows/device the launch+collective overhead wins
            self._sharded_min_rows = 1024 * _mesh_devices()
        # host strategy: vectorized predicate pushed down before the mask
        self._host_pred_fn = None
        if pred_node is not None:
            from transferia_tpu.predicate import compile_mask

            self._host_pred_fn = compile_mask(pred_node)
        # auto-placement state (ns/row EMAs; -1 = not yet measured)
        self._ns_row = {"host": -1.0, "device": -1.0}
        self._ema_rows = {"host": 0.0, "device": 0.0}
        self._batch_no = 0
        self._dev_samples = 0
        self._choice_logged = False
        self._device_gated = False
        # the activation's book: opened with the first batch (a chain
        # that is planned and never applied claims nothing)
        self._shared = shared
        self._owed: Optional[_HostReading] = None

    def suitable(self, table: TableID, schema: TableSchema) -> bool:
        # constructed at plan time from already-suitable members
        return True

    def result_schema(self, schema: TableSchema) -> TableSchema:
        for m in self.members:
            schema = m.result_schema(schema)
        return schema

    def result_table(self, table: TableID) -> TableID:
        for m in self.members:
            table = m.result_table(table)
        return table

    def describe(self) -> str:
        inner = "+".join(m.describe() for m in self.members)
        return f"device[{inner}]"

    def apply(self, batch: ColumnBatch) -> TransformResult:
        if batch.n_rows == 0:
            # keep schema transformation without a device launch
            out = batch
            for m in self.members:
                out = m.apply(out).transformed
            return TransformResult(out)
        try:
            strategy = self._pick_strategy(batch.n_rows, batch)
            if strategy == "host":
                return self._apply_host(batch)
            return self._apply_device(batch)
        finally:
            # a measuring batch that failed leaves the reading empty:
            # the chains waiting for it measure for themselves
            self._publish_host_reading()

    def _take_host_reading(self, book: PlacementBook, key) -> None:
        """The activation's host reading for this step, as this chain's
        own first; or the duty to take it."""
        reading, mine = book.reading(key)
        if mine:
            self._owed = reading
            return
        reading.done.wait()
        if reading.ns_row >= 0:
            self._ns_row["host"] = reading.ns_row
            self._ema_rows["host"] = reading.rows

    def _publish_host_reading(self) -> None:
        reading, self._owed = self._owed, None
        if reading is not None:
            if self._ns_row["host"] >= 0:
                reading.ns_row = self._ns_row["host"]
                reading.rows = self._ema_rows["host"]
            reading.done.set()

    def _estimate_link_bytes(self, n_rows: int, batch=None
                             ) -> tuple[float, float]:
        """(h2d, d2h) bytes the device strategy would move for a batch,
        accounting for the compressed dispatch plane (ops/dispatch.py):
        a dict-encoded masked column whose hexed pool is already
        device-resident costs ZERO link bytes; an unhashed pool costs
        one pool upload (not per-row blocks); encoded predicate columns
        ship their dtype bytes + an n/8 bitmap and return an n/8 keep
        mask.  With encoding off (or no batch to inspect), the raw-wire
        constants apply: ~128 SHA-block bytes/row per masked column in,
        32 digest bytes/row out."""
        from transferia_tpu.ops.dispatch import encoding_enabled

        enc = encoding_enabled()
        # the dict route differs per program: the single-device pool
        # route ships NOTHING per row (codes rebind on host); the mesh
        # dict route ships the int32 codes sharded (4 B/row) plus per-
        # row digest words back, with the pool digest matrix amortized
        # by its memo exactly like the hexed pool
        mesh_route = (self.sharded_program is not None
                      and n_rows >= self._sharded_min_rows)
        h2d = 0.0
        d2h = 0.0
        for name, key in self.mask_entries:
            col = None
            if batch is not None and name in batch.columns:
                col = batch.column(name)
            if enc and col is not None and col.is_lazy_dict:
                pool = col.dict_enc.pool
                if mesh_route:
                    if pool.n_values > 2 * max(n_rows, 1) and \
                            pool.memo_get(("hmac_digest_rows",
                                           bytes(key))) is None:
                        # economics-rejected on the mesh: flat wire
                        h2d += 128.0 * n_rows
                        d2h += 32.0 * n_rows
                        continue
                    if pool.memo_get(("hmac_digest_rows",
                                      bytes(key))) is None:
                        h2d += 128.0 * pool.n_values  # one pool upload
                        d2h += 32.0 * pool.n_values
                    # the memo amortizes the pool HASH, not the wire:
                    # the host digest matrix re-ships with every launch
                    # (it rides the jit args), so charge it per batch
                    h2d += 32.0 * pool.n_values  # replicated digests
                    h2d += 4.0 * n_rows   # sharded codes
                    d2h += 32.0 * n_rows  # gathered digest words back
                    continue
                if pool.memo_get(("hmac_hex", bytes(key))) is not None:
                    continue  # hexed pool already resident: free
                if pool.n_values <= 2 * max(n_rows, 1):
                    # one pool upload (~2 SHA blocks/value) + pool
                    # digests back — amortized across every batch that
                    # shares the pool, but charged to this one
                    h2d += 128.0 * pool.n_values
                    d2h += 32.0 * pool.n_values
                continue  # economics-rejected pools subset-hash on
                # the host inside the device strategy: zero link bytes
            h2d += 128.0 * n_rows
            d2h += 32.0 * n_rows
        if self.pred_node is not None:
            for name in self.pred_cols:
                itemsize = 8
                if name in self.decimal_scales:
                    itemsize = 4    # the scaled values cross as int32
                elif (batch is not None and name in batch.columns
                        and not batch.column(name).is_lazy_dict):
                    itemsize = batch.column(name).data.dtype.itemsize
                h2d += n_rows * itemsize
                h2d += n_rows / 8 if enc else n_rows
            d2h += n_rows / 8 if enc else n_rows  # the keep mask
        return h2d, d2h

    def _predict_device_ns_row(self, n_rows: int, batch=None) -> float:
        """Link-model estimate of the device strategy's cost per row.

        Two syncs (dispatch + collect) pay the launch overhead; the
        bytes-over-link terms come from _estimate_link_bytes, which
        folds the dispatch compression ratio in — so `auto` placement
        judges the ENCODED wire, not the raw one.  The mask's compute
        is charged at DEVICE_MASK_ROWS_PER_S; a run without a mask has
        no such term (the predicate is a few compares a row on data
        already resident: the launch overhead covers it).
        """
        from transferia_tpu.ops.linkprobe import probe_link

        link = probe_link()
        h2d_bytes, d2h_bytes = self._estimate_link_bytes(n_rows, batch)
        s = (2 * link.launch_overhead_s
             + h2d_bytes / link.h2d_bytes_per_s
             + d2h_bytes / link.d2h_bytes_per_s)
        if self.mask_entries:
            s += n_rows / DEVICE_MASK_ROWS_PER_S
        return s * 1e9 / max(n_rows, 1)

    # only probe the device strategy when the link model says it could
    # plausibly win — a probe batch on a link that cannot win lands
    # straight in the p99
    PROBE_HEADROOM = 4.0

    def _pick_strategy(self, n_rows: int = 0, batch=None) -> str:
        strategy, reason, predicted = self._decide(n_rows, batch)
        TELEMETRY.record_placement(reason)
        if trace.enabled():
            trace.instant(
                "placement", strategy=strategy, reason=reason,
                rows=n_rows,
                host_ns_row=round(self._ns_row["host"], 1),
                device_ns_row=round(self._ns_row["device"], 1),
                predicted_device_ns_row=round(predicted, 1))
        return strategy

    def _decide(self, n_rows: int, batch) -> tuple[str, str, float]:
        """(strategy, reason, the link model's ns/row for the device or
        -1 where it was not asked).  The reason is one of
        stats/trace.py PLACEMENT_REASONS."""
        mode = placement_mode()
        if mode in ("device", "host"):
            return mode, "pinned", -1.0
        # auto: measure each strategy once, keep the winner, re-probe the
        # loser every REPROBE_EVERY batches (links drift — see linkprobe)
        if self._shared is not None:
            # the chain's first batch: by the book if it is a small one
            shared, self._shared = self._shared, None
            if n_rows <= self.SHARED_READING_MAX_ROWS:
                self._take_host_reading(*shared)
        host_ns, dev_ns = self._ns_row["host"], self._ns_row["device"]
        if host_ns < 0:
            return "host", "host_first", -1.0
        if dev_ns < 0:
            predicted = self._predict_device_ns_row(max(n_rows, 1), batch)
            if predicted > host_ns * self.PROBE_HEADROOM:
                if not self._device_gated:
                    self._device_gated = True
                    logger.info(
                        "fused step %s placement: host (device gated by "
                        "link model: predicted %.0fns/row vs host "
                        "%.0fns/row)", self.describe(), predicted, host_ns)
                return "host", "link_gated", predicted
            return "device", "device_explore", predicted
        winner = "host" if host_ns <= dev_ns else "device"
        if self._batch_no % self.REPROBE_EVERY == self.REPROBE_EVERY - 1:
            loser = "device" if winner == "host" else "host"
            if loser == "device":
                # the link model gates device re-probes too
                predicted = self._predict_device_ns_row(max(n_rows, 1),
                                                        batch)
                if predicted > host_ns * self.PROBE_HEADROOM:
                    return winner, "link_gated", predicted
                return loser, "reprobe", predicted
            return loser, "reprobe", -1.0
        if not self._choice_logged:
            self._choice_logged = True
            logger.info(
                "fused step %s placement: %s (host=%.0fns/row "
                "device=%.0fns/row)", self.describe(), winner,
                host_ns, dev_ns)
        return winner, f"winner_{winner}", -1.0

    def _observe(self, strategy: str, seconds: float, n_rows: int) -> None:
        self._batch_no += 1
        if strategy == "device":
            self._dev_samples += 1
            if self._dev_samples == 1:
                # the first device batch carries the XLA compile (seconds
                # on TPU) — recording it would poison the EMA and pin the
                # auto-tuner to host on hardware where device wins
                return
        ns = seconds * 1e9 / max(n_rows, 1)
        prev = self._ns_row[strategy]
        if prev < 0:
            self._ns_row[strategy] = ns
            self._ema_rows[strategy] = float(n_rows)
            return
        # a mean over rows, not over batches: a flush tick's batch of a
        # few thousand rows is mostly what a batch costs whatever its
        # size, and read as ns a row it made the strategy that happened
        # to take it lose to the other (PERF.md section 6, PR 27)
        w_prev = 0.7 * (self._ema_rows[strategy] or n_rows)
        w_new = 0.3 * n_rows
        self._ns_row[strategy] = (w_prev * prev + w_new * ns) \
            / max(w_prev + w_new, 1e-9)
        self._ema_rows[strategy] = w_prev + w_new

    def placement_summary(self) -> str:
        """Read-only diagnostics line (no probing side effects)."""
        host_ns, dev_ns = self._ns_row["host"], self._ns_row["device"]
        if host_ns < 0 and dev_ns < 0:
            current = "unmeasured"
        elif dev_ns < 0:
            current = "host"
        elif host_ns < 0:
            current = "device"
        else:
            current = "host" if host_ns <= dev_ns else "device"
        def fmt(v: float) -> str:
            if v >= 0:
                return f"{v:.0f}ns/row"
            return ("gated-by-link-model" if self._device_gated
                    else "unmeasured")

        return (f"placement={current} host={fmt(host_ns)} "
                f"device={fmt(dev_ns)}")

    def _apply_device(self, batch: ColumnBatch) -> TransformResult:
        import time as _time

        from transferia_tpu.ops.dispatch import (
            device_hmac_dict_pool,
            encoding_enabled,
        )
        from transferia_tpu.ops.fused import hex_to_varwidth

        t0 = _time.perf_counter()
        pred_inputs = self._device_pred_inputs(batch)
        if pred_inputs is None:
            # a DECIMAL column's scaled values do not fit the int32 the
            # chip compares in: this batch is the host's
            TELEMETRY.record_filter_host_unsafe()
            return self._apply_host(batch)
        program = self.program
        if (self.sharded_program is not None
                and batch.n_rows >= self._sharded_min_rows):
            program = self.sharded_program
        # device-resident dict masking: a DictEnc column's pool hashes
        # ON DEVICE once per (pool, key) and the batch's row bytes never
        # cross the link — on the single-device program the codes rebind
        # to the hexed pool on the host; on the MESH program the codes
        # shard over the row axis and each device gathers per-row digest
        # words from the replicated pool digest matrix (fusedmesh
        # DictMaskInput) — either way the flat bytes never ship.
        dict_cols: dict[str, Column] = {}
        mask_inputs = []
        flat_entries = []
        flat_states = []
        use_pool_route = encoding_enabled() and program is self.program
        use_mesh_dict = encoding_enabled() and program is not self.program
        for (name, key), states in zip(self.mask_entries,
                                       self.program._states):
            col = batch.column(name)
            if use_pool_route and col.is_lazy_dict:
                hexed = device_hmac_dict_pool(bytes(key),
                                              col.dict_enc.pool,
                                              col.n_rows)
                if hexed is not None:
                    from transferia_tpu.transform.plugins.mask import (
                        dict_hex_column,
                    )

                    dict_cols[name] = dict_hex_column(col, hexed)
                    TELEMETRY.record_mask_route("device_pool",
                                                batch.n_rows)
                    continue
                # pool too large for this batch's economics: hash the
                # referenced SUBSET on host instead of flattening the
                # column into SHA blocks for the wire — the DictEnc
                # column comes straight off the decode plane and stays
                # encoded on the host route too
                from transferia_tpu.transform.plugins.mask import (
                    mask_dict_column,
                )

                dict_cols[name] = mask_dict_column(bytes(key), col)
                TELEMETRY.record_mask_route("host_subset", batch.n_rows)
                continue
            if use_mesh_dict and col.is_lazy_dict:
                from transferia_tpu.parallel.fusedmesh import (
                    dict_mask_input,
                )

                dmi = dict_mask_input(bytes(key), col)
                if dmi is not None:
                    # stays in the program (digests byte-identical to
                    # the flat route), but the OUTPUT keeps the
                    # encoding: the digest-rows memo dict_mask_input
                    # just warmed makes the hexed pool a conversion,
                    # not a re-hash, and the codes rebind to it —
                    # mesh outputs stay dict-encoded end to end
                    # instead of rematerializing rows*64 hex bytes on
                    # the host.  (The input must stay in mask_inputs:
                    # the sharded program zips its key states with
                    # inputs positionally.)
                    mask_inputs.append(dmi)
                    TELEMETRY.record_mask_route("device_pool",
                                                batch.n_rows)

                    hexed = device_hmac_dict_pool(bytes(key),
                                                  col.dict_enc.pool,
                                                  col.n_rows)
                    if hexed is not None:
                        from transferia_tpu.transform.plugins.mask \
                            import dict_hex_column

                        dict_cols[name] = dict_hex_column(col, hexed)
                        flat_entries.append((name, True))
                    else:
                        flat_entries.append((name, False))
                    continue
                # economics-rejected pool: the flat block wire, as the
                # mesh always shipped before the dict route existed
            mask_inputs.append((col.data, col.offsets))
            TELEMETRY.record_mask_route("device_flat", batch.n_rows)
            flat_entries.append((name, False))
            flat_states.append(states)
        if self.pred_node is not None:
            TELEMETRY.record_filter_rows("device", batch.n_rows)
        if mask_inputs or self.pred_node is not None:
            if program is self.program:
                hexes, keep = program.run(
                    mask_inputs, pred_inputs, batch.n_rows,
                    states=flat_states,
                )
            else:
                hexes, keep = program.run(
                    mask_inputs, pred_inputs, batch.n_rows
                )
        else:
            hexes, keep = [], None  # everything rode the pool route
        with trace.span("host_post"):
            cols = dict(batch.columns)
            for (name, preserved), hx in zip(flat_entries, hexes):
                if preserved:
                    continue  # dict_cols carries the rebound column
                validity = batch.column(name).validity
                data, offsets = hex_to_varwidth(hx, validity)
                cols[name] = Column(name, CanonicalType.UTF8, data,
                                    offsets, validity)
            cols.update(dict_cols)
            out = batch.with_columns(cols,
                                     self.result_schema(batch.schema))
            if keep is not None and not keep.all():
                out = out.filter(keep)
        self._observe("device", _time.perf_counter() - t0, batch.n_rows)
        return TransformResult(out)

    def _device_pred_inputs(self, batch: ColumnBatch) -> Optional[dict]:
        """name -> (fixed-width data, validity) of the predicate's
        columns as the device program compares them; None where a
        DECIMAL column of this batch has no exact int32 form."""
        from transferia_tpu.ops.dispatch import narrow_pred_i32
        from transferia_tpu.predicate.exact import decimal_scaled

        inputs = {}
        for name in self.pred_cols:
            col = batch.column(name)
            data = col.data
            if name in self.decimal_scales:
                scaled, _ = decimal_scaled(col, self.decimal_scales[name])
                data = narrow_pred_i32(scaled)
                if data is None:
                    return None
            inputs[name] = (data, col.validity)
        return inputs

    def _apply_host(self, batch: ColumnBatch) -> TransformResult:
        """Host strategy with predicate pushdown.

        The fusion preconditions guarantee the predicate never reads a
        column masked in this run, so filtering FIRST and hashing only the
        surviving rows is byte-equivalent to the device program (which
        hashes every row, then compacts) — it just skips the wasted
        hashes.  The hash itself is the batched C++ SHA-NI path
        (native/hostops.cpp), GIL-released so part threads overlap.
        """
        import time as _time

        from transferia_tpu.transform.plugins.mask import (
            _host_hmac_hex,
            mask_dict_column,
        )

        t0 = _time.perf_counter()
        cur = batch
        if self._host_pred_fn is not None:
            keep = self._host_pred_fn(batch)
            TELEMETRY.record_filter_rows("host", batch.n_rows)
            if not keep.all():
                cur = batch.filter(keep)
        with trace.span("host_mask"):
            cols = dict(cur.columns)
            for name, key in self.mask_entries:
                col = cur.column(name)
                if col.is_lazy_dict:
                    # O(unique) hash: pool once (or the referenced
                    # subset when the pool dwarfs the batch), codes stay
                    cols[name] = mask_dict_column(key, col)
                    continue
                data, offsets = _host_hmac_hex(
                    key, col.data, col.offsets, col.validity)
                cols[name] = Column(name, CanonicalType.UTF8, data,
                                    offsets, col.validity)
            out = cur.with_columns(cols,
                                   self.result_schema(batch.schema))
        self._observe("host", _time.perf_counter() - t0, batch.n_rows)
        return TransformResult(out)


def _mesh_devices() -> int:
    """Visible jax device count.  A backend that cannot initialize
    raises here, at plan time — it is not a zero-device host."""
    import jax

    return len(jax.devices())


def _mask_target_cols(step: MaskField, schema: TableSchema) -> list[str]:
    return [c for c in step.columns if schema.find(c) is not None]


def maybe_fuse_steps(steps: Sequence[Transformer], in_table: TableID,
                     in_schema: TableSchema,
                     placement_book: Optional[PlacementBook] = None
                     ) -> list[Transformer]:
    """Replace device-able runs with DeviceFusedSteps (plan-time)."""
    if not device_fusion_enabled() or not steps:
        return list(steps)
    from transferia_tpu.predicate.device import device_compatible
    from transferia_tpu.predicate.exact import bind_device, column_scale

    out: list[Transformer] = []
    schema = in_schema
    i = 0
    n = len(steps)
    while i < n:
        # try to grow a fusable run starting at i
        group: list[Transformer] = []
        mask_entries: list[tuple[str, bytes]] = []
        pred_parts = []
        device_parts = []
        decimal_scales: dict[str, int] = {}
        masked: set[str] = set()
        run_schema = schema
        j = i
        while j < n:
            st = steps[j]
            if isinstance(st, MaskField):
                targets = _mask_target_cols(st, run_schema)
                if (not targets
                        or any(c in masked for c in targets)
                        or any(not run_schema.find(c)
                               .data_type.is_variable_width
                               for c in targets)):
                    break
                for c in targets:
                    mask_entries.append((c, st.key))
                masked.update(targets)
            elif isinstance(st, FilterRows):
                if (not device_compatible(st.node, run_schema)
                        or (st.node.columns() & masked)):
                    break
                if not isinstance(st.node, TrueNode):
                    # an always-true filter joins the run as a no-op
                    pred_parts.append(st.node)
                    device_parts.append(bind_device(st.node, run_schema))
                    for c in st.node.columns():
                        cs = run_schema.find(c)
                        if cs.data_type == CanonicalType.DECIMAL:
                            decimal_scales[c] = column_scale(cs)
            else:
                break
            group.append(st)
            run_schema = st.result_schema(run_schema)
            j += 1
        if mask_entries or pred_parts:
            # whether the run pays for its launches is measured per
            # batch (DeviceFusedStep._pick_strategy), masks or none
            pred_node = device_pred = None
            if pred_parts:
                from transferia_tpu.predicate.ast import And

                pred_node = (pred_parts[0] if len(pred_parts) == 1
                             else And(tuple(pred_parts)))
                device_pred = (device_parts[0] if len(device_parts) == 1
                               else And(tuple(device_parts)))
            from transferia_tpu.runtime.backend import log_backend_once

            # a worker that came up on the CPU platform says so here,
            # before its first "device" step runs on XLA-CPU
            log_backend_once()
            shared = None
            if placement_book is not None:
                shared = (placement_book,
                          (in_table, in_schema.fingerprint(), i))
            fused = DeviceFusedStep(group, mask_entries, pred_node,
                                    device_pred, decimal_scales, shared)
            logger.info("fused %d transformer steps onto device: %s",
                        len(group), fused.describe())
            out.append(fused)
            schema = run_schema
            i = j
        else:
            out.append(steps[i])
            schema = steps[i].result_schema(schema)
            i += 1
    return out
