"""User-function transformer (reference: registry/lambda cloud-function rows
transform + registry/custom).

TPU-first twist: the user function operates on the *columnar* view — a dict
of numpy/jax arrays — and may be a jax.jit-compiled function (the
BASELINE.json "lambda-transformer as user jax.jit" config).  Three forms:

  fn(columns: dict[str, array]) -> dict[str, array]     # replace columns
  fn(columns) -> bool mask                              # row filter
  fn(batch: ColumnBatch) -> ColumnBatch                 # full control

Registered callables are referenced by dotted path or passed directly via
`register_lambda`.

Two schedule-level protections make user jit functions safe in streaming
replication (where batch sizes are ragged and every poll pays the
host↔device link — see ops/linkprobe.py):

  - shape bucketing (columns/mask modes): inputs pad to the next
    power-of-2 row count before the call and outputs slice back, so a
    jitted fn compiles O(log n) times instead of once per distinct batch
    size.  Rows are the contract unit (the reference's lambda transform
    is a per-row cloud function), so elementwise semantics hold and the
    padded tail is discarded.  Opt out with bucket: false for
    full-array fns (reductions over the row axis).
  - link-aware placement (same policy as the fused mask/filter step):
    the fn runs on the host CPU backend or the accelerator, whichever
    measures faster per row, with the accelerator probe gated by the
    link model so a link that cannot win never eats a probe batch.
    TRANSFERIA_TPU_PLACEMENT=device|host pins it.
"""

from __future__ import annotations

import importlib
import logging
import threading
import time
from typing import Any, Callable, Optional

import numpy as np

from transferia_tpu.abstract.schema import TableID, TableSchema
from transferia_tpu.columnar.batch import Column, ColumnBatch
from transferia_tpu.transform.base import TransformResult, Transformer
from transferia_tpu.transform.registry import register_transformer

logger = logging.getLogger(__name__)

_LAMBDAS: dict[str, Callable] = {}


def register_lambda(name: str, fn: Callable) -> None:
    """Register a named user function for lambda_transformer configs."""
    _LAMBDAS[name] = fn


def _resolve(ref: str) -> Callable:
    if ref in _LAMBDAS:
        return _LAMBDAS[ref]
    if ":" in ref:
        mod, attr = ref.split(":", 1)
        return getattr(importlib.import_module(mod), attr)
    raise KeyError(
        f"unknown lambda {ref!r}; register via register_lambda or use "
        f"'module:function' form"
    )


@register_transformer("lambda")
class LambdaTransformer(Transformer):
    """config: function: "name" | "module:attr"; mode: columns|mask|batch;
    tables: optional include list."""

    # placement probing (mirrors transform/fused.py DeviceFusedStep)
    REPROBE_EVERY = 256
    PROBE_HEADROOM = 4.0
    BUCKET_MIN = 256

    def __init__(self, function: str | Callable, mode: str = "columns",
                 tables: Optional[list[str]] = None,
                 bucket: bool = True):
        # resolution is lazy for dotted paths: transfer configs must
        # validate on machines where the user module isn't importable
        # (e.g. `trtpu validate` on a control host) — but the value's TYPE
        # is still checked eagerly so validate catches nulls/maps
        if not callable(function) and not isinstance(function, str):
            raise ValueError(
                f"lambda: function must be a callable or a "
                f"'module:attr' string, got {type(function).__name__}"
            )
        self._fn = function if callable(function) else None
        self._ref = function if isinstance(function, str) else None
        if mode not in ("columns", "mask", "batch"):
            raise ValueError(f"lambda: bad mode {mode!r}")
        self.mode = mode
        self.fn_name = function if isinstance(function, str) else \
            getattr(function, "__name__", "callable")
        self.tables = [TableID.parse(t) for t in tables] if tables else None
        self.bucket = bool(bucket)
        self._ns_row = {"host": -1.0, "device": -1.0}
        # first call per strategy pays the jit compile: warm, don't score
        self._warmed = {"host": False, "device": False}
        self._batch_no = 0
        self._choice_logged = False
        self._device_gated = False
        # sink workers push concurrently through the same transformer;
        # guard the placement state (an unguarded race can score a
        # compile-laden call and poison the EWMA for good)
        self._state_lock = threading.Lock()

    @property
    def fn(self) -> Callable:
        if self._fn is None:
            self._fn = _resolve(self._ref)
        return self._fn

    def suitable(self, table: TableID, schema: TableSchema) -> bool:
        if self.tables is None:
            return True
        return any(table.include_matches(p) for p in self.tables)

    # -- placement + bucketing ------------------------------------------------
    def _predict_device_ns_row(self, n_rows: int, in_bytes: int) -> float:
        """Link-model estimate: two syncs plus moving the input columns
        over and a similar volume back; compute is charged at the
        fused step's DEVICE_MASK_ROWS_PER_S (a v5e figure — a user fn
        is usually cheaper than the HMAC it was measured on)."""
        from transferia_tpu.ops.linkprobe import probe_link
        from transferia_tpu.transform.fused import DEVICE_MASK_ROWS_PER_S

        link = probe_link()
        s = (2 * link.launch_overhead_s
             + in_bytes / link.h2d_bytes_per_s
             + in_bytes / link.d2h_bytes_per_s
             + n_rows / DEVICE_MASK_ROWS_PER_S)
        return s * 1e9 / max(n_rows, 1)

    def _pick_strategy(self, n_rows: int, in_bytes: int) -> str:
        from transferia_tpu.transform.fused import placement_mode

        mode = placement_mode()
        if mode in ("device", "host"):
            return mode
        host_ns, dev_ns = self._ns_row["host"], self._ns_row["device"]
        if host_ns < 0:
            return "host"  # includes the unscored warm-up call
        if dev_ns < 0:
            predicted = self._predict_device_ns_row(n_rows, in_bytes)
            if predicted > host_ns * self.PROBE_HEADROOM:
                if not self._device_gated:
                    self._device_gated = True
                    logger.info(
                        "lambda %s placement: host (device gated by link "
                        "model: predicted %.0fns/row vs host %.0fns/row)",
                        self.fn_name, predicted, host_ns)
                return "host"
            return "device"
        winner = "host" if host_ns <= dev_ns else "device"
        if self._batch_no % self.REPROBE_EVERY == self.REPROBE_EVERY - 1:
            loser = "device" if winner == "host" else "host"
            if loser == "device":
                predicted = self._predict_device_ns_row(n_rows, in_bytes)
                if predicted > host_ns * self.PROBE_HEADROOM:
                    return winner
            return loser
        if not self._choice_logged:
            self._choice_logged = True
            logger.info("lambda %s placement: %s (host %.0fns/row, "
                        "device %.0fns/row)", self.fn_name, winner,
                        host_ns, dev_ns)
        return winner

    def _call_fn(self, arrays: dict, n_rows: int):
        """Run the user fn with shape bucketing and measured placement."""
        run_arrays = arrays
        if self.bucket and n_rows > 0:
            m = self.BUCKET_MIN
            while m < n_rows:
                m <<= 1
            if m != n_rows:
                if not getattr(self, "_bucket_logged", False):
                    self._bucket_logged = True
                    logger.info(
                        "lambda %s: shape bucketing active (inputs pad "
                        "to power-of-2 rows; per-ROW fns only — a fn "
                        "computing across the row axis must set "
                        "bucket: false)", self.fn_name)
                pad = m - n_rows
                run_arrays = {
                    k: np.concatenate([v, np.zeros(pad, v.dtype)])
                    for k, v in arrays.items()
                }
        in_bytes = sum(v.nbytes for v in run_arrays.values())
        with self._state_lock:
            strategy = self._pick_strategy(n_rows, in_bytes)
            self._batch_no += 1
            # claim the warm-up slot atomically: exactly one concurrent
            # call absorbs the compile unscored
            warming = not self._warmed[strategy]
            if warming:
                self._warmed[strategy] = True
        t0 = time.perf_counter()
        if strategy == "host":
            try:
                import jax

                cpu = jax.devices("cpu")[0]
            except Exception:
                cpu = None
            if cpu is not None:
                import jax

                with jax.default_device(cpu):
                    out = self.fn(run_arrays)
            else:
                out = self.fn(run_arrays)
        else:
            out = self.fn(run_arrays)
        # materialize (forces any device work to finish) then unslice
        if isinstance(out, dict):
            out = {k: np.asarray(v)[:n_rows] for k, v in out.items()}
        else:
            out = np.asarray(out)[:n_rows]
        ns_row = (time.perf_counter() - t0) * 1e9 / max(n_rows, 1)
        if not warming:
            with self._state_lock:
                prev = self._ns_row[strategy]
                self._ns_row[strategy] = (ns_row if prev < 0
                                          else 0.7 * prev + 0.3 * ns_row)
        return out

    def apply(self, batch: ColumnBatch) -> TransformResult:
        if self.mode == "batch":
            return TransformResult(self.fn(batch))
        arrays = {
            name: col.data for name, col in batch.columns.items()
            if col.offsets is None and col.data is not None
        }
        if self.mode == "mask":
            mask = np.asarray(
                self._call_fn(arrays, batch.n_rows)).astype(np.bool_)
            return TransformResult(batch.filter(mask))
        out = self._call_fn(arrays, batch.n_rows)
        cols = dict(batch.columns)
        for name, arr in out.items():
            arr = np.asarray(arr)
            old = cols.get(name)
            ctype = old.ctype if (old is not None
                                  and old.data is not None
                                  and arr.dtype == old.data.dtype) \
                else _infer_ctype(arr)
            cols[name] = Column(
                name, ctype, arr, None,
                old.validity if old is not None and old.offsets is None
                else None,
            )
        schema = batch.schema.with_types({
            name: cols[name].ctype for name in out if name in cols
        })
        return TransformResult(batch.with_columns(cols, schema))

    def describe(self) -> str:
        return f"lambda({self.fn_name})"


def _infer_ctype(arr: np.ndarray):
    from transferia_tpu.abstract.schema import CanonicalType

    mapping = {
        "int8": CanonicalType.INT8, "int16": CanonicalType.INT16,
        "int32": CanonicalType.INT32, "int64": CanonicalType.INT64,
        "uint8": CanonicalType.UINT8, "uint16": CanonicalType.UINT16,
        "uint32": CanonicalType.UINT32, "uint64": CanonicalType.UINT64,
        "float32": CanonicalType.FLOAT, "float64": CanonicalType.DOUBLE,
        "bool": CanonicalType.BOOLEAN,
    }
    key = str(arr.dtype)
    if key not in mapping:
        raise ValueError(f"lambda produced unsupported dtype {arr.dtype}")
    return mapping[key]
