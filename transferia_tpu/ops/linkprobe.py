"""Host↔device link profiling for cost-based op placement.

A device data plane's profitability depends on the link as much as the
chip: the fused mask+filter program pays two syncs and the bytes it moves
each way, and whether that beats the C++ host path is a property of the
launch cost and the H2D/D2H bandwidth this process actually has.  The
reference has no analogue — its CUDA path assumes a local PCIe GPU — so
this module measures instead of assuming.

probe_link() measures, once per process:
  - launch_overhead_s: wall time of a tiny jitted round trip (median of 3)
  - h2d_bytes_per_s:   device_put of a 4 MiB array
  - d2h_bytes_per_s:   np.asarray of a freshly computed 4 MiB device array
    (a fresh array defeats jax's host-side copy cache)

The result feeds transform/fused.py's placement auto-tuner and
ops/fused.py's chunk sizing.  TRANSFERIA_TPU_LINK="rtt_ms,h2d_mbs,d2h_mbs"
overrides the measurement (tests pin placement decisions with it); on the
CPU backend the "link" is in-process and a constant ideal profile is
returned without measuring.  On an accelerator backend a probe that
raises is an error: there is no made-up profile to fall back to.

`interchange/streams.py` prices the worker↔worker WIRE, not the chip; it
has its own probe and its own env pin (`TRANSFERIA_TPU_STREAM_LINK`).
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass
from typing import Optional

import numpy as np

from transferia_tpu.runtime import knobs


@dataclass(frozen=True)
class LinkProfile:
    backend: str
    launch_overhead_s: float
    h2d_bytes_per_s: float
    d2h_bytes_per_s: float
    measured: bool  # False for env-pinned / in-process constants

    def describe(self) -> str:
        suffix = "" if self.measured else " (pinned)"
        return (
            f"backend={self.backend} launch={self.launch_overhead_s * 1e3:.1f}ms "
            f"h2d={self.h2d_bytes_per_s / 1e6:.0f}MB/s "
            f"d2h={self.d2h_bytes_per_s / 1e6:.0f}MB/s"
            f"{suffix}"
        )


_lock = threading.Lock()
_cached: Optional[LinkProfile] = None

# In-process backends (cpu) move "transfers" at memcpy speed and launch in
# tens of microseconds; measuring would only add test latency.
_INPROCESS = dict(launch_overhead_s=100e-6,
                  h2d_bytes_per_s=8e9, d2h_bytes_per_s=8e9)

_PROBE_BYTES = 4 << 20


def _parse_env(backend: str) -> Optional[LinkProfile]:
    env = knobs.env_raw("TRANSFERIA_TPU_LINK")
    if not env:
        return None
    try:
        rtt_ms, h2d_mbs, d2h_mbs = (float(x) for x in env.split(","))
    except ValueError:
        return None
    # clamp: zero/negative bandwidths would divide-by-zero in the cost
    # model; a pinned "dead link" still has to be a number
    return LinkProfile(backend=backend,
                       launch_overhead_s=max(rtt_ms, 0.0) / 1e3,
                       h2d_bytes_per_s=max(h2d_mbs, 1e-3) * 1e6,
                       d2h_bytes_per_s=max(d2h_mbs, 1e-3) * 1e6,
                       measured=False)


def _measure(backend: str) -> LinkProfile:
    import jax

    # launch overhead: tiny jitted op, enqueue + sync
    tiny = jax.jit(lambda x: x + 1)
    x = jax.device_put(np.zeros(8, np.float32))
    tiny(x).block_until_ready()  # compile outside the timed window
    rtts = []
    for _ in range(3):
        t0 = time.perf_counter()
        tiny(x).block_until_ready()
        rtts.append(time.perf_counter() - t0)
    launch = sorted(rtts)[1]

    buf = np.zeros(_PROBE_BYTES, dtype=np.uint8)
    t0 = time.perf_counter()
    dev = jax.device_put(buf)
    dev.block_until_ready()
    h2d_s = time.perf_counter() - t0

    # a derived array defeats the host-copy cache; subtract the launch
    # overhead so the figure is marginal bandwidth, not latency
    bump = jax.jit(lambda a: a + 1)
    fresh = bump(dev)
    fresh.block_until_ready()
    t0 = time.perf_counter()
    np.asarray(fresh)
    d2h_s = time.perf_counter() - t0
    d2h_s = max(d2h_s - launch, 1e-9)

    return LinkProfile(
        backend=backend,
        launch_overhead_s=launch,
        h2d_bytes_per_s=_PROBE_BYTES / max(h2d_s, 1e-9),
        d2h_bytes_per_s=_PROBE_BYTES / d2h_s,
        measured=True,
    )


def probe_link(force: bool = False) -> LinkProfile:
    """The process-wide link profile (measured once, then cached).
    Raises whatever the backend raised when the probe cannot run."""
    global _cached
    if _cached is not None and not force:
        return _cached
    with _lock:
        if _cached is not None and not force:
            return _cached
        import jax

        backend = jax.default_backend()
        profile = _parse_env(backend)
        if profile is None:
            if backend == "cpu":
                profile = LinkProfile(backend=backend, measured=False,
                                      **_INPROCESS)
            else:
                profile = _measure(backend)
        _cached = profile
        return profile


def reset_link_cache() -> None:
    global _cached
    with _lock:
        _cached = None
