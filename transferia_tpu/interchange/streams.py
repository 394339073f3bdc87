"""Substream-count model for the multi-stream Flight lane.

A single DoPut/DoGet stream is serialization/ack bound long before the
NIC saturates (the Arrow Flight benchmark paper, PAPERS.md): one
stream's framing loop runs on one core, so N concurrent substreams
scale wire throughput until the aggregate link ceiling.  This module
prices that trade the same way `ops/linkprobe.py` prices the
host↔device link — measure once per process, allow an env pin, fall
back to a DEGRADED worst-case profile that re-probes after a bounded
number of reads:

- `probe_stream_link()` measures single-stream Arrow IPC framing
  throughput (the serialization floor a Flight substream rides) and
  models the aggregate ceiling as `stream × headroom`;
- `TRANSFERIA_TPU_STREAM_LINK="setup_ms,stream_mbs,link_mbs"` pins the
  profile (tests pin stream-count decisions with it);
- `auto_substreams(part_bytes, n_batches)` picks the substream count
  that minimizes modeled wall time
  `setup + bytes / min(n·stream_bw, link_bw) + (n-1)·coord`,
  preferring FEWER streams within 5% — stream count autos from part
  bytes and the probed link;
- `TRANSFERIA_TPU_FLIGHT_STREAMS` (≥1) pins the count outright
  (`runtime/knobs.py`); 0/unset means auto.
"""

from __future__ import annotations

import logging
import time
from dataclasses import dataclass
from typing import Optional

from transferia_tpu.runtime import knobs, lockwatch

# hard cap on striping: past 8 substreams the gRPC/framing overhead
# dominates any loopback or NIC we model (the bench curve is 1/2/4/8)
MAX_STREAMS = 8

# parts below this stripe no matter what the model says: substream
# setup would dominate a sub-megabyte part
_MIN_STRIPE_BYTES = 1 << 20

# modeled aggregate ceiling over one stream's serialization rate: how
# many substreams can scale before the wire itself is the bottleneck
_LINK_HEADROOM = 4.0

# per-substream coordination cost as a fraction of the setup cost
# (thread + writer open/close, reassembly bookkeeping)
_COORD_FRACTION = 0.25

_PROBE_BYTES = 4 << 20


@dataclass(frozen=True)
class StreamProfile:
    setup_s: float             # per-substream open/close overhead
    stream_bytes_per_s: float  # one stream's serialization throughput
    link_bytes_per_s: float    # aggregate wire ceiling
    measured: bool             # False for env-pinned constants
    degraded: bool = False     # wedged-probe fallback: re-probed later

    def describe(self) -> str:
        suffix = ""
        if self.degraded:
            suffix = " (degraded)"
        elif not self.measured:
            suffix = " (pinned)"
        return (f"setup={self.setup_s * 1e3:.1f}ms "
                f"stream={self.stream_bytes_per_s / 1e6:.0f}MB/s "
                f"link={self.link_bytes_per_s / 1e6:.0f}MB/s{suffix}")


_lock = lockwatch.named_lock("stream.probe")
_cached: Optional[StreamProfile] = None
_degraded_reads = 0

_REPROBE_DEFAULT = 256


def _reprobe_every() -> int:
    # 0 disables re-probing (same contract as TRANSFERIA_TPU_LINK_REPROBE)
    return max(0, knobs.env_int("TRANSFERIA_TPU_STREAM_REPROBE",
                                _REPROBE_DEFAULT))


def _parse_env() -> Optional[StreamProfile]:
    env = knobs.env_raw("TRANSFERIA_TPU_STREAM_LINK")
    if not env:
        return None
    try:
        setup_ms, stream_mbs, link_mbs = (float(x) for x in env.split(","))
    except ValueError:
        return None
    # clamp: zero/negative bandwidths would divide-by-zero in the model
    return StreamProfile(setup_s=max(setup_ms, 0.0) / 1e3,
                         stream_bytes_per_s=max(stream_mbs, 1e-3) * 1e6,
                         link_bytes_per_s=max(link_mbs, 1e-3) * 1e6,
                         measured=False)


def _measure() -> StreamProfile:
    """Single-stream Arrow IPC framing throughput (the serialization
    floor a Flight substream rides on loopback)."""
    import numpy as np

    from transferia_tpu.interchange._pyarrow import pyarrow

    pa = pyarrow("the substream link probe")
    data = np.arange(_PROBE_BYTES // 8, dtype=np.int64)
    rb = pa.record_batch([pa.array(data)], names=["probe"])

    def one_pass() -> float:
        sink = pa.BufferOutputStream()
        t0 = time.perf_counter()
        with pa.ipc.new_stream(sink, rb.schema) as w:
            w.write_batch(rb)
        return time.perf_counter() - t0

    one_pass()  # warm the allocator outside the timed window
    secs = min(one_pass() for _ in range(3))
    stream_bw = _PROBE_BYTES / max(secs, 1e-9)
    # setup: one empty stream open/close round trip stands in for the
    # per-substream writer negotiation
    t0 = time.perf_counter()
    sink = pa.BufferOutputStream()
    with pa.ipc.new_stream(sink, rb.schema):
        pass
    setup = max(time.perf_counter() - t0, 1e-6)
    return StreamProfile(setup_s=setup,
                         stream_bytes_per_s=stream_bw,
                         link_bytes_per_s=stream_bw * _LINK_HEADROOM,
                         measured=True)


def probe_stream_link(force: bool = False) -> StreamProfile:
    """The process-wide substream profile (measured once, cached).

    A DEGRADED profile (probe failed) re-measures after every
    TRANSFERIA_TPU_STREAM_REPROBE reads (default 256) — a transiently
    wedged allocator must not pin single-stream puts forever.  (This
    prices a network wire; the chip's link, `ops/linkprobe.probe_link`,
    has no such fallback: a failed probe there is an error.)"""
    global _cached, _degraded_reads
    if _cached is not None and not force:
        if not _cached.degraded:
            return _cached
        with _lock:
            cur = _cached
            if cur is not None:
                if cur.degraded:
                    _degraded_reads += 1
                    every = _reprobe_every()
                    if every and _degraded_reads >= every:
                        _degraded_reads = 0
                        try:
                            _cached = _measure()
                        except Exception:
                            # still wedged: keep the worst-case
                            # fallback and retry after another window
                            logging.getLogger(__name__).debug(
                                "stream re-probe failed", exc_info=True)
                return _cached
            # raced with reset_stream_cache: fall through and re-detect
    with _lock:
        if _cached is not None and not force:
            return _cached
        profile = _parse_env()
        if profile is None:
            try:
                profile = _measure()
            except Exception:  # wedged probe: assume worst-case framing
                profile = StreamProfile(setup_s=5e-3,
                                        stream_bytes_per_s=5e7,
                                        link_bytes_per_s=1e8,
                                        measured=False, degraded=True)
        _cached = profile
        return profile


def reset_stream_cache() -> None:
    global _cached, _degraded_reads
    with _lock:
        _cached = None
        _degraded_reads = 0


def pinned_streams() -> int:
    """TRANSFERIA_TPU_FLIGHT_STREAMS ≥ 1 pins the substream count;
    0/unset lets `auto_substreams` price it from the probed link."""
    return max(0, knobs.env_int("TRANSFERIA_TPU_FLIGHT_STREAMS", 0))


def modeled_seconds(n: int, part_bytes: int,
                    profile: Optional[StreamProfile] = None) -> float:
    """Modeled wall time of one part put over n substreams: one setup
    (opens run concurrently), the byte wave at min(n·stream, link)
    bandwidth, and a per-extra-stream coordination term."""
    p = profile or probe_stream_link()
    bw = min(n * p.stream_bytes_per_s, p.link_bytes_per_s)
    return (p.setup_s + part_bytes / max(bw, 1e-3)
            + (n - 1) * p.setup_s * _COORD_FRACTION)


def auto_substreams(part_bytes: int, n_batches: int) -> int:
    """Substream count for one part: the env pin when set, else the
    modeled-time argmin over 1..min(MAX_STREAMS, n_batches), preferring
    fewer streams within 5% (stripe coordination is pure overhead when
    the wire would not have been the bottleneck)."""
    n_batches = max(1, int(n_batches))
    pinned = pinned_streams()
    if pinned:
        return max(1, min(pinned, MAX_STREAMS, n_batches))
    if part_bytes < _MIN_STRIPE_BYTES or n_batches < 2:
        return 1
    profile = probe_stream_link()
    best_n, best_t = 1, modeled_seconds(1, part_bytes, profile)
    for n in range(2, min(MAX_STREAMS, n_batches) + 1):
        t = modeled_seconds(n, part_bytes, profile)
        if t < best_t * 0.95:
            best_n, best_t = n, t
    return best_n
