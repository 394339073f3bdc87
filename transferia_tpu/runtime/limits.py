"""Worker resource-limit management (reference: pkg/runtime/shared/
limits.go — derive the RAM budget from the cgroup and keep the runtime
under it; Go uses debug.SetMemoryLimit/SetGCPercent, here the equivalent
levers are gc pressure + a watchdog that reacts before the OOM killer).

apply_resource_limits() is called by the CLI at worker startup:
- reads the cgroup (v2 memory.max / v1 limit_in_bytes) or an explicit
  limit;
- starts a watchdog thread that samples RSS; above the soft fraction it
  forces a full gc.collect() and logs; above the hard fraction it calls
  the on_pressure callback (default: log loudly — sinks' bufferers also
  see memory pressure through the memthrottle middleware).

apply_allocator_policy() is called beside it, once a process: the
allocators do not go to the kernel for each object.
"""

from __future__ import annotations

import ctypes
import gc
import logging
import os
import sys
import threading
from typing import Callable, Optional

logger = logging.getLogger(__name__)


def effective_cpus() -> float:
    """Cores this process can actually use (affinity ∩ cgroup quota).

    The sizing input for host-parallel work: the fs provider's
    column-parallel decode / readahead auto-knobs derive from it, so a
    1-core CI box degrades to serial behavior instead of thrashing."""
    try:
        n = float(len(os.sched_getaffinity(0)))
    except (AttributeError, OSError):
        n = float(os.cpu_count() or 1)
    try:  # cgroup v2: "max 100000" or "<quota> <period>"
        with open("/sys/fs/cgroup/cpu.max") as fh:
            quota_s, period_s = fh.read().split()
        if quota_s != "max":
            n = min(n, int(quota_s) / int(period_s))
    except (OSError, ValueError):
        pass
    return round(n, 2)


def cgroup_memory_limit() -> Optional[int]:
    """Container memory limit in bytes, None when unlimited/unknown."""
    for path in ("/sys/fs/cgroup/memory.max",
                 "/sys/fs/cgroup/memory/memory.limit_in_bytes"):
        try:
            with open(path) as fh:
                raw = fh.read().strip()
        except OSError:
            continue
        if raw in ("max", ""):
            return None
        try:
            limit = int(raw)
        except ValueError:
            continue
        # v1 reports a huge number when unlimited
        if limit >= 1 << 60:
            return None
        return limit
    return None


def process_rss() -> int:
    """Resident set size in bytes (/proc self)."""
    try:
        with open("/proc/self/statm") as fh:
            pages = int(fh.read().split()[1])
        return pages * os.sysconf("SC_PAGE_SIZE")
    except (OSError, IndexError, ValueError):
        return 0


class MemoryWatchdog:
    def __init__(self, limit_bytes: int,
                 soft_fraction: float = 0.8,
                 hard_fraction: float = 0.95,
                 interval: float = 5.0,
                 on_pressure: Optional[Callable[[int, int], None]] = None,
                 rss_fn: Callable[[], int] = process_rss):
        self.limit = limit_bytes
        self.soft = int(limit_bytes * soft_fraction)
        self.hard = int(limit_bytes * hard_fraction)
        self.interval = interval
        self.on_pressure = on_pressure
        self.rss_fn = rss_fn
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.soft_hits = 0
        self.hard_hits = 0

    def check_once(self) -> str:
        """One sample; returns 'ok' | 'soft' | 'hard' (tests call this)."""
        rss = self.rss_fn()
        if rss >= self.hard:
            self.hard_hits += 1
            logger.error(
                "memory watchdog: rss %dMiB >= %d%% of the %dMiB limit",
                rss >> 20, int(100 * self.hard / self.limit),
                self.limit >> 20)
            gc.collect()
            if self.on_pressure is not None:
                self.on_pressure(rss, self.limit)
            return "hard"
        if rss >= self.soft:
            self.soft_hits += 1
            logger.warning(
                "memory watchdog: rss %dMiB above soft threshold "
                "(%dMiB of %dMiB)", rss >> 20, self.soft >> 20,
                self.limit >> 20)
            gc.collect()
            return "soft"
        return "ok"

    def start(self) -> "MemoryWatchdog":
        def loop():
            while not self._stop.wait(self.interval):
                self.check_once()

        self._thread = threading.Thread(target=loop, daemon=True,
                                        name="memory-watchdog")
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()


def apply_resource_limits(limit_bytes: Optional[int] = None,
                          on_pressure: Optional[Callable] = None
                          ) -> Optional[MemoryWatchdog]:
    """Start the watchdog from an explicit or cgroup-derived limit.
    Returns None (and does nothing) when no limit is discoverable —
    bare-metal runs stay unmanaged, like the reference outside k8s."""
    limit = limit_bytes if limit_bytes is not None \
        else cgroup_memory_limit()
    if not limit:
        logger.info("no memory limit discovered; watchdog disabled")
        return None
    # tame the allocator a bit under a limit, like SetGCPercent
    gc.set_threshold(400, 10, 10)
    wd = MemoryWatchdog(limit, on_pressure=on_pressure).start()
    logger.info("memory watchdog armed at %dMiB (cgroup)", limit >> 20)
    return wd


# glibc's mallopt parameters (malloc.h) at the values PR 36 measured on
# the chip's host (PERF.md section 6, "PR 36": the nine readings).  A
# thread other than the first gets an arena whose heap glibc grows a
# page at a time, one mprotect each, and gives back the same way: with
# these a heap is taken whole, what is freed is kept, and a buffer up to
# half a heap comes from the heap (setting any of the three stops
# glibc's own raising of the mmap threshold from 128 KiB, so it is set
# to the most that raising reaches, DEFAULT_MMAP_THRESHOLD_MAX).
_MALLOPT = (
    ("M_TOP_PAD", -2, 64 << 20),
    ("M_TRIM_THRESHOLD", -1, 1 << 30),
    ("M_MMAP_THRESHOLD", -3, 32 << 20),
)
_allocator_policy_applied = False


def apply_allocator_policy() -> None:
    """One allocator policy a process, set before any part thread
    exists: on Linux with glibc the three `mallopt` settings above.
    The first call applies it and later calls do nothing (the setting
    is the process's for life); where `mallopt` is missing (musl,
    macOS) no call does anything.  A setting glibc refuses (return 0)
    is logged once as a warning and never raised."""
    global _allocator_policy_applied
    if _allocator_policy_applied:
        return
    _allocator_policy_applied = True
    if not sys.platform.startswith("linux"):
        return
    try:
        mallopt = ctypes.CDLL(None).mallopt
    except (OSError, AttributeError):
        return
    for name, param, value in _MALLOPT:
        if mallopt(param, value) == 0:
            logger.warning("allocator policy: mallopt(%s, %d) was "
                           "refused; the setting stays glibc's default",
                           name, value)
