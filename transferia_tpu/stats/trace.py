"""End-to-end pipeline tracing: spans, device telemetry, Perfetto export.

The sampling profiler (stats/profiler.py) answers "which frame burns
CPU".  It does not show the *timeline*: whether device waits overlap
host packing, where a batch stalls between parsequeue and the sink, or
when an XLA recompile lands inside the measured window.  This module
records begin/end spans into a bounded ring buffer and exports Chrome
trace-event JSON loadable in Perfetto / `chrome://tracing` — the
span-level attribution Thallus-style transport analysis needs
(PAPERS.md) and the per-stage transfer accounting the Arrow Flight
benchmarking work shows wire-speed columnar systems live or die on.
It is the one recorder of stage time: `stage_summary()` is the per-stage
wall breakdown `trtpu trace` prints.

Design constraints:

- near-zero overhead when disabled: `span()` does ONE module-bool check
  and returns a shared no-op singleton — no allocation, no lock;
- thread-safe when enabled: per-thread span stacks (nesting + self-time
  attribution need no lock), one lock only around ring appends;
- monotonic clocks (`time.perf_counter`), microsecond timestamps
  relative to the capture epoch (what the trace-event format expects);
  beside it the thread's CPU clock (`time.thread_time`), so a span says
  how much of its self time its thread was on a core - the rest it
  waited: for the GIL, a lock, a socket, a core (enabled path only);
- bounded memory: a `deque(maxlen=capacity)` ring — a forgotten-enabled
  tracer on a long replication run costs a fixed buffer, never OOM.

Span taxonomy (see ARCHITECTURE.md "Tracing & device telemetry"):
roots `part` / `batch` / `replication_attempt` carry identity args
(transfer_id, table, part, batch_seq); stage spans `source_decode`,
`pivot`, `pack`, `device_dispatch`, `device_wait`, `host_post`,
`transform`, `serialize`, `bufferer_flush`, `sink_push`, `sink` nest
under them.  `device_dispatch`/`device_wait` carry byte counts as args.
`file_read` is the read of one block of a text object (args `bytes`,
`path`) and `source_decode` with `format="jsonl"` its decode (args `rows`,
`bytes`, `path` `"block"` or `"row"`), both in
providers/s3readers.py::read_json_lines.
`decode_readahead` spans live on the prefetcher worker threads
(providers/readahead.py) — decode running there shows as its own
track, overlapping the part's downstream spans.  Waits that are known
only once they end (`queue_wait`, `decode_wait`) are recorded with
`complete()`, which takes self time from no parent.  Where a thread
blocks, the wait is a real span, so that it takes its time out of its
parent and names the idle gap it covers: `sink_wait` (the parsequeue's
stages on the sink), `inflight_wait` (`ParseQueue.add` with
`max_inflight` units unacked: the poll thread), and in
tasks/snapshot.py the part thread's `part_open`, `push_backpressure`
(under `batch`), `part_drain`, `part_close` (`phase` `done`, and
`close` after `part` has ended), `part_commit` under `part`, and the
worker thread's `part_claim` and `part_report` between parts; the file
source's pushed-down predicate is `scan_filter` (providers/file.py):
what is left of `part` and `batch` self time is the source iterator's
own work.

Recorded tuple: (name, tid, tname, t0_s, dur_s, self_s, depth, args,
trace_id, span_id, parent_id, self_cpu_s); consumers index it or slice
it, so a field is only ever added at the end.

While tracing is on every span also enters a
`jax.profiler.TraceAnnotation` of the same name, so a `jax.profiler`
trace taken at the same time holds the program's spans in its host
plane, on the threads that ran them and on the device events' clock.

`DeviceTelemetry` is the always-on counter half: H2D/D2H bytes and
transfer counts, device launches, XLA compile events (hooked via jax's
monitoring events — fired exactly on jit-cache misses that reach the
backend compiler; persistent-cache loads are told apart from real
compiles), the host's wait for device results, and `auto` placement's
decisions.  It folds into the
prometheus `Metrics` facade via `fold_into()` (stats/registry.py
DeviceStats).

Causality (PR 10): every recorded span carries (trace_id, span_id,
parent_id).  The active span context rides a `contextvars.ContextVar`,
so nesting links parent→child automatically on one thread, and the
capture/adopt pair carries it across thread hops (readahead workers,
upload-part pool, fleet worker slots) and — via `wire_format` /
`parse_wire` — across the Flight gRPC metadata and the shm framing
metadata.  The Chrome export emits flow events for every parent link
that crosses a thread, so one transfer renders as a single
causally-linked timeline in Perfetto even when its spans live on six
threads.
"""

from __future__ import annotations

import contextvars
import itertools
import json
import os
import threading
import time
import weakref
from collections import deque
from typing import NamedTuple, Optional

DEFAULT_CAPACITY = 200_000  # spans; ~100 bytes each -> bounded ~20MB

_enabled = False
_epoch = 0.0
_lock = threading.Lock()
_ring: deque = deque(maxlen=DEFAULT_CAPACITY)
_tls = threading.local()
# lifetime count of ring appends (NOT ring length: the deque evicts).
# The obs-segment exporter (stats/fleetobs.py) uses this as its delta
# mark — "how many new records since my last export" — without the
# record tuples themselves needing sequence fields.
_recorded = 0
# jax.profiler.TraceAnnotation, looked up once by enable(); None without
# jax.  Entered by every Span beside its own clock (enabled path only).
_annotation = None
_annotation_tried = False
# CLOCK_THREAD_CPUTIME_ID; a platform without it records None
_HAS_THREAD_TIME = hasattr(time, "thread_time")


class SpanContext(NamedTuple):
    """The propagation token: which trace, which span is 'current'.

    Immutable and tiny on purpose — it crosses thread boundaries by
    value and the wire as `"<trace_id>:<span_id>"`."""

    trace_id: int
    span_id: int


# span/trace ids are process-unique counters salted by (host, pid) so
# ids minted on both ends of an in-host wire (Flight loopback, shm
# handoff between forked workers) — or by two pid-1 containers on
# DIFFERENT hosts feeding one merged fleet timeline
# (stats/fleetobs.py) — never collide in one merged view
import socket as _socket
import zlib as _zlib

_ids = itertools.count(
    ((_zlib.crc32(_socket.gethostname().encode()) & 0xFFFF) << 48)
    + ((os.getpid() & 0xFFFF) << 32) + 1)
_ctx: "contextvars.ContextVar[Optional[SpanContext]]" = \
    contextvars.ContextVar("trtpu_trace_ctx", default=None)


class _NoopSpan:
    """Shared disabled-path singleton: falsy, allocation-free."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def __bool__(self):
        return False

    def add(self, **args) -> None:
        pass

    def context(self) -> Optional[SpanContext]:
        return None


_NOOP = _NoopSpan()


class Span:
    __slots__ = ("name", "args", "_t0", "_child", "_c0", "_child_cpu",
                 "trace_id", "span_id", "parent_id", "_token", "_ann")

    def __init__(self, name: str, args: Optional[dict] = None):
        self.name = name
        self.args = args
        self._t0 = 0.0
        self._child = 0.0  # seconds covered by nested spans
        self._c0 = None    # the thread's CPU clock at entry
        self._child_cpu = 0.0  # CPU seconds of nested spans
        self.trace_id = 0
        self.span_id = 0
        self.parent_id = 0
        self._token = None
        self._ann = None

    def __bool__(self):
        return True

    def add(self, **args) -> None:
        """Attach args discovered mid-span (bytes moved, row counts)."""
        if self.args is None:
            self.args = args
        else:
            self.args.update(args)

    def context(self) -> SpanContext:
        """This span's propagation token (valid after __enter__)."""
        return SpanContext(self.trace_id, self.span_id)

    def __enter__(self):
        stack = getattr(_tls, "stack", None)
        if stack is None:
            stack = _tls.stack = []
        stack.append(self)
        parent = _ctx.get()
        self.span_id = next(_ids)
        if parent is not None:
            self.trace_id = parent.trace_id
            self.parent_id = parent.span_id
        else:
            self.trace_id = self.span_id  # a new root starts its trace
        self._token = _ctx.set(SpanContext(self.trace_id, self.span_id))
        if _annotation is not None:
            self._ann = _annotation(self.name)
            self._ann.__enter__()
        # the CPU clock first at both ends: a read of it is a system
        # call, and so each clock's interval holds one of the two reads -
        # the other falls to the parent, on both clocks alike
        if _HAS_THREAD_TIME:
            self._c0 = time.thread_time()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        cpu = time.thread_time() - self._c0 \
            if self._c0 is not None else None
        t1 = time.perf_counter()
        dur = t1 - self._t0
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        if self._token is not None:
            _ctx.reset(self._token)
            self._token = None
        stack = _tls.stack
        stack.pop()
        depth = len(stack)
        if depth:
            stack[-1]._child += dur
            if cpu is not None:
                stack[-1]._child_cpu += cpu
        t = threading.current_thread()
        global _recorded
        with _lock:
            _recorded += 1
            _ring.append((
                self.name, t.ident, t.name,
                self._t0 - _epoch, dur, max(0.0, dur - self._child),
                depth, self.args,
                self.trace_id, self.span_id, self.parent_id,
                None if cpu is None
                else max(0.0, cpu - self._child_cpu),
            ))
        return False


def enable(on: bool = True, capacity: Optional[int] = None) -> None:
    global _enabled, _epoch, _ring
    if capacity is not None and capacity != _ring.maxlen:
        with _lock:
            _ring = deque(_ring, maxlen=capacity)
    if on and not _enabled and _epoch == 0.0:
        _epoch = time.perf_counter()
    _enabled = on
    if on:
        install_jit_hooks()
        _find_annotation()


def _find_annotation() -> None:
    """Import the profiler's annotation class once (no-op without jax)."""
    global _annotation, _annotation_tried
    if _annotation_tried:
        return
    _annotation_tried = True
    try:
        from jax.profiler import TraceAnnotation
    except ImportError:  # pragma: no cover - jax optional
        return
    _annotation = TraceAnnotation


def enabled() -> bool:
    return _enabled


def reset() -> None:
    """Clear the span ring and restart the capture epoch.  Does NOT
    touch TELEMETRY: the device counters are cumulative process state
    (a /metrics scrape depends on them); reset those explicitly."""
    global _epoch
    with _lock:
        _ring.clear()
    _epoch = time.perf_counter()


def span(name: str, **args):
    """The ONE per-site call.  Disabled: one bool check, shared no-op
    singleton back (hot sites attach args via `if sp: sp.add(...)` so
    the disabled path allocates nothing)."""
    if not _enabled:
        return _NOOP
    return Span(name, args or None)


def instant(name: str, ctx: Optional[SpanContext] = None,
            **args) -> None:
    """Point event (XLA compiles, retries, chaos fires).  Lands ON the
    active span: the recorded tuple carries the current trace/span ids
    (or an explicit `ctx`), so Perfetto shows the instant inside the
    span that was running when it fired."""
    if not _enabled:
        return
    at = ctx if ctx is not None else _ctx.get()
    trace_id = at.trace_id if at else 0
    parent_id = at.span_id if at else 0
    t = threading.current_thread()
    global _recorded
    with _lock:
        _recorded += 1
        _ring.append((name, t.ident, t.name,
                      time.perf_counter() - _epoch, 0.0, 0.0, -1,
                      args or None, trace_id, 0, parent_id, None))


# depth of a complete() record: it sat on no thread's stack.  >= 0 so
# every span reader keeps it; stage_summary tells it from thread time
WAIT_DEPTH = 1 << 20


def complete(name: str, t0: float, dur: float,
             parent: Optional[SpanContext] = None, **args) -> None:
    """Record a span RETROACTIVELY from wall measurements already taken
    (`t0` in time.perf_counter seconds).  This is how queue-wait style
    intervals — observed only once they end, on whatever thread ends
    them — still land as real spans on the owning trace (fleet ticket
    queue wait, admission→dispatch).  Such a record is an item's
    passive wait, not what its thread was doing: it overlaps that
    thread's real spans, deducts from no parent, and carries
    WAIT_DEPTH so stage_summary keeps it out of the stage shares."""
    if not _enabled:
        return
    at = parent if parent is not None else _ctx.get()
    span_id = next(_ids)
    trace_id = at.trace_id if at else span_id
    parent_id = at.span_id if at else 0
    t = threading.current_thread()
    global _recorded
    with _lock:
        _recorded += 1
        _ring.append((name, t.ident, t.name, t0 - _epoch, dur,
                      dur, WAIT_DEPTH, args or None, trace_id, span_id,
                      parent_id, None))


def current() -> Optional[str]:
    """Innermost active span name on this thread (tests, debugging)."""
    stack = getattr(_tls, "stack", None)
    return stack[-1].name if stack else None


def current_context() -> Optional[SpanContext]:
    """The active span's propagation token (None when tracing is off or
    no span is open).  Capture this BEFORE handing work to another
    thread; the worker re-enters it with `adopted()`."""
    if not _enabled:
        return None
    return _ctx.get()


class adopted:
    """Re-enter a captured SpanContext on another thread:

        ctx = trace.current_context()          # submitting thread
        ...
        with trace.adopted(ctx):               # worker thread
            with trace.span("decode_readahead"):  # parents to ctx
                ...

    A None ctx is a no-op, so call sites never need to branch on
    whether tracing was on at capture time."""

    __slots__ = ("_ctx", "_token")

    def __init__(self, ctx: Optional[SpanContext]):
        self._ctx = ctx
        self._token = None

    def __enter__(self):
        if self._ctx is not None and _enabled:
            self._token = _ctx.set(self._ctx)
        return self

    def __exit__(self, *exc):
        if self._token is not None:
            _ctx.reset(self._token)
            self._token = None
        return False


def wire_format(ctx: Optional[SpanContext]) -> str:
    """Serialize a context for a wire hop (Flight gRPC metadata, shm
    framing metadata).  Empty string when there is nothing to carry."""
    if ctx is None:
        return ""
    return f"{ctx.trace_id}:{ctx.span_id}"


def parse_wire(s) -> Optional[SpanContext]:
    """Inverse of wire_format; tolerant of junk (a malformed header
    must never fail the data-plane call it rode in on)."""
    if not s:
        return None
    if isinstance(s, bytes):
        s = s.decode("ascii", "replace")
    trace_s, _, span_s = s.partition(":")
    try:
        return SpanContext(int(trace_s), int(span_s))
    except ValueError:
        return None


def spans() -> list[tuple]:
    """Raw recorded tuples (name, tid, tname, t0_s, dur_s, self_s,
    depth, args, trace_id, span_id, parent_id, self_cpu_s) — depth -1
    marks instants (span_id 0, parent_id = the span they fired on).
    `self_cpu_s` is what of `self_s` the thread spent on a core
    (`time.thread_time`); None for instants, for `complete()` records
    and where the platform has no such clock."""
    with _lock:
        return list(_ring)


def record_count() -> int:
    """Lifetime number of records appended (monotonic; survives ring
    eviction).  `spans()[-(record_count() - mark):]` is the exporter's
    bounded delta since `mark` — records evicted past the ring capacity
    are simply lost, which the obs plane reports as `spans_dropped`."""
    with _lock:
        return _recorded


def spans_with_count() -> tuple[int, list]:
    """(record_count, ring snapshot) under ONE lock hold — the obs
    exporter's delta window.  Reading the two separately would let
    concurrent appends displace the oldest records of the intended
    window out of the tail slice, silently losing them while
    `spans_dropped` stays 0."""
    with _lock:
        return _recorded, list(_ring)


def epoch_unix() -> float:
    """Wall-clock time of this process's capture epoch (the zero point
    of every recorded t0).  Cross-process merge (stats/fleetobs.py)
    aligns N processes' timelines by shifting each one's spans by its
    exported epoch — perf_counter zeros are process-arbitrary, wall
    clocks are the only shared axis."""
    return time.time() - (time.perf_counter() - _epoch)


# -- export -----------------------------------------------------------------

def export_chrome_trace() -> dict:
    """Chrome trace-event JSON (dict; json.dump it).  Loadable in
    Perfetto and chrome://tracing: "X" complete events with tid/ts/dur
    in microseconds, thread-name metadata, instants as "i", and flow
    events ("s"/"f" pairs keyed by the child span id) for every
    parent→child link that crosses a thread — the arrows that stitch a
    readahead worker's decode, a fleet lane's run, and a Flight
    server-side span onto the submitting timeline.  A span that has the
    thread's CPU clock carries `tdur`, its CPU time with its children's
    (no `tts`: the clock's value at the start is not kept)."""
    recorded = spans()
    events: list[dict] = []
    seen_threads: dict[int, str] = {}
    # span_id -> (tid, ts_us) for flow-arrow sources
    located: dict[int, tuple[int, float]] = {}
    # (parent span_id, tid) -> CPU seconds of the children recorded so
    # far on that thread: a child is recorded before its parent
    child_cpu: dict[tuple[int, int], float] = {}
    for rec in recorded:
        name, tid, tname, t0, dur, _self_s, depth, args = rec[:8]
        trace_id, span_id, parent_id, self_cpu = rec[8:12]
        if tid not in seen_threads:
            seen_threads[tid] = tname
        ts = round(t0 * 1e6, 1)
        ev = {
            "name": name,
            "cat": "pipeline",
            "pid": 1,
            "tid": tid,
            "ts": ts,
        }
        if depth < 0:
            ev["ph"] = "i"
            ev["s"] = "t"
        else:
            ev["ph"] = "X"
            ev["dur"] = round(dur * 1e6, 1)
            if span_id:
                located[span_id] = (tid, ts)
            if self_cpu is not None:
                cpu = self_cpu + child_cpu.pop((span_id, tid), 0.0)
                ev["tdur"] = round(cpu * 1e6, 1)
                if parent_id:
                    child_cpu[(parent_id, tid)] = \
                        child_cpu.get((parent_id, tid), 0.0) + cpu
        if args:
            ev["args"] = {k: _jsonable(v) for k, v in args.items()}
        if trace_id:
            ids = ev.setdefault("args", {})
            ids["trace_id"] = trace_id
            if span_id:
                ids["span_id"] = span_id
            if parent_id:
                ids["parent_id"] = parent_id
        events.append(ev)
    flows: list[dict] = []
    for rec in recorded:
        _name, tid, _tn, t0, _dur, _s, depth, _a = rec[:8]
        _trace_id, span_id, parent_id = rec[8:11]
        if depth < 0 or not parent_id:
            continue
        src = located.get(parent_id)
        if src is None or src[0] == tid:
            continue  # same-thread nesting needs no arrow
        ts = round(t0 * 1e6, 1)
        flows.append({"name": "causal", "cat": "flow", "ph": "s",
                      "id": span_id, "pid": 1, "tid": src[0],
                      "ts": src[1]})
        flows.append({"name": "causal", "cat": "flow", "ph": "f",
                      "bp": "e", "id": span_id, "pid": 1, "tid": tid,
                      "ts": ts})
    meta = [
        {"name": "process_name", "ph": "M", "pid": 1, "tid": 0,
         "args": {"name": "transferia-tpu"}},
    ]
    for tid, tname in sorted(seen_threads.items()):
        meta.append({"name": "thread_name", "ph": "M", "pid": 1,
                     "tid": tid, "args": {"name": tname}})
    counters = TELEMETRY.snapshot()
    return {
        "traceEvents": meta + events + flows,
        "displayTimeUnit": "ms",
        "otherData": {"device_telemetry": counters},
    }


def _jsonable(v):
    try:
        json.dumps(v)
        return v
    except (TypeError, ValueError):
        return str(v)


def write_chrome_trace(path: str) -> int:
    """Dump the trace to a file; returns the number of events."""
    doc = export_chrome_trace()
    with open(path, "w") as fh:
        json.dump(doc, fh)
    return len(doc["traceEvents"])


def stage_summary(wall_seconds: Optional[float] = None) -> dict:
    """Per-stage aggregation: calls, p50/p99 ms, total and self seconds,
    `cpu_s` (of the self seconds, those the thread spent on a core: the
    rest it waited - for the GIL, a lock, a socket, a core; None where no
    record of the stage has the clock),
    bytes moved (summed from span `bytes` args), plus wall span and the
    overlap factor (sum of self-times / wall — >1 means stages overlap
    across threads; the ratio between stages is the signal).  Records
    made by complete() are waits, not thread time: they come back
    under `waits`, outside `stages` and the overlap factor."""
    recorded = [s for s in spans() if s[6] >= 0]
    per: dict[str, dict] = {}
    wait_names = set()
    t_min, t_max = None, None
    for rec in recorded:
        name, _tid, _tn, t0, dur, self_s, depth, args = rec[:8]
        d = per.setdefault(name, {"calls": 0, "total_s": 0.0,
                                  "self_s": 0.0, "cpu_s": None,
                                  "bytes": 0, "durs": []})
        if depth == WAIT_DEPTH:
            wait_names.add(name)
        d["calls"] += 1
        d["total_s"] += dur
        d["self_s"] += self_s
        if rec[11] is not None:
            d["cpu_s"] = (d["cpu_s"] or 0.0) + rec[11]
        d["durs"].append(dur)
        if args and isinstance(args.get("bytes"), (int, float)):
            d["bytes"] += int(args["bytes"])
        t_min = t0 if t_min is None else min(t_min, t0)
        t_max = max(t_max or 0.0, t0 + dur)
    wall = wall_seconds if wall_seconds else (
        (t_max - t_min) if recorded else 0.0)
    for d in per.values():
        durs = sorted(d.pop("durs"))
        n = len(durs)
        d["p50_ms"] = round(durs[max(0, (n + 1) // 2 - 1)] * 1000, 3)
        d["p99_ms"] = round(
            durs[max(0, min(n - 1, int(0.99 * n)))] * 1000, 3)
        d["total_s"] = round(d["total_s"], 4)
        d["self_s"] = round(d["self_s"], 4)
        if d["cpu_s"] is not None:
            d["cpu_s"] = round(d["cpu_s"], 4)

    def largest_first(names) -> dict:
        return dict(sorted(((n, per[n]) for n in names),
                           key=lambda kv: -kv[1]["self_s"]))

    stages = largest_first(per.keys() - wait_names)
    total_self = sum(d["self_s"] for d in stages.values())
    return {
        "wall_s": round(wall, 4),
        "overlap_factor": round(total_self / wall, 3) if wall else 0.0,
        "stages": stages,
        "waits": largest_first(wait_names),
    }


def format_summary(wall_seconds: Optional[float] = None) -> str:
    """Human table for `trtpu trace`: stages by self time, largest
    first (`cpu_s`: what of `self_s` the thread was on a core), then the
    waits (`~name`), then the device counters."""
    s = stage_summary(wall_seconds)
    lines = [
        f"wall={s['wall_s']:.2f}s overlap_factor={s['overlap_factor']}",
        f"{'stage':<18} {'calls':>7} {'p50_ms':>9} {'p99_ms':>9} "
        f"{'total_s':>8} {'self_s':>8} {'cpu_s':>8} {'bytes':>12}",
    ]
    for name, d in (*s["stages"].items(),
                    *((f"~{n}", d) for n, d in s["waits"].items())):
        cpu = "-" if d["cpu_s"] is None else f"{d['cpu_s']:.2f}"
        lines.append(
            f"{name:<18} {d['calls']:>7} {d['p50_ms']:>9.2f} "
            f"{d['p99_ms']:>9.2f} {d['total_s']:>8.2f} "
            f"{d['self_s']:>8.2f} {cpu:>8} {d['bytes']:>12}")
    if s["waits"]:
        lines.append("~ a wait recorded once it ended: "
                     "in no stage share, not in overlap_factor")
    tel = TELEMETRY.snapshot()
    if tel["device_launches"] or tel["compile_events"]:
        lines.append(
            f"device: launches={tel['device_launches']} "
            f"h2d={tel['h2d_bytes']}B/{tel['h2d_transfers']}x "
            f"d2h={tel['d2h_bytes']}B/{tel['d2h_transfers']}x "
            f"device_wait={tel['device_wait_seconds']:.3f}s "
            f"compiles={tel['compile_events']} "
            f"({tel['compile_seconds']:.2f}s, of them "
            f"{tel['compile_cache_hits']} cache loads "
            f"{tel['compile_cache_seconds']:.2f}s)")
    return "\n".join(lines)


_capture_lock = threading.Lock()


def _capture_window(wait: float, cancelled: threading.Event,
                    lock_timeout: float) -> Optional[dict]:
    """One capture cycle (see capture_seconds for the policy).  Holds
    the capture lock for the whole window so concurrent requests can't
    clobber each other's enable-state restore.  The lock acquire is
    BOUNDED and the cancel flag is re-checked after it: an abandoned
    helper whose caller already 503'd must exit instead of queueing
    forever and then running a full reset/enable window nobody reads
    (that both leaked one blocked thread per timed-out request and
    kept clearing the span ring long after the clients were gone)."""
    if not _capture_lock.acquire(timeout=lock_timeout):
        return None
    try:
        if cancelled.is_set():
            return None
        if _enabled:
            time.sleep(wait)
            return export_chrome_trace()
        reset()
        enable(True)
        time.sleep(wait)
        doc = export_chrome_trace()
        enable(False)
        return doc
    finally:
        _capture_lock.release()


def capture_seconds(seconds: float,
                    deadline_grace: float = 15.0) -> dict:
    """The `/debug/trace?seconds=N` implementation.

    When tracing is already on (a `trtpu trace` run, bench --trace, or
    an operator who enabled it), the ring belongs to that capture:
    sample the window WITHOUT resetting — destroying an in-progress
    capture from a debug endpoint would be hostile.  Only a
    tracing-off process gets the reset/enable/disable cycle.

    The window runs on a dedicated HELPER thread with a hard deadline:
    a long capture must never pin the calling HTTP worker past
    `seconds + grace` (earlier versions slept on the request thread
    and, behind the shared capture lock or a keep-alive connection,
    starved every other `/debug/*` endpoint — including `/debug/fleet`
    mid kill-trial).  On deadline the helper is abandoned (it finishes
    its cycle and restores the enable state on its own) and
    TimeoutError is raised for the caller to turn into a 503."""
    wait = max(0.05, min(seconds, 60.0))
    # the helper may also queue behind another capture holding the
    # lock for up to a full window — budget one extra window for that
    deadline = 2 * wait + max(1.0, deadline_grace)
    out: dict = {}
    done = threading.Event()
    cancelled = threading.Event()

    def _run() -> None:
        try:
            out["doc"] = _capture_window(wait, cancelled,
                                         lock_timeout=deadline)
        except BaseException as e:  # surfaced on the caller
            out["err"] = e
        finally:
            done.set()

    t = threading.Thread(target=_run, name="trace-capture",
                         daemon=True)
    t.start()
    if not done.wait(deadline):
        cancelled.set()
        raise TimeoutError(
            f"trace capture exceeded its deadline "
            f"({wait:.0f}s window); helper abandoned")
    if "err" in out:
        raise out["err"]
    if out.get("doc") is None:
        # the helper lost the lock race past its own deadline or was
        # cancelled between acquire and check — same operator story
        raise TimeoutError(
            "trace capture could not take the capture lock "
            "(another capture window in flight)")
    return out["doc"]


def iter_chrome_trace_chunks(doc: Optional[dict] = None,
                             chunk_bytes: int = 64 * 1024):
    """Yield the Chrome trace JSON as a sequence of ~chunk_bytes string
    chunks — the `/debug/trace` endpoint streams these with chunked
    transfer encoding instead of materializing one multi-MB `bytes`
    (a 60s capture of a busy fleet is easily 100k+ events).  Events
    are accumulated up to the chunk size before yielding: the
    handler's wfile is unbuffered, so one yield per event would mean
    one syscall/TCP segment per ~100-byte event."""
    if doc is None:
        doc = export_chrome_trace()
    buf: list[str] = ['{"traceEvents":[']
    size = len(buf[0])
    first = True
    for ev in doc["traceEvents"]:
        piece = ("" if first else ",") + json.dumps(ev)
        first = False
        buf.append(piece)
        size += len(piece)
        if size >= chunk_bytes:
            yield "".join(buf)
            buf, size = [], 0
    other = {k: v for k, v in doc.items() if k != "traceEvents"}
    tail = json.dumps(other)
    # splice the remaining top-level keys after the events array
    buf.append("]" + ("," + tail[1:-1] if tail != "{}" else "") + "}")
    yield "".join(buf)


# -- device telemetry --------------------------------------------------------

def _ledger():
    """The attribution plane (stats/ledger.py LEDGER): device counters
    route their increments through it under the ambient (transfer,
    tenant, part) scope, which is what makes the ledger's conservation
    invariant hold by construction.  Lazy import: ledger lazily reads
    TELEMETRY back for reconciliation."""
    from transferia_tpu.stats.ledger import LEDGER

    return LEDGER


PLACEMENT_REASONS = ("pinned", "host_first", "device_explore",
                     "link_gated", "winner_host", "winner_device",
                     "reprobe")


class DeviceTelemetry:
    """Always-on device-side counters (increments are per-dispatch, not
    per-row — a lock'd int add is noise next to a device launch).

    The sampling profiler cannot see any of these: device waits look
    like idle, H2D/D2H time hides inside jnp.asarray/np.asarray calls,
    and a jit recompile inside a measured window silently poisons it.
    These counters are the placement model's inputs.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self.reset()

    def reset(self) -> None:
        with self._lock:
            self.h2d_bytes = 0
            self.h2d_transfers = 0
            self.d2h_bytes = 0
            self.d2h_transfers = 0
            self.device_launches = 0
            self.compile_events = 0
            self.compile_seconds = 0.0
            # of those, the ones the persistent cache answered: jax
            # reports a 0.2 s load and a 17 s compile as the same event
            self.compile_cache_hits = 0
            self.compile_cache_seconds = 0.0
            # the host's wait for device results (np.asarray in
            # ops/fused.py::_collect), not time the device ran
            self.device_wait_seconds = 0.0
            # compressed dispatch plane (ops/dispatch.py): actual bytes
            # staged vs what the raw wire would have shipped, plus the
            # dict-pool residency economics
            self.h2d_encoded_bytes = 0
            self.h2d_raw_equiv_bytes = 0
            self.dict_pool_hits = 0
            self.dict_pool_uploads = 0
            # pool interning (columnar/batch.intern_pool): producers
            # re-creating identical pool bytes converged on one object
            self.dict_pool_share_hits = 0
            # decode-buffer pinning decisions (parquet_native
            # _finish_bytearray): bytes a kept pool VIEW pins beyond the
            # pool itself vs bytes copied out to release the buffer
            self.dict_pool_pinned_bytes = 0
            self.dict_pool_copied_bytes = 0
            # dict-native pipeline honesty pair: columns handled in
            # their code+pool encoding end-to-end vs columns some
            # consumer flattened (Column._materialize) — a dict-heavy
            # snapshot that finishes with nonzero flat materializations
            # has a leak in a code-aware fast path
            self.lazy_dict_preserved = 0
            self.dict_flat_materializations = 0
            # rows per mask route inside the DEVICE strategy
            # (transform/fused.py _apply_device): per-row SHA blocks
            # hashed on the chip, codes rebound to a chip-hashed pool,
            # or the referenced pool subset hashed on the HOST — a
            # "device" pass whose rows all took the last route left the
            # chip nothing but the predicate
            self.mask_route_rows = {"device_flat": 0, "device_pool": 0,
                                    "host_subset": 0}
            # why each batch of a fused step went where it went
            # (transform/fused.py DeviceFusedStep._pick_strategy), one
            # count a batch
            self.placements = dict.fromkeys(PLACEMENT_REASONS, 0)
            # rows whose filter_rows predicate ran on the chip / on the
            # host, and batches the device strategy handed back to the
            # host because a DECIMAL column's scaled values did not fit
            # the int32 the chip compares in (transform/fused.py)
            self.filter_rows = {"device": 0, "host": 0}
            self.filter_batches_host_unsafe = 0
            # units the parsequeue's push stage handed to the sink, and
            # of those the ones handed over while an earlier unit was
            # still unacked (parsequeue/queue.py): how often pushing
            # ahead of the acks engages
            self.parsequeue_pushes = 0
            self.parsequeue_pushes_ahead = 0
            # fetched batches the kafka queue client handed out, and of
            # those the ones served from the decoded remainder of an
            # earlier response with no broker request for the partition
            # in that call (providers/kafka/provider.py)
            self.kafka_handouts = 0
            self.kafka_handouts_buffered = 0
            # records the Kafka sink framed at push into a part's staged
            # record sections, and of those the ones framed straight from
            # a renderer's block, with no object per message
            # (providers/kafka/provider.py::KafkaSinker._frame)
            self.kafka_records_framed = 0
            self.kafka_records_framed_block = 0
            # parts the MySQL source streamed (one result set each), rows
            # the Debezium emitter rendered, of those the ones it rendered
            # from columns and of those the ones its native renderer
            # took, and batches the transformer chain passed on because
            # no step of it applies to their table
            self.mysql_parts = 0
            self.debezium_rows = 0
            self.debezium_rows_fast = 0
            self.debezium_rows_native = 0
            self.chain_batches_untouched = 0
            # rows the file sources' JSON-lines reader decoded, of those
            # the ones the block decode took (the others went through
            # the row path, one `json.loads` each), and the bytes of
            # their lines (providers/s3readers.py::read_json_lines)
            self.jsonl_rows = 0
            self.jsonl_rows_block = 0
            self.jsonl_bytes = 0
            # what the whole process (every thread) spent between the
            # opening and the closing of its snapshot operations
            # (tasks/snapshot.py, getrusage): CPU time and of it system
            # time - an allocator that goes to the kernel for each
            # object shows as system time
            self.proc_cpu_ms = 0.0
            self.proc_cpu_sys_ms = 0.0
            # per-target fold baselines: several pipelines may each
            # fold the (process-global) counters into their own
            # Metrics; one shared baseline would split deltas between
            # them arbitrarily
            self._folded: "weakref.WeakKeyDictionary" = \
                weakref.WeakKeyDictionary()

    # Ledger adds happen BEFORE the telemetry increment (and the
    # ledger reads telemetry first in its reconciliation): at any poll
    # the ledger total is >= the telemetry counter for routed fields,
    # so positive drift (telemetry ahead) always means a real
    # attribution bypass, never an increment caught between the two
    # locks.

    def record_h2d(self, nbytes: int) -> None:
        _ledger().add(h2d_bytes=int(nbytes))
        with self._lock:
            self.h2d_bytes += int(nbytes)
            self.h2d_transfers += 1

    def record_d2h(self, nbytes: int) -> None:
        _ledger().add(d2h_bytes=int(nbytes))
        with self._lock:
            self.d2h_bytes += int(nbytes)
            self.d2h_transfers += 1

    def record_launch(self, n: int = 1) -> None:
        _ledger().add(launches=n)
        with self._lock:
            self.device_launches += n

    def record_filter_rows(self, where: str, n_rows: int) -> None:
        with self._lock:
            self.filter_rows[where] += int(n_rows)

    def record_filter_host_unsafe(self) -> None:
        with self._lock:
            self.filter_batches_host_unsafe += 1

    def record_dispatch(self, encoded_bytes: int,
                        raw_equiv_bytes: int) -> None:
        """One encoded H2D staging: what actually crossed the link vs
        what the uncompressed wire would have shipped."""
        _ledger().add(h2d_encoded_bytes=int(encoded_bytes),
                      h2d_raw_equiv_bytes=int(raw_equiv_bytes))
        with self._lock:
            self.h2d_encoded_bytes += int(encoded_bytes)
            self.h2d_raw_equiv_bytes += int(raw_equiv_bytes)

    def record_pool_hit(self) -> None:
        """A dict pool's hexed form was already device-memoized."""
        with self._lock:
            self.dict_pool_hits += 1

    def record_pool_upload(self) -> None:
        with self._lock:
            self.dict_pool_uploads += 1

    def record_pool_share_hit(self) -> None:
        """A re-created pool matched an interned one by content."""
        with self._lock:
            self.dict_pool_share_hits += 1

    def record_pool_buffer(self, pinned: int = 0, copied: int = 0) -> None:
        """One decode-buffer retention decision: `pinned` extra bytes a
        kept view keeps alive, or `copied` pool bytes memcpy'd out."""
        with self._lock:
            self.dict_pool_pinned_bytes += int(pinned)
            self.dict_pool_copied_bytes += int(copied)

    def record_dict_preserved(self, n: int = 1) -> None:
        """A dict column crossed a pipeline stage still code-encoded."""
        with self._lock:
            self.lazy_dict_preserved += n

    def record_dict_materialize(self) -> None:
        """A lazy dict column flattened to (data, offsets) — the event
        the dict-native reduction plane exists to eliminate."""
        with self._lock:
            self.dict_flat_materializations += 1

    def record_mask_route(self, route: str, n_rows: int) -> None:
        with self._lock:
            self.mask_route_rows[route] += int(n_rows)

    def record_placement(self, reason: str) -> None:
        with self._lock:
            # .get: a reason _decide adds must not fail the batch
            self.placements[reason] = self.placements.get(reason, 0) + 1

    def record_parsequeue_push(self, ahead: bool) -> None:
        with self._lock:
            self.parsequeue_pushes += 1
            self.parsequeue_pushes_ahead += ahead

    def record_kafka_handout(self, buffered: bool) -> None:
        with self._lock:
            self.kafka_handouts += 1
            self.kafka_handouts_buffered += buffered

    def record_kafka_framed(self, n_records: int, block: bool) -> None:
        with self._lock:
            self.kafka_records_framed += int(n_records)
            if block:
                self.kafka_records_framed_block += int(n_records)

    def record_mysql_part(self) -> None:
        with self._lock:
            self.mysql_parts += 1

    def record_debezium_rows(self, n_rows: int, path: str) -> None:
        """path: the `serialize` span's - "native", "fast" or "row"; a
        native row is a columnar ("fast") row too."""
        with self._lock:
            self.debezium_rows += int(n_rows)
            if path != "row":
                self.debezium_rows_fast += int(n_rows)
            if path == "native":
                self.debezium_rows_native += int(n_rows)

    def record_jsonl(self, rows: int, rows_block: int,
                     nbytes: int) -> None:
        with self._lock:
            self.jsonl_rows += int(rows)
            self.jsonl_rows_block += int(rows_block)
            self.jsonl_bytes += int(nbytes)

    def record_proc_usage(self, before, after) -> None:
        """`resource.getrusage(RUSAGE_SELF)` at the two ends of one
        snapshot operation."""
        sys_ms = (after.ru_stime - before.ru_stime) * 1e3
        with self._lock:
            self.proc_cpu_ms += (after.ru_utime - before.ru_utime) * 1e3 \
                + sys_ms
            self.proc_cpu_sys_ms += sys_ms

    def record_chain_untouched(self) -> None:
        with self._lock:
            self.chain_batches_untouched += 1

    def record_device_wait(self, seconds: float) -> None:
        _ledger().add(device_wait_seconds=seconds)
        with self._lock:
            self.device_wait_seconds += seconds

    def record_compile(self, seconds: float,
                       cache_hit: bool = False) -> None:
        _ledger().add(compiles=1, compile_seconds=seconds)
        with self._lock:
            self.compile_events += 1
            self.compile_seconds += seconds
            if cache_hit:
                self.compile_cache_hits += 1
                self.compile_cache_seconds += seconds

    def snapshot(self) -> dict:
        with self._lock:
            ratio = (self.h2d_raw_equiv_bytes
                     / max(self.h2d_encoded_bytes, 1))
            return {
                "h2d_bytes": self.h2d_bytes,
                "h2d_transfers": self.h2d_transfers,
                "d2h_bytes": self.d2h_bytes,
                "d2h_transfers": self.d2h_transfers,
                "device_launches": self.device_launches,
                "compile_events": self.compile_events,
                "compile_seconds": round(self.compile_seconds, 4),
                "compile_cache_hits": self.compile_cache_hits,
                "compile_cache_seconds":
                    round(self.compile_cache_seconds, 4),
                "device_wait_seconds":
                    round(self.device_wait_seconds, 4),
                "h2d_encoded_bytes": self.h2d_encoded_bytes,
                "h2d_raw_equiv_bytes": self.h2d_raw_equiv_bytes,
                "dispatch_compression_ratio": round(ratio, 2),
                "dict_pool_hits": self.dict_pool_hits,
                "dict_pool_uploads": self.dict_pool_uploads,
                "dict_pool_share_hits": self.dict_pool_share_hits,
                "dict_pool_pinned_bytes": self.dict_pool_pinned_bytes,
                "dict_pool_copied_bytes": self.dict_pool_copied_bytes,
                "lazy_dict_preserved": self.lazy_dict_preserved,
                "dict_flat_materializations":
                    self.dict_flat_materializations,
                **{f"mask_rows_{route}": n
                   for route, n in self.mask_route_rows.items()},
                **{f"placement_{reason}": n
                   for reason, n in self.placements.items()},
                "filter_rows_device": self.filter_rows["device"],
                "filter_rows_host": self.filter_rows["host"],
                "filter_batches_host_unsafe":
                    self.filter_batches_host_unsafe,
                "parsequeue_pushes": self.parsequeue_pushes,
                "parsequeue_pushes_ahead": self.parsequeue_pushes_ahead,
                "kafka_handouts": self.kafka_handouts,
                "kafka_handouts_buffered": self.kafka_handouts_buffered,
                "kafka_records_framed": self.kafka_records_framed,
                "kafka_records_framed_block":
                    self.kafka_records_framed_block,
                "mysql_parts": self.mysql_parts,
                "debezium_rows": self.debezium_rows,
                "debezium_rows_fast": self.debezium_rows_fast,
                "debezium_rows_native": self.debezium_rows_native,
                "chain_batches_untouched": self.chain_batches_untouched,
                "jsonl_rows": self.jsonl_rows,
                "jsonl_rows_block": self.jsonl_rows_block,
                "jsonl_bytes": self.jsonl_bytes,
                "proc_cpu_ms": round(self.proc_cpu_ms, 3),
                "proc_cpu_sys_ms": round(self.proc_cpu_sys_ms, 3),
            }

    def fold_into(self, metrics) -> None:
        """Publish deltas since this target's last fold into the
        prometheus Metrics facade (stats/registry.py DeviceStats) —
        counters only inc, so folds carry the delta, making repeated
        folds safe.  The counters are process-global (the device is
        shared), so every pipeline's metrics sees full device
        activity."""
        from transferia_tpu.stats.registry import DeviceStats

        ds = DeviceStats(metrics)
        with self._lock:
            # counters AND baseline read/update under ONE lock hold: a
            # snapshot taken outside it could be stale by the time the
            # baseline updates, regressing prev and re-publishing
            # already-counted deltas on the next fold
            snap = {
                "h2d_bytes": self.h2d_bytes,
                "h2d_transfers": self.h2d_transfers,
                "d2h_bytes": self.d2h_bytes,
                "d2h_transfers": self.d2h_transfers,
                "device_launches": self.device_launches,
                "compile_events": self.compile_events,
                "compile_seconds": self.compile_seconds,
                "device_wait_seconds": self.device_wait_seconds,
                "h2d_encoded_bytes": self.h2d_encoded_bytes,
                "h2d_raw_equiv_bytes": self.h2d_raw_equiv_bytes,
                "dict_pool_hits": self.dict_pool_hits,
                "dict_pool_uploads": self.dict_pool_uploads,
                "dict_pool_share_hits": self.dict_pool_share_hits,
                "dict_pool_pinned_bytes": self.dict_pool_pinned_bytes,
                "dict_pool_copied_bytes": self.dict_pool_copied_bytes,
                "lazy_dict_preserved": self.lazy_dict_preserved,
                "dict_flat_materializations":
                    self.dict_flat_materializations,
            }
            prev = self._folded.setdefault(metrics, {})
            for key, counter in (
                ("h2d_bytes", ds.h2d_bytes),
                ("h2d_transfers", ds.h2d_transfers),
                ("d2h_bytes", ds.d2h_bytes),
                ("d2h_transfers", ds.d2h_transfers),
                ("device_launches", ds.launches),
                ("compile_events", ds.compiles),
                ("compile_seconds", ds.compile_seconds),
                ("device_wait_seconds", ds.device_wait_seconds),
                ("h2d_encoded_bytes", ds.h2d_encoded_bytes),
                ("h2d_raw_equiv_bytes", ds.h2d_raw_equiv_bytes),
                ("dict_pool_hits", ds.dict_pool_hits),
                ("dict_pool_uploads", ds.dict_pool_uploads),
                ("dict_pool_share_hits", ds.dict_pool_share_hits),
                ("dict_pool_pinned_bytes", ds.dict_pool_pinned_bytes),
                ("dict_pool_copied_bytes", ds.dict_pool_copied_bytes),
                ("lazy_dict_preserved", ds.lazy_dict_preserved),
                ("dict_flat_materializations",
                 ds.dict_flat_materializations),
            ):
                delta = snap[key] - prev.get(key, 0)
                if delta > 0:
                    counter.inc(delta)
                prev[key] = snap[key]
            # ratio is a gauge (an absolute, not a delta): raw-equiv
            # over encoded across the process lifetime
            if self.h2d_encoded_bytes:
                ds.compression_ratio.set(
                    self.h2d_raw_equiv_bytes / self.h2d_encoded_bytes)


TELEMETRY = DeviceTelemetry()

_hooks_installed = False
_hooks_lock = threading.Lock()


def install_jit_hooks() -> None:
    """Route jax's compile-duration monitoring events into TELEMETRY
    (+ a trace instant).  The backend-compile event fires exactly when a
    jit cache miss reaches the XLA compiler — the recompile signal a
    bucketed-shape engine must watch (ARCHITECTURE.md shape
    discipline).  jax times `compile_or_get_cached` as a whole, so a
    persistent-cache load fires the same event as a compile; the
    cache's own `cache_hits` event fires inside that interval on the
    same thread, and a thread-local flag carries it to the duration
    event that closes the interval.  Idempotent; silently a no-op
    without jax."""
    global _hooks_installed
    with _hooks_lock:
        if _hooks_installed:
            return
        try:
            from jax import monitoring as _mon
        except ImportError:  # pragma: no cover - jax optional
            return

        def _on_event(event: str, **kw) -> None:
            if event.endswith("/compilation_cache/cache_hits"):
                _tls.compile_cache_hit = True

        def _on_duration(event: str, duration: float, **kw) -> None:
            if event.endswith("backend_compile_duration"):
                hit = getattr(_tls, "compile_cache_hit", False)
                _tls.compile_cache_hit = False
                TELEMETRY.record_compile(duration, cache_hit=hit)
                instant("xla_compile", seconds=round(duration, 4),
                        cache_hit=hit)

        _mon.register_event_listener(_on_event)
        _mon.register_event_duration_secs_listener(_on_duration)
        _hooks_installed = True
