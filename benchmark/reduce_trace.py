"""From the profiler's trace to numbers: device busy seconds, time per XLA
module, the operations that took most time, the longest idle gaps.

Two steps, so that the arithmetic can be checked on a small recorded trace
(benchmark/tests/data/) without a chip: `load_xplane` turns the profiler's
`.xplane.pb` into plain lists, `reduce` does the rest.

Busy is the union of the intervals in which an operation ran on a device
(the `XLA Ops` line of each `/device:TPU:n` plane; the `XLA Modules` line
where a plane has no such line), averaged over the devices that ran
anything (`busy_s`) and device by device (`busy_per_device_s`, one entry
per device plane that ran anything, in the planes' order).  A gap is
attributed to the program span (stats/trace.py) it is
most about (see `_covering_span`), where the two clocks can be aligned.  The spans' epoch is
known on the wall clock (`trace.epoch_unix()`), and so is the moment
`start_trace` returned.  On the v5e the profiler stamps device events in
nanoseconds from the start of the trace (PERF.md section 6, PR 24); a trace
stamped with the wall clock is read too; where the stamps fit neither, every
gap is `unattributed`.  An operation is named by the left-hand side of its
HLO line and its opcode (`%while.115 while`): the line itself runs to
kilobytes.
"""

from __future__ import annotations

import glob
import os
import re

OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
TOP = 10
# stats/trace.py::WAIT_DEPTH, the depth field of a `trace.complete()` record:
# an item's passive wait (`queue_wait`, `decode_wait`), nothing a thread did
WAIT_DEPTH = 1 << 20


def load_xplane(path: str) -> dict:
    """{"planes": [{"name", "lines": [{"name", "events": [[name, start_ns,
    duration_ns], ...]}]}]} of the device planes."""
    from jax.profiler import ProfileData

    out = {"planes": []}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:"):
            continue
        lines = []
        for line in plane.lines:
            lines.append({"name": line.name, "events": [
                [e.name, int(e.start_ns), int(e.duration_ns)]
                for e in line.events]})
        out["planes"].append({"name": plane.name, "lines": lines})
    return out


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _module_name(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")


def _op_name(name: str) -> str:
    lhs, eq, rest = name.partition(" = ")
    if not eq:
        return name[:64]
    m = _OPCODE.search(" " + rest)
    return (lhs + (" " + m.group(1) if m else ""))[:64]


def _clock(busy: list, window_s: float, window_unix_ns) -> tuple[str, int]:
    """('trace_start' | 'unix' | 'unknown', what to add to a stamp to get
    wall-clock nanoseconds)."""
    if not busy or not window_unix_ns:
        return "unknown", 0
    lo, hi = busy[0][0], busy[-1][1]
    slack = 10**9
    if -slack <= lo and hi <= window_s * 1e9 + slack:
        return "trace_start", window_unix_ns[0]
    if window_unix_ns[0] - slack <= lo and hi <= window_unix_ns[1] + slack:
        return "unix", 0
    return "unknown", 0


def reduce(doc: dict, window_s: float, spans: list = (),
           span_epoch_unix: float = 0.0,
           window_unix_ns: tuple[int, int] | None = None) -> dict:
    """`window_s`: the traced window on the host's clock.  `spans`: the
    program's span tuples (name, tid, tname, t0_s, dur_s, self_s, depth,
    ...), their t0 relative to `span_epoch_unix`."""
    busy_per_device = []
    modules: dict[str, float] = {}
    ops: dict[str, float] = {}
    all_busy: list[tuple[int, int]] = []
    for plane in doc["planes"]:
        by_name = {ln["name"]: ln["events"] for ln in plane["lines"]}
        op_events = by_name.get(OPS_LINE) or by_name.get(MODULES_LINE) or []
        for name, _start, dur in by_name.get(MODULES_LINE, []):
            modules[_module_name(name)] = \
                modules.get(_module_name(name), 0.0) + dur / 1e9
        for name, _start, dur in op_events:
            ops[_op_name(name)] = ops.get(_op_name(name), 0.0) + dur / 1e9
        merged = _union([(s, s + d) for _n, s, d in op_events if d > 0])
        if merged:
            busy_per_device.append(sum(b - a for a, b in merged) / 1e9)
            all_busy.extend(merged)
    busy_s = sum(busy_per_device) / len(busy_per_device) \
        if busy_per_device else 0.0
    merged = _union(all_busy)
    clock, shift = _clock(merged, window_s, window_unix_ns)
    if clock != "unknown":   # on the wall clock from here on
        merged = [(a + shift, b + shift) for a, b in merged]
    gaps = _gaps(merged, window_unix_ns if clock != "unknown" else None)
    # the spans, on the wall clock, sorted by start for the sweep
    walls = sorted(
        (int((span_epoch_unix + s[3]) * 1e9),
         int((span_epoch_unix + s[3] + s[4]) * 1e9), s[6], s[0])
        for s in spans if s[6] >= 0) if clock != "unknown" else []
    idle: dict[str, float] = {}
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:200]:
        label = _covering_span(a, b, walls) if walls else "unattributed"
        idle[label] = idle.get(label, 0.0) + (b - a) / 1e9

    def top(d: dict) -> list:
        return [[k, v] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]

    return {"busy_s": busy_s, "busy_per_device_s": busy_per_device,
            "window_s": window_s, "clock": clock,
            "devices_busy": len(busy_per_device), "modules": modules,
            "longest_gap_s": max((b - a for a, b in gaps), default=0) / 1e9,
            "breakdown": {"device_ops": top(ops), "idle_gaps": top(idle)}}


def _gaps(busy: list[tuple[int, int]], window_unix_ns) -> list:
    """The idle intervals between busy ones, and, where the window's own
    bounds are known on the same clock, before the first and after the
    last."""
    if not busy:
        return []
    lo, hi = busy[0][0], busy[-1][1]
    if window_unix_ns:
        lo, hi = min(lo, window_unix_ns[0]), max(hi, window_unix_ns[1])
    edges = [lo] + [t for ab in busy for t in ab] + [hi]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def _covering_span(a: int, b: int, walls: list) -> str:
    """The span that the gap [a, b) is most about: overlap squared over the
    span's length, so that a span lying inside the gap, or one the gap lies
    inside of and not much longer, beats a root span that covers the whole
    window and every gap in it.  A record of an item's wait says what the
    item did, not the host, and is passed over."""
    best, best_score = "unattributed", 0.0
    for s0, s1, depth, name in walls:
        if s0 >= b:
            break
        if depth == WAIT_DEPTH:
            continue
        overlap = min(b, s1) - max(a, s0)
        if overlap > 0:
            score = overlap * overlap / max(s1 - s0, 1)
            if score > best_score:
                best, best_score = name, score
    return best


def reduce_dir(trace_dir: str, window_s: float, spans: list = (),
               span_epoch_unix: float = 0.0,
               window_unix_ns: tuple[int, int] | None = None) -> dict:
    """Reduce the newest `.xplane.pb` under a `jax.profiler` directory."""
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        return reduce({"planes": []}, window_s)
    return reduce(load_xplane(found[-1]), window_s, spans, span_epoch_unix,
                  window_unix_ns)
