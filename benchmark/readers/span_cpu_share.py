"""Reader `span_cpu_share`: of the self seconds of the named program spans,
summed over threads, the share in which the thread was on a core (`%`).

params: {"spans": [names], "where": {arg: value}}, as `span_self_time` takes
them.  A span carries its self seconds on the wall clock (field 5) and, as
its twelfth field, on the clock of the thread that ran it
(`time.thread_time`: CLOCK_THREAD_CPUTIME_ID); the reader gives
100 * sum(cpu) / sum(self) over the spans that have both, as the clocks read:
nothing is cut off at 100.  A thread's CPU clock may tick coarsely (10 ms
on the chip's host), so one short span reads 0 or a whole tick and only a
sum over many ticks says anything: list a cell where the named spans hold
seconds, not milliseconds.  A share well over 100 means the recorder's two
clocks do not cover the same interval, which is a fault to repair there.
What is missing to 100 the thread spent waiting: for the GIL, a lock, a
socket, a free core.  The clock is the calling thread's alone: work that a
native library hands to threads of its own (arrow's readers with
`use_threads`, a BLAS pool) is not in it and reads as waiting.  A
`trace.complete()` record (an item's wait, depth `WAIT_DEPTH`) ran on no
thread and is passed over.  Nothing to read - no such span, no self time,
or span tuples of eleven fields, which a program without the clock
records - returns nothing.
"""

WAIT_DEPTH = 1 << 20   # stats/trace.py::WAIT_DEPTH


def read(params: dict, data: dict):
    names = set(params["spans"])
    where = params.get("where", {}).items()
    hits = [s for s in data["spans"]
            if s[0] in names and 0 <= s[6] < WAIT_DEPTH
            and len(s) > 11 and s[11] is not None
            and all((s[7] or {}).get(k) == v for k, v in where)]
    self_s = sum(s[5] for s in hits)
    if not self_s:
        return None
    return 100.0 * sum(s[11] for s in hits) / self_s
