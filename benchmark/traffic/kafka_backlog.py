"""Traffic kind `kafka_backlog`: a restarted transfer catching up.

The generator keeps `backlog` messages ahead of the consumer, topping the
partitions up as the fetch offsets advance, so the source is never what the
system waits for.  The window closes at the first insert that arrives at or
after `--seconds` (the sink writes in flushes: a window cut between two
would read one flush more or less by where the cut fell), and its seconds
are those that elapsed.  At the close the broker is fenced at the
consumer's position: what the consumer took (all that lies below the
highest offset it asks for) is what the system has to land, and the rest of
the backlog, which is never empty by design, is no operation of this run.

Fields of the cell's `params`: backlog, chunk_events, users, zipf_s,
warm_waves, trace_seconds, drain_quiet_s.
"""

from __future__ import annotations

import time

from benchmark import events as ev
from benchmark import kafka_common
from benchmark.kafka_common import STREAM_WINDOW, KafkaWorld

drive = kafka_common.drive
CLOSE_WAIT_S = 10.0


class World(KafkaWorld):
    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self._pending: list = []
        self._chunks_made = 0

    def _top_up(self) -> None:
        depth = int(self.params["backlog"])
        need = depth - (self.broker.produced() - self.broker.consumed())
        cut = []
        while need > 0:
            if not self._pending:
                e = self.make_events(STREAM_WINDOW, self._chunks_made,
                                     int(self.params["chunk_events"]))
                self._chunks_made += 1
                # id order, so the partitions fill side by side
                self._pending = sorted(ev.batches(e),
                                       key=lambda b: int(b[2][0]),
                                       reverse=True)
            b = self._pending.pop()
            cut.append(b)
            need -= len(b[2])
        if cut:
            self.send(cut)

    def attempted(self):
        return self.taken()

    def cmd_open(self) -> dict:
        self._top_up()          # the backlog is there when the worker starts

        def loop():
            while not self.stop.is_set():
                self._top_up()
                time.sleep(0.002)

        self.run_generator(loop)
        self.mark_open()
        return {"t_open_ns": self.t_open}

    def cmd_window(self) -> dict:
        want = self.t_open + int(self.seconds * 1e9)
        time.sleep(max(0.0, (want - time.monotonic_ns()) / 1e9))
        deadline = want + int(CLOSE_WAIT_S * 1e9)
        t_close = 0
        while not t_close:
            with self.ch.lock:
                late = [t for t, _n, _r in self.ch.visible if t >= want]
            if late:
                t_close = min(late)
            elif time.monotonic_ns() > deadline:
                t_close = time.monotonic_ns()   # nothing lands any more
            else:
                time.sleep(0.001)
        self.stop_generator()
        self.broker.fence()
        with self.ch.lock:
            inside = [r for t, _n, r in self.ch.visible
                      if self.t_open < t <= t_close]
        return {"t_open_ns": self.t_open, "t_close_ns": t_close,
                "window_s": (t_close - self.t_open) / 1e9,
                "rows_in_window": sum(inside),
                "inserts_in_window": len(inside),
                **self.polls_since_open(),
                "backlog_at_close": sum(self.broker.backlog())}


def end_to_end(account: dict) -> dict:
    return {"replication_rows_per_s":
            account["rows_in_window"] / account["window_s"]}


def window_rows(account: dict) -> int:
    return account["rows_in_window"]


def account_numbers(account: dict) -> dict:
    return {"events_undrained": [account["undrained"], 0]}
