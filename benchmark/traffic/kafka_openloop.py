"""Traffic kind `kafka_openloop`: a live stream at a fixed rate.

Poisson arrivals at `rate` events a second, fixed in the cell and never
searched for.  Every event has a due time, a function of the seed alone; a
producer that lingers `linger_ms` sends what fell due in each tick as one
record batch per partition.  An event's lag is its first arrival in
ClickHouse minus its due time, so a stall is charged to every event that
waited behind it; how late the generator itself ran (append minus end of
tick) is reported beside it.  The window is the events due in
[0, --seconds); the generator stops there, and the tail includes what lands
in the drain.

Fields of the cell's `params`: rate, linger_ms, users, zipf_s,
warm_waves, trace_seconds, drain_quiet_s.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark import events as ev
from benchmark import kafka_common
from benchmark.kafka_common import STREAM_WINDOW, KafkaWorld

drive = kafka_common.drive
NEVER_MS = 1e12   # an event that never landed, where a number has to stand


def due_times(seed: int, stream: int, rate: float,
              seconds: float) -> np.ndarray:
    """Seconds after the phase opens at which each event is due."""
    rng = np.random.default_rng([seed, stream, 1 << 20])
    n = int(rate * seconds * 1.05) + 1000
    due = np.cumsum(rng.exponential(1.0 / rate, n))
    while due[-1] < seconds:  # 5% short: draw on
        more = np.cumsum(rng.exponential(1.0 / rate, n)) + due[-1]
        due = np.concatenate([due, more])
    return due[due < seconds]


class World(KafkaWorld):
    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.tick = float(self.params["linger_ms"]) / 1000.0
        self.due = np.zeros(0)
        self.first_id = 0
        self._backlog_samples: list[tuple[float, int]] = []

    def attempted(self):
        return self.send_times() > 0

    def cmd_open(self) -> dict:
        self.due = due_times(self.seed, STREAM_WINDOW,
                             float(self.params["rate"]), self.seconds)
        self.first_id = self.next_id
        e = self.make_events(STREAM_WINDOW, 0, len(self.due))
        plan = ev.batches(e, np.floor(self.due / self.tick).astype(np.int64))
        t0 = time.monotonic_ns()

        def loop():
            i = 0
            while i < len(plan) and not self.stop.is_set():
                g = plan[i][1]
                wait = (t0 + int((g + 1) * self.tick * 1e9)
                        - time.monotonic_ns()) / 1e9
                if wait > 0:
                    time.sleep(wait)
                j = i
                while j < len(plan) and plan[j][1] == g:
                    j += 1
                self.send(plan[i:j])
                i = j
                if g % 20 == 0:
                    self._backlog_samples.append(
                        ((time.monotonic_ns() - t0) / 1e9,
                         sum(self.broker.backlog())))

        self.run_generator(loop)
        self.mark_open()
        self.t_open = t0        # due times count from the plan's zero
        return {"t_open_ns": self.t_open}

    def cmd_window(self) -> dict:
        t_close = self.t_open + int(self.seconds * 1e9)
        time.sleep(max(0.0, (t_close - time.monotonic_ns()) / 1e9))
        if self.generator is not None:
            self.generator.join(timeout=30)   # the last tick's batches
        self.stop_generator()
        return {"t_open_ns": self.t_open, "t_close_ns": t_close,
                "window_s": self.seconds,
                "landed_at_close": self.ch.total_rows(),
                **self.polls_since_open()}

    def after_drain(self, attempted: np.ndarray) -> dict:
        n0, n1 = self.first_id, self.first_id + len(self.due)
        due_ns = self.t_open + (self.due * 1e9).astype(np.int64)
        arrived = self.arrivals()[n0:n1]
        sent = self.send_times()[n0:n1]
        tick_end = self.t_open + (
            (np.floor(self.due / self.tick) + 1) * self.tick * 1e9
        ).astype(np.int64)
        never = arrived == 0
        lag_ms = np.where(never, NEVER_MS, (arrived - due_ns) / 1e6)
        late_ms = (sent[sent > 0] - tick_end[sent > 0]) / 1e6
        samples = self._backlog_samples
        quarter = max(1, len(samples) // 4)
        with self.ch.lock:
            inserts = sum(1 for t, _n, _r in self.ch.visible
                          if t > self.t_open)
        return {
            "events_due": int(n1 - n0),
            "events_never_landed": int(never.sum()),
            "events_unsent": int((sent == 0).sum()),
            "lag_p50_ms": float(np.percentile(lag_ms, 50)),
            "lag_p95_ms": float(np.percentile(lag_ms, 95)),
            "lag_p99_ms": float(np.percentile(lag_ms, 99)),
            "lag_max_ms": float(lag_ms.max()),
            "generator_late_p95_ms": float(np.percentile(late_ms, 95))
            if len(late_ms) else None,
            "inserts_since_open": inserts,
            # the broker's backlog through the window: a rate the system
            # cannot sustain shows as a last quarter above the first
            "backlog_first_quarter": float(np.mean(
                [b for _t, b in samples[:quarter]])) if samples else None,
            "backlog_last_quarter": float(np.mean(
                [b for _t, b in samples[-quarter:]])) if samples else None,
            "backlog_max": max((b for _t, b in samples), default=None),
        }


def end_to_end(account: dict) -> dict:
    return {"replication_lag_p50_ms": account["lag_p50_ms"],
            "replication_lag_p95_ms": account["lag_p95_ms"]}


def window_rows(account: dict) -> int:
    return account["events_due"]


def account_numbers(account: dict) -> dict:
    return {"events_undrained": [account["undrained"], 0],
            "events_unsent": [account["events_unsent"], 0]}
