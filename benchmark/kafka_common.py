"""What the two kafka2ch traffic kinds share: the world side (broker and
ClickHouse stand-ins, the warm phase, the ground truth, the comparison) and
the system side (the replication worker around the window).

Imported by the world process: nothing at module level may import JAX or
the program under test.
"""

from __future__ import annotations

import threading
import time

import numpy as np

from benchmark import broker as broker_mod
from benchmark import events as ev
from benchmark.chserver import ClickHouseStandIn

STREAM_WARM, STREAM_WINDOW = 0, 1
DRAIN_TIMEOUT_S = 180.0   # while rows keep arriving: late is late, not lost
DRAIN_QUIET_S = 20.0      # nothing has arrived for this long: lost


class KafkaWorld:
    """Base of the kafka traffic kinds' world side.  A subclass adds the
    generator (`cmd_open` starts it, `cmd_window` runs the window) and says
    which of the events it sent the system has to land (`attempted()`).

    The window ends with the broker fenced and the stream drained."""

    def __init__(self, cell: dict, config: dict, seed: int, seconds: float,
                 work_dir: str):
        self.cell = cell
        self.params = cell["params"]
        self.seed = seed
        self.seconds = seconds
        self.n_partitions = int(config["partitions"])
        self.population = ev.Population(int(self.params["users"]),
                                        float(self.params["zipf_s"]))
        self.broker = broker_mod.BrokerStandIn(config["topic"],
                                               self.n_partitions).start()
        self.ch = ClickHouseStandIn().start()
        self.salt = f"salt-{seed}"
        # ground truth, by event index (id - ID0), grown as events are made
        self._chunks: list[ev.Events] = []
        self.next_id = 0    # events made
        # what was appended to each partition, in offset order, and when
        self._part_idx: list[list[np.ndarray]] = [
            [] for _ in range(self.n_partitions)]
        self._send_idx: list[np.ndarray] = []
        self._send_ns: list[int] = []
        self.t_open = 0
        self._at_open = (0, 0, 0)
        self.stop = threading.Event()
        self.generator: threading.Thread | None = None
        self.generator_error: str | None = None

    def endpoints(self) -> dict:
        return {"KAFKA_BROKERS": f"127.0.0.1:{self.broker.port}",
                "CH_HOST": "127.0.0.1", "CH_PORT": self.ch.port,
                "MASK_SALT": self.salt}

    # -- making and sending events ------------------------------------------------
    def make_events(self, stream: int, index: int, n: int) -> ev.Events:
        e = ev.Events(self.seed, stream, index, self.next_id, n,
                      self.population, self.n_partitions)
        self._chunks.append(e)
        self.next_id += n
        return e

    def send(self, cut: list) -> None:
        """Append record batches (as events.batches cuts them) now."""
        now = time.monotonic_ns()
        ms = int(time.time() * 1000)
        self.broker.append_many([
            (p, broker_mod.encode_batch(rec.tobytes(), len(idx), ms),
             len(idx)) for p, _g, idx, rec in cut])
        for p, _g, idx, _rec in cut:
            self._part_idx[p].append(idx)
            self._send_idx.append(idx)
        self._send_ns.extend([now] * len(cut))

    def run_generator(self, target) -> None:
        self.stop.clear()

        def guarded():
            try:
                target()
            except Exception:
                import traceback

                self.generator_error = traceback.format_exc()

        self.generator = threading.Thread(target=guarded, name="generator",
                                          daemon=True)
        self.generator.start()

    def stop_generator(self) -> None:
        self.stop.set()
        if self.generator is not None:
            self.generator.join(timeout=30)
            self.generator = None
        if self.generator_error:
            raise RuntimeError("generator failed:\n" + self.generator_error)

    # -- the account ------------------------------------------------------------------
    def taken(self) -> np.ndarray:
        """Per event index: whether it lies below the consumer's position,
        the highest offset it has asked for: it took what came before."""
        out = np.zeros(self.next_id, dtype=bool)
        for p, position in enumerate(self.broker.positions):
            idx = self._part_idx[p]
            if idx:
                out[np.concatenate(idx)[:position]] = True
        return out

    def send_times(self) -> np.ndarray:
        """Append time of every event made so far (0 = never sent)."""
        out = np.zeros(self.next_id, dtype=np.int64)
        for idx, t in zip(self._send_idx, self._send_ns):
            out[idx] = t
        return out

    def arrivals(self) -> np.ndarray:
        """First arrival of every event in ClickHouse (0 = never)."""
        never = np.iinfo(np.int64).max
        first = np.full(self.next_id, never, dtype=np.int64)
        with self.ch.lock:
            inserts = [i for n, t in self.ch.tables.items()
                       if not n.startswith("__") for i in t.inserts()]
        for ins in inserts:
            if "id" not in ins.cols:
                continue
            idx = np.asarray(ins.cols["id"], dtype=np.int64) - ev.ID0
            ok = (idx >= 0) & (idx < self.next_id)
            np.minimum.at(first, idx[ok], ins.arrival_ns)
        first[first == never] = 0
        return first

    def drain(self, attempted: np.ndarray) -> int:
        """Wait until every attempted event has arrived; returns how many
        have not when nothing more has arrived for `drain_quiet_s` (the
        cell's, or DRAIN_QUIET_S), or DRAIN_TIMEOUT_S are up."""
        quiet = float(self.params.get("drain_quiet_s", DRAIN_QUIET_S))
        start = time.monotonic_ns()
        want = int(attempted.sum())
        while True:
            if self.ch.total_rows() >= want:   # cheap, and necessary
                missing = int((attempted & (self.arrivals() == 0)).sum())
                if not missing:
                    return 0
            now = time.monotonic_ns()
            with self.ch.lock:
                last = max([start] + [t for t, _n, _r in
                                      self.ch.visible[-1:]])
            if now - last > quiet * 1e9 \
                    or now - start > DRAIN_TIMEOUT_S * 1e9:
                return int((attempted & (self.arrivals() == 0)).sum())
            time.sleep(0.01)

    # -- commands ---------------------------------------------------------------------
    def cmd_warm(self) -> dict:
        """Bursts of `warm_waves` events, each landed before the next is
        sent.  `auto` explores the device on a new chain's second and third
        flush only, so what the waves have to cover is the size of those
        two flushes in the window: the programs for them compile now."""
        t0 = time.monotonic()
        for i, size in enumerate(self.params["warm_waves"]):
            self.send(ev.batches(self.make_events(STREAM_WARM, i, int(size))))
            missing = self.drain(self.send_times() > 0)
            if missing:
                b = self.broker
                raise RuntimeError(
                    f"warm wave {i}: {missing} of {size} events never "
                    f"landed (fetched to {b.positions}, log ends "
                    f"{[log.end for log in b.logs]}, landed rows "
                    f"{self.ch.total_rows()}, sink errors "
                    f"{self.ch.errors[:3]})")
        return {"events": self.next_id, "seconds": time.monotonic() - t0}

    def mark_open(self) -> None:
        self.t_open = time.monotonic_ns()
        b = self.broker
        self._at_open = (b.fetches_with_rows, b.fetches_empty, b.consumed())

    def polls_since_open(self) -> dict:
        b = self.broker
        w, e, c = self._at_open
        return {"fetches_with_rows": b.fetches_with_rows - w,
                "fetches_empty": b.fetches_empty - e,
                "consumed_in_window": b.consumed() - c}

    def cmd_drain(self) -> dict:
        attempted = self.attempted()
        missing = self.drain(attempted)
        return {"attempted": int(attempted.sum()), "undrained": missing,
                "landed_rows": self.ch.total_rows(),
                **self.after_drain(attempted)}

    def after_drain(self, attempted: np.ndarray) -> dict:
        return {}

    def cmd_verify(self) -> dict:
        """Every landed row against the ground truth: id known, user_email
        hashlib's HMAC of the produced one, amount and ts equal; every
        event the system was handed landed at least once, in
        `events_clean`."""
        from benchmark import reference

        cat = np.concatenate
        truth = {"users": cat([c.users for c in self._chunks]),
                 "eighths": cat([c.eighths for c in self._chunks]),
                 "ts": cat([c.ts for c in self._chunks])}
        attempted = self.attempted()
        tables = self.ch.data_tables()
        inserts = [i for t in tables for i in self.ch.take_inserts(t)]
        out = reference.compare_events(
            inserts, tables, self.cell["expect_table"], truth,
            self.send_times() > 0, attempted, ev.Hmac(self.salt.encode()))
        email = len(ev.email_of(0))
        out["info"]["sha_block_bytes_per_row"] = {
            "user_email": float((email + 9 + 63) // 64 * 64)}
        return out

    def close(self) -> None:
        self.stop.set()
        self.broker.stop()
        self.ch.stop()


# -- system side -----------------------------------------------------------------------

def drive(ctx) -> dict:
    """Warm with one worker, stop it, open the window on a restarted one.

    Every window opens with `run_replication` being called, as after a
    deploy or a crash: the backlog is waiting (catch-up) or the stream is
    flowing (steady), the chain is new, and `auto` does its exploring inside
    the window, which is also what puts the device's work inside it."""
    from transferia_tpu.cli.config import load_transfer
    from transferia_tpu.coordinator import MemoryCoordinator
    from transferia_tpu.runtime import run_replication

    transfer = load_transfer(ctx.transfer_yaml)
    cp = MemoryCoordinator()
    failures: list[BaseException] = []

    def start_worker():
        stop = threading.Event()

        def run():
            try:
                run_replication(transfer, cp, stop_event=stop, backoff=0.2)
            except BaseException as e:  # surfaced after the join
                failures.append(e)

        th = threading.Thread(target=run, name="replication", daemon=True)
        th.start()
        return stop, th

    def stop_worker(stop, th):
        stop.set()
        th.join(timeout=150)   # 64 queued batches, a second each
        if th.is_alive():
            raise RuntimeError("replication worker did not stop")
        if failures:
            raise failures[0]

    stop, th = start_worker()
    try:
        ctx.world("warm")
    finally:
        stop_worker(stop, th)
    ctx.window_open()
    ctx.world("open")
    stop, th = start_worker()
    try:
        ctx.trace_for(ctx.cell["params"].get("trace_seconds", 6.0))
        account = ctx.world("window")
        ctx.window_close()
        account.update(ctx.world("drain"))
    finally:
        stop_worker(stop, th)
    return account
