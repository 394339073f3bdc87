"""Parse -> push stage -> ack stage pipeline."""

from __future__ import annotations

import collections
import concurrent.futures
import contextlib
import logging
import threading
import time
from typing import Any, Callable, Generic, Optional, Sequence, TypeVar

from transferia_tpu.abstract.interfaces import AsyncSink, Batch
from transferia_tpu.stats import trace

logger = logging.getLogger(__name__)

T = TypeVar("T")

# parse_fn(raw) -> Batch|list[Batch]; ack_fn(raw, error: Exception|None)
ParseFn = Callable[[Any], Any]
AckFn = Callable[[Any, Optional[BaseException]], None]


class ParseQueue(Generic[T]):
    """N-worker parse stage feeding an AsyncSink through a push stage and
    an ack stage (upstream's parse/push/ack shape, SURVEY §2.4 axis 4).

    The push stage takes units in Add() order, waits for the unit's parse
    and hands every non-empty batch to `sink.async_push` without waiting
    for the returned futures: one pushing thread, so the sink sees batches
    in exactly Add() order.  The ack stage takes the pushed units in the
    same order, waits for all of a unit's futures and calls
    `ack_fn(raw, err)`: a unit is acked without error only after every one
    of its futures resolved without error, and never before an earlier
    unit was acked — the at-least-once contract queue sources rely on to
    commit offsets.  A buffered sink so gets flushes that hold what
    arrived since the last one, not one unit a flush; a sink that resolves
    its futures inside `async_push` sees one unit at a time as before.

    The first failure (parse, push future or `ack_fn`) latches: nothing
    more is pushed once it is seen, that unit and every later one is acked
    with the error, `add()` and `wait()` raise it.  A unit that was already
    handed to the sink when an earlier one failed may land; it is never
    acked without error, so the source commits nothing past the failed
    unit and a restart reads both again: duplicates, never loss.

    `max_inflight` (units added and not yet acked) is the one bound on
    memory and on what a restart replays.
    """

    def __init__(self, parallelism: int, sink: AsyncSink,
                 parse_fn: ParseFn, ack_fn: AckFn,
                 max_inflight: int = 64):
        self.sink = sink
        self.parse_fn = parse_fn
        self.ack_fn = ack_fn
        self._pool = concurrent.futures.ThreadPoolExecutor(
            max_workers=max(1, parallelism), thread_name_prefix="parse"
        )
        self._pusher = threading.Thread(
            target=self._push_loop, name="parsequeue-push", daemon=True
        )
        self._acker = threading.Thread(
            target=self._ack_loop, name="parsequeue-ack", daemon=True
        )
        self._cv = threading.Condition()
        # added, not yet taken by the push stage: (raw, parse_fut, t_add)
        self._queue: collections.deque[tuple] = collections.deque()
        # taken by the push stage, not yet acked: (raw, futures, error)
        self._pushed: collections.deque[tuple] = collections.deque()
        self._closed = False
        self._push_done = False  # the push stage has exited
        self._failure: Optional[BaseException] = None
        self._inflight = threading.Semaphore(max_inflight)
        self._outstanding = 0  # added but not yet acked (guarded by _cv)
        self._pusher.start()
        self._acker.start()

    # -- public -------------------------------------------------------------
    def add(self, raw: T) -> None:
        """Enqueue one unit; raises immediately if the queue has failed."""
        if self._failure is not None:
            raise self._failure
        if self._closed:
            raise RuntimeError("parsequeue closed")
        if not self._inflight.acquire(blocking=False):
            # max_inflight units are unacked: the caller (a source's
            # poll thread) waits here for the ack stage
            with trace.span("inflight_wait"):
                self._inflight.acquire()
        parse_fut = self._pool.submit(self._safe_parse, raw)
        # the enqueue time rides the tuple: the wait between here and
        # the push stage taking the item is staleness the program adds
        t_add = time.perf_counter() if trace.enabled() else 0.0
        with self._cv:
            self._queue.append((raw, parse_fut, t_add))
            self._outstanding += 1
            self._cv.notify_all()

    def close(self) -> None:
        with self._cv:
            if self._closed:
                return
            self._closed = True
            self._cv.notify_all()
        self._pusher.join(timeout=60)
        self._acker.join(timeout=60)
        self._pool.shutdown(wait=False, cancel_futures=True)

    @property
    def failure(self) -> Optional[BaseException]:
        return self._failure

    # -- internals ----------------------------------------------------------
    def _safe_parse(self, raw: T):
        # the parser layer runs here (parse workers): decode raw broker
        # messages into batches — the source_decode stage of the timeline
        from transferia_tpu.chaos.failpoints import failpoint

        failpoint("parsequeue.parse")
        with trace.span("source_decode"):
            return self.parse_fn(raw)

    def _latch(self, err: BaseException) -> None:
        with self._cv:
            if self._failure is not None:
                return
            self._failure = err
        logger.error("parsequeue failed: %s", err)

    def _push_loop(self) -> None:
        """Push stage: Add() order in, `async_push` out, no waiting for
        the futures."""
        while True:
            with self._cv:
                while not self._queue and not self._closed:
                    self._cv.wait(timeout=0.5)
                if not self._queue:
                    # closed and drained: the ack stage ends after the
                    # last pushed unit
                    self._push_done = True
                    self._cv.notify_all()
                    return
                raw, parse_fut, t_add = self._queue.popleft()
            if t_add:
                trace.complete("queue_wait", t_add,
                               time.perf_counter() - t_add,
                               rows=_batch_len(raw))
            futs: list = []
            batches: list = []
            err: Optional[BaseException] = None
            try:
                parsed = parse_fut.result()
                batches = [b for b in (parsed if isinstance(parsed, list)
                                       else [parsed])
                           if b is not None and _batch_len(b)]
            except BaseException as e:
                err = e
            # once failed, drain without pushing: the units go to the
            # ack stage with the error and nothing more reaches the sink
            err = self._failure or err
            if err is None and batches:
                with self._cv:
                    ahead = bool(self._pushed)
                try:
                    # "sink_wait", not "sink_push": the actual push
                    # executes (and is spanned) inside the async sink's
                    # own worker.  Handing over is a wait too: for the
                    # whole push where the sink resolves inline, for the
                    # flush that holds its lock where it buffers
                    with trace.span("sink_wait"):
                        for b in batches:
                            futs.append(self.sink.async_push(b))
                    trace.TELEMETRY.record_parsequeue_push(ahead)
                except BaseException as e:
                    err = e
            if err is not None:
                self._latch(err)
            with self._cv:
                self._pushed.append((raw, futs, err))
                self._cv.notify_all()

    def _ack_loop(self) -> None:
        """Ack stage: pushed units in the same order, `ack_fn` after all
        of a unit's futures."""
        failed: Optional[BaseException] = None  # the first, in ack order
        while True:
            with self._cv:
                while not self._pushed and not self._push_done:
                    self._cv.wait(timeout=0.5)
                if not self._pushed:
                    return
                # it stays at the head until it is acked: the push
                # stage counts a hand-over as ahead while it is there
                raw, futs, err = self._pushed[0]
            # a unit behind a failed one is acked with that error
            # whatever became of its own futures
            err = err or failed
            if err is None:
                # the ordered-delivery wait for the flush that holds the
                # unit's batches; no wait, no span, where it is over
                waited = contextlib.nullcontext() \
                    if all(f.done() for f in futs) \
                    else trace.span("sink_wait")
                try:
                    with waited:
                        for f in futs:
                            f.result()
                except BaseException as e:
                    err = e
            try:
                self.ack_fn(raw, err)
            except BaseException as ack_err:
                err = err or ack_err
            if err is not None:
                failed = failed or err
                self._latch(err)
            with self._cv:
                self._pushed.popleft()
                self._outstanding -= 1
                self._cv.notify_all()
            self._inflight.release()

    def wait(self) -> None:
        """Block until everything added so far is pushed+acked
        (WaitableParseQueue.Wait for rebalances)."""
        with self._cv:
            while self._outstanding > 0:
                self._cv.wait(timeout=0.5)
        if self._failure is not None:
            raise self._failure


def _batch_len(b) -> int:
    try:
        return b.n_rows if hasattr(b, "n_rows") else len(b)
    except TypeError:
        return 1


WaitableParseQueue = ParseQueue
