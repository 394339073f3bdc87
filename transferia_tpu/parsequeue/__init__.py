"""ParseQueue: parallel parse, a push stage, an ack stage.

Reference parity: pkg/parsequeue/parsequeue.go:17-90 + README guarantees:
N parse workers run concurrently, a push stage hands units to the sink
strictly in Add() order without waiting for the push futures, an ack
stage acks them in the same order as their pushes resolve, and the first
error latches (fail-fast; subsequent Adds fail immediately).
WaitableParseQueue adds Wait() for partition rebalances.
"""

from transferia_tpu.parsequeue.queue import ParseQueue, WaitableParseQueue

__all__ = ["ParseQueue", "WaitableParseQueue"]
