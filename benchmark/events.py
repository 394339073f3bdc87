"""The kafka2ch event stream, from the seed: who, how much, when, and the
JSON bytes and record batches that carry it.

Every event is `{"id", "user_email", "amount", "ts"}` as `examples/kafka2ch`
has it.  Users are drawn Zipf(s) from a fixed population, `user_email` is a
function of the user and the partition a hash of the user, so a hot user is
a hot partition.  All fields are rendered at a fixed width (ids from 10**9,
users from 10**6, amounts `ddd.ddd` in eighths), which makes every message
the same length and lets numpy build millions of them in a second; the
values are exact in binary, so the landed doubles compare with `==`.

Nothing here knows the consumer: ids, users and due times are functions of
(seed, stream, index) alone.
"""

from __future__ import annotations

import hashlib

import numpy as np

from benchmark import broker

ID0 = 1_000_000_000
USER0 = 1_000_000
TS0 = 1_790_000_000_000_000   # epoch microseconds of event 0

_TEMPLATE = (b'{"id": 0000000000, "user_email": "user0000000@mail000.example",'
             b' "amount": 000.000, "ts": 0000000000000000}')
_AT_ID = _TEMPLATE.index(b"0000000000")
_AT_USER = _TEMPLATE.index(b'"user0') + 5
_AT_MAIL = _TEMPLATE.index(b"@mail") + 5
_AT_WHOLE = _TEMPLATE.index(b'"amount": ') + 10
_AT_FRAC = _AT_WHOLE + 4
_AT_TS = _TEMPLATE.index(b'"ts": ') + 6
VALUE_LEN = len(_TEMPLATE)


def _digits(out: np.ndarray, at: int, width: int, v: np.ndarray) -> None:
    pows = 10 ** np.arange(width - 1, -1, -1, dtype=np.int64)
    out[:, at:at + width] = (v[:, None] // pows % 10 + 48).astype(np.uint8)


class Population:
    """Zipf(s) over `users` ranks, by inverse CDF (numpy's zipf is
    unbounded); rank r is user r."""

    def __init__(self, users: int, s: float):
        w = 1.0 / np.arange(1, users + 1, dtype=np.float64) ** s
        self.cdf = np.cumsum(w)
        self.cdf /= self.cdf[-1]

    def draw(self, rng, n: int) -> np.ndarray:
        return np.searchsorted(self.cdf, rng.random(n)).astype(np.int64)


def partition_of(users: np.ndarray, n_partitions: int) -> np.ndarray:
    h = (users.astype(np.uint64) + np.uint64(USER0)) \
        * np.uint64(0x9E3779B97F4A7C15)
    return ((h >> np.uint64(40)) % np.uint64(n_partitions)).astype(np.int64)


class Events:
    """`n` consecutive events of one stream, ids from `first_id`."""

    def __init__(self, seed: int, stream: int, index: int, first_id: int,
                 n: int, population: Population, n_partitions: int):
        rng = np.random.default_rng([seed, stream, index])
        self.first = first_id
        self.ids = ID0 + first_id + np.arange(n, dtype=np.int64)
        self.users = population.draw(rng, n)
        self.eighths = rng.integers(800, 8000, n)      # 100.000 .. 999.875
        self.ts = TS0 + first_id + np.arange(n, dtype=np.int64)
        self.partitions = partition_of(self.users, n_partitions)

    def __len__(self) -> int:
        return len(self.ids)

    def values(self) -> np.ndarray:
        """(n, VALUE_LEN) uint8: the JSON message of every event."""
        out = np.tile(np.frombuffer(_TEMPLATE, dtype=np.uint8),
                      (len(self), 1))
        u = self.users + USER0
        _digits(out, _AT_ID, 10, self.ids)
        _digits(out, _AT_USER, 7, u)
        _digits(out, _AT_MAIL, 3, 100 + u % 877)
        _digits(out, _AT_WHOLE, 3, self.eighths // 8)
        _digits(out, _AT_FRAC, 3, self.eighths % 8 * 125)
        _digits(out, _AT_TS, 16, self.ts)
        return out


def email_of(user: int) -> bytes:
    u = int(user) + USER0
    return b"user%07d@mail%03d.example" % (u, 100 + u % 877)


def batches(events: Events, group: np.ndarray | None = None
            ) -> list[tuple[int, int, np.ndarray, np.ndarray]]:
    """Cut the events into record batches: per partition, in id order, at
    most broker.RECORDS_PER_BATCH each, never across a change of `group`
    (the open loop's send tick).  Returns (partition, group, event indices
    (id - ID0), record bytes (k, R)) in (group, partition) order."""
    n = len(events)
    grp = np.zeros(n, dtype=np.int64) if group is None else group
    order = np.lexsort((np.arange(n), events.partitions, grp))
    p = events.partitions[order]
    g = grp[order]
    new_run = np.ones(n, dtype=bool)
    new_run[1:] = (p[1:] != p[:-1]) | (g[1:] != g[:-1])
    run_start = np.maximum.accumulate(np.where(new_run, np.arange(n), 0))
    within = np.arange(n) - run_start
    pos = within % broker.RECORDS_PER_BATCH
    starts = np.flatnonzero(pos == 0)
    records = broker.encode_records(events.values()[order], pos)
    ends = np.append(starts[1:], n)
    return [(int(p[a]), int(g[a]), events.first + order[a:b], records[a:b])
            for a, b in zip(starts, ends)]


class Hmac:
    """HMAC-SHA256 from hashlib's SHA-256 alone (RFC 2104), with the two
    padded-key states hashed once: the reference for `mask_field`."""

    def __init__(self, key: bytes):
        if len(key) > 64:
            key = hashlib.sha256(key).digest()
        key = key.ljust(64, b"\x00")
        self._inner = hashlib.sha256(bytes(b ^ 0x36 for b in key))
        self._outer = hashlib.sha256(bytes(b ^ 0x5C for b in key))

    def hexdigest(self, msg: bytes) -> bytes:
        inner = self._inner.copy()
        inner.update(msg)
        outer = self._outer.copy()
        outer.update(inner.digest())
        return outer.hexdigest().encode()
