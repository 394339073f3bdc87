"""Faults planted in the timed path, underneath the harness: the controls
that the comparison has to fail, and the faults a cell can have.

Each breaks the program under test in this process (never the reference,
the world or the harness) at the place where an answer is produced - the
ClickHouse sink and its client:

  drop_insert       the control for at-least-once (kafka2ch): one INSERT is
                    acknowledged and never written, as a sink that confirms
                    before it writes, or offsets committed ahead of it;
  duplicate_insert  the control for exactly-once (clickbench-parquet2ch):
                    one INSERT is written twice, as a retry with no fence;
  half_batch        half of one batch left out of what the sink writes;
  alter_answer      the masked values of one batch altered where they are
                    produced (one hex digit of every digest in one INSERT,
                    as a wrong key for one launch would; a snapshot cell
                    compares a sample of the rows, so one altered value
                    alone would be seen one time in `sample_one_in`).

`benchmark/control.py` runs a cell with one of them on the chip;
`benchmark/tests/` does at a size a test run can hold.  The benchmark's own
runs never import this module.
"""

from __future__ import annotations

import re
import threading

NAMES = ("drop_insert", "duplicate_insert", "half_batch", "alter_answer")
_HEX64 = re.compile(rb"[0-9a-f]{64}")


def plant(name: str, nth: int = 2):
    """Plant the fault on the `nth` data insert (or batch) of the run;
    returns a function that takes it out again."""
    from transferia_tpu.providers.clickhouse.client import CHClient
    from transferia_tpu.providers.clickhouse.provider import CHSinker

    if name not in NAMES:
        raise ValueError(f"unknown fault {name!r}; one of {NAMES}")
    lock = threading.Lock()
    seen = {"n": 0, "fired": 0}

    def due() -> bool:
        with lock:
            seen["n"] += 1
            if seen["n"] == nth:
                seen["fired"] += 1
                return True
            return False

    insert = CHClient.insert_rowbinary
    push = CHSinker.push

    def faulty_insert(self, table, columns, payload):
        if table.startswith("__trtpu_commits") or not due():
            return insert(self, table, columns, payload)
        if name == "drop_insert":
            return None
        if name == "duplicate_insert":
            insert(self, table, columns, payload)
            return insert(self, table, columns, payload)
        altered = _HEX64.sub(      # alter_answer
            lambda m: (b"1" if m.group()[:1] == b"0" else b"0")
            + m.group()[1:], payload)
        if altered == payload:
            with lock:               # no digest in this one: take the next
                seen["n"] -= 1
                seen["fired"] -= 1
        return insert(self, table, columns, altered)

    def faulty_push(self, batch):
        import numpy as np

        n = getattr(batch, "n_rows", 0)
        if n >= 2 and due():
            batch = batch.filter(np.arange(n) < n // 2)
        return push(self, batch)

    if name == "half_batch":
        CHSinker.push = faulty_push
    else:
        CHClient.insert_rowbinary = faulty_insert

    def remove() -> int:
        CHClient.insert_rowbinary = insert
        CHSinker.push = push
        return seen["fired"]

    return remove
