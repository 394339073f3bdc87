"""Traffic kind `snapshot_passes_mysql2kafka`: back-to-back snapshots of a
whole MySQL database into one Kafka topic as Debezium envelopes.

`snapshot_passes`' window, to the letter: a whole number of passes, it
closes at the first pass end at or after `--seconds`, not before
`min_passes` passes and, where the cell gives `max_passes`, not after that
many; a pass is `trtpu activate` from its call to its return, after which
the world's broker is asked what it holds; the process-wide memos are
reset before each pass; the trace runs over the window's first pass.  The
end-to-end numbers, the window's rows and the account's compared numbers
are `snapshot_passes`' own functions.

Warm-up is that kind's too, two passes before the window opens: one part
alone through one part thread, then one whole pass of the transfer itself.
The part pass is the transfer with its include list cut to the one masked
table and an incremental cursor on that table's district column
(`regular_snapshot.incremental`, an option the program has: the source
then reads `WHERE c_d_id > 8` as one unsplit part), so it meets the mask's
program and nothing else.  What they land is taken out of the world and
held to nothing; the account says how long each took and what it compiled
or loaded.

The world builds the database from the seed (`tpccgen.py`, the tables and
distributions in the configuration's columns file), frames every table's
row packets once, before the window opens, and starts both stand-ins
(`mysqlserver.py`, `broker_produce.py`).  After a pass everything the
broker holds is taken out of it; a thread of the world decodes the
window's passes behind the system's back (`reference_tpcc.PassDigest`) and
the comparison, once the window has closed, holds every completed pass to
the reference's account of the generator's arrays.  `standin_fault` (set
by `control_tpcc.py` alone): `served_balance_low` has the MySQL stand-in
serve one sampled customer's `c_balance` a cent low, `dropped_acked_record`
has the broker lose one record of the window's first publish after
acknowledging it.

The warehouses, the columns' file, topic and partitions are the
configuration's; fields of the cell's `params`: sample_one_in, min_passes,
max_passes; the transformer chain is the cell's `transformation`.
"""

from __future__ import annotations

import os
import queue
import struct
import threading
import time

import numpy as np

from benchmark import reference_tpcc, tpccgen
from benchmark.broker_produce import ProduceBroker
from benchmark.mysqlserver import MySQLStandIn, Table
from benchmark.traffic.snapshot_passes import (  # noqa: F401
    COMPILE_COUNTERS,
    account_numbers,
    end_to_end,
    window_rows,
)

_HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WARM_TABLE, WARM_CURSOR, WARM_AFTER = "customer", "c_d_id", 8


def serve_a_balance_low(table: dict, texts: list, row: int) -> None:
    """The control: `row`'s c_balance is served a cent lower than the
    generator's arrays, which the reference reads, hold it."""
    import pyarrow as pa

    at = [c["name"] for c in table["columns"]].index("c_balance")
    scale = table["columns"][at]["scale"]
    low = tpccgen.decimal_text(
        np.asarray([table["cols"]["c_balance"][row] - 1]), scale)[0].as_py()
    values = texts[at].to_pylist()
    values[row] = low
    texts[at] = pa.array(values, type=pa.large_string())


class World:
    def __init__(self, cell: dict, config: dict, seed: int, seconds: float,
                 work_dir: str):
        self.cell = cell
        p = cell["params"]
        self.seed = seed
        self.one_in = int(p["sample_one_in"])
        self.spec = tpccgen.load_columns(
            os.path.join(_HERE, "configs", config["columns"]))
        # a rehearsal's smaller population (the tests' `shrink` alone)
        small = dict(config.get("population", {}))
        if "items" in small:
            self.spec["items"] = small.pop("items")
        self.spec["per_warehouse"].update(small)
        self.config = config
        fault = p.get("standin_fault")
        if fault not in (None, "served_balance_low",
                         "dropped_acked_record"):
            raise ValueError(f"unknown standin_fault {fault!r}")
        t0 = time.monotonic()
        self.db = tpccgen.generate(seed, int(config["warehouses"]),
                                   self.spec)
        self.salt = f"salt-{seed}"
        self.password = f"pw-{seed}"
        self.expected = reference_tpcc.Expected(
            self.db, self.spec, config, self._masked(), self.salt.encode(),
            seed, self.one_in)
        self.mysql = MySQLStandIn(password=self.password)
        self.fault_row = None
        self.text_bytes = 0
        for name, t in self.db.items():
            texts = tpccgen.text_columns(t)
            if fault == "served_balance_low" and name == WARM_TABLE:
                # a sampled row outside the warm part's range
                rows = self.expected.sample_rows[name]
                self.fault_row = int(rows[
                    t["cols"][WARM_CURSOR][rows] <= WARM_AFTER][0])
                serve_a_balance_low(t, texts, self.fault_row)
            framed, offsets = tpccgen.frame_rows(texts)
            self.text_bytes += len(framed) - 4 * t["rows"]
            ints = {c["name"]: t["cols"][c["name"]] for c in t["columns"]
                    if c["kind"] == "int"
                    and c["name"] not in t["nulls"]}
            self.mysql.add(Table(
                self.spec["database"], name,
                [(c["name"], c["mysql"], c["name"] in t["nulls"])
                 for c in t["columns"]],
                list(t["key"]), ints, framed, offsets))
        self.mysql.start()
        self.broker = ProduceBroker(
            config["topic"], int(config["partitions"])).start()
        self._arm_broker_fault = fault == "dropped_acked_record"
        self._warm_ends = 0
        self.build_seconds = time.monotonic() - t0
        self.digests: list[reference_tpcc.PassDigest] = []
        self._work: "queue.Queue" = queue.Queue()
        self._digest_error: str | None = None
        self._worker = threading.Thread(target=self._digest_loop,
                                        name="digest", daemon=True)
        self._worker.start()
        self._cost0: dict = {}

    def _masked(self) -> dict:
        """{table: [columns]} of the cell's mask_field steps."""
        out: dict[str, list] = {}
        for t in self.cell["transformation"]["transformers"]:
            if "mask_field" not in t:
                raise ValueError("snapshot_passes_mysql2kafka: the "
                                 "reference reads mask_field steps alone")
            tables = t["mask_field"].get("tables")
            if not tables:
                raise ValueError("mask_field without `tables`")
            for name in tables:
                out.setdefault(name.split(".")[-1], []).extend(
                    t["mask_field"]["columns"])
        return out

    def endpoints(self) -> dict:
        masked = sum(self.db[t]["rows"] * len(cols)
                     for t, cols in self._masked().items())
        return {"MYSQL_HOST": "127.0.0.1", "MYSQL_PORT": self.mysql.port,
                "MYSQL_DB": self.spec["database"],
                "MYSQL_PASSWORD": self.password,
                "KAFKA_BROKERS": f"127.0.0.1:{self.broker.port}",
                "MASK_SALT": self.salt,
                "SOURCE_ROWS": self.expected.rows,
                "MASKED_CELLS": masked}

    def _digest_loop(self) -> None:
        while True:
            item = self._work.get()
            if item is None:
                return
            try:
                digest = reference_tpcc.PassDigest(self.spec, self.seed,
                                                   self.one_in)
                for partition, blob in item:
                    digest.add(partition, blob)
                self.digests.append(digest)
            except Exception:
                import traceback

                self._digest_error = traceback.format_exc()
            finally:
                self._work.task_done()

    def cmd_pass_end(self, in_window: bool) -> dict:
        """What the pass landed, taken out of the broker and counted."""
        batches, cost = self.broker.take()
        rows = sum(struct.unpack_from("!i", blob, 57)[0]
                   for _p, blob in batches)
        with self.mysql.lock:
            cost.update({f"mysql_{k}": v
                         for k, v in self.mysql.cost.items()})
            errors = list(self.mysql.errors)
        errors += self.broker.errors
        if in_window:
            self._work.put(batches)
        else:
            self._warm_ends += 1
            if self._arm_broker_fault and self._warm_ends == 2:
                # armed once both warm passes are over: the window's
                # first publish
                self.broker.drop_one_acked_record = True
        # this pass's share of the counters, which run on
        out = {k: v - self._cost0.get(k, 0) for k, v in cost.items()}
        self._cost0 = cost
        return {"rows": rows, "tables": [], "standin_cost": out,
                "server_errors": errors}

    def cmd_verify(self) -> dict:
        t0 = time.monotonic()
        self._work.join()
        waited = time.monotonic() - t0
        if self._digest_error:
            raise RuntimeError("the digest thread failed:\n"
                               + self._digest_error)
        out = reference_tpcc.compare_snapshot(
            self.expected, self.digests, int(self.config["partitions"]))
        out["info"].update(
            reference_seconds=time.monotonic() - t0 - waited,
            digest_wait_seconds=waited,
            database_build_seconds=self.build_seconds,
            text_bytes_per_row=self.text_bytes / max(self.expected.rows, 1),
            sha_block_bytes_per_row=self.expected.sha_block_bytes_per_row(),
            tables={n: t["rows"] for n, t in self.db.items()},
            standin_fault_row=self.fault_row,
            standin_dropped=list(self.broker.dropped))
        return out

    def close(self) -> None:
        self._work.put(None)
        self.mysql.stop()
        self.broker.stop()


# -- system side -----------------------------------------------------------------------

def render_part_pass(ctx) -> str:
    """The transfer with the masked table alone and an incremental cursor
    on its district column: one part, one part thread."""
    import yaml

    with open(ctx.transfer_yaml) as fh:
        doc = yaml.safe_load(fh)
    db = ctx.world.endpoints["MYSQL_DB"]
    doc["data_objects"] = [f"{db}.{WARM_TABLE}"]
    doc["regular_snapshot"] = {"incremental": [{
        "namespace": db, "name": WARM_TABLE, "cursor_field": WARM_CURSOR,
        "initial_state": str(WARM_AFTER)}]}
    out = os.path.join(ctx.work_dir, "transfer-warm.yaml")
    with open(out, "w") as fh:
        yaml.safe_dump(doc, fh)
    return out


def drive(ctx) -> dict:
    from transferia_tpu.cli.main import main as trtpu
    from transferia_tpu.columnar.batch import reset_intern_cache
    from transferia_tpu.stats.trace import TELEMETRY

    def one_pass(yaml_path: str) -> tuple[int, float, dict]:
        """(exit code, seconds, what it compiled or loaded)"""
        reset_intern_cache()
        before = TELEMETRY.snapshot()
        t0 = time.monotonic_ns()
        rc = trtpu(["--log-level", "warning", "activate",
                    "--transfer", yaml_path])
        seconds = (time.monotonic_ns() - t0) / 1e9
        after = TELEMETRY.snapshot()
        return rc, seconds, {k: after.get(k, 0) - before.get(k, 0)
                             for k in COMPILE_COUNTERS}

    # warm: one part alone, then one whole pass (the module's docstring)
    ctx.warm_yaml = render_part_pass(ctx)
    warm = []
    for yaml_path in (ctx.warm_yaml, ctx.transfer_yaml):
        rc, seconds, compiled = one_pass(yaml_path)
        landed = ctx.world("pass_end", in_window=False)
        if rc != 0 or not landed["rows"]:
            raise RuntimeError(f"warm pass: rc={rc}, landed {landed}")
        warm.append((seconds, compiled))
    params = ctx.cell["params"]
    min_passes = int(params.get("min_passes", 1))
    max_passes = int(params.get("max_passes", 0))      # 0: no cap
    ctx.window_open()
    t_open = time.monotonic_ns()
    passes = []
    while True:
        if not passes:
            ctx.trace_start()
        rc, seconds, compiled = one_pass(ctx.transfer_yaml)
        if not passes:
            ctx.trace_stop()
        got = ctx.world("pass_end", in_window=True)
        passes.append({"rc": rc, "seconds": seconds, "compiled": compiled,
                       "rows_landed": got["rows"], "tables": got["tables"],
                       "standin_cost": got["standin_cost"],
                       "server_errors": got["server_errors"]})
        if len(passes) == max_passes or (
                len(passes) >= min_passes
                and time.monotonic_ns() - t_open >= ctx.seconds * 1e9):
            break
    t_close = time.monotonic_ns()
    ctx.window_close()
    ep = ctx.world.endpoints
    rows = int(ep["SOURCE_ROWS"])
    cost = [p["standin_cost"] for p in passes]
    return {"t_open_ns": t_open, "t_close_ns": t_close,
            "window_s": (t_close - t_open) / 1e9, "passes": passes,
            "warm_part_seconds": warm[0][0], "warm_part_telemetry": warm[0][1],
            "warm_pass_seconds": warm[1][0], "warm_telemetry": warm[1][1],
            "source_rows_per_pass": rows,
            "window_rows": len(passes) * rows,
            "masked_cells_in_window": len(passes) * int(ep["MASKED_CELLS"]),
            "produce_requests": sum(c["produce_requests"] for c in cost),
            "produce_bytes": sum(c["produce_bytes"] for c in cost),
            "records_landed": sum(c["records"] for c in cost),
            "rows_skipped_by_offset": sum(
                c["mysql_rows_skipped_by_offset"] for c in cost),
            "offset_statements": sum(
                c["mysql_offset_statements"] for c in cost),
            "pass_seconds_sum": sum(p["seconds"] for p in passes),
            "rc_nonzero": sum(1 for p in passes if p["rc"] != 0)}
