"""Traffic kind `snapshot_passes`: back-to-back snapshots of one table.

The window is a whole number of passes: a pass that has started is
finished, and the window's seconds are those that elapsed.  It closes at the
first pass end at or after `--seconds`, not before `min_passes` passes and,
where the cell gives `max_passes`, not after that many: a cell whose passes
change as the process ages (PERF.md section 6, PR 28: the pii cell's fifth
is a quarter shorter than its first four and meets programs they do not)
gives both the same number, so that every run does the same work and none
adds a pass where the others stop.  A pass is
`trtpu activate` from its call to its return, after which the world's
ClickHouse is asked what it holds; the process-wide dictionary memos are
reset before each pass (a deployment runs one pass, and a second must not
be answered by the first one's memo - PERF.md section 6, PR 21, item 7).

Warm-up is two passes before the window opens: the first part file alone
through one part thread (`ctx.warm_yaml`), then one whole pass of the
transfer itself - every part file through every part thread, the bufferer
merging batches as it will in the window.  Which programs a pass meets
follows how its batches were merged and cut into chunks, so neither pass
alone meets all that the window will; after both the window seldom meets
one (PERF.md section 6, PR 28, has the runs).  What they land is taken out
of the world and held to nothing; the account says how long each took and
what it compiled or loaded
(`warm_part_seconds`, `warm_part_telemetry`, `warm_pass_seconds`,
`warm_telemetry`), and every window pass says the same of itself
(`compiled`).

The world keeps of every insert its exact row count and the rows whose key
falls into the seed's one-in-`sample_one_in` class; the comparison holds
every completed pass of the window to the reference's account of the source
files.

The table (rows, file_rows, batch_rows, the columns' file) is the
configuration's; fields of the cell's `params`: key, sample_one_in,
min_passes, max_passes; the transformer chain is the cell's
`transformation`.
"""

from __future__ import annotations

import statistics
import time

from benchmark import events as ev
from benchmark import reference
from benchmark.chserver import ClickHouseStandIn


class World:
    def __init__(self, cell: dict, config: dict, seed: int, seconds: float,
                 work_dir: str):
        self.cell = cell
        p = cell["params"]
        self.key = p["key"]
        self.keep = reference.key_sampler(self.key, int(p["sample_one_in"]),
                                          seed)
        self.ch = ClickHouseStandIn(keep=self.keep).start()
        self.salt = f"salt-{seed}"
        self.passes: list[dict] = []

    def endpoints(self) -> dict:
        return {"CH_HOST": "127.0.0.1", "CH_PORT": self.ch.port,
                "MASK_SALT": self.salt}

    def cmd_pass_end(self, in_window: bool) -> dict:
        """What the pass landed, taken out of the tables and counted."""
        tables = self.ch.data_tables()
        ch_types: dict = {}
        with self.ch.lock:
            for t in tables:
                ch_types.update(self.ch.tables[t].columns)
        inserts = [i for t in tables for i in self.ch.take_inserts(t)]
        rows = sum(i.rows for i in inserts)
        if in_window:
            self.passes.append({"inserts": inserts, "ch_types": ch_types,
                                "tables": tables})
        with self.ch.lock:
            cost = dict(self.ch.cost)
        return {"rows": rows, "tables": tables, "standin_cost": cost,
                "server_errors": list(self.ch.errors)}

    def cmd_verify(self, files: list[str]) -> dict:
        chain = self.cell["transformation"]["transformers"]
        masked = [c for t in chain if "mask_field" in t
                  for c in t["mask_field"]["columns"]]
        filters = [t["filter_rows"]["filter"] for t in chain
                   if "filter_rows" in t]
        if len(filters) != 1:
            raise ValueError("snapshot_passes: the reference reads exactly "
                             "one filter_rows step")
        t0 = time.monotonic()
        expected = reference.expected_from_source(
            files, filters[0], masked, self.key, self.keep,
            ev.Hmac(self.salt.encode()))
        out = reference.compare_snapshot(self.passes, expected)
        out["info"]["reference_seconds"] = time.monotonic() - t0
        out["info"]["sha_block_bytes_per_row"] = \
            expected["sha_block_bytes_per_row"]
        out["info"]["source_rows"] = expected["source_rows"]
        return out

    def close(self) -> None:
        self.ch.stop()


# -- system side -----------------------------------------------------------------------

COMPILE_COUNTERS = ("compile_events", "compile_cache_hits", "compile_seconds",
                    "compile_cache_seconds")


def drive(ctx) -> dict:
    from transferia_tpu.cli.main import main as trtpu
    from transferia_tpu.columnar.batch import reset_intern_cache
    from transferia_tpu.providers.parquet_native import reset_file_caches
    from transferia_tpu.stats.trace import TELEMETRY

    def one_pass(yaml_path: str) -> tuple[int, float, dict]:
        """(exit code, seconds, what it compiled or loaded)"""
        reset_file_caches()
        reset_intern_cache()
        before = TELEMETRY.snapshot()
        t0 = time.monotonic_ns()
        rc = trtpu(["--log-level", "warning", "activate",
                    "--transfer", yaml_path])
        seconds = (time.monotonic_ns() - t0) / 1e9
        after = TELEMETRY.snapshot()
        return rc, seconds, {k: after.get(k, 0) - before.get(k, 0)
                             for k in COMPILE_COUNTERS}

    # warm: one part file alone, then one whole pass (the module's docstring)
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
    return {"t_open_ns": t_open, "t_close_ns": t_close,
            "window_s": (t_close - t_open) / 1e9, "passes": passes,
            "warm_part_seconds": warm[0][0], "warm_part_telemetry": warm[0][1],
            "warm_pass_seconds": warm[1][0], "warm_telemetry": warm[1][1],
            "source_rows_per_pass": int(ctx.config["table"]["rows"]),
            "window_rows": len(passes) * int(ctx.config["table"]["rows"]),
            "pass_seconds_sum": sum(p["seconds"] for p in passes),
            "rc_nonzero": sum(1 for p in passes if p["rc"] != 0)}


def end_to_end(account: dict) -> dict:
    """Both numbers of every window; BENCHMARK.json says which of them a
    cell reports end to end.  The rate is all the window's rows over all its
    seconds.  The median pass is what a cell reports whose window now and
    then meets a program that warm-up did not (PERF.md section 6, PR 28):
    that pass takes 4-14 s more, the rate of a four-pass window falls by
    12-27% with it, and the median of the four does not move."""
    return {"snapshot_rows_per_s":
            window_rows(account) / account["window_s"],
            "snapshot_pass_p50_s":
            statistics.median(p["seconds"] for p in account["passes"])}


def window_rows(account: dict) -> int:
    return len(account["passes"]) * account["source_rows_per_pass"]


def account_numbers(account: dict) -> dict:
    return {"activate_rc_nonzero": [account["rc_nonzero"], 0],
            "sink_server_errors": [
                len(account["passes"][-1]["server_errors"]), 0]}
