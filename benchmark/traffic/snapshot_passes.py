"""Traffic kind `snapshot_passes`: back-to-back snapshots of one table.

The window is a whole number of passes, `min_passes` at the least: a pass
that has started is finished, and the window's seconds are those that
elapsed.  A pass is
`trtpu activate` from its call to its return, after which the world's
ClickHouse is asked what it holds; the process-wide dictionary memos are
reset before each pass (a deployment runs one pass, and a second must not
be answered by the first one's memo - PERF.md section 6, PR 21, item 7).

The world keeps of every insert its exact row count and the rows whose key
falls into the seed's one-in-`sample_one_in` class; the comparison holds
every completed pass of the window to the reference's account of the source
files.

The table (rows, file_rows, batch_rows, the columns' file) is the
configuration's; fields of the cell's `params`: key, sample_one_in,
min_passes; the transformer chain is the cell's `transformation`.
"""

from __future__ import annotations

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

def drive(ctx) -> dict:
    from transferia_tpu.cli.main import main as trtpu
    from transferia_tpu.columnar.batch import reset_intern_cache
    from transferia_tpu.providers.parquet_native import reset_file_caches

    def one_pass(yaml_path: str) -> tuple[int, int, int]:
        reset_file_caches()
        reset_intern_cache()
        t0 = time.monotonic_ns()
        rc = trtpu(["--log-level", "warning", "activate",
                    "--transfer", yaml_path])
        return rc, t0, time.monotonic_ns()

    # warm: every program this cell's shapes need, over one part file
    rc, _t0, _t1 = one_pass(ctx.warm_yaml)
    warm = ctx.world("pass_end", in_window=False)
    if rc != 0 or not warm["rows"]:
        raise RuntimeError(f"warm pass: rc={rc}, landed {warm}")
    ctx.window_open()
    t_open = time.monotonic_ns()
    passes = []
    while True:
        if not passes:
            ctx.trace_start()
        rc, t0, t1 = one_pass(ctx.transfer_yaml)
        if not passes:
            ctx.trace_stop()
        got = ctx.world("pass_end", in_window=True)
        passes.append({"rc": rc, "seconds": (t1 - t0) / 1e9,
                       "rows_landed": got["rows"], "tables": got["tables"],
                       "standin_cost": got["standin_cost"],
                       "server_errors": got["server_errors"]})
        if time.monotonic_ns() - t_open >= ctx.seconds * 1e9 \
                and len(passes) >= int(ctx.cell["params"].get(
                    "min_passes", 1)):
            break
    t_close = time.monotonic_ns()
    ctx.window_close()
    return {"t_open_ns": t_open, "t_close_ns": t_close,
            "window_s": (t_close - t_open) / 1e9, "passes": passes,
            "source_rows_per_pass": int(ctx.config["table"]["rows"]),
            "rc_nonzero": sum(1 for p in passes if p["rc"] != 0)}


def end_to_end(account: dict) -> dict:
    rows = len(account["passes"]) * account["source_rows_per_pass"]
    return {"snapshot_rows_per_s": rows / account["window_s"]}


def window_rows(account: dict) -> int:
    return len(account["passes"]) * account["source_rows_per_pass"]


def account_numbers(account: dict) -> dict:
    return {"activate_rc_nonzero": [account["rc_nonzero"], 0],
            "sink_server_errors": [
                len(account["passes"][-1]["server_errors"]), 0]}
