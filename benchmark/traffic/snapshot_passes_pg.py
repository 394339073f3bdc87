"""Traffic kind `snapshot_passes_pg`: back-to-back snapshots of one
Postgres table into ClickHouse.

`snapshot_passes`' window, to the letter: a whole number of passes, it
closes at the first pass end at or after `--seconds`, not before
`min_passes` passes and, where the cell gives `max_passes`, not after that
many; a pass is `trtpu activate` from its call to its return, after which
the world's ClickHouse is asked what it holds; the process-wide memos are
reset before each pass; the trace runs over the window's first pass.  The
end-to-end numbers, the window's rows and the account's compared numbers
are `snapshot_passes`' own functions.

Warm-up is that kind's too, two passes before the window opens: one ctid
part alone through one part thread, then one whole pass of the transfer
itself.  The part pass is the transfer with an incremental cursor on
`ctid` (`regular_snapshot.incremental`, an option the program has: the
source then reads `WHERE "ctid" > '(page,0)'` as one unsplit part), the
page being where the table's last ctid part starts under the provider's
default part size.  What they land is taken out of the world and held to
nothing; the account says how long each took and what it compiled or
loaded.

The world builds the table from the seed (`tpchgen.py`, the columns and
distributions in the configuration's columns file), frames its COPY text
once, before the window opens, and starts both stand-ins.  The ClickHouse
one keeps every landed row (`sample_one_in` 1: some 1.9% of the table
passes the filter), and the comparison holds every completed pass of the
window, whole, to the reference's account of the generator's arrays
(`reference_lineitem.py`).  `standin_fault` (set by `control_pg.py` alone)
has the Postgres stand-in serve one row's `l_discount` a hundredth low.

The scale factor, the columns' file and the rows a heap page holds are the
configuration's; fields of the cell's `params`: key, sample_one_in (1),
min_passes, max_passes; the transformer chain is the cell's
`transformation`.
"""

from __future__ import annotations

import os
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmark import reference_lineitem, tpchgen
from benchmark.chserver import ClickHouseStandIn
from benchmark.pgserver import PAGE_BYTES, Heap, PostgresStandIn
from benchmark.traffic import snapshot_passes
from benchmark.traffic.snapshot_passes import (  # noqa: F401
    COMPILE_COUNTERS,
    account_numbers,
    end_to_end,
    window_rows,
)

_HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_CHUNK_ROWS = 262144
# providers/postgres/provider.py PGSourceParams.desired_part_size_bytes
_DEFAULT_PART_BYTES = 256 << 20


def framed_heap(table: dict) -> tuple[np.ndarray, np.ndarray]:
    """The whole table as the backend's CopyData stream, and the offset
    of every row's message in it: chunks written side by side."""
    n = table["rows"]
    with ThreadPoolExecutor(min(len(os.sched_getaffinity(0)), 12)) as pool:
        parts = list(pool.map(
            lambda lo: tpchgen.frame_rows(
                tpchgen.copy_text(table, lo, min(n, lo + _CHUNK_ROWS))),
            range(0, n, _CHUNK_ROWS)))
    base = np.cumsum([0] + [len(p[0]) for p in parts])
    offsets = np.concatenate(
        [p[1][:-1] + b for p, b in zip(parts, base)] + [base[-1:]])
    return np.concatenate([p[0] for p in parts]), offsets


def serve_a_discount_low(table: dict, framed: np.ndarray,
                         offsets: np.ndarray, filter_expr: str) -> int:
    """The control: the first row that passes the filter with the lowest
    discount it lets through is served one hundredth lower (`0.05` as
    `0.04`: the same length, so the framing stands).  The generator's
    arrays, which the reference reads, say what the table holds; the
    system, comparing exactly, drops the row.  Returns the row."""
    cols = table["cols"]
    passing = reference_lineitem.eval_filter(filter_expr, table)
    low = int(cols["l_discount"][passing].min())
    row = int(np.flatnonzero(passing & (cols["l_discount"] == low))[0])
    line = framed[offsets[row] + 5:offsets[row + 1]]
    fields = bytes(line).split(b",")
    at = table["names"].index("l_discount")
    old, new = b"%d.%02d" % divmod(low, 100), b"%d.%02d" % divmod(low - 1,
                                                                 100)
    if fields[at] != old or len(old) != len(new):
        raise RuntimeError(f"row {row}: discount field {fields[at]!r}")
    start = sum(len(f) + 1 for f in fields[:at])
    line[start:start + len(new)] = np.frombuffer(new, dtype=np.uint8)
    return row


class World(snapshot_passes.World):
    def __init__(self, cell: dict, config: dict, seed: int, seconds: float,
                 work_dir: str):
        self.cell = cell
        p = cell["params"]
        spec = tpchgen.load_columns(
            os.path.join(_HERE, "configs", config["columns"]))
        if list(p["key"]) != spec["key"] or int(p["sample_one_in"]) != 1:
            raise ValueError("snapshot_passes_pg compares every landed row "
                             f"by {spec['key']}")
        t0 = time.monotonic()
        self.table = tpchgen.generate(seed, float(config["scale_factor"]),
                                      spec)
        framed, offsets = framed_heap(self.table)
        self.fault_row = None
        if p.get("standin_fault"):
            self.fault_row = serve_a_discount_low(
                self.table, framed, offsets, self._filter())
        self.build_seconds = time.monotonic() - t0
        self.copy_bytes = len(framed) - 5 * self.table["rows"]
        self.password = f"pw-{seed}"
        self.pg = PostgresStandIn(self.password)
        key = set(spec["key"])
        self.heap = Heap(
            spec["schema"], spec["table"],
            [(c["name"], c["pg"], c["name"] in key, True)
             for c in spec["columns"]],
            framed, offsets, int(spec["rows_per_heap_page"]))
        self.pg.add(self.heap)
        self.pg.start()
        self.ch = ClickHouseStandIn().start()
        self.passes: list[dict] = []

    def _filter(self) -> str:
        filters = [t["filter_rows"]["filter"]
                   for t in self.cell["transformation"]["transformers"]
                   if "filter_rows" in t]
        if len(filters) != 1:
            raise ValueError("snapshot_passes_pg: the reference reads "
                             "exactly one filter_rows step")
        return filters[0]

    def endpoints(self) -> dict:
        # where the last ctid part starts, by the source's own rule at
        # its default part size (provider.py shard_table)
        pages = self.heap.pages
        parts = min(-(-pages * PAGE_BYTES // _DEFAULT_PART_BYTES), 64)
        per = -(-pages // parts)
        return {"PG_HOST": "127.0.0.1", "PG_PORT": self.pg.port,
                "PG_DB": "tpch", "PG_PASSWORD": self.password,
                "CH_HOST": "127.0.0.1", "CH_PORT": self.ch.port,
                "SOURCE_ROWS": self.table["rows"],
                "PG_SCHEMA": self.heap.schema, "PG_TABLE": self.heap.name,
                "LAST_PART_PAGE": (parts - 1) * per, "CTID_PARTS": parts}

    def cmd_pass_end(self, in_window: bool) -> dict:
        out = super().cmd_pass_end(in_window)
        with self.pg.lock:
            out["standin_cost"].update(
                {f"pg_{k}": v for k, v in self.pg.cost.items()})
            out["server_errors"] += self.pg.errors
        return out

    def cmd_verify(self) -> dict:
        t0 = time.monotonic()
        expected = reference_lineitem.expected_rows(self.table,
                                                    self._filter())
        out = reference_lineitem.compare_snapshot(self.passes, expected)
        out["info"].update(
            reference_seconds=time.monotonic() - t0,
            table_build_seconds=self.build_seconds,
            copy_bytes_per_row=self.copy_bytes / max(self.table["rows"], 1),
            ctid_parts=self.endpoints()["CTID_PARTS"],
            standin_fault_row=self.fault_row)
        return out

    def close(self) -> None:
        self.pg.stop()
        self.ch.stop()


# -- system side -----------------------------------------------------------------------

def render_part_pass(ctx) -> str:
    """The transfer with an incremental cursor on `ctid` at the page where
    the table's last ctid part starts: one part, one part thread."""
    import yaml

    with open(ctx.transfer_yaml) as fh:
        doc = yaml.safe_load(fh)
    ep = ctx.world.endpoints
    doc["regular_snapshot"] = {"incremental": [{
        "namespace": ep["PG_SCHEMA"], "name": ep["PG_TABLE"],
        "cursor_field": "ctid",
        "initial_state": f"({ep['LAST_PART_PAGE']},0)"}]}
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

    # warm: one ctid part alone, then one whole pass (the module's docstring)
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
    rows = int(ctx.world.endpoints["SOURCE_ROWS"])
    return {"t_open_ns": t_open, "t_close_ns": t_close,
            "window_s": (t_close - t_open) / 1e9, "passes": passes,
            "warm_part_seconds": warm[0][0], "warm_part_telemetry": warm[0][1],
            "warm_pass_seconds": warm[1][0], "warm_telemetry": warm[1][1],
            "source_rows_per_pass": rows,
            "window_rows": len(passes) * rows,
            "pass_seconds_sum": sum(p["seconds"] for p in passes),
            "rc_nonzero": sum(1 for p in passes if p["rc"] != 0)}
