"""Traffic kind `snapshot_passes_jsonl`: back-to-back snapshots of one table
that lies in a directory of JSON-lines objects, into ClickHouse.

`snapshot_passes`' window, to the letter: a whole number of passes, it
closes at the first pass end at or after `--seconds`, not before
`min_passes` passes and, where the cell gives `max_passes`, not after that
many; a pass is `trtpu activate` from its call to its return, after which
the world's ClickHouse is asked what it holds; the process-wide memos are
reset before each pass; the trace runs over the window's first pass.  The
end-to-end numbers, the window's rows and the account's compared numbers
are `snapshot_passes`' own functions, and so is the comparison: the
world's `verify` is that kind's, on the parquet part files.

Warm-up is that kind's too, two passes before the window opens: one object
alone through one part thread (the transfer with the one-object directory
as its path), then one whole pass of the transfer itself.  What they land
is taken out of the world and held to nothing; the account says how long
each took and what it compiled or loaded.  After the first of them the
table the sink created is looked at: a program that does not take the
declared `output_schema` infers every integer as Int64 and every time as
String, lands that, and is stopped there (a configuration error) - the
window would compare nothing of use and a pass by the row-by-row path
takes minutes.

The world writes the table twice, before it answers: as parquet part files
(`datagen.py`, the truth the reference reads after the window) and, from
those files, as JSONEachRow text (`jsonlgen.py`), an object a part file.
`text_fault` (a column's name, set by `control_jsonl.py` alone) alters one
digit of that column in one line of the text after both are written: a
row the filter keeps and the comparison samples.

The table (rows, file_rows, batch_rows, the columns' file) is the
configuration's `source_table`; fields of the cell's `params`: key,
sample_one_in, min_passes, max_passes; the transformer chain is the
cell's `transformation`.
"""

from __future__ import annotations

import json
import os
import re
import time

from benchmark import datagen, jsonlgen
from benchmark.traffic import snapshot_passes
from benchmark.traffic.snapshot_passes import (  # noqa: F401
    COMPILE_COUNTERS,
    account_numbers,
    end_to_end,
    window_rows,
)

_HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the checking machine refuses a file over 128 MiB
FILE_LIMIT_BYTES = 118 << 20
# create.sql's types as the sink's ClickHouse table has to carry them
CH_TYPES = {"int16": "Int16", "int32": "Int32", "int64": "Int64",
            "string": "String", "timestamp": "DateTime", "date": "Date32"}


def alter_a_digit(text_path: str, row: int, column: str
                   ) -> tuple[int, str, str]:
    """The control: in line `row` of the object, the last digit of
    `column`'s value is changed (9 -> 8, any other one up), in place and
    at the same length.  Returns (the row, the value before, after)."""
    with open(text_path, "r+b") as fh:
        data = fh.read()
        at = 0
        for _ in range(row):
            at = data.index(b"\n", at) + 1
        m = re.compile(rb'"' + column.encode() + rb'":"?-?(\d+)').search(
            data, at, data.index(b"\n", at))
        if m is None:
            raise RuntimeError(f"{text_path}:{row}: no number in {column}")
        old = m.group(1)
        new = old[:-1] + (b"8" if old.endswith(b"9")
                          else bytes([old[-1] + 1]))
        fh.seek(m.end(1) - 1)
        fh.write(new[-1:])
    return row, old.decode(), new.decode()


class World(snapshot_passes.World):
    def __init__(self, cell: dict, config: dict, seed: int, seconds: float,
                 work_dir: str):
        super().__init__(cell, config, seed, seconds, work_dir)
        t = config["source_table"]
        columns_file = os.path.join(_HERE, "configs", t["columns"])
        workers = min(len(os.sched_getaffinity(0)), 12)
        t0 = time.monotonic()
        self.files = datagen.generate(
            os.path.join(work_dir, "hits"), seed, int(t["rows"]),
            int(t["file_rows"]), int(t["batch_rows"]), workers=workers,
            columns_file=columns_file)
        t1 = time.monotonic()
        self.text_dir = os.path.join(work_dir, "hits-jsonl")
        self.text = jsonlgen.generate(self.files, self.text_dir, workers)
        self.build_seconds = {"parquet": t1 - t0,
                              "jsonl": time.monotonic() - t1}
        self.rows = sum(n for _, n, _ in self.text)
        if self.rows != int(t["rows"]):
            raise RuntimeError(f"wrote {self.rows} rows of {t['rows']}")
        big = max(size for _, _, size in self.text)
        if big > FILE_LIMIT_BYTES:
            raise RuntimeError(
                f"an object of {big} bytes: over {FILE_LIMIT_BYTES}, take "
                f"fewer file_rows")
        self.warm_dir = os.path.join(work_dir, "hits-jsonl-warm")
        os.makedirs(self.warm_dir)
        first = self.text[0][0]
        os.link(first, os.path.join(self.warm_dir, os.path.basename(first)))
        self.fault = None
        if cell["params"].get("text_fault"):
            self.fault = self._plant(cell["params"]["text_fault"])
        with open(columns_file) as fh:
            self.ch_types = {c["name"]: CH_TYPES[c["type"]]
                             for c in json.load(fh)["columns"]}

    def _plant(self, column: str) -> tuple[int, str, str]:
        """One digit of `column` altered in the text of the first row of
        the last object (the warm one is a link to the first) that the
        filter keeps and the comparison samples: the truth says what the
        source held, the text what the system read."""
        import pyarrow.parquet as pq

        from benchmark import reference

        chain = self.cell["transformation"]["transformers"]
        expr = next(t["filter_rows"]["filter"] for t in chain
                    if "filter_rows" in t)
        t = pq.read_table(self.files[-1])
        seen = reference.eval_filter(expr, lambda n: t[n].to_numpy()) \
            & self.keep({self.key: t[self.key].to_numpy()})
        return alter_a_digit(self.text[-1][0], int(seen.argmax()), column)

    def endpoints(self) -> dict:
        return {**super().endpoints(), "JSONL_PATH": self.text_dir,
                "JSONL_WARM_PATH": self.warm_dir, "SOURCE_ROWS": self.rows,
                "SOURCE_BYTES": sum(size for _, _, size in self.text)}

    def cmd_pass_end(self, in_window: bool) -> dict:
        """`snapshot_passes`', and how many of the landed table's columns
        are not create.sql's type (Nullable or not)."""
        tables = self.ch.data_tables()
        with self.ch.lock:
            landed = {n: t for name in tables
                      for n, t in self.ch.tables[name].columns.items()}
        out = super().cmd_pass_end(in_window)
        wrong = {n: landed.get(n) for n, t in self.ch_types.items()
                 if landed.get(n) not in (t, f"Nullable({t})")}
        out["ch_types_wrong"] = dict(list(wrong.items())[:8])
        if in_window:
            self.passes[-1]["ch_types_wrong"] = len(wrong)
        return out

    def cmd_verify(self) -> dict:
        out = super().cmd_verify(self.files)
        out["numbers"]["ch_types_wrong"] = [
            sum(p["ch_types_wrong"] for p in self.passes), 0]
        out["info"].update(
            table_build_seconds=self.build_seconds,
            object_bytes_max=max(size for _, _, size in self.text),
            objects=len(self.text), text_fault=self.fault)
        return out


# -- system side -----------------------------------------------------------------------

def render_part_pass(ctx) -> str:
    """The transfer with the one-object directory as its path: one part,
    one part thread."""
    import yaml

    with open(ctx.transfer_yaml) as fh:
        doc = yaml.safe_load(fh)
    doc["src"]["params"]["path"] = ctx.world.endpoints["JSONL_WARM_PATH"]
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

    # warm: one object alone, then one whole pass (the module's docstring)
    ctx.warm_yaml = render_part_pass(ctx)
    warm = []
    for yaml_path in (ctx.warm_yaml, ctx.transfer_yaml):
        rc, seconds, compiled = one_pass(yaml_path)
        landed = ctx.world("pass_end", in_window=False)
        if rc != 0 or not landed["rows"]:
            raise RuntimeError(f"warm pass: rc={rc}, landed {landed}")
        if landed["ch_types_wrong"]:
            raise SystemExit(
                "configuration error: the sink's table is not the declared "
                "output_schema's (the program did not take it): "
                f"{landed['ch_types_wrong']}")
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
            "source_bytes_per_pass": int(
                ctx.world.endpoints["SOURCE_BYTES"]),
            "window_rows": len(passes) * rows,
            "pass_seconds_sum": sum(p["seconds"] for p in passes),
            "rc_nonzero": sum(1 for p in passes if p["rc"] != 0)}
