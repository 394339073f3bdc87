"""The benchmark's ClickHouse stand-in: the HTTP interface, as far as the
ClickHouse sink of the system under test speaks it.

A copy in spirit of `tests/recipes/fake_clickhouse.py` (later PRs may edit
`tests/`, so the benchmark keeps its own), rebuilt around what a benchmark
needs from it:

  * every INSERT is walked by the native RowBinary walker (rowbinary.py):
    its rows are counted exactly and decoded to columns inside the request,
    as a server parses what it is sent;
  * a data table keeps, of each insert, the row count, the arrival time on
    this process's clock, and the rows that `keep` selects (all of them, or
    a sample by key drawn from the seed): a pass of the wide table is 2.3 GB
    of RowBinary, which is not kept;
  * `ALTER TABLE .. REPLACE PARTITION ID .. FROM ..` moves the staged
    inserts, without touching a row: that is the publish of the sink's
    staged commit, and the moment its rows become visible.

Tables whose name starts with `__` belong to the sink's own machinery
(`__trtpu_commits`, `__trtpu_stg_*`) and are never counted as delivered
data; a staged part's rows are counted when its publish makes them visible.
"""

from __future__ import annotations

import json
import re
import threading
import time
import urllib.parse
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np

from benchmark import rowbinary


class Insert:
    __slots__ = ("rows", "arrival_ns", "cols", "masks")

    def __init__(self, rows, arrival_ns, cols, masks):
        self.rows = rows
        self.arrival_ns = arrival_ns
        self.cols = cols      # {name: ndarray | LargeBinaryArray}, kept rows
        self.masks = masks    # {name: bool ndarray} for Nullable columns


class Table:
    def __init__(self, name: str, columns: dict, order_by: list):
        self.name = name
        self.columns = columns          # name -> ClickHouse type
        self.order_by = order_by
        self.partitions: dict[str, list[Insert]] = {}

    def inserts(self) -> list[Insert]:
        return [i for part in self.partitions.values() for i in part]

    def row_count(self) -> int:
        return sum(i.rows for i in self.inserts())


def is_internal(name: str) -> bool:
    return name.startswith("__")


class ClickHouseStandIn:
    """`keep(cols) -> bool mask | None` selects the rows of a data-table
    insert that are kept for the comparison (None keeps all)."""

    def __init__(self, keep=None):
        self.tables: dict[str, Table] = {}
        self.lock = threading.Lock()
        self.keep = keep
        # (arrival_ns, table, rows) of rows that became visible in a data
        # table: a direct insert at its arrival, a staged part at its publish
        self.visible: list[tuple[int, str, int]] = []
        self.errors: list[str] = []
        # what serving cost this process: bytes received, and seconds spent
        # receiving bodies and handling queries, summed over its threads
        self.cost = {"bytes": 0, "recv_s": 0.0, "handle_s": 0.0,
                     "requests": 0}
        self._srv: ThreadingHTTPServer | None = None
        self._thread: threading.Thread | None = None
        self.port = 0

    # -- what the world reads ------------------------------------------------
    def total_rows(self) -> int:
        with self.lock:
            return sum(t.row_count() for n, t in self.tables.items()
                       if not is_internal(n))

    def data_tables(self) -> list[str]:
        with self.lock:
            return sorted(n for n in self.tables if not is_internal(n))

    def take_inserts(self, table: str) -> list[Insert]:
        """The table's inserts, handed over and forgotten."""
        with self.lock:
            t = self.tables.get(table)
            if t is None:
                return []
            out = t.inserts()
            t.partitions = {}
            return out

    # -- lifecycle -------------------------------------------------------------
    def start(self) -> "ClickHouseStandIn":
        srv = self

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"  # the client keeps connections alive

            def do_POST(self):
                t0 = time.monotonic()
                length = int(self.headers.get("Content-Length") or 0)
                body = self.rfile.read(length)
                t1 = time.monotonic()
                qs = urllib.parse.parse_qs(
                    urllib.parse.urlparse(self.path).query)
                query = (qs.get("query") or [""])[0]
                try:
                    out = srv.handle(query, body)
                    status = 200
                except Exception as e:  # a server answers 500 and lives on
                    out = f"{type(e).__name__}: {e}".encode()
                    status = 500
                    with srv.lock:
                        srv.errors.append(out.decode("utf-8", "replace"))
                with srv.lock:
                    srv.cost["bytes"] += length
                    srv.cost["recv_s"] += t1 - t0
                    srv.cost["handle_s"] += time.monotonic() - t1
                    srv.cost["requests"] += 1
                self.send_response(status)
                self.send_header("Content-Length", str(len(out)))
                self.end_headers()
                self.wfile.write(out)

            def log_message(self, *a):
                pass

        self._srv = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        self._srv.daemon_threads = True
        self.port = self._srv.server_port
        self._thread = threading.Thread(target=self._srv.serve_forever,
                                        name="ch-standin", daemon=True)
        self._thread.start()
        return self

    def stop(self) -> None:
        if self._srv is not None:
            self._srv.shutdown()
            self._srv.server_close()
            self._thread.join(timeout=10)
            self._srv = None

    # -- protocol ----------------------------------------------------------------
    def handle(self, query: str, body: bytes) -> bytes:
        q = query.strip()
        low = q.lower()
        if low == "select 1":
            return b"1\n"
        if low.startswith("insert into"):
            return self._insert(q, body)
        if low.startswith("create table if not exists"):
            return self._create(q)
        m = re.match(r"(drop|truncate) table if exists `?(\w+)`?", q, re.I)
        if m:
            with self.lock:
                if m.group(1).lower() == "drop":
                    self.tables.pop(m.group(2), None)
                elif m.group(2) in self.tables:
                    self.tables[m.group(2)].partitions = {}
            return b""
        m = re.match(r"alter table `?(\w+)`? replace partition id "
                     r"'([^']*)' from `?(\w+)`?\s*$", q, re.I)
        if m:
            return self._replace_partition(*m.groups())
        m = re.match(r"alter table `?(\w+)`? drop partition id '([^']*)'",
                     q, re.I)
        if m:
            with self.lock:
                t = self.tables.get(m.group(1))
                if t is not None:
                    t.partitions.pop(m.group(2), None)
            return b""
        m = re.match(r"select max\(`?(\w+)`?\) from `?(\w+)`? "
                     r"where `?(\w+)`? = '([^']*)'", q, re.I)
        if m:
            return self._select_max(*m.groups())
        if "from system.clusters" in low:
            return json.dumps({"data": []}).encode()
        if "from system.tables" in low:
            mn = re.search(r"name = '(\w+)'", q)
            with self.lock:
                if mn and low.startswith("select count()"):
                    n = 1 if mn.group(1) in self.tables else 0
                    return json.dumps({"data": [[n]]}).encode()
                data = [{"name": n, "total_rows": t.row_count()}
                        for n, t in self.tables.items()]
            return json.dumps({"data": data}).encode()
        if "from system.columns" in low:
            m = re.search(r"table = '(\w+)'", q)
            with self.lock:
                t = self.tables.get(m.group(1)) if m else None
                data = [{"name": c, "type": typ,
                         "is_in_primary_key": 1 if c in t.order_by else 0}
                        for c, typ in t.columns.items()] if t else []
            return json.dumps({"data": data}).encode()
        m = re.match(r"select count\(\) from `?(\w+)`?", q, re.I)
        if m:
            with self.lock:
                t = self.tables.get(m.group(1))
                n = t.row_count() if t else 0
            return json.dumps({"data": [[n]]}).encode()
        raise ValueError(f"ClickHouse stand-in: unhandled query: {q[:160]}")

    def _create(self, q: str) -> bytes:
        name = re.match(r"CREATE TABLE IF NOT EXISTS `?(\w+)`?", q,
                        re.I).group(1)
        inner = re.search(r"\((.*)\)\s*ENGINE", q, re.S | re.I).group(1)
        columns = {}
        for part in _split_top_level(inner):
            col, rest = part.strip().split(None, 1)
            # `String DEFAULT '<slug>'` on the staged-commit part column
            rest = re.split(r"\s+DEFAULT\s+", rest, flags=re.I)[0]
            columns[col.strip("`")] = rest.strip()
        mo = re.search(r"ORDER BY \(([^)]*)\)", q, re.I)
        order_by = [c.strip().strip("`") for c in mo.group(1).split(",")
                    if c.strip()] if mo else []
        with self.lock:
            if name not in self.tables:
                self.tables[name] = Table(name, columns, order_by)
        return b""

    def _insert(self, q: str, body: bytes) -> bytes:
        m = re.match(r"INSERT INTO `?(\w+)`?\s*\((.*?)\)\s*FORMAT RowBinary",
                     q, re.S | re.I)
        if not m:
            raise ValueError(f"ClickHouse stand-in: unhandled insert: "
                             f"{q[:160]}")
        name = m.group(1)
        col_names = [c.strip().strip("`") for c in m.group(2).split(",")]
        with self.lock:
            table = self.tables.get(name)
            if table is None:
                raise ValueError(f"Table {name} does not exist")
            types = [table.columns[c] for c in col_names]
        # parse outside the lock: the walker releases the GIL, so four
        # part threads' inserts parse side by side
        rows, cols, masks = rowbinary.Layout(col_names, types).decode(body)
        if self.keep is not None and rows:
            # staged parts' rows are data too; `keep` answers None for a
            # table without the key column (the sink's fence table)
            sel = self.keep(cols)
            if sel is not None:
                cols = {k: _take(v, sel) for k, v in cols.items()}
                masks = {k: v[sel] for k, v in masks.items()}
        now = time.monotonic_ns()
        with self.lock:
            table = self.tables.get(name)
            if table is None:
                raise ValueError(f"Table {name} does not exist")
            table.partitions.setdefault("", []).append(
                Insert(rows, now, cols, masks))
            if not is_internal(name):
                self.visible.append((now, name, rows))
        return b""

    def _replace_partition(self, final: str, slug: str, src: str) -> bytes:
        now = time.monotonic_ns()
        with self.lock:
            dst = self.tables.get(final)
            stg = self.tables.get(src)
            if dst is None or stg is None:
                raise ValueError("no such table for REPLACE PARTITION")
            moved = stg.inserts()
            dst.partitions[slug] = moved
            if not is_internal(final):
                self.visible.append((now, final,
                                     sum(i.rows for i in moved)))
        return b""

    def _select_max(self, col: str, tbl: str, kcol: str, kval: str) -> bytes:
        best = None
        with self.lock:
            t = self.tables.get(tbl)
            for ins in (t.inserts() if t else []):
                keys = ins.cols[kcol].to_pylist()
                vals = ins.cols[col]
                for k, v in zip(keys, vals):
                    if k.decode() == kval:
                        best = int(v) if best is None else max(best, int(v))
        return json.dumps({"data": [[best]]}).encode()


def _take(col, sel: np.ndarray):
    if isinstance(col, np.ndarray):
        return col[sel]
    import pyarrow as pa

    return col.filter(pa.array(sel))


def _split_top_level(inner: str) -> list[str]:
    parts, depth, cur = [], 0, ""
    for ch in inner:
        depth += (ch == "(") - (ch == ")")
        if ch == "," and depth == 0:
            parts.append(cur)
            cur = ""
        else:
            cur += ch
    if cur.strip():
        parts.append(cur)
    return parts
