"""The benchmark's MySQL stand-in: the client/server protocol as far as the
MySQL source of the system under test speaks it on a snapshot.

Written for the benchmark as `pgserver.py` was (later PRs may edit
`tests/`, so the benchmark keeps its own), around what a benchmark needs:

  * the protocol-10 handshake with `mysql_native_password` (the scramble
    is checked), `COM_QUERY` with text result sets (EOF framing: the
    program does not negotiate DEPRECATE_EOF), `COM_PING`, `COM_QUIT`;
  * the statements the provider issues, matched against those statements
    and not parsed as SQL: the table list and a table's columns from
    `information_schema`, the primary key's column order
    (`information_schema.STATISTICS`), a table's size, `COUNT(*)`,
    `MIN`/`MAX` of a key column under equality conditions, `SHOW MASTER
    STATUS`, and `SELECT <the table's columns> FROM <table> [WHERE <key
    column> <op> <integer> AND ...] [ORDER BY <key columns>] [LIMIT n
    [OFFSET m]]`;
  * a table is its rows in primary-key order as row packets framed
    before any window opens (`tpccgen.frame_rows`) with the offset of
    every row in them: a SELECT's rows are the runs of rows its
    conditions keep, each one `sendall` of a slice, so the stand-in's
    side of a pass is a copy;
  * `OFFSET` is answered as MySQL answers it - the rows before it are
    found and thrown away - and what was thrown away is counted
    (`rows_skipped_by_offset`): a source that pages by OFFSET is neither
    refused nor flattered;
  * what serving cost this process - bytes sent, seconds its threads
    spent in `sendall`, statements, result sets - is kept.

Anything else is answered with an ERR packet at once and remembered in
`errors`.  Imports nothing from `tests/` or `transferia_tpu/`.
"""

from __future__ import annotations

import hashlib
import os
import re
import socket
import socketserver
import struct
import threading
import time

import numpy as np


class Table:
    """One table: its catalog rows, its key columns as arrays, and its
    rows as framed row packets."""

    def __init__(self, database: str, name: str, columns: list[tuple],
                 key: list[str], key_arrays: dict, framed: np.ndarray,
                 row_offsets: np.ndarray):
        # columns: (name, COLUMN_TYPE as information_schema spells it,
        # nullable); key: the primary key's columns in index order;
        # key_arrays: {column: int64 array} for every column a WHERE may
        # name (the key's, or for a keyless table any integer column)
        self.database = database
        self.name = name
        self.columns = columns
        self.key = key
        self.key_arrays = key_arrays
        self.framed = memoryview(np.ascontiguousarray(framed))
        self.row_offsets = row_offsets
        self.rows = len(row_offsets) - 1

    @property
    def data_bytes(self) -> int:
        return int(self.row_offsets[-1])


class MySQLStandIn:
    def __init__(self, user: str = "root", password: str = ""):
        self.user = user
        self.password = password
        self.tables: dict[tuple[str, str], Table] = {}
        self.lock = threading.Lock()
        self.errors: list[str] = []
        self.cost = {"bytes": 0, "send_s": 0.0, "statements": 0,
                     "result_sets": 0, "rows_sent": 0,
                     "offset_statements": 0, "rows_skipped_by_offset": 0}
        self.port = 0
        self._srv = None
        self._thread = None

    def add(self, table: Table) -> None:
        self.tables[(table.database, table.name)] = table

    def start(self) -> "MySQLStandIn":
        standin = self

        class Handler(socketserver.BaseRequestHandler):
            def handle(self):
                try:
                    _Session(self.request, standin).run()
                except (ConnectionError, OSError):
                    pass

        class Server(socketserver.ThreadingTCPServer):
            allow_reuse_address = True
            daemon_threads = True

        self._srv = Server(("127.0.0.1", 0), Handler)
        self.port = self._srv.server_address[1]
        self._thread = threading.Thread(target=self._srv.serve_forever,
                                        name="mysql-standin", daemon=True)
        self._thread.start()
        return self

    def stop(self) -> None:
        if self._srv is not None:
            self._srv.shutdown()
            self._srv.server_close()
            self._thread.join(timeout=10)
            self._srv = None


def _lenenc(v) -> bytes:
    if v is None:
        return b"\xfb"
    b = v if isinstance(v, bytes) else str(v).encode()
    n = len(b)
    if n < 251:
        return bytes([n]) + b
    if n < 1 << 16:
        return b"\xfc" + struct.pack("<H", n) + b
    return b"\xfd" + struct.pack("<I", n)[:3] + b


def native_password_token(password: str, nonce: bytes) -> bytes:
    if not password:
        return b""
    h1 = hashlib.sha1(password.encode()).digest()
    h3 = hashlib.sha1(nonce + hashlib.sha1(h1).digest()).digest()
    return bytes(a ^ b for a, b in zip(h1, h3))


_TABLE = r"`(?P<db>\w+)`\.`(?P<t>\w+)`"
_SELECT = re.compile(
    r"^select (?P<cols>`\w+`(?:, `\w+`)*) from " + _TABLE
    + r"(?: where (?P<where>.+?))?(?: order by (?P<order>`\w+`(?:, `\w+`)*))?"
    r"(?: limit (?P<limit>\d+)(?: offset (?P<offset>\d+))?)?$")
_MINMAX = re.compile(
    r"^select min\(`(?P<c>\w+)`\) as lo, max\(`(?P=c)`\) as hi from "
    + _TABLE + r"(?: where (?P<where>.+))?$")
_MAX = re.compile(r"^select max\(`(?P<c>\w+)`\) from " + _TABLE + "$")
_COUNT = re.compile(r"^select count\(\*\) from " + _TABLE + "$")
_COND = re.compile(r"^\(?`(\w+)` (>=|<=|>|<|=) '?(-?\d+)'?\)?$")
_SCHEMA_TABLE = re.compile(
    r"table_schema = '(\w+)' and table_name = '(\w+)'")
_OPS = {">": np.greater, ">=": np.greater_equal, "<": np.less,
        "<=": np.less_equal, "=": np.equal}


class _Session:
    def __init__(self, sock: socket.socket, standin: MySQLStandIn):
        self.sock = sock
        self.standin = standin
        self.seq = 0
        sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)

    # -- framing -----------------------------------------------------------------
    def recv_exact(self, n: int) -> bytes:
        out = bytearray()
        while len(out) < n:
            chunk = self.sock.recv(n - len(out))
            if not chunk:
                raise ConnectionError("client went away")
            out += chunk
        return bytes(out)

    def read_packet(self) -> bytes:
        head = self.recv_exact(4)
        self.seq = (head[3] + 1) & 0xFF
        return self.recv_exact(head[0] | head[1] << 8 | head[2] << 16)

    def packet(self, payload: bytes) -> bytes:
        out = struct.pack("<I", len(payload))[:3] + bytes([self.seq]) \
            + payload
        self.seq = (self.seq + 1) & 0xFF
        return out

    def send_ok(self) -> None:
        self.sock.sendall(self.packet(b"\x00\x00\x00\x02\x00\x00\x00"))

    def eof(self) -> bytes:
        return self.packet(b"\xfe\x00\x00\x02\x00")

    def send_err(self, message: str, errno: int = 1064) -> None:
        with self.standin.lock:
            self.standin.errors.append(message)
        self.sock.sendall(self.packet(
            b"\xff" + struct.pack("<H", errno) + b"#42000"
            + message.encode()))

    def header(self, names: list[str]) -> bytes:
        out = self.packet(bytes([len(names)]))
        for n in names:
            out += self.packet(
                _lenenc(b"def") + _lenenc(b"") + _lenenc(b"")
                + _lenenc(b"") + _lenenc(n) + _lenenc(n) + b"\x0c"
                + struct.pack("<HIBHB", 33, 255, 0xFD, 0, 0) + b"\x00\x00")
        return out + self.eof()

    def rows(self, names: list[str], rows: list[list]) -> None:
        out = self.header(names)
        for row in rows:
            out += self.packet(b"".join(_lenenc(v) for v in row))
        self.sock.sendall(out + self.eof())

    # -- the session -----------------------------------------------------------------
    def run(self) -> None:
        nonce = os.urandom(20).replace(b"\x00", b"\x01")
        caps = 0x1 | 0x8 | 0x200 | 0x8000 | 0x80000
        self.sock.sendall(self.packet(
            b"\x0a8.0.0-standin\x00" + struct.pack("<I", 1) + nonce[:8]
            + b"\x00" + struct.pack("<H", caps & 0xFFFF) + bytes([33])
            + struct.pack("<H", 2) + struct.pack("<H", caps >> 16)
            + bytes([21]) + b"\x00" * 10 + nonce[8:] + b"\x00"
            + b"mysql_native_password\x00"))
        resp = self.read_packet()
        pos = 32
        end = resp.index(b"\x00", pos)
        user = resp[pos:end].decode()
        token = resp[end + 2:end + 2 + resp[end + 1]]
        if user != self.standin.user or token != native_password_token(
                self.standin.password, nonce):
            self.send_err(f"Access denied for user '{user}'", 1045)
            return
        self.send_ok()
        while True:
            pkt = self.read_packet()
            if pkt[:1] == b"\x01":              # COM_QUIT
                return
            if pkt[:1] == b"\x0e":              # COM_PING
                self.send_ok()
                continue
            if pkt[:1] != b"\x03":
                self.send_err(f"stand-in: command {pkt[:1]!r} not spoken")
                continue
            sql = pkt[1:].decode()
            with self.standin.lock:
                self.standin.cost["statements"] += 1
            try:
                self.statement(sql)
            except (ConnectionError, OSError):
                raise
            except Exception as e:      # a server answers and lives on
                self.send_err(f"{type(e).__name__}: {e}")

    def table(self, db: str, name: str) -> Table:
        t = self.standin.tables.get((db, name))
        if t is None:
            raise LookupError(f"Table '{db}.{name}' doesn't exist")
        return t

    def statement(self, sql: str) -> None:
        low = " ".join(sql.lower().split())
        tables = self.standin.tables
        if low == "select 1":
            return self.rows(["1"], [[1]])
        if low.startswith("show master status"):
            return self.rows(["File", "Position", "Executed_Gtid_Set"],
                             [["binlog.000001", 4, ""]])
        if "from information_schema.tables" in low:
            m = _SCHEMA_TABLE.search(low)
            if m and "data_length" in low:
                return self.rows(["size"],
                                 [[self.table(*m.groups()).data_bytes]])
            m = re.search(r"table_schema = '(\w+)'", low)
            return self.rows(["name", "eta"],
                             [[t.name, t.rows] for (d, _), t in
                              tables.items() if m and d == m.group(1)])
        if "from information_schema.columns" in low:
            t = self.table(*_SCHEMA_TABLE.search(low).groups())
            return self.rows(
                ["name", "typ", "full_typ", "nullable", "ckey"],
                [[n, typ.split("(")[0], typ, "YES" if nullable else "NO",
                  "PRI" if n in t.key else ""]
                 for n, typ, nullable in t.columns])
        if "from information_schema.statistics" in low:
            t = self.table(*_SCHEMA_TABLE.search(low).groups())
            return self.rows(["name"], [[k] for k in t.key])
        if m := _COUNT.match(low):
            return self.rows(["count"],
                             [[self.table(m["db"], m["t"]).rows]])
        if m := _MINMAX.match(low):
            t = self.table(m["db"], m["t"])
            v = t.key_arrays[m["c"]][self.keep(t, m["where"])]
            return self.rows(["lo", "hi"], [[int(v.min()), int(v.max())]
                                            if len(v) else [None, None]])
        if m := _MAX.match(low):
            t = self.table(m["db"], m["t"])
            v = t.key_arrays[m["c"]]
            return self.rows(["max"], [[int(v.max()) if len(v) else None]])
        if m := _SELECT.match(low):
            return self.select(m)
        raise NotImplementedError(f"stand-in: statement not spoken: "
                                  f"{sql[:160]}")

    @staticmethod
    def keep(t: Table, where) -> np.ndarray:
        """The rows a WHERE of `col` OP integer [AND ...] keeps."""
        mask = np.ones(t.rows, dtype=bool)
        for cond in (where.split(" and ") if where else ()):
            m = _COND.match(cond.strip())
            if m is None or m.group(1) not in t.key_arrays:
                raise NotImplementedError(
                    f"stand-in: condition not spoken: {cond}")
            mask &= _OPS[m.group(2)](t.key_arrays[m.group(1)],
                                     int(m.group(3)))
        return mask

    def select(self, m) -> None:
        t = self.table(m["db"], m["t"])
        cols = [c.strip("`") for c in m["cols"].split(", ")]
        if cols != [c[0] for c in t.columns]:
            raise NotImplementedError(
                "stand-in: SELECT of other than the table's columns in "
                "their order")
        kept = np.flatnonzero(self.keep(t, m["where"]))
        order = [c.strip("`") for c in m["order"].split(", ")] \
            if m["order"] else []
        if order and order != t.key:
            # rows are held in primary-key order; any other order of
            # key columns is sorted for, as a server would
            if any(c not in t.key_arrays for c in order):
                raise NotImplementedError(
                    "stand-in: ORDER BY other than key columns")
            kept = kept[np.lexsort([t.key_arrays[c][kept]
                                    for c in reversed(order)])]
        self.serve(t, kept, m)

    def serve(self, t: Table, kept: np.ndarray, m) -> None:
        skipped = 0
        if m["offset"] is not None:
            skipped = min(int(m["offset"]), len(kept))
            kept = kept[skipped:]
        if m["limit"] is not None:
            kept = kept[:int(m["limit"])]
        # the runs of consecutive rows: each is one slice of the framed
        # bytes
        if len(kept):
            breaks = np.flatnonzero(np.diff(kept) != 1) + 1
            starts = np.concatenate([[0], breaks])
            ends = np.concatenate([breaks, [len(kept)]])
            runs = [(int(kept[a]), int(kept[b - 1]) + 1)
                    for a, b in zip(starts, ends)]
        else:
            runs = []
        head = self.header([c[0] for c in t.columns])
        sent = len(head)
        t0 = time.monotonic()
        self.sock.sendall(head)
        for lo, hi in runs:
            data = t.framed[int(t.row_offsets[lo]):int(t.row_offsets[hi])]
            self.sock.sendall(data)
            sent += len(data)
        self.sock.sendall(self.eof())
        spent = time.monotonic() - t0
        with self.standin.lock:
            cost = self.standin.cost
            cost["bytes"] += sent
            cost["send_s"] += spent
            cost["result_sets"] += 1
            cost["rows_sent"] += len(kept)
            if m["offset"] is not None:
                cost["offset_statements"] += 1
                cost["rows_skipped_by_offset"] += skipped
