"""The benchmark's PostgreSQL stand-in: protocol v3, as far as the Postgres
source of the system under test speaks it on a snapshot.

Written for the benchmark as `chserver.py` and `broker.py` were (later PRs
may edit `tests/`, so the benchmark keeps its own), around what a benchmark
needs from it:

  * start-up with cleartext password authentication, the simple query
    protocol, and the catalog statements the provider issues (the table
    list, a table's columns with `format_type`, `pg_relation_size`,
    `relpages`, `count(*)`, `max("ctid")` for an incremental cursor, the
    WAL position): matched against those statements, not parsed as SQL;
  * `COPY (SELECT <columns> FROM <table> [WHERE ctid >= '(lo,0)'::tid AND
    ctid < '(hi,0)'::tid | WHERE "ctid" > '(lo,0)']) TO STDOUT WITH
    (FORMAT csv ...)` sends what
    PostgreSQL's backend sends: one `CopyData` message a row.  The framed
    bytes of the whole heap are built before any window opens
    (`tpchgen.frame_rows`) with the offset of every page in them, so a
    page range is one `sendall` of a slice and the stand-in is not what a
    pass waits for;
  * a heap is pages of `rows_per_page` rows: sizes, page counts and ctid
    ranges are answered from that;
  * what serving cost this process - bytes sent, seconds its threads
    spent in `sendall`, statements - is kept, as the ClickHouse
    stand-in's is.

Anything else is answered with an ErrorResponse and remembered in
`errors`.  Imports nothing from `tests/` or `transferia_tpu/`.
"""

from __future__ import annotations

import re
import socket
import socketserver
import struct
import threading
import time

import numpy as np

PAGE_BYTES = 8192


class Heap:
    """One table: its catalog row and its rows as framed COPY text."""

    def __init__(self, schema: str, name: str, columns: list[tuple],
                 framed: np.ndarray, row_offsets: np.ndarray,
                 rows_per_page: int):
        # columns: (name, type as format_type spells it, primary key,
        # not null)
        self.schema = schema
        self.name = name
        self.columns = columns
        self.framed = memoryview(np.ascontiguousarray(framed))
        self.rows = len(row_offsets) - 1
        self.rows_per_page = rows_per_page
        self.pages = max(1, -(-self.rows // rows_per_page))
        starts = np.arange(self.pages + 1, dtype=np.int64) * rows_per_page
        self.page_offsets = row_offsets[np.minimum(starts, self.rows)]

    def slice(self, lo_page: int, hi_page: int) -> tuple[memoryview, int]:
        """(the messages of pages [lo, hi), their rows)."""
        lo = min(max(lo_page, 0), self.pages)
        hi = min(max(hi_page, lo), self.pages)
        rows = min(hi * self.rows_per_page, self.rows) \
            - min(lo * self.rows_per_page, self.rows)
        return self.framed[int(self.page_offsets[lo]):
                           int(self.page_offsets[hi])], rows


class PostgresStandIn:
    def __init__(self, password: str = ""):
        self.password = password
        self.heaps: dict[tuple[str, str], Heap] = {}
        self.lock = threading.Lock()
        self.errors: list[str] = []
        self.cost = {"bytes": 0, "send_s": 0.0, "statements": 0,
                     "copies": 0, "copy_rows": 0}
        self.port = 0
        self._srv = None
        self._thread = None

    def add(self, heap: Heap) -> None:
        self.heaps[(heap.schema, heap.name)] = heap

    def start(self) -> "PostgresStandIn":
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
                                        name="pg-standin", daemon=True)
        self._thread.start()
        return self

    def stop(self) -> None:
        if self._srv is not None:
            self._srv.shutdown()
            self._srv.server_close()
            self._thread.join(timeout=10)
            self._srv = None


_REGCLASS = re.compile(r"'\"?(\w+)\"?\.\"?(\w+)\"?'")
_COPY = re.compile(
    r"^copy \(select (?P<cols>.*?) from \"?(?P<ns>\w+)\"?\.\"?(?P<t>\w+)\"?"
    r"(?: where (?:ctid >= '\((?P<lo>\d+),0\)'::tid and "
    r"ctid < '\((?P<hi>\d+),0\)'::tid|\"ctid\" > '\((?P<after>\d+),0\)'))?"
    r"\) to stdout with \(format csv(?:, header false)?\)$")
_MAX_CTID = re.compile(
    r'^select max\("ctid"\) from "?(\w+)"?\."?(\w+)"?$')


class _Session:
    def __init__(self, sock: socket.socket, standin: PostgresStandIn):
        self.sock = sock
        self.standin = standin
        sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)

    # -- framing -----------------------------------------------------------------
    def send(self, kind: bytes, payload: bytes = b"") -> None:
        self.sock.sendall(kind + struct.pack("!I", len(payload) + 4)
                          + payload)

    def recv_exact(self, n: int) -> bytes:
        out = bytearray()
        while len(out) < n:
            chunk = self.sock.recv(n - len(out))
            if not chunk:
                raise ConnectionError("client went away")
            out += chunk
        return bytes(out)

    def recv_message(self) -> tuple[bytes, bytes]:
        head = self.recv_exact(5)
        length = struct.unpack("!I", head[1:])[0]
        return head[:1], self.recv_exact(length - 4)

    def ready(self) -> None:
        self.send(b"Z", b"I")

    def error(self, message: str, code: str = "XX000") -> None:
        with self.standin.lock:
            self.standin.errors.append(message)
        self.send(b"E", b"SERROR\x00C" + code.encode() + b"\x00M"
                  + message.encode() + b"\x00\x00")

    def rows(self, names: list[str], rows: list[list]) -> None:
        desc = struct.pack("!H", len(names))
        for n in names:       # text format, type oid 25, no table
            desc += n.encode() + b"\x00" + struct.pack("!IHIhih", 0, 0, 25,
                                                       -1, -1, 0)
        self.send(b"T", desc)
        for row in rows:
            body = struct.pack("!H", len(row))
            for v in row:
                if v is None:
                    body += struct.pack("!i", -1)
                else:
                    text = str(v).encode()
                    body += struct.pack("!i", len(text)) + text
            self.send(b"D", body)
        self.send(b"C", b"SELECT %d\x00" % len(rows))

    # -- the session -----------------------------------------------------------------
    def run(self) -> None:
        length = struct.unpack("!I", self.recv_exact(4))[0]
        body = self.recv_exact(length - 4)
        if struct.unpack("!I", body[:4])[0] == 80877103:   # SSLRequest
            self.sock.sendall(b"N")
            length = struct.unpack("!I", self.recv_exact(4))[0]
            body = self.recv_exact(length - 4)
        if self.standin.password:
            self.send(b"R", struct.pack("!I", 3))          # cleartext
            kind, payload = self.recv_message()
            if kind != b"p" or payload.rstrip(b"\x00").decode() \
                    != self.standin.password:
                self.error("password authentication failed", "28P01")
                return
        self.send(b"R", struct.pack("!I", 0))
        for k, v in (("server_version", "16.0"),
                     ("client_encoding", "UTF8")):
            self.send(b"S", k.encode() + b"\x00" + v.encode() + b"\x00")
        self.send(b"K", struct.pack("!II", 1, 1))
        self.ready()
        while True:
            kind, payload = self.recv_message()
            if kind == b"X":
                return
            if kind != b"Q":
                self.error(f"stand-in: message {kind!r} not spoken")
                self.ready()
                continue
            sql = payload.rstrip(b"\x00").decode()
            with self.standin.lock:
                self.standin.cost["statements"] += 1
            try:
                self.statement(sql)
            except (ConnectionError, OSError):
                raise
            except Exception as e:   # a server answers and lives on
                self.error(f"{type(e).__name__}: {e}")
            self.ready()

    def heap(self, sql: str) -> Heap:
        m = _REGCLASS.search(sql)
        heap = self.standin.heaps.get((m.group(1), m.group(2))) \
            if m else None
        if heap is None:
            raise LookupError(f"relation does not exist: {sql[:120]}")
        return heap

    def statement(self, sql: str) -> None:
        low = " ".join(sql.lower().split())
        heaps = self.standin.heaps
        if low == "select 1":
            return self.rows(["?column?"], [[1]])
        if low == "select pg_current_wal_lsn()":
            return self.rows(["pg_current_wal_lsn"], [["0/1000000"]])
        if "from pg_class c join pg_namespace" in low:
            return self.rows(["ns", "name", "eta"],
                             [[h.schema, h.name, h.rows]
                              for h in heaps.values()])
        if "from pg_attribute" in low:
            return self.rows(
                ["name", "typ", "notnull", "is_pk"],
                [[name, typ, "t" if notnull else "f", "t" if pk else "f"]
                 for name, typ, pk, notnull in self.heap(sql).columns])
        if "pg_relation_size" in low:
            return self.rows(["pg_relation_size"],
                             [[self.heap(sql).pages * PAGE_BYTES]])
        if low.startswith("select relpages from pg_class"):
            return self.rows(["relpages"], [[self.heap(sql).pages]])
        m = re.match(r'select count\(\*\) from "?(\w+)"?\."?(\w+)"?$', low)
        if m:
            heap = heaps.get((m.group(1), m.group(2)))
            if heap is None:
                raise LookupError("relation does not exist")
            return self.rows(["count"], [[heap.rows]])
        m = _MAX_CTID.match(low)
        if m:       # an incremental snapshot's next cursor
            heap = heaps.get((m.group(1), m.group(2)))
            if heap is None:
                raise LookupError("relation does not exist")
            last = heap.rows - (heap.pages - 1) * heap.rows_per_page
            return self.rows(["max"], [[f"({heap.pages - 1},{last})"]])
        m = _COPY.match(low)
        if m:
            return self.copy_out(m)
        raise NotImplementedError(f"stand-in: statement not spoken: "
                                  f"{sql[:160]}")

    def copy_out(self, m) -> None:
        heap = self.standin.heaps.get((m.group("ns"), m.group("t")))
        if heap is None:
            raise LookupError("relation does not exist")
        cols = [c.strip().strip('"') for c in m.group("cols").split(",")]
        if cols != [c[0] for c in heap.columns]:
            raise NotImplementedError(
                "stand-in: COPY of other than the table's columns in "
                "their order")
        if m.group("after") is not None:
            # a cursor on ctid: item numbers start at 1, so every row of
            # that page and of the pages after it is past (page,0)
            data, rows = heap.slice(int(m.group("after")), heap.pages)
        elif m.group("lo") is None:
            data, rows = heap.slice(0, heap.pages)
        else:
            data, rows = heap.slice(int(m.group("lo")), int(m.group("hi")))
        self.send(b"H", struct.pack("!bh", 0, len(cols))
                  + struct.pack("!h", 0) * len(cols))
        t0 = time.monotonic()
        self.sock.sendall(data)
        spent = time.monotonic() - t0
        self.send(b"c")
        self.send(b"C", b"COPY %d\x00" % rows)
        with self.standin.lock:
            cost = self.standin.cost
            cost["bytes"] += len(data)
            cost["send_s"] += spent
            cost["copies"] += 1
            cost["copy_rows"] += rows
