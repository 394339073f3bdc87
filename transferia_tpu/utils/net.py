"""Shared socket helpers for the wire-protocol clients."""

from __future__ import annotations

import socket
import ssl

# buffers one sendmsg takes at most (Linux's IOV_MAX)
_IOV_MAX = 1024


class BufferedSock:
    """Read-buffering wrapper over a socket (drop-in for recv_exact).

    Wire clients parse many small frames (a PG COPY row, a MySQL packet,
    a RowBinary value): raw per-frame recv() means 2+ syscalls per frame
    and dominates wall time on fast links.  This wrapper refills a local
    buffer in large chunks and serves recv() from it; writes and every
    other attribute pass through to the underlying socket.  recv_into is
    intentionally not exposed: parsers here are frame-splitters, not
    zero-copy consumers.
    """

    REFILL = 1 << 18

    def __init__(self, sock: socket.socket):
        self._sock = sock
        self._buf = bytearray()
        self._pos = 0

    def recv(self, n: int) -> bytes:
        avail = len(self._buf) - self._pos
        if avail == 0:
            if n >= self.REFILL:
                # large reads bypass the buffer entirely
                return self._sock.recv(n)
            chunk = self._sock.recv(self.REFILL)
            if not chunk:
                return b""
            self._buf = bytearray(chunk)
            self._pos = 0
            avail = len(chunk)
        take = min(n, avail)
        out = bytes(self._buf[self._pos:self._pos + take])
        self._pos += take
        if self._pos == len(self._buf):
            self._buf = bytearray()
            self._pos = 0
        return out

    def pending(self) -> int:
        """Bytes already buffered (e.g. to drain before a mode switch)."""
        return len(self._buf) - self._pos

    def __getattr__(self, name):
        return getattr(self._sock, name)


def recv_exact(sock: socket.socket, n: int,
               closed_msg: str = "connection closed by peer") -> bytes:
    """Read exactly n bytes (raises ConnectionError on EOF).

    Accumulates into a list to avoid O(n^2) bytes concatenation on large
    frames (COPY chunks, fetch responses).
    """
    parts: list[bytes] = []
    got = 0
    while got < n:
        chunk = sock.recv(n - got)
        if not chunk:
            raise ConnectionError(closed_msg)
        parts.append(chunk)
        got += len(chunk)
    return b"".join(parts) if len(parts) != 1 else parts[0]


def send_pieces(sock: socket.socket, pieces) -> None:
    """Send buffers in order as they are, with no join: a sendmsg over as
    many as one call takes, a partial send resumed where it stopped.  A
    TLS socket has no scatter send and takes them a sendall each."""
    if isinstance(sock, ssl.SSLSocket):
        for piece in pieces:
            sock.sendall(piece)
        return
    views = [memoryview(p).cast("B") for p in pieces if len(p)]
    i = 0
    while i < len(views):
        sent = sock.sendmsg(views[i:i + _IOV_MAX])
        while sent:
            n = len(views[i])
            if sent < n:
                views[i] = views[i][sent:]
                break
            sent -= n
            i += 1
