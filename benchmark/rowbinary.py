"""RowBinary payloads -> columns, for the benchmark's ClickHouse stand-in.

Independent of the program under test: the layout is ClickHouse's
documented one, walked by `native/rowbin.cpp` (built once per checkout
into `benchmark/_build/`, named by the source's hash).  A decoded column
is a numpy array (fixed width) or a pyarrow LargeBinaryArray (String);
a Nullable column also yields a boolean null mask.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

import numpy as np
import pyarrow as pa

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "native", "rowbin.cpp")
_BUILD_DIR = os.path.join(_HERE, "_build")

# ClickHouse type -> (numpy dtype, width); String has width 0
FIXED = {
    "Int8": "<i1", "Int16": "<i2", "Int32": "<i4", "Int64": "<i8",
    "UInt8": "<u1", "UInt16": "<u2", "UInt32": "<u4", "UInt64": "<u8",
    "Float32": "<f4", "Float64": "<f8", "Bool": "<u1", "Date32": "<i4",
    "DateTime": "<u4", "DateTime64(6)": "<i8",
}

_lib = None
_lib_lock = threading.Lock()


def build() -> str:
    """Compile the walker if this checkout has not yet; returns the path."""
    with open(_SRC, "rb") as fh:
        tag = hashlib.sha256(fh.read()).hexdigest()[:16]
    out = os.path.join(_BUILD_DIR, f"librowbin-{tag}.so")
    if not os.path.exists(out):
        os.makedirs(_BUILD_DIR, exist_ok=True)
        tmp = f"{out}.{os.getpid()}.tmp"
        subprocess.run(["g++", "-O2", "-shared", "-fPIC", "-o", tmp, _SRC],
                       check=True)
        os.replace(tmp, out)
    return out


def _load():
    global _lib
    with _lib_lock:
        if _lib is None:
            lib = ctypes.CDLL(build())
            p = ctypes.c_void_p
            lib.rb_scan.argtypes = [p, ctypes.c_longlong, p, p,
                                    ctypes.c_int, p]
            lib.rb_scan.restype = ctypes.c_longlong
            lib.rb_decode.argtypes = [p, ctypes.c_longlong, p, p,
                                      ctypes.c_int, ctypes.c_longlong,
                                      p, p, p, p]
            lib.rb_decode.restype = ctypes.c_longlong
            _lib = lib
        return _lib


def split_type(ch_type: str) -> tuple[str, bool]:
    """'Nullable(Int64)' -> ('Int64', True)."""
    if ch_type.startswith("Nullable(") and ch_type.endswith(")"):
        return ch_type[9:-1], True
    return ch_type, False


class Layout:
    """The column types of one INSERT, in the form the walker takes."""

    def __init__(self, names: list[str], types: list[str]):
        self.names = list(names)
        self.base = []
        nullable = []
        widths = []
        for t in types:
            base, null = split_type(t)
            if base != "String" and base not in FIXED:
                raise ValueError(f"rowbinary: unsupported type {t!r}")
            self.base.append(base)
            nullable.append(1 if null else 0)
            widths.append(0 if base == "String"
                          else np.dtype(FIXED[base]).itemsize)
        self.widths = np.asarray(widths, dtype=np.int32)
        self.nullable = np.asarray(nullable, dtype=np.uint8)

    def scan(self, body: bytes) -> tuple[int, np.ndarray]:
        """(rows, bytes per String column); raises on a malformed body."""
        lib = _load()
        str_bytes = np.zeros(len(self.names), dtype=np.int64)
        buf = np.frombuffer(body, dtype=np.uint8)
        rows = lib.rb_scan(buf.ctypes.data if len(body) else None,
                           len(body), self.widths.ctypes.data,
                           self.nullable.ctypes.data, len(self.names),
                           str_bytes.ctypes.data)
        if rows < 0:
            raise ValueError("rowbinary: malformed payload")
        return int(rows), str_bytes

    def decode(self, body: bytes) -> tuple[int, dict, dict]:
        """(rows, {name: column}, {name: null mask}) of the whole body."""
        lib = _load()
        rows, str_bytes = self.scan(body)
        n = len(self.names)
        ptr = ctypes.c_void_p
        fixed = (ptr * n)()
        nulls = (ptr * n)()
        offsets = (ptr * n)()
        chars = (ptr * n)()
        keep: list = []  # the arrays the pointers refer to
        out_fixed: dict[int, np.ndarray] = {}
        out_nulls: dict[int, np.ndarray] = {}
        out_str: dict[int, tuple] = {}
        for c in range(n):
            if self.nullable[c]:
                a = np.zeros(max(rows, 1), dtype=np.uint8)
                nulls[c] = a.ctypes.data
                out_nulls[c] = a
            if self.widths[c]:
                a = np.empty(max(rows, 1) * int(self.widths[c]),
                             dtype=np.uint8)
                fixed[c] = a.ctypes.data
                out_fixed[c] = a
            else:
                off = np.zeros(rows + 1, dtype=np.int64)
                dat = np.empty(max(int(str_bytes[c]), 1), dtype=np.uint8)
                offsets[c] = off.ctypes.data
                chars[c] = dat.ctypes.data
                out_str[c] = (off, dat, int(str_bytes[c]))
        buf = np.frombuffer(body, dtype=np.uint8)
        got = lib.rb_decode(buf.ctypes.data if len(body) else None,
                            len(body), self.widths.ctypes.data,
                            self.nullable.ctypes.data, n, rows,
                            fixed, nulls, offsets, chars)
        if got != rows:
            raise ValueError("rowbinary: decode disagrees with scan")
        cols: dict = {}
        masks: dict = {}
        for c, name in enumerate(self.names):
            if c in out_fixed:
                cols[name] = out_fixed[c][:rows * int(self.widths[c])].view(
                    FIXED[self.base[c]])
            else:
                off, dat, nbytes = out_str[c]
                cols[name] = pa.LargeBinaryArray.from_buffers(
                    pa.large_binary(), rows,
                    [None, pa.py_buffer(off), pa.py_buffer(dat[:nbytes])])
            if c in out_nulls:
                masks[name] = out_nulls[c][:rows].astype(bool)
        return rows, cols, masks
