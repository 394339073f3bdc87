"""The `hits` table as ClickHouse writes it in `JSONEachRow`: one JSON
object a row and a line, the form ClickBench publishes as `hits.json.gz`.

The truth stays the parquet part files `datagen.py` writes: every file
here is one of them read back with pyarrow and written as text, row for
row and column for column in the file's order, so the reference
(`reference.py::expected_from_source`, which reads the parquet) holds for
the text too.  The format is ClickHouse's defaults of that file's day:
64-bit integers as quoted decimal strings
(`output_format_json_quote_64bit_integers`), narrower ones bare, DateTime
`"YYYY-MM-DD hh:mm:ss"`, Date `"YYYY-MM-DD"`, strings with `\\`, `"`, `/`
(`output_format_json_escape_forward_slashes`) and the control characters
escaped and everything else raw UTF-8, no space after `:` or `,`.

All of it is arrow's string kernels over whole columns - a row's text is
the element-wise join of its 105 rendered values with the constant pieces
between them, and a file is that array's data buffer - so a file of some
75 MiB takes a second or two and no line is ever a Python string.  No code
of the system under test is used.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import pyarrow as pa
import pyarrow.compute as pc
import pyarrow.parquet as pq
import pyarrow.types as pt

# what JSONEachRow escapes in a string, backslash first
_ESCAPES = [("\\", "\\\\"), ('"', '\\"'), ("/", "\\/"), ("\b", "\\b"),
            ("\f", "\\f"), ("\n", "\\n"), ("\r", "\\r"), ("\t", "\\t")]
_CONTROL = "[\x00-\x07\x0b\x0e-\x1f]"


def _escape(col: pa.Array) -> pa.Array:
    for raw, esc in _ESCAPES:
        col = pc.replace_substring(col, raw, esc)
    if pc.any(pc.match_substring_regex(col, _CONTROL)).as_py():
        # \u00XX for what has no short escape: rare, so row by row
        col = pa.array([None if s is None else "".join(
            f"\\u{ord(c):04x}" if ord(c) < 0x20 else c for c in s)
            for s in col.to_pylist()], type=pa.string())
    return col


def render(col: pa.Array) -> tuple[pa.Array, bool]:
    """(a column's values as JSONEachRow writes them, without the quotes;
    whether it quotes them)"""
    t = col.type
    if pt.is_string(t) or pt.is_large_string(t):
        return _escape(col.cast(pa.string())), True
    if pt.is_timestamp(t):
        # whole seconds (parquet reads a seconds column back as ms)
        return pc.strftime(col.cast(pa.timestamp("s")),
                           "%Y-%m-%d %H:%M:%S"), True
    if pt.is_date(t):
        return pc.strftime(col, "%Y-%m-%d"), True
    if pt.is_integer(t):
        return col.cast(pa.string()), pt.is_int64(t) or pt.is_uint64(t)
    raise ValueError(f"jsonlgen: no JSONEachRow form for {t}")


def lines_of(table: pa.Table) -> pa.Array:
    """One string a row: the row's line, its newline included."""
    pieces, close = [], "{"
    for name in table.column_names:
        col, quoted = render(table[name].combine_chunks())
        if col.null_count:
            raise ValueError(f"jsonlgen: NULL in {name}")
        q = '"' if quoted else ""
        pieces += [pa.scalar(f'{close}"{name}":{q}'), col]
        close = q + ","
    pieces += [pa.scalar(close[:-1] + "}\n"), ""]
    return pc.binary_join_element_wise(*pieces)


def write_file(parquet_path: str, out_path: str) -> tuple[str, int, int]:
    """One part file as text; (its path, its rows, its bytes)."""
    lines = lines_of(pq.read_table(parquet_path))
    offsets = lines.buffers()[1].to_pybytes()
    lo = int.from_bytes(offsets[:4], "little")
    hi = int.from_bytes(offsets[-4:], "little")
    with open(out_path, "wb") as fh:
        fh.write(memoryview(lines.buffers()[2])[lo:hi])
    return out_path, len(lines), hi - lo


def generate(parquet_files: list[str], out_dir: str,
             workers: int) -> list[tuple[str, int, int]]:
    """`part-NNNNN.parquet` -> `out_dir/part-NNNNN.jsonl`, in order."""
    os.makedirs(out_dir, exist_ok=True)
    outs = [os.path.join(
        out_dir, os.path.splitext(os.path.basename(f))[0] + ".jsonl")
        for f in parquet_files]
    with ThreadPoolExecutor(max(1, workers)) as pool:
        return list(pool.map(write_file, parquet_files, outs))
