"""TPC-C's nine tables at W warehouses, as arrays and as the row packets a
MySQL server sends of them.

What the tables are - their 92 columns, MySQL types, keys, cardinalities
and the ranges of the specification's population clause - is the data
file the configuration names (`configs/tpcc-columns.json`); the rules
that join the tables (an order's lines, the last 900 orders of a district
undelivered, a history row a customer) are written here.  The values are
drawn by numpy from `--seed`: one seed gives one database.  Rows are held
in primary-key order (HISTORY in its customers' order).

A column is held as what a comparison needs: an int64 array for `int`,
`dec` (the unscaled integer; the column's `scale` says where the point
is) and `datetime` (seconds since 1970-01-01 UTC), a pyarrow string array
for `str`; `nulls[name]` is a mask where a column has NULLs.
`text_columns` renders every column as the text MySQL's text protocol
sends, `frame_rows` packs those into row packets.

numpy and pyarrow only: the world imports this, never the program.
"""

from __future__ import annotations

import calendar
import json
import time

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc


def load_columns(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def _epoch(text: str) -> int:
    return calendar.timegm(time.strptime(text, "%Y-%m-%d %H:%M:%S"))


class _Draw:
    """The specification's random strings and numbers over one generator."""

    def __init__(self, rng, spec: dict):
        self.rng = rng
        self.alphabet = np.frombuffer(spec["a_string_alphabet"].encode(),
                                      dtype=np.uint8)
        self.digits = np.frombuffer(b"0123456789", dtype=np.uint8)

    def strings(self, n: int, lo: int, hi: int, alphabet=None,
                suffix: bytes = b"") -> tuple[np.ndarray, np.ndarray]:
        """(bytes end to end, int64 offsets): n random strings of lo..hi
        characters, each followed by `suffix`."""
        alphabet = self.alphabet if alphabet is None else alphabet
        body = self.rng.integers(lo, hi + 1, n) if hi > lo \
            else np.full(n, lo, dtype=np.int64)
        lens = body + len(suffix)
        offsets = np.zeros(n + 1, dtype=np.int64)
        np.cumsum(lens, out=offsets[1:])
        data = alphabet[self.rng.integers(0, len(alphabet),
                                          int(offsets[-1]))]
        if suffix:
            tail = np.frombuffer(suffix, dtype=np.uint8)
            at = (offsets[1:] - len(suffix))[:, None] \
                + np.arange(len(suffix))
            data[at] = tail
        return data, offsets

    def with_original(self, data, offsets, share: float, word: bytes):
        """`word` written over a random position of `share` of the
        strings (each is longer than the word)."""
        n = len(offsets) - 1
        rows = np.flatnonzero(self.rng.random(n) < share)
        lens = offsets[rows + 1] - offsets[rows]
        at = offsets[rows] + (self.rng.random(len(rows))
                              * (lens - len(word) + 1)).astype(np.int64)
        data[at[:, None] + np.arange(len(word))] = np.frombuffer(
            word, dtype=np.uint8)
        return rows


def _arrow(data: np.ndarray, offsets: np.ndarray) -> pa.Array:
    return pa.Array.from_buffers(
        pa.large_string(), len(offsets) - 1,
        [None, pa.py_buffer(offsets), pa.py_buffer(data)])


def _column(draw: _Draw, c: dict, n: int, spec: dict, given: dict):
    """One column of n rows from its `values` rule; `given` holds the
    columns a table's own rules made."""
    if c["name"] in given:
        return given[c["name"]]
    v = c["values"]
    rng = draw.rng
    if c["kind"] == "str":
        if v == "zip":
            return _arrow(*draw.strings(n, 4, 4, draw.digits, b"11111"))
        if "a_string" in v:
            data, offsets = draw.strings(n, *v["a_string"])
            if "original_share" in c:
                draw.with_original(data, offsets, c["original_share"],
                                   b"ORIGINAL")
            return _arrow(data, offsets)
        if "n_string" in v:
            return _arrow(*draw.strings(n, *v["n_string"], draw.digits))
        if "constant" in v:
            return pa.array([v["constant"]] * n, type=pa.large_string())
        if "choice" in v:
            second = rng.random(n) < v["share_of_second"]
            return pa.array(v["choice"], type=pa.large_string()).take(
                pa.array(second.astype(np.int8)))
    elif c["kind"] == "datetime" and v == "load_time":
        t = spec["load_time"]
        return _epoch(t["from"]) + rng.integers(0, t["span_seconds"], n)
    elif isinstance(v, dict) and "constant" in v:
        return np.full(n, v["constant"], dtype=np.int64)
    elif isinstance(v, dict) and "uniform" in v:
        return rng.integers(v["uniform"][0], v["uniform"][1] + 1, n)
    raise ValueError(f"tpccgen: no rule for column {c['name']}")


def _last_names(draw: _Draw, spec: dict, c_id: np.ndarray) -> pa.Array:
    syl = spec["last_name_syllables"]
    pool = pa.array([syl[i // 100] + syl[i // 10 % 10] + syl[i % 10]
                     for i in range(1000)], type=pa.large_string())
    nu = spec["last_name_nurand"]
    rng = draw.rng
    n = len(c_id)
    span = nu["y"] - nu["x"] + 1
    c_const = int(rng.integers(0, nu["A"] + 1))
    drawn = (((rng.integers(0, nu["A"] + 1, n)
               | rng.integers(nu["x"], nu["y"] + 1, n)) + c_const)
             % span) + nu["x"]
    code = np.where(c_id <= nu["first_customers_in_order"], c_id - 1, drawn)
    return pool.take(pa.array(code))


def generate(seed: int, warehouses: int, spec: dict) -> dict:
    """{table name: {"name", "key", "columns", "rows", "cols", "nulls"}}."""
    rng = np.random.default_rng([seed, 0x7CC])
    draw = _Draw(rng, spec)
    per = spec["per_warehouse"]
    w_n, d_n = int(warehouses), per["districts"]
    c_n, o_n = per["customers_per_district"], per["orders_per_district"]
    new_n = per["new_orders_per_district"]
    districts = w_n * d_n

    def grid(*sizes):
        """Row-major index columns of a (sizes...) grid, 1-based."""
        idx = np.indices(sizes, dtype=np.int64).reshape(len(sizes), -1)
        return [i + 1 for i in idx]

    given: dict[str, dict] = {t["name"]: {} for t in spec["tables"]}
    nulls: dict[str, dict] = {t["name"]: {} for t in spec["tables"]}
    given["warehouse"]["w_id"] = np.arange(1, w_n + 1, dtype=np.int64)
    g = given["district"]
    g["d_w_id"], g["d_id"] = grid(w_n, d_n)
    g = given["customer"]
    g["c_w_id"], g["c_d_id"], g["c_id"] = grid(w_n, d_n, c_n)
    g["c_last"] = _last_names(draw, spec, g["c_id"])
    h = given["history"]
    h["h_c_w_id"] = h["h_w_id"] = g["c_w_id"]
    h["h_c_d_id"] = h["h_d_id"] = g["c_d_id"]
    h["h_c_id"] = g["c_id"]
    o = given["orders"]
    o["o_w_id"], o["o_d_id"], o["o_id"] = grid(w_n, d_n, o_n)
    o["o_c_id"] = np.argsort(rng.random((districts, c_n)),
                             axis=1).reshape(-1).astype(np.int64) + 1
    orders = districts * o_n
    undelivered = o["o_id"] > o_n - new_n
    nulls["orders"]["o_carrier_id"] = undelivered
    lo, hi = per["lines_per_order"]
    o["o_ol_cnt"] = rng.integers(lo, hi + 1, orders)
    t = spec["load_time"]
    o["o_entry_d"] = _epoch(t["from"]) + rng.integers(
        0, t["span_seconds"], orders)
    n = given["new_order"]
    n["no_w_id"], n["no_d_id"], n["no_o_id"] = grid(w_n, d_n, new_n)
    n["no_o_id"] = n["no_o_id"] + (o_n - new_n)
    ol = given["order_line"]
    of = np.repeat(np.arange(orders), o["o_ol_cnt"])
    lines = len(of)
    starts = np.cumsum(o["o_ol_cnt"]) - o["o_ol_cnt"]
    ol["ol_w_id"] = ol["ol_supply_w_id"] = o["o_w_id"][of]
    ol["ol_d_id"], ol["ol_o_id"] = o["o_d_id"][of], o["o_id"][of]
    ol["ol_number"] = np.arange(lines, dtype=np.int64) - starts[of] + 1
    ol["ol_delivery_d"] = o["o_entry_d"][of]
    nulls["order_line"]["ol_delivery_d"] = undelivered[of]
    amount = next(c for tb in spec["tables"] if tb["name"] == "order_line"
                  for c in tb["columns"] if c["name"] == "ol_amount")
    a_lo, a_hi = amount["values"]["undelivered_uniform"]
    ol["ol_amount"] = np.where(undelivered[of],
                               rng.integers(a_lo, a_hi + 1, lines),
                               amount["values"]["delivered"])
    given["item"]["i_id"] = np.arange(1, spec["items"] + 1, dtype=np.int64)
    s = given["stock"]
    s["s_w_id"], s["s_i_id"] = grid(w_n, per["stock"])
    rows = {"warehouse": w_n, "district": districts,
            "customer": districts * c_n, "history": districts * c_n,
            "new_order": districts * new_n, "orders": orders,
            "order_line": lines, "item": spec["items"],
            "stock": w_n * per["stock"]}
    out = {}
    for tb in spec["tables"]:
        name = tb["name"]
        cols = {c["name"]: _column(draw, c, rows[name], spec, given[name])
                for c in tb["columns"]}
        out[name] = {"name": name, "key": tb["key"],
                     "columns": tb["columns"], "rows": rows[name],
                     "cols": cols, "nulls": nulls[name]}
    return out


# -- as MySQL's text protocol sends it -------------------------------------------

def decimal_text(unscaled: np.ndarray, scale: int) -> pa.Array:
    """DECIMAL(m,scale) as MySQL prints it: -10.00, 0.1234, 300000.00."""
    mag = np.abs(unscaled)
    whole = pa.array(mag // 10 ** scale).cast(pa.large_string())
    if scale:
        frac = pc.utf8_lpad(
            pa.array(mag % 10 ** scale).cast(pa.large_string()),
            scale, "0")
        whole = pc.binary_join_element_wise(
            whole, frac, pa.scalar(".", pa.large_string()))
    sign = pa.array(["", "-"], type=pa.large_string()).take(
        pa.array((unscaled < 0).astype(np.int8)))
    return pc.binary_join_element_wise(
        sign, whole, pa.scalar("", pa.large_string()))


def text_columns(table: dict) -> list[pa.Array]:
    """Every column as the text a MySQL server sends, NULL where the
    column is NULL."""
    out = []
    for c in table["columns"]:
        v = table["cols"][c["name"]]
        if c["kind"] == "int":
            text = pa.array(v).cast(pa.large_string())
        elif c["kind"] == "dec":
            text = decimal_text(v, c["scale"])
        elif c["kind"] == "datetime":
            text = pc.strftime(pa.array(v, type=pa.timestamp("s")),
                               format="%Y-%m-%d %H:%M:%S") \
                .cast(pa.large_string())
        else:
            text = v
        null = table["nulls"].get(c["name"])
        if null is not None:
            text = pc.if_else(pa.array(null), pa.scalar(None, text.type),
                              text)
        out.append(text.combine_chunks()
                   if isinstance(text, pa.ChunkedArray) else text)
    return out


def _buffers(arr: pa.Array) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(data, offsets, null mask) of a large_string array."""
    n = len(arr)
    bufs = arr.buffers()
    offsets = np.frombuffer(bufs[1], dtype=np.int64)[
        arr.offset:arr.offset + n + 1]
    data = np.frombuffer(bufs[2], dtype=np.uint8) if bufs[2] is not None \
        else np.zeros(0, dtype=np.uint8)
    null = np.asarray(arr.is_null()) if arr.null_count \
        else np.zeros(n, dtype=bool)
    return data, offsets, null


def frame_rows(texts: list[pa.Array]) -> tuple[np.ndarray, np.ndarray]:
    """(the rows as text-protocol row packets end to end, the offset of
    every row's packet in them and the end): a packet is a 3-byte
    little-endian length, a sequence byte and the fields, each a
    length-encoded string (0xFB alone for NULL).  The sequence byte counts
    rows from 0: no client of this benchmark checks it against its own."""
    n = len(texts[0])
    cols = [_buffers(t) for t in texts]
    lens = np.stack([np.where(null, 0, off[1:] - off[:-1])
                     for _d, off, null in cols], axis=1)
    null = np.stack([c[2] for c in cols], axis=1)
    if (lens >= 1 << 16).any():
        raise ValueError("tpccgen: a field of 64 KiB or more")
    head = np.where(~null & (lens >= 251), 3, 1)
    cell = head + lens
    payload = cell.sum(axis=1)
    row_at = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(payload + 4, out=row_at[1:])
    out = np.zeros(int(row_at[-1]), dtype=np.uint8)
    out[row_at[:-1]] = payload & 0xFF
    out[row_at[:-1] + 1] = (payload >> 8) & 0xFF
    out[row_at[:-1] + 2] = payload >> 16
    out[row_at[:-1] + 3] = np.arange(n) & 0xFF
    cell_at = row_at[:-1, None] + 4 + np.cumsum(cell, axis=1) - cell
    for j, (data, off, _null) in enumerate(cols):
        at, ln = cell_at[:, j], lens[:, j]
        wide = head[:, j] == 3
        out[at] = np.where(null[:, j], 0xFB, np.where(wide, 0xFC, ln))
        if wide.any():
            out[at[wide] + 1] = ln[wide] & 0xFF
            out[at[wide] + 2] = ln[wide] >> 8
        total = int(ln.sum())
        if total:
            starts = np.zeros(n + 1, dtype=np.int64)
            np.cumsum(ln, out=starts[1:])
            src = np.arange(total, dtype=np.int64) \
                + np.repeat(off[:-1] - starts[:-1], ln)
            dst = np.arange(total, dtype=np.int64) \
                + np.repeat(at + head[:, j] - starts[:-1], ln)
            out[dst] = data[src]
    return out, row_at
