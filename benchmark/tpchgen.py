"""TPC-H's LINEITEM, as integer arrays and as the text PostgreSQL's
`COPY ... TO STDOUT WITH (FORMAT csv)` writes of it.

What the table is - its 16 columns, their SQL types and the ranges of the
specification's population clause - is the data file the configuration
names (`configs/tpch-lineitem-columns.json`), and none of its numbers is
written here.  dbgen's own random streams are not reproduced: the values
are drawn by numpy from `--seed`, so one seed gives one table; the row
count is the specification's (6,001,215 a scale factor: orders' line counts
are drawn 1 to 7 and then moved by one, within that range, until they add
up to it).  Everything a comparison needs stays an integer:
money in cents, dates in days since 1970-01-01, the character columns as
indexes into small pools.

numpy and pyarrow only: the world imports this, never the program.
"""

from __future__ import annotations

import datetime
import io
import json

import numpy as np
import pyarrow as pa
import pyarrow.csv as pacsv

_EPOCH = datetime.date(1970, 1, 1)
# placeholders for the delimiter and the quote while pyarrow writes the
# text: it writes every string quoted or none, PostgreSQL only those that
# need it
_SEP, _QUOTE = 1, 2
_TO_CSV = bytes.maketrans(bytes([_SEP, _QUOTE]), b',"')


def _days(text: str) -> int:
    return (datetime.date.fromisoformat(text) - _EPOCH).days


def load_columns(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def _spec(spec: dict, name: str):
    return next(c for c in spec["columns"] if c["name"] == name)["values"]


def _comment_pool(spec: dict, rng) -> list[bytes]:
    """`comment_pool` pieces, 10 to 43 characters each, of a text made of
    the grammar's words: sentences of noun and verb phrases, a comma in
    some noun phrases (such a field is quoted in CSV), a terminator."""
    w = spec["text_words"]
    lo, hi = _spec(spec, "l_comment")["text_length"]

    def pick(kind):
        words = w[kind]
        return words[int(rng.integers(0, len(words)))]

    parts = []
    size = 0
    while size < 400_000:
        np_ = [pick("adjectives"), pick("nouns")]
        if rng.random() < 0.25:
            np_.insert(1, pick("adjectives"))
            np_[0] += ","
        sentence = " ".join(np_ + [pick("verbs"), pick("adverbs"),
                                   pick("prepositions"), "the",
                                   pick("nouns")]) + pick("terminators") + " "
        parts.append(sentence)
        size += len(sentence)
    text = "".join(parts).encode()
    n = int(spec["comment_pool"])
    starts = rng.integers(0, len(text) - hi, n)
    lens = rng.integers(lo, hi + 1, n)
    return [text[int(s):int(s + ln)].strip() or b"final deposits"
            for s, ln in zip(starts, lens)]


def generate(seed: int, scale_factor: float, spec: dict) -> dict:
    """{column: integer array} of the whole table, and the pools the
    character columns index: `pools[name][code]` is the field's text."""
    rng = np.random.default_rng([seed, 0x7C9])
    orders = max(1, int(round(spec["orders_per_scale_factor"]
                              * scale_factor)))
    every, first = (spec["order_key"]["used_of_every"],
                    spec["order_key"]["first"])
    i = np.arange(orders, dtype=np.int64)
    order_key = (i // first) * every + i % first + 1
    d_lo, d_hi = (_days(spec["order_date"][k]) for k in ("from", "to"))
    order_date = rng.integers(d_lo, d_hi + 1, orders)
    l_lo, l_hi = spec["lines_per_order"]
    lines = rng.integers(l_lo, l_hi + 1, orders)
    want = int(round(spec["rows_per_scale_factor"] * scale_factor))
    want = min(max(want, orders * l_lo), orders * l_hi)
    while (diff := want - int(lines.sum())):
        # the published row count: orders with room move by one line
        step = 1 if diff > 0 else -1
        room = np.flatnonzero(lines < l_hi if diff > 0 else lines > l_lo)
        lines[rng.choice(room, min(abs(diff), len(room)),
                         replace=False)] += step
    n = int(lines.sum())
    of = np.repeat(i, lines)
    starts = np.cumsum(lines) - lines
    linenumber = np.arange(n, dtype=np.int64) - starts[of] + 1

    parts = max(1, int(spec["parts_per_scale_factor"] * scale_factor))
    supps = max(4, int(spec["suppliers_per_scale_factor"] * scale_factor))
    partkey = rng.integers(1, parts + 1, n)
    j = rng.integers(0, 4, n)
    suppkey = (partkey + j * (supps // 4 + (partkey - 1) // supps)) \
        % supps + 1
    q_lo, q_hi = _spec(spec, "l_quantity")["uniform"]
    quantity = rng.integers(q_lo, q_hi + 1, n)
    retail = 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)
    d0, d1 = _spec(spec, "l_discount")["uniform_cents"]
    t0, t1 = _spec(spec, "l_tax")["uniform_cents"]
    s0, s1 = _spec(spec, "l_shipdate")["order_date_plus"]
    c0, c1 = _spec(spec, "l_commitdate")["order_date_plus"]
    r0, r1 = _spec(spec, "l_receiptdate")["ship_date_plus"]
    shipdate = order_date[of] + rng.integers(s0, s1 + 1, n)
    commitdate = order_date[of] + rng.integers(c0, c1 + 1, n)
    receiptdate = shipdate + rng.integers(r0, r1 + 1, n)
    today = _days(spec["current_date"])
    # pools: R A N | O F
    returnflag = np.where(receiptdate <= today, rng.integers(0, 2, n), 2)
    linestatus = np.where(shipdate > today, 0, 1)
    instruct = _spec(spec, "l_shipinstruct")["choice"]
    modes = _spec(spec, "l_shipmode")["choice"]
    comments = _comment_pool(spec, rng)
    cols = {
        "l_orderkey": order_key[of], "l_partkey": partkey,
        "l_suppkey": suppkey, "l_linenumber": linenumber,
        "l_quantity": quantity * 100, "l_extendedprice": quantity * retail,
        "l_discount": rng.integers(d0, d1 + 1, n),
        "l_tax": rng.integers(t0, t1 + 1, n),
        "l_returnflag": returnflag, "l_linestatus": linestatus,
        "l_shipdate": shipdate, "l_commitdate": commitdate,
        "l_receiptdate": receiptdate,
        "l_shipinstruct": rng.integers(0, len(instruct), n),
        "l_shipmode": rng.integers(0, len(modes), n),
        "l_comment": rng.integers(0, len(comments), n),
    }
    pools = {
        "l_returnflag": [b"R", b"A", b"N"], "l_linestatus": [b"O", b"F"],
        # character(n) comes out of PostgreSQL padded to n
        "l_shipinstruct": [s.encode().ljust(25) for s in instruct],
        "l_shipmode": [s.encode().ljust(10) for s in modes],
        "l_comment": comments,
    }
    return {"rows": n, "cols": {k: np.ascontiguousarray(v, dtype=np.int64)
                                for k, v in cols.items()},
            "pools": pools,
            "csv_pools": {k: pa.array([_csv_field(v) for v in pool],
                                      type=pa.binary())
                          for k, pool in pools.items()},
            "names": [c["name"] for c in spec["columns"]],
            "pg_types": {c["name"]: c["pg"] for c in spec["columns"]}}


def cents_text(cents: np.ndarray) -> list[bytes]:
    """numeric(15,2) as PostgreSQL prints it: `17.00`, `0.05`."""
    return [b"%d.%02d" % divmod(int(c), 100) for c in cents]


def _numeric(cents: np.ndarray) -> pa.Array:
    words = np.empty((len(cents), 2), dtype=np.int64)
    words[:, 0] = cents
    words[:, 1] = cents >> 63
    return pa.Array.from_buffers(pa.decimal128(15, 2), len(cents),
                                 [None, pa.py_buffer(words)])


def _csv_field(value: bytes) -> bytes:
    """A character field as COPY's csv mode writes it: quoted only where
    it holds the delimiter, a quote or a line end (placeholders here)."""
    if b"," in value or b'"' in value or b"\n" in value:
        return bytes([_QUOTE]) + value.replace(
            b'"', bytes([_QUOTE, _QUOTE])) + bytes([_QUOTE])
    return value


def copy_text(table: dict, lo: int, hi: int) -> bytes:
    """Rows [lo, hi) as `COPY (SELECT <all columns>) TO STDOUT WITH
    (FORMAT csv)` writes them, one line a row."""
    arrays = []
    for name in table["names"]:
        col = table["cols"][name][lo:hi]
        pg = table["pg_types"][name]
        if name in table["pools"]:
            arrays.append(table["csv_pools"][name].take(
                pa.array(col.astype(np.int32))))
        elif pg.startswith("numeric"):
            arrays.append(_numeric(col))
        elif pg == "date":
            arrays.append(pa.array(col.astype(np.int32)).cast(pa.date32()))
        else:
            arrays.append(pa.array(col))
    out = io.BytesIO()
    pacsv.write_csv(
        pa.Table.from_arrays(arrays, names=table["names"]), out,
        pacsv.WriteOptions(include_header=False, delimiter=chr(_SEP),
                           quoting_style="none"))
    return out.getvalue().translate(_TO_CSV)


def frame_rows(text: bytes) -> tuple[np.ndarray, np.ndarray]:
    """The text as the backend sends it: one CopyData message a row
    (`d`, int32 length of the rest, the line).  Returns (the framed bytes,
    the offset of every row's message in them and the end)."""
    buf = np.frombuffer(text, dtype=np.uint8)
    ends = np.flatnonzero(buf == 10) + 1
    if len(ends) == 0 or ends[-1] != len(buf):
        raise ValueError("COPY text does not end with a line end")
    n = len(ends)
    starts = np.concatenate(([0], ends[:-1]))
    at = starts + 5 * np.arange(n)            # each header in the output
    out = np.empty(len(buf) + 5 * n, dtype=np.uint8)
    body = np.ones(len(out), dtype=bool)
    length = (ends - starts + 4).astype(np.uint32)
    out[at] = ord("d")
    body[at] = False
    for k in range(4):
        out[at + 1 + k] = (length >> (8 * (3 - k))) & 0xFF
        body[at + 1 + k] = False
    out[body] = buf
    return out, np.concatenate((at, [len(out)]))
