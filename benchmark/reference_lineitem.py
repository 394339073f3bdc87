"""The plain reference of the `tpch-lineitem-pg2ch` configuration, and the
comparison that decides `correct` in its cells.

What should be in the sink is worked out from the generator's own integer
arrays (quantity, discount, tax and price in hundredths, dates in day
numbers): the filter is a dozen lines of numpy over integers and
`datetime.date`, read from the cell's own filter text by a few regular
expressions.  No line of the program under test is imported and nothing it
has computed is taken; the COPY text the stand-in serves is never parsed;
no float is anywhere (a literal becomes hundredths through
`decimal.Decimal` of its text), and the expected sink values are rendered
here (`f"{cents // 100}.{cents % 100:02d}"`, day numbers, the blank-padded
CHARs of the generator's pools).  Every completed pass's landed
rows are compared whole, by (l_orderkey, l_linenumber), over all 16
columns: integers and dates as numbers, numeric(15,2) and the character
columns as the text the stand-in sent.  Every comparison is exact, so
every limit is 0.
"""

from __future__ import annotations

import datetime
import decimal
import re

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

_EPOCH = datetime.date(1970, 1, 1)
_OPS = {"<": np.less, "<=": np.less_equal, ">": np.greater,
        ">=": np.greater_equal, "=": np.equal, "!=": np.not_equal}
_NUM = r"-?\d+(?:\.\d+)?"
_DATE = re.compile(r"^(\w+)\s*(<=|>=|!=|<|>|=)\s*'([^']+)'$")
_CMP = re.compile(rf"^(\w+)\s*(<=|>=|!=|<|>|=)\s*({_NUM})$")
_BETWEEN = re.compile(rf"^(\w+)\s+BETWEEN\s+({_NUM})\s+AND\s+({_NUM})$",
                      re.I)


def _cents(text: str) -> int:
    scaled = decimal.Decimal(text) * 100
    if scaled != scaled.to_integral_value():
        raise ValueError(f"reference filter: {text} is no whole cent")
    return int(scaled)


def eval_filter(expr: str, table: dict) -> np.ndarray:
    """`term (AND term)*` over the generator's arrays -> bool mask; a
    term is `date_col OP 'YYYY-MM-DD'`, `numeric_col OP number` or
    `numeric_col BETWEEN number AND number`.  BETWEEN's own AND is told
    from the joining ones by reading the terms left to right."""
    words = re.split(r"\s+AND\s+", expr.strip(), flags=re.I)
    terms = []
    while words:
        t = words.pop(0)
        if re.search(r"\sBETWEEN\s", t, re.I):
            t += " AND " + words.pop(0)
        terms.append(t)
    cols, pg = table["cols"], table["pg_types"]
    out = np.ones(table["rows"], dtype=bool)
    for t in terms:
        if m := _DATE.match(t):
            name, op, iso = m.groups()
            if pg[name] != "date":
                raise ValueError(f"reference filter: {name} is no date")
            out &= _OPS[op](cols[name], (datetime.date.fromisoformat(iso)
                                         - _EPOCH).days)
        elif m := _BETWEEN.match(t):
            name, lo, hi = m.groups()
            if not pg[name].startswith("numeric"):
                raise ValueError(f"reference filter: {name} is no numeric")
            out &= (cols[name] >= _cents(lo)) & (cols[name] <= _cents(hi))
        elif m := _CMP.match(t):
            name, op, lit = m.groups()
            if not pg[name].startswith("numeric"):
                raise ValueError(f"reference filter: {name} is no numeric")
            out &= _OPS[op](cols[name], _cents(lit))
        else:
            raise ValueError(f"reference filter: cannot read {t!r}")
    return out


def cents_text(cents) -> list[bytes]:
    """numeric(15,2) as PostgreSQL prints it and the sink lands it:
    `17.00`, `0.05` (hundredths are never negative in LINEITEM)."""
    return [f"{int(c) // 100}.{int(c) % 100:02d}".encode() for c in cents]


def row_keys(orderkey, linenumber) -> np.ndarray:
    return np.asarray(orderkey).astype(np.int64) * 8 \
        + np.asarray(linenumber).astype(np.int64)


def expected_rows(table: dict, filter_expr: str) -> dict:
    """The rows that pass, sorted by key: {"keys", "cols": {name: int64
    array | large_binary array}}."""
    keep = np.flatnonzero(eval_filter(filter_expr, table))
    keys = row_keys(table["cols"]["l_orderkey"][keep],
                    table["cols"]["l_linenumber"][keep])
    order = np.argsort(keys, kind="stable")
    keep, keys = keep[order], keys[order]
    cols = {}
    for name in table["names"]:
        v = table["cols"][name][keep]
        if name in table["pools"]:
            pool = pa.array(table["pools"][name], type=pa.large_binary())
            cols[name] = pool.take(pa.array(v))
        elif table["pg_types"][name].startswith("numeric"):
            cols[name] = pa.array(cents_text(v), type=pa.large_binary())
        else:
            cols[name] = v
    return {"keys": keys, "cols": cols, "source_rows": table["rows"]}


def compare_pass(inserts: list, ch_types: dict, expected: dict) -> dict:
    live = [i for i in inserts if i.rows]
    n = sum(i.rows for i in live)
    names = list(expected["cols"])
    if not live or any(c not in live[0].cols
                       for c in ("l_orderkey", "l_linenumber")):
        return {"rows_missing": len(expected["keys"]), "rows_extra": n,
                "rows_duplicated": 0, "cells_mismatched": 0,
                "rows_compared": 0, "rows_bad": 0}
    keys = row_keys(
        np.concatenate([np.asarray(i.cols["l_orderkey"]) for i in live]),
        np.concatenate([np.asarray(i.cols["l_linenumber"]) for i in live]))
    order = np.argsort(keys, kind="stable")
    keys = keys[order]
    first = np.ones(n, dtype=bool)
    first[1:] = keys[1:] != keys[:-1]
    exp_keys = expected["keys"]
    at = np.searchsorted(exp_keys, keys)
    at[at >= len(exp_keys)] = 0
    known = exp_keys[at] == keys if len(exp_keys) else np.zeros(n, bool)
    good = known & first
    matched = at[good]
    cells_bad = 0
    rows_bad = np.zeros(int(good.sum()), dtype=bool)
    for name in names:
        want = expected["cols"][name]
        parts = [i.cols.get(name) for i in live]
        numeric = isinstance(want, np.ndarray)
        if name not in ch_types or any(
                p is None or isinstance(p, np.ndarray) != numeric
                for p in parts):
            cells_bad += len(matched)      # no such column, or of the
            rows_bad[:] = True             # other kind
            continue
        nulls = np.concatenate(
            [i.masks.get(name, np.zeros(i.rows, dtype=bool))
             for i in live])[order][good]
        if numeric:
            got = np.concatenate(parts).astype(np.int64)[order][good]
            bad = (got != want[matched]) | nulls
        else:
            got = pa.concat_arrays(parts).take(pa.array(order[good]))
            bad = ~pc.equal(got, want.take(pa.array(matched))).to_numpy(
                zero_copy_only=False) | nulls
        cells_bad += int(bad.sum())
        rows_bad |= bad
    return {
        "rows_missing": int(len(exp_keys) - good.sum()),
        "rows_extra": int((first & ~known).sum()),
        "rows_duplicated": int((~first).sum()),
        "cells_mismatched": cells_bad,
        "rows_compared": int(good.sum()),
        "rows_bad": int(rows_bad.sum()),
    }


def compare_snapshot(passes: list[dict], expected: dict) -> dict:
    """`passes`: [{"inserts", "ch_types", "tables"}] of every completed
    pass of the window."""
    total = dict.fromkeys(("rows_missing", "rows_extra", "rows_duplicated",
                           "cells_mismatched", "rows_compared", "rows_bad"),
                          0)
    tables_unexpected = 0
    for p in passes:
        tables_unexpected += max(0, len(p["tables"]) - 1)
        for k, v in compare_pass(p["inserts"], p["ch_types"],
                                 expected).items():
            total[k] += v
    compared = total.pop("rows_compared")
    rows_bad = total.pop("rows_bad")
    numbers = {k: [v, 0] for k, v in total.items()}
    numbers["tables_unexpected"] = [tables_unexpected, 0]
    numbers["no_pass_completed"] = [0 if passes else 1, 0]
    kept = len(expected["keys"])
    attempted = kept * len(passes)
    failed = min(attempted, total["rows_missing"] + total["rows_extra"]
                 + total["rows_duplicated"] + rows_bad)
    return {"numbers": numbers, "attempted": attempted, "failed": failed,
            "info": {"passes": len(passes), "rows_compared": compared,
                     "kept_per_pass": kept,
                     "source_rows": expected["source_rows"]}}
