"""The plain reference, and the comparison that decides `correct`.

What should be in the sink is worked out here from what the world produced
(the kafka events) or from the source files (the parquet table, read with
pyarrow), with hashlib for the mask and a dozen lines for the filter: no
line of the program under test is imported, and nothing it has computed is
taken.  Every comparison is exact, so every limit is 0.

A comparison returns {"numbers": {name: [value, limit]}, "attempted",
"failed", "info"}; `correct` is every value <= its limit.
"""

from __future__ import annotations

import re

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pyarrow.parquet as pq

from benchmark import events as ev

_MIX = np.uint64(0x9E3779B97F4A7C15)


# -- sampling by key ---------------------------------------------------------------

def key_sampler(key: str, one_in: int, seed: int):
    """keep(cols) -> bool mask: the rows whose key hashes into the seed's
    residue class; None where the table has no such column."""
    salt = np.uint64(np.random.default_rng([seed, 77]).integers(1, 2**62))

    def keep(cols: dict):
        if key not in cols:
            return None
        k = np.asarray(cols[key]).astype(np.int64).view(np.uint64)
        with np.errstate(over="ignore"):
            h = (k ^ salt) * _MIX
        return (h >> np.uint64(33)) % np.uint64(one_in) == 0

    return keep


# -- the filter -------------------------------------------------------------------------

_CMP = re.compile(r"^\s*(\w+)\s*(<=|>=|!=|<|>|=)\s*(-?\d+)\s*$")
_OPS = {"<": np.less, "<=": np.less_equal, ">": np.greater,
        ">=": np.greater_equal, "=": np.equal, "!=": np.not_equal}


def _terms(expr: str) -> list[str]:
    terms = re.split(r"\s+AND\s+", expr.strip(), flags=re.I)
    for t in terms:
        if not _CMP.match(t):
            raise ValueError(f"reference filter: cannot read {t!r}")
    return terms


def eval_filter(expr: str, column) -> np.ndarray:
    """`col OP int (AND col OP int)*` over column(name) -> bool mask."""
    out = None
    for t in _terms(expr):
        name, op, lit = _CMP.match(t).groups()
        m = _OPS[op](np.asarray(column(name)).astype(np.int64), int(lit))
        out = m if out is None else out & m
    return out


# -- snapshot cells -------------------------------------------------------------------------

def _as_bytes_array(col) -> pa.Array:
    if isinstance(col, pa.ChunkedArray):
        col = col.combine_chunks()
    return col.cast(pa.large_binary())


def masked_values(col: pa.Array, mac: ev.Hmac) -> pa.Array:
    """hex(HMAC-SHA256(salt, value)) of every value, hashing each distinct
    value once (the table's strings come from pools)."""
    enc = col.dictionary_encode()
    hexes = pa.array([mac.hexdigest(v) for v in
                      enc.dictionary.to_pylist()], type=pa.large_binary())
    return hexes.take(enc.indices)


def expected_from_source(files: list[str], filter_expr: str,
                         masked: list[str], key: str, keep,
                         mac: ev.Hmac) -> dict:
    """What one pass must land: the count of rows that pass the filter and,
    of those `keep` selects, every column, masked where the chain masks,
    sorted by key.  Also the masked columns' SHA block bytes per source
    row, for the roofline's numerator."""
    from concurrent.futures import ThreadPoolExecutor

    def one(path: str):
        t = pq.read_table(path)
        ok = eval_filter(filter_expr, lambda n: t[n].to_numpy())
        kept = int(ok.sum())
        sel = ok & keep({key: t[key].to_numpy()})
        blocks = {}
        for c in masked:
            n = pc.binary_length(t[c]).to_numpy().astype(np.int64)
            blocks[c] = int((((n + 9 + 63) // 64) * 64).sum())
        return kept, t.filter(pa.array(sel)), t.num_rows, blocks

    with ThreadPoolExecutor(max_workers=4) as pool:
        parts = list(pool.map(one, files))
    table = pa.concat_tables([p[1] for p in parts]).combine_chunks()
    order = np.argsort(table[key].to_numpy(), kind="stable")
    table = table.take(pa.array(order))
    cols = {}
    for name in table.column_names:
        col = table[name].combine_chunks()
        if name in masked:
            col = masked_values(_as_bytes_array(col), mac)
        cols[name] = col
    source_rows = sum(p[2] for p in parts)
    return {
        "kept": sum(p[0] for p in parts), "source_rows": source_rows,
        "cols": cols, "key": key,
        "sha_block_bytes_per_row": {
            c: sum(p[3][c] for p in parts) / max(source_rows, 1)
            for c in masked},
    }


def _landed_numeric(col: np.ndarray, ch_type: str) -> np.ndarray:
    return np.asarray(col).astype(np.int64 if "Float" not in ch_type
                                  else np.float64)


def _expected_numeric(col: pa.Array, ch_type: str) -> np.ndarray:
    if pa.types.is_timestamp(col.type):
        unit = {"s": 1, "ms": 10**3, "us": 10**6, "ns": 10**9}[col.type.unit]
        ticks = col.cast(pa.int64()).to_numpy()
        per_s = 10**6 if ch_type.startswith("DateTime64") else 1
        return ticks * per_s // unit
    arr = col.to_numpy(zero_copy_only=False)
    return arr.astype(np.float64 if "Float" in ch_type else np.int64)


def compare_pass(inserts: list, ch_types: dict, expected: dict) -> dict:
    """One pass's landed rows against `expected`."""
    key = expected["key"]
    landed_rows = sum(i.rows for i in inserts)
    kept = [i for i in inserts if i.rows and len(i.cols.get(key, ()))]
    exp_keys = expected["cols"][key].to_numpy()
    if kept:
        keys = np.concatenate([np.asarray(i.cols[key]) for i in kept])
    else:
        keys = np.zeros(0, dtype=np.int64)
    order = np.argsort(keys, kind="stable")
    keys = keys[order]
    at = np.searchsorted(exp_keys, keys)
    at[at >= len(exp_keys)] = 0
    known = exp_keys[at] == keys if len(exp_keys) else \
        np.zeros(len(keys), dtype=bool)
    first = np.ones(len(keys), dtype=bool)
    first[1:] = keys[1:] != keys[:-1]
    good = known & first                       # one landed row per key
    matched = at[good]
    cells_bad = 0
    rows_bad = np.zeros(int(good.sum()), dtype=bool)
    for name, exp in expected["cols"].items():
        ch_type = ch_types.get(name)
        if ch_type is None:
            cells_bad += len(matched)
            rows_bad[:] = True
            continue
        parts = [i.cols[name] for i in kept]
        nulls = np.concatenate(
            [i.masks.get(name, np.zeros(len(i.cols[key]), dtype=bool))
             for i in kept])[order][good] if kept else np.zeros(0, bool)
        if isinstance(parts[0], np.ndarray) if parts else False:
            got = _landed_numeric(np.concatenate(parts), ch_type)[order][good]
            want = _expected_numeric(exp, ch_type)[matched]
            bad = (got != want) | nulls
        else:
            got = pa.concat_arrays(parts).take(pa.array(order[good])) \
                if parts else pa.array([], type=pa.large_binary())
            want = _as_bytes_array(exp).take(pa.array(matched))
            bad = ~pc.equal(got, want).to_numpy(zero_copy_only=False) | nulls
        cells_bad += int(bad.sum())
        rows_bad |= bad
    return {
        "rows_missing": max(0, expected["kept"] - landed_rows),
        "rows_extra": max(0, landed_rows - expected["kept"]),
        "sample_keys_missing": int(len(exp_keys) - good.sum()),
        "sample_rows_unexpected": int(len(keys) - good.sum()),
        "sample_cells_mismatched": cells_bad,
        "sample_rows_compared": int(good.sum()),
        "sample_rows_bad": int(rows_bad.sum()),
    }


def compare_snapshot(passes: list[dict], expected: dict) -> dict:
    """`passes`: [{"inserts", "ch_types", "tables"}] of every completed
    pass of the window."""
    total = {k: 0 for k in ("rows_missing", "rows_extra",
                            "sample_keys_missing", "sample_rows_unexpected",
                            "sample_cells_mismatched", "sample_rows_compared",
                            "sample_rows_bad")}
    tables_unexpected = 0
    for p in passes:
        tables_unexpected += max(0, len(p["tables"]) - 1)
        for k, v in compare_pass(p["inserts"], p["ch_types"],
                                 expected).items():
            total[k] += v
    compared = total.pop("sample_rows_compared")
    rows_bad = total.pop("sample_rows_bad")
    numbers = {k: [v, 0] for k, v in total.items()}
    numbers["tables_unexpected"] = [tables_unexpected, 0]
    numbers["no_pass_completed"] = [0 if passes else 1, 0]
    attempted = expected["kept"] * len(passes)
    failed = min(attempted, total["rows_missing"] + total["rows_extra"]
                 + rows_bad + total["sample_keys_missing"])
    return {"numbers": numbers, "attempted": attempted, "failed": failed,
            "info": {"passes": len(passes), "sample_rows_compared": compared,
                     "kept_per_pass": expected["kept"]}}


# -- kafka cells ---------------------------------------------------------------------------

def compare_events(inserts: list, tables: list[str], expect_table: str,
                   truth: dict, sent: np.ndarray, attempted: np.ndarray,
                   mac: ev.Hmac) -> dict:
    """`sent` / `attempted`: per event index, whether the generator sent
    it and whether the system has to have landed it; `truth`: the users,
    amounts (in eighths) and timestamps by event index."""
    produced = len(sent)
    stray = [sorted(i.cols) for i in inserts if "id" not in i.cols]
    if stray:
        raise ValueError(f"landed inserts without an id column: tables "
                         f"{tables}, columns {stray[:3]}")
    ids = np.concatenate([np.asarray(i.cols["id"], dtype=np.int64)
                          for i in inserts]) if inserts \
        else np.zeros(0, dtype=np.int64)
    idx = ids - ev.ID0
    known = (idx >= 0) & (idx < produced)
    known[known] = sent[idx[known]]
    idx_k = idx[known]
    seen = np.zeros(produced, dtype=bool)
    seen[idx_k] = True
    bad = np.zeros(len(idx_k), dtype=bool)
    if len(idx_k):
        def col(name):
            return [i.cols[name] for i in inserts]

        def nulls(name):
            return np.concatenate(
                [i.masks.get(name, np.zeros(i.rows, dtype=bool))
                 for i in inserts])[known]

        amount = np.concatenate(col("amount"))[known]
        ts = np.concatenate(col("ts"))[known]
        bad |= (amount != truth["eighths"][idx_k] / 8.0) | nulls("amount")
        bad |= (ts != truth["ts"][idx_k]) | nulls("ts")
        users = truth["users"][idx_k]
        uniq, inverse = np.unique(users, return_inverse=True)
        hexes = pa.array([mac.hexdigest(ev.email_of(u)) for u in uniq],
                         type=pa.large_binary())
        want = hexes.take(pa.array(inverse))
        got = pa.concat_arrays(col("user_email")).filter(pa.array(known))
        bad |= ~pc.equal(got, want).to_numpy(zero_copy_only=False)
        bad |= nulls("user_email")
    events_bad = np.zeros(produced, dtype=bool)
    events_bad[idx_k[bad]] = True
    numbers = {
        "events_missing": [int((attempted & ~seen).sum()), 0],
        "rows_unknown_id": [int((~known).sum()), 0],
        "rows_field_mismatch": [int(bad.sum()), 0],
        "tables_unexpected": [len([t for t in tables if t != expect_table]),
                              0],
    }
    return {"numbers": numbers, "attempted": int(attempted.sum()),
            "failed": int((attempted & (~seen | events_bad)).sum()),
            "info": {"rows_landed": int(len(ids)),
                     "duplicates": int(known.sum() - seen.sum())}}
