"""The plain reference of the `tpcc-mysql2kafka-debezium` configuration, and
the comparison that decides `correct` in its cell.

What a pass must have put into the topic is worked out from the
generator's arrays (`tpccgen.generate`): per table the multiset of primary
keys - of whole rows for HISTORY, which has no key - that must have landed
once each, and for the keys of the seed's one-in-`sample_one_in` class
(every WAREHOUSE and DISTRICT row) the whole envelope, field by field.  No
line of the program under test is imported: record batches are decoded
here, JSON by the standard library, the mask by `hashlib` (`events.Hmac`).

The handling modes are the configuration's (`handling` in its file) and
only the stated ones are implemented: DECIMAL as the string MySQL prints
(`decimal.handling.mode=string`), DATETIME as epoch milliseconds
(`io.debezium.time.Timestamp`), CHAR without its pad, NULL as JSON null,
a table without a primary key under a null message key.

Every comparison is exact, so every limit is 0.
"""

from __future__ import annotations

import collections
import json
import re
import struct
import zlib

import numpy as np

from benchmark import events as ev

_MASK64 = (1 << 64) - 1
_MIX = 0x9E3779B97F4A7C15
ALWAYS_SAMPLED = ("warehouse", "district")
_PAIR = re.compile(rb'"(\w+)"\s*:\s*(-?\d+)')
_TABLE = re.compile(rb'"table"\s*:\s*"(\w+)"')
_CONNECT = {"tinyint": "int16", "smallint": "int16", "int": "int32",
            "bigint": "int64"}
_HANDLING = {"decimal": "string", "datetime": "epoch_millis",
             "char": "unpadded", "null": "json_null",
             "keyless_table": "null_key"}


def check_handling(handling: dict) -> None:
    for k, v in _HANDLING.items():
        if handling.get(k) != v:
            raise ValueError(f"reference_tpcc implements {k}={v!r}, the "
                             f"configuration states {handling.get(k)!r}")


# -- sampling -------------------------------------------------------------------------

def _start(table: str, seed: int) -> int:
    return (zlib.crc32(table.encode()) ^ (seed * _MIX)) & _MASK64


def sampled(table: str, key: tuple, seed: int, one_in: int) -> bool:
    """Whether the row of `table` under `key` (the primary key's values
    in the key's order) is in the seed's class."""
    if table in ALWAYS_SAMPLED:
        return True
    h = _start(table, seed)
    for v in key:
        h = ((h ^ (v & _MASK64)) * _MIX) & _MASK64
        h ^= h >> 29
    return h % one_in == 0


def sampled_mask(table: str, keys: np.ndarray, seed: int,
                 one_in: int) -> np.ndarray:
    """`sampled` over an (n, k) array of keys."""
    if table in ALWAYS_SAMPLED:
        return np.ones(len(keys), dtype=bool)
    h = np.full(len(keys), _start(table, seed), dtype=np.uint64)
    with np.errstate(over="ignore"):
        for j in range(keys.shape[1]):
            h = (h ^ keys[:, j].astype(np.int64).view(np.uint64)) \
                * np.uint64(_MIX)
            h ^= h >> np.uint64(29)
    return h % np.uint64(one_in) == 0


# -- record batches ------------------------------------------------------------------

def iter_records(blob: bytes):
    """(key or None, value or None) of every record of one uncompressed
    record batch v2."""
    count, = struct.unpack_from("!i", blob, 57)
    attributes, = struct.unpack_from("!h", blob, 21)
    if attributes & 0x07:
        raise ValueError("reference_tpcc: a compressed record batch")
    pos = 61
    for _ in range(count):
        length, pos = _varint(blob, pos)
        end = pos + length
        pos += 1                                  # attributes
        _ts, pos = _varint(blob, pos)
        _delta, pos = _varint(blob, pos)
        klen, pos = _varint(blob, pos)
        key = None
        if klen >= 0:
            key = blob[pos:pos + klen]
            pos += klen
        vlen, pos = _varint(blob, pos)
        value = None
        if vlen >= 0:
            value = blob[pos:pos + vlen]
        yield key, value
        pos = end


def _varint(buf: bytes, pos: int) -> tuple[int, int]:
    b = buf[pos]
    if b < 0x80:
        return (b >> 1) ^ -(b & 1), pos + 1
    z, shift = b & 0x7F, 7
    pos += 1
    while True:
        b = buf[pos]
        pos += 1
        z |= (b & 0x7F) << shift
        if b < 0x80:
            return (z >> 1) ^ -(z & 1), pos
        shift += 7


# -- what a pass landed ------------------------------------------------------------

class PassDigest:
    """One pass's records, reduced: per table the key values and the
    partition of every keyed record, the `after` object of every record
    without a key, the sizes, and key and value of the sampled ones."""

    def __init__(self, spec: dict, seed: int, one_in: int):
        self.seed, self.one_in = seed, one_in
        self.key_of = {t["name"]: t["key"] for t in spec["tables"]}
        self.keys: dict[str, list] = collections.defaultdict(list)
        self.partitions: dict[str, list] = collections.defaultdict(list)
        self.keyless: dict[str, list] = collections.defaultdict(list)
        self.samples: dict[tuple, list] = collections.defaultdict(list)
        self.records = 0
        self.key_bytes = 0
        self.value_bytes = 0
        self.unparsed = 0
        self.partitions_written: set[int] = set()

    def add(self, partition: int, blob: bytes) -> None:
        for key, value in iter_records(blob):
            self.records += 1
            self.partitions_written.add(partition)
            self.key_bytes += len(key or b"")
            self.value_bytes += len(value or b"")
            if value is None:
                self.unparsed += 1
                continue
            # the writer's compact form first, any JSON spacing after
            at = value.rfind(b'"table":"')
            if at >= 0:
                table = value[at + 9:value.index(b'"', at + 9)].decode()
            else:
                named = _TABLE.findall(value)
                table = named[-1].decode() if named else ""
            names = self.key_of.get(table)
            if names is None:
                self.unparsed += 1
            elif key is None:
                a = value.rfind(b'"after":')
                b = value.find(b',"source":', a)
                if a >= 0 and b >= 0:
                    self.keyless[table].append(value[a + 8:b])
                    continue
                try:
                    doc = json.loads(value)
                    after = doc.get("payload", doc)["after"]
                    self.keyless[table].append(json.dumps(after).encode())
                except (ValueError, KeyError, TypeError, AttributeError):
                    self.unparsed += 1
            else:
                at = key.rfind(b'"payload"')
                got = dict(_PAIR.findall(key[at + 9:] if at >= 0 else key))
                try:
                    pk = tuple(int(got[n.encode()]) for n in names)
                except KeyError:
                    self.unparsed += 1
                    continue
                self.keys[table].append(pk)
                self.partitions[table].append(partition)
                if sampled(table, pk, self.seed, self.one_in):
                    self.samples[(table, pk)].append((key, value, partition))


# -- what a pass must land ---------------------------------------------------------

def _composite(keys: np.ndarray, radix: np.ndarray) -> np.ndarray:
    """Keys as one int64 each (mixed radix); -1 for a key out of range."""
    out = np.zeros(len(keys), dtype=np.int64)
    bad = np.zeros(len(keys), dtype=bool)
    for j, r in enumerate(radix):
        col = keys[:, j]
        bad |= (col < 0) | (col >= r)
        out = out * int(r) + col
    out[bad] = -1
    return out


def _value(table: dict, c: dict, i: int):
    """What `after` must hold of column c in row i, under the stated
    handling modes."""
    null = table["nulls"].get(c["name"])
    if null is not None and null[i]:
        return None
    v = table["cols"][c["name"]][i]
    if c["kind"] == "int":
        return int(v)
    if c["kind"] == "dec":
        v, scale = int(v), c["scale"]
        sign, mag = ("-" if v < 0 else ""), abs(v)
        if not scale:
            return f"{sign}{mag}"
        return f"{sign}{mag // 10 ** scale}.{mag % 10 ** scale:0{scale}d}"
    if c["kind"] == "datetime":
        return int(v) * 1000
    return v.as_py()


def field_type(c: dict) -> tuple[str, str | None]:
    """(Connect type, semantic name) of a column in the schema block."""
    if c["kind"] == "int":
        return _CONNECT[c["mysql"].split("(")[0]], None
    if c["kind"] == "datetime":
        return "int64", "io.debezium.time.Timestamp"
    return "string", None


class Expected:
    def __init__(self, db: dict, spec: dict, config: dict, masked: dict,
                 salt: bytes, seed: int, one_in: int):
        """masked: {table: [columns]} of the cell's mask_field steps."""
        check_handling(config["handling"])
        self.db, self.spec = db, spec
        self.database = spec["database"]
        self.include_schema = bool(config["include_schema"])
        self.masked, self.mac = masked, ev.Hmac(salt)
        self.seed, self.one_in = seed, one_in
        self.rows = sum(t["rows"] for t in db.values())
        self.radix, self.composite, self.sample_rows = {}, {}, {}
        for name, t in db.items():
            if not t["key"]:
                continue
            keys = np.stack([t["cols"][k] for k in t["key"]], axis=1)
            self.radix[name] = keys.max(axis=0) + 1
            comp = _composite(keys, self.radix[name])
            if len(np.unique(comp)) != len(comp):
                raise ValueError(f"generator: duplicate key in {name}")
            self.composite[name] = np.sort(comp)
            self.sample_rows[name] = np.flatnonzero(
                sampled_mask(name, keys, seed, one_in))

    def row(self, name: str, i: int) -> dict:
        t = self.db[name]
        out = {c["name"]: _value(t, c, i) for c in t["columns"]}
        for c in self.masked.get(name, ()):
            if out[c] is not None:
                out[c] = self.mac.hexdigest(out[c].encode()).decode()
        return out

    def sha_block_bytes_per_row(self) -> dict:
        """The masked columns' SHA block bytes a masked-table row."""
        import pyarrow.compute as pc

        out = {}
        for name, cols in self.masked.items():
            for c in cols:
                n = pc.binary_length(self.db[name]["cols"][c]).to_numpy() \
                    .astype(np.int64)
                out[c] = float((((n + 9 + 63) // 64) * 64).mean())
        return out


# -- the comparison ------------------------------------------------------------------

def _check_schema(block, t: dict, masked: list, what: str) -> int:
    """Mismatches of a schema block against the table's columns."""
    if not isinstance(block, dict) or block.get("type") != "struct":
        return 1
    fields = {f.get("field"): f for f in block.get("fields", [])}
    if what == "key":
        want = [c for c in t["columns"] if c["name"] in t["key"]]
        have = fields
    else:
        want = t["columns"]
        for part in ("before", "after", "source", "op", "ts_ms"):
            if part not in fields:
                return 1
        have = {f.get("field"): f
                for f in fields["after"].get("fields", [])}
        if fields["before"].get("fields") != fields["after"].get("fields"):
            return 1
    bad = abs(len(have) - len(want))
    for c in want:
        f = have.get(c["name"])
        typ, semantic = ("string", None) if c["name"] in masked \
            else field_type(c)
        if f is None or f.get("type") != typ or f.get("name") != semantic:
            bad += 1
    return bad


def _compare_sample(exp: Expected, name: str, i: int, key: bytes,
                    value: bytes) -> int:
    """Mismatched cells of one sampled envelope."""
    t = exp.db[name]
    masked = exp.masked.get(name, [])
    try:
        k, v = json.loads(key), json.loads(value)
    except ValueError:
        return len(t["columns"]) + 1
    bad = 0
    if exp.include_schema:
        if set(k) != {"schema", "payload"} or set(v) != {"schema",
                                                          "payload"}:
            return len(t["columns"]) + 1
        bad += _check_schema(k["schema"], t, masked, "key")
        bad += _check_schema(v["schema"], t, masked, "value")
        k, v = k["payload"], v["payload"]
    want = exp.row(name, i)
    if k != {c: want[c] for c in t["key"]}:
        bad += 1
    if not isinstance(v, dict):
        return bad + len(t["columns"])
    src = v.get("source") or {}
    bad += (v.get("op") != "r") + (v.get("before") is not None) \
        + (src.get("db") != exp.database) + (src.get("table") != name) \
        + (src.get("connector") != "mysql") \
        + (src.get("snapshot") != "true")
    after = v.get("after")
    if not isinstance(after, dict):
        return bad + len(t["columns"])
    bad += len(set(after) - set(want))
    for c, w in want.items():
        if c not in after or after[c] != w or type(after[c]) is not type(w):
            bad += 1
    return bad


def compare_pass(exp: Expected, d: PassDigest) -> tuple[dict, dict]:
    """(the pass's numbers, {table: (its keys as composites in order,
    their partitions)})."""
    out = dict.fromkeys(("rows_missing", "rows_extra", "rows_duplicated",
                         "sample_cells_mismatched", "sample_keys_missing",
                         "records_unparsed", "rows_compared",
                         "samples_compared"), 0)
    out["records_unparsed"] = d.unparsed
    keyed = {}
    for name, t in exp.db.items():
        if not t["key"]:
            want = collections.Counter(
                json.dumps(exp.row(name, i), separators=(",", ":"),
                           sort_keys=True) for i in range(t["rows"]))
            got = collections.Counter()
            for raw in d.keyless.get(name, ()):
                try:
                    got[json.dumps(json.loads(raw), separators=(",", ":"),
                                   sort_keys=True)] += 1
                except ValueError:
                    out["records_unparsed"] += 1
            out["rows_missing"] += sum((want - got).values())
            out["rows_extra"] += sum((got - want).values())
            out["rows_compared"] += sum((want & got).values())
            # a keyed record of a table without a key is no row of it
            out["rows_extra"] += len(d.keys.get(name, ()))
            continue
        out["rows_extra"] += len(d.keyless.get(name, ()))
        got = np.asarray(d.keys.get(name, []), dtype=np.int64).reshape(
            -1, len(t["key"]))
        comp = _composite(got, exp.radix[name])
        uniq, counts = np.unique(comp, return_counts=True)
        known = np.isin(uniq, exp.composite[name])
        out["rows_duplicated"] += int((counts[known] - 1).sum())
        out["rows_extra"] += int(counts[~known].sum())
        out["rows_missing"] += int(t["rows"] - known.sum())
        out["rows_compared"] += int(known.sum())
        order = np.argsort(comp, kind="stable")
        keyed[name] = (comp[order], np.asarray(
            d.partitions.get(name, []), dtype=np.int64)[order])
        for i in exp.sample_rows[name]:
            pk = tuple(int(t["cols"][k][i]) for k in t["key"])
            hits = d.samples.get((name, pk))
            if not hits:
                out["sample_keys_missing"] += 1
                continue
            out["samples_compared"] += 1
            out["sample_cells_mismatched"] += _compare_sample(
                exp, name, int(i), hits[0][0], hits[0][1])
    return out, keyed


def compare_snapshot(exp: Expected, digests: list[PassDigest],
                     n_partitions: int) -> dict:
    """`digests`: every completed pass of the window."""
    total = dict.fromkeys(("rows_missing", "rows_extra", "rows_duplicated",
                           "sample_cells_mismatched", "sample_keys_missing",
                           "records_unparsed"), 0)
    compared = samples = moved = unwritten = 0
    first = None
    for d in digests:
        got, keyed = compare_pass(exp, d)
        compared += got.pop("rows_compared")
        samples += got.pop("samples_compared")
        for k, v in got.items():
            total[k] += v
        unwritten += n_partitions - len(
            d.partitions_written & set(range(n_partitions)))
        if first is None:
            first = keyed
            continue
        # a key's partition is the same in every pass
        for name, (comp, parts) in keyed.items():
            comp0, parts0 = first.get(name, (comp[:0], parts[:0]))
            both, i0, i1 = np.intersect1d(comp0, comp, return_indices=True)
            moved += int((parts0[i0] != parts[i1]).sum())
    numbers = {k: [v, 0] for k, v in total.items()}
    numbers["partition_moved"] = [moved, 0]
    numbers["partitions_unwritten"] = [unwritten, 0]
    numbers["no_pass_completed"] = [0 if digests else 1, 0]
    attempted = exp.rows * len(digests)
    failed = min(attempted, total["rows_missing"] + total["rows_extra"]
                 + total["rows_duplicated"] + total["records_unparsed"]
                 + total["sample_cells_mismatched"]
                 + total["sample_keys_missing"] + moved)
    return {"numbers": numbers, "attempted": attempted, "failed": failed,
            "info": {"passes": len(digests), "rows_compared": compared,
                     "samples_compared": samples,
                     "source_rows": exp.rows,
                     "records_per_pass": [d.records for d in digests],
                     "landed_bytes_per_pass": [
                         d.key_bytes + d.value_bytes for d in digests]}}
