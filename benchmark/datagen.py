"""The ClickBench `hits` table, as a directory of parquet part files.

One general generator: what the table is - its 105 columns, their public
types and the statistics their values are drawn to - is the data file the
configuration names (`configs/hits-columns.json`), and nothing of it is
written here.  File i is made from `default_rng([seed, i])` and the string
pools from the seed alone, so the files can be written side by side by a
process pool; the `unique` column carries the row's index in its low bits,
so the comparison can join on it; the count of rows that pass a filter is
the reference's business, not the generator's.

Run before JAX is imported: the pool's workers are spawned, and a process
that holds the chip must not be copied.
"""

from __future__ import annotations

import json
import multiprocessing
import os

import numpy as np

_NP = {"int16": np.int16, "int32": np.int32, "int64": np.int64}
_SCATTER = 7919      # rank -> id step where the column gives none (a prime)

# alphabets: 256 slots each, so one uint8 draw picks a unit by its weight;
# the units of one alphabet are all as wide (Cyrillic letters are two bytes
# in UTF-8, and so are the separators that stand among them)
_LETTERS = "eeeeaaaaoooiiinnnsssrrrtttllcdmpuhgbkvfwyxzjq"
_RU = "оооооеееееаааааииииинннтттссрррввлллккммддппуяяыьгзбчйхжшюцщэф"
_UNITS = {
    "url": list(_LETTERS * 4 + "0123456789" * 3 + "/" * 16 + "-" * 6
                + "_" * 4 + "." * 5 + "=" * 4 + "&" * 3 + "?" * 2 + "%" * 2),
    "token": list(_LETTERS * 3 + "ABCDEFGHIJKLMNOPQRSTUVWXYZ" * 2
                  + "0123456789" * 4 + "_" * 4 + "-" * 4),
    "text_ru": list(_RU) * 6 + [", "] * 20 + [". "] * 8 + [" -"] * 32,
}
CORPUS_BYTES = 1 << 14


def _corpus(seed: int, alphabet: str) -> tuple[np.ndarray, int]:
    """(bytes, unit width): the text every string of this alphabet is a
    piece of.  Real URLs, titles and phrases repeat their words, and a
    parquet writer's snappy finds them; strings of independent random
    letters would not compress at all, and a part file of four row groups
    would pass the checking machine's 128 MiB."""
    units = [u.encode() for u in _UNITS[alphabet]]
    width = len(units[0])
    if any(len(u) != width for u in units):
        raise ValueError(f"alphabet {alphabet!r}: units of unequal width")
    table = np.frombuffer(b"".join(
        units[i * len(units) // 256] for i in range(256)),
        dtype=np.uint8).reshape(256, width)
    rng = np.random.default_rng(
        [seed, 1 << 29, sorted(_UNITS).index(alphabet)])
    codes = rng.integers(0, 256, CORPUS_BYTES // width, dtype=np.uint8)
    return table[codes].ravel(), width


def load_columns(path: str) -> dict:
    with open(path) as fh:
        doc = json.load(fh)
    names = [c["name"] for c in doc["columns"]]
    if len(set(names)) != len(names):
        raise ValueError(f"{path}: a column is named twice")
    return doc


# -- draws -------------------------------------------------------------------------

_cdfs: dict = {}


def _zipf_ranks(rng, n_ranks: int, s: float, n: int) -> np.ndarray:
    key = (n_ranks, s)
    if key not in _cdfs:
        w = 1.0 / np.arange(1, n_ranks + 1, dtype=np.float64) ** s
        cdf = np.cumsum(w)
        _cdfs[key] = cdf / cdf[-1]
    return np.minimum(np.searchsorted(_cdfs[key], rng.random(n)),
                      n_ranks - 1).astype(np.int64)


def _choice(rng, weights: list, n: int) -> np.ndarray:
    cdf = np.cumsum(np.asarray(weights, dtype=np.float64))
    return np.minimum(np.searchsorted(cdf / cdf[-1], rng.random(n)),
                      len(weights) - 1)


def _scaled(spec: dict, key: str, scale: float) -> int:
    n = int(spec[key])
    if spec.get("scales_with_rows"):
        n = int(n * scale)
    return max(8, n)


def _mix64(x: np.ndarray) -> np.ndarray:
    """splitmix64's finaliser: ranks -> ids that look like hashes."""
    x = x.astype(np.uint64)
    with np.errstate(over="ignore"):
        x = (x + np.uint64(0x9E3779B97F4A7C15))
        x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return (x ^ (x >> np.uint64(31))).view(np.int64)


def _numbers(rng, spec: dict, dtype, n: int, scale: float, lo: int,
             done: dict) -> np.ndarray:
    kind = spec["kind"]
    if kind == "const":
        return np.full(n, spec["value"], dtype=dtype)
    if kind == "choice":
        return np.asarray(spec["values"], dtype=dtype)[
            _choice(rng, spec["weights"], n)]
    if kind == "zipf":
        ids = int(spec["n"])
        r = _zipf_ranks(rng, ids, float(spec["s"]), n)
        step = int(spec.get("step", _SCATTER))
        return (int(spec.get("first", 0)) + r * step % ids).astype(dtype)
    if kind == "zipf_id64":
        return _mix64(_zipf_ranks(rng, _scaled(spec, "n", scale),
                                  float(spec["s"]), n))
    if kind == "uniform":
        v = rng.integers(int(spec["lo"]), int(spec["hi"]), n,
                         dtype=np.int64, endpoint=True)
        if spec.get("zero"):
            v[rng.random(n) < float(spec["zero"])] = 0
        return v.astype(dtype)
    if kind == "mostly":
        v = _numbers(rng, spec["else"], dtype, n, scale, lo, done)
        v[rng.random(n) < float(spec["share"])] = spec["value"]
        return v
    if kind == "unique":
        # unique by construction: 38 random bits over the row's index
        return (rng.integers(0, 2**38, n) << 24) \
            | (lo + np.arange(n, dtype=np.int64))
    if kind == "time":
        base = np.datetime64(spec["from"], "s").astype(np.int64)
        return base + rng.integers(0, 86_400 * int(spec["days"]), n)
    if kind == "time_after":
        return done[spec["column"]] + rng.integers(
            0, int(spec["max_seconds"]), n)
    if kind == "date_of":
        return (done[spec["column"]] // 86_400).astype(np.int32)
    raise ValueError(f"datagen: unknown kind of values {kind!r}")


# -- strings -------------------------------------------------------------------------

def _random_strings(rng, n: int, mean: float, sigma: float, corpus,
                    cap_means: float = 4.0):
    """n strings of log-normal length (about `mean` bytes, none over
    `cap_means` times that), each a piece of the corpus; a pyarrow
    StringArray."""
    import pyarrow as pa

    text, width = corpus
    mu = np.log(max(mean, 1.0)) - sigma * sigma / 2.0
    cap = max(2, min(int(cap_means * mean), len(text) // 2) // width)
    lens = np.clip(np.rint(rng.lognormal(mu, sigma, n) / width), 1,
                   cap).astype(np.int64) * width
    starts = rng.integers(0, (len(text) - cap * width) // width, n) * width
    offsets = np.concatenate([[0], np.cumsum(lens)])
    parts = []
    for lo in range(0, n, 65536):
        hi = min(n, lo + 65536)
        # a ragged gather: byte j of string i is text[starts[i] + j]
        at = np.repeat(starts[lo:hi] - (offsets[lo:hi] - offsets[lo]),
                       lens[lo:hi])
        at += np.arange(offsets[hi] - offsets[lo])
        parts.append(text[at])
    return pa.StringArray.from_buffers(
        n, pa.py_buffer(offsets.astype(np.int32)),
        pa.py_buffer(np.concatenate(parts) if parts
                     else np.zeros(0, np.uint8)))


def _pool(seed: int, index: int, spec: dict, scale: float, cap: float):
    """A string column's pool, from the seed and the column's place alone;
    entry 0 is the empty string."""
    import pyarrow as pa
    import pyarrow.compute as pc

    if spec["kind"] == "literal":
        return pa.array(spec["values"], type=pa.string())
    rng = np.random.default_rng([seed, 1 << 30, index])
    n = _scaled(spec, "distinct", scale)
    mean, sigma = float(spec["len_mean"]), float(spec["len_sigma"])
    pre = spec.get("prefixes")
    if pre:
        lead = pre["lead"]
        hosts = _random_strings(rng, max(8, int(pre["distinct"])),
                                float(pre["len_mean"]),
                                float(pre["len_sigma"]),
                                _corpus(seed, "token"))
        picked = hosts.take(pa.array(_zipf_ranks(
            rng, len(hosts), float(pre["zipf_s"]), n)))
        tails = _random_strings(
            rng, n, max(4.0, mean - len(lead) - float(pre["len_mean"]) - 1),
            sigma, _corpus(seed, spec["alphabet"]), cap)
        values = pc.binary_join_element_wise(
            pa.scalar(lead), picked, pa.scalar("/"), tails, "")
    else:
        values = _random_strings(rng, n, mean, sigma,
                                 _corpus(seed, spec["alphabet"]), cap)
    return pa.concat_arrays([pa.array([""], type=pa.string()), values])


def _strings(rng, spec: dict, pool, n: int):
    import pyarrow as pa
    import pyarrow.compute as pc

    if spec["kind"] == "literal":
        idx = _choice(rng, spec["weights"], n)
    else:
        idx = 1 + _zipf_ranks(rng, len(pool) - 1, float(spec["zipf_s"]), n)
        idx[rng.random(n) < float(spec["empty"])] = 0
    # plain strings: the parquet writer builds its own dictionary pages per
    # row group and gives them up where a real writer would
    return pc.take(pool, pa.array(idx.astype(np.int32)))


def write_part(job: tuple) -> str:
    """One part file: rows [lo, lo + n) of the table."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    seed, index, lo, n, batch_rows, scale, columns_file, path = job
    rng = np.random.default_rng([seed, index])
    done: dict[str, np.ndarray] = {}
    cols: dict[str, object] = {}
    table = load_columns(columns_file)
    cap = float(table["len_cap_means"])
    for ci, col in enumerate(table["columns"]):
        name, typ, spec = col["name"], col["type"], col["values"]
        if typ == "string":
            cols[name] = _strings(rng, spec,
                                  _pool(seed, ci, spec, scale, cap), n)
            continue
        v = _numbers(rng, spec, _NP.get(typ, np.int64), n, scale, lo, done)
        done[name] = v
        if typ == "timestamp":
            cols[name] = pa.array(v.astype("datetime64[s]"))
        elif typ == "date":
            cols[name] = pa.array(v.astype(np.int32), type=pa.date32())
        else:
            cols[name] = pa.array(v)
    out = os.path.join(path, f"part-{index:05d}.parquet")
    pq.write_table(pa.table(cols), out, row_group_size=batch_rows,
                   compression="snappy")
    return out


def generate(path: str, seed: int, rows: int, file_rows: int,
             batch_rows: int, workers: int, columns_file: str) -> list[str]:
    """Write the table under `path`; returns the part files in order."""
    os.makedirs(path, exist_ok=True)
    scale = rows / load_columns(columns_file)["rows_at_source"]
    jobs = [(seed, i, lo, min(file_rows, rows - lo), batch_rows, scale,
             columns_file, path)
            for i, lo in enumerate(range(0, rows, file_rows))]
    workers = max(1, min(workers, len(jobs)))
    if workers == 1:
        return [write_part(j) for j in jobs]
    ctx = multiprocessing.get_context("spawn")
    with ctx.Pool(workers) as pool:
        return pool.map(write_part, jobs, chunksize=1)
