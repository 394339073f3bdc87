"""Benchmark: ClickBench-style parquet snapshot through the TPU data plane.

Measures the north-star path (BASELINE.json): S3/fs parquet -> columnar
batches (arrow, no row pivot) -> transformer chain (HMAC-SHA256 PII mask on
the device + vectorized predicate filter) -> sink.  Prints ONE JSON line:

    {"metric": "clickbench_snapshot_rows_per_sec", "value": N,
     "unit": "rows/sec", "vs_baseline": N / 10_000_000}

vs_baseline is relative to the BASELINE.md target (>=10M rows/sec/chip on
v5e-1); the reference publishes no absolute numbers (BASELINE.md), so the
target ratio is the honest comparator.

Runs on the real TPU only (no conftest import): the default run exits
non-zero and prints no metric when JAX resolves any other platform, and a
failed phase fails the run.  Dataset: a synthetic subset of
ClickBench `hits` (docs/benchmarks.md:9-17 in the reference — ~100M rows,
70 cols; here fewer rows/cols, same shape of workload: wide numerics +
URL/title strings), generated once into /tmp/trtpu_bench.
"""

from __future__ import annotations

import json
import os
import sys
import time
from typing import Optional

import numpy as np

from transferia_tpu.runtime import knobs

ROWS = knobs.env_int("BENCH_ROWS", 2_000_000)
WIDE_ROWS = knobs.env_int("BENCH_WIDE_ROWS", 10_000_000)
BATCH_ROWS = knobs.env_int("BENCH_BATCH_ROWS", 131_072)
DATA_DIR = knobs.env_str("BENCH_DIR", "/tmp/trtpu_bench")
PARQUET = os.path.join(DATA_DIR, f"hits_{ROWS}.parquet")
WIDE_PARQUET = os.path.join(DATA_DIR, f"hits_wide_{WIDE_ROWS}.parquet")


def _auto_process_count() -> int:
    """Upload workers for the bench runs.

    The loader's parts are CPU-bound here (decode + hash + pivot all on
    the host), so oversubscribing the available cores only adds GIL
    churn and context switches — on a 1-core box a run spent 345% of
    wall in 4 time-sliced decode threads.  Use the real
    affinity count, capped at the reference's ProcessCount default of 4
    (pkg/abstract/runtime.go:105-107)."""
    pinned = knobs.env_int("BENCH_PROCESS_COUNT", 0)
    if pinned:
        return pinned
    return max(1, min(4, int(_effective_cpus())))


def _part_path(path: str, i: int) -> str:
    return os.path.join(path, f"part-{i:05d}.parquet")


def generate_dataset(path: str = PARQUET, rows: int = ROWS,
                     batch_rows: int = BATCH_ROWS, seed: int = 42,
                     max_file_rows: Optional[int] = None) -> None:
    """The 10-column table: URLs are near-unique (~10M distinct paths),
    so a masked URL crosses the link as per-row SHA blocks.

    With `max_file_rows`, `path` is a directory of part files of at most
    that many rows each (the same rows, in the same order) — for a
    machine that limits the size of one file."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    os.makedirs(os.path.dirname(path), exist_ok=True)
    if os.path.exists(path):
        if not os.path.exists(path + ".expected.json"):
            # dataset from an older bench.py: derive the ground truth
            # from the two filter columns (cheap columnar read)
            t = pq.read_table(path,
                              columns=["RegionID", "ResolutionWidth"])
            kept = int(((t["RegionID"].to_numpy() < 400)
                        & (t["ResolutionWidth"].to_numpy() >= 390)).sum())
            with open(path + ".expected.json", "w") as fh:
                json.dump({"rows": t.num_rows, "kept": kept}, fh)
        return
    rng = np.random.default_rng(seed)
    n = rows
    watch_id = rng.integers(0, 2**62, n, dtype=np.int64)
    user_id = rng.integers(0, 10_000_000, n, dtype=np.int64)
    counter_id = rng.integers(0, 5000, n).astype(np.int32)
    region_id = rng.integers(0, 500, n).astype(np.int32)
    event_time = (1_700_000_000 + rng.integers(0, 86_400 * 30, n)).astype(
        "datetime64[s]"
    )
    res_w = rng.choice(
        np.array([1280, 1366, 1536, 1920, 2560, 360, 390], dtype=np.int32), n
    )
    is_mobile = (rng.random(n) < 0.4).astype(np.int8)
    # URLs ~30-90 bytes (vectorized string build)
    host_ids = rng.integers(0, 997, n)
    path_ids = rng.integers(0, 10_000_019, n)
    urls = np.char.add(
        np.char.add("https://example-", host_ids.astype("U4")),
        np.char.add(".com/page/", path_ids.astype("U9")),
    )
    titles = np.char.add("Title ", rng.integers(0, 99_991, n).astype("U6"))
    phrase_pool = np.array(["", "", "", "buy tpu", "fast etl",
                            "weather tomorrow", "наушники"], dtype=object)
    phrases = phrase_pool[rng.integers(0, len(phrase_pool), n)]
    table = pa.table({
        "WatchID": watch_id,
        "UserID": user_id,
        "CounterID": counter_id,
        "RegionID": region_id,
        "EventTime": pa.array(event_time),
        "ResolutionWidth": res_w,
        "IsMobile": is_mobile,
        "URL": pa.array(urls.tolist(), type=pa.string()),
        "Title": pa.array(titles.tolist(), type=pa.string()),
        "SearchPhrase": pa.array(phrases.tolist(), type=pa.string()),
    })
    if max_file_rows:
        os.makedirs(path)
        for i, lo in enumerate(range(0, n, max_file_rows)):
            pq.write_table(table.slice(lo, max_file_rows),
                           _part_path(path, i), row_group_size=batch_rows,
                           compression="snappy")
    else:
        pq.write_table(table, path, row_group_size=batch_rows,
                       compression="snappy")
    # ground truth for the bench's completeness check: rows the transfer
    # chain keeps (make_transfer's filter) — catches silent row loss in
    # pushdown/transform regardless of where rows get dropped
    kept = int(((region_id < 400) & (res_w >= 390)).sum())
    with open(path + ".expected.json", "w") as fh:
        json.dump({"rows": n, "kept": kept}, fh)


def expected_kept(parquet: str = PARQUET) -> Optional[int]:
    try:
        with open(parquet + ".expected.json") as fh:
            return int(json.load(fh)["kept"])
    except (OSError, ValueError, KeyError):
        return None  # dataset generated by an older bench.py


# ~70-column ClickBench `hits` shape (docs/benchmarks.md:3,9-17 in the
# reference: ~100M rows x 70 cols).  Column names/types follow the public
# hits schema; values are synthetic.  (name, dtype, cardinality-ish knob):
# i8/i16/i32/i64 numerics plus a string tail with realistic repeat rates.
_WIDE_NUM_COLS = [
    # (name, numpy dtype, high exclusive bound)
    ("WatchID", "int64", 2**62), ("JavaEnable", "int8", 2),
    ("GoodEvent", "int8", 2), ("CounterID", "int32", 5000),
    ("ClientIP", "int32", 2**31 - 1), ("RegionID", "int32", 500),
    ("UserID", "int64", 10_000_000), ("CounterClass", "int8", 3),
    ("OS", "int8", 100), ("UserAgent", "int8", 80),
    ("IsRefresh", "int8", 2), ("RefererCategoryID", "int16", 3000),
    ("RefererRegionID", "int32", 5000), ("URLCategoryID", "int16", 3000),
    ("URLRegionID", "int32", 5000), ("ResolutionWidth", "int16", 0),
    ("ResolutionHeight", "int16", 2200), ("ResolutionDepth", "int8", 33),
    ("FlashMajor", "int8", 12), ("FlashMinor", "int8", 12),
    ("NetMajor", "int8", 5), ("NetMinor", "int8", 10),
    ("UserAgentMajor", "int16", 120), ("CookieEnable", "int8", 2),
    ("JavascriptEnable", "int8", 2), ("IsMobile", "int8", 2),
    ("MobilePhone", "int8", 90), ("IPNetworkID", "int32", 4_000_000),
    ("TraficSourceID", "int8", 10), ("SearchEngineID", "int16", 100),
    ("AdvEngineID", "int8", 60), ("IsArtifical", "int8", 2),
    ("WindowClientWidth", "int16", 2560), ("WindowClientHeight", "int16", 1600),
    ("ClientTimeZone", "int16", 1440), ("SilverlightVersion1", "int8", 6),
    ("SilverlightVersion2", "int8", 10), ("SilverlightVersion3", "int32", 70000),
    ("SilverlightVersion4", "int16", 200), ("CodeVersion", "int32", 3000),
    ("IsLink", "int8", 2), ("IsDownload", "int8", 2),
    ("IsNotBounce", "int8", 2), ("FUniqID", "int64", 2**62),
    ("HID", "int32", 2**31 - 1), ("IsOldCounter", "int8", 2),
    ("IsEvent", "int8", 2), ("IsParameter", "int8", 2),
    ("DontCountHits", "int8", 2), ("WithHash", "int8", 2),
    ("Age", "int8", 100), ("Sex", "int8", 3), ("Income", "int8", 10),
    ("Interests", "int16", 0x7FFF), ("Robotness", "int8", 5),
    ("RemoteIP", "int32", 2**31 - 1), ("WindowName", "int32", 10000),
    ("OpenerName", "int32", 10000), ("HistoryLength", "int16", 64),
    ("HTTPError", "int16", 600), ("SendTiming", "int32", 30000),
    ("DNSTiming", "int32", 5000),
]


def _string_pool(rng, n: int, prefix: str, lo: int, hi: int) -> "object":
    """Pool of n distinct strings, lengths in [lo, hi) (vectorized)."""
    import pyarrow as pa

    ids = np.arange(n)
    pads = rng.integers(lo, hi, n)
    vals = [f"{prefix}{i}" for i in ids]
    out = [v + "x" * max(0, int(p) - len(v)) for v, p in zip(vals, pads)]
    return pa.array(out, type=pa.string())


def generate_wide_dataset(path: str = WIDE_PARQUET,
                          rows: int = WIDE_ROWS,
                          batch_rows: int = BATCH_ROWS,
                          seed: int = 7,
                          max_file_rows: Optional[int] = None) -> None:
    """ClickBench-shaped wide dataset: ~70 cols, `rows` rows, written
    chunk-at-a-time so generation stays inside a few hundred MB of RAM.
    Strings sample from pools (URLs/titles repeat in real weblogs); the
    two filter columns keep the 10-col set's predicate semantics so the
    same transfer spec drives both datasets.

    With `max_file_rows`, `path` is a directory of part files of at most
    that many rows each, rolled between chunks: at 500,000 rows or more
    per file the rows are those of the one-file table."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    os.makedirs(os.path.dirname(path), exist_ok=True)
    if os.path.exists(path) and os.path.exists(
            path + ".expected.json"):
        return
    rng = np.random.default_rng(seed)
    res_choices = np.array([1280, 1366, 1536, 1920, 2560, 360, 390],
                           dtype=np.int16)
    url_pool = _string_pool(rng, 500_000, "https://example.test/p/", 30, 90)
    title_pool = _string_pool(rng, 120_000, "Title ", 12, 40)
    referer_pool = _string_pool(rng, 200_000, "https://ref.test/r/", 20, 70)
    phrase_pool = pa.array(["", "", "", "buy tpu", "fast etl",
                            "weather tomorrow", "наушники", "котики"],
                           type=pa.string())
    charset_pool = pa.array(["utf-8", "windows-1251", "koi8-r", ""],
                            type=pa.string())
    model_pool = _string_pool(rng, 2000, "phone-", 6, 18)
    lang_pool = pa.array(["ru", "en", "de", "tr", "zh"], type=pa.string())
    color_pool = pa.array(list("KWGYRB"), type=pa.string())

    def dict_col(pool, idx):
        # materialize plain strings (arrow C++ take) and let the parquet
        # writer build per-row-group dict pages with its real fallback
        # behavior — writing a prebuilt DictionaryArray would embed the
        # FULL pool as every row group's dict page (a pathological file
        # no real writer produces)
        import pyarrow.compute as pc

        return pc.take(pool, pa.array(idx, type=pa.int32()))

    writer = None
    kept = 0
    chunk = min(500_000, max_file_rows or 500_000)
    n_files = file_rows = 0
    try:
        for lo in range(0, rows, chunk):
            n = min(chunk, rows - lo)
            if max_file_rows and writer is not None \
                    and file_rows + n > max_file_rows:
                writer.close()
                writer = None
            cols: dict[str, object] = {}
            for name, dt, bound in _WIDE_NUM_COLS:
                if name == "ResolutionWidth":
                    cols[name] = rng.choice(res_choices, n)
                elif bound == 2:
                    cols[name] = (rng.random(n) < 0.3).astype(np.int8)
                else:
                    cols[name] = rng.integers(0, bound, n).astype(dt)
            ev = (1_700_000_000 + rng.integers(0, 86_400 * 30, n)).astype(
                "datetime64[s]")
            cols["EventTime"] = pa.array(ev)
            cols["ClientEventTime"] = pa.array(ev + rng.integers(0, 120, n))
            cols["LocalEventTime"] = pa.array(ev + rng.integers(0, 3600, n))
            cols["URL"] = dict_col(url_pool,
                                   rng.integers(0, len(url_pool), n))
            cols["Title"] = dict_col(title_pool,
                                     rng.integers(0, len(title_pool), n))
            cols["Referer"] = dict_col(referer_pool,
                                       rng.integers(0, len(referer_pool), n))
            cols["SearchPhrase"] = dict_col(
                phrase_pool, rng.integers(0, len(phrase_pool), n))
            cols["PageCharset"] = dict_col(
                charset_pool, rng.integers(0, len(charset_pool), n))
            cols["MobilePhoneModel"] = dict_col(
                model_pool, rng.integers(0, len(model_pool), n))
            cols["BrowserLanguage"] = dict_col(
                lang_pool, rng.integers(0, len(lang_pool), n))
            cols["HitColor"] = dict_col(
                color_pool, rng.integers(0, len(color_pool), n))
            kept += int(((cols["RegionID"] < 400)
                         & (cols["ResolutionWidth"] >= 390)).sum())
            tbl = pa.table(cols)
            if writer is None:
                out = path
                if max_file_rows:
                    os.makedirs(path, exist_ok=True)
                    out = _part_path(path, n_files)
                writer = pq.ParquetWriter(out, tbl.schema,
                                          compression="snappy")
                n_files += 1
                file_rows = 0
            writer.write_table(tbl, row_group_size=batch_rows)
            file_rows += n
    finally:
        if writer is not None:
            writer.close()
    with open(path + ".expected.json", "w") as fh:
        json.dump({"rows": rows, "kept": kept}, fh)


def make_transfer(process_count: int, parquet: str = PARQUET):
    from transferia_tpu.models import Transfer
    from transferia_tpu.models.transfer import (
        Runtime,
        ShardingUploadParams,
    )
    from transferia_tpu.providers.file import FileSourceParams
    from transferia_tpu.providers.stdout import NullTargetParams

    return Transfer(
        id="bench",
        src=FileSourceParams(path=parquet, format="parquet", table="hits",
                             batch_rows=BATCH_ROWS),
        dst=NullTargetParams(),
        transformation={"transformers": [
            {"mask_field": {"columns": ["URL"], "salt": "bench-salt"}},
            {"filter_rows": {
                "filter": "RegionID < 400 AND ResolutionWidth >= 390"}},
        ]},
        runtime=Runtime(sharding=ShardingUploadParams(
            process_count=process_count)),
    )


def run_pipeline(limit_rows: int | None = None,
                 process_count: int | None = None,
                 parquet: str = PARQUET,
                 total_rows: int = ROWS) -> tuple[int, float]:
    """Timed: parquet -> transform chain -> devnull sink, through the real
    snapshot loader (row-group parts in parallel so host decode, H2D,
    device hash, and D2H overlap across parts).  Returns (rows, seconds)."""
    from transferia_tpu.abstract.table import TableDescription
    from transferia_tpu.abstract.schema import TableID
    from transferia_tpu.coordinator import MemoryCoordinator
    from transferia_tpu.factories import make_sinker, new_storage
    from transferia_tpu.tasks import SnapshotLoader

    # the transformer chain fuses mask+filter into one device program by
    # default (transform/fused.py); no explicit backend switch needed
    if process_count is None:
        process_count = _auto_process_count()
    transfer = make_transfer(process_count, parquet)
    t0 = time.perf_counter()
    if limit_rows is not None:
        # warmup path: single-thread partial run to compile all programs
        storage = new_storage(transfer)
        sink = make_sinker(transfer, snapshot_stage=False)
        rows = 0

        class _Enough(Exception):
            pass

        def pusher(batch):
            nonlocal rows
            sink.push(batch)
            rows += batch.n_rows
            if rows >= limit_rows:
                raise _Enough()

        try:
            storage.load_table(
                TableDescription(id=TableID("fs", "hits")), pusher
            )
        except _Enough:
            pass
        return rows, time.perf_counter() - t0

    cp = MemoryCoordinator()
    loader = SnapshotLoader(transfer, cp, operation_id="bench-op")
    loader.upload_tables()
    dt = time.perf_counter() - t0
    prog = cp.operation_progress("bench-op")
    # completeness gate: with scan pushdown the coordinator counts
    # post-filter rows, so compare against the generator's ground truth
    # — a pushdown/transform bug that drops rows fails the bench loudly
    # instead of hiding inside a throughput number
    want = expected_kept(parquet)
    if want is not None and prog.completed_rows != want:
        raise AssertionError(
            f"row loss: sink got {prog.completed_rows} rows, chain "
            f"semantics require {want}")
    # the throughput denominator is the SOURCE table size: the snapshot's
    # job is to process the whole table, however much of it pushdown let
    # it skip
    return total_rows, dt


def measure_device_kernel(rows: int = 1 << 20) -> dict:
    """Sustained on-chip HMAC-SHA256 mask throughput, data resident.

    This isolates the device kernel from the host↔device link: one large
    launch amortizes the per-launch overhead, and timing spans several
    back-to-back launches on resident buffers.  It is what the chip
    itself sustains on the mask op; the end-to-end number above includes
    the link and the host (the tail prints both so the gap is
    attributable).
    """
    import jax
    import jax.numpy as jnp

    from transferia_tpu.ops.sha256 import _hmac_key_states, hmac_device_core

    backend = jax.default_backend()
    mb = 2  # 2 SHA blocks/row: a ~60-90 byte URL, the ClickBench shape
    rng = np.random.default_rng(11)
    blocks = rng.integers(0, 256, size=(rows, mb * 64), dtype=np.uint8)
    nblocks = np.full(rows, mb, dtype=np.int32)
    inner, outer = _hmac_key_states(b"bench-salt")
    st_i, st_o = jnp.asarray(inner[0]), jnp.asarray(outer[0])
    fn = jax.jit(lambda b, nb: hmac_device_core(b, nb, st_i, st_o, mb))
    db = jax.device_put(blocks)
    dnb = jax.device_put(nblocks)
    fn(db, dnb).block_until_ready()  # compile + warm
    iters = 4
    t0 = time.perf_counter()
    outs = [fn(db, dnb) for _ in range(iters)]
    for o in outs:
        o.block_until_ready()
    dt = time.perf_counter() - t0
    rps = rows * iters / dt
    return {
        "metric": "device_mask_kernel_rows_per_sec",
        "value": round(rps),
        "unit": "rows/sec",
        "vs_baseline": round(rps / 10_000_000, 4),
        "backend": backend,
        "launch_rows": rows,
        "sha_blocks_per_row": mb,
    }


def measure_device_decode(rows: int = 1 << 22) -> dict:
    """Sustained ON-CHIP RLE-dictionary decode: bit-unpack of packed
    codes + dictionary gather on resident buffers (ops/decode.py).

    This is the "columnar decode on TPU" clause of BASELINE.json
    config 3, timed the same way as the mask kernel: the end-to-end
    pipeline decodes parquet on the host, so this is the kernel alone.
    Shape mirrors the wide bench's URL column: bit_width 17 codes
    against a 131072-entry pool."""
    import jax

    backend = jax.default_backend()
    from transferia_tpu.ops.decode import decode_dict_run

    bw = 17
    rng = np.random.default_rng(13)
    n_pool = 1 << bw
    pool = rng.integers(-10**9, 10**9, n_pool).astype(np.int32)
    codes = rng.integers(0, n_pool, rows, dtype=np.uint64)
    # pack on host (numpy): little-endian bit stream
    nbits = rows * bw
    words64 = np.zeros((nbits + 31) // 32, dtype=np.uint64)
    starts = np.arange(rows, dtype=np.uint64) * np.uint64(bw)
    wi = (starts >> np.uint64(5)).astype(np.int64)
    off = (starts & np.uint64(31))
    np.bitwise_or.at(words64, wi,
                     (codes << off) & np.uint64(0xFFFFFFFF))
    spill = off + np.uint64(bw) > np.uint64(32)
    np.bitwise_or.at(words64, wi[spill] + 1,
                     codes[spill] >> (np.uint64(32) - off[spill]))
    words = words64.astype(np.uint32)
    from transferia_tpu.ops.decode import decode_dict_loop

    dwords = jax.device_put(words)
    dpool = jax.device_put(pool)
    out = decode_dict_run(dwords, dpool, bw, rows)
    out.block_until_ready()  # compile + warm
    # prove the chip really decoded: sample-compare against the host
    sample = np.asarray(out[:4096])
    expect = pool[codes[:4096].astype(np.int64)]
    if not np.array_equal(sample, expect):
        raise AssertionError("device decode mismatch vs host reference")
    # Sustained rate: the op is pure HBM traffic (~8 bytes/row), so
    # launch overhead would be a visible share of a launch-per-iteration
    # loop.  decode_dict_loop runs the decode back-to-back INSIDE one
    # launch (carry-serialized against CSE).  int() fetches the value,
    # which is the sync.
    iters = 64
    int(decode_dict_loop(dwords, dpool, bw, rows, iters))  # compile+warm
    t0 = time.perf_counter()
    int(decode_dict_loop(dwords, dpool, bw, rows, iters))
    dt = time.perf_counter() - t0
    rps = rows * iters / dt
    # HBM per decode: words in + code gather + values out (+pool, small)
    bytes_per_iter = words.nbytes * 2 + 4 * rows + 4 * rows
    return {
        "metric": "device_decode_rows_per_sec",
        "value": round(rps),
        "unit": "rows/sec",
        "vs_baseline": round(rps / 10_000_000, 4),
        "backend": backend,
        "bit_width": bw,
        "pool_entries": n_pool,
        "launch_rows": rows,
        "loop_iters": iters,
        "hbm_gb_per_sec": round(rps / rows * bytes_per_iter / 1e9, 1),
        # gatherless lane unpack made the bit-unpack VPU work; the
        # remaining bound is the dictionary gather itself (~140M
        # random gathers/s on v5e)
        "note": "single-launch fori_loop, resident buffers; gather-bound",
    }


def measure_device_fingerprint(rows: int = 1 << 20) -> dict:
    """Sustained ON-CHIP checksum-fingerprint rate (ops/rowhash.py
    DeviceFingerprintProgram) — the kernel-alone figure the mask and
    decode kernels already have; where end-to-end fingerprinting runs
    is TableFingerprinter's measured call.  Shape: one int64 column +
    one 64-byte var-width column, the checksum task's typical mix."""
    import jax
    import jax.numpy as jnp

    backend = jax.default_backend()
    from transferia_tpu.abstract.schema import (
        CanonicalType,
        ColSchema,
        TableID,
        TableSchema,
    )
    from transferia_tpu.columnar.batch import Column, ColumnBatch
    from transferia_tpu.ops import rowhash

    rng = np.random.default_rng(17)
    ids = rng.integers(0, 2**62, rows)
    urls = [f"https://example.test/p/{i % 997:04d}/x" for i in range(256)]
    data = np.frombuffer(("".join(urls[i % 256] for i in range(rows))
                          ).encode(), dtype=np.uint8)
    lens = np.array([len(urls[i % 256]) for i in range(rows)],
                    dtype=np.int64)
    offsets = np.zeros(rows + 1, dtype=np.int32)
    np.cumsum(lens, out=offsets[1:])
    schema = TableSchema([
        ColSchema("id", CanonicalType.INT64, primary_key=True),
        ColSchema("url", CanonicalType.UTF8),
    ])
    batch = ColumnBatch(TableID("b", "fp"), schema, {
        "id": Column("id", CanonicalType.INT64, ids.astype(np.int64)),
        "url": Column("url", CanonicalType.UTF8, data, offsets),
    })
    cols, n_rows = rowhash.prep_batch(batch)
    prog = rowhash.DeviceFingerprintProgram()
    # build the resident argument set exactly as dispatch() does, once
    from transferia_tpu.columnar.batch import bucket_rows

    bucket = bucket_rows(n_rows)
    assert bucket == n_rows  # power-of-two rows: no padding
    sig = tuple((c.kind, c.width if c.kind == "var" else 0)
                for c in cols)
    fn = prog._program_for(sig)
    fixed_lo = tuple(jnp.asarray(c.lo) for c in cols
                     if c.kind == "fixed")
    fixed_hi = tuple(jnp.asarray(c.hi) for c in cols
                     if c.kind == "fixed")
    var_blocks = tuple(jnp.asarray(c.ensure_blocks()) for c in cols
                       if c.kind == "var")
    validities = tuple(None for _ in cols)
    rowmask = jnp.ones(n_rows, dtype=jnp.bool_)
    seeds1 = jnp.asarray(np.array(
        [rowhash._col_seed(c.name, 0) for c in cols], dtype=np.uint32))
    seeds2 = jnp.asarray(np.array(
        [rowhash._col_seed(c.name, 1) for c in cols], dtype=np.uint32))
    nulls1 = jnp.asarray(np.full(len(cols), rowhash._NULL1, np.uint32))
    nulls2 = jnp.asarray(np.full(len(cols), rowhash._NULL2, np.uint32))
    powers1 = tuple(jnp.asarray(rowhash._powers(c.width, int(rowhash._P1)))
                    for c in cols if c.kind == "var")
    powers2 = tuple(jnp.asarray(rowhash._powers(c.width, int(rowhash._P2)))
                    for c in cols if c.kind == "var")

    import functools

    # NOTE: the big arrays ride as ARGUMENTS — captured as closure
    # constants they embed into the program and compilation stalls
    @functools.partial(jax.jit, static_argnums=(0,))
    def loop(iters, flo, fhi, vb, rm, s1, s2, p1, p2):
        def body(i, acc):
            out = fn(flo, fhi, vb, (), (), (), validities, rm,
                     s1 ^ (acc & jnp.uint32(1)), s2,
                     nulls1, nulls2, p1, p2)
            return acc + out[0]

        return jax.lax.fori_loop(0, iters, body, jnp.uint32(0))

    iters = 64
    # ONE compiled shape: the warm call uses the same static iters;
    # int() fetches the value, which is the sync
    int(loop(iters, fixed_lo, fixed_hi, var_blocks, rowmask,
             seeds1, seeds2, powers1, powers2))
    t0 = time.perf_counter()
    int(loop(iters, fixed_lo, fixed_hi, var_blocks, rowmask,
             seeds1, seeds2, powers1, powers2))
    dt = time.perf_counter() - t0
    rps = rows * iters / dt
    return {
        "metric": "device_fingerprint_rows_per_sec",
        "value": round(rps),
        "unit": "rows/sec",
        "vs_baseline": round(rps / 10_000_000, 4),
        "backend": backend,
        "launch_rows": rows,
        "loop_iters": iters,
        "cols": "int64 + 64B var",
        "note": "single-launch fori_loop on resident buffers",
    }


def measure_mesh_1dev(rows: int = 1 << 17) -> dict:
    """ShardedFusedProgram on a 1-device mesh on the REAL chip, vs the
    plain fused device program on the same inputs.

    The mesh path's correctness is pinned on the virtual CPU mesh
    (tests + dryrun_multichip); this line gives it hardware execution
    evidence and quantifies the mesh wrapper's overhead at N=1 — the
    delta an operator pays to run the multichip-shaped program before
    adding chips.
    """
    from transferia_tpu.ops.fused import FusedMaskFilterProgram
    from transferia_tpu.parallel.fusedmesh import ShardedFusedProgram
    from transferia_tpu.predicate.parser import parse as pred_parse

    rng = np.random.default_rng(21)
    urls = np.char.add("https://example-",
                       rng.integers(0, 997, rows).astype("U4"))
    flat = "".join(urls.tolist()).encode()
    lens = np.array([len(u) for u in urls], dtype=np.int64)
    offsets = np.zeros(rows + 1, dtype=np.int32)
    np.cumsum(lens, out=offsets[1:])
    data = np.frombuffer(flat, dtype=np.uint8)
    region = rng.integers(0, 500, rows).astype(np.int32)
    node = pred_parse("RegionID < 400")
    mask_cols = [(data, offsets)]
    pred_cols = {"RegionID": (region, None)}

    # Interleave plain/mesh iterations (drift hits both alike) and
    # compare MEDIANS — 3-iteration means swung 0.3%..18.6% on one
    # capture; report the spread so a noisy host is visible in the
    # record instead of masquerading as mesh overhead.
    plain = FusedMaskFilterProgram([b"bench-salt"], node)
    sharded = ShardedFusedProgram([b"bench-salt"], node)
    plain.run(mask_cols, pred_cols, rows)    # compile + warm
    out = sharded.run(mask_cols, pred_cols, rows)
    iters = 9
    plain_ts, mesh_ts = [], []
    for _ in range(iters):
        t0 = time.perf_counter()
        plain.run(mask_cols, pred_cols, rows)
        plain_ts.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        out = sharded.run(mask_cols, pred_cols, rows)
        mesh_ts.append(time.perf_counter() - t0)
    import statistics

    plain_s = statistics.median(plain_ts)
    mesh_s = statistics.median(mesh_ts)
    spread_pct = round(100 * (max(mesh_ts) - min(mesh_ts))
                       / max(mesh_s, 1e-9), 1)
    hexes, keep = out
    kept = int(keep.sum()) if keep is not None else rows
    if sharded.last_kept != kept:
        raise AssertionError(
            f"mesh psum kept {sharded.last_kept} != host keep {kept}")
    return {
        "metric": "mesh1_fused_ms_per_batch",
        "unit": "ms",
        "value": round(mesh_s * 1000, 2),
        "plain_device_ms": round(plain_s * 1000, 2),
        "mesh_overhead_pct": round(100 * (mesh_s - plain_s)
                                   / max(plain_s, 1e-9), 1),
        "iter_spread_pct": spread_pct,
        "iters": iters,
        "rows": rows,
        "devices": sharded.n_dev,
        "kept": kept,
        # the mesh program ships one monolithic padded block while the
        # plain program may overlap chunks (ops/fused._chunk_rows)
        "note": "overhead=monolithic vs chunked transfer at N=1",
    }


def measure_fingerprint(n_batches: int = 15) -> Optional[dict]:
    """Checksum-fingerprint throughput over the ClickBench batches.

    The checksum task's fingerprint method (tasks/checksum.py,
    ops/rowhash.py): order-independent two-lane digest, backend chosen
    by measurement (device reduction when the link supports it, the C++
    single-pass polyhash otherwise).  Full-table validation speed is a
    first-class metric for a data-transfer framework — this one runs at
    memory-bandwidth-adjacent speed on the host path.
    """
    from transferia_tpu.abstract.schema import TableID
    from transferia_tpu.abstract.table import TableDescription
    from transferia_tpu.factories import new_storage
    from transferia_tpu.ops.rowhash import TableFingerprinter

    transfer = make_transfer(process_count=1)
    storage = new_storage(transfer)
    batches = []

    class _Enough(Exception):
        pass

    def collect(batch):
        batches.append(batch)
        if len(batches) >= n_batches:
            raise _Enough()

    try:
        storage.load_table(
            TableDescription(id=TableID("fs", "hits")), collect)
    except _Enough:
        pass
    if not batches:
        return None
    # warm: let auto decide on real batches AND pay any device compile
    # outside the timed window (the jit cache is module-global, so the
    # timed instance reuses the compiled program)
    warm = TableFingerprinter(backend="auto")
    warm.push(batches[0])
    warm.push(batches[0])
    warm.result()
    decided = warm._decided or "host"
    fp = TableFingerprinter(backend=decided)
    rows = sum(b.n_rows for b in batches)
    t0 = time.perf_counter()
    for b in batches:
        fp.push(b)
    agg = fp.result()
    dt = time.perf_counter() - t0
    return {
        "metric": "checksum_fingerprint_rows_per_sec",
        "value": round(rows / dt),
        "unit": "rows/sec",
        "rows": rows,
        "backend": decided,
        "digest": agg.digest(),
    }


def measure_transform_latency(n_batches: int = 16) -> list:
    """Steady-state single-stream per-batch transform latency (the
    BASELINE kafka2ch config's headline metric shape): one warm chain
    instance, the first (compile-carrying) apply discarded, no competing
    upload threads — unlike the throughput run, where apply windows
    include cross-thread device queueing."""
    from transferia_tpu.abstract.schema import TableID
    from transferia_tpu.abstract.table import TableDescription
    from transferia_tpu.factories import new_storage
    from transferia_tpu.transform.chain import build_chain

    transfer = make_transfer(process_count=1)
    chain = build_chain(transfer.transformation)
    storage = new_storage(transfer)
    batches = []

    class _Enough(Exception):
        pass

    def collect(batch):
        batches.append(batch)
        if len(batches) >= n_batches + 1:
            raise _Enough()

    try:
        storage.load_table(
            TableDescription(id=TableID("fs", "hits")), collect)
    except _Enough:
        pass
    if not batches:
        return []
    # warm: under auto placement the first applies are the strategy
    # probes (host measure, then — link permitting — the device probe
    # whose first launch carries the XLA compile); three warm applies
    # cover host + compile + steady device so the timed loop below is
    # pure steady state for whichever strategy the tuner kept
    for _ in range(3):
        chain.apply(batches[0])
    out = []
    for b in batches[1:]:
        t0 = time.perf_counter()
        chain.apply(b)
        out.append(time.perf_counter() - t0)
    # expose what the auto-tuner decided for this chain (tail diagnostics)
    from transferia_tpu.transform.fused import DeviceFusedStep

    plan = chain.plan_for(batches[0].table_id, batches[0].schema)
    for step in plan.steps:
        if isinstance(step, DeviceFusedStep):
            global _placement_note
            _placement_note = step.placement_summary()
    return out


_placement_note = ""


def measure_kafka2ch(n_partitions: int = 16,
                     msgs_per_partition: int = 1500) -> dict:
    """BASELINE kafka2ch config: fake-Kafka JSON -> parser -> mask+filter
    chain -> ClickHouse sink; returns steady-state replication-path
    transform latency (the chain.apply window inside the sink middleware
    stack) and end-to-end rows/sec.  Uses the in-repo fake wire servers
    (tests/recipes) — the same servers the e2e suite authenticates
    against."""
    import threading

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from tests.recipes.fake_clickhouse import FakeCH
    from tests.recipes.fake_kafka import FakeKafka
    from transferia_tpu.coordinator import MemoryCoordinator
    from transferia_tpu.models import Transfer, TransferType
    from transferia_tpu.providers.clickhouse import CHTargetParams
    from transferia_tpu.providers.kafka.client import KafkaClient, Record
    from transferia_tpu.providers.kafka.provider import KafkaSourceParams
    from transferia_tpu.runtime.local import run_replication
    from transferia_tpu.stats import trace

    srv = FakeKafka(n_partitions=n_partitions).start()
    ch = FakeCH().start()
    try:
        seed = KafkaClient([f"127.0.0.1:{srv.port}"])
        srv.create_topic("hits")
        for p in range(n_partitions):
            seed.produce("hits", p, [
                Record(key=b"", value=json.dumps({
                    "id": p * msgs_per_partition + i,
                    "url": f"https://bench.example/{i}",
                    "region": i % 500,
                }).encode())
                for i in range(msgs_per_partition)
            ])
        seed.close()
        cp = MemoryCoordinator()
        t = Transfer(
            id="bench-k2ch", type=TransferType.INCREMENT_ONLY,
            src=KafkaSourceParams(
                brokers=[f"127.0.0.1:{srv.port}"], topic="hits",
                parallelism=4,
                parser={"json": {"schema": [
                    {"name": "id", "type": "int64", "key": True},
                    {"name": "url", "type": "utf8"},
                    {"name": "region", "type": "int32"},
                ], "table": "hits"}},
            ),
            dst=CHTargetParams(host="127.0.0.1", port=ch.port,
                               bufferer=None),
            transformation={"transformers": [
                {"mask_field": {"columns": ["url"], "salt": "bench"}},
                {"filter_rows": {"filter": "region < 400"}},
            ]},
        )
        expected = sum(1 for _ in range(n_partitions)
                       for i in range(msgs_per_partition)
                       if i % 500 < 400)
        # per-batch transform latency: the `transform` spans' durations
        was_tracing = trace.enabled()
        trace.reset()
        trace.enable(True)
        stop = threading.Event()
        th = threading.Thread(
            target=run_replication, args=(t, cp),
            kwargs={"stop_event": stop, "backoff": 0.2}, daemon=True,
        )
        t0 = time.perf_counter()
        th.start()

        def ch_rows():
            return ch.total_rows()

        deadline = time.monotonic() + 120
        while ch_rows() < expected and time.monotonic() < deadline:
            time.sleep(0.05)
        dt = time.perf_counter() - t0
        stop.set()
        th.join(timeout=10)
        rows = ch_rows()
        trace.enable(was_tracing)
        lat = sorted(s[4] for s in trace.spans()
                     if s[0] == "transform" and s[6] >= 0)
        out = {
            "metric": "kafka2ch_transform_p99_ms",
            "unit": "ms",
            "rows": rows,
            "rows_per_sec": round(rows / dt) if dt else 0,
        }
        if lat:
            import math

            n = len(lat)
            # drop the first (compile-carrying) sample per part stream
            steady = lat[:max(1, n - 1)] if n > 4 else lat
            out["value"] = round(
                steady[max(0, math.ceil(0.99 * len(steady)) - 1)] * 1000,
                3)
            out["p50_ms"] = round(
                steady[max(0, math.ceil(0.50 * len(steady)) - 1)] * 1000,
                3)
            out["batches"] = n
        return out
    finally:
        srv.stop()
        ch.stop()


_bench_lambda_jit = {}


def bench_lambda(arrays: dict) -> dict:
    """User lambda for the SR fan-in config: a jax.jit columns transform
    (sign-flip ids outside the region window) — the `lambda` transformer
    resolves it by "bench:bench_lambda"."""
    import jax
    import jax.numpy as jnp

    fn = _bench_lambda_jit.get("fn")
    if fn is None:
        fn = jax.jit(lambda ids, region:
                     jnp.where(region < 400, ids, -ids))
        _bench_lambda_jit["fn"] = fn
    return {"id": np.asarray(fn(arrays["id"], arrays["region"]))}


def measure_pg2ch(rows: int = 300_000) -> dict:
    """BASELINE pg2ch config: PG COPY snapshot -> SQL-predicate
    transformer -> ClickHouse sink, through the real activate path
    against the in-repo fake wire servers."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from tests.recipes.fake_clickhouse import FakeCH
    from tests.recipes.fake_postgres import FakePG, FakeTable
    from transferia_tpu.coordinator import MemoryCoordinator
    from transferia_tpu.models import Transfer
    from transferia_tpu.providers.clickhouse import CHTargetParams
    from transferia_tpu.providers.postgres import PGSourceParams
    from transferia_tpu.tasks import activate_delivery

    pg = FakePG().start()
    ch = FakeCH().start()
    try:
        pg.add_table(FakeTable(
            "public", "hits",
            [("id", "bigint", True, True),
             ("url", "text", False, False),
             ("region", "integer", False, False),
             ("score", "double precision", False, False)],
            [{"id": str(i), "url": f"https://e.test/{i % 997}",
              "region": str(i % 500), "score": f"{(i % 91) * 1.5}"}
             for i in range(rows)],
        ))
        t = Transfer(
            id="bench-pg2ch",
            src=PGSourceParams(host="127.0.0.1", port=pg.port,
                               database="db", user="u"),
            dst=CHTargetParams(host="127.0.0.1", port=ch.port,
                               bufferer=None),
            transformation={"transformers": [
                {"filter_rows": {
                    "filter": "region < 400 AND score >= 10"}},
            ]},
        )
        t0 = time.perf_counter()
        activate_delivery(t, MemoryCoordinator())
        dt = time.perf_counter() - t0
        got = ch.total_rows()
        expected = sum(1 for i in range(rows)
                       if i % 500 < 400 and (i % 91) * 1.5 >= 10)
        if got != expected:
            raise AssertionError(f"pg2ch row loss: {got} != {expected}")
        return {"metric": "pg2ch_snapshot_rows_per_sec",
                "value": round(rows / dt), "unit": "rows/sec",
                "rows": rows, "sink_rows": got,
                "seconds": round(dt, 2)}
    finally:
        pg.stop()
        ch.stop()


def measure_mysql2kafka(rows: int = 200_000,
                        n_partitions: int = 16) -> dict:
    """BASELINE mysql2kafka config: MySQL snapshot -> PII mask ->
    Debezium-envelope serializer -> partitioned Kafka producer across 16
    partitions (the CDC envelope path at snapshot volume; binlog-tail
    latency is covered by the replication e2e suite)."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from tests.recipes.fake_kafka import FakeKafka
    from tests.recipes.fake_mysql import FakeMySQL, FakeMyTable
    from transferia_tpu.coordinator import MemoryCoordinator
    from transferia_tpu.models import Transfer
    from transferia_tpu.providers.kafka.provider import KafkaTargetParams
    from transferia_tpu.providers.mysql import MySQLSourceParams
    from transferia_tpu.tasks import activate_delivery

    my = FakeMySQL().start()
    kf = FakeKafka(n_partitions=n_partitions).start()
    try:
        my.add_table(FakeMyTable(
            "db", "users",
            [("id", "bigint", "bigint", True, True),
             ("email", "varchar", "varchar(255)", False, False),
             ("region", "int", "int", False, False)],
            [{"id": i, "email": f"user{i}@example.test",
              "region": i % 500} for i in range(rows)],
        ))
        t = Transfer(
            id="bench-my2kf",
            src=MySQLSourceParams(host="127.0.0.1", port=my.port,
                                  database="db", user="root"),
            dst=KafkaTargetParams(
                brokers=[f"127.0.0.1:{kf.port}"], topic="cdc",
                serializer="debezium"),
            transformation={"transformers": [
                {"mask_field": {"columns": ["email"],
                                "salt": "bench"}},
            ]},
        )
        t0 = time.perf_counter()
        activate_delivery(t, MemoryCoordinator())
        dt = time.perf_counter() - t0
        got = sum(len(p) for p in kf.topics.get("cdc", []))
        if got != rows:
            raise AssertionError(f"mysql2kafka row loss: {got} != {rows}")
        return {"metric": "mysql2kafka_debezium_rows_per_sec",
                "value": round(rows / dt), "unit": "rows/sec",
                "rows": rows, "partitions": n_partitions,
                "seconds": round(dt, 2)}
    finally:
        my.stop()
        kf.stop()


def measure_kafka_sr2ch(n_partitions: int = 64,
                        msgs_per_partition: int = 1200) -> dict:
    """BASELINE Kafka+Confluent-SR -> CH config: 64-partition fan-in of
    confluent-wire AVRO records resolved through the fake schema
    registry, a user jax.jit lambda transformer, ClickHouse sink."""
    import threading

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from tests.recipes.fake_clickhouse import FakeCH
    from tests.recipes.fake_kafka import FakeKafka
    from tests.recipes.fake_sr import FakeSchemaRegistry
    from transferia_tpu.coordinator import MemoryCoordinator
    from transferia_tpu.models import Transfer, TransferType
    from transferia_tpu.providers.clickhouse import CHTargetParams
    from transferia_tpu.providers.kafka.client import KafkaClient, Record
    from transferia_tpu.providers.kafka.provider import KafkaSourceParams
    from transferia_tpu.runtime.local import run_replication

    def zz(n: int) -> bytes:
        u = (n << 1) ^ (n >> 63) if n < 0 else (n << 1)
        out = bytearray()
        while True:
            b = u & 0x7F
            u >>= 7
            out.append(b | (0x80 if u else 0))
            if not u:
                return bytes(out)

    schema_json = json.dumps({
        "type": "record", "name": "Hit", "fields": [
            {"name": "id", "type": "long"},
            {"name": "url", "type": "string"},
            {"name": "region", "type": "int"},
        ]})
    sr = FakeSchemaRegistry().start()
    srv = FakeKafka(n_partitions=n_partitions).start()
    ch = FakeCH().start()
    try:
        import urllib.request

        req = urllib.request.Request(
            sr.url + "/subjects/hits-value/versions",
            data=json.dumps({"schema": schema_json}).encode(),
            headers={"Content-Type":
                     "application/vnd.schemaregistry.v1+json"})
        sid = json.loads(urllib.request.urlopen(req,
                                                timeout=10).read())["id"]
        seed = KafkaClient([f"127.0.0.1:{srv.port}"])
        srv.create_topic("hits")
        header = b"\x00" + sid.to_bytes(4, "big")
        for p in range(n_partitions):
            recs = []
            for i in range(msgs_per_partition):
                rid = p * msgs_per_partition + i
                url = f"https://e.test/{rid % 997}".encode()
                body = (zz(rid) + zz(len(url)) + url
                        + zz(rid % 500))
                recs.append(Record(key=b"", value=header + body))
            seed.produce("hits", p, recs)
        seed.close()
        t = Transfer(
            id="bench-sr2ch", type=TransferType.INCREMENT_ONLY,
            src=KafkaSourceParams(
                brokers=[f"127.0.0.1:{srv.port}"], topic="hits",
                parallelism=4,
                parser={"confluent_schema_registry": {
                    "registry_url": sr.url, "table": "hits"}},
            ),
            dst=CHTargetParams(host="127.0.0.1", port=ch.port,
                               bufferer=None),
            transformation={"transformers": [
                # user lambda as a jax.jit program (bench_lambda below)
                {"lambda": {"function": "bench:bench_lambda"}},
            ]},
        )
        expected = n_partitions * msgs_per_partition
        cp = MemoryCoordinator()
        stop = threading.Event()
        th = threading.Thread(
            target=run_replication, args=(t, cp),
            kwargs={"stop_event": stop, "backoff": 0.2}, daemon=True)
        t0 = time.perf_counter()
        th.start()

        def ch_rows():
            return ch.total_rows()

        deadline = time.monotonic() + 180
        while ch_rows() < expected and time.monotonic() < deadline:
            time.sleep(0.05)
        dt = time.perf_counter() - t0
        stop.set()
        th.join(timeout=10)
        got = ch_rows()
        if got != expected:
            raise AssertionError(
                f"kafka-sr2ch row loss: {got} != {expected}")
        return {"metric": "kafka_sr64_2ch_rows_per_sec",
                "value": round(got / dt), "unit": "rows/sec",
                "rows": got, "partitions": n_partitions,
                "seconds": round(dt, 2)}
    finally:
        sr.stop()
        srv.stop()
        ch.stop()


def _trace_out_path() -> str:
    """Timeline artifact control: `--trace[=path]` argv or BENCH_TRACE
    env.  When set, the headline window records pipeline spans
    (stats/trace.py) and writes a Perfetto-loadable trace.json next to
    the usual stderr diagnostics — every benchmark run can then ship a
    timeline artifact alongside its numbers."""
    out = knobs.env_str("BENCH_TRACE", "")
    for a in sys.argv[1:]:
        if a == "--trace":
            out = out or os.path.join(DATA_DIR, "bench_trace.json")
        elif a.startswith("--trace="):
            out = a.split("=", 1)[1]
    return out


# -- regression gate (--against) ---------------------------------------------

# every metric line printed this run (tail diagnostics + headline):
# the --against gate compares THESE against a prior bench artifact
_METRICS_EMITTED: list[dict] = []


def _emit(obj: dict) -> None:
    """One '#'-prefixed stderr metric line, remembered for --against."""
    _METRICS_EMITTED.append(obj)
    print(f"# {json.dumps(obj)}", file=sys.stderr)


# metrics where smaller is the improvement (latencies); everything else
# is a throughput/ratio where bigger is better
_LOWER_IS_BETTER = ("_ms", "_ms_per_batch")

# default band: a candidate may be up to this fraction WORSE than the
# prior before the gate trips
DEFAULT_TOLERANCE = 0.15

# per-metric bands for the known-noisy lines (kernel-alone device
# numbers were taken at 4-64 iterations; the fake-wire configs are
# scheduling-bound on 1-core boxes)
TOLERANCE_OVERRIDES = {
    "device_mask_kernel_rows_per_sec": 0.5,
    "device_decode_rows_per_sec": 0.5,
    "device_fingerprint_rows_per_sec": 0.5,
    "mesh1_fused_ms_per_batch": 0.6,
    "kafka2ch_transform_p99_ms": 0.6,
    "kafka_sr64_2ch_rows_per_sec": 0.4,
    "mysql2kafka_debezium_rows_per_sec": 0.4,
    "pg2ch_snapshot_rows_per_sec": 0.4,
    "fleet_transfers_per_sec": 0.4,
    # merged-histogram dispatch tails (fleet/bench.py via stats/hdr.py):
    # scheduling-bound on the 1-core bench boxes, and the p999 of a
    # ~100-sample window is a single observation — wide bands on
    # purpose; the histogram's merge==concat exactness is pinned by
    # unit tests, not by run-to-run latency stability
    "fleet_dispatch_p50_ms": 0.6,
    "fleet_dispatch_p99_ms": 0.8,
    "fleet_dispatch_p999_ms": 1.0,
    # end-to-end freshness p99 (SLO plane): wall-clock from the sample
    # source's event-time stamp to sink publish — dominated by queue
    # wait on the 1-core boxes, so it swings with scheduling like the
    # dispatch tails above; the SLO verdict math is pinned by
    # tests/unit/test_slo.py, not by run-to-run latency stability
    "replication_lag_p99_ms": 0.8,
    # loopback-gRPC round trips on the 1-core bench boxes are
    # scheduling-bound; the wire-bytes ratio is the stable signal and
    # gates through wire_bytes-derived fields, not rows/s
    "encoded_wire_rows_per_sec": 0.5,
    # multi-stream lane: a loopback put+get per curve point, so the
    # same scheduling noise as encoded_wire applies; the 4-vs-1 ratio
    # divides two such numbers and on the 1-core bench boxes carries
    # NO parallelism signal at all (substream threads timeshare one
    # core) — the lane's contracts gate in-run (pool-once, encoded
    # shrink) and in tests, not through this ratio
    "interchange_multistream_rows_per_sec": 0.5,
    "interchange_stream4_speedup": 1.0,
    # staging-store reads are lexsort-bound and swing with the 1-core
    # boxes' scheduling; the cutover seal is a sub-ms in-memory
    # decision where a single preemption doubles the mean — the
    # correctness half (compaction equivalence, no-flatten pin) gates
    # through the run's own `ok`, not through these bands
    "mvcc_merge_layered_rows_per_sec": 0.4,
    "mvcc_merge_compacted_rows_per_sec": 0.4,
    "mvcc_cutover_ms": 0.8,
    # spill is Arrow-IPC encode + a heap-blob put, rebuild replays the
    # whole manifest through decode + re-land — both wall-clock
    # numbers swing with the 1-core boxes' scheduling; the durability
    # contracts (byte-identical rebuild, no-flatten round trip) gate
    # through the run's own `ok` and the spill conformance tests
    "mvcc_spill_mbs": 0.5,
    "mvcc_rebuild_ms": 0.8,
}


def load_bench_metrics(path: str) -> dict[str, dict]:
    """{metric_name: metric_obj} out of a bench artifact.

    Accepts all three shapes the repo carries: a driver-captured
    BENCH_rNN.json wrapper (`{"tail": "...log lines..."}`), a raw bench
    log (stderr '#' lines + the stdout headline), or a JSON-lines file
    of metric objects.  The LAST occurrence of a metric wins (the
    headline prints early as a crash-safety copy, then final)."""
    with open(path) as fh:
        text = fh.read()
    out: dict[str, dict] = {}

    def take(obj) -> None:
        if isinstance(obj, dict) and isinstance(obj.get("metric"), str):
            out[obj["metric"]] = obj

    lines = text.splitlines()
    try:
        doc = json.loads(text)
    except ValueError:
        doc = None
    if isinstance(doc, dict):
        take(doc)
        if isinstance(doc.get("tail"), str):
            lines = doc["tail"].splitlines()
        else:
            lines = []
    elif isinstance(doc, list):
        for it in doc:
            take(it)
        lines = []
    for ln in lines:
        ln = ln.strip()
        if ln.startswith("#"):
            ln = ln.lstrip("# ").strip()
        if not ln.startswith("{"):
            continue
        try:
            take(json.loads(ln))
        except ValueError:
            continue
    return out


def compare_against(prior: dict[str, dict], current: dict[str, dict],
                    tolerance: Optional[float] = None
                    ) -> tuple[list[dict], list[str]]:
    """Per-metric comparison with tolerance bands.

    Returns (regressions, report_lines).  Only metrics present in BOTH
    sets with numeric nonzero prior values are gated; the rest are
    reported as skipped so a silently-vanished metric is visible."""
    base_tol = DEFAULT_TOLERANCE if tolerance is None else tolerance
    regressions: list[dict] = []
    lines: list[str] = []
    for name in sorted(prior):
        p = prior[name].get("value")
        c = (current.get(name) or {}).get("value")
        if name not in current:
            lines.append(f"{name}: SKIP (not emitted by this run)")
            continue
        if not isinstance(p, (int, float)) or \
                not isinstance(c, (int, float)) or p <= 0:
            lines.append(f"{name}: SKIP (non-comparable values "
                         f"{p!r} -> {c!r})")
            continue
        tol = max(TOLERANCE_OVERRIDES.get(name, 0.0), base_tol)
        lower_better = name.endswith(_LOWER_IS_BETTER)
        if lower_better and c <= 0:
            # a 0 latency is a broken measurement, not an infinite win
            lines.append(f"{name}: SKIP (non-comparable values "
                         f"{p!r} -> {c!r})")
            continue
        ratio = (p / c) if lower_better else (c / p)
        verdict = "OK" if ratio >= 1.0 - tol else "REGRESSION"
        lines.append(
            f"{name}: {p} -> {c} "
            f"({'x' if not lower_better else '/'}{ratio:.3f} vs "
            f"floor {1.0 - tol:.2f}) {verdict}")
        if verdict == "REGRESSION":
            regressions.append({
                "metric": name, "prior": p, "current": c,
                "ratio": round(ratio, 4), "tolerance": tol,
                "lower_is_better": lower_better,
            })
    for name in sorted(set(current) - set(prior)):
        lines.append(f"{name}: NEW (no prior value)")
    return regressions, lines


def run_regression_gate(against_path: str,
                        current: dict[str, dict],
                        tolerance: Optional[float] = None) -> int:
    try:
        prior = load_bench_metrics(against_path)
    except (OSError, UnicodeDecodeError) as e:
        print(f"# against: unreadable artifact {against_path}: {e}",
              file=sys.stderr)
        return 2
    if not prior:
        print(f"# against: no metric lines found in {against_path}",
              file=sys.stderr)
        return 2
    regressions, lines = compare_against(prior, current, tolerance)
    for ln in lines:
        print(f"# against: {ln}", file=sys.stderr)
    verdict = {"metric": "bench_regression_gate",
               "ok": not regressions,
               "against": os.path.basename(against_path),
               "compared": sum(1 for ln in lines if "SKIP" not in ln
                               and "NEW" not in ln),
               "regressions": regressions}
    print(f"# {json.dumps(verdict)}", file=sys.stderr)
    return 1 if regressions else 0


def _against_args() -> tuple[Optional[str], Optional[str],
                             Optional[float]]:
    """(--against PATH, --candidate PATH, --tolerance F) off argv.
    With --candidate the gate compares two artifacts and never runs a
    benchmark (the verify-skill smoke); without it the gate runs after
    whatever bench stage argv selected, over the metrics it emitted."""
    against = candidate = None
    tolerance = None
    argv = sys.argv[1:]
    for i, a in enumerate(argv):
        if a == "--against" and i + 1 < len(argv):
            against = argv[i + 1]
        elif a.startswith("--against="):
            against = a.split("=", 1)[1]
        elif a == "--candidate" and i + 1 < len(argv):
            candidate = argv[i + 1]
        elif a.startswith("--candidate="):
            candidate = a.split("=", 1)[1]
        elif a == "--tolerance" and i + 1 < len(argv):
            tolerance = _parse_tolerance(argv[i + 1])
        elif a.startswith("--tolerance="):
            tolerance = _parse_tolerance(a.split("=", 1)[1])
    return against, candidate, tolerance


def _parse_tolerance(raw: str) -> float:
    """Bad input exits 2 (unusable input), NOT 1 — a CI wrapper keying
    on the gate's exit codes must never read a flag typo as a perf
    regression."""
    try:
        tol = float(raw)
    except ValueError:
        print(f"# against: invalid --tolerance {raw!r} "
              f"(want a fraction like 0.15)", file=sys.stderr)
        raise SystemExit(2)
    if tol < 0:
        print(f"# against: --tolerance must be >= 0, got {tol}",
              file=sys.stderr)
        raise SystemExit(2)
    return tol


def measure_dispatch() -> dict:
    """`--dispatch`: the compressed dispatch plane's micro-bench —
    identical dict-heavy mask+filter batches (the clickbench URL shape:
    low-cardinality string column + int filter column) dispatched
    through the fused device program with the encoding forced RAW vs
    AUTO (ops/dispatch.py).  Reports rows/s per mode plus the
    encoded-vs-raw-equivalent H2D compression ratio; the acceptance bar
    is >=5x on the dict-heavy shape."""
    import jax

    from transferia_tpu.abstract import TableID
    from transferia_tpu.abstract.schema import new_table_schema
    from transferia_tpu.columnar.batch import (
        Column,
        ColumnBatch,
        DictEnc,
        DictPool,
        _offsets_from_lengths,
    )
    from transferia_tpu.ops import dispatch as dsp
    from transferia_tpu.stats.trace import TELEMETRY
    from transferia_tpu.transform import build_chain
    from transferia_tpu.transform.fused import (
        set_device_fusion,
        set_placement,
    )

    rows = knobs.env_int("BENCH_DISPATCH_ROWS", 131_072)
    n_batches = max(1, knobs.env_int("BENCH_DISPATCH_BATCHES", 4))
    uniques = 4096
    tid = TableID("bench", "dispatch")
    schema = new_table_schema([("URL", "utf8"), ("RegionID", "int32")])
    rng = np.random.default_rng(11)
    vals = [f"https://bench{i}.example/path/{i % 97}/{i}"
            for i in range(uniques)]
    bufs = [v.encode() for v in vals]
    pool_data = np.frombuffer(b"".join(bufs), dtype=np.uint8).copy()
    pool_off = _offsets_from_lengths([len(b) for b in bufs] + [0])

    # identical data for both modes: draw once, rebind per-mode pools
    batch_data = [
        (rng.integers(0, uniques, rows).astype(np.int32),
         rng.integers(0, 500, rows).astype(np.int32))
        for _ in range(n_batches)
    ]

    def batches(pool):
        out = []
        for codes, regions in batch_data:
            url = Column("URL", schema.find("URL").data_type,
                         dict_enc=DictEnc(codes, pool=pool))
            region = Column(
                "RegionID", schema.find("RegionID").data_type, regions)
            out.append(ColumnBatch(tid, schema,
                                   {"URL": url, "RegionID": region}))
        return out

    cfg = {"transformers": [
        {"mask_field": {"columns": ["URL"], "salt": "bench-salt"}},
        {"filter_rows": {"filter": "RegionID < 400"}},
    ]}

    def run_mode(mode: str) -> tuple[float, dict]:
        # fresh pool per mode so neither rides the other's memo
        pool = DictPool(pool_data, pool_off, null_code=uniques)
        data = batches(pool)
        dsp.set_dispatch_encoding(mode)
        set_device_fusion(True)
        set_placement("device")
        try:
            chain = build_chain(cfg)
            chain.apply(data[0])  # warm: compiles + pool upload
            TELEMETRY.reset()
            t0 = time.perf_counter()
            total = 0
            for b in data:
                out = chain.apply(b)
                total += out.n_rows
            dt = time.perf_counter() - t0
            assert total > 0
            return (n_batches * rows) / max(dt, 1e-9), \
                TELEMETRY.snapshot()
        finally:
            set_device_fusion(None)
            set_placement(None)
            dsp.set_dispatch_encoding(None)

    raw_rps, raw_snap = run_mode("raw")
    enc_rps, enc_snap = run_mode("auto")
    ratio = (enc_snap["h2d_raw_equiv_bytes"]
             / max(enc_snap["h2d_encoded_bytes"], 1))
    return {
        "metric": "dispatch_encoded_rows_per_sec",
        "unit": "rows/sec",
        "value": round(enc_rps),
        "raw_rows_per_sec": round(raw_rps),
        "speedup_vs_raw": round(enc_rps / max(raw_rps, 1e-9), 2),
        "compression_ratio": round(ratio, 1),
        "h2d_encoded_bytes": enc_snap["h2d_encoded_bytes"],
        "h2d_raw_equiv_bytes": enc_snap["h2d_raw_equiv_bytes"],
        "h2d_raw_mode_bytes": raw_snap["h2d_bytes"],
        "dict_pool_hits": enc_snap["dict_pool_hits"],
        "rows_per_batch": rows,
        "batches": n_batches,
        # this stage also runs as a CPU smoke: the line says where
        "platform": jax.default_backend(),
    }


def measure_checksum_dict() -> dict:
    """`--checksum-dict`: the dict-native reduction plane's A/B — the
    SAME dict-heavy batches (clickbench URL shape: one low-cardinality
    string column + one int64 id) fingerprinted flat (pre-materialized
    buffers, the pre-PR wire) vs code-native (DictEnc columns, pool
    accumulators + code gather).  Digest equality is asserted; the
    acceptance bar is >=3x rows/s on this shape with ZERO flat
    materializations on the dict run."""
    from transferia_tpu.abstract import TableID
    from transferia_tpu.abstract.schema import new_table_schema
    from transferia_tpu.columnar.batch import (
        Column,
        ColumnBatch,
        DictEnc,
        DictPool,
        _offsets_from_lengths,
    )
    from transferia_tpu.ops.rowhash import TableFingerprinter
    from transferia_tpu.stats.trace import TELEMETRY

    rows = knobs.env_int("BENCH_CHECKSUM_DICT_ROWS", 262_144)
    n_batches = max(1, knobs.env_int("BENCH_CHECKSUM_DICT_BATCHES", 8))
    uniques = 4096
    tid = TableID("bench", "checksum_dict")
    # the ClickBench `hits` character: one wide id plus several
    # low-cardinality string columns riding parquet dictionaries
    dict_cols = ("URL", "Referer", "SearchPhrase")
    schema = new_table_schema(
        [("id", "int64", True)] + [(c, "utf8") for c in dict_cols])
    rng = np.random.default_rng(13)
    pools = {}
    for ci, cname in enumerate(dict_cols):
        vals = [f"https://bench{ci}-{i}.example/path/{i % 97}/{i}"
                for i in range(uniques)]
        bufs = [v.encode() for v in vals]
        pool_data = np.frombuffer(b"".join(bufs), dtype=np.uint8).copy()
        pool_off = _offsets_from_lengths([len(b) for b in bufs] + [0])
        pools[cname] = DictPool(pool_data, pool_off, null_code=uniques)

    batch_data = [
        (np.arange(i * rows, (i + 1) * rows, dtype=np.int64),
         {c: rng.integers(0, uniques, rows).astype(np.int32)
          for c in dict_cols})
        for i in range(n_batches)
    ]
    id_t = schema.find("id").data_type

    def mk_batches(flat: bool):
        out = []
        for ids, codes in batch_data:
            cols = {"id": Column("id", id_t, ids)}
            for c in dict_cols:
                enc = DictEnc(codes[c], pool=pools[c])
                ct = schema.find(c).data_type
                cols[c] = (Column(c, ct, *enc.materialize()) if flat
                           else Column(c, ct, dict_enc=enc))
            out.append(ColumnBatch(tid, schema, cols))
        return out

    dict_batches = mk_batches(flat=False)
    flat_batches = mk_batches(flat=True)

    def run(batches) -> tuple[float, str]:
        fp = TableFingerprinter(backend="host")
        fp.push(batches[0])  # warm: native lib load, acc memo
        fp = TableFingerprinter(backend="host")
        t0 = time.perf_counter()
        for b in batches:
            fp.push(b)
        agg = fp.result()
        dt = time.perf_counter() - t0
        return (n_batches * rows) / max(dt, 1e-9), agg.digest()

    flat_rps, flat_digest = run(flat_batches)
    TELEMETRY.reset()
    dict_rps, dict_digest = run(dict_batches)
    snap = TELEMETRY.snapshot()
    if dict_digest != flat_digest:
        raise AssertionError(
            f"dict-native digest {dict_digest} != flat {flat_digest}")
    return {
        "metric": "checksum_dict_fingerprint_rows_per_sec",
        "unit": "rows/sec",
        "value": round(dict_rps),
        "flat_rows_per_sec": round(flat_rps),
        "speedup_vs_flat": round(dict_rps / max(flat_rps, 1e-9), 2),
        "digest": dict_digest,
        "digest_match": True,
        "dict_flat_materializations":
            snap["dict_flat_materializations"],
        "lazy_dict_preserved": snap["lazy_dict_preserved"],
        "rows_per_batch": rows,
        "batches": n_batches,
        "pool_values": uniques,
    }


def measure_encoded_wire() -> dict:
    """`--encoded-wire`: the pool-once encoded Flight wire's A/B —
    identical dict-heavy batches (clickbench URL shape) streamed
    through a loopback Flight server with the encoded wire forced OFF
    (dict columns materialize flat per batch — the pre-PR wire) vs ON
    (one Arrow dictionary batch per stream, then codes-only record
    batches).  The run asserts the pool-once telemetry (each DictPool
    ships at most once per stream) and reports rows/s per mode plus
    the bytes-on-wire ratio; the acceptance bar is encoded wire bytes
    < 0.5x flat on this shape."""
    from transferia_tpu.abstract.schema import (
        CanonicalType,
        TableID,
        new_table_schema,
    )
    from transferia_tpu.columnar.batch import (
        Column,
        ColumnBatch,
        DictEnc,
        DictPool,
        _offsets_from_lengths,
    )
    from transferia_tpu.interchange import convert
    from transferia_tpu.interchange.flight import (
        FlightShardClient,
        ShardFlightServer,
    )
    from transferia_tpu.interchange.telemetry import TELEMETRY as ITEL

    rows = knobs.env_int("BENCH_ENCODED_WIRE_ROWS", 65_536)
    n_batches = max(1, knobs.env_int("BENCH_ENCODED_WIRE_BATCHES", 4))
    uniques = 4096
    tid = TableID("bench", "encoded_wire")
    schema = new_table_schema([("URL", "utf8"), ("RegionID", "int32")])
    vals = [f"https://bench{i}.example/path/{i % 97}/{i}"
            for i in range(uniques)]
    bufs = [v.encode() for v in vals]
    pool_data = np.frombuffer(b"".join(bufs), dtype=np.uint8).copy()
    pool_off = _offsets_from_lengths([len(b) for b in bufs] + [0])
    rng = np.random.default_rng(17)
    batch_data = [
        (rng.integers(0, uniques, rows).astype(np.int32),
         rng.integers(0, 500, rows).astype(np.int32))
        for _ in range(n_batches)
    ]

    def batches(pool):
        out = []
        for codes, regions in batch_data:
            out.append(ColumnBatch(tid, schema, {
                "URL": Column("URL", CanonicalType.UTF8,
                              dict_enc=DictEnc(codes, pool=pool)),
                "RegionID": Column("RegionID", CanonicalType.INT32,
                                   regions),
            }))
        return out

    def run_mode(encoded: bool, server, client,
                 key: str) -> tuple[float, int]:
        pool = DictPool(pool_data, pool_off, null_code=uniques)
        data = batches(pool)
        convert.set_encoded_wire(encoded)
        try:
            # warm the FULL round trip: the first dictionary-bearing
            # stream pays one-time arrow/grpc code-path setup (~0.6s)
            # that must not land in the timed window
            client.put_part(key, data)
            client.get_part(key)
            ITEL.reset()
            t0 = time.perf_counter()
            client.put_part(key, data)
            got = client.get_part(key)
            dt = time.perf_counter() - t0
            n_out = sum(b.n_rows for b in got)
            assert n_out == n_batches * rows, \
                f"row mismatch {n_out} != {n_batches * rows}"
            snap = ITEL.snapshot()
            if encoded and snap["pools_shipped"] > 1:
                raise AssertionError(
                    f"pool shipped {snap['pools_shipped']}x on one "
                    f"stream (pool-once contract broken)")
            return (n_batches * rows) / max(dt, 1e-9), snap["bytes_in"]
        finally:
            convert.set_encoded_wire(None)

    with ShardFlightServer(enable_shm=False) as server:
        with FlightShardClient(server.location,
                               allow_shm=False) as client:
            flat_rps, flat_bytes = run_mode(False, server, client,
                                            "bench.wire/flat")
            enc_rps, enc_bytes = run_mode(True, server, client,
                                          "bench.wire/enc")
    return {
        "metric": "encoded_wire_rows_per_sec",
        "unit": "rows/sec",
        "value": round(enc_rps),
        "flat_rows_per_sec": round(flat_rps),
        "speedup_vs_flat": round(enc_rps / max(flat_rps, 1e-9), 2),
        "wire_bytes_encoded": enc_bytes,
        "wire_bytes_flat": flat_bytes,
        "wire_bytes_ratio": round(enc_bytes / max(flat_bytes, 1), 3),
        "pool_once": True,
        "rows_per_batch": rows,
        "batches": n_batches,
        "pool_values": uniques,
    }


def measure_interchange() -> dict:
    """`--interchange`: the Arrow interchange plane's shard-handoff
    stage — identical sample batches moved via the row-pivot baseline
    (ChangeItems out and back), the Arrow IPC stream, the shared-memory
    segment, and loopback Flight; reports rows/s per path plus the
    zero-copy buffer ratio (interchange/bench.py).  The acceptance bar
    is the IPC-or-shm path beating the pivot baseline by >= 2x."""
    from transferia_tpu.interchange.bench import run_interchange_bench

    rows = knobs.env_int("BENCH_INTERCHANGE_ROWS", 500_000)
    return run_interchange_bench(rows=rows, batch_rows=65_536)


def _emit_multistream(report: dict) -> None:
    """The multi-stream lane's own gate lines out of an interchange
    report: rows/s at 4 substreams on the dict-heavy shape, and the
    4-vs-1 scaling ratio.  Both carry TOLERANCE_OVERRIDES bands — on a
    1-core bench box the ratio is pure scheduling noise (substream
    threads timeshare the core), so the band is wide on purpose."""
    curve = report.get("stream_curve") or {}
    four = curve.get("4") or {}
    if four.get("rows_per_sec"):
        _emit({"metric": "interchange_multistream_rows_per_sec",
               "unit": "rows/sec", "value": four["rows_per_sec"],
               "wire_mb": four.get("wire_mb"),
               "encoded_wire_ratio": four.get("encoded_wire_ratio")})
    if report.get("stream4_speedup"):
        _emit({"metric": "interchange_stream4_speedup", "unit": "x",
               "value": report["stream4_speedup"]})


def measure_fleet() -> dict:
    """`--fleet`: the fleet control plane's scheduler bench — 100+
    concurrent sample→memory transfers through admission control +
    weighted fair-share dispatch (fleet/bench.py).  Tracked metrics:
    p50/p99 scheduler dispatch latency and the Jain fairness index
    under the 10:1 tenant skew (acceptance bar >= 0.9), with the
    delivery audit (no transfer lost or double-admitted) folded into
    `ok`."""
    from transferia_tpu.fleet.bench import run_fleet_bench

    return run_fleet_bench(
        transfers=knobs.env_int("BENCH_FLEET_TRANSFERS", 120),
        workers=knobs.env_int("BENCH_FLEET_WORKERS", 8),
        rows=knobs.env_int("BENCH_FLEET_ROWS", 256),
    )


def measure_mvcc() -> dict:
    """`--mvcc`: the MVCC staging store's two read shapes — layered
    merge-on-read vs the compacted base — plus the cutover seal
    latency floor (mvcc/bench.py).  The run self-checks compaction
    row-equivalence and the zero-flat-materializations pin; both fold
    into `ok`."""
    from transferia_tpu.mvcc.bench import run_mvcc_bench

    return run_mvcc_bench(
        rows=knobs.env_int("BENCH_MVCC_ROWS", 200_000),
        layers=knobs.env_int("BENCH_MVCC_LAYERS", 12),
    )


def main() -> int:
    from transferia_tpu.runtime.backend import (
        require_tpu,
        setup_compile_cache,
    )
    from transferia_tpu.stats import trace as _trace

    against, candidate, tolerance = _against_args()
    if against and candidate:
        # pure compare mode: two artifacts, no benchmark run — the
        # verify-skill smoke and ad-hoc "did rNN regress vs rMM" checks
        try:
            cand = load_bench_metrics(candidate)
        except (OSError, UnicodeDecodeError) as e:
            print(f"# against: unreadable artifact {candidate}: {e}",
                  file=sys.stderr)
            return 2
        if not cand:
            # a truncated/empty candidate would turn every prior
            # metric into a SKIP and pass the gate — a run that
            # emitted nothing is unusable input, not a clean bill
            print(f"# against: no metric lines found in {candidate}",
                  file=sys.stderr)
            return 2
        return run_regression_gate(against, cand, tolerance)

    setup_compile_cache()  # before the first jit of any stage

    def gated(rc: int = 0) -> int:
        if against:
            grc = run_regression_gate(
                against, {m["metric"]: m for m in _METRICS_EMITTED},
                tolerance)
            return rc or grc
        return rc

    if "--fleet" in sys.argv[1:]:
        # standalone stage: scheduler latency + fairness (one JSON
        # line).  --trace[=path]/BENCH_TRACE wraps the whole fleet run
        # in a capture: with causal propagation on, one transfer's
        # admission → queue-wait → dispatch → parts → device work
        # exports as a single linked timeline
        from transferia_tpu.fleet.bench import format_report as _fmt_fleet

        trace_out = _trace_out_path()
        if trace_out:
            from transferia_tpu.stats import trace as _trace

            _trace.reset()
            _trace.enable(True)
        try:
            report = measure_fleet()
        finally:
            if trace_out:
                from transferia_tpu.stats import trace as _trace

                _trace.enable(False)
                n_events = _trace.write_chrome_trace(trace_out)
                print(f"# trace: {n_events} events -> {trace_out}",
                      file=sys.stderr)
        for line in _fmt_fleet(report).splitlines():
            print(f"# {line}", file=sys.stderr)
        _METRICS_EMITTED.append(report)
        # the merged-histogram dispatch tail rides the --against gate
        # as its own metric lines (latency direction: *_ms suffix)
        for q in ("p50", "p99", "p999"):
            _emit({"metric": f"fleet_dispatch_{q}_ms", "unit": "ms",
                   "value": report[f"dispatch_hdr_{q}_ms"]})
        # end-to-end freshness tail (SLO plane): event-time → publish
        # lag over the run window, latency direction like any *_ms
        if report.get("replication_lag_count"):
            _emit({"metric": "replication_lag_p99_ms", "unit": "ms",
                   "value": report["replication_lag_p99_ms"]})
        print(json.dumps(report))
        return gated(0 if report["ok"] else 1)

    if "--mvcc" in sys.argv[1:]:
        # standalone stage: layered vs compacted staging-store reads +
        # cutover seal latency (one JSON line); the run self-checks
        # compaction equivalence and the no-flatten pin
        from transferia_tpu.mvcc.bench import format_report as _fmt_mvcc

        report = measure_mvcc()
        for line in _fmt_mvcc(report).splitlines():
            print(f"# {line}", file=sys.stderr)
        _METRICS_EMITTED.append(report)
        _emit({"metric": "mvcc_merge_compacted_rows_per_sec",
               "unit": "rows/sec",
               "value": report["compacted_rows_per_sec"]})
        _emit({"metric": "mvcc_cutover_ms", "unit": "ms",
               "value": report["cutover_ms"]})
        _emit({"metric": "mvcc_spill_mbs", "unit": "MB/s",
               "value": report["spill_mbs"]})
        _emit({"metric": "mvcc_rebuild_ms", "unit": "ms",
               "value": report["rebuild_ms"]})
        print(json.dumps(report))
        return gated(0 if report["ok"] else 1)

    if "--interchange" in sys.argv[1:]:
        # standalone stage: one stdout JSON line, diagnostics on stderr
        from transferia_tpu.interchange.bench import format_report

        report = measure_interchange()
        for line in format_report(report).splitlines():
            print(f"# {line}", file=sys.stderr)
        _METRICS_EMITTED.append(report)
        _emit_multistream(report)
        print(json.dumps(report))
        return gated()

    if "--checksum-dict" in sys.argv[1:]:
        # standalone stage: flat vs code-native fingerprint (one JSON
        # line, printed next to checksum_fingerprint_rows_per_sec's
        # shape so the two headline checksum rates read together)
        report = measure_checksum_dict()
        print(f"# checksum-dict: code-native {report['value']} rows/s "
              f"vs flat {report['flat_rows_per_sec']} rows/s "
              f"({report['speedup_vs_flat']}x), "
              f"flat_materializations="
              f"{report['dict_flat_materializations']}", file=sys.stderr)
        _METRICS_EMITTED.append(report)
        print(json.dumps(report))
        return gated()

    if "--encoded-wire" in sys.argv[1:]:
        # standalone stage: pool-once Flight wire vs flat (one JSON
        # line); the run itself asserts the pool-once telemetry
        report = measure_encoded_wire()
        print(f"# encoded-wire: {report['value']} rows/s vs flat "
              f"{report['flat_rows_per_sec']} rows/s "
              f"({report['speedup_vs_flat']}x), wire bytes "
              f"{report['wire_bytes_encoded']} vs "
              f"{report['wire_bytes_flat']} "
              f"({report['wire_bytes_ratio']}x)", file=sys.stderr)
        _METRICS_EMITTED.append(report)
        print(json.dumps(report))
        return gated()

    if "--dispatch" in sys.argv[1:]:
        # standalone stage: encoded vs raw H2D dispatch (one JSON line)
        report = measure_dispatch()
        print(f"# dispatch: encoded {report['value']} rows/s vs raw "
              f"{report['raw_rows_per_sec']} rows/s "
              f"({report['speedup_vs_raw']}x), compression "
              f"{report['compression_ratio']}x", file=sys.stderr)
        _METRICS_EMITTED.append(report)
        print(json.dumps(report))
        return gated()

    # the default run is a DEVICE measurement: no chip, no metric line
    # (the standalone stages above are host-path smokes and run anywhere)
    device = require_tpu()
    print(f"# device: {json.dumps(device)}", file=sys.stderr)
    t_gen = time.perf_counter()
    generate_dataset()
    generate_wide_dataset()
    gen_s = time.perf_counter() - t_gen

    # warmup: compile the hash/filter programs on the first batches
    # (also the once-per-process runtime warm — cold device init happens
    # here, outside the timed window)
    warm_rows, warm_s = run_pipeline(limit_rows=BATCH_ROWS * 2,
                                     parquet=WIDE_PARQUET)

    # headline: the ClickBench-shaped wide dataset (~70 cols) — the shape
    # the 10M rows/s target is defined on (reference docs/benchmarks.md)
    from transferia_tpu.stats.profiler import profile as cpu_profile

    from transferia_tpu.providers import parquet_native, readahead

    parquet_native.reset_fallback_stats()
    readahead.reset_stats()
    trace_out = _trace_out_path()
    # the stage line and the exported trace are one recording
    _trace.reset()
    _trace.enable(True)
    with cpu_profile() as prof:
        rows, dt = run_pipeline(parquet=WIDE_PARQUET, total_rows=WIDE_ROWS)
    _trace.enable(False)
    stage_note = _trace.format_summary(dt, one_line=True)
    ra = readahead.snapshot_stats()
    if ra["prefetched_groups"]:
        # queue-depth evidence that decode overlapped downstream work —
        # rides the stages string so BENCH_*.json captures it
        stage_note += (
            f" readahead_groups={ra['prefetched_groups']}"
            f" readahead_depth_avg={ra['avg_depth']}"
            f" readahead_depth_max={ra['max_depth']}"
            f" readahead_inflight_mb_max="
            f"{ra['max_inflight_bytes'] / 1e6:.0f}")
    if trace_out:
        n_events = _trace.write_chrome_trace(trace_out)
        print(f"# trace: {n_events} events -> {trace_out}",
              file=sys.stderr)
        for line in _trace.format_summary(dt).splitlines():
            print(f"# trace: {line}", file=sys.stderr)
    native_fallbacks = parquet_native.fallback_stats()
    rps = rows / dt
    # second shape: the 10-col near-unique-URL dataset (own warmup so its
    # differently-shaped programs never compile inside the timed window)
    run_pipeline(limit_rows=BATCH_ROWS * 2)
    rows10, dt10 = run_pipeline()
    latencies = measure_transform_latency()
    import resource

    peak_rss_mb = round(
        resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024)
    result = {
        "metric": "clickbench_snapshot_rows_per_sec",
        "value": round(rps),
        "unit": "rows/sec",
        "vs_baseline": round(rps / 10_000_000, 4),
        "device": {"platform": device["platform"],
                   "kind": device["device_kind"],
                   "count": device["device_count"]},
        "cpu_count": _effective_cpus(),
        "dataset": {"rows": rows, "cols": _dataset_cols(WIDE_PARQUET)},
        "native_fallback_cols": len(native_fallbacks),
        "peak_rss_mb": peak_rss_mb,
        "stages": stage_note or None,
    }
    if WIDE_ROWS >= 100_000_000:
        # scale-proof marker (BENCH_WIDE_ROWS=100000000): dict pools and
        # the 2GiB offset guards under ~100M rows — the evidence lives in
        # the existing dataset/peak_rss_mb/native_fallback_cols fields
        result["scale_proof"] = True
    if native_fallbacks:
        result["native_fallbacks"] = native_fallbacks
    # crash-safety copy: the official line prints LAST (the driver tails
    # the output), but an OOM in an aux bench must not erase the headline
    print(f"# headline(early): {json.dumps(result)}", file=sys.stderr)
    lat_note = ""
    if latencies:
        import math

        lat = sorted(latencies)
        n = len(lat)
        p50 = lat[max(0, math.ceil(0.50 * n) - 1)] * 1000
        p99 = lat[max(0, math.ceil(0.99 * n) - 1)] * 1000  # nearest rank
        lat_note = (f" transform_latency_ms=p50:{p50:.2f}/p99:{p99:.2f}"
                    f" ({n} single-stream batches, steady state)")
    print(
        f"# rows={rows} time={dt:.2f}s warmup={warm_s:.1f}s "
        f"gen={gen_s:.1f}s batch={BATCH_ROWS} "
        f"process_count={_auto_process_count()} "
        f"backend={device['platform']}"
        f"{lat_note} dataset={WIDE_PARQUET}",
        file=sys.stderr,
    )
    _emit({'metric': 'clickbench10_snapshot_rows_per_sec',
           'value': round(rows10 / dt10), 'unit': 'rows/sec',
           'rows': rows10,
           'note': '10-col dataset, near-unique URLs'})
    if stage_note:
        print(f"# stages: {stage_note}", file=sys.stderr)
    if prof.report is not None and prof.report.samples:
        for line in prof.report.format(10).splitlines():
            print(f"# profile: {line}", file=sys.stderr)
    from transferia_tpu.ops.linkprobe import probe_link
    from transferia_tpu.stats.trace import TELEMETRY as _tel

    link_note = probe_link().describe()
    _snap = _tel.snapshot()
    if _snap["h2d_encoded_bytes"]:
        link_note += (
            f" dispatch_ratio={_snap['dispatch_compression_ratio']}"
            f" dict_pool_hits={_snap['dict_pool_hits']}")
    print(f"# link: {link_note}"
          + (f" {_placement_note}" if _placement_note else ""),
          file=sys.stderr)
    # aux phases: each prints one tail line.  A phase that raises fails
    # the run — the headline's crash-safety copy is already on stderr.
    # All of them run in THIS process: it holds the chip, and a child
    # that needed it could not get it.
    _emit(measure_device_kernel())
    _emit(measure_device_decode())
    _emit(measure_device_fingerprint())
    _emit(measure_mesh_1dev())
    fprint = measure_fingerprint()
    if fprint:
        _emit(fprint)
    if knobs.env_str("BENCH_SKIP_CHECKSUM_DICT", "") != "1":
        _emit(measure_checksum_dict())
    if knobs.env_str("BENCH_SKIP_INTERCHANGE", "") != "1":
        ichg = measure_interchange()
        _emit(ichg)
        _emit_multistream(ichg)
    if knobs.env_str("BENCH_SKIP_ENCODED_WIRE", "") != "1":
        _emit(measure_encoded_wire())
    if knobs.env_str("BENCH_SKIP_DISPATCH", "") != "1":
        _emit(measure_dispatch())
    # remaining BASELINE configs
    if knobs.env_str("BENCH_SKIP_KAFKA2CH", "") != "1":
        _emit(measure_kafka2ch())
    if knobs.env_str("BENCH_SKIP_CONFIGS", "") != "1":
        for fn in (measure_pg2ch, measure_mysql2kafka,
                   measure_kafka_sr2ch):
            _emit(fn())
    # the ONE stdout JSON line, last so tail-capture always records it
    _METRICS_EMITTED.append(result)
    print(json.dumps(result))
    return gated()


def _effective_cpus() -> float:
    """Cores this process can actually use (affinity ∩ cgroup quota) —
    shared with the fs provider's decode auto-knobs."""
    from transferia_tpu.runtime.limits import effective_cpus

    return effective_cpus()


def _dataset_cols(path: str) -> int:
    import pyarrow.parquet as pq

    return pq.ParquetFile(path).metadata.num_columns


if __name__ == "__main__":
    sys.exit(main() or 0)
