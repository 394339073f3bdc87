"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, started cold, drives the two main paths through the entry
points a user calls and checks what comes out:

  * snapshot:    `trtpu activate` (transferia_tpu.cli.main.main) over an
                 `fs` parquet source -> mask_field(URL) + filter_rows ->
                 memory sink, once per placement (host = the reference,
                 device, device again, auto), on the ClickBench-shaped
                 73-column table and on the 10-column table, each a
                 directory of four-row-group part files.  At these
                 sizes parquet's writer gives up on a dictionary for the
                 URL column, so URLs cross the link as per-row SHA
                 blocks on both; the wide leg also masks SearchPhrase
                 (eight values), whose pool hashes on the chip once;
  * replication: `run_replication`, as `trtpu replicate` calls it, over
                 examples/kafka2ch.yaml (JSON parser, rename + mask) from
                 the in-repo fake broker into the fake ClickHouse, device
                 placement pinned, poll sizes ragged.

Right means: the rows the generator's ground truth says survive the
filter, a table fingerprint equal to the host pass, masked values equal
to hashlib's HMAC on a sample, and device counters that show the chip
did the work.  Any failed check, or any exception, is a non-zero exit.

The script REFUSES to run unless JAX resolves a TPU: it exits non-zero
and prints no result.  `--rehearsal` runs the same legs on the CPU
backend at a tiny size to debug the script itself; it says so, prints no
rate and no result line, and its exit code says nothing about the chip.

One process uses the chip: everything below runs in this process, the
fake servers are threads, and the only child is the C++ compiler.

    python chip_smoke.py            # on a machine with a TPU
    python chip_smoke.py --rehearsal  # CPU, tiny, debugging only
"""

from __future__ import annotations

import argparse
import functools
import hashlib
import hmac
import json
import logging
import os
import resource
import shutil
import sys
import threading
import time
from typing import Optional

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)

FILTER = "RegionID < 400 AND ResolutionWidth >= 390"
# masked columns per table: URL is the PII column of both tables; SearchPhrase
# stays dictionary-encoded off the parquet page, which makes it the
# column that takes the device-pool mask route at full size
MASKED = {"wide": ["URL", "SearchPhrase"], "ten": ["URL"]}
SNAPSHOT_SALT = "smoke-salt"
REPLICATION_SALT = "smoke-replication-salt"
_TS0 = 1_790_000_000_000_000  # epoch microseconds of the first event
# loggers whose INFO lines are part of what this script establishes: the
# backend a worker resolved at its first fused plan
_INFO_LOGGERS = ("transferia_tpu.runtime.backend",)


class Checks:
    """Named pass/fail facts; every failure is kept, none is fatal until
    the end, so one chip run reports all of them."""

    def __init__(self):
        self.items: list[dict] = []

    def check(self, name: str, ok: bool, detail: str = "") -> None:
        self.items.append({"name": name, "ok": bool(ok), "detail": detail})
        print(f"  [{'ok' if ok else 'FAIL'}] {name}"
              + (f" — {detail}" if detail else ""), flush=True)

    def failed(self) -> list[dict]:
        return [c for c in self.items if not c["ok"]]


class _PlacementLog(logging.Handler):
    """Collects the fused steps' own placement log lines — the surface
    an operator of `trtpu activate` sees (the loader owns the per-part
    chains, so there is no step object to ask afterwards)."""

    def __init__(self):
        super().__init__(level=logging.INFO)
        self.lines: list[str] = []

    def emit(self, record: logging.LogRecord) -> None:
        msg = record.getMessage()
        if "placement:" in msg:
            self.lines.append(msg)

    def attach(self, logger: logging.Logger) -> None:
        """INFO into this handler only — one "fused N steps" line per
        part would bury the run's own output on stderr."""
        self._restore = (logger.level, logger.propagate)
        logger.setLevel(logging.INFO)
        logger.propagate = False
        logger.addHandler(self)

    def detach(self, logger: logging.Logger) -> None:
        if self in logger.handlers:
            logger.removeHandler(self)
            logger.setLevel(self._restore[0])
            logger.propagate = self._restore[1]


def _phase(title: str) -> None:
    print(f"\n== {title}", flush=True)


def _cache_entries(path: str) -> int:
    if not os.path.isdir(path):
        return 0
    return sum(1 for n in os.listdir(path) if not n.endswith("-atime"))


# -- data ----------------------------------------------------------------------

def _part_path(path: str, i: int) -> str:
    return os.path.join(path, f"part-{i:05d}.parquet")


def _write_expected(path: str, rows: int, kept: int) -> None:
    """The generator's ground truth beside the table: the rows FILTER
    keeps, counted on the generated columns — a transfer that loses or
    invents rows anywhere fails against it."""
    with open(path + ".expected.json", "w") as fh:
        json.dump({"rows": rows, "kept": kept}, fh)


def expected_kept(path: str) -> int:
    with open(path + ".expected.json") as fh:
        return int(json.load(fh)["kept"])


def generate_dataset(path: str, rows: int, batch_rows: int, seed: int,
                     max_file_rows: Optional[int] = None) -> None:
    """The 10-column table: URLs are near-unique (~10M distinct paths),
    so a masked URL crosses the link as per-row SHA blocks.

    With `max_file_rows`, `path` is a directory of part files of at most
    that many rows each (the same rows, in the same order) — for a
    machine that limits the size of one file."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    os.makedirs(os.path.dirname(path), exist_ok=True)
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
    _write_expected(path, n,
                    int(((region_id < 400) & (res_w >= 390)).sum()))


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


# rows drawn from the generator at a time (a test cuts it down to roll
# part files at a small size)
_WIDE_CHUNK_ROWS = 500_000


def generate_wide_dataset(path: str, rows: int, batch_rows: int, seed: int,
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
    chunk = min(_WIDE_CHUNK_ROWS, max_file_rows or _WIDE_CHUNK_ROWS)
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
    _write_expected(path, rows, kept)


def generate(args, data_dir: str) -> dict:
    """Both tables as directories of part files of at most
    `args.file_rows` rows: the machine that checks this script limits the
    size of one file (the wide table is 1.6 GB), and a directory of parts
    is what the `fs` source is pointed at in deployment anyway."""
    wide = os.path.join(data_dir, f"hits_wide_{args.rows}")
    ten = os.path.join(data_dir, f"hits_{args.rows10}")
    t0 = time.perf_counter()
    generate_wide_dataset(wide, args.rows, args.batch_rows, args.seed,
                          max_file_rows=args.file_rows)
    generate_dataset(ten, args.rows10, args.batch_rows, args.seed + 1,
                     max_file_rows=args.file_rows)
    import pyarrow.parquet as pq

    out = {"seconds": round(time.perf_counter() - t0, 2), "tables": {}}
    for name, path in (("wide", wide), ("ten", ten)):
        files = sorted(os.path.join(path, f) for f in os.listdir(path))
        metas = [pq.ParquetFile(f).metadata for f in files]
        sizes = [os.path.getsize(f) for f in files]
        out["tables"][name] = {
            "path": path, "files": len(files),
            "rows": sum(m.num_rows for m in metas),
            "columns": metas[0].num_columns,
            "row_groups": sum(m.num_row_groups for m in metas),
            "file_mb": round(sum(sizes) / 1e6, 1),
            "largest_file_mb": round(max(sizes) / 1e6, 1),
            "expected_kept": expected_kept(path),
        }
    return out


# -- snapshot leg ----------------------------------------------------------------

def _transformation(name: str) -> dict:
    return {"transformers": [
        {"mask_field": {"columns": MASKED[name], "salt": SNAPSHOT_SALT}},
        {"filter_rows": {"filter": FILTER}},
    ]}


def _write_transfer_yaml(path: str, transfer_id: str, name: str,
                         parquet: str, args) -> None:
    doc = {
        "id": transfer_id,
        "type": "SNAPSHOT_ONLY",
        # one part per file: left to itself the source cuts files this
        # small into one-group parts, and a fused step that sees a single
        # batch never gets to weigh the device under `auto`
        "src": {"type": "fs", "params": {
            "path": parquet, "format": "parquet", "table": "hits",
            "batch_rows": args.batch_rows,
            "rowgroups_per_part": args.file_rows // args.batch_rows}},
        "dst": {"type": "memory", "params": {"sink_id": transfer_id}},
        "transformation": _transformation(name),
        "runtime": {"process_count": args.process_count},
    }
    import yaml

    with open(path, "w") as fh:
        yaml.safe_dump(doc, fh)


def _mask_sample_mismatches(source, masked_cols: list[str], batch,
                            n: int = 2048) -> int:
    """Independent reference for the mask: hashlib's HMAC-SHA256 of the
    SOURCE table's values for a sample of landed rows, joined by
    WatchID.  `source` is the file's (WatchID, masked columns) table."""
    import pyarrow as pa
    import pyarrow.compute as pc

    n = min(n, batch.n_rows)
    ids = batch.column("WatchID").to_pylist()[:n]
    hit = source.filter(pc.is_in(
        source["WatchID"], value_set=pa.array(ids, type=pa.int64())))
    row_of = {wid: i for i, wid in enumerate(hit["WatchID"].to_pylist())}
    key = SNAPSHOT_SALT.encode()
    bad = 0
    for col in masked_cols:
        plain = hit[col].to_pylist()
        got = batch.column(col).to_pylist()[:n]
        for wid, masked in zip(ids, got):
            want = hmac.new(key, plain[row_of[wid]].encode(),
                            hashlib.sha256).hexdigest()
            if isinstance(masked, bytes):
                masked = masked.decode()
            bad += masked != want
    return bad


def snapshot_pass(table: dict, name: str, placement: str, tag: str,
                  args, work_dir: str, source) -> dict:
    """One `trtpu activate` of the table under one placement mode."""
    from transferia_tpu.abstract.interfaces import is_columnar
    from transferia_tpu.cli.main import main as trtpu
    from transferia_tpu.columnar.batch import reset_intern_cache
    from transferia_tpu.ops.rowhash import TableFingerprinter
    from transferia_tpu.providers.memory import get_store
    from transferia_tpu.providers.parquet_native import reset_file_caches
    from transferia_tpu.stats.trace import TELEMETRY
    from transferia_tpu.transform.fused import set_placement

    transfer_id = f"smoke-{name}-{tag}"
    yaml_path = os.path.join(work_dir, f"{transfer_id}.yaml")
    _write_transfer_yaml(yaml_path, transfer_id, name, table["path"], args)
    store = get_store(transfer_id)
    store.clear()
    # dict pools are shared for the process — per decoded dict page and
    # per (file, column) content — and carry their hashed form as a
    # memo: without this the host pass's memo would answer for the chip
    # in every later pass
    reset_file_caches()
    reset_intern_cache()
    set_placement(placement)
    TELEMETRY.reset()
    t0 = time.perf_counter()
    try:
        rc = trtpu(["--log-level", "warning", "activate",
                    "--transfer", yaml_path])
    finally:
        set_placement(None)
    seconds = time.perf_counter() - t0
    tel = TELEMETRY.snapshot()
    fp = TableFingerprinter(backend="host")
    first = None
    for b in store.batches:
        if is_columnar(b) and b.n_rows:
            first = first or b
            fp.push(b)
    out = {
        "placement": placement, "rc": rc,
        "rows_landed": store.row_count(),
        "seconds": round(seconds, 3),
        "fingerprint": fp.result().digest(),
        "mask_sample_mismatches": (
            _mask_sample_mismatches(source, MASKED[name], first)
            if first is not None else -1),
        "telemetry": tel,
    }
    store.clear()
    return out


def snapshot_leg(name: str, table: dict, args, work_dir: str,
                 checks: Checks, rehearsal: bool) -> dict:
    _phase(f"snapshot leg: {name} table, {table['rows']} rows x "
           f"{table['columns']} cols, {table['row_groups']} row groups "
           f"in {table['files']} files")
    import pyarrow.parquet as pq

    placement_log = _PlacementLog()
    fused_logger = logging.getLogger("transferia_tpu.transform.fused")
    source = pq.read_table(table["path"],
                           columns=["WatchID"] + MASKED[name])
    out: dict = {"passes": {}}
    for tag, placement in (("host", "host"), ("device", "device"),
                           ("device2", "device"), ("auto", "auto")):
        if tag == "auto":
            placement_log.attach(fused_logger)
        try:
            res = snapshot_pass(table, name, placement, tag, args,
                                work_dir, source)
        finally:
            placement_log.detach(fused_logger)
        out["passes"][tag] = res
        tel = res["telemetry"]
        line = (f"  {tag:8s} rows={res['rows_landed']} "
                f"launches={tel['device_launches']} "
                f"h2d={tel['h2d_bytes']} d2h={tel['d2h_bytes']} "
                f"compiles={tel['compile_events']} "
                f"routes(flat/pool/host_subset)="
                f"{tel['mask_rows_device_flat']}/"
                f"{tel['mask_rows_device_pool']}/"
                f"{tel['mask_rows_host_subset']}")
        if not rehearsal:
            line += (f" {res['seconds']:.2f}s "
                     f"compile={tel['compile_seconds']:.1f}s")
        print(line, flush=True)
    host, dev, dev2, auto = (out["passes"][k] for k in
                             ("host", "device", "device2", "auto"))
    want = table["expected_kept"]
    for tag, res in out["passes"].items():
        checks.check(f"{name}/{tag}: activate exits 0 and lands "
                     f"expected_kept rows",
                     res["rc"] == 0 and res["rows_landed"] == want,
                     f"landed {res['rows_landed']}, want {want}")
        checks.check(f"{name}/{tag}: masked {'+'.join(MASKED[name])} "
                     f"sample equals hashlib HMAC",
                     res["mask_sample_mismatches"] == 0,
                     f"{res['mask_sample_mismatches']} mismatches")
        if tag != "host":
            checks.check(f"{name}/{tag}: fingerprint equals the host pass",
                         res["fingerprint"] == host["fingerprint"],
                         f"{res['fingerprint']} vs {host['fingerprint']}")
    checks.check(f"{name}/host: the host pass never touched the device",
                 host["telemetry"]["device_launches"] == 0)
    dtel = dev["telemetry"]
    checks.check(f"{name}/device: device_launches, h2d_bytes, d2h_bytes > 0",
                 min(dtel["device_launches"], dtel["h2d_bytes"],
                     dtel["d2h_bytes"]) > 0,
                 f"{dtel['device_launches']} launches, "
                 f"{dtel['h2d_bytes']} B in, {dtel['d2h_bytes']} B out")
    if len(MASKED[name]) > 1:
        checks.check(f"{name}/device: the chip hashed the dictionary "
                     f"pool itself (pool uploads > 0)",
                     dtel["dict_pool_uploads"] > 0,
                     f"{dtel['dict_pool_uploads']} uploads, "
                     f"{dtel['dict_pool_hits']} memo hits")
    checks.check(f"{name}/device2: a repeated device pass compiles nothing",
                 dev2["telemetry"]["compile_events"] == 0,
                 f"{dev2['telemetry']['compile_events']} compile events")
    out["auto_placement_log"] = placement_log.lines
    atel = auto["telemetry"]
    print(f"  auto: {len(placement_log.lines)} placement decisions logged, "
          f"{atel['device_launches']} device launches", flush=True)
    for msg in placement_log.lines[:8]:
        print(f"    {msg}", flush=True)
    return out


# -- one long-lived chain under auto placement -------------------------------------

def chain_probe(name: str, table: dict, args, n_batches: int,
                rehearsal: bool, checks: Checks) -> dict:
    """`auto` on ONE chain that lives for n_batches (what a replication
    stream has, and a snapshot part only when it spans several row
    groups): the fused step's own placement_summary() with the host and
    device ns/row behind it — report only.  With more than one device
    it also pins one batch to the device and holds the mesh program's
    psum'd kept count to the host strategy's."""
    from transferia_tpu.abstract.schema import TableID
    from transferia_tpu.abstract.table import TableDescription
    from transferia_tpu.factories import new_storage
    from transferia_tpu.models import Transfer
    from transferia_tpu.providers.file import FileSourceParams
    from transferia_tpu.providers.stdout import NullTargetParams
    from transferia_tpu.transform.chain import build_chain
    from transferia_tpu.transform.fused import (
        DeviceFusedStep,
        set_placement,
    )

    transformation = _transformation(name)
    transfer = Transfer(
        id=f"smoke-probe-{name}",
        src=FileSourceParams(path=table["path"], format="parquet",
                             table="hits", batch_rows=args.batch_rows),
        dst=NullTargetParams(), transformation=transformation)
    batches: list = []

    class _Enough(Exception):
        pass

    def collect(batch):
        batches.append(batch)
        if len(batches) >= n_batches:
            raise _Enough()

    try:
        new_storage(transfer).load_table(
            TableDescription(id=TableID("fs", "hits")), collect)
    except _Enough:
        pass
    set_placement("auto")
    try:
        chain = build_chain(transformation)
        for b in batches:
            chain.apply(b)
        plan = chain.plan_for(batches[0].table_id, batches[0].schema)
        steps = [s for s in plan.steps if isinstance(s, DeviceFusedStep)]
        summaries = [s.placement_summary() for s in steps]
        if any(s.sharded_program is not None for s in steps):
            set_placement("host")
            host_kept = chain.apply(batches[0]).n_rows
            set_placement("device")
            dev_kept = chain.apply(batches[0]).n_rows
            for s in steps:
                sp = s.sharded_program
                checks.check(
                    f"mesh/{name}: psum'd kept count over {sp.n_dev} "
                    f"devices equals the host strategy's",
                    sp.last_kept == dev_kept == host_kept,
                    f"psum {sp.last_kept}, device {dev_kept}, "
                    f"host {host_kept}")
    finally:
        set_placement(None)
    out = {"batches": len(batches), "steps": []}
    for s, summary in zip(steps, summaries):
        if rehearsal:  # the decision, not the CPU host's ns/row
            summary = summary.split()[0]
        entry = {"step": s.describe(), "summary": summary,
                 "sharded_program": s.sharded_program is not None}
        sp = s.sharded_program
        if sp is not None:
            entry["mesh_devices"] = sp.n_dev
            entry["last_kept"] = sp.last_kept
        out["steps"].append(entry)
        print(f"  {name}: {entry['step']}: {entry['summary']}"
              + (f" mesh={sp.n_dev}" if sp is not None else ""),
              flush=True)
    return out


# -- replication leg -----------------------------------------------------------------

def replication_leg(args, checks: Checks, rehearsal: bool) -> dict:
    """examples/kafka2ch.yaml, as `trtpu replicate` runs it (activate is
    a no-op for INCREMENT_ONLY; then run_replication), fake broker ->
    fake ClickHouse.  Messages arrive in waves of very different sizes,
    so the polls are ragged and the row buckets get exercised."""
    from tests.recipes.fake_clickhouse import FakeCH
    from tests.recipes.fake_kafka import FakeKafka
    from transferia_tpu.cli.config import load_transfer
    from transferia_tpu.coordinator import MemoryCoordinator
    from transferia_tpu.providers.kafka.client import KafkaClient, Record
    from transferia_tpu.runtime import run_replication
    from transferia_tpu.stats.trace import TELEMETRY
    from transferia_tpu.transform.fused import set_placement

    waves = args.waves
    _phase(f"replication leg: examples/kafka2ch.yaml, waves {waves}")
    n_partitions = 4
    srv = FakeKafka(n_partitions=n_partitions).start()
    ch = FakeCH().start()
    stop = threading.Event()
    th = None
    set_placement("device")
    TELEMETRY.reset()
    try:
        os.environ["KAFKA_BROKERS"] = f"127.0.0.1:{srv.port}"
        os.environ["CH_HOST"] = "127.0.0.1"
        os.environ["MASK_SALT"] = REPLICATION_SALT
        transfer = load_transfer(
            os.path.join(ROOT, "examples", "kafka2ch.yaml"))
        transfer.dst.port = ch.port      # the example pins CH's 8123
        transfer.dst.bufferer = None     # push per poll
        srv.create_topic("events")
        cp = MemoryCoordinator()
        th = threading.Thread(
            target=run_replication, args=(transfer, cp),
            kwargs={"stop_event": stop, "backoff": 0.2}, daemon=True)
        th.start()
        producer = KafkaClient([f"127.0.0.1:{srv.port}"])
        emails: dict[int, str] = {}
        produced = 0
        t0 = time.perf_counter()
        for wave, size in enumerate(waves):
            recs: list[list] = [[] for _ in range(n_partitions)]
            for i in range(produced, produced + size):
                emails[i] = f"user{i}.w{wave}@mail{i % 977}.example"
                recs[i % n_partitions].append(Record(
                    key=b"", value=json.dumps({
                        "id": i, "user_email": emails[i],
                        "amount": (i % 1000) / 8.0,
                        "ts": _TS0 + i,
                    }).encode()))
            for p in range(n_partitions):
                if recs[p]:
                    producer.produce("events", p, recs[p])
            produced += size
            deadline = time.monotonic() + 300
            while ch.total_rows() < produced and \
                    time.monotonic() < deadline:
                time.sleep(0.02)
        seconds = time.perf_counter() - t0
        producer.close()
    finally:
        stop.set()
        if th is not None:
            th.join(timeout=30)
        set_placement(None)
        srv.stop()
        ch.stop()
    tel = TELEMETRY.snapshot()
    landed = ch.total_rows()
    tables = sorted(n for n in ch.tables if not n.startswith("__trtpu"))
    key = REPLICATION_SALT.encode()
    bad = ids_seen = 0
    for name in tables:
        for row in ch.rows(name):
            ids_seen += 1
            i = int(row["id"])
            want = hmac.new(key, emails[i].encode(),
                            hashlib.sha256).hexdigest().encode()
            bad += (row["user_email"] != want
                    or row["amount"] != (i % 1000) / 8.0
                    or row["ts"] != _TS0 + i)
    out = {"waves": list(waves), "produced": produced, "landed": landed,
           "tables": tables, "telemetry": tel}
    line = (f"  produced={produced} landed={landed} tables={tables} "
            f"launches={tel['device_launches']} "
            f"compiles={tel['compile_events']} "
            f"routes(flat/pool/host_subset)="
            f"{tel['mask_rows_device_flat']}/{tel['mask_rows_device_pool']}/"
            f"{tel['mask_rows_host_subset']}")
    if not rehearsal:
        out["seconds"] = round(seconds, 3)
        line += (f" {seconds:.2f}s compile={tel['compile_seconds']:.1f}s")
    print(line, flush=True)
    checks.check("replication: rows produced == rows landed",
                 landed == produced, f"{landed} of {produced}")
    checks.check("replication: the worker thread stopped",
                 th is not None and not th.is_alive())
    checks.check("replication: rename landed rows in events_clean",
                 tables == ["events_clean"], str(tables))
    checks.check("replication: every landed row equals the produced one, "
                 "user_email as hashlib's HMAC",
                 bad == 0 and ids_seen == produced,
                 f"{bad} mismatches over {ids_seen} rows")
    checks.check("replication: device_launches > 0",
                 tel["device_launches"] > 0,
                 f"{tel['device_launches']} launches")
    return out


# -- kernels alone, beside the placement models' constants ---------------------------

def measure_device_kernel(rows: int = 1 << 20) -> int:
    """Sustained on-chip HMAC-SHA256 mask throughput, data resident.

    This isolates the device kernel from the host↔device link: one large
    launch amortizes the per-launch overhead, and timing spans several
    back-to-back launches on resident buffers.  It is what the chip
    itself sustains on the mask op, in rows/s: the figure
    transform/fused.py's DEVICE_MASK_ROWS_PER_S cites.
    """
    import jax
    import jax.numpy as jnp

    from transferia_tpu.ops.sha256 import _hmac_key_states, hmac_device_core

    mb = 2  # 2 SHA blocks/row: a ~60-90 byte URL, the ClickBench shape
    rng = np.random.default_rng(11)
    blocks = rng.integers(0, 256, size=(rows, mb * 64), dtype=np.uint8)
    nblocks = np.full(rows, mb, dtype=np.int32)
    inner, outer = _hmac_key_states(SNAPSHOT_SALT.encode())
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
    return round(rows * iters / dt)


def measure_device_fingerprint(rows: int = 1 << 20) -> int:
    """Sustained ON-CHIP checksum-fingerprint rate in rows/s
    (ops/rowhash.py DeviceFingerprintProgram), 64 passes over resident
    buffers in one launch: the figure DEVICE_FINGERPRINT_ROWS_PER_S
    cites.  Shape: one int64 column + one 64-byte var-width column, the
    checksum task's typical mix."""
    import jax
    import jax.numpy as jnp

    from transferia_tpu.abstract.schema import (
        CanonicalType,
        ColSchema,
        TableID,
        TableSchema,
    )
    from transferia_tpu.columnar.batch import (
        Column,
        ColumnBatch,
        bucket_rows,
    )
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
    assert bucket_rows(n_rows) == n_rows  # power-of-two rows: no padding
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
    return round(rows * iters / dt)


def kernel_rates() -> dict:
    from transferia_tpu.ops.rowhash import DEVICE_FINGERPRINT_ROWS_PER_S
    from transferia_tpu.transform.fused import DEVICE_MASK_ROWS_PER_S

    _phase("kernels alone (resident buffers), beside the model constants")
    mask = measure_device_kernel()
    fprint = measure_device_fingerprint()
    out = {
        "mask_rows_per_s": mask,
        "mask_model_constant": DEVICE_MASK_ROWS_PER_S,
        "fingerprint_rows_per_s": fprint,
        "fingerprint_model_constant": DEVICE_FINGERPRINT_ROWS_PER_S,
    }
    print(f"  mask kernel {mask:,} rows/s "
          f"(model: {DEVICE_MASK_ROWS_PER_S:,.0f}); fingerprint "
          f"{fprint:,} rows/s "
          f"(model: {DEVICE_FINGERPRINT_ROWS_PER_S:,.0f})", flush=True)
    return out


# -- main -----------------------------------------------------------------------------

def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--rehearsal", action="store_true",
                   help="CPU backend, tiny sizes, no rates, no result "
                        "line: debugs this script, says nothing about "
                        "the chip")
    p.add_argument("--seed", type=int, default=7)
    p.add_argument("--rows", type=int, default=None,
                   help="wide (73-col) table rows (default 10,000,000; "
                        "rehearsal 60,000)")
    p.add_argument("--rows10", type=int, default=None,
                   help="10-col table rows (default 2,000,000; "
                        "rehearsal 60,000)")
    p.add_argument("--batch-rows", type=int, default=None,
                   help="rows per row group (default 131,072; "
                        "rehearsal 16,384)")
    p.add_argument("--process-count", type=int, default=4,
                   help="snapshot part threads (runtime.process_count)")
    p.add_argument("--out", default=os.path.join(ROOT, "chiprun_out"),
                   help="directory for chip_smoke.json")
    p.add_argument("--data-dir", default=os.path.join(ROOT, ".chip_smoke"),
                   help="scratch directory for generated data "
                        "(emptied at exit)")
    args = p.parse_args(argv)
    small = args.rehearsal
    args.rows = args.rows or (60_000 if small else 10_000_000)
    args.rows10 = args.rows10 or (60_000 if small else 2_000_000)
    # 16,384-row groups keep ~11k rows after scan pushdown: enough to
    # take the mesh route when the rehearsal sees 8 virtual CPU devices
    args.batch_rows = args.batch_rows or (16_384 if small else 131_072)
    # four row groups to a part file (wide: ~80 MB); two in the rehearsal,
    # so that it reads more than one file too
    args.file_rows = args.batch_rows * (2 if small else 4)
    args.waves = ((300, 1700, 60, 900) if small
                  else (3_000, 17_000, 600, 40_000))
    return args


def run(args, summary: dict, checks: Checks) -> None:
    """Every phase, in order, filling `summary` and `checks` as it goes.
    Exceptions propagate — a crashed phase is a crashed run."""
    import jax

    from transferia_tpu.runtime.backend import (
        describe_backend,
        require_tpu,
        setup_compile_cache,
    )

    rehearsal = args.rehearsal
    summary.update(rehearsal=rehearsal, seed=args.seed)
    t_start = time.perf_counter()

    _phase("backend")
    cache_dir = setup_compile_cache()
    cache_before = _cache_entries(cache_dir)
    cache_events = {"hits": 0, "misses": 0}

    def on_event(event: str, **kw) -> None:
        if event.endswith("/cache_hits"):
            cache_events["hits"] += 1
        elif event.endswith("/cache_misses"):
            cache_events["misses"] += 1

    jax.monitoring.register_event_listener(on_event)
    backend = describe_backend() if rehearsal else require_tpu()
    summary["backend"] = backend
    print("  " + json.dumps(backend), flush=True)
    if rehearsal:
        print("  REHEARSAL on the CPU backend: no rate below is printed "
              "and none is meant; there is no result line", flush=True)
    print(f"  compile cache: {cache_dir} ({cache_before} entries)",
          flush=True)
    fsize = resource.getrlimit(resource.RLIMIT_FSIZE)[0]
    summary["file_size_limit_bytes"] = (
        None if fsize == resource.RLIM_INFINITY else fsize)
    print(f"  file size limit: {summary['file_size_limit_bytes']} bytes; "
          f"data part files hold {args.file_rows} rows at most", flush=True)

    _phase("native library: forced build from the tracked sources")
    from transferia_tpu import native

    so = native.build(force=True)
    lib = native.lib()
    checks.check("native: built from source and loaded",
                 lib is not None and os.path.exists(so),
                 os.path.basename(str(so)))
    summary["native"] = os.path.basename(str(so))

    _phase("data: generated from the seed")
    shutil.rmtree(args.data_dir, ignore_errors=True)
    os.makedirs(args.data_dir)
    data = generate(args, args.data_dir)
    summary["data"] = data
    for name, t in data["tables"].items():
        print(f"  {name}: {t['rows']} rows x {t['columns']} cols, "
              f"{t['row_groups']} row groups in {t['files']} files, "
              f"{t['file_mb']} MB (largest file {t['largest_file_mb']} MB), "
              f"expected_kept={t['expected_kept']}", flush=True)
    checks.check("data: wide table is 73 columns wide",
                 data["tables"]["wide"]["columns"] == 73)

    _phase("link")
    from transferia_tpu.ops.fused import _chunk_rows
    from transferia_tpu.ops.linkprobe import probe_link

    link = probe_link()
    summary["link"] = {
        "describe": link.describe(), "measured": link.measured,
        "launch_overhead_s": link.launch_overhead_s,
        "h2d_bytes_per_s": link.h2d_bytes_per_s,
        "d2h_bytes_per_s": link.d2h_bytes_per_s,
        "chunk_rows": _chunk_rows(),
    }
    print(f"  {link.describe()} chunk_rows={_chunk_rows()}", flush=True)
    if not rehearsal:
        checks.check("link: profile is measured, not pinned",
                     link.measured, link.describe())

    summary["snapshot"] = {}
    for name in ("wide", "ten"):
        summary["snapshot"][name] = snapshot_leg(
            name, data["tables"][name], args, args.data_dir, checks,
            rehearsal)

    _phase("auto placement on one long-lived chain")
    summary["chain_probe"] = {
        name: chain_probe(name, data["tables"][name], args, 8, rehearsal,
                          checks)
        for name in ("wide", "ten")}
    if backend["device_count"] > 1:
        for name, probe in summary["chain_probe"].items():
            checks.check(
                f"mesh/{name}: fused steps built the sharded program",
                all(s["sharded_program"] for s in probe["steps"]))
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in jax.devices()]
        summary["device_peak_bytes"] = peaks
        print(f"  peak bytes in use per device: {peaks}", flush=True)
        if not rehearsal:
            checks.check("mesh: every device held data (peak > 1 MiB)",
                         min(peaks) > (1 << 20), str(peaks))

    summary["replication"] = replication_leg(args, checks, rehearsal)

    if not rehearsal:
        summary["kernels"] = kernel_rates()

    cache_after = _cache_entries(cache_dir)
    summary["compile_cache"] = {
        "dir": cache_dir, "entries_before": cache_before,
        "entries_written": cache_after - cache_before,
        "persistent_hits": cache_events["hits"],
        "persistent_misses": cache_events["misses"],
    }
    _phase("compile cache")
    print(f"  {cache_after - cache_before} entries written to {cache_dir} "
          f"({cache_before} found at start); persistent-cache hits="
          f"{cache_events['hits']} misses={cache_events['misses']}",
          flush=True)
    if not rehearsal:
        compile_s = sum(
            p["telemetry"]["compile_seconds"]
            for leg in summary["snapshot"].values()
            for p in leg["passes"].values()
        ) + summary["replication"]["telemetry"]["compile_seconds"]
        summary["compile_seconds_legs"] = round(compile_s, 2)
        summary["compile_cache_was_warm"] = cache_before > 0
        summary["wall_seconds"] = round(time.perf_counter() - t_start, 1)
        print(f"  compile seconds inside the legs: {compile_s:.1f} "
              f"({'warm' if cache_before else 'cold'} cache); wall "
              f"{summary['wall_seconds']}s", flush=True)


def main(argv=None) -> int:
    args = parse_args(argv)
    logging.basicConfig(
        level=logging.WARNING, stream=sys.stderr,
        format="%(asctime)s %(levelname).1s %(name)s: %(message)s")
    for name in _INFO_LOGGERS:
        logging.getLogger(name).setLevel(logging.INFO)
    summary: dict = {}
    checks = Checks()
    try:
        run(args, summary, checks)
    finally:
        # whatever happened, leave what was learned on disk — this does
        # not swallow the exception
        shutil.rmtree(args.data_dir, ignore_errors=True)
        failed = checks.failed()
        summary["checks"] = checks.items
        summary["ok"] = bool(checks.items) and not failed
        summary["claim"] = None
        os.makedirs(args.out, exist_ok=True)
        with open(os.path.join(args.out, "chip_smoke.json"), "w") as fh:
            json.dump(summary, fh, indent=1, default=str)
    if failed:
        print(f"\nFAILED: {len(failed)} of {len(checks.items)} checks",
              file=sys.stderr)
        for c in failed:
            print(f"  {c['name']}: {c['detail']}", file=sys.stderr)
        return 1
    if args.rehearsal:
        print(f"\nrehearsal: all {len(checks.items)} checks passed on the "
              f"CPU backend — no result", flush=True)
        return 0
    b = summary["backend"]
    print(json.dumps({"ok": True, "device": {
        "platform": b["platform"], "kind": b["device_kind"],
        "count": b["device_count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
