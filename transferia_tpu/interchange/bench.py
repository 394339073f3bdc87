"""Interchange shard-handoff benchmark: pivot vs IPC vs shm vs Flight.

Behind `trtpu flight bench`.  All paths move the SAME deterministic
sample batches from a producer to a consumer that materializes
ColumnBatches; what varies is the wire:

- `pivot`   the row baseline: unpivot to ChangeItems and re-pivot —
            what every handoff paid before the interchange plane;
- `ipc`     Arrow IPC stream bytes through an in-memory buffer
            (the arrow_ipc provider's file/fd path);
- `shm`     shared-memory segment handoff (write once, map back);
- `flight`  loopback Flight DoPut → DoGet over real gRPC.

Reported per path: rows/s, MB/s, speedup vs pivot — plus the zero-copy
buffer ratio observed on the interchange paths (telemetry.py), the
plane's honesty metric.
"""

from __future__ import annotations

import io
import time
from typing import Optional

from transferia_tpu.abstract.schema import TableID
from transferia_tpu.columnar.batch import ColumnBatch
from transferia_tpu.interchange.telemetry import TELEMETRY


def _mk_batches(rows: int, batch_rows: int, preset: str,
                dict_encode: bool = False):
    from transferia_tpu.providers.sample import make_batch

    tid = TableID("bench", "interchange")
    return [make_batch(preset, tid, start, min(batch_rows, rows - start), 7,
                       dict_encode=dict_encode)
            for start in range(0, rows, batch_rows)]


def _time(fn) -> float:
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def run_interchange_bench(rows: int = 200_000, batch_rows: int = 16_384,
                          preset: str = "iot",
                          with_flight: bool = True,
                          flight_uri: Optional[str] = None,
                          stream_counts: tuple = (1, 2, 4, 8)) -> dict:
    """Run all paths over identical batches; returns the report dict.

    With Flight enabled the bench also drives the multi-stream lane
    over the DICT-HEAVY shape (`stream_curve`): the same part put/got
    at each substream count in `stream_counts`, reporting rows/s and
    bytes-on-wire per point (the frontier), and ASSERTING in-run that
    each put ships every pool exactly once (pool-once per part, not
    per substream) and that the encoded wire genuinely shrinks
    (`encoded_wire_ratio` > 1).  The shm path runs through the region
    buffer pool; `region_copied_bytes` staying 0 is the zero-
    intermediate-copy proof of that path."""
    from transferia_tpu.interchange import ipc, shm
    from transferia_tpu.interchange.convert import arrow_to_batch

    batches = _mk_batches(rows, batch_rows, preset)
    n_rows = sum(b.n_rows for b in batches)
    n_bytes = sum(b.nbytes() for b in batches)

    # pivot baseline: the ChangeItem row round trip every pre-interchange
    # handoff paid (serialize rows out, pivot rows back in)
    def pivot_path():
        for b in batches:
            ColumnBatch.from_rows(b.to_rows())

    pivot_s = _time(pivot_path)

    TELEMETRY.reset()

    # Arrow IPC stream through a memory buffer (file/fd provider path)
    def ipc_path():
        buf = io.BytesIO()
        w = ipc.StreamWriter(buf)
        for b in batches:
            w.write(b)
        w.finish()
        buf.seek(0)
        for _ in ipc.iter_stream(buf):
            pass

    ipc_s = _time(ipc_path)

    # shared-memory segment handoff (decode → region → map, no
    # intermediate copy: region_copied_bytes must stay 0)
    def shm_path():
        h = shm.write_segment(batches)
        att = shm.attach(h)
        att.batches()
        att.close()
        shm.unlink_segment(h)

    shm_s = _time(shm_path)
    region_snap = TELEMETRY.snapshot()
    if region_snap["region_copied_bytes"]:
        raise AssertionError(
            "region path copied "
            f"{region_snap['region_copied_bytes']} bytes — the "
            "decode→region→socket path must be zero-copy")

    flight_s = None
    stream_curve: dict[str, dict] = {}
    if with_flight:
        from transferia_tpu.interchange.flight import (
            FlightShardClient,
            ShardFlightServer,
        )

        server = None
        try:
            if flight_uri is None:
                server = ShardFlightServer()
                flight_uri = server.location
            with FlightShardClient(flight_uri, allow_shm=False) as cli:
                def flight_path():
                    cli.put_part("bench.interchange/0", batches)
                    for _ in cli.get_part("bench.interchange/0"):
                        pass

                flight_s = _time(flight_path)
                cli.drop("bench.interchange/0")
                # snapshot the single-shape counters BEFORE the curve:
                # each curve point resets telemetry to isolate its own
                # pool-once / wire-bytes accounting
                snap = TELEMETRY.snapshot()
                stream_curve = _stream_curve(
                    cli, rows, batch_rows, preset, stream_counts)
        finally:
            if server is not None:
                server.close()
    if not with_flight:
        snap = TELEMETRY.snapshot()
    zc_total = snap["zero_copy_buffers"] + snap["copied_buffers"]

    def path_stats(seconds: Optional[float]):
        if seconds is None:
            return None
        return {
            "rows_per_sec": round(n_rows / seconds),
            "mb_per_sec": round(n_bytes / seconds / 1e6, 1),
            "speedup_vs_pivot": round(pivot_s / seconds, 2),
        }

    report = {
        "metric": "interchange_shard_handoff",
        "rows": n_rows,
        "bytes": n_bytes,
        "batch_rows": batch_rows,
        "paths": {
            "pivot": path_stats(pivot_s),
            "ipc": path_stats(ipc_s),
            "shm": path_stats(shm_s),
            "flight": path_stats(flight_s),
        },
        "zero_copy_buffers": snap["zero_copy_buffers"],
        "copied_buffers": snap["copied_buffers"],
        "zero_copy_ratio": round(
            snap["zero_copy_buffers"] / zc_total, 4) if zc_total else 0.0,
        "regions_sealed": snap["regions_sealed"],
        "region_pinned_bytes": snap["region_pinned_bytes"],
        "region_copied_bytes": snap["region_copied_bytes"],
    }
    if stream_curve:
        report["stream_curve"] = stream_curve
        base = stream_curve.get("1", {}).get("rows_per_sec")
        four = stream_curve.get("4", {}).get("rows_per_sec")
        if base and four:
            report["stream4_speedup"] = round(four / base, 2)
    best = max(s["rows_per_sec"] for k, s in report["paths"].items()
               if s is not None and k != "pivot")
    report["value"] = best
    report["unit"] = "rows/sec"
    return report


def _stream_curve(cli, rows: int, batch_rows: int, preset: str,
                  stream_counts) -> dict[str, dict]:
    """The multi-stream scaling curve over the DICT-HEAVY shape: one
    part put+got per substream count, each point reporting rows/s and
    the bytes the wire actually carried (the bytes-on-wire vs rows/s
    frontier).  Asserts the pool-once-per-part and encoded-wire-shrink
    contracts IN-RUN — a silently flat or pool-re-shipping wire would
    otherwise still produce a plausible-looking curve."""
    dict_batches = _mk_batches(rows, batch_rows, preset, dict_encode=True)
    n_rows = sum(b.n_rows for b in dict_batches)
    key = "bench.interchange/streams"
    # warmup put/get: pool interning, arrow wrapping memos, and the
    # stream-link probe all pay once — they must not be billed to the
    # first curve point (it would fake the scaling ratio)
    cli.put_part(key, dict_batches, streams=1)
    for _ in cli.get_part(key):
        pass
    cli.drop(key)
    curve: dict[str, dict] = {}
    pools_per_put: Optional[int] = None
    for n in stream_counts:
        n = max(1, min(int(n), len(dict_batches)))
        if str(n) in curve:
            continue
        TELEMETRY.reset()

        def one_put(n=n):
            cli.put_part(key, dict_batches, streams=n)
            for _ in cli.get_part(key):
                pass

        secs = _time(one_put)
        cli.drop(key)
        s = TELEMETRY.snapshot()
        shipped = s["pool_bytes_shipped"] + s["codes_bytes_shipped"]
        if pools_per_put is None:
            pools_per_put = s["pools_shipped"]
        # pool-once per PART: striping must not multiply pool ships
        if s["pools_shipped"] != pools_per_put:
            raise AssertionError(
                f"{n}-substream put shipped {s['pools_shipped']} pools "
                f"(expected {pools_per_put}) — pool-once-per-part "
                "contract broken")
        if shipped and s["flat_equiv_bytes"] <= shipped:
            raise AssertionError(
                "encoded wire did not shrink the dict-heavy shape "
                f"({s['flat_equiv_bytes']} flat vs {shipped} shipped)")
        curve[str(n)] = {
            "rows_per_sec": round(n_rows / secs),
            "wire_mb": round(s["bytes_out"] / 1e6, 2),
            "pools_shipped": s["pools_shipped"],
            "encoded_wire_ratio": round(
                s["flat_equiv_bytes"] / shipped, 2) if shipped else 0.0,
            "substreams": s["substreams_out"],
        }
    return curve


def format_report(report: dict) -> str:
    lines = [f"interchange handoff: {report['rows']} rows, "
             f"{report['bytes'] / 1e6:.1f} MB, "
             f"batch={report['batch_rows']}"]
    for name, s in report["paths"].items():
        if s is None:
            continue
        lines.append(
            f"  {name:>6}: {s['rows_per_sec']:>12,} rows/s  "
            f"{s['mb_per_sec']:>8.1f} MB/s  "
            f"{s['speedup_vs_pivot']:>6.2f}x vs pivot")
    lines.append(
        f"  zero-copy buffers: {report['zero_copy_buffers']} "
        f"({report['zero_copy_ratio']:.0%} of adoptions)")
    if report.get("regions_sealed"):
        lines.append(
            f"  regions: {report['regions_sealed']} sealed, "
            f"{report['region_copied_bytes']} bytes copied")
    for n, pt in (report.get("stream_curve") or {}).items():
        lines.append(
            f"  flight x{n}: {pt['rows_per_sec']:>12,} rows/s  "
            f"{pt['wire_mb']:>8.2f} MB wire  "
            f"pools={pt['pools_shipped']}  "
            f"ratio={pt['encoded_wire_ratio']:.1f}x")
    if "stream4_speedup" in report:
        lines.append(
            f"  4-substream speedup vs 1: {report['stream4_speedup']}x")
    return "\n".join(lines)
