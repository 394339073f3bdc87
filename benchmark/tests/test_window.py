"""Window arithmetic, with a consumer of the tests' own in the system's
place: whole passes; the lag of events that land in the drain is counted;
a stall inside the window lowers the rate and raises the tail."""

import http.client
import json
import os
import struct
import threading
import time
import types
import urllib.parse

from benchmark import broker as broker_mod
from benchmark import events as ev
from benchmark.traffic import kafka_backlog, kafka_openloop, snapshot_passes

CONFIG = {"topic": "events", "partitions": 2}
DDL = ("CREATE TABLE IF NOT EXISTS `events_clean` (`id` Int64, "
       "`user_email` String, `amount` Nullable(Float64), "
       "`ts` Nullable(DateTime64(6))) ENGINE = MergeTree() ORDER BY (`id`)")


def _varint(n):
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


class Consumer(threading.Thread):
    """Fetches over the broker's wire handler, lands over HTTP, one insert
    every `period` seconds; `stall` (start, seconds) holds it once."""

    def __init__(self, world, period, stall=None):
        super().__init__(daemon=True)
        self.w, self.period, self.stall = world, period, stall
        self.stop = threading.Event()
        self.mac = ev.Hmac(world.salt.encode())
        self.t0 = time.monotonic()

    def _post(self, query, body=b""):
        conn = http.client.HTTPConnection("127.0.0.1", self.w.ch.port)
        conn.request("POST", "/?" + urllib.parse.urlencode(
            {"query": query}), body=body)
        resp = conn.getresponse()
        data = resp.read()
        conn.close()
        assert resp.status == 200, data

    def _fetch(self, offsets):
        body = struct.pack("!iiiib", -1, 50, 1, 1 << 20, 0)
        body += struct.pack("!i", 1) + struct.pack("!h", 6) + b"events"
        body += struct.pack("!i", len(offsets))
        for p, o in enumerate(offsets):
            body += struct.pack("!iqi", p, o, 1 << 20)
        req = struct.pack("!hhi", 1, 4, 7) + struct.pack("!h", 1) + b"t" \
            + body
        r = broker_mod._Reader(self.w.broker.handle_request(req))
        r.take("!ii")
        rows = []
        for _ in range(r.take("!i")):
            r.string()
            for _ in range(r.take("!i")):
                p, _err, _high, _lso = r.take("!ihqq")
                r.take("!i")
                blob = r.buf[r.pos + 4:r.pos + 4 + r.take("!i")]
                r.pos += len(blob)
                pos = 0
                while pos < len(blob):
                    base, length = struct.unpack_from("!qi", blob, pos)
                    count = struct.unpack_from("!i", blob, pos + 57)[0]
                    rec = (length - 49) // count
                    for k in range(count):
                        if base + k >= offsets[p]:
                            at = pos + 61 + k * rec + 8
                            rows.append(json.loads(
                                blob[at:at + ev.VALUE_LEN]))
                            offsets[p] = base + k + 1
                    pos += 12 + length
        return rows

    def run(self):
        self._post(DDL)
        offsets = [0] * CONFIG["partitions"]
        while not self.stop.is_set():
            now = time.monotonic() - self.t0
            if self.stall and now >= self.stall[0]:
                time.sleep(self.stall[1])
                self.stall = None
            rows = self._fetch(offsets)
            if rows:
                out = bytearray()
                for d in rows:
                    email = self.mac.hexdigest(d["user_email"].encode())
                    out += struct.pack("<q", d["id"]) + _varint(len(email)) \
                        + email + b"\x00" + struct.pack("<d", d["amount"]) \
                        + b"\x00" + struct.pack("<q", d["ts"])
                self._post("INSERT INTO events_clean (`id`, `user_email`, "
                           "`amount`, `ts`) FORMAT RowBinary", bytes(out))
            time.sleep(self.period)


def _cell(**params):
    return {"expect_table": "events_clean",
            "params": {"users": 500, "zipf_s": 1.1, **params}}


def _open_loop(stall):
    w = kafka_openloop.World(_cell(rate=400, linger_ms=5, warm_waves=[3]),
                             CONFIG, 11, 1.5, "")
    c = Consumer(w, 0.1, stall)
    try:
        c.start()
        w.cmd_warm()
        w.cmd_open()
        c.t0 = time.monotonic()
        acc = w.cmd_window()
        acc.update(w.cmd_drain())
        cmp_ = w.cmd_verify()
    finally:
        c.stop.set()
        c.join(timeout=10)
        w.close()
    return acc, cmp_


def test_open_loop_counts_the_drain_and_a_stall_raises_the_tail():
    calm, cmp_calm = _open_loop(None)
    stalled, cmp_stalled = _open_loop((0.5, 1.0))
    for acc, cmp_ in ((calm, cmp_calm), (stalled, cmp_stalled)):
        assert acc["undrained"] == 0 and acc["events_never_landed"] == 0
        assert acc["events_unsent"] == 0
        assert abs(acc["events_due"] - 600) < 120
        assert all(v <= lim for v, lim in cmp_["numbers"].values())
        assert cmp_["attempted"] == acc["attempted"]
        assert acc["generator_late_p95_ms"] < 250   # other tests run beside
    # what was due near the close landed after it, and its lag is counted
    assert calm["landed_at_close"] < calm["attempted"]
    assert calm["lag_max_ms"] < 1000
    assert stalled["lag_p95_ms"] > calm["lag_p95_ms"] + 300
    assert stalled["lag_max_ms"] > 900


def _backlog(stall):
    w = kafka_backlog.World(
        _cell(backlog=4000, chunk_events=4096, warm_waves=[64]),
        CONFIG, 12, 1.5, "")
    c = Consumer(w, 0.1, stall)
    try:
        c.start()
        w.cmd_warm()
        w.cmd_open()
        c.t0 = time.monotonic()
        acc = w.cmd_window()
        acc.update(w.cmd_drain())
        cmp_ = w.cmd_verify()
    finally:
        c.stop.set()
        c.join(timeout=10)
        w.close()
    return acc, cmp_


def test_backlog_window_closes_on_an_insert_and_a_stall_lowers_the_rate():
    calm, cmp_calm = _backlog(None)
    stalled, _ = _backlog((0.5, 1.0))
    assert calm["window_s"] >= 1.5
    assert calm["undrained"] == 0
    assert all(v <= lim for v, lim in cmp_calm["numbers"].values())
    # the backlog is never empty by design: what no fetch carried is no
    # operation of the run
    assert 0 < cmp_calm["attempted"] < calm["attempted"] + 4000
    rate = kafka_backlog.end_to_end
    assert rate(stalled)["replication_rows_per_s"] \
        < 0.8 * rate(calm)["replication_rows_per_s"]


def test_a_snapshot_window_is_a_whole_number_of_passes(monkeypatch):
    import transferia_tpu.cli.main as cli

    calls, asked = [], []

    def activate(argv):
        calls.append(argv)
        time.sleep(0.2)
        return 0

    def world(cmd, **kw):
        asked.append((cmd, kw, len(calls)))
        return {"rows": 686, "tables": ["hits"], "standin_cost": {},
                "server_errors": []}

    monkeypatch.setattr(cli, "main", activate)
    ctx = types.SimpleNamespace(
        cell={"params": {"min_passes": 2}}, seconds=0.5,
        config={"table": {"rows": 1000}}, transfer_yaml="t.yaml",
        warm_yaml="w.yaml", world=world,
        window_open=lambda: asked.append(("open", {}, len(calls))),
        window_close=lambda: None,
        trace_start=lambda: None, trace_stop=lambda: None)
    acc = snapshot_passes.drive(ctx)
    assert len(acc["passes"]) == 3            # the third started at 0.4 s
    assert acc["window_s"] >= 0.6
    # warm-up is the first part file alone, then the transfer's own whole
    # pass; what they landed is taken out of the world before the window
    # opens and is in no rate
    assert [c[-1] for c in calls] == ["w.yaml"] + ["t.yaml"] * 4
    assert asked[:3] == [("pass_end", {"in_window": False}, 1),
                         ("pass_end", {"in_window": False}, 2),
                         ("open", {}, 2)]
    assert all(kw == {"in_window": True} for _c, kw, _n in asked[3:])
    assert 0.2 <= acc["warm_part_seconds"] < 0.4     # one call each
    assert 0.2 <= acc["warm_pass_seconds"] < 0.4
    for counted in (acc["warm_part_telemetry"], acc["warm_telemetry"],
                    acc["passes"][0]["compiled"]):
        assert set(counted) == set(snapshot_passes.COMPILE_COUNTERS)
    got = snapshot_passes.end_to_end(acc)
    assert got["snapshot_rows_per_s"] == 3000 / acc["window_s"]
    # the rate per layer where the median stands end to end: the same rows
    # over the passes' own seconds (a traced window also holds the
    # profiler's stop, which is no pass)
    assert acc["window_rows"] == 3000
    assert acc["pass_seconds_sum"] == sum(
        p["seconds"] for p in acc["passes"]) <= acc["window_s"]
    # the median pass: the middle one of three, whatever the slowest took
    took = sorted(p["seconds"] for p in acc["passes"])
    assert got["snapshot_pass_p50_s"] == took[1]
    acc["passes"][0]["seconds"] += 20.0
    assert snapshot_passes.end_to_end(acc)["snapshot_pass_p50_s"] \
        in took[1:]
    # a window shorter than a pass still holds `min_passes` of them
    ctx.seconds = 0.05
    assert len(snapshot_passes.drive(ctx)["passes"]) == 2
    # and one that `--seconds` would keep open closes at `max_passes`: a
    # cell that gives both the same number does the same work in every run
    for seconds, params, held in ((5.0, {"min_passes": 2, "max_passes": 4}, 4),
                                  (0.05, {"min_passes": 4, "max_passes": 4}, 4),
                                  (0.5, {"min_passes": 1, "max_passes": 4}, 3)):
        ctx.seconds, ctx.cell = seconds, {"params": params}
        acc = snapshot_passes.drive(ctx)
        assert len(acc["passes"]) == held
        assert snapshot_passes.end_to_end(acc)["snapshot_rows_per_s"] \
            == held * 1000 / acc["window_s"]


def test_a_fetch_response_is_filled_to_the_requests_bytes():
    log = broker_mod.PartitionLog()
    blobs = [broker_mod.encode_batch(b"x" * 100, 1, 5) for _ in range(10)]
    for b in blobs:
        log.append(b, 1)
    one = len(blobs[0])
    # whole batches up to max_bytes; the first however small the limit;
    # from the batch that holds the offset; nothing at or past the fence
    assert len(log.read(0, 1 << 62, 3 * one + 5)) == 3 * one
    assert len(log.read(4, 1 << 62, 1)) == one
    assert len(log.read(4, 6, 1 << 20)) == 2 * one
    assert log.read(6, 6, 1 << 20) == b""
    assert struct.unpack_from("!q", log.read(7, 1 << 62, one))[0] == 7


def test_span_time_is_divided_by_the_rows_the_spans_worked_on():
    from benchmark.readers import span_self_time

    spans = [("source_decode", 0, 0, 0, 2.0, 1.5, 0),
             ("sink", 0, 0, 0, 1.0, 0.5, 0),
             ("source_decode", 0, 0, 0, 9.0, 9.0, -1)]   # still open
    data = {"spans": spans, "rows": 1000,
            "account": {"consumed_in_window": 4000}}
    read = span_self_time.read
    assert read({"spans": ["source_decode"]}, data) == 1.5 / 1e-3
    assert read({"spans": ["source_decode"],
                 "per": "consumed_in_window"}, data) == 1.5 / 4e-3
    assert read({"spans": ["pivot"]}, data) is None
    assert read({"spans": ["sink"], "per": "nothing"}, data) is None


def test_span_time_can_be_held_to_the_spans_args():
    from benchmark.readers import span_self_time

    # one name, three callers: the ClickHouse POST, the asynchronizer's
    # wrapper around it (no args), the arrow_ipc write
    spans = [("sink_push", 0, 0, 0, 1.0, 0.75, 2,
              {"direction": "clickhouse_http", "bytes": 10}),
             ("sink_push", 0, 0, 0, 1.25, 0.25, 1, None),
             ("sink_push", 0, 0, 0, 0.5, 0.5, 1, {"direction": "ipc"})]
    data = {"spans": spans, "rows": 1000, "account": {}}
    read = span_self_time.read
    assert read({"spans": ["sink_push"]}, data) == 1.5 / 1e-3
    where = {"direction": "clickhouse_http"}
    assert read({"spans": ["sink_push"], "where": where}, data) == 0.75 / 1e-3
    assert read({"spans": ["sink_push"],
                 "where": {"direction": "flight"}}, data) is None
    for family in ("snapshot", "pii", "catchup"):
        with open(os.path.join(
                os.path.dirname(span_self_time.__file__), os.pardir,
                "metrics", f"sink_wire_s_per_mrow.{family}.json")) as fh:
            assert json.load(fh)["params"]["where"] == where
