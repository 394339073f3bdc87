"""Regression bound on the kafka fan-in push path.

Round-4 review found the 64-partition Confluent-SR fan-in collapsing
under its own bench: one sink push of 200 rows took 56 seconds (a jit
recompile per distinct batch shape + one wire round-trip per partition
per poll).  This pins the fixed behavior end-to-end:

  - all rows land (at-least-once, sequencer-ordered commits)
  - p99 sink push latency stays bounded — the stall class hid inside a
    green run because only the average was visible
  - the multi-partition fetch path (KafkaClient.fetch_multi) drains a
    many-partition topic in bounded wall time

Reference behavior: pkg/providers/kafka/source.go:104-195 (franz-go
multi-partition polls + sequencer).
"""

import json
import threading
import time

import pytest

from tests.recipes.fake_clickhouse import FakeCH
from tests.recipes.fake_kafka import FakeKafka
from tests.recipes.fake_sr import FakeSchemaRegistry
from transferia_tpu.coordinator import MemoryCoordinator
from transferia_tpu.middlewares.sync import Measurer
from transferia_tpu.models import Transfer, TransferType
from transferia_tpu.providers.clickhouse import CHTargetParams
from transferia_tpu.providers.kafka.client import KafkaClient, Record
from transferia_tpu.providers.kafka.protocol import enc_varint as _zz
from transferia_tpu.providers.kafka.provider import KafkaSourceParams
from transferia_tpu.runtime.local import run_replication

N_PARTITIONS = 16
MSGS_PER_PARTITION = 150


def test_fanin_p99_push_latency_bounded():
    schema_json = json.dumps({
        "type": "record", "name": "Hit", "fields": [
            {"name": "id", "type": "long"},
            {"name": "url", "type": "string"},
            {"name": "region", "type": "int"},
        ]})
    sr = FakeSchemaRegistry().start()
    srv = FakeKafka(n_partitions=N_PARTITIONS).start()
    ch = FakeCH().start()
    try:
        import urllib.request

        req = urllib.request.Request(
            sr.url + "/subjects/hits-value/versions",
            data=json.dumps({"schema": schema_json}).encode(),
            headers={"Content-Type":
                     "application/vnd.schemaregistry.v1+json"})
        sid = json.loads(
            urllib.request.urlopen(req, timeout=10).read())["id"]
        seed = KafkaClient([f"127.0.0.1:{srv.port}"])
        srv.create_topic("hits")
        header = b"\x00" + sid.to_bytes(4, "big")
        for p in range(N_PARTITIONS):
            recs = []
            for i in range(MSGS_PER_PARTITION):
                rid = p * MSGS_PER_PARTITION + i
                url = f"https://e.test/{rid % 97}".encode()
                recs.append(Record(
                    key=b"",
                    value=header + _zz(rid) + _zz(len(url)) + url
                    + _zz(rid % 500)))
            seed.produce("hits", p, recs)
        seed.close()

        t = Transfer(
            id="fanin-lat", type=TransferType.INCREMENT_ONLY,
            src=KafkaSourceParams(
                brokers=[f"127.0.0.1:{srv.port}"], topic="hits",
                parallelism=4,
                parser={"confluent_schema_registry": {
                    "registry_url": sr.url, "table": "hits"}},
            ),
            dst=CHTargetParams(host="127.0.0.1", port=ch.port,
                               bufferer=None),
        )
        expected = N_PARTITIONS * MSGS_PER_PARTITION
        cp = MemoryCoordinator()
        stop = threading.Event()
        th = threading.Thread(target=run_replication, args=(t, cp),
                              kwargs={"stop_event": stop, "backoff": 0.2},
                              daemon=True)
        t0 = time.monotonic()
        th.start()

        def ch_rows():
            return ch.total_rows()

        deadline = time.monotonic() + 90
        while ch_rows() < expected and time.monotonic() < deadline:
            time.sleep(0.05)
        drain_seconds = time.monotonic() - t0
        # read BEFORE stopping: instances are weakly registered and die
        # with the sink chain when replication shuts down
        p99 = Measurer.global_quantile(0.99)
        stop.set()
        th.join(timeout=15)

        assert ch_rows() == expected, (
            f"row loss: {ch_rows()} != {expected}")
        # generous for a 1-core CI box, still far below the 56s stall
        # class this guards against; global = across every pipeline
        assert p99 > 0.0, "no pushes observed"
        assert p99 < 5.0, f"p99 sink push latency {p99:.1f}s"
        assert drain_seconds < 60, f"drain took {drain_seconds:.0f}s"
    finally:
        sr.stop()
        srv.stop()
        ch.stop()
