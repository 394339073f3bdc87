"""The generators: the same seed gives the same files and messages, another
seed gives others, and the open loop's schedule is the seed's alone."""

import hashlib
import os
import struct

import google_crc32c
import numpy as np
import pyarrow.parquet as pq

from benchmark import broker, datagen
from benchmark import events as ev
from benchmark.traffic import kafka_openloop

BIG = 3_000_000_019   # the driver's seeds do not fit 32 signed bits
COLUMNS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs", "hits-columns.json")


def _digest(path):
    t = pq.read_table(path)
    h = hashlib.sha256()
    for name in ("WatchID", "RegionID", "URL", "SearchPhrase", "EventTime"):
        h.update(repr(t[name].to_pylist()[:50]).encode())
    return h.hexdigest(), t


def test_parquet_parts_same_seed_same_rows_other_seed_other(tmp_path):
    a = datagen.generate(str(tmp_path / "a"), BIG, 5000, 2000, 512, 1, COLUMNS)
    b = datagen.generate(str(tmp_path / "b"), BIG, 5000, 2000, 512, 2, COLUMNS)
    c = datagen.generate(str(tmp_path / "c"), BIG + 1, 5000, 2000, 512, 1,
                         COLUMNS)
    assert [os.path.basename(p) for p in a] == [
        "part-00000.parquet", "part-00001.parquet", "part-00002.parquet"]
    for pa_, pb, pc_ in zip(a, b, c):
        da, ta = _digest(pa_)
        assert da == _digest(pb)[0]        # serial or in a pool: the same
        assert da != _digest(pc_)[0]
        assert ta.num_columns == 105
    ids = np.concatenate([pq.read_table(p)["WatchID"].to_numpy() for p in a])
    assert len(ids) == 5000 == len(np.unique(ids))
    assert pq.ParquetFile(a[0]).metadata.num_row_groups == 4


def test_events_are_the_seeds_alone():
    pop = ev.Population(1000, 1.1)
    a = ev.Events(BIG, 1, 0, 100, 300, pop, 4)
    b = ev.Events(BIG, 1, 0, 100, 300, pop, 4)
    c = ev.Events(BIG + 1, 1, 0, 100, 300, pop, 4)
    assert (a.values() == b.values()).all()
    assert not (a.users == c.users).all()
    msg = bytes(a.values()[7])
    import json

    doc = json.loads(msg)
    assert doc["id"] == ev.ID0 + 107 and doc["ts"] == ev.TS0 + 107
    assert doc["amount"] == a.eighths[7] / 8.0
    assert doc["user_email"].encode() == ev.email_of(a.users[7])
    assert len(msg) == ev.VALUE_LEN
    # a hot user is a hot partition
    assert len(set(a.partitions[a.users == a.users[0]])) == 1


def test_record_batches_read_back_with_an_independent_reading():
    pop = ev.Population(50, 1.1)
    e = ev.Events(BIG, 1, 0, 0, 200, pop, 2)
    seen = []
    for p, _g, idx, rec in ev.batches(e):
        assert len(idx) <= broker.RECORDS_PER_BATCH
        blob = broker.encode_batch(rec.tobytes(), len(idx), 1234)
        base, length, _epoch, magic, crc = struct.unpack_from("!qiibI", blob)
        assert magic == 2 and length == len(blob) - 12
        assert crc == google_crc32c.value(blob[21:])
        count = struct.unpack_from("!i", blob, 57)[0]
        assert count == len(idx)
        pos = 61
        for k in range(count):
            # length (2-byte varint), attributes, tsDelta, offsetDelta, key
            assert blob[pos + 2] == 0 and blob[pos + 3] == 0
            assert blob[pos + 4] == 2 * k and blob[pos + 5] == 1
            value = blob[pos + 8:pos + 8 + ev.VALUE_LEN]
            assert value == bytes(e.values()[idx[k]])
            assert (e.partitions[idx[k]] == p)
            pos += rec.shape[1]
        assert pos == len(blob)
        seen.extend(idx.tolist())
    assert sorted(seen) == list(range(200))


def test_open_loop_due_times_do_not_depend_on_the_consumer():
    a = kafka_openloop.due_times(BIG, 1, 500.0, 4.0)
    b = kafka_openloop.due_times(BIG, 1, 500.0, 4.0)
    c = kafka_openloop.due_times(BIG + 1, 1, 500.0, 4.0)
    assert (a == b).all() and len(a) != len(c) or not (a[:50] == c[:50]).all()
    assert (np.diff(a) > 0).all() and a[-1] < 4.0
    assert abs(len(a) - 2000) < 200          # Poisson at the cell's rate
