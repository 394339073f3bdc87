"""The Debezium emitter's native renderer (native/hostops.cpp
`debezium_render_size` / `debezium_render_write`) against the Python
renderer it stands in for: the same keys and values byte for byte on the
nine TPC-C table shapes, on text that needs every escape json.dumps knows,
on columns that come to it as Python-rendered fragments; the batches it
leaves to Python; four threads at once; the counter and the span's arg."""

import json
import pathlib
import threading
import time

import numpy as np
import pyarrow as pa
import pytest

import transferia_tpu.providers.mysql.provider  # noqa: F401  (type rules)
from transferia_tpu import native
from transferia_tpu.abstract.kinds import KIND_CODES, Kind
from transferia_tpu.abstract.schema import (
    CanonicalType,
    ColSchema,
    TableID,
    TableSchema,
)
from transferia_tpu.columnar.batch import Column, ColumnBatch
from transferia_tpu.debezium.emitter import DebeziumEmitter
from transferia_tpu.serializers.formats import MessageBlock
from transferia_tpu.stats import trace
from transferia_tpu.typesystem.rules import map_source_type

TPCC = json.loads((pathlib.Path(__file__).parents[2] / "benchmark" /
                   "configs" / "tpcc-columns.json").read_text())["tables"]
NOW = 1_753_000_000.0
TS = 1_704_085_686_000_000       # 2024-01-01 05:08:06 UTC, microseconds


@pytest.fixture(autouse=True)
def one_clock(monkeypatch):
    # the envelope's ts_ms is the wall clock's: one reading for both sides
    monkeypatch.setattr(time, "time", lambda: NOW)


def emitter(**cfg):
    cfg.setdefault("topic_prefix", "tpcc")
    cfg.setdefault("connector", "mysql")
    cfg.setdefault("source_db_type", "tpcc")
    return DebeziumEmitter(**cfg)


def columnar(em, batch, snapshot):
    """(pairs, path) of the columnar renderers, the native path's block
    cut into its pairs; None where neither takes the batch."""
    out = em._emit_columnar(batch, snapshot)
    if out is None or out[1] != "native":
        return out
    assert isinstance(out[0], MessageBlock)
    return out[0].pairs(), out[1]


def taken(batch, snapshot=True, **cfg):
    """(pairs, path) of the columnar renderers, the native one first."""
    assert native.lib() is not None
    out = columnar(emitter(**cfg), batch, snapshot)
    assert out is not None
    return out


def python_pairs(batch, snapshot=True, **cfg):
    """The Python renderer's pairs, through the repo's own switch (`lib()`
    caches the loaded library, so both are needed)."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(native, "_lib", None)
        mp.setenv("TRANSFERIA_TPU_NO_NATIVE", "1")
        assert native.lib() is None
        pairs, path = columnar(emitter(**cfg), batch, snapshot)
    assert path == "fast"
    return pairs


def same(batch, snapshot=True, **cfg):
    pairs, path = taken(batch, snapshot, **cfg)
    assert path == "native"
    want = python_pairs(batch, snapshot, **cfg)
    assert len(pairs) == len(want) == batch.n_rows
    for i, (got, exp) in enumerate(zip(pairs, want)):
        assert got == exp, f"row {i}"
    assert all(type(k) in (bytes, type(None)) and type(v) is bytes
               for k, v in pairs)
    return pairs


# -- the nine TPC-C table shapes ---------------------------------------------

def _values(col: dict, n: int, rng) -> pa.Array:
    kind, mysql = col["kind"], col["mysql"]
    nulls = rng.random(n) < 0.3 if "null" in col else np.zeros(n, bool)
    if kind == "int":
        top = {"tinyint": 127, "smallint": 32767, "int": 2 ** 31 - 1}[mysql]
        arrow = {"tinyint": pa.int8(), "smallint": pa.int16(),
                 "int": pa.int32()}[mysql]
        return pa.array(rng.integers(-top - 1, top, n, endpoint=True),
                        arrow, mask=nulls)
    if kind == "datetime":
        return pa.array(TS + rng.integers(0, 10 ** 12, n),
                        pa.timestamp("us"), mask=nulls)
    if kind == "dec":
        scale = int(mysql.split(",")[1].rstrip(")"))
        return pa.array([f"{v / 10 ** scale:.{scale}f}"
                         for v in rng.integers(-9999, 99999, n)])
    width = int(mysql.split("(")[1].rstrip(")"))
    alphabet = np.array(list("abcXYZ 0189-'%{}:,\"\\"))
    return pa.array(["".join(rng.choice(alphabet, rng.integers(0, width,
                                                               endpoint=True)))
                     for _ in range(n)])


def tpcc_batch(table: dict, n: int = 64, seed: int = 7) -> ColumnBatch:
    rng = np.random.default_rng(seed)
    cols, arrays = [], {}
    for col in table["columns"]:
        cols.append(ColSchema(
            name=col["name"],
            data_type=map_source_type("mysql", col["mysql"].split("(")[0]),
            primary_key=col["name"] in table["key"],
            required=col["name"] in table["key"],
            original_type=f"mysql:{col['mysql']}"))
        arrays[col["name"]] = _values(col, n, rng)
    return ColumnBatch.from_arrow(
        pa.record_batch(arrays), TableID("tpcc", table["name"]),
        TableSchema(cols))


def test_the_shapes_are_the_specifications():
    assert sorted(len(t["key"]) for t in TPCC) == [0, 1, 1, 2, 2, 3, 3, 3, 4]
    assert sum(len(t["columns"]) for t in TPCC) == 92


@pytest.mark.parametrize("snapshot", [True, False])
@pytest.mark.parametrize("include_schema", [True, False])
@pytest.mark.parametrize("table", TPCC, ids=[t["name"] for t in TPCC])
def test_tpcc_shapes_render_the_python_bytes(table, include_schema,
                                             snapshot):
    batch = tpcc_batch(table)
    pairs = same(batch, snapshot, include_schema=include_schema)
    doc = json.loads(pairs[0][1])
    payload = doc["payload"] if include_schema else doc
    assert payload["op"] == ("r" if snapshot else "c")
    assert list(payload["after"]) == [c["name"] for c in table["columns"]]
    if table["key"]:
        key = json.loads(pairs[0][0])
        assert list(key["payload"] if include_schema else key) == \
            sorted(table["key"], key=list(payload["after"]).index)
    else:
        assert {k for k, _ in pairs} == {None}


_BLOCK_CASES = {
    "customer": (lambda: tpcc_batch(TPCC[2], n=200), True),
    "stock": (lambda: tpcc_batch(TPCC[3], n=200), False),
    "text": (lambda: text_batch(ADVERSARIAL), False),
    "text_keyless": (lambda: text_batch(ADVERSARIAL, keyed=False), False),
}


@pytest.mark.parametrize("case", list(_BLOCK_CASES))
def test_a_batch_renders_into_one_block_the_pairs_are_cut_from(case):
    # the values, and the keys, end to end in one buffer each - what the
    # Kafka sink frames as they are, and emit_batch cuts
    make, include_schema = _BLOCK_CASES[case]
    batch = make()
    pairs = same(batch, include_schema=include_schema)
    em = emitter(include_schema=include_schema)
    b = em.emit_block(batch, snapshot=True)
    assert isinstance(b, MessageBlock) and b.n == batch.n_rows
    assert b.values == b"".join(v for _, v in pairs)
    assert b.value_offsets.dtype == np.int64
    assert b.value_offsets.tolist() == [0, *np.cumsum(
        [len(v) for _, v in pairs]).tolist()]
    assert b.key_null is None and b.value_null is None
    if not batch.schema.key_columns():
        assert b.keys is None and b.key_offsets is None
    else:
        assert b.keys == b"".join(k for k, _ in pairs)
    assert b.pairs() == pairs
    assert em.emit_batch(batch, snapshot=True) == pairs


# -- text --------------------------------------------------------------------

ADVERSARIAL = [
    "", '"', "\\", '\\"', "%", "%s %d %%", "\x7f",
    # two, three and four bytes of UTF-8, the first and last of each width
    "\u00e9", "\u00df\u00fc\u00f6", "\u0080\u07ff", "\u20ac",
    "\u0800\ud7ff\ue000\ufffd\uffff", "\ud55c\uad6d\uc5b4",
    "\U0001f600", "\U00010000\U0010ffff",
    "".join(map(chr, range(0x20))), "tab\there", "a\nb\rc\bd\fe",
    # an escape at each place of the eight-byte stride
    "x" * 7 + '"', "x" * 8 + "\\", "x" * 9 + "\n" + "y" * 20,
    "\u00e9" * 300, "plain ascii, nothing to escape: {}[]:,'",
    ("0123456789abcdef" * 4096)[:65536], ("ab\"c\u20ac" * 20000)[:65536],
    None, "after a null",
]


def text_batch(values, ctype=CanonicalType.UTF8, keyed=True):
    schema = TableSchema([
        ColSchema("id", CanonicalType.INT64, primary_key=keyed,
                  required=True),
        ColSchema("v", ctype),
    ])
    cols = {
        "id": Column.from_pylist("id", CanonicalType.INT64,
                                 list(range(len(values)))),
        "v": Column.from_pylist("v", ctype, values),
    }
    return ColumnBatch(TableID("db", "t"), schema, cols)


@pytest.mark.parametrize("ctype", [CanonicalType.UTF8,
                                   CanonicalType.DECIMAL])
def test_adversarial_text_is_quoted_as_json_dumps_quotes_it(ctype):
    batch = text_batch(ADVERSARIAL, ctype)
    assert batch.columns["v"].validity is not None
    pairs = same(batch, include_schema=False)
    got = [json.loads(v)["after"]["v"] for _, v in pairs]
    assert got == ADVERSARIAL
    assert all(v.isascii() for _, v in pairs)


def test_a_text_key_is_quoted_too():
    schema = TableSchema([
        ColSchema("k", CanonicalType.UTF8, primary_key=True, required=True),
        ColSchema("n", CanonicalType.INT32),
    ])
    keys = [v for v in ADVERSARIAL if v is not None]
    cols = {"k": Column.from_pylist("k", CanonicalType.UTF8, keys),
            "n": Column.from_pylist("n", CanonicalType.INT32,
                                    [None] + list(range(1, len(keys))))}
    pairs = same(ColumnBatch(TableID("db", "t"), schema, cols))
    assert [json.loads(k)["payload"]["k"] for k, _ in pairs] == keys


def test_a_null_under_a_value_reads_null():
    # validity, not the bytes, says what is null: a null cell may hold any
    col = Column.from_pylist("v", CanonicalType.UTF8, ["kept", "lost", "x"])
    col.validity = np.array([True, False, True])
    batch = text_batch(["a", "b", "c"])
    batch.columns["v"] = col
    pairs = same(batch, include_schema=False)
    assert [json.loads(v)["after"]["v"] for _, v in pairs] == \
        ["kept", None, "x"]


@pytest.mark.parametrize("bad", [
    b"\x80", b"\xc0\xaf", b"\xc1\xbf", b"\xc3", b"\xe2\x82", b"\xe0\x9f\xbf",
    b"\xed\xa0\x80", b"\xf0\x8f\xbf\xbf", b"\xf4\x90\x80\x80", b"\xf5\x80",
    b"ok\xffok", b"\xf0\x9f\x98",
])
def test_bytes_that_are_not_utf8_are_pythons_to_read(bad):
    col = Column.from_pylist("v", CanonicalType.UTF8, ["fine", "x", None])
    raw = b"fine" + bad
    col.data = np.frombuffer(raw, dtype=np.uint8).copy()
    col.offsets = np.array([0, 4, len(raw), len(raw)], dtype=np.int32)
    batch = text_batch(["a", "b", None])
    batch.columns["v"] = col
    before = trace.TELEMETRY.snapshot()
    pairs, path = taken(batch)
    assert path == "fast"
    assert pairs == python_pairs(batch)
    emitter().emit_batch(batch, snapshot=True)
    after = trace.TELEMETRY.snapshot()
    assert after["debezium_rows_native"] == before["debezium_rows_native"]
    assert after["debezium_rows_fast"] - before["debezium_rows_fast"] == 3


def test_random_bytes_are_utf8_exactly_where_pythons_decoder_says():
    rng = np.random.default_rng(5)
    alphabet = np.frombuffer(
        b'ab"\\\n\x00\x1f\x7f\x80\xbf\xc2\xc3\xe0\xa0\xed\x9f\xef\xf0\x90\xf4'
        b'\x8f\xf5\xff ', dtype=np.uint8)
    em = emitter(include_schema=False)
    batch = text_batch(["a", "b"])
    well_formed = 0
    for _ in range(2000):
        raw = bytes(rng.choice(alphabet, rng.integers(0, 12)))
        try:
            text = raw.decode("utf-8")
        except UnicodeDecodeError:
            text = None
        batch.columns["v"] = Column(
            "v", CanonicalType.UTF8,
            np.frombuffer(raw + b"tail", dtype=np.uint8),
            np.array([0, len(raw), len(raw) + 4], dtype=np.int32))
        pairs, path = columnar(em, batch, True)
        assert (path == "native") == (text is not None), raw
        if text is not None:
            well_formed += 1
            assert json.loads(pairs[0][1])["after"]["v"] == text
            assert pairs[0][1].count(json.dumps(text).encode()) == 1
    assert 100 < well_formed < 1900


# -- integers ----------------------------------------------------------------

@pytest.mark.parametrize("ctype,dtype,values", [
    (CanonicalType.INT64, np.int64,
     [0, -1, 9, 10, -10, 99, 100, 12345, -(2 ** 63), 2 ** 63 - 1, None]),
    (CanonicalType.UINT64, np.uint64,
     [0, 9, 10, 2 ** 63, 2 ** 64 - 1, 10 ** 19, None]),
    (CanonicalType.INT8, np.int8, [-128, 127, 0, None]),
    (CanonicalType.UINT16, np.uint16, [65535, 0, None]),
    (CanonicalType.DATE, np.int32, [0, -719162, 19723, None]),
    (CanonicalType.DATETIME, np.int64, [0, 1_704_085_686, -1, None]),
    (CanonicalType.TIMESTAMP, np.int64, [0, TS, -TS, None]),
])
def test_integers_are_their_digits(ctype, dtype, values):
    data = np.array([v or 0 for v in values], dtype=dtype)
    valid = np.array([v is not None for v in values])
    batch = text_batch(["a"] * len(values))
    batch.schema = TableSchema(list(batch.schema)[:1] + [ColSchema("v", ctype)])
    batch.columns["v"] = Column("v", ctype, data, validity=valid)
    same(batch, include_schema=False)


def test_mysql_datetime_is_milliseconds_and_datetime6_is_not():
    for mysql, want in (("datetime", TS // 1000), ("datetime(3)", TS // 1000),
                        ("datetime(6)", TS)):
        batch = text_batch(["a", "b"])
        batch.schema = TableSchema(list(batch.schema)[:1] + [ColSchema(
            "v", CanonicalType.TIMESTAMP, original_type=f"mysql:{mysql}")])
        batch.columns["v"] = Column("v", CanonicalType.TIMESTAMP,
                                    np.array([TS, -1], dtype=np.int64))
        pairs = same(batch, include_schema=False)
        assert json.loads(pairs[0][1])["after"]["v"] == want


# -- columns that come as Python's fragments ---------------------------------

def fragment_batch(n=40, nan=False):
    rng = np.random.default_rng(11)
    floats = rng.normal(size=n) * 10.0 ** rng.integers(-8, 12, n)
    if nan:
        floats[n // 2] = np.nan
    schema = TableSchema([
        ColSchema("id", CanonicalType.INT32, primary_key=True,
                  required=True),
        ColSchema("f", CanonicalType.DOUBLE),
        ColSchema("g", CanonicalType.FLOAT),
        ColSchema("b", CanonicalType.BOOLEAN),
        ColSchema("raw", CanonicalType.STRING),
        ColSchema("e", CanonicalType.UTF8,
                  original_type="mysql:enum('a','b')"),
        ColSchema("big", CanonicalType.UINT64,
                  original_type="mysql:bigint(20) unsigned"),
        ColSchema("y", CanonicalType.INT32, original_type="mysql:year"),
        ColSchema("tags", CanonicalType.ANY, original_type="pg:text[]"),
        ColSchema("name", CanonicalType.UTF8),
    ])
    some = lambda vals: [None if i % 7 == 3 else v  # noqa: E731
                         for i, v in enumerate(vals)]
    cols = {
        "id": Column.from_pylist("id", CanonicalType.INT32, list(range(n))),
        "f": Column("f", CanonicalType.DOUBLE, floats,
                    validity=np.arange(n) % 5 != 1),
        "g": Column("g", CanonicalType.FLOAT, floats.astype(np.float32)),
        "b": Column.from_pylist("b", CanonicalType.BOOLEAN,
                                some([i % 2 == 0 for i in range(n)])),
        "raw": Column.from_pylist("raw", CanonicalType.STRING,
                                  some([bytes(range(i)) for i in range(n)])),
        "e": Column.from_pylist("e", CanonicalType.UTF8,
                                some(["ab"[i % 2] for i in range(n)])),
        "big": Column.from_pylist("big", CanonicalType.UINT64,
                                  some([2 ** 64 - 1 - i for i in range(n)])),
        "y": Column.from_pylist("y", CanonicalType.INT32,
                                some([1990 + i for i in range(n)])),
        "tags": Column.from_pylist("tags", CanonicalType.ANY,
                                   some([["a", 'b"'], []] * (n // 2))),
        "name": Column.from_pylist("name", CanonicalType.UTF8,
                                   some([f"n{i}é" for i in range(n)])),
    }
    return ColumnBatch(TableID("pub", "t"), schema, cols)


@pytest.mark.parametrize("include_schema", [True, False])
def test_floats_booleans_bytes_and_slow_types_are_copied(include_schema):
    batch = fragment_batch()
    em = emitter()
    assert [em._native_column(batch.columns[cs.name], cs) is not None
            for cs in batch.schema] == [True] + [False] * 8 + [True]
    pairs = same(batch, snapshot=False, include_schema=include_schema)
    slow = [p for it in batch.to_rows() for p in emitter(
        include_schema=include_schema).emit_item(it, False)]
    assert pairs == slow


def test_a_lazy_dictionary_column_stays_lazy():
    batch = fragment_batch()
    arr = pa.array(["x", "y", None, "x"] * 10).dictionary_encode()
    cs = ColSchema("name", CanonicalType.UTF8)
    rb = pa.record_batch({"name": arr})
    lazy = ColumnBatch.from_arrow(rb, TableID("pub", "t"),
                                  TableSchema([cs])).columns["name"]
    if not lazy.is_lazy_dict:
        pytest.skip("from_arrow flattens dictionaries here")
    batch.columns["name"] = lazy
    before = trace.TELEMETRY.snapshot()["dict_flat_materializations"]
    same(batch, snapshot=False)
    assert lazy.is_lazy_dict
    assert trace.TELEMETRY.snapshot()["dict_flat_materializations"] == before


# -- source metadata row by row: the Python renderer's ------------------------

@pytest.mark.parametrize("have", ["lsns", "commit_times", "txn_ids", "all"])
def test_a_cdc_batch_of_inserts_is_the_python_renderers(have):
    batch = tpcc_batch(TPCC[2], n=6)
    if have in ("lsns", "all"):
        batch.lsns = np.array([0, 1, 2 ** 40, 7, 0, 2 ** 62], dtype=np.int64)
    if have in ("commit_times", "all"):
        batch.commit_times = np.array(
            [0, 1, 999_999, 1_000_000, TS * 1000, TS * 1000 + 1],
            dtype=np.int64)
    if have in ("txn_ids", "all"):
        batch.txn_ids = ["", "tx-1", 'q"\\', "é", None, "5"]
    batch.kinds = np.full(6, KIND_CODES[Kind.INSERT], dtype=np.int8)
    before = trace.TELEMETRY.snapshot()
    pairs = emitter().emit_batch(batch, snapshot=False)
    after = trace.TELEMETRY.snapshot()
    assert after["debezium_rows_fast"] - before["debezium_rows_fast"] == 6
    assert after["debezium_rows_native"] == before["debezium_rows_native"]
    assert taken(batch, snapshot=False) == (pairs, "fast")
    src = [json.loads(v)["payload"]["source"] for _, v in pairs]
    if have in ("lsns", "all"):
        assert [s["lsn"] for s in src] == [None, 1, 2 ** 40, 7, None, 2 ** 62]
    if have in ("commit_times", "all"):
        assert [s["ts_ms"] for s in src][:4] == [int(NOW * 1000), 0, 0, 1]
    if have in ("txn_ids", "all"):
        assert [s["txId"] for s in src] == [None, "tx-1", 'q"\\', "é", None,
                                            "5"]


# -- what the native renderer leaves alone -----------------------------------

class _Packer:
    def pack(self, topic, schema, payload):
        return json.dumps(payload, default=str).encode()


def _nan(batch):
    return fragment_batch(nan=True)


def _update(batch):
    batch.kinds = np.full(batch.n_rows, KIND_CODES[Kind.INSERT],
                          dtype=np.int8)
    batch.kinds[3] = KIND_CODES[Kind.UPDATE]
    return batch


@pytest.mark.parametrize("case", ["nan", "update", "packer"])
def test_out_of_the_envelope_goes_row_by_row_and_counts_no_native_row(case):
    batch = fragment_batch()
    em = emitter()
    if case == "nan":
        batch = fragment_batch(nan=True)
    elif case == "update":
        batch = _update(batch)
    else:
        em.key_packer = em.value_packer = _Packer()
    assert em._emit_columnar(batch, False) is None
    before = trace.TELEMETRY.snapshot()
    pairs = em.emit_batch(batch)
    after = trace.TELEMETRY.snapshot()
    assert len(pairs) == batch.n_rows
    assert after["debezium_rows"] - before["debezium_rows"] == batch.n_rows
    assert after["debezium_rows_fast"] == before["debezium_rows_fast"]
    assert after["debezium_rows_native"] == before["debezium_rows_native"]


def test_switched_off_the_python_renderer_takes_the_batch():
    batch = tpcc_batch(TPCC[0])
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(native, "_lib", None)
        mp.setenv("TRANSFERIA_TPU_NO_NATIVE", "1")
        before = trace.TELEMETRY.snapshot()
        emitter().emit_batch(batch, snapshot=True)
        after = trace.TELEMETRY.snapshot()
    assert after["debezium_rows_native"] == before["debezium_rows_native"]
    assert after["debezium_rows_fast"] - before["debezium_rows_fast"] == 64


def test_a_text_column_without_offsets_is_not_read_natively():
    # the schema says UTF8, the column holds fixed-width integers: no
    # address of offsets to hand over, so Python's renderer gets the
    # column, which raises as it did before there was a native one
    batch = text_batch(["abc", "def"])
    batch.columns["v"] = Column("v", CanonicalType.INT64,
                                np.array([1, 2], dtype=np.int64))
    assert emitter()._native_column(batch.columns["v"],
                                    batch.schema.find("v")) is None
    with pytest.raises(TypeError):
        emitter()._emit_columnar(batch, True)


def test_offsets_that_do_not_fit_are_an_error_not_a_read():
    batch = text_batch(["abc", "def"])
    batch.columns["v"].offsets = np.array([0, 3, 60], dtype=np.int32)
    with pytest.raises(ValueError, match="offsets"):
        emitter()._emit_columnar(batch, True)
    batch.columns["v"].offsets = np.array([0, 5, 3], dtype=np.int32)
    with pytest.raises(ValueError, match="decrease"):
        emitter()._emit_columnar(batch, True)


# -- threads, counter, span --------------------------------------------------

def test_four_threads_render_the_single_threaded_bytes():
    batches = [tpcc_batch(TPCC[i], n=3000, seed=i) for i in (2, 6, 8, 3)]
    em = emitter()
    alone = [columnar(em, b, True) for b in batches]
    assert [path for _, path in alone] == ["native"] * 4
    got = [None] * 4
    start = threading.Barrier(4)

    def work(i):
        start.wait()
        for _ in range(3):
            got[i] = em.emit_batch(batches[i], snapshot=True)

    threads = [threading.Thread(target=work, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not any(t.is_alive() for t in threads)
    assert got == [pairs for pairs, _ in alone]


@pytest.mark.parametrize("path", ["native", "fast", "row"])
def test_the_span_and_the_counters_say_which_path(path):
    batch = tpcc_batch(TPCC[4], n=5)
    items = batch.to_rows() if path == "row" else batch
    with pytest.MonkeyPatch.context() as mp:
        if path == "fast":
            mp.setattr(native, "_lib", None)
            mp.setenv("TRANSFERIA_TPU_NO_NATIVE", "1")
        before = trace.TELEMETRY.snapshot()
        trace.enable(True)
        trace.reset()
        try:
            emitter().emit_batch(items, snapshot=True)
            spans = [s for s in trace.spans() if s[0] == "serialize"]
        finally:
            trace.enable(False)
            trace.reset()
        after = trace.TELEMETRY.snapshot()
    assert [s[7] for s in spans] == [
        {"format": "debezium", "path": path, "rows": 5}]
    moved = {k: after[k] - before[k] for k in
             ("debezium_rows", "debezium_rows_fast", "debezium_rows_native")}
    assert moved == {"debezium_rows": 5,
                     "debezium_rows_fast": 0 if path == "row" else 5,
                     "debezium_rows_native": 5 if path == "native" else 0}
