"""The block decode of JSON lines (parsers/generic.py::JsonBlockDecoder)
and the one reader the file sources share
(providers/s3readers.py::read_json_lines): the block path against the row
path cell for cell on ClickHouse's JSONEachRow, the fallback of a single
line, the failures that fail a pass, the three sources on equal lines,
spans and counters, and a one-batch part's way by the placement book.
"""

import io
import json

import numpy as np
import pyarrow as pa
import pytest

from transferia_tpu.abstract.schema import (
    CanonicalType,
    TableID,
    declared_schema,
)
from transferia_tpu.abstract.table import TableDescription
from transferia_tpu.parsers.base import Message
from transferia_tpu.parsers.generic import (
    GenericJsonParser,
    JsonBlockDecoder,
    JsonLineError,
    row_value,
    temporal_from_text,
)
from transferia_tpu.providers import s3readers
from transferia_tpu.providers.file import FileSourceParams, FileStorage
from transferia_tpu.stats import trace

TID = TableID("fs", "t")
# the six types of ClickBench's create.sql
DECLARED = [
    {"name": "WatchID", "type": "int64"},
    {"name": "Width", "type": "int16"},
    {"name": "ClientIP", "type": "int32"},
    {"name": "URL", "type": "utf8"},
    {"name": "EventTime", "type": "datetime"},
    {"name": "EventDate", "type": "date"},
]
SCHEMA = declared_schema(DECLARED, "test")
I16, I32, I64 = (np.iinfo(t) for t in (np.int16, np.int32, np.int64))

TEXTS = ["", "plain", "http://e.com/a/b?x=1&y=2", 'say "hi"', "back\\slash",
         "tab\there", "line\nfeed", "cr\rhere", "bell\b\f", "nul\x01\x1f",
         "Привет, мир", "emoji \U0001F600 \U00010348", "mixed \\/ \"п\"\n",
         "é ü ß", "{\"not\":\"an object\"}", "trailing\\"]


def each_row(text: str) -> str:
    """A string as ClickHouse's JSONEachRow writes it: `\\`, `"`, `/` and
    the control characters escaped, everything else raw UTF-8."""
    out = []
    for c in text:
        if c in '\\"/':
            out.append("\\" + c)
        elif c in "\b\f\n\r\t":
            out.append("\\" + "bfnrt"["\b\f\n\r\t".index(c)])
        elif ord(c) < 0x20:
            out.append(f"\\u{ord(c):04x}")
        else:
            out.append(c)
    return "".join(out)


def seeded_rows(n: int, seed: int = 5) -> list[dict]:
    rng = np.random.default_rng(seed)
    ends = {"WatchID": [I64.min, I64.max, 0, -1],
            "Width": [I16.min, I16.max, 0, -1],
            "ClientIP": [I32.min, I32.max, 0, -1]}
    rows = []
    for i in range(n):
        t = int(rng.integers(0, 2_000_000_000))
        rows.append({
            "WatchID": ends["WatchID"][i] if i < 4
            else int(rng.integers(I64.min, I64.max)),
            "Width": ends["Width"][i] if i < 4
            else int(rng.integers(I16.min, I16.max)),
            "ClientIP": ends["ClientIP"][i] if i < 4
            else int(rng.integers(I32.min, I32.max)),
            "URL": TEXTS[i % len(TEXTS)] + ("" if i % 7 else f" #{i}"),
            "EventTime": t, "EventDate": t // 86_400})
    return rows


def each_row_line(row: dict, quote64: bool = True, order=None,
                  drop=()) -> bytes:
    """One row as a JSONEachRow line: 64-bit integers quoted (or bare),
    DateTime and Date as text."""
    def fmt(name):
        v = row[name]
        if name == "URL":
            return f'"{each_row(v)}"'
        if name == "EventTime":
            return '"' + str(np.datetime64(v, "s")).replace("T", " ") + '"'
        if name == "EventDate":
            return f'"{np.datetime64(v, "D")}"'
        return f'"{v}"' if name == "WatchID" and quote64 else str(v)

    names = [n for n in (order or list(row)) if n not in drop]
    return ("{" + ",".join(f'"{n}":{fmt(n)}' for n in names)
            + "}").encode()


def table_columns(tbl: pa.Table) -> dict:
    return {n: tbl[n].to_pylist() for n in tbl.column_names}


def batch_columns(batch) -> dict:
    return {n: c.to_pylist() for n, c in batch.columns.items()}


def read_all(data: bytes, schema=SCHEMA, batch_rows: int = 1 << 20,
             **kw) -> list:
    got = []
    s3readers.read_json_lines(io.BytesIO(data), "mem.jsonl", TID, schema,
                              batch_rows, got.append, **kw)
    return got


@pytest.fixture
def telemetry():
    trace.TELEMETRY.reset()
    return lambda: {k: v for k, v in trace.TELEMETRY.snapshot().items()
                    if k.startswith("jsonl_")}


# -- the block path against the row path, cell for cell -------------------------------

VARIANTS = {
    "quoted_int64": {},
    "bare_int64": {"quote64": False},
    "shuffled_keys": {"order": ["URL", "EventDate", "WatchID", "EventTime",
                                "ClientIP", "Width"]},
    "missing_key": {"drop": ("ClientIP",)},
    "missing_text_key": {"drop": ("EventTime", "WatchID")},
}


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_block_path_equals_row_path_cell_for_cell(variant):
    rows = seeded_rows(400)
    lines = [each_row_line(r, **VARIANTS[variant]) for r in rows]
    dec = JsonBlockDecoder(list(SCHEMA))
    block = dec.read(b"\n".join(lines) + b"\n", len(lines))
    by_row = dec.rows_table(lines)
    assert block.schema == by_row.schema == dec.schema
    assert table_columns(block) == table_columns(by_row)
    # and both equal what was written
    dropped = VARIANTS[variant].get("drop", ())
    for name in SCHEMA.names():
        want = [None if name in dropped else r[name] for r in rows]
        assert block[name].to_pylist() == want, name


@pytest.mark.parametrize("text", TEXTS)
def test_every_escape_comes_back_as_the_text_it_stood_for(text):
    row = dict(seeded_rows(1)[0], URL=text)
    line = each_row_line(row)
    assert json.loads(line)["URL"] == text
    dec = JsonBlockDecoder(list(SCHEMA))
    assert dec.read(line + b"\n", 1)["URL"].to_pylist() == [text]
    assert dec.rows_table([line])["URL"].to_pylist() == [text]


@pytest.mark.parametrize("escaped,text", [
    (r"\u0041\u00e9", "Aé"), (r"\ud83d\ude00", "\U0001F600"),
    (r"\u043f\u0440", "пр"), (r"a\/b", "a/b")])
def test_unicode_escapes_are_undone(escaped, text):
    line = b'{"WatchID":"1","URL":"' + escaped.encode() + b'"}'
    dec = JsonBlockDecoder(list(SCHEMA))
    assert dec.read(line, 1)["URL"].to_pylist() == [text]
    assert dec.rows_table([line])["URL"].to_pylist() == [text]


@pytest.mark.parametrize("name,lo,hi", [
    ("Width", I16.min, I16.max), ("ClientIP", I32.min, I32.max),
    ("WatchID", I64.min, I64.max)])
def test_the_ends_of_a_width_are_held_and_one_past_them_is_refused(
        name, lo, hi):
    dec = JsonBlockDecoder(list(SCHEMA))
    for quoted in (False, True):
        q = '"' if quoted else ""
        ok = "\n".join(f'{{"{name}":{q}{v}{q}}}' for v in (lo, hi)).encode()
        assert dec.read(ok, 2)[name].to_pylist() == [lo, hi]
        for v in (lo - 1, hi + 1):
            bad = f'{{"{name}":{q}{v}{q}}}'.encode()
            with pytest.raises(pa.ArrowInvalid):
                dec.read(bad, 1)
            with pytest.raises(JsonLineError):
                dec.rows_table([bad])


def test_a_file_without_a_final_newline_and_blank_lines():
    rows = seeded_rows(50)
    lines = [each_row_line(r) for r in rows]
    data = b"\n".join(lines[:20]) + b"\n\n  \n" + b"\n".join(lines[20:])
    assert not data.endswith(b"\n")
    got = read_all(data)
    assert [b.n_rows for b in got] == [50]
    assert batch_columns(got[0])["WatchID"] == [r["WatchID"] for r in rows]
    assert read_all(b"") == [] and read_all(b"\n\n") == []


@pytest.mark.parametrize("block_bytes", [1, 5, 16, 33, 64, 257, 1000])
def test_a_read_boundary_inside_a_character_or_an_escape(
        monkeypatch, block_bytes, telemetry):
    """The object is read `JSONL_BLOCK_BYTES` at a time and a block handed
    to the decode ends at a newline, so a read that ends inside a
    two- or four-byte character, or between `\\` and what it escapes,
    costs nothing."""
    rows = seeded_rows(64)
    data = b"\n".join(each_row_line(r) for r in rows) + b"\n"
    whole = batch_columns(read_all(data)[0])
    monkeypatch.setattr(s3readers, "JSONL_BLOCK_BYTES", block_bytes)
    got = read_all(data)
    assert len(got) == 1 and batch_columns(got[0]) == whole
    assert whole["URL"] == [r["URL"] for r in rows]
    assert telemetry()["jsonl_rows_block"] == 2 * 64


def test_batches_of_at_most_batch_rows(monkeypatch):
    rows = seeded_rows(1000)
    data = b"\n".join(each_row_line(r) for r in rows) + b"\n"
    monkeypatch.setattr(s3readers, "JSONL_BLOCK_BYTES", 4096)
    got = read_all(data, batch_rows=300)
    assert [b.n_rows for b in got] == [300, 300, 300, 100]
    assert sum((batch_columns(b)["WatchID"] for b in got), []) == \
        [r["WatchID"] for r in rows]
    for b in got:
        assert b.schema == SCHEMA
        for cs in SCHEMA:
            col = b.columns[cs.name]
            if not cs.data_type.is_variable_width:
                assert col.data.dtype == cs.data_type.np_dtype


def test_the_types_the_sink_sees_are_the_declared_ones():
    from transferia_tpu.providers.clickhouse.provider import ddl_for_schema

    got = read_all(each_row_line(seeded_rows(1)[0]))[0]
    ddl = ddl_for_schema(TID, got.schema)
    for want in ("`WatchID` Nullable(Int64)", "`Width` Nullable(Int16)",
                 "`ClientIP` Nullable(Int32)", "`URL` Nullable(String)",
                 "`EventTime` Nullable(DateTime)",
                 "`EventDate` Nullable(Date32)"):
        assert want in ddl, ddl


# -- the row path alone: a line the block path cannot take --------------------------

def test_one_bad_line_among_10000_falls_back_alone_and_is_counted(
        telemetry):
    rows = seeded_rows(10_000)
    lines = [each_row_line(r) for r in rows]
    # a float that is a whole number: no integer to arrow's reader, one
    # to the row path
    lines[6_789] = each_row_line(rows[6_789]).replace(
        b'"Width":' + str(rows[6_789]["Width"]).encode(),
        b'"Width":' + str(rows[6_789]["Width"]).encode() + b".0")
    trace.enable(True)
    trace.reset()
    try:
        got = read_all(b"\n".join(lines) + b"\n")
        spans = [s for s in trace.spans() if s[0] == "source_decode"]
    finally:
        trace.enable(False)
    assert [b.n_rows for b in got] == [10_000]
    cols = batch_columns(got[0])
    for name in SCHEMA.names():
        assert cols[name] == [r[name] for r in rows], name
    assert telemetry() == {"jsonl_rows": 10_000, "jsonl_rows_block": 9_999,
                           "jsonl_bytes": sum(map(len, lines)) + 10_000}
    assert [s[7]["path"] for s in spans] == ["row"]
    assert spans[0][7]["rows"] == 10_000 and spans[0][7]["format"] == "jsonl"


def test_mixed_quoting_in_one_block_still_reads_every_row(telemetry):
    rows = seeded_rows(300)
    lines = [each_row_line(r, quote64=bool(i % 3)) for i, r in
             enumerate(rows)]
    got = read_all(b"\n".join(lines))
    assert batch_columns(got[0])["WatchID"] == [r["WatchID"] for r in rows]
    # halved down to ranges of one quoting, each of which arrow takes
    assert telemetry()["jsonl_rows"] == 300


@pytest.mark.parametrize("bad", [
    b'{"Width":40000}', b'{"Width":"-32769"}', b'{"ClientIP":2147483648}',
    b'{"WatchID":"9223372036854775808"}', b'{"Width":1.5}',
    b'{"Width":true}', b'{"EventTime":"2013-07-15 25:00:00"}',
    b'{"EventDate":"2013-02-30"}', b'{"EventTime":"yesterday"}',
    b'{"URL":{"nested":1}}', b'{"WatchID":"1"', b'[1,2]', b'not json',
    b'{"WatchID":"1"} {"WatchID":"2"}'])
def test_a_line_neither_path_takes_fails_the_read(bad, tmp_path):
    rows = seeded_rows(20)
    lines = [each_row_line(r) for r in rows]
    lines[11] = bad
    path = tmp_path / "bad.jsonl"
    path.write_bytes(b"\n".join(lines) + b"\n")
    storage = FileStorage(FileSourceParams(
        path=str(path), format="jsonl", table="t",
        output_schema=DECLARED))
    with pytest.raises(s3readers.ReaderError, match="unparsed JSON line"):
        storage.load_table(TableDescription(id=TID), lambda b: None)


def test_a_value_outside_a_declared_int16_fails_the_pass(tmp_path):
    """`trtpu activate` on such an object ends non-zero, as on a parquet
    page that cannot be read."""
    import yaml

    from transferia_tpu.abstract.errors import TableUploadError
    from transferia_tpu.cli.main import main as trtpu

    rows = seeded_rows(50)
    good = tmp_path / "good"
    bad = tmp_path / "bad"
    for d, width in ((good, 1), (bad, 32768)):
        d.mkdir()
        lines = [each_row_line(r) for r in rows]
        lines[30] = each_row_line(dict(rows[30], Width=width))
        (d / "a.jsonl").write_bytes(b"\n".join(lines) + b"\n")
    rcs = {}
    for d in (good, bad):
        cfg = tmp_path / f"{d.name}.yaml"
        cfg.write_text(yaml.safe_dump({
            "id": d.name, "type": "SNAPSHOT_ONLY",
            "src": {"type": "fs", "params": {
                "path": str(d), "format": "jsonl", "table": "t",
                "output_schema": DECLARED}},
            "dst": {"type": "fs", "params": {
                "path": str(tmp_path / f"out-{d.name}"),
                "format": "parquet"}}}))
        try:
            rcs[d.name] = trtpu(["--log-level", "error", "activate",
                                 "--transfer", str(cfg)])
        except TableUploadError as e:      # the process's exit code 1
            assert "Width: 32768 is outside int16" in str(e)
            rcs[d.name] = 1
    assert rcs == {"good": 0, "bad": 1}


@pytest.mark.parametrize("ctype,v,want", [
    (CanonicalType.INT16, "12", 12), (CanonicalType.INT16, 12.0, 12),
    (CanonicalType.INT64, str(I64.max), I64.max),
    (CanonicalType.DATETIME, "2013-07-15 10:47:34", 1373885254),
    (CanonicalType.DATETIME, "2013-07-15T10:47:34", 1373885254),
    (CanonicalType.DATETIME, 1373885254, 1373885254),
    (CanonicalType.DATETIME, "1373885254", 1373885254),
    (CanonicalType.TIMESTAMP, "2013-07-15 10:47:34.250000",
     1373885254_250_000),
    (CanonicalType.DATE, "2013-07-15", 15901),
    (CanonicalType.DATE, "1969-12-31", -1),
    (CanonicalType.DOUBLE, "1.5", 1.5), (CanonicalType.DOUBLE, 2, 2.0),
    (CanonicalType.BOOLEAN, "TRUE", True), (CanonicalType.UTF8, 5, "5"),
    (CanonicalType.UTF8, None, None)])
def test_row_value(ctype, v, want):
    from transferia_tpu.abstract.schema import ColSchema

    got = row_value(ColSchema("c", ctype), v)
    assert got == want and type(got) is type(want)


def test_temporal_text_the_block_and_the_row_path_agree_on():
    texts = ["1970-01-01 00:00:00", "2013-07-15 10:47:34",
             "2038-01-19 03:14:08", "1969-12-31 23:59:59", "2106-02-07"]
    arrow = pa.array(texts).cast(pa.timestamp("s")).cast(pa.int64())
    assert arrow.to_pylist() == [
        temporal_from_text(CanonicalType.DATETIME, t) for t in texts]
    with pytest.raises(ValueError):
        temporal_from_text(CanonicalType.DATETIME, "2013-07-15 10:47:34.5")
    with pytest.raises(ValueError):
        temporal_from_text(CanonicalType.DATETIME, "2013-07-15 10:47:34Z")


# -- the three sources on equal lines -------------------------------------------------

def test_fs_s3_and_the_kafka_parser_give_equal_columns_for_equal_lines(
        tmp_path):
    import fsspec

    rows = seeded_rows(600)
    lines = [each_row_line(r) for r in rows]
    path = tmp_path / "x.jsonl"
    path.write_bytes(b"\n".join(lines) + b"\n")
    fs_got, s3_got = [], []
    FileStorage(FileSourceParams(
        path=str(path), format="jsonl", table="t",
        output_schema=DECLARED)).load_table(
            TableDescription(id=TID), fs_got.append)
    reader = s3readers.make_reader("jsonl", declared=SCHEMA)
    assert reader.infer_schema(None, "never opened") == SCHEMA
    reader.read(fsspec.filesystem("file"), str(path), TID, SCHEMA, 1 << 20,
                s3_got.append)
    parser = GenericJsonParser(schema=DECLARED, table="t", namespace="fs",
                               add_system_cols=False)
    result = parser.do_batch([Message(value=ln, topic="t", offset=i)
                              for i, ln in enumerate(lines)])
    assert result.unparsed is None and len(result.batches) == 1
    want = batch_columns(fs_got[0])
    assert want == batch_columns(s3_got[0]) == \
        batch_columns(result.batches[0])
    assert want["EventTime"] == [r["EventTime"] for r in rows]
    for name, col in result.batches[0].columns.items():
        assert col.ctype == fs_got[0].columns[name].ctype
        if col.offsets is None:
            assert col.data.dtype == fs_got[0].columns[name].data.dtype


def test_the_kafka_parser_reads_bare_epoch_counts_as_before():
    parser = GenericJsonParser(schema=[
        {"name": "id", "type": "int64", "key": True},
        {"name": "ts", "type": "timestamp"},
        {"name": "amount", "type": "double"}], table="events")
    msgs = [Message(value=json.dumps(
        {"id": i, "ts": 1_700_000_000_000_000 + i, "amount": i / 8}
    ).encode(), topic="t", offset=i) for i in range(300)]
    batch = parser.do_batch(msgs).batches[0]
    assert batch.columns["ts"].to_pylist() == \
        [1_700_000_000_000_000 + i for i in range(300)]
    assert parser._block_decoder()._variant[0] == frozenset()


def test_an_inferred_schema_with_nested_values_goes_by_the_row_path(
        tmp_path, telemetry):
    path = tmp_path / "n.jsonl"
    path.write_text("".join(json.dumps(
        {"id": i, "tags": {"a": i}, "s": f"x{i}"}) + "\n"
        for i in range(10)))
    got = []
    storage = FileStorage(FileSourceParams(path=str(path), format="jsonl"))
    storage.load_table(TableDescription(id=TableID("fs", "data")),
                       got.append)
    assert batch_columns(got[0])["tags"] == [{"a": i} for i in range(10)]
    assert telemetry()["jsonl_rows"] == 10
    assert telemetry()["jsonl_rows_block"] == 0


# -- spans and counters -----------------------------------------------------------------------

def test_span_args_and_the_three_counters(tmp_path, monkeypatch, telemetry):
    rows = seeded_rows(500)
    data = b"\n".join(each_row_line(r) for r in rows) + b"\n"
    path = tmp_path / "p.jsonl"
    path.write_bytes(data)
    monkeypatch.setattr(s3readers, "JSONL_BLOCK_BYTES", len(data) // 3)
    trace.enable(True)
    trace.reset()
    try:
        FileStorage(FileSourceParams(
            path=str(path), format="jsonl", table="t",
            output_schema=DECLARED)).load_table(
                TableDescription(id=TID), lambda b: None)
        spans = trace.spans()
    finally:
        trace.enable(False)
    reads = [s[7] for s in spans if s[0] == "file_read"]
    decodes = [s[7] for s in spans if s[0] == "source_decode"]
    assert sum(a["bytes"] for a in reads) == len(data)
    assert {a["path"] for a in reads} == {str(path)}
    assert reads[-1]["bytes"] == 0              # the read that met the end
    assert sum(a["rows"] for a in decodes) == 500
    assert sum(a["bytes"] for a in decodes) == len(data)
    assert {(a["format"], a["path"]) for a in decodes} == \
        {("jsonl", "block")}
    assert len(decodes) >= 3
    assert telemetry() == {"jsonl_rows": 500, "jsonl_rows_block": 500,
                           "jsonl_bytes": len(data)}


# -- a one-batch part and the placement book ------------------------------------------------

@pytest.mark.parametrize("n_rows,by_the_book", [(32_768, True),
                                                (32_769, False)])
def test_a_one_batch_part_of_32768_rows_goes_by_the_placement_book(
        tmp_path, monkeypatch, n_rows, by_the_book):
    """An object of 32,768 rows is one batch (batch_rows 131,072), so the
    part's chain sees a first batch within SHARED_READING_MAX_ROWS: the
    activation's first such part measures the host and the others spend
    their batch on the device.  One row more and each part measures for
    itself."""
    from transferia_tpu.ops import linkprobe as lp
    from transferia_tpu.transform import build_chain
    from transferia_tpu.transform.fused import (
        PlacementBook,
        set_device_fusion,
        set_placement,
    )

    fast = lp.LinkProfile(backend="tpu", launch_overhead_s=1e-7,
                          h2d_bytes_per_s=1e13, d2h_bytes_per_s=1e13,
                          measured=True)
    monkeypatch.setattr(lp, "probe_link", lambda force=False: fast)
    path = tmp_path / "part.jsonl"
    path.write_bytes(b"".join(
        b'{"WatchID":"%d","Width":%d,"URL":"http:\\/\\/e.com\\/%d"}\n'
        % (i, i % 2000, i % 97) for i in range(n_rows)))
    storage = FileStorage(FileSourceParams(
        path=str(path), format="jsonl", table="t", batch_rows=131_072,
        output_schema=DECLARED))
    parts = []
    storage.load_table(TableDescription(id=TID), parts.append)
    assert [b.n_rows for b in parts] == [n_rows]
    config = {"transformers": [
        {"mask_field": {"columns": ["URL"], "salt": "s"}},
        {"filter_rows": {"filter": "Width >= 390"}}]}
    trace.TELEMETRY.reset()
    set_device_fusion(True)
    set_placement("auto")
    try:
        book = PlacementBook()
        outs = [build_chain(config, placement_book=book).apply(parts[0])
                for _ in range(2)]
    finally:
        set_placement(None)
        set_device_fusion(None)
    tel = trace.TELEMETRY.snapshot()
    placed = {r: tel[f"placement_{r}"] for r in trace.PLACEMENT_REASONS
              if tel[f"placement_{r}"]}
    if by_the_book:
        assert placed == {"host_first": 1, "device_explore": 1}
    else:
        assert placed == {"host_first": 2} and not book._readings
    assert outs[0].n_rows == outs[1].n_rows == \
        sum(1 for i in range(n_rows) if i % 2000 >= 390)
    assert batch_columns(outs[0])["URL"][:50] == \
        batch_columns(outs[1])["URL"][:50]
