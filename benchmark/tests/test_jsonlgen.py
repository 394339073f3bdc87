"""`jsonlgen.py`: every generated line, read by Python's `json`, is the
truth parquet's row; the text is ClickHouse's JSONEachRow (64-bit integers
quoted, `/` escaped, non-ASCII raw, DateTime and Date as text); an object
at the configuration's `file_rows` stays under the checking machine's
limit; and the control's altered digit is one digit of one line."""

import datetime
import json
import os

import pyarrow as pa
import pyarrow.parquet as pq

from benchmark import datagen, jsonlgen
from benchmark.traffic import snapshot_passes_jsonl

BIG = 3_000_000_019   # the driver's seeds do not fit 32 signed bits
CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs")
COLUMNS = os.path.join(CONFIGS, "hits-columns.json")


def _as_json_reads_it(value):
    """A parquet cell as `json.loads` gives it back from JSONEachRow."""
    if isinstance(value, datetime.datetime):
        return value.strftime("%Y-%m-%d %H:%M:%S")
    if isinstance(value, datetime.date):
        return value.isoformat()
    return value


def test_every_line_is_the_truths_row(tmp_path):
    files = datagen.generate(str(tmp_path / "hits"), BIG, 3000, 1500, 512,
                             1, COLUMNS)
    text = jsonlgen.generate(files, str(tmp_path / "jsonl"), 2)
    assert [os.path.basename(p) for p, _, _ in text] == [
        "part-00000.jsonl", "part-00001.jsonl"]
    with open(COLUMNS) as fh:
        types = {c["name"]: c["type"] for c in json.load(fh)["columns"]}
    for parquet, (path, rows, size) in zip(files, text):
        truth = pq.read_table(parquet)
        with open(path, "rb") as fh:
            data = fh.read()
        assert len(data) == size == os.path.getsize(path)
        assert data.endswith(b"}\n") and rows == truth.num_rows == 1500
        lines = data.split(b"\n")[:-1]
        assert len(lines) == rows
        want = {n: truth[n].to_pylist() for n in truth.column_names}
        for i, line in enumerate(lines):
            got = json.loads(line)
            assert list(got) == truth.column_names      # create.sql's order
            for name, value in got.items():
                cell = _as_json_reads_it(want[name][i])
                if types[name] == "int64":
                    assert value == str(cell), (i, name)   # quoted
                else:
                    assert value == cell, (i, name)
        # the form, byte for byte: no space, `/` escaped, Cyrillic raw
        first = lines[0]
        assert b'": ' not in first and b", " not in first.split(b'"Title"')[0]
        assert b"http:\\/\\/" in first and b"http://" not in data
        assert "о".encode() in data and b"\\u04" not in data
        assert b'"EventTime":"2013-07-' in first
        assert b'"EventDate":"2013-07-' in first


def test_the_escapes_jsoneachrow_has():
    table = pa.table({
        "id": pa.array([1, -(2 ** 63), 2 ** 63 - 1], type=pa.int64()),
        "n": pa.array([0, -32768, 32767], type=pa.int16()),
        "s": pa.array(['q"uote\\ and /slash', "tab\t nl\n cr\r b\b f\f",
                       "ctl\x01\x1f Привет \U0001F600"]),
        "at": pa.array([0, 1373885254, 2 ** 31], type=pa.timestamp("s")),
        "day": pa.array([0, 15901, -1], type=pa.date32())})
    lines = jsonlgen.lines_of(table).to_pylist()
    assert lines[0] == ('{"id":"1","n":0,"s":"q\\"uote\\\\ and \\/slash",'
                        '"at":"1970-01-01 00:00:00","day":"1970-01-01"}\n')
    assert '"s":"tab\\t nl\\n cr\\r b\\b f\\f"' in lines[1]
    assert '"id":"-9223372036854775808","n":-32768' in lines[1]
    assert '"s":"ctl\\u0001\\u001f Привет \U0001F600"' in lines[2]
    assert '"at":"2038-01-19 03:14:08","day":"1969-12-31"' in lines[2]
    for line, row in zip(lines, table.to_pylist()):
        got = json.loads(line)
        assert got["s"] == row["s"] and got["id"] == str(row["id"])


def test_an_object_at_the_configurations_file_rows_is_under_the_limit(
        tmp_path):
    with open(os.path.join(CONFIGS, "clickbench-jsonl2ch.json")) as fh:
        t = json.load(fh)["source_table"]
    assert t["rows"] % t["file_rows"] == 0 and t["file_rows"] <= 32768
    # an eighth of an object, written at the table's scale
    rows = t["file_rows"] // 8
    files = datagen.generate(str(tmp_path / "hits"), BIG, rows, rows,
                             t["batch_rows"], 1, COLUMNS)
    (_, n, size), = jsonlgen.generate(files, str(tmp_path / "jsonl"), 1)
    assert n == rows
    per_row = size / rows
    assert 2000 < per_row < 2800
    assert per_row * t["file_rows"] < snapshot_passes_jsonl.FILE_LIMIT_BYTES


def test_the_controls_fault_is_one_digit_of_one_line(tmp_path):
    files = datagen.generate(str(tmp_path / "hits"), BIG, 200, 200, 512, 1,
                             COLUMNS)
    (path, _, _), = jsonlgen.generate(files, str(tmp_path / "jsonl"), 1)
    with open(path, "rb") as fh:
        before = fh.read().split(b"\n")
    row, old, new = snapshot_passes_jsonl.alter_a_digit(path, 137,
                                                        "CounterID")
    with open(path, "rb") as fh:
        after = fh.read().split(b"\n")
    assert row == 137 and len(old) == len(new) and old != new
    changed = [i for i, (a, b) in enumerate(zip(before, after)) if a != b]
    assert changed == [137] and len(before) == len(after)
    was, now = json.loads(before[137]), json.loads(after[137])
    assert {k for k in was if was[k] != now[k]} == {"CounterID"}
    assert (str(was["CounterID"]), str(now["CounterID"])) == (old, new)
    assert abs(was["CounterID"] - now["CounterID"]) == 1
