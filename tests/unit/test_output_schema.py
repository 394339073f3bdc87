"""`output_schema` on the `fs` and `s3` sources: a declared table schema for
JSON lines and CSV (upstream's OutputSchema), validated where the transfer
is loaded; inference stays the default without it."""

import json

import pytest
import yaml

from transferia_tpu.abstract.schema import (
    CanonicalType,
    TableID,
    declared_schema,
)
from transferia_tpu.abstract.table import TableDescription
from transferia_tpu.cli.config import parse_transfer_yaml
from transferia_tpu.cli.main import main as trtpu
from transferia_tpu.providers.file import FileSourceParams, FileStorage
from transferia_tpu.providers.s3 import S3SourceParams, S3Storage

DECLARED = [
    {"name": "id", "type": "int64", "key": True},
    {"name": "n", "type": "int16"},
    {"name": "s", "type": "utf8"},
    {"name": "at", "type": "datetime"},
    {"name": "day", "type": "date"},
]
TID = TableID("fs", "t")


def transfer_yaml(src_params: dict, src_type: str = "fs") -> str:
    return yaml.safe_dump({
        "id": "t", "type": "SNAPSHOT_ONLY",
        "src": {"type": src_type, "params": src_params},
        "dst": {"type": "stdout", "params": {}}})


BAD = {
    "not_a_list": ({"name": "id", "type": "int64"}, "must be a list"),
    "entry_not_a_mapping": (["id"], "is not a mapping"),
    "unknown_key": ([{"name": "id", "type": "int64", "nullable": True}],
                    "unknown keys"),
    "no_name": ([{"type": "int64"}], "has no name"),
    "empty_name": ([{"name": "", "type": "int64"}], "has no name"),
    "named_twice": ([{"name": "id", "type": "int64"},
                     {"name": "id", "type": "utf8"}], "twice"),
    "unknown_type": ([{"name": "id", "type": "bigint"}], "unknown type"),
    "no_type": ([{"name": "id"}], "unknown type"),
    "key_not_a_flag": ([{"name": "id", "type": "int64", "key": "yes"}],
                       "key must be true or false"),
}


@pytest.mark.parametrize("case", sorted(BAD))
@pytest.mark.parametrize("provider", ["fs", "s3"])
def test_a_schema_that_cannot_be_read_is_refused(case, provider):
    items, message = BAD[case]
    params = FileSourceParams if provider == "fs" else S3SourceParams
    with pytest.raises(ValueError, match=message) as e:
        params(format="jsonl", output_schema=items)
    assert f"{provider} source" in str(e.value)


@pytest.mark.parametrize("fmt", ["parquet", "line", "nginx", "proto"])
def test_a_format_with_a_schema_of_its_own_takes_none(fmt):
    params = S3SourceParams if fmt != "parquet" else FileSourceParams
    with pytest.raises(ValueError, match="carries its own schema"):
        params(format=fmt, output_schema=DECLARED)
    assert params(format=fmt).output_schema == []


def test_declared_schema_names_types_and_keys():
    schema = declared_schema(DECLARED, "here")
    assert schema.names() == ["id", "n", "s", "at", "day"]
    assert [c.data_type for c in schema] == [
        CanonicalType.INT64, CanonicalType.INT16, CanonicalType.UTF8,
        CanonicalType.DATETIME, CanonicalType.DATE]
    assert [c.name for c in schema.key_columns()] == ["id"]
    assert declared_schema([], "here") is None
    assert declared_schema(None, "here") is None


@pytest.mark.parametrize("fmt", ["jsonl", "csv"])
def test_with_it_no_line_is_read_to_learn_the_schema(fmt, tmp_path):
    missing = str(tmp_path / "not-there" / f"*.{fmt}")
    fs = FileStorage(FileSourceParams(path=missing, format=fmt, table="t",
                                      output_schema=DECLARED))
    assert fs.table_schema(TID) == declared_schema(DECLARED, "x")
    s3 = S3Storage(S3SourceParams(url=f"file://{missing}", format=fmt,
                                  table="t", output_schema=DECLARED))
    assert s3.table_schema(TableID("s3", "t")) == \
        declared_schema(DECLARED, "x")


def test_without_it_the_schema_is_inferred_as_before(tmp_path):
    path = tmp_path / "d.jsonl"
    path.write_text('{"id":"7","n":1,"at":"2013-07-15 10:47:34"}\n')
    schema = FileStorage(FileSourceParams(
        path=str(path), format="jsonl")).table_schema(TID)
    assert [(c.name, c.data_type) for c in schema] == [
        ("id", CanonicalType.UTF8), ("n", CanonicalType.INT64),
        ("at", CanonicalType.UTF8)]


@pytest.mark.parametrize("provider", ["fs", "s3"])
def test_validate_takes_a_declared_schema_and_names_a_bad_one(
        provider, tmp_path, capsys):
    where = {"path": str(tmp_path)} if provider == "fs" \
        else {"url": f"file://{tmp_path}"}
    good = tmp_path / "good.yaml"
    good.write_text(transfer_yaml(
        {**where, "format": "jsonl", "output_schema": DECLARED}, provider))
    assert trtpu(["validate", "--transfer", str(good)]) == 0
    transfer = parse_transfer_yaml(good.read_text())
    assert transfer.src.output_schema == DECLARED
    bad = tmp_path / "bad.yaml"
    bad.write_text(transfer_yaml(
        {**where, "format": "jsonl",
         "output_schema": [{"name": "id", "type": "bigint"}]}, provider))
    capsys.readouterr()
    assert trtpu(["validate", "--transfer", str(bad)]) == 1
    err = capsys.readouterr().err
    assert "INVALID" in err and "unknown type 'bigint'" in err


def test_the_example_and_the_benchmark_declare_create_sqls_columns():
    import os

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "benchmark", "configs",
                           "hits-columns.json")) as fh:
        columns = json.load(fh)["columns"]
    names = {"string": "utf8", "timestamp": "datetime"}
    want = [{"name": c["name"], "type": names.get(c["type"], c["type"])}
            for c in columns]
    for rel in (("examples", "clickbench_s3_jsonl2ch.yaml"),
                ("benchmark", "configs", "clickbench-jsonl2ch.yaml")):
        with open(os.path.join(root, *rel)) as fh:
            doc = yaml.safe_load(fh.read().replace("${CH_PORT}", "8123"))
        assert doc["src"]["params"]["format"] == "jsonl"
        assert doc["src"]["params"]["output_schema"] == want, rel
        assert len(want) == 105


def test_a_declared_csv_is_read_at_its_types(tmp_path):
    path = tmp_path / "d.csv"
    path.write_text("id,n,s,at,day\n"
                    "9223372036854775807,-5,x,2013-07-15 10:47:34,"
                    "2013-07-15\n"
                    "2,7,,1970-01-01 00:00:01,1970-01-02\n")
    for storage, tid in (
            (FileStorage(FileSourceParams(
                path=str(path), format="csv", table="t",
                output_schema=DECLARED)), TID),
            (S3Storage(S3SourceParams(
                url=f"file://{path}", format="csv", table="t",
                output_schema=DECLARED)), TableID("s3", "t"))):
        got = []
        storage.load_table(TableDescription(id=tid), got.append)
        cols = {n: c.to_pylist() for n, c in got[0].columns.items()}
        assert cols["id"] == [9223372036854775807, 2]
        assert cols["n"] == [-5, 7]
        assert cols["at"] == [1373885254, 1]
        assert cols["day"] == [15901, 1]
        assert got[0].columns["n"].data.dtype == "int16"
        assert got[0].schema == declared_schema(DECLARED, "x")


def test_the_sink_ddl_of_a_declared_table_is_the_parquet_forms():
    """The same column types give the same ClickHouse DDL whether the
    schema came from a parquet footer or was declared."""
    import pyarrow as pa

    from transferia_tpu.columnar.batch import arrow_to_table_schema
    from transferia_tpu.providers.clickhouse.provider import ddl_for_schema

    from_parquet = arrow_to_table_schema(pa.schema([
        ("id", pa.int64()), ("n", pa.int16()), ("s", pa.string()),
        ("at", pa.timestamp("s")), ("day", pa.date32())]))
    declared = declared_schema(
        [dict(c, key=False) for c in DECLARED], "x")
    assert ddl_for_schema(TID, declared) == ddl_for_schema(TID,
                                                           from_parquet)
