"""chip_smoke.py on a machine without a chip.

Two facts only a CPU host can pin: with no TPU the script refuses (exit
code, no result line, no rate), and its legs hold together end to end in
`--rehearsal` (CPU backend, tiny sizes) so chip time is never spent
debugging the script.  Each runs the script as a child process: it owns
its own JAX backend, compile-cache setting and loggers.

The script's two table generators are its own; they are imported from
the repo root and held to what every check of the smoke rests on: the
seed alone decides the rows, however the files are cut, and
`expected_kept` is the filter's count on those rows.
"""

from __future__ import annotations

import json
import os
import resource
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402  (repo-root script, not a package)


# the rehearsal's 73-column table is 10.8 MB: one file of it would not fit
FILE_SIZE_LIMIT = 8 << 20


def _limit_file_size():
    resource.setrlimit(resource.RLIMIT_FSIZE,
                       (FILE_SIZE_LIMIT, FILE_SIZE_LIMIT))


def _run(args, tmp_path, **env):
    child_env = {**os.environ, "JAX_PLATFORMS": "cpu",
                 "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jaxcache"),
                 **env}
    return subprocess.run(
        [sys.executable, SCRIPT, "--out", str(tmp_path / "out"),
         "--data-dir", str(tmp_path / "data"), *args],
        capture_output=True, text=True, timeout=600, env=child_env,
        cwd=str(tmp_path), preexec_fn=_limit_file_size)


def _result_lines(stdout: str) -> list[str]:
    return [ln for ln in stdout.splitlines() if ln.startswith('{"ok"')]


def test_refuses_to_run_without_a_tpu(tmp_path):
    proc = _run([], tmp_path)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert _result_lines(proc.stdout) == []
    assert "rows/s" not in proc.stdout
    # it stopped at the backend check: no data generated, no leg run
    assert "snapshot leg" not in proc.stdout
    assert not (tmp_path / "data").exists()


def test_rehearsal_drives_every_leg_and_prints_no_result(tmp_path):
    proc = _run(["--rehearsal"], tmp_path)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    out = proc.stdout
    assert "REHEARSAL" in out
    assert _result_lines(out) == []
    assert "rows/s" not in out and "ns/row" not in out
    for leg in ("snapshot leg: wide", "snapshot leg: ten",
                "replication leg"):
        assert leg in out
    assert "FAIL" not in out
    with open(tmp_path / "out" / "chip_smoke.json") as fh:
        summary = json.load(fh)
    assert summary["rehearsal"] is True and summary["ok"] is True
    assert list(summary)[-1] == "claim" and summary["claim"] is None
    assert summary["backend"]["platform"] == "cpu"
    dev = summary["snapshot"]["ten"]["passes"]["device"]
    assert dev["telemetry"]["device_launches"] > 0
    assert summary["replication"]["landed"] == \
        summary["replication"]["produced"] > 0
    # the machine that checks the script limits the size of one file:
    # both tables are directories of part files, read as one table
    assert summary["file_size_limit_bytes"] == FILE_SIZE_LIMIT
    for table in summary["data"]["tables"].values():
        assert table["files"] > 1
        assert table["largest_file_mb"] * 1e6 < FILE_SIZE_LIMIT
    # the cache went where the variable said, and nowhere else
    assert not (tmp_path / ".jax_cache").exists()
    assert summary["compile_cache"]["dir"] == str(tmp_path / "jaxcache")
    # scratch data is gone
    assert not (tmp_path / "data").exists()


# -- the generators ------------------------------------------------------------

ROWS = 20_000
BATCH_ROWS = 2_048
# rows the wide generator draws at a time, cut down from 500,000 so that
# part files of 4,096 and 8,192 rows hold whole chunks, as four-row-group
# files of 524,288 do at full size
CHUNK_ROWS = 4_096
GENERATORS = {"ten": (chip_smoke.generate_dataset, 10),
              "wide": (chip_smoke.generate_wide_dataset, 73)}


@pytest.fixture(scope="module")
def one_file(tmp_path_factory):
    """(table, seed) -> the table written as ONE file, read back; each
    is generated once for the module, by the first test that asks (which
    has cut the chunk down by then)."""
    import pyarrow.parquet as pq

    made: dict = {}

    def get(table: str, seed: int):
        if (table, seed) not in made:
            path = str(tmp_path_factory.mktemp(f"{table}{seed}") / "hits")
            GENERATORS[table][0](path, ROWS, BATCH_ROWS, seed)
            made[table, seed] = (path, pq.read_table(path))
        return made[table, seed]

    return get


def _source_reads(path: str):
    """The table as the smoke's transfer sees it: through the `fs`
    source, file or directory of part files alike."""
    from transferia_tpu.abstract.schema import TableID
    from transferia_tpu.abstract.table import TableDescription
    from transferia_tpu.factories import new_storage
    from transferia_tpu.models import Transfer
    from transferia_tpu.providers.file import FileSourceParams
    from transferia_tpu.providers.stdout import NullTargetParams

    storage = new_storage(Transfer(
        id="smoke-test",
        src=FileSourceParams(path=path, format="parquet", table="hits",
                             batch_rows=BATCH_ROWS),
        dst=NullTargetParams()))
    tid = TableID("fs", "hits")
    batches: list = []
    storage.load_table(TableDescription(id=tid), batches.append)
    return storage.table_schema(tid), batches


@pytest.mark.requires_pyarrow
@pytest.mark.parametrize("seed", [7, 8])
@pytest.mark.parametrize("max_file_rows", [None, 4_096, 8_192])
@pytest.mark.parametrize("table", ["ten", "wide"])
def test_generator_rows_depend_on_the_seed_alone(
        table, max_file_rows, seed, one_file, tmp_path, monkeypatch):
    import pyarrow.parquet as pq

    from transferia_tpu.abstract.schema import CanonicalType

    monkeypatch.setattr(chip_smoke, "_WIDE_CHUNK_ROWS", CHUNK_ROWS)
    generate, n_columns = GENERATORS[table]
    ref_path, ref = one_file(table, seed)
    path = str(tmp_path / "hits")
    generate(path, ROWS, BATCH_ROWS, seed, max_file_rows=max_file_rows)
    if max_file_rows:
        files = sorted(os.listdir(path))
        assert files == [f"part-{i:05d}.parquet"
                         for i in range(-(-ROWS // max_file_rows))]
        assert all(pq.ParquetFile(os.path.join(path, f)).metadata.num_rows
                   <= max_file_rows for f in files)
    got = pq.read_table(path)
    # the same columns, in the same order, value for value — a second run
    # in one file, or the same rows cut into part files
    assert got.schema.equals(ref.schema)
    assert got.equals(ref)
    assert got.num_rows == ROWS and got.num_columns == n_columns
    # another seed, other rows
    other = one_file(table, 15 - seed)[1]
    assert not got["WatchID"].equals(other["WatchID"])
    assert not got["URL"].equals(other["URL"])
    # ground truth: the smoke's FILTER, evaluated plainly
    assert chip_smoke.FILTER == "RegionID < 400 AND ResolutionWidth >= 390"
    kept = int(((got["RegionID"].to_numpy() < 400)
                & (got["ResolutionWidth"].to_numpy() >= 390)).sum())
    assert 0 < kept < ROWS
    assert chip_smoke.expected_kept(path) == kept
    assert chip_smoke.expected_kept(ref_path) == kept
    # what the transfer reads: every column under its own name, the
    # masked ones strings, the filter's and the join key integers
    schema, batches = _source_reads(path)
    assert [c.name for c in schema.columns] == got.column_names
    assert sum(b.n_rows for b in batches) == ROWS
    types = {c.name: c.data_type for c in schema.columns}
    assert all(types[c] == CanonicalType.UTF8
               for c in chip_smoke.MASKED[table])
    assert types["WatchID"] == CanonicalType.INT64
    assert types["RegionID"] == CanonicalType.INT32
    assert types["ResolutionWidth"] in (CanonicalType.INT16,
                                        CanonicalType.INT32)
    assert np.array_equal(
        np.concatenate([b.column("WatchID").data[:b.n_rows]
                        for b in batches]),
        got["WatchID"].to_numpy())
