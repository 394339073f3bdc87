"""Scan-predicate pushdown: arrow-side pre-filtering in the source scan.

The chain planner exposes its leading row filter
(Transformation.pushable_predicate), the snapshot loader installs it
into ScanPredicateStorage sources, and the fs reader applies it with
arrow compute before the columnar pivot (predicate/arroweval.py).
Pushdown is advisory — the chain re-applies the predicate — so every
test also asserts byte-identical output with pushdown on and off.
"""

import numpy as np
import pyarrow as pa
import pytest

from transferia_tpu.abstract.schema import TableID, new_table_schema
from transferia_tpu.columnar.batch import ColumnBatch
from transferia_tpu.predicate import compile_mask, parse
from transferia_tpu.predicate.arroweval import eval_mask
from transferia_tpu.transform import build_chain

TID = TableID("db", "t")


def make_rb(n=500, with_nulls=True):
    rng = np.random.default_rng(4)
    region = rng.integers(0, 500, n)
    region_vals = [None if with_nulls and i % 11 == 0 else int(region[i])
                   for i in range(n)]
    return pa.record_batch({
        "id": pa.array(range(n), type=pa.int64()),
        "region": pa.array(region_vals, type=pa.int32()),
        "name": pa.array([None if i % 13 == 0 else f"u{i}"
                          for i in range(n)], type=pa.string()),
        "score": pa.array([float(i) * 0.5 for i in range(n)],
                          type=pa.float64()),
    })


PREDICATES = [
    "region < 250",
    "region >= 100 AND score < 200",
    "region < 50 OR region > 450",
    "NOT (region < 250)",
    "region IN (1, 2, 3, 400)",
    "region BETWEEN 100 AND 300",
    "name IS NULL",
    "name IS NOT NULL AND region < 300",
    "name ~ 'u1%'",
]


@pytest.mark.parametrize("text", PREDICATES)
def test_arrow_eval_matches_numpy_3vl(text):
    node = parse(text)
    rb = make_rb()
    mask = eval_mask(node, rb)
    assert mask is not None
    # arrow semantics: null mask entries drop rows on filter
    arrow_keep = np.asarray(mask.fill_null(False))
    schema = new_table_schema([
        ("id", "int64", True), ("region", "int32"),
        ("name", "utf8"), ("score", "double"),
    ])
    batch = ColumnBatch.from_arrow(rb, TID, schema)
    np_keep = compile_mask(node)(batch)
    np.testing.assert_array_equal(arrow_keep, np_keep)


def test_arrow_eval_bails_on_missing_column():
    assert eval_mask(parse("nope < 5"), make_rb()) is None


SCHEMA = new_table_schema([
    ("id", "int64", True), ("url", "utf8"), ("region", "int32"),
])


def _chain(config):
    return build_chain({"transformers": config})


def test_pushable_after_mask_of_other_columns():
    c = _chain([
        {"mask_field": {"columns": ["url"], "salt": "s"}},
        {"filter_rows": {"filter": "region < 100"}},
    ])
    node = c.pushable_predicate(TID, SCHEMA)
    assert node is not None and node.columns() == {"region"}


def test_not_pushable_when_predicate_reads_masked_column():
    c = _chain([
        {"mask_field": {"columns": ["url"], "salt": "s"}},
        {"filter_rows": {"filter": "url = 'x'"}},
    ])
    assert c.pushable_predicate(TID, SCHEMA) is None


def test_not_pushable_past_opaque_step():
    c = _chain([
        {"rename_tables": {"tables": [
            {"from": "db.t", "to": "db.t2"}]}},
        {"filter_rows": {"filter": "region < 100"}},
    ])
    assert c.pushable_predicate(TID, SCHEMA) is None


def test_leading_filter_is_pushable():
    c = _chain([{"filter_rows": {"filter": "region < 100"}}])
    node = c.pushable_predicate(TID, SCHEMA)
    assert node is not None


class TestFileSourceE2E:
    def _write_parquet(self, tmp_path, n=2000):
        import pyarrow.parquet as pq

        rng = np.random.default_rng(7)
        table = pa.table({
            "id": pa.array(range(n), type=pa.int64()),
            "url": pa.array([f"https://h/{i}" for i in range(n)]),
            "region": pa.array(
                [None if i % 17 == 0 else int(x) for i, x in
                 enumerate(rng.integers(0, 500, n))], type=pa.int32()),
        })
        path = str(tmp_path / "t.parquet")
        pq.write_table(table, path, row_group_size=512)
        return path

    def _run(self, path, pushdown: bool):
        from transferia_tpu.coordinator import MemoryCoordinator
        from transferia_tpu.models import Transfer
        from transferia_tpu.providers.file import FileSourceParams
        from transferia_tpu.providers.memory import (
            MemoryTargetParams,
            get_store,
        )
        from transferia_tpu.tasks import SnapshotLoader

        sid = f"pushdown_{pushdown}"
        t = Transfer(
            id=sid,
            src=FileSourceParams(path=path, format="parquet",
                                 table="hits", batch_rows=512),
            dst=MemoryTargetParams(sink_id=sid),
            transformation={"transformers": [
                {"mask_field": {"columns": ["url"], "salt": "s"}},
                {"filter_rows": {"filter": "region < 250"}},
            ]},
        )
        loader = SnapshotLoader(t, MemoryCoordinator(),
                                operation_id=f"op-{sid}")
        if not pushdown:
            loader._setup_scan_pushdown = lambda *a, **k: None
        loader.upload_tables()
        store = get_store(sid)
        return [it.as_dict() for it in store.rows()]

    def test_storage_level_pruning_counter(self, tmp_path):
        from transferia_tpu.abstract.table import TableDescription
        from transferia_tpu.providers.file import (
            FileSourceParams,
            FileStorage,
        )

        path = self._write_parquet(tmp_path)
        st = FileStorage(FileSourceParams(path=path, format="parquet",
                                          table="hits", batch_rows=512))
        tid = st.table
        st.set_scan_predicate(tid, parse("region < 250"))
        got = []
        st.load_table(TableDescription(id=tid),
                      lambda b: got.append(b.n_rows))
        assert st.scan_rows_pruned > 0
        assert sum(got) + st.scan_rows_pruned == 2000

    def test_the_scan_filter_is_a_span_of_the_part_thread(self, tmp_path):
        """The pushed-down predicate runs on the part thread between the
        decode and the `batch` it feeds: a span of its own under `part`
        (tasks/snapshot.py), so `part` self time does not hold it."""
        from transferia_tpu.stats import trace

        path = self._write_parquet(tmp_path)
        trace.enable(True)
        trace.reset()
        try:
            pushed = self._run(path, pushdown=True)
        finally:
            trace.enable(False)
        spans = [s for s in trace.spans() if s[6] >= 0]
        trace.reset()
        by_id = {s[9]: s for s in spans}
        filters = [s for s in spans if s[0] == "scan_filter"]
        assert len(filters) == 4                  # one a row group
        assert 0 < len(pushed) < 2000     # and it dropped rows
        assert all(by_id[s[10]][0] == "part" for s in filters)

    def test_zone_map_prunes_sorted_row_groups(self, tmp_path):
        """Sorted data: min/max stats disprove whole row groups -> they
        are skipped before decode."""
        import pyarrow.parquet as pq

        from transferia_tpu.abstract.table import TableDescription
        from transferia_tpu.providers.file import (
            FileSourceParams,
            FileStorage,
        )

        n = 4000
        table = pa.table({
            "id": pa.array(range(n), type=pa.int64()),
            "region": pa.array(range(n), type=pa.int32()),  # sorted
        })
        path = str(tmp_path / "sorted.parquet")
        pq.write_table(table, path, row_group_size=500)
        st = FileStorage(FileSourceParams(path=path, format="parquet",
                                          table="s", batch_rows=500))
        st.set_scan_predicate(st.table, parse("region < 750"))
        got = []
        st.load_table(TableDescription(id=st.table),
                      lambda b: got.append(b.n_rows))
        # groups [1000,1500), [1500,2000)... disproved entirely: 6 of 8
        # groups never decode; within-group filtering trims the rest
        assert st.scan_rows_pruned >= 3000
        assert sum(got) == 750

    def test_range_disproves_unit(self):
        from transferia_tpu.predicate.stats import (
            ColumnRange,
            range_disproves,
        )

        r = {"x": ColumnRange(min=100, max=200, null_count=0)}
        assert range_disproves(parse("x < 50"), r)
        assert range_disproves(parse("x > 200"), r)
        assert range_disproves(parse("x = 99"), r)
        assert range_disproves(parse("x BETWEEN 10 AND 50"), r)
        assert range_disproves(parse("x IN (1, 2)"), r)
        assert range_disproves(parse("x IS NULL"), r)
        assert range_disproves(parse("x < 50 OR x > 300"), r)
        assert range_disproves(parse("x < 150 AND x > 180"), r) is False
        assert not range_disproves(parse("x < 150"), r)
        assert not range_disproves(parse("x != 150"), r)
        assert not range_disproves(parse("y < 50"), r)  # unknown column
        assert not range_disproves(parse("NOT (x < 50)"), r)

    def test_pushdown_output_identical_and_prunes(self, tmp_path):
        path = self._write_parquet(tmp_path)
        base = self._run(path, pushdown=False)
        pushed = self._run(path, pushdown=True)

        def key(r):
            return r["id"]

        assert sorted((r["id"], r["url"], r["region"]) for r in base) == \
            sorted((r["id"], r["url"], r["region"]) for r in pushed)
        assert len(pushed) > 0
        # nulls in the filter column were dropped (SQL 3VL)
        assert all(r["region"] is not None and r["region"] < 250
                   for r in pushed)
