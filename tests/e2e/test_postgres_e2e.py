"""PG provider e2e against the fake wire server (cf. reference pg2ch/pg2pg
suites + pgrecipe)."""

import pytest

from transferia_tpu.abstract import TableID
from transferia_tpu.coordinator import MemoryCoordinator
from transferia_tpu.models import Transfer, TransferType
from transferia_tpu.providers.memory import MemoryTargetParams, get_store
from transferia_tpu.providers.postgres import PGSourceParams, PGTargetParams
from transferia_tpu.providers.sample import SampleSourceParams
from transferia_tpu.tasks import activate_delivery
from tests.recipes.fake_postgres import FakePG, FakeTable


USERS = FakeTable("public", "users", [
    ("id", "bigint", True, True),
    ("name", "text", False, False),
    ("score", "double precision", False, False),
], rows=[
    {"id": str(i), "name": f"user{i}", "score": str(i * 1.5)}
    for i in range(50)
])


@pytest.fixture
def fake_pg():
    srv = FakePG().start()
    srv.add_table(FakeTable(USERS.namespace, USERS.name,
                            USERS.columns, [dict(r) for r in USERS.rows]))
    yield srv
    srv.stop()


def pg_src(srv, **kw):
    return PGSourceParams(host="127.0.0.1", port=srv.port,
                          database="db", user="u", **kw)


def test_pg_snapshot_to_memory(fake_pg):
    store = get_store("pg1")
    store.clear()
    t = Transfer(id="pg1", src=pg_src(fake_pg),
                 dst=MemoryTargetParams(sink_id="pg1"))
    activate_delivery(t, MemoryCoordinator())
    tid = TableID("public", "users")
    assert store.row_count(tid) == 50
    rows = store.rows(tid)
    by_id = {r.value("id"): r for r in rows}
    assert by_id[7].value("name") == "user7"
    assert by_id[7].value("score") == pytest.approx(10.5)
    # canonical schema came from the catalog with pk flag
    assert rows[0].table_schema.find("id").primary_key
    assert rows[0].table_schema.find("id").original_type == "pg:bigint"


def test_pg_snapshot_with_transformers(fake_pg):
    store = get_store("pg2")
    store.clear()
    t = Transfer(
        id="pg2", src=pg_src(fake_pg),
        dst=MemoryTargetParams(sink_id="pg2"),
        transformation={"transformers": [
            {"filter_rows": {"filter": "score > 30"}},
        ]},
    )
    activate_delivery(t, MemoryCoordinator())
    ids = sorted(r.value("id") for r in store.rows(TableID("public",
                                                           "users")))
    assert ids == list(range(21, 50))  # score = 1.5*id > 30


def test_pg_scram_auth():
    srv = FakePG(password="s3cret", scram=True).start()
    try:
        srv.add_table(FakeTable("public", "t", [("id", "bigint", True,
                                                 True)], [{"id": "1"}]))
        from transferia_tpu.providers.postgres.wire import PGConnection

        conn = PGConnection(host="127.0.0.1", port=srv.port,
                            database="db", user="u",
                            password="s3cret").connect()
        assert conn.scalar("SELECT 1") == "1"
        conn.close()
        # wrong password rejected
        with pytest.raises(Exception, match="SCRAM|auth"):
            PGConnection(host="127.0.0.1", port=srv.port, database="db",
                         user="u", password="wrong").connect()
    finally:
        srv.stop()


def test_sample_to_pg_sink(fake_pg):
    t = Transfer(
        id="pg3",
        src=SampleSourceParams(preset="users", table="people", rows=30,
                               batch_rows=10),
        dst=PGTargetParams(host="127.0.0.1", port=fake_pg.port,
                           database="db", user="u"),
    )
    activate_delivery(t, MemoryCoordinator())
    t_rows = fake_pg.tables[("sample", "people")].rows
    assert len(t_rows) == 30
    assert t_rows[0]["email"].endswith("@example.com")
    # DDL declared pk
    assert any(c[0] == "user_id" and c[2] for c in
               fake_pg.tables[("sample", "people")].columns)


def test_pg_cdc_rows_applied(fake_pg):
    """Row-kind batches (insert/update/delete) through the PG sink."""
    from transferia_tpu.abstract import ChangeItem, Kind, OldKeys
    from transferia_tpu.abstract.schema import new_table_schema
    from transferia_tpu.providers.postgres.provider import PGSinker

    schema = new_table_schema([("id", "int64", True), ("v", "utf8")])
    sinker = PGSinker(PGTargetParams(host="127.0.0.1", port=fake_pg.port,
                                     database="db", user="u"))

    def item(kind, id_, v=None, old=None):
        return ChangeItem(
            kind=kind, schema="public", table="cdc",
            column_names=("id", "v") if kind != Kind.DELETE else (),
            column_values=(id_, v) if kind != Kind.DELETE else (),
            table_schema=schema,
            old_keys=OldKeys(("id",), (old,)) if old is not None
            else OldKeys(),
        )

    sinker.push([item(Kind.INSERT, 1, "a"), item(Kind.INSERT, 2, "b")])
    sinker.push([item(Kind.UPDATE, 2, "b2")])
    sinker.push([item(Kind.DELETE, None, old=1)])
    rows = fake_pg.tables[("public", "cdc")].rows
    assert rows == [{"id": "2", "v": "b2"}]
    sinker.close()


def test_pg_ddl_objects_transfer(fake_pg):
    """pg_dump.go parity: indexes/views/sequences move to a PG target
    after the snapshot (pk indexes skipped, idempotent forms)."""
    from transferia_tpu.coordinator import MemoryCoordinator
    from transferia_tpu.models import Transfer
    from transferia_tpu.providers.postgres import (
        PGSourceParams,
        PGTargetParams,
    )
    from transferia_tpu.tasks import activate_delivery
    from tests.recipes.fake_postgres import FakePG

    fake_pg.indexes.extend([
        ("public", "src_t", "src_t_pkey",
         "CREATE UNIQUE INDEX src_t_pkey ON public.src_t (id)"),
        ("public", "src_t", "src_t_v_idx",
         "CREATE INDEX src_t_v_idx ON public.src_t (v)"),
    ])
    fake_pg.views.append(
        ("public", "v_active", "SELECT id, v FROM public.src_t"))
    fake_pg.sequences.append(("public", "src_t_id_seq", 1, 1, 42))

    dst = FakePG().start()
    try:
        t = Transfer(
            id="ddl1",
            src=PGSourceParams(host="127.0.0.1", port=fake_pg.port,
                               database="db", user="u",
                               transfer_ddl=True),
            dst=PGTargetParams(host="127.0.0.1", port=dst.port,
                               database="dw", user="u"),
        )
        activate_delivery(t, MemoryCoordinator())
        ddl = dst.executed_ddl
        assert any("CREATE INDEX IF NOT EXISTS src_t_v_idx" in s
                   for s in ddl), ddl
        assert not any("src_t_pkey" in s for s in ddl)  # pk skipped
        assert any('CREATE OR REPLACE VIEW "public"."v_active"' in s
                   for s in ddl)
        assert any('CREATE SEQUENCE IF NOT EXISTS "public".'
                   '"src_t_id_seq"' in s for s in ddl)
        assert any('setval(\'"public"."src_t_id_seq"\', 42)' in s
                   for s in ddl)
        # and the rows landed before the DDL hook ran
        assert sum(len(tb.rows) for tb in dst.tables.values()) > 0
    finally:
        dst.stop()


def _lineitem(n=120):
    import datetime

    rows = []
    for i in range(n):
        rows.append({
            "l_orderkey": str(1 + i // 4), "l_linenumber": str(1 + i % 4),
            "l_quantity": f"{1 + i % 50}.00",
            "l_discount": f"0.{i % 11:02d}",
            "l_extendedprice": f"{900 + i * 37}.{i % 100:02d}",
            "l_shipdate": (datetime.date(1993, 11, 1)
                           + datetime.timedelta(days=5 * i)).isoformat(),
            "l_comment": None if i % 13 == 0 else f"note, {i}",
        })
    return FakeTable("public", "lineitem", [
        ("l_orderkey", "bigint", True, True),
        ("l_linenumber", "integer", True, True),
        ("l_quantity", "numeric(15,2)", False, True),
        ("l_discount", "numeric(15,2)", False, True),
        ("l_extendedprice", "numeric(15,2)", False, True),
        ("l_shipdate", "date", False, True),
        ("l_comment", "character varying(44)", False, False),
    ], rows=rows, rows_per_page=7)


Q6 = ("l_shipdate >= '1994-01-01' AND l_shipdate < '1995-01-01' "
      "AND l_discount BETWEEN 0.05 AND 0.07 AND l_quantity < 24")


@pytest.mark.parametrize("placement", ["host", "device", "auto"])
def test_pg_numeric_and_date_through_ctid_parts(placement):
    """A `numeric(15,2)` and a `date` column from COPY text through ctid
    parts and a filter_rows step on both: precision and scale reach the
    schema, the filter keeps what Decimal and date arithmetic keep, and
    the source's spans are recorded."""
    import datetime
    from decimal import Decimal

    from transferia_tpu.providers.postgres.provider import PGStorage
    from transferia_tpu.abstract.table import TableDescription
    from transferia_tpu.stats import trace
    from transferia_tpu.transform.fused import set_placement

    table = _lineitem()
    srv = FakePG().start()
    srv.add_table(table)
    src = pg_src(srv, desired_part_size_bytes=3 * 8192, batch_rows=16)
    tid = TableID("public", "lineitem")
    store = get_store("pg-li-" + placement)
    store.clear()
    trace.enable(True)
    trace.reset()
    set_placement(placement)
    try:
        storage = PGStorage(src)
        schema = storage.table_schema(tid)
        disc = schema.find("l_discount")
        assert disc.original_type == "pg:numeric(15,2)"
        assert dict(disc.properties) == {"precision": 15, "scale": 2}
        assert schema.find("l_shipdate").data_type.value == "date"
        parts = storage.shard_table(TableDescription(id=tid))
        storage.close()
        assert len(parts) == 6 and all("ctid" in p.filter for p in parts)
        t = Transfer(
            id="pg-li-" + placement, src=src,
            dst=MemoryTargetParams(sink_id="pg-li-" + placement),
            transformation={"transformers": [
                {"filter_rows": {"filter": Q6}}]})
        activate_delivery(t, MemoryCoordinator())
        spans = trace.spans()
    finally:
        set_placement(None)
        trace.enable(False)
        srv.stop()
    want = sorted(
        (int(r["l_orderkey"]), int(r["l_linenumber"])) for r in table.rows
        if datetime.date(1994, 1, 1)
        <= datetime.date.fromisoformat(r["l_shipdate"])
        < datetime.date(1995, 1, 1)
        and Decimal("0.05") <= Decimal(r["l_discount"]) <= Decimal("0.07")
        and Decimal(r["l_quantity"]) < 24)
    assert 3 <= len(want) < 40
    got = store.rows(tid)
    assert sorted((r.value("l_orderkey"), r.value("l_linenumber"))
                  for r in got) == want
    by_key = {(int(r["l_orderkey"]), int(r["l_linenumber"])): r
              for r in table.rows}
    for r in got:
        src_row = by_key[(r.value("l_orderkey"), r.value("l_linenumber"))]
        # the text Postgres sent, untouched
        assert str(r.value("l_extendedprice")) == src_row["l_extendedprice"]
        assert str(r.value("l_discount")) == src_row["l_discount"]
        assert r.value("l_comment") == src_row["l_comment"]
    names = {}
    for s in spans:
        if s[6] >= 0:
            names.setdefault(s[0], []).append(s[7] or {})
    decodes = [a for a in names["source_decode"]
               if a.get("format") == "pg_copy"]
    assert sum(a["rows"] for a in decodes) == len(table.rows)
    assert sum(a["bytes"] for a in names["pg_copy_read"]) == \
        sum(a["bytes"] for a in decodes) > 0
    assert len(names["pg_copy_read"]) >= 6
    assert names["decimal_view"] and all(
        a["columns"] == 1 for a in names["decimal_view"])
    coerce, = {tuple(sorted(s[7].items())) for s in spans
               if s[0] == "predicate_coerce" and s[6] < 0}
    assert dict(coerce)["l_shipdate"] == "[8766, 9131]"
