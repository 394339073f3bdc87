"""filter_rows on DECIMAL and DATE columns, against a row-at-a-time
reference written with decimal.Decimal and datetime.date.

A DECIMAL column compares as SQL `numeric` does - exactly, on integers at
the column's scale - on the host (predicate/compile.py) and on the device
(the fused step's program, predicate/device.py); a DATE column takes a
string that reads as an ISO date.  Host, device and
reference give the same rows for every predicate below, NULLs by Kleene
logic; a batch whose scaled values pass 32 bits is the host's, and is
counted.
"""

import datetime
from decimal import Decimal as D

import numpy as np
import pytest

from transferia_tpu.abstract import TableID
from transferia_tpu.abstract.schema import (
    CanonicalType,
    ColSchema,
    TableSchema,
)
from transferia_tpu.columnar import ColumnBatch
from transferia_tpu.predicate import compile_mask, parse
from transferia_tpu.predicate import exact
from transferia_tpu.predicate.device import device_compatible
from transferia_tpu.stats.trace import TELEMETRY
from transferia_tpu.transform import build_chain
from transferia_tpu.transform.fused import (
    DeviceFusedStep,
    set_device_fusion,
    set_placement,
)

TID = TableID("public", "lineitem")
NUMERIC = (("precision", 15), ("scale", 2))
SCHEMA = TableSchema([
    ColSchema("k", CanonicalType.INT32, primary_key=True),
    ColSchema("amt", CanonicalType.DECIMAL, properties=NUMERIC,
              original_type="pg:numeric(15,2)"),
    ColSchema("qty", CanonicalType.DECIMAL, properties=NUMERIC,
              original_type="pg:numeric(15,2)"),
    ColSchema("d", CanonicalType.DATE),
])
EPOCH = datetime.date(1970, 1, 1)
date = datetime.date

AMOUNTS = ["0.04", "0.05", "0.06", "0.07", "0.08", "0.00", "-0.05",
           "-0.04", "-0.06", "-12.30", "-12.31", "-12.29", "1.00", "0.99",
           "1.01", "23.99", "24.00", "24.01", "21474836.47",
           "21474836.46", "-21474836.48", None]
QUANTITIES = ["23.00", "24.00", "25.00", "1.00", "50.00", None]
DATES = [date(1993, 12, 31), date(1994, 1, 1), date(1994, 1, 2),
         date(1994, 12, 31), date(1995, 1, 1), date(1969, 12, 31), None]
# scaled at 2 these pass int32 (and one of them 2**53): a batch that holds
# them has no int32 form
WIDE = ["21474836.48", "-21474836.49", "99999999999.99",
        "-90071992547409.93"]


def rows_of(amounts):
    rng = np.random.default_rng(11)
    out = []
    for a in amounts:
        for q in QUANTITIES:
            for d in DATES:
                out.append((a, q, d))
    rng.shuffle(out)
    return out


def batch_of(rows):
    return ColumnBatch.from_pydict(TID, SCHEMA, {
        "k": list(range(len(rows))),
        "amt": [r[0] for r in rows],
        "qty": [r[1] for r in rows],
        "d": [None if r[2] is None else (r[2] - EPOCH).days
              for r in rows],
    })


# -- the reference: one row at a time, three-valued --------------------------------

def cmp3(a, op, b):
    if a is None or b is None:
        return None
    return {"<": a < b, "<=": a <= b, ">": a > b, ">=": a >= b,
            "=": a == b, "!=": a != b}[op]


def and3(*xs):
    if any(x is False for x in xs):
        return False
    return None if any(x is None for x in xs) else True


def or3(*xs):
    if any(x is True for x in xs):
        return True
    return None if any(x is None for x in xs) else False


def not3(x):
    return None if x is None else not x


def in3(a, values):
    return or3(*[cmp3(a, "=", v) for v in values])


def num(text):
    return None if text is None else D(text)


CASES = [
    # TPC-H Q6's predicate at its validation parameters
    ("d >= '1994-01-01' AND d < '1995-01-01' "
     "AND amt BETWEEN 0.05 AND 0.07 AND qty < 24",
     lambda a, q, d: and3(cmp3(d, ">=", date(1994, 1, 1)),
                          cmp3(d, "<", date(1995, 1, 1)),
                          cmp3(a, ">=", D("0.05")), cmp3(a, "<=", D("0.07")),
                          cmp3(q, "<", D(24)))),
    # both sides of every literal
    ("amt < 0.05", lambda a, q, d: cmp3(a, "<", D("0.05"))),
    ("amt <= 0.05", lambda a, q, d: cmp3(a, "<=", D("0.05"))),
    ("amt > 0.07", lambda a, q, d: cmp3(a, ">", D("0.07"))),
    ("amt >= 0.07", lambda a, q, d: cmp3(a, ">=", D("0.07"))),
    ("amt = 0.07", lambda a, q, d: cmp3(a, "=", D("0.07"))),
    ("amt != 0.07", lambda a, q, d: cmp3(a, "!=", D("0.07"))),
    ("amt = 0.070", lambda a, q, d: cmp3(a, "=", D("0.07"))),
    ("qty < 24", lambda a, q, d: cmp3(q, "<", D(24))),
    ("qty >= 24.0", lambda a, q, d: cmp3(q, ">=", D(24))),
    # a literal with more digits than the column's scale
    ("amt < 0.055", lambda a, q, d: cmp3(a, "<", D("0.055"))),
    ("amt <= 0.055", lambda a, q, d: cmp3(a, "<=", D("0.055"))),
    ("amt > 0.055", lambda a, q, d: cmp3(a, ">", D("0.055"))),
    ("amt >= 0.055", lambda a, q, d: cmp3(a, ">=", D("0.055"))),
    ("amt = 0.055", lambda a, q, d: cmp3(a, "=", D("0.055"))),
    ("amt != 0.055", lambda a, q, d: cmp3(a, "!=", D("0.055"))),
    ("amt > -0.045", lambda a, q, d: cmp3(a, ">", D("-0.045"))),
    ("amt <= -12.305", lambda a, q, d: cmp3(a, "<=", D("-12.305"))),
    # negative values and literals
    ("amt < -0.05", lambda a, q, d: cmp3(a, "<", D("-0.05"))),
    ("amt >= -12.3", lambda a, q, d: cmp3(a, ">=", D("-12.3"))),
    ("amt BETWEEN -12.30 AND -0.05",
     lambda a, q, d: and3(cmp3(a, ">=", D("-12.30")),
                          cmp3(a, "<=", D("-0.05")))),
    ("NOT (amt < 0)", lambda a, q, d: not3(cmp3(a, "<", D(0)))),
    # literals at and past the width the device compares in
    ("amt <= 21474836.47", lambda a, q, d: cmp3(a, "<=", D("21474836.47"))),
    ("amt < 21474836.48", lambda a, q, d: cmp3(a, "<", D("21474836.48"))),
    ("amt >= 99999999999.99",
     lambda a, q, d: cmp3(a, ">=", D("99999999999.99"))),
    ("amt != -30000000.00", lambda a, q, d: cmp3(a, "!=", D(-30000000))),
    ("amt > -21474836.48", lambda a, q, d: cmp3(a, ">", D("-21474836.48"))),
    ("amt = 1.0e0", lambda a, q, d: cmp3(a, "=", D(1))),
    ("amt < '0.06'", lambda a, q, d: cmp3(a, "<", D("0.06"))),
    # lists and NULLs
    ("amt IN (0.05, 0.07, 0.055)",
     lambda a, q, d: in3(a, [D("0.05"), D("0.07"), D("0.055")])),
    ("amt NOT IN (0.05, NULL)",
     lambda a, q, d: not3(in3(a, [D("0.05"), None]))),
    ("amt IN (0.055)", lambda a, q, d: in3(a, [D("0.055")])),
    ("amt IS NULL OR qty > 24",
     lambda a, q, d: or3(a is None, cmp3(q, ">", D(24)))),
    ("amt IS NOT NULL AND NOT qty = 24",
     lambda a, q, d: and3(a is not None, not3(cmp3(q, "=", D(24))))),
    ("amt = NULL", lambda a, q, d: None),
    # dates
    ("d = '1994-01-01'", lambda a, q, d: cmp3(d, "=", date(1994, 1, 1))),
    ("d != '1994-01-01'",
     lambda a, q, d: cmp3(d, "!=", date(1994, 1, 1))),
    ("d < '1994-01-01'", lambda a, q, d: cmp3(d, "<", date(1994, 1, 1))),
    ("d >= \"1995-01-01\"", lambda a, q, d: cmp3(d, ">=", date(1995, 1, 1))),
    ("d <= '1969-12-31'",
     lambda a, q, d: cmp3(d, "<=", date(1969, 12, 31))),
    ("d BETWEEN '1994-01-01' AND '1994-12-31'",
     lambda a, q, d: and3(cmp3(d, ">=", date(1994, 1, 1)),
                          cmp3(d, "<=", date(1994, 12, 31)))),
    ("d IN ('1994-01-01', '1995-01-01')",
     lambda a, q, d: in3(d, [date(1994, 1, 1), date(1995, 1, 1)])),
    ("d IS NULL OR NOT (d > '1994-01-01' OR amt > 1)",
     lambda a, q, d: or3(d is None, not3(or3(
         cmp3(d, ">", date(1994, 1, 1)), cmp3(a, ">", D(1)))))),
]


def through_chain(text, batch, placement):
    set_device_fusion(True)
    set_placement(placement)
    try:
        chain = build_chain(
            {"transformers": [{"filter_rows": {"filter": text}}]})
        step, = chain.plan_for(TID, batch.schema).steps
        assert isinstance(step, DeviceFusedStep) and not step.mask_entries
        return chain.apply(batch).column(
            batch.schema.names()[0]).to_pylist()
    finally:
        set_device_fusion(None)
        set_placement(None)


@pytest.mark.parametrize("text,ref", CASES, ids=[c[0] for c in CASES])
def test_host_and_device_give_the_reference_rows(text, ref):
    rows = rows_of(AMOUNTS)
    batch = batch_of(rows)
    want = [i for i, (a, q, d) in enumerate(rows)
            if ref(num(a), num(q), d) is True]
    assert 0 <= len(want) < len(rows)
    node = parse(text)
    assert device_compatible(node, SCHEMA)
    host = np.nonzero(compile_mask(node)(batch))[0].tolist()
    assert host == want
    before = TELEMETRY.snapshot()
    assert through_chain(text, batch, "host") == want
    assert through_chain(text, batch, "device") == want
    after = TELEMETRY.snapshot()
    assert after["filter_rows_device"] - before["filter_rows_device"] \
        == len(rows)
    assert after["filter_rows_host"] - before["filter_rows_host"] == len(rows)
    assert after["filter_batches_host_unsafe"] \
        == before["filter_batches_host_unsafe"]


@pytest.mark.parametrize("text,ref", CASES[:30],
                         ids=[c[0] for c in CASES[:30]])
def test_a_batch_past_32_bits_is_the_hosts_and_is_counted(text, ref):
    rows = rows_of(AMOUNTS + WIDE)
    batch = batch_of(rows)
    want = [i for i, (a, q, d) in enumerate(rows)
            if ref(num(a), num(q), d) is True]
    assert np.nonzero(compile_mask(parse(text))(batch))[0].tolist() == want
    before = TELEMETRY.snapshot()
    assert through_chain(text, batch, "device") == want
    after = TELEMETRY.snapshot()
    reads_amt = "amt" in parse(text).columns()
    assert after["filter_batches_host_unsafe"] \
        - before["filter_batches_host_unsafe"] == (1 if reads_amt else 0)
    assert after["filter_rows_device"] - before["filter_rows_device"] \
        == (0 if reads_amt else len(rows))


@pytest.mark.parametrize("values", [
    ["0.5", "123456789012345678901234567890.5"], ["0.5", "-1e40"],
    ["0.5", "NaN"], ["0.5", "cheap"], ["0.5", ""],
], ids=["wide", "exponent", "nan", "text", "empty"])
def test_a_value_that_is_no_int64_at_its_scale_is_an_error(values):
    schema = TableSchema([ColSchema("x", CanonicalType.DECIMAL)])
    batch = ColumnBatch.from_pydict(TID, schema, {"x": values})
    with pytest.raises(ValueError, match="'x'"):
        compile_mask(parse("x > 0.5"))(batch)


def test_a_decimal_without_a_scale_takes_the_batchs_own():
    schema = TableSchema([ColSchema("x", CanonicalType.DECIMAL)])
    # no scale in the schema: the batch's own, and never the device
    assert not device_compatible(parse("x > 0.5"), schema)
    plain = ColumnBatch.from_pydict(TID, schema,
                                    {"x": ["0.050", "0.05", "0.0501", None]})
    assert compile_mask(parse("x = 0.05"))(plain).tolist() == \
        [True, True, False, False]
    assert compile_mask(parse("x > 0.05"))(plain).tolist() == \
        [False, False, True, False]


def test_no_float_is_near_the_comparison():
    # 0.07 is no binary fraction: its float32 and its float64 differ, so a
    # comparison made in either moves the boundary; and the text '0.070'
    # is not the text '0.07'
    assert float(np.float32(0.07)) != 0.07
    batch = ColumnBatch.from_pydict(TID, SCHEMA, {
        "k": [0, 1, 2, 3], "amt": ["0.07", "0.1", "0.070", "-0.07"],
        "qty": ["1.00"] * 4, "d": [0] * 4})
    for text, want in [("amt <= 0.07", [0, 2, 3]), ("amt >= 0.07", [0, 1, 2]),
                       ("amt = 0.07", [0, 2]), ("amt < 0.1", [0, 2, 3])]:
        assert np.nonzero(compile_mask(parse(text))(batch))[0].tolist() \
            == want
        assert through_chain(text, batch, "device") == want


@pytest.mark.parametrize("text", [
    "d >= 'yesterday'", "d = 'deleted'", "d IN ('1994-01-01', 'x')",
    "d < 1.5", "d = TRUE", "d BETWEEN '1994-01-01' AND '1994-13-01'",
    "amt BETWEEN '1994-01-01' AND 2",
    "amt > 'cheap'", "amt = TRUE", "amt < '1994-01-01'",
    "amt BETWEEN 1 AND 'x'",
])
def test_a_literal_its_column_cannot_take_fails_the_plan(text):
    chain = build_chain({"transformers": [{"filter_rows": {"filter": text}}]})
    with pytest.raises(ValueError):
        chain.plan_for(TID, SCHEMA)
    # and never reaches numpy as `int >= str`
    with pytest.raises(ValueError):
        compile_mask(parse(text))(batch_of(rows_of(AMOUNTS[:3])))


def test_an_integer_predicate_compiles_to_what_it_did():
    schema = TableSchema([ColSchema("RegionID", CanonicalType.INT32),
                          ColSchema("ResolutionWidth", CanonicalType.INT16)])
    node = parse("RegionID < 400 AND ResolutionWidth >= 390")
    assert exact.coerce_literals(node, schema) == {}
    assert exact.bind_device(node, schema) == node
    between = parse("RegionID BETWEEN 1 AND 400 OR ResolutionWidth IN (1, 2)")
    assert exact.bind_device(between, schema) == between
    assert repr(node) == ("And(parts=(Cmp(column='RegionID', op='<', "
                          "value=400), Cmp(column='ResolutionWidth', "
                          "op='>=', value=390)))")
    assert type(node.parts[0].value) is int
    assert device_compatible(node, schema)


def test_coerce_literals_says_what_it_made():
    got = exact.coerce_literals(
        parse("amt BETWEEN 0.05 AND 0.07 AND d >= '1994-01-01' "
              "AND qty < 24 AND amt != 0.055"), SCHEMA)
    assert got == {"amt": [("=", 5), ("=", 7), "always"], "d": [8766],
                   "qty": [("<", 2400)]}


def test_bind_device_folds_to_integers():
    bound = exact.bind_device(
        parse("amt BETWEEN 0.05 AND 0.07 AND d < '1995-01-01' "
              "AND qty < 24.005 AND amt != 0.055"), SCHEMA)
    assert repr(bound) == repr(parse(
        "(amt >= 5 AND amt <= 7) AND d < 9131 AND qty <= 2400 "
        f"AND amt >= {-2**31}"))


# -- seeded LINEITEM-shaped batches against the benchmark's reference --------------
#
# benchmark/reference_lineitem.py works on the generator's integer arrays
# (hundredths, day numbers) and imports nothing of the program: host mask
# == device mask == the reference's mask, NULLs (which never match) in
# every predicate column.

LINEITEM = TableSchema([
    ColSchema("l_orderkey", CanonicalType.INT32, primary_key=True),
    ColSchema("l_quantity", CanonicalType.DECIMAL, properties=NUMERIC,
              original_type="pg:numeric(15,2)"),
    ColSchema("l_discount", CanonicalType.DECIMAL, properties=NUMERIC,
              original_type="pg:numeric(15,2)"),
    ColSchema("l_extendedprice", CanonicalType.DECIMAL, properties=NUMERIC,
              original_type="pg:numeric(15,2)"),
    ColSchema("l_shipdate", CanonicalType.DATE),
])
Q6 = ("l_shipdate >= '1994-01-01' AND l_shipdate < '1995-01-01' "
      "AND l_discount BETWEEN 0.05 AND 0.07 AND l_quantity < 24")
LINEITEM_FILTERS = [Q6] + [
    f"{col} {op} {lit}"
    for col, lit in (("l_shipdate", "'1994-06-15'"), ("l_discount", "0.05"),
                     ("l_quantity", "24"), ("l_extendedprice", "30000.50"))
    for op in ("<", "<=", ">", ">=", "=", "!=")]


def lineitem_batch(seed, n_rows):
    import os

    from benchmark import tpchgen

    spec = tpchgen.load_columns(os.path.join(
        os.path.dirname(tpchgen.__file__), "configs",
        "tpch-lineitem-columns.json"))
    table = tpchgen.generate(seed, n_rows / 6001215, spec)
    assert table["rows"] == n_rows
    rng = np.random.default_rng([seed, 5])
    data, nulls = {"l_orderkey": list(range(n_rows))}, {}
    for name in ("l_quantity", "l_discount", "l_extendedprice",
                 "l_shipdate"):
        null = rng.random(n_rows) < 0.03
        nulls[name] = null
        ints = table["cols"][name]
        if name == "l_shipdate":
            data[name] = [None if z else int(v) for v, z in zip(ints, null)]
        else:
            data[name] = [None if z else f"{int(v) // 100}.{int(v) % 100:02d}"
                          for v, z in zip(ints, null)]
    return table, nulls, ColumnBatch.from_pydict(TID, LINEITEM, data)


@pytest.mark.parametrize("seed,n_rows", [(3, 1000), (2_147_483_777, 20000)])
@pytest.mark.parametrize("text", LINEITEM_FILTERS,
                         ids=[f.replace(" ", "") for f in LINEITEM_FILTERS])
def test_lineitem_host_device_and_reference_masks_are_equal(text, seed,
                                                            n_rows):
    from benchmark import reference_lineitem

    table, nulls, batch = lineitem_batch(seed, n_rows)
    want = reference_lineitem.eval_filter(text, table)
    node = parse(text)
    for name in node.columns():
        want = want & ~nulls[name]
    assert want.sum() < n_rows and (want.any() or " = " in text)
    host = compile_mask(node)(batch)
    np.testing.assert_array_equal(host, want)
    assert device_compatible(node, LINEITEM)
    before = TELEMETRY.snapshot()
    kept = through_chain(text, batch, "device")
    after = TELEMETRY.snapshot()
    assert kept == np.flatnonzero(want).tolist()
    # l_extendedprice in cents fits int32 at this size: nothing is unsafe
    assert after["filter_batches_host_unsafe"] == \
        before["filter_batches_host_unsafe"]
    assert after["filter_rows_device"] - before["filter_rows_device"] \
        == n_rows
