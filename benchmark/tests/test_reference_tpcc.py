"""`reference_tpcc` against envelopes written by hand (`tpcc_helpers`):
a faithful pass reads all zeros, and each limit is tripped by one small
alteration."""

import copy
import json

import pytest

from benchmark import reference_tpcc, tpccgen
from benchmark.tests import tpcc_helpers as h


@pytest.fixture(scope="module")
def world():
    spec = h.small_spec()
    db = tpccgen.generate(5, 1, spec)
    exp = h.expected(db, spec)
    return spec, exp, h.whole_pass(exp)


def compare(world, *passes):
    spec, exp, _ = world
    out = reference_tpcc.compare_snapshot(
        exp, [h.digest(exp, spec, p) for p in passes], 16)
    return {k: v for k, (v, _l) in out["numbers"].items()}, out


def find(pass_, pred):
    for p, records in pass_.items():
        for i, (k, v) in enumerate(records):
            if pred(k, v):
                return p, i
    raise AssertionError("no such record")


def sampled_customer(world):
    spec, exp, _ = world
    i = int(exp.sample_rows["customer"][0])
    return i, h.envelope(exp, "customer", i)


def test_a_faithful_pass_reads_all_zeros(world):
    spec, exp, good = world
    numbers, out = compare(world, good, good)
    assert set(numbers) == {
        "rows_missing", "rows_extra", "rows_duplicated",
        "sample_cells_mismatched", "sample_keys_missing",
        "records_unparsed", "partition_moved", "partitions_unwritten",
        "no_pass_completed"}
    assert not any(numbers.values()), numbers
    assert out["failed"] == 0 and out["attempted"] == 2 * exp.rows
    assert out["info"]["rows_compared"] == 2 * exp.rows
    # every warehouse and district row, and about a quarter of the rest
    per_pass = out["info"]["samples_compared"] // 2
    assert per_pass >= 11 and 0.15 < per_pass / exp.rows < 0.35


def altered(world, fn):
    bad = copy.deepcopy(world[2])
    fn(bad)
    return bad


def test_one_altered_byte_of_a_sampled_value(world):
    _i, (key, value) = sampled_customer(world)

    def alter(bad):
        p, i = find(bad, lambda k, v: k == key)
        at = value.index(b'"c_balance":"-10.00"') + len(b'"c_balance":"-10.0')
        bad[p][i] = (key, value[:at] + b"1" + value[at + 1:])

    numbers, out = compare(world, altered(world, alter))
    assert numbers.pop("sample_cells_mismatched") == 1
    assert not any(numbers.values()) and out["failed"] == 1


@pytest.mark.parametrize("what,old,new", [
    ("op", b'"op":"r"', b'"op":"c"'),
    ("masked column left clear", None, None),
    ("datetime in microseconds", None, None),
    ("schema type", b'"type":"int16"', b'"type":"int32"'),
    ("key field", None, None),
])
def test_each_part_of_an_envelope_is_held(world, what, old, new):
    spec, exp, good = world
    i, (key, value) = sampled_customer(world)
    new_key = key
    if what == "masked column left clear":
        doc = json.loads(value)
        doc["payload"]["after"]["c_phone"] = \
            exp.db["customer"]["cols"]["c_phone"][i].as_py()
        new_value = json.dumps(doc, separators=(",", ":")).encode()
    elif what == "datetime in microseconds":
        doc = json.loads(value)
        doc["payload"]["after"]["c_since"] *= 1000
        new_value = json.dumps(doc, separators=(",", ":")).encode()
    elif what == "key field":
        doc = json.loads(key)
        doc["schema"]["fields"] = doc["schema"]["fields"][:-1]
        new_key, new_value = json.dumps(doc).encode(), value
    else:
        assert value.count(old) >= 1
        new_value = value.replace(old, new, 1)

    def alter(bad):
        p, j = find(bad, lambda k, v: k == key)
        bad[p][j] = (new_key, new_value)

    numbers, _ = compare(world, altered(world, alter))
    assert numbers.pop("sample_cells_mismatched") >= 1
    assert not any(numbers.values()), numbers


def test_a_lost_a_doubled_and_a_foreign_record(world):
    _i, (key, value) = sampled_customer(world)

    def lose(bad):
        p, i = find(bad, lambda k, v: k == key)
        del bad[p][i]

    numbers, _ = compare(world, altered(world, lose))
    assert (numbers.pop("rows_missing"),
            numbers.pop("sample_keys_missing")) == (1, 1)
    assert not any(numbers.values())

    def double(bad):
        p, _i2 = find(bad, lambda k, v: k == key)
        bad[p].append((key, value))

    numbers, _ = compare(world, altered(world, double))
    assert numbers.pop("rows_duplicated") == 1 and not any(numbers.values())

    def foreign(bad):
        doc = json.loads(key)
        doc["payload"]["c_id"] = 39        # of 40 a district: in range,
        doc["payload"]["c_d_id"] = 11      # but no such district
        bad[0].append((json.dumps(doc).encode(), value))

    numbers, _ = compare(world, altered(world, foreign))
    assert numbers.pop("rows_extra") == 1 and not any(numbers.values())


def test_history_is_held_row_by_row(world):
    def is_history(k, v):
        return k is None

    def alter(bad):
        p, i = find(bad, is_history)
        _k, v = bad[p][i]
        at = v.index(b'"h_amount":"10.00"') + len(b'"h_amount":"10.0')
        bad[p][i] = (None, v[:at] + b"1" + v[at + 1:])

    numbers, _ = compare(world, altered(world, alter))
    assert (numbers.pop("rows_missing"), numbers.pop("rows_extra")) == (1, 1)
    assert not any(numbers.values())

    def keyed(bad):      # a key where Debezium gives none
        p, i = find(bad, is_history)
        bad[p][i] = (b'{"payload":{"h_c_id":1}}', bad[p][i][1])

    numbers, _ = compare(world, altered(world, keyed))
    assert numbers["rows_missing"] == 1
    assert numbers["records_unparsed"] + numbers["rows_extra"] == 1


def test_garbage_a_moved_key_and_an_unwritten_partition(world):
    _i, (key, value) = sampled_customer(world)

    def garbage(bad):
        bad[3].append((b"k", b"not an envelope"))

    numbers, _ = compare(world, altered(world, garbage))
    assert numbers.pop("records_unparsed") == 1
    assert not any(numbers.values())

    def move(bad):
        p, i = find(bad, lambda k, v: k == key)
        bad[(p + 1) % 16].append(bad[p].pop(i))

    numbers, _ = compare(world, world[2], altered(world, move))
    assert numbers.pop("partition_moved") == 1
    assert not any(numbers.values())

    def empty_one(bad):
        bad[4].extend(bad[9])
        bad[9] = []

    numbers, _ = compare(world, altered(world, empty_one))
    assert numbers.pop("partitions_unwritten") == 1
    assert not any(numbers.values())
    numbers, _ = compare(world)
    assert numbers["no_pass_completed"] == 1


def test_only_the_stated_handling_modes_are_implemented():
    cfg = h.config()
    reference_tpcc.check_handling(cfg["handling"])
    with pytest.raises(ValueError):
        reference_tpcc.check_handling({**cfg["handling"],
                                       "decimal": "precise"})


def test_the_sampler_is_the_same_by_row_and_by_array(world):
    spec, exp, _ = world
    import numpy as np

    for name, t in exp.db.items():
        if not t["key"]:
            continue
        keys = np.stack([t["cols"][k] for k in t["key"]], axis=1)
        mask = reference_tpcc.sampled_mask(name, keys, 5, 4)
        assert [reference_tpcc.sampled(name, tuple(int(x) for x in k), 5, 4)
                for k in keys[:200]] == mask[:200].tolist()
