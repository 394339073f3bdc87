"""The RowBinary walker against payloads built by hand, and the
comparison against rows it has to pass and rows it has to fail."""

import struct

import numpy as np
import pyarrow as pa
import pytest

from benchmark import events as ev
from benchmark import reference, rowbinary
from benchmark.chserver import Insert


def _varint(n):
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _event_rows(rows):
    """[(id, email bytes, amount | None, ts)] -> RowBinary of events_clean."""
    out = bytearray()
    for i, email, amount, ts in rows:
        out += struct.pack("<q", i) + _varint(len(email)) + email
        out += b"\x01" if amount is None else b"\x00" + struct.pack(
            "<d", amount)
        out += b"\x00" + struct.pack("<q", ts)
    return bytes(out)


TYPES = ["Int64", "String", "Nullable(Float64)", "Nullable(DateTime64(6))"]
NAMES = ["id", "user_email", "amount", "ts"]


def test_walker_decodes_fixed_string_and_nullable():
    body = _event_rows([(7, b"ab", 1.5, 70), (8, b"x" * 300, None, 80)])
    rows, cols, masks = rowbinary.Layout(NAMES, TYPES).decode(body)
    assert rows == 2
    assert cols["id"].tolist() == [7, 8]
    assert cols["user_email"].to_pylist() == [b"ab", b"x" * 300]
    assert cols["amount"].tolist() == [1.5, 0.0]
    assert masks["amount"].tolist() == [False, True]
    assert cols["ts"].tolist() == [70, 80]


@pytest.mark.parametrize("body", [b"\x01", _event_rows([(1, b"a", 1.0, 1)])[:-3],
                                  _event_rows([(1, b"a", 1.0, 1)]) + b"\x00"])
def test_walker_refuses_a_malformed_payload(body):
    with pytest.raises(ValueError):
        rowbinary.Layout(NAMES, TYPES).decode(body)


def _landed(pop, e, mac, alter=None, drop=None, extra=None):
    rows = []
    for k in range(len(e)):
        if k == drop:
            continue
        email = mac.hexdigest(ev.email_of(e.users[k]))
        amount = e.eighths[k] / 8.0
        if k == alter:
            email = b"0" + email[1:] if email[:1] != b"0" else b"1" + email[1:]
        rows.append((int(e.ids[k]), email, amount, int(e.ts[k])))
    if extra:
        rows.append(extra)
    n, cols, masks = rowbinary.Layout(NAMES, TYPES).decode(_event_rows(rows))
    return [Insert(n, 123, cols, masks)]


def _sound(numbers):
    return all(v <= lim for v, lim in numbers.values())


def _compare(inserts, e, mac, tables=("events_clean",)):
    truth = {"users": e.users, "eighths": e.eighths, "ts": e.ts}
    sent = np.ones(len(e), dtype=bool)
    return reference.compare_events(inserts, list(tables), "events_clean",
                                    truth, sent, sent, mac)


def test_event_comparison_passes_sound_rows_and_names_each_fault():
    pop = ev.Population(100, 1.1)
    e = ev.Events(5, 1, 0, 0, 40, pop, 4)
    mac = ev.Hmac(b"salt-5")
    import hashlib
    import hmac

    assert mac.hexdigest(b"abc") == hmac.new(
        b"salt-5", b"abc", hashlib.sha256).hexdigest().encode()
    ok = _compare(_landed(pop, e, mac), e, mac)
    assert _sound(ok["numbers"]) and ok["failed"] == 0
    assert ok["attempted"] == 40
    cases = {
        "events_missing": _landed(pop, e, mac, drop=3),
        "rows_field_mismatch": _landed(pop, e, mac, alter=5),
        "rows_unknown_id": _landed(pop, e, mac,
                                   extra=(ev.ID0 + 999, b"z" * 64, 1.0, 1)),
    }
    for number, inserts in cases.items():
        out = _compare(inserts, e, mac)
        assert out["numbers"][number][0] == 1, number
        assert not _sound(out["numbers"])
    wrong_table = _compare(_landed(pop, e, mac), e, mac,
                           tables=("events", "events_clean"))
    assert wrong_table["numbers"]["tables_unexpected"][0] == 1
    # at least once: a duplicate is counted and is no fault
    twice = _landed(pop, e, mac) + _landed(pop, e, mac)
    dup = _compare(twice, e, mac)
    assert _sound(dup["numbers"])
    assert dup["info"]["duplicates"] == 40


def test_filter_and_key_sampler():
    cols = {"A": np.array([1, 5, 9]), "B": np.array([3, 3, 0])}
    got = reference.eval_filter("A < 9 AND B >= 3", cols.__getitem__)
    assert got.tolist() == [True, True, False]
    with pytest.raises(ValueError):
        reference.eval_filter("A LIKE 'x'", cols.__getitem__)
    keys = np.arange(100000, dtype=np.int64) * 7919
    keep = reference.key_sampler("K", 16, 3_000_000_019)
    sel = keep({"K": keys})
    assert 0.05 < sel.mean() < 0.075
    assert (keep({"K": keys}) == sel).all()
    assert (reference.key_sampler("K", 16, 5)({"K": keys}) != sel).any()
    assert keep({"other": keys}) is None


def test_snapshot_comparison_catches_each_fault():
    keys = pa.array(np.arange(10, 20, dtype=np.int64))
    expected = {"kept": 40, "key": "K", "cols": {
        "K": keys, "S": pa.array([b"v%d" % i for i in range(10)],
                                 type=pa.large_binary()),
        "N": pa.array(np.arange(10, dtype=np.int32))}}
    types = {"K": "Int64", "S": "Nullable(String)", "N": "Nullable(Int32)"}

    def ins(ks, rows=40, s=None, n=None):
        ks = np.asarray(ks, dtype=np.int64)
        return [Insert(rows, 1, {
            "K": ks,
            "S": pa.array(s or [b"v%d" % (k - 10) for k in ks],
                          type=pa.large_binary()),
            "N": np.asarray(n if n is not None else ks - 10,
                            dtype=np.int32)}, {})]

    sound = reference.compare_pass(ins(range(19, 9, -1)), types, expected)
    assert {k: v for k, v in sound.items() if v} == {
        "sample_rows_compared": 10}
    assert reference.compare_pass(ins(range(10, 20), rows=39), types,
                                  expected)["rows_missing"] == 1
    assert reference.compare_pass(ins(range(10, 20), rows=41), types,
                                  expected)["rows_extra"] == 1
    assert reference.compare_pass(ins(range(11, 20)), types,
                                  expected)["sample_keys_missing"] == 1
    assert reference.compare_pass(ins(list(range(10, 20)) + [10, 77]), types,
                                  expected)["sample_rows_unexpected"] == 2
    bad = reference.compare_pass(
        ins(range(10, 20), n=[0, 1, 2, 3, 4, 5, 6, 7, 8, 0]), types, expected)
    assert bad["sample_cells_mismatched"] == 1 and bad["sample_rows_bad"] == 1
