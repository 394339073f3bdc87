"""RowBinary encoder: golden bytes, round-trip, nullable/var-width edges."""

import struct

import numpy as np
import pytest

from transferia_tpu import native
from transferia_tpu.abstract import TableID
from transferia_tpu.abstract.schema import CanonicalType, new_table_schema
from transferia_tpu.columnar import ColumnBatch
from transferia_tpu.columnar.batch import (
    Column,
    DictEnc,
    _offsets_from_lengths,
)
from transferia_tpu.providers.clickhouse.rowbinary import (
    _encode_varints,
    _fixed_width,
    decode_rowbinary,
    encode_rowbinary,
    encoder_path,
)


def test_varint_encoding():
    data, lens = _encode_varints(np.array([0, 1, 127, 128, 300, 16384]))
    assert lens.tolist() == [1, 1, 1, 2, 2, 3]
    # golden: 300 = 0xAC 0x02
    start = int(lens[:4].sum())
    assert data[start:start + 2].tolist() == [0xAC, 0x02]
    assert data[0:1].tolist() == [0]
    assert data[2:3].tolist() == [127]
    assert data[3:5].tolist() == [0x80, 0x01]


def test_golden_bytes_fixed_and_string():
    schema = new_table_schema([("a", "int32", True), ("s", "utf8")])
    b = ColumnBatch.from_pydict(TableID("", "t"), schema, {
        "a": [7, -1], "s": ["hi", ""],
    })
    out = encode_rowbinary(b, nullable={"a": False, "s": False})
    want = (
        struct.pack("<i", 7) + b"\x02hi"
        + struct.pack("<i", -1) + b"\x00"
    )
    assert out == want


def test_nullable_golden():
    schema = new_table_schema([("x", "int64"), ("s", "utf8")])
    b = ColumnBatch.from_pydict(TableID("", "t"), schema, {
        "x": [5, None], "s": [None, "ok"],
    })
    out = encode_rowbinary(b, nullable={"x": True, "s": True})
    want = (
        b"\x00" + struct.pack("<q", 5) + b"\x01"      # row0: 5, NULL
        + b"\x01" + b"\x00\x02ok"                      # row1: NULL, "ok"
    )
    assert out == want


def test_roundtrip_all_types():
    schema = new_table_schema([
        ("i8", "int8"), ("i64", "int64", True), ("u32", "uint32"),
        ("f", "float"), ("d", "double"), ("b", "boolean"),
        ("s", "utf8"), ("raw", "string"), ("ts", "timestamp"),
        ("dt", "datetime"),
    ])
    b = ColumnBatch.from_pydict(TableID("", "t"), schema, {
        "i8": [-5, 7], "i64": [1, 2], "u32": [10, 20],
        "f": [1.5, -2.5], "d": [3.25, 0.0], "b": [True, False],
        "s": ["héllo", "x" * 300], "raw": [b"\x00\xff", b""],
        "ts": [1_700_000_000_000_000, 0], "dt": [1_700_000_000, 1],
    })
    nullable = {c.name: False for c in schema}
    out = encode_rowbinary(b, nullable)
    back = decode_rowbinary(out, schema, nullable)
    got = back.to_pydict()
    src = b.to_pydict()
    for k in src:
        if k in ("f",):
            assert got[k] == pytest.approx(src[k])
        else:
            assert got[k] == src[k], k


def test_roundtrip_nullable_mix():
    schema = new_table_schema([("a", "int32"), ("s", "utf8")])
    b = ColumnBatch.from_pydict(TableID("", "t"), schema, {
        "a": [1, None, 3, None], "s": [None, "x", None, "yy"],
    })
    nullable = {"a": True, "s": True}
    back = decode_rowbinary(encode_rowbinary(b, nullable), schema, nullable)
    assert back.to_pydict() == b.to_pydict()


def test_large_strings_multibyte_varint():
    schema = new_table_schema([("s", "utf8")])
    big = "A" * 20000  # 3-byte varint
    b = ColumnBatch.from_pydict(TableID("", "t"), schema, {"s": [big, "b"]})
    nullable = {"s": False}
    back = decode_rowbinary(encode_rowbinary(b, nullable), schema, nullable)
    assert back.to_pydict()["s"] == [big, "b"]


# -- the native writer against the numpy path and the decoder ----------------

_FIXED = [t for t in CanonicalType if _fixed_width(t) is not None]
_VALIDITY = ("none", "some", "all_null")
# a varint of 1 byte (<= 127), 2 bytes (128, 300) and 3 bytes (16,384+)
_STRING_LENGTHS = (0, 1, 127, 128, 300, 16_384, 20_000)


def _validity(kind, n, rng):
    if kind == "none":
        return None
    if kind == "all_null":
        return np.zeros(n, dtype=bool)
    valid = rng.random(n) < 0.6
    valid[:2] = [False, True]  # both, whatever the draw
    return valid


def _fixed_column(name, ctype, n, rng, validity=None):
    wire, _ = _fixed_width(ctype)
    if wire.kind == "f":
        data = rng.standard_normal(n)
    else:
        # whole wire range, but what Python ints and int64 storage hold
        info = np.iinfo(wire)
        data = rng.integers(max(info.min, -2**62), min(info.max, 2**62),
                            n, endpoint=True)
    if ctype == CanonicalType.BOOLEAN:
        data = data % 2
    return Column(name, ctype, data.astype(ctype.np_dtype), None, validity)


def _var_column(name, ctype, lens, rng, validity=None):
    offsets = _offsets_from_lengths(np.asarray(lens, dtype=np.int64))
    data = rng.integers(ord("a"), ord("z"), int(offsets[-1]),
                        endpoint=True).astype(np.uint8)
    return Column(name, ctype, data, offsets, validity)


def _batch(*columns):
    schema = new_table_schema([(c.name, c.ctype) for c in columns])
    return ColumnBatch(TableID("", "t"), schema,
                       {c.name: c for c in columns})


def _numpy_path(batch, nullable):
    """The bytes of the numpy encoder, through the repo's own switch
    (`lib()` caches the loaded library, so both are needed)."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(native, "_lib", None)
        mp.setenv("TRANSFERIA_TPU_NO_NATIVE", "1")
        assert encoder_path() == "numpy"
        return encode_rowbinary(batch, nullable)


def _landed(batch, nullable):
    """What a reader of the wire bytes sees: the batch, with a null in a
    column that is not nullable turned into zero or the empty string."""
    want = batch.to_pydict()
    for name, col in batch.columns.items():
        if nullable[name]:
            continue
        empty = {CanonicalType.STRING: b"", CanonicalType.UTF8: "",
                 CanonicalType.BOOLEAN: False, CanonicalType.FLOAT: 0.0,
                 CanonicalType.DOUBLE: 0.0}.get(col.ctype, 0)
        want[name] = [empty if v is None else v for v in want[name]]
    return want


def _differential_cases():
    """id -> (columns, nullable by column name); built when the test
    runs, from the id's own seed."""
    cases = {}
    for ctype in _FIXED:
        # every wire dtype in all four forms, between strings so that a
        # wrong width shifts everything after it
        def build(rng, ctype=ctype):
            n = 257
            cols = [
                _var_column("lead", CanonicalType.UTF8,
                            rng.integers(0, 9, n), rng),
                _fixed_column("plain", ctype, n, rng),
                _fixed_column("nullable", ctype, n, rng),
                _fixed_column("holes", ctype, n, rng,
                              _validity("some", n, rng)),
                _fixed_column("nullable_holes", ctype, n, rng,
                              _validity("some", n, rng)),
                _var_column("tail", CanonicalType.STRING,
                            rng.integers(0, 9, n), rng),
            ]
            return cols, {"lead": False, "plain": False, "nullable": True,
                          "holes": False, "nullable_holes": True,
                          "tail": True}
        cases[f"type-{ctype.value}"] = build
    for width in ("fixed", "var"):
        for is_nullable in (False, True):
            for kind in _VALIDITY:
                def build(rng, width=width, is_nullable=is_nullable,
                          kind=kind):
                    n = 64
                    valid = _validity(kind, n, rng)
                    if width == "fixed":
                        col = _fixed_column("x", CanonicalType.INT32, n,
                                            rng, valid)
                    else:
                        col = _var_column("x", CanonicalType.UTF8,
                                          rng.integers(0, 200, n), rng,
                                          valid)
                    return ([_fixed_column("k", CanonicalType.INT64, n, rng),
                             col,
                             _fixed_column("z", CanonicalType.UINT8, n, rng)],
                            {"k": False, "x": is_nullable, "z": False})
                cases[f"{width}-{'nullable' if is_nullable else 'required'}"
                      f"-validity_{kind}"] = build
    for length in _STRING_LENGTHS:
        def build(rng, length=length):
            lens = [length, 0, length, 1, length]
            valid = np.array([True, True, False, True, True])
            return ([_var_column("s", CanonicalType.UTF8, lens, rng),
                     _var_column("n", CanonicalType.STRING, lens, rng,
                                 valid),
                     _fixed_column("after", CanonicalType.INT16, 5, rng)],
                    {"s": False, "n": True, "after": False})
        cases[f"string-length-{length}"] = build

    def dict_encoded(rng):
        pool = _var_column("pool", CanonicalType.UTF8,
                           [0, 3, 130, 1, 17_000], rng)
        codes = rng.integers(0, 5, 300).astype(np.int32)
        col = Column("d", CanonicalType.UTF8,
                     validity=_validity("some", 300, rng),
                     dict_enc=DictEnc(codes, pool.data, pool.offsets))
        assert col.is_lazy_dict
        return ([col, _fixed_column("i", CanonicalType.INT32, 300, rng)],
                {"d": True, "i": False})
    cases["dict-encoded-string"] = dict_encoded

    for n in (0, 1, 100_000):
        def build(rng, n=n):
            return ([_fixed_column("a", CanonicalType.INT64, n, rng),
                     _var_column("s", CanonicalType.UTF8,
                                 rng.integers(0, 12, n), rng,
                                 _validity("some", n, rng) if n > 1
                                 else None),
                     _fixed_column("b", CanonicalType.UINT16, n, rng,
                                   _validity("some", n, rng) if n > 1
                                   else None)],
                    {"a": False, "s": True, "b": True})
        cases[f"rows-{n}"] = build

    def column_order(rng):
        # names in no sorted order: the wire follows batch.columns
        names = ["m", "b", "z", "a", "q"]
        cols = [_fixed_column(x, CanonicalType.UINT8, 3, rng)
                if i % 2 else
                _var_column(x, CanonicalType.UTF8, [1, 2, 3], rng)
                for i, x in enumerate(names)]
        return cols, {x: False for x in names}
    cases["column-order"] = column_order
    return cases


_CASES = _differential_cases()


@pytest.mark.parametrize("case", list(_CASES))
def test_native_writer_matches_numpy_path_and_decoder(case):
    seed = int.from_bytes(case.encode(), "little") % 2**32
    columns, nullable = _CASES[case](np.random.default_rng(seed))
    batch = _batch(*columns)
    assert encoder_path() == "native"
    got = encode_rowbinary(batch, nullable)
    assert isinstance(got, bytes)
    assert got == _numpy_path(batch, nullable)
    back = decode_rowbinary(got, batch.schema, nullable)
    assert list(back.columns) == list(batch.columns)
    assert back.to_pydict() == _landed(batch, nullable)


def test_native_writer_refuses_buffers_it_cannot_write_from():
    rng = np.random.default_rng(5)
    key = _fixed_column("k", CanonicalType.INT32, 3, rng)
    col = _var_column("s", CanonicalType.UTF8, [4, 4, 4], rng)
    nullable = {"k": False, "s": False}
    for bad in ([0, 8, 4, 12],      # decreasing
                [0, 4, 8, 13],      # past the buffer
                [0, 4, 8]):         # a row short of the batch
        col.offsets = np.array(bad, dtype=np.int32)
        with pytest.raises(ValueError):
            encode_rowbinary(_batch(key, col), nullable)
    col.offsets = np.array([0, 4, 8, 12], dtype=np.int32)
    col.validity = np.ones(2, dtype=bool)
    with pytest.raises(ValueError):
        encode_rowbinary(_batch(key, col), nullable)


def test_eight_threads_each_get_their_own_bytes():
    """No scratch shared between calls: 8 part threads, 8 different
    batches, written at once with the GIL released."""
    import sys
    import threading

    batches, nullable = [], {"a": False, "s": True, "t": False, "b": True}
    for i in range(8):
        rng = np.random.default_rng(100 + i)
        n = 20_000 + 1_000 * i
        batches.append(_batch(
            _fixed_column("a", CanonicalType.INT64, n, rng),
            _var_column("s", CanonicalType.UTF8,
                        rng.integers(0, 60, n), rng,
                        _validity("some", n, rng)),
            _var_column("t", CanonicalType.STRING,
                        rng.integers(100, 200, n), rng),
            _fixed_column("b", CanonicalType.INT16, n, rng,
                          _validity("some", n, rng)),
        ))
    want = [_numpy_path(b, nullable) for b in batches]
    assert len(set(want)) == 8
    got = [None] * 8
    start = threading.Barrier(8)

    def encode(i):
        start.wait(timeout=30)
        for _ in range(3):
            got[i] = encode_rowbinary(batches[i], nullable)

    threads = [threading.Thread(target=encode, args=(i,))
               for i in range(8)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert got == want
