"""Typed comparisons on DECIMAL and DATE columns, for both predicate
back ends (predicate/compile.py on the host, predicate/device.py on the
chip): a literal is coerced by its column's canonical type when the plan
is made, and what cannot be coerced fails the activation
(`coerce_literals`), never a batch.

A DECIMAL column travels as the text the source sent (utf8 bytes +
offsets), which is what the sink lands.  It compares as SQL `numeric`
does: the text becomes unscaled int64 integers at the column's scale
(`12.30` at scale 2 is 1230: `decimal_scaled`), the literal becomes an
integer at the same scale, and the comparison is made on integers.  No
float is involved anywhere: the literal keeps the text it was written as
(predicate/ast.py NumberText), the column is parsed by pyarrow's string
-> decimal128 cast.  A literal with more fractional digits than the
scale (`x < 0.055` at scale 2) is no integer there; it is folded into the
operator on its floor instead (`x <= 5`; `scaled_cmp`), which is exact
too.  A value that is no plain number, or does not fit int64 at the
scale, is a ValueError, not a NULL.

A DATE column holds int32 days; a string literal that reads as an ISO
date (`YYYY-MM-DD`) becomes its day number; any other string is a
ValueError that names column and literal.
"""

from __future__ import annotations

import datetime
import decimal
from typing import Optional, Union

import numpy as np

from transferia_tpu.abstract.schema import (
    CanonicalType,
    ColSchema,
    TableSchema,
)
from transferia_tpu.predicate.ast import (
    And, Between, Cmp, InList, Node, Not, NumberText, Or,
)
from transferia_tpu.stats import trace

_EPOCH = datetime.date(1970, 1, 1)
_CTX = decimal.Context(prec=200)
I32_MIN = -2**31

# what `x op literal` folds to when the literal is no integer at the
# column's scale, or lies outside the width the values are compared in
ALWAYS, NEVER = "always", "never"


def column_scale(cs: Optional[ColSchema]) -> Optional[int]:
    """Digits after the point of a DECIMAL column, where the source's
    catalog gave them (ColSchema.properties, ("scale", n))."""
    if cs is None:
        return None
    for key, value in cs.properties or ():
        if key == "scale":
            return int(value)
    return None


def date_days(value, column: str) -> int:
    """A literal against a DATE column, as days since 1970-01-01."""
    if isinstance(value, str):
        try:
            return (datetime.date.fromisoformat(value) - _EPOCH).days
        except ValueError as e:
            raise ValueError(f"DATE column {column!r} compared with "
                             f"{value!r}, which is no date") from e
    if isinstance(value, bool) or not isinstance(value, int):
        raise ValueError(f"DATE column {column!r} compared with {value!r}")
    return value          # a day number, as before


def as_decimal(value, column: str) -> decimal.Decimal:
    """The literal as the exact number it was written as."""
    try:
        if isinstance(value, NumberText):
            return decimal.Decimal(value.text)
        if isinstance(value, bool):
            raise decimal.InvalidOperation
        if isinstance(value, int):
            return decimal.Decimal(value)
        if isinstance(value, float):
            return decimal.Decimal(repr(value))
        if isinstance(value, str):
            d = decimal.Decimal(value.strip())
            if d.is_finite():
                return d
    except decimal.InvalidOperation:
        pass
    raise ValueError(f"DECIMAL column {column!r} compared with {value!r}, "
                     f"which is no number")


def scaled_cmp(op: str, lit: decimal.Decimal, scale: int
               ) -> Union[tuple[str, int], str]:
    """`x op lit` for x an integer count of 10**-scale: (op', k) with k
    an integer, or ALWAYS / NEVER (of rows that are not NULL)."""
    d = _CTX.scaleb(lit, scale)
    floor = d.to_integral_value(rounding=decimal.ROUND_FLOOR)
    if floor == d:
        return op, int(floor)
    if op in ("<", "<="):
        return "<=", int(floor)
    if op in (">", ">="):
        return ">=", int(floor) + 1
    return NEVER if op == "=" else ALWAYS


def clamp(op: str, k: int, bits: int = 32
          ) -> Union[tuple[str, int], str]:
    """`x op k` for x known to fit a signed integer of `bits`, k any
    integer."""
    if k >= 2**(bits - 1):
        return ALWAYS if op in ("<", "<=", "!=") else NEVER
    if k < -2**(bits - 1):
        return ALWAYS if op in (">", ">=", "!=") else NEVER
    return op, k


def fold(op: str, lit: decimal.Decimal, scale: int, bits: int
         ) -> Union[tuple[str, int], str]:
    """`x op lit` for x an integer of `bits` at `scale`: (op', k) with k
    inside that width, or ALWAYS / NEVER."""
    folded = scaled_cmp(op, lit, scale)
    if folded in (ALWAYS, NEVER):
        return folded
    return clamp(*folded, bits=bits)


# -- the column, as integers at a scale ---------------------------------------

def _arrow_text(col):
    import pyarrow as pa

    n = len(col.offsets) - 1
    valid = None
    if col.validity is not None:
        valid = pa.py_buffer(np.packbits(col.validity, bitorder="little"))
    wide = col.offsets.dtype.itemsize == 8
    return pa.Array.from_buffers(
        pa.large_string() if wide else pa.string(), n,
        [valid, pa.py_buffer(np.ascontiguousarray(col.offsets)),
         pa.py_buffer(np.ascontiguousarray(col.data))])


def decimal_scaled(col, scale: Optional[int]) -> tuple[np.ndarray, int]:
    """(unscaled int64 values of a DECIMAL text column at `scale`, the
    scale): 0 at NULL rows.  Without a scale from the schema it is the
    most digits after the point that the batch holds.  Raises ValueError
    where a value is no plain number (NaN, a digit beyond the scale) or
    does not fit 64 bits.  The view is kept on the column (`memo`): a
    batch's host and device strategies, and every comparison of one
    predicate, parse it once."""
    memo = col.memo
    if memo is not None and memo[0] == "decimal_scaled" \
            and (scale is None or memo[2] == scale):
        return memo[1], memo[2]
    import pyarrow as pa
    import pyarrow.compute as pc

    with trace.span("decimal_view", rows=col.n_rows, columns=1):
        arr = _arrow_text(col)
        if scale is None:
            dot = pc.find_substring(arr, ".")
            frac = pc.if_else(pc.less(dot, 0), 0, pc.subtract(
                pc.subtract(pc.binary_length(arr), dot), 1))
            scale = int(pc.max(frac).as_py() or 0)
        try:
            dec = arr.cast(pa.decimal128(38, scale))
        except (pa.ArrowInvalid, pa.ArrowNotImplementedError) as e:
            raise ValueError(f"DECIMAL column {col.name!r} holds a value "
                             f"that is no number at scale {scale}: {e}") \
                from e
        words = np.frombuffer(dec.buffers()[1], dtype=np.int64,
                              count=2 * len(arr),
                              offset=16 * dec.offset).reshape(-1, 2)
        low = np.ascontiguousarray(words[:, 0])
        fits = words[:, 1] == (low >> 63)
        if col.validity is not None:
            low[~col.validity] = 0
            fits = fits | ~col.validity
        if not fits.all():
            raise ValueError(f"DECIMAL column {col.name!r} holds a value "
                             f"beyond 64 bits at scale {scale}")
    col.memo = ("decimal_scaled", low, scale)
    return low, scale


# -- when the plan is made ---------------------------------------------------------

def _leaves(node: Node):
    if isinstance(node, (And, Or)):
        for p in node.parts:
            yield from _leaves(p)
    elif isinstance(node, Not):
        yield from _leaves(node.inner)
    elif isinstance(node, (Cmp, InList, Between)):
        yield node


def literal_values(leaf) -> list:
    """The literals of a Cmp, InList or Between."""
    if isinstance(leaf, InList):
        return list(leaf.values)
    if isinstance(leaf, Between):
        return [leaf.low, leaf.high]
    return [leaf.value]


def coerce_literals(node: Node, schema: TableSchema) -> dict:
    """Every literal of `node` as its column's type compares it - a day
    number against DATE, (operator, unscaled integer) against a DECIMAL
    whose scale the schema gives - by column; recorded once a plan as the
    instant `predicate_coerce`.  Raises ValueError for a literal that
    cannot be compared with its column as SQL would: no date against a
    DATE column, no number against a DECIMAL one."""
    coerced: dict[str, list] = {}
    for leaf in _leaves(node):
        cs = schema.find(leaf.column)
        if cs is None:
            continue
        like = isinstance(leaf, Cmp) and leaf.op == "~"
        op = leaf.op if isinstance(leaf, Cmp) else "="
        for v in literal_values(leaf):
            if v is None:
                continue
            if cs.data_type == CanonicalType.DATE:
                coerced.setdefault(leaf.column, []).append(
                    date_days(v, leaf.column))
            elif cs.data_type == CanonicalType.DECIMAL and not like:
                lit = as_decimal(v, leaf.column)
                scale = column_scale(cs)
                coerced.setdefault(leaf.column, []).append(
                    str(lit) if scale is None
                    else scaled_cmp(op, lit, scale))
    if coerced:
        trace.instant("predicate_coerce", **{
            c: repr(v) for c, v in coerced.items()})
    return coerced


def bind_device(node: Node, schema: TableSchema) -> Node:
    """The predicate as the device program compares it: every literal on
    a DATE column a day number, every comparison on a DECIMAL column one
    between int32 values at the column's scale (the step hands the
    program such values, or keeps the batch on the host).  Only for a
    predicate `device_compatible` accepted."""
    if isinstance(node, (And, Or)):
        return type(node)(tuple(bind_device(p, schema)
                                for p in node.parts))
    if isinstance(node, Not):
        return Not(bind_device(node.inner, schema))
    if not isinstance(node, (Cmp, InList, Between)):
        return node
    cs = schema.find(node.column)
    if cs is None or cs.data_type not in (CanonicalType.DATE,
                                          CanonicalType.DECIMAL):
        return node         # every other type: the node as it was
    if isinstance(node, Between):
        return And((bind_device(Cmp(node.column, ">=", node.low), schema),
                    bind_device(Cmp(node.column, "<=", node.high), schema)))
    if cs.data_type == CanonicalType.DATE:
        if isinstance(node, InList):
            return InList(node.column, tuple(
                None if v is None else date_days(v, node.column)
                for v in node.values), node.negate)
        if node.value is None:
            return node
        return Cmp(node.column, node.op, date_days(node.value, node.column))
    scale = column_scale(cs)
    if isinstance(node, InList):
        # each member is an equality: one that can match nothing at this
        # scale goes, a NULL stays for the three-valued answer
        kept = []
        for v in node.values:
            if v is None:
                kept.append(None)
                continue
            folded = fold("=", as_decimal(v, node.column), scale, 32)
            if folded != NEVER:
                kept.append(folded[1])
        return InList(node.column, tuple(kept), node.negate)
    if node.value is None:
        return node
    folded = fold(node.op, as_decimal(node.value, node.column), scale, 32)
    if folded == ALWAYS:       # of the rows that are not NULL
        return Cmp(node.column, ">=", I32_MIN)
    if folded == NEVER:
        return Cmp(node.column, "<", I32_MIN)
    return Cmp(node.column, folded[0], folded[1])
