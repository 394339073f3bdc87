"""Compile predicate AST to a jnp boolean-mask program (device path).

The host twin is predicate/compile.py (numpy, authoritative semantics —
SQL Kleene three-valued logic, NULL comparisons never match).  This module
emits the same masks as jnp expressions so the row filter can ride the same
XLA launch as the HMAC mask and numeric casts (the fused transform step,
ops/fused.py) instead of a separate host pass per batch.

Device eligibility is deliberately narrow: fixed-width columns whose
dtype survives the x32 device boundary bit-exactly (bool, int8/16/32,
uint8/16, float32, date32), and DECIMAL columns whose scale the schema
gives: those compare as integers at that scale (predicate/exact.py), in
int32, and the fused step checks per batch that the values fit it - a
batch that does not goes to the host and is counted.  64-bit integers
would be silently truncated by the jax x32 default and float64
comparisons would change answers in float32 — those predicates stay on
the host path.  String comparisons stay host-side too
(predicate/compile.py's length-prefiltered gathers are already vectorized
and the device gain would be eaten by transfers).

The program is compiled from the predicate as `exact.bind_device` rewrote
it for the schema: day numbers for dates, scaled integers for decimals.

Reference being displaced: pkg/transformer/registry/filter_rows — a
row-at-a-time Go predicate interpreter.
"""

from __future__ import annotations

from typing import Callable

import numpy as np

from transferia_tpu.abstract.schema import CanonicalType, TableSchema
from transferia_tpu.predicate import exact
from transferia_tpu.predicate.ast import (
    And, Between, Cmp, InList, IsNull, Node, Not, Or, TrueNode,
)

# dtypes that cross the host->device boundary bit-exactly under jax x32
_DEVICE_SAFE = {
    CanonicalType.BOOLEAN,
    CanonicalType.INT8,
    CanonicalType.INT16,
    CanonicalType.INT32,
    CanonicalType.UINT8,
    CanonicalType.UINT16,
    CanonicalType.FLOAT,   # float32
    CanonicalType.DATE,    # int32 days
}

# DeviceCols: column name -> (data jnp array, validity jnp bool array)
DeviceMaskFn = Callable[[dict], "object"]


def device_compatible(node: Node, schema: TableSchema) -> bool:
    """True when every referenced column evaluates bit-exactly on device."""
    ok, _ = _walk(node, schema)
    return ok


def _walk(node: Node, schema: TableSchema) -> tuple[bool, bool]:
    if isinstance(node, TrueNode):
        return True, False
    if isinstance(node, (And, Or)):
        return all(_walk(p, schema)[0] for p in node.parts), False
    if isinstance(node, Not):
        return _walk(node.inner, schema)
    if isinstance(node, (IsNull, Between, InList, Cmp)):
        cs = schema.find(node.column)
        if cs is None:
            return False, False
        if cs.data_type == CanonicalType.DECIMAL:
            return _decimal_device_safe(node, cs), False
        if cs.data_type not in _DEVICE_SAFE:
            return False, False
        if isinstance(node, IsNull):
            return True, False
        if isinstance(node, Cmp) and node.op == "~":
            return False, False
        return all(v is None or _literal_device_safe(v, cs.data_type)
                   for v in exact.literal_values(node)), False
    return False, False


def _decimal_device_safe(node: Node, cs) -> bool:
    """A DECIMAL column compares on the device as int32 values at the
    scale its schema gives; every literal has to be a number."""
    if exact.column_scale(cs) is None:
        return False
    if isinstance(node, IsNull):
        return True
    if isinstance(node, Cmp) and node.op == "~":
        return False
    try:
        for v in exact.literal_values(node):
            if v is not None:
                exact.as_decimal(v, node.column)
    except ValueError:
        return False
    return True


def _literal_device_safe(v, ctype: CanonicalType) -> bool:
    """True when comparing `v` against a ctype column on device gives the
    same answer as the host path (numpy, which promotes to int64/float64).

    The device evaluates in the column's own 32-bit dtype, so a literal
    that doesn't fit it bit-exactly can silently change comparisons
    (e.g. float32(16777217) == 16777216.0) — such predicates must stay on
    the host path.
    """
    if isinstance(v, bool):
        return ctype == CanonicalType.BOOLEAN
    if ctype == CanonicalType.BOOLEAN:
        return False
    if isinstance(v, str):
        if ctype != CanonicalType.DATE:
            return False
        try:
            exact.date_days(v, "")
        except ValueError:
            return False
        return True
    if isinstance(v, int):
        if ctype == CanonicalType.FLOAT:
            # int literal vs float32 column: exact iff it fits 2^24
            return abs(v) <= 2**24
        # integer columns: the literal must fit the column dtype (numpy
        # would upcast and compare exactly; jnp would overflow the trace)
        info = np.iinfo(ctype.np_dtype)
        return info.min <= v <= info.max
    if isinstance(v, float):
        if ctype == CanonicalType.FLOAT:
            # must survive the float64 -> float32 round-trip bit-exactly
            return float(np.float32(v)) == v or np.isnan(v)
        # float literal vs integer column: the device comparison happens
        # in float32, so EVERY possible column value must be f32-exact —
        # true only for the sub-24-bit integer dtypes.  int32/date columns
        # hold values like 2^24+1 that collapse onto the literal in f32
        # (host float64 keeps them distinct), so those stay on the host.
        if ctype in (CanonicalType.INT32, CanonicalType.DATE):
            return False
        return float(np.float32(v)) == v
    return False


def compile_mask_jnp(node: Node) -> DeviceMaskFn:
    """Build (cols, n_rows) -> bool keep-mask as a pure-jnp function.

    cols maps column name -> (data, validity) jnp arrays; validity is
    always materialized (callers pass all-True when the column has no null
    bitmap) so the traced program has a static structure.  n_rows is the
    (static, bucketed) batch length — TrueNode needs it when the predicate
    references no columns at all.
    Semantics match predicate/compile.py: UNKNOWN rows do not match.
    """

    def fn(cols: dict, n_rows: int):
        t, _u = _eval3_jnp(node, cols, n_rows)
        return t

    return fn


def _eval3_jnp(node: Node, cols: dict, n: int):
    import jax.numpy as jnp

    if isinstance(node, TrueNode):
        ones = jnp.ones(n, dtype=jnp.bool_)
        return ones, jnp.zeros_like(ones)
    if isinstance(node, And):
        t, u = _eval3_jnp(node.parts[0], cols, n)
        f = ~t & ~u
        for p in node.parts[1:]:
            t2, u2 = _eval3_jnp(p, cols, n)
            f = f | (~t2 & ~u2)
            t = t & t2
        return t, ~t & ~f
    if isinstance(node, Or):
        t, u = _eval3_jnp(node.parts[0], cols, n)
        f = ~t & ~u
        for p in node.parts[1:]:
            t2, u2 = _eval3_jnp(p, cols, n)
            f = f & (~t2 & ~u2)
            t = t | t2
        return t, ~t & ~f
    if isinstance(node, Not):
        t, u = _eval3_jnp(node.inner, cols, n)
        return ~t & ~u, u
    if isinstance(node, IsNull):
        _, valid = cols[node.column]
        null = ~valid
        return ((~null if node.negate else null),
                jnp.zeros_like(null))
    if isinstance(node, Between):
        return _eval3_jnp(And((
            Cmp(node.column, ">=", node.low),
            Cmp(node.column, "<=", node.high),
        )), cols, n)
    if isinstance(node, InList):
        data, valid = cols[node.column]
        mask = jnp.zeros(data.shape[0], dtype=jnp.bool_)
        has_null_literal = any(v is None for v in node.values)
        for v in node.values:
            if v is not None:
                mask = mask | _cmp_jnp(data, "=", v)
        t = mask & valid
        f = ~mask & valid
        if has_null_literal:
            f = jnp.zeros_like(f)
        if node.negate:
            t, f = f, t
        return t, ~t & ~f
    if isinstance(node, Cmp):
        data, valid = cols[node.column]
        if node.value is None:
            # col <op> NULL is always UNKNOWN
            return (jnp.zeros(data.shape[0], dtype=jnp.bool_),
                    jnp.ones(data.shape[0], dtype=jnp.bool_))
        t = _cmp_jnp(data, node.op, node.value) & valid
        return t, ~valid
    raise TypeError(f"unknown predicate node {node!r}")


def _cmp_jnp(data, op: str, value):
    if op == "=":
        return data == value
    if op == "!=":
        return data != value
    if op == "<":
        return data < value
    if op == "<=":
        return data <= value
    if op == ">":
        return data > value
    if op == ">=":
        return data >= value
    raise ValueError(f"unsupported device op {op!r}")


def device_validity(col_validity, n: int):
    """Materialize a validity array for the device program."""
    if col_validity is None:
        return np.ones(n, dtype=np.bool_)
    return col_validity
