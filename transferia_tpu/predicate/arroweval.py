"""Predicate evaluation on arrow RecordBatches (scan-predicate pushdown).

Storages that decode through arrow (the fs/S3 parquet+csv readers) can
pre-filter record batches in C++ before the columnar pivot — the chain
then re-applies the same predicate as an all-true no-op, so pushdown is
a pure optimization, never a semantic dependency.  SQL 3VL matches the
numpy compiler (predicate/compile.py): a row is kept only when the
predicate is definitely true; NULL comparisons are unknown and drop.

eval_mask returns None whenever any part of the AST is unsupported on
the batch (missing column, LIKE on non-strings, etc.) — callers fall
back to unfiltered decode.
"""

from __future__ import annotations

from typing import Optional

from transferia_tpu.predicate.ast import (
    And,
    Between,
    Cmp,
    InList,
    IsNull,
    Node,
    Not,
    Or,
    TrueNode,
)


def _scalar(c, v, column: str):
    """The literal as the arrow column's type compares it: a day number
    against a date, the exact decimal of its text against a decimal
    (predicate/exact.py); a literal that cannot be coerced raises
    ValueError and the batch is decoded unfiltered."""
    import pyarrow as pa

    from transferia_tpu.predicate import exact

    if pa.types.is_date32(c.type):
        return pa.scalar(exact.date_days(v, column), pa.int32()).cast(c.type)
    if pa.types.is_decimal(c.type):
        return pa.scalar(exact.as_decimal(v, column))
    return pa.scalar(v)


def _eval(node: Node, rb):
    """Nullable BooleanArray: null entries are the 3VL 'unknown'.

    Arrow's Kleene kernels propagate unknowns exactly like the numpy
    compiler's (valid, value) mask pairs, so the tri-state rides a
    single nullable array here.
    """
    import pyarrow as pa
    import pyarrow.compute as pc

    names = set(rb.schema.names)

    def col(name):
        if name not in names:
            raise KeyError(name)
        return rb.column(name)

    if isinstance(node, TrueNode):
        n = rb.num_rows
        t = pa.array([True] * n, type=pa.bool_())
        return t
    if isinstance(node, Cmp):
        c = col(node.column)
        v = node.value
        if node.op == "~":
            if not pa.types.is_string(c.type) and \
                    not pa.types.is_large_string(c.type):
                raise TypeError("LIKE on non-string")
            # dialect parity: this predicate language treats only '%' as
            # a wildcard (predicate/compile.py:_like_general re-escapes
            # everything else), while arrow's match_like is full SQL
            # LIKE — escape '_' and '\' so both evaluators agree, or a
            # pushed-down NOT LIKE would drop rows the chain keeps
            pat = str(v).replace("\\", "\\\\").replace("_", "\\_")
            return pc.match_like(c, pat)
        ops = {"=": pc.equal, "!=": pc.not_equal, "<": pc.less,
               "<=": pc.less_equal, ">": pc.greater,
               ">=": pc.greater_equal}
        if node.op not in ops:
            raise ValueError(node.op)
        return ops[node.op](c, _scalar(c, v, node.column))
    if isinstance(node, InList):
        c = col(node.column)
        non_null = [v for v in node.values if v is not None]
        mask = pc.is_in(c, value_set=pa.array(non_null, type=c.type))
        if len(non_null) != len(node.values):
            # SQL: a NULL literal in the list makes every non-match
            # UNKNOWN (x != NULL is unknown), not FALSE — matching the
            # numpy compiler's _eval3 so pushdown never diverges from
            # the chain's filter (NOT IN would otherwise KEEP rows the
            # chain drops)
            mask = pc.if_else(mask, mask, pa.scalar(None, pa.bool_()))
        # arrow is_in returns false (not null) for null inputs; SQL IN
        # with NULL input is unknown -> mark nulls unknown explicitly
        mask = pc.if_else(pc.is_null(c), pa.scalar(None, pa.bool_()),
                          mask)
        if node.negate:
            mask = pc.invert(mask)
        return mask
    if isinstance(node, IsNull):
        c = col(node.column)
        mask = pc.is_null(c)
        if node.negate:
            mask = pc.invert(mask)
        return mask
    if isinstance(node, Between):
        c = col(node.column)
        return pc.and_kleene(
            pc.greater_equal(c, _scalar(c, node.low, node.column)),
            pc.less_equal(c, _scalar(c, node.high, node.column)))
    if isinstance(node, And):
        out = None
        for p in node.parts:
            m = _eval(p, rb)
            out = m if out is None else pc.and_kleene(out, m)
        return out
    if isinstance(node, Or):
        out = None
        for p in node.parts:
            m = _eval(p, rb)
            out = m if out is None else pc.or_kleene(out, m)
        return out
    if isinstance(node, Not):
        return pc.invert(_eval(node.inner, rb))
    raise TypeError(type(node).__name__)


def eval_mask(node: Node, rb) -> Optional[object]:
    """Keep-mask (nullable BooleanArray) for a RecordBatch, or None when
    the predicate cannot be evaluated on this batch.  NULL entries mean
    'unknown' and must be dropped by the caller
    (RecordBatch.filter(..., null_selection_behavior='drop') default)."""
    try:
        return _eval(node, rb)
    except (KeyError, TypeError, ValueError, ArithmeticError):
        return None
    except Exception:
        # arrow raises pa.lib.ArrowInvalid and friends on type mismatch
        return None
