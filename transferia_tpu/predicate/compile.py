"""Compile predicate AST to a vectorized mask function over ColumnBatch.

SQL three-valued logic collapsed the usual way: NULL comparisons are False
(rows with NULL in a compared column don't match), IS NULL sees validity.

Fixed-width columns evaluate as single numpy ops.  Variable-width (string)
columns evaluate with length-prefiltered flat-byte gathers — vectorized, no
per-row Python except the LIKE '%x%' contains fallback.  The same structure
is jit-compatible for the device path (ops/ kernels swap numpy for jnp).
"""

from __future__ import annotations

from typing import Callable

import numpy as np

from transferia_tpu.abstract.schema import CanonicalType
from transferia_tpu.columnar.batch import Column, ColumnBatch
from transferia_tpu.predicate import exact
from transferia_tpu.predicate.ast import (
    And, Between, Cmp, InList, IsNull, Node, Not, Or, TrueNode,
)

MaskFn = Callable[[ColumnBatch], np.ndarray]


def compile_mask(node: Node) -> MaskFn:
    """Build batch -> bool mask with SQL (Kleene) three-valued logic: rows
    whose predicate evaluates to UNKNOWN (NULL-involved) do not match, even
    under NOT — matching what the same WHERE clause does at a source DB.
    Raises KeyError at eval time if a referenced column is absent (callers
    check node.columns() for suitability)."""

    def fn(batch: ColumnBatch) -> np.ndarray:
        t, _n = _eval3(node, batch)
        return t

    return fn


def _eval3(node: Node, batch: ColumnBatch) -> tuple[np.ndarray, np.ndarray]:
    """Kleene evaluation: returns (true_mask, unknown_mask)."""
    n = batch.n_rows
    if isinstance(node, TrueNode):
        return np.ones(n, dtype=np.bool_), np.zeros(n, dtype=np.bool_)
    if isinstance(node, And):
        t, u = _eval3(node.parts[0], batch)
        f = ~t & ~u
        for p in node.parts[1:]:
            t2, u2 = _eval3(p, batch)
            f = f | (~t2 & ~u2)
            t = t & t2
        u = ~t & ~f
        return t, u
    if isinstance(node, Or):
        t, u = _eval3(node.parts[0], batch)
        f = ~t & ~u
        for p in node.parts[1:]:
            t2, u2 = _eval3(p, batch)
            f = f & (~t2 & ~u2)
            t = t | t2
        u = ~t & ~f
        return t, u
    if isinstance(node, Not):
        t, u = _eval3(node.inner, batch)
        return ~t & ~u, u
    if isinstance(node, IsNull):
        col = batch.column(node.column)
        if col.validity is None:
            null = np.zeros(n, dtype=np.bool_)
        else:
            null = ~col.validity
        # IS [NOT] NULL never yields UNKNOWN
        return (~null if node.negate else null), np.zeros(n, dtype=np.bool_)
    if isinstance(node, Between):
        return _eval3(And((
            Cmp(node.column, ">=", node.low),
            Cmp(node.column, "<=", node.high),
        )), batch)
    if isinstance(node, InList):
        col_null = ~_valid(batch, node.column)
        mask = np.zeros(n, dtype=np.bool_)
        has_null_literal = any(v is None for v in node.values)
        for v in node.values:
            if v is not None:
                mask |= _eval_cmp(Cmp(node.column, "=", v), batch)
        # SQL IN semantics: TRUE when matched; UNKNOWN when the column is
        # NULL or (no match and a NULL literal is in the list); else FALSE.
        t = mask & ~col_null
        f = ~mask & ~col_null
        if has_null_literal:
            f = np.zeros(n, dtype=np.bool_)
        if node.negate:
            t, f = f, t
        return t, ~t & ~f
    if isinstance(node, Cmp):
        t = _eval_cmp(node, batch)
        unknown = ~_valid(batch, node.column)
        if node.value is None:
            unknown = np.ones(n, dtype=np.bool_)
        return t & ~unknown, unknown
    raise TypeError(f"unknown predicate node {node!r}")


def _valid(batch: ColumnBatch, name: str) -> np.ndarray:
    col = batch.column(name)
    if col.validity is None:
        return np.ones(batch.n_rows, dtype=np.bool_)
    return col.validity


def _eval_cmp(node: Cmp, batch: ColumnBatch) -> np.ndarray:
    col = batch.column(node.column)
    valid = _valid(batch, node.column)
    if node.value is None:
        # col = NULL is never true in SQL; use IS NULL instead
        return np.zeros(batch.n_rows, dtype=np.bool_)
    if col.ctype == CanonicalType.DECIMAL and col.offsets is not None \
            and node.op != "~":
        return _eval_cmp_decimal(node, col, valid,
                                 batch.schema.find(node.column))
    if col.offsets is None:
        if col.ctype == CanonicalType.BOOLEAN:
            lit = bool(node.value)
        elif col.ctype == CanonicalType.DATE:
            lit = exact.date_days(node.value, node.column)
        else:
            lit = node.value
        arr = col.data
        try:
            if node.op == "=":
                m = arr == lit
            elif node.op == "!=":
                m = arr != lit
            elif node.op == "<":
                m = arr < lit
            elif node.op == "<=":
                m = arr <= lit
            elif node.op == ">":
                m = arr > lit
            elif node.op == ">=":
                m = arr >= lit
            elif node.op == "~":
                raise ValueError(
                    f"LIKE on non-string column {node.column!r}"
                )
            else:
                raise ValueError(f"unknown op {node.op!r}")
        except TypeError as e:
            raise ValueError(
                f"type mismatch comparing {node.column!r} with {lit!r}"
            ) from e
        return np.asarray(m, dtype=np.bool_) & valid
    return _eval_cmp_str(node, col, valid)


_NUMPY_OPS = {"=": np.equal, "!=": np.not_equal, "<": np.less,
              "<=": np.less_equal, ">": np.greater, ">=": np.greater_equal}


def _eval_cmp_decimal(node: Cmp, col: Column, valid: np.ndarray,
                      cs) -> np.ndarray:
    """A DECIMAL (text) column against a number, as SQL `numeric`
    compares: on integers at the column's scale (predicate/exact.py)."""
    lit = exact.as_decimal(node.value, node.column)
    scaled, scale = exact.decimal_scaled(col, exact.column_scale(cs))
    folded = exact.fold(node.op, lit, scale, 64)
    if folded == exact.NEVER:
        return np.zeros(len(valid), dtype=np.bool_)
    if folded == exact.ALWAYS:
        return valid.copy()
    return _NUMPY_OPS[folded[0]](scaled, folded[1]) & valid


def _gather_eq(col: Column, candidates: np.ndarray, lit: bytes,
               where: str) -> np.ndarray:
    """For candidate rows (all length>=len(lit)), check bytes equal at
    prefix/suffix/exact position. Returns bool per candidate."""
    L = len(lit)
    if L == 0:
        return np.ones(len(candidates), dtype=np.bool_)
    starts = col.offsets[:-1][candidates].astype(np.int64)
    ends = col.offsets[1:][candidates].astype(np.int64)
    if where == "suffix":
        base = ends - L
    else:
        base = starts
    idx = base[:, None] + np.arange(L)
    gathered = col.data[idx]
    return (gathered == np.frombuffer(lit, dtype=np.uint8)).all(axis=1)


def _eval_cmp_str(node: Cmp, col: Column, valid: np.ndarray) -> np.ndarray:
    n = col.n_rows
    lens = (col.offsets[1:] - col.offsets[:-1]).astype(np.int64)
    lit_s = node.value if isinstance(node.value, str) else str(node.value)
    out = np.zeros(n, dtype=np.bool_)

    if node.op == "~":  # LIKE
        pat = lit_s
        if pat.startswith("%") and pat.endswith("%") and len(pat) >= 2:
            needle = pat[1:-1].encode()
            if "%" in pat[1:-1]:
                return _like_general(col, pat, valid)
            # contains: per-candidate python check (rare path)
            cand = np.nonzero(valid & (lens >= len(needle)))[0]
            for i in cand:
                s = bytes(col.data[col.offsets[i]:col.offsets[i + 1]])
                if needle in s:
                    out[i] = True
            return out
        if pat.endswith("%") and "%" not in pat[:-1]:
            lit = pat[:-1].encode()
            cand = np.nonzero(valid & (lens >= len(lit)))[0]
            if len(cand):
                out[cand] = _gather_eq(col, cand, lit, "prefix")
            return out
        if pat.startswith("%") and "%" not in pat[1:]:
            lit = pat[1:].encode()
            cand = np.nonzero(valid & (lens >= len(lit)))[0]
            if len(cand):
                out[cand] = _gather_eq(col, cand, lit, "suffix")
            return out
        if "%" not in pat:
            node = Cmp(node.column, "=", pat)
        else:
            return _like_general(col, pat, valid)

    lit = (node.value if isinstance(node.value, str)
           else str(node.value)).encode()
    if node.op in ("=", "!="):
        cand = np.nonzero(valid & (lens == len(lit)))[0]
        if len(cand):
            out[cand] = _gather_eq(col, cand, lit, "prefix")
        if node.op == "!=":
            out = ~out & valid
        return out
    if node.op in ("<", "<=", ">", ">="):
        # lexicographic compare: decode is unavoidable without a kernel;
        # vectorize via object array comparison
        vals = np.array(
            [bytes(col.data[col.offsets[i]:col.offsets[i + 1]])
             for i in range(n)],
            dtype=object,
        )
        cmp = {"<": vals < lit, "<=": vals <= lit,
               ">": vals > lit, ">=": vals >= lit}[node.op]
        return np.asarray(cmp, dtype=np.bool_) & valid
    raise ValueError(f"unknown string op {node.op!r}")


def _like_general(col: Column, pattern: str, valid: np.ndarray) -> np.ndarray:
    """Multi-wildcard LIKE via regex per row (rare path)."""
    import re as _re

    parts = [_re.escape(p) for p in pattern.split("%")]
    rx = _re.compile("^" + ".*".join(parts) + "$", _re.DOTALL)
    out = np.zeros(col.n_rows, dtype=np.bool_)
    for i in np.nonzero(valid)[0]:
        s = bytes(col.data[col.offsets[i]:col.offsets[i + 1]])
        if rx.match(s.decode("utf-8", errors="replace")):
            out[i] = True
    return out
