"""Column/row filter transformers (registry/filter, registry/filter_rows)."""

from __future__ import annotations

from typing import Optional

import numpy as np

from transferia_tpu.abstract.schema import TableID, TableSchema
from transferia_tpu.columnar.batch import ColumnBatch
from transferia_tpu.predicate import compile_mask, parse
from transferia_tpu.predicate.exact import coerce_literals
from transferia_tpu.stats.trace import TELEMETRY
from transferia_tpu.transform.base import TransformResult, Transformer
from transferia_tpu.transform.registry import register_transformer


def _parse_table_patterns(tables) -> Optional[list[TableID]]:
    if not tables:
        return None
    return [TableID.parse(t) for t in tables]


def _tables_match(patterns: Optional[list[TableID]], table: TableID) -> bool:
    if patterns is None:
        return True
    return any(table.include_matches(p) for p in patterns)


@register_transformer("filter_columns")
class FilterColumns(Transformer):
    """Keep/drop columns (pkg/transformer/registry/filter columns mode).

    config: include: [...] or exclude: [...]; tables: optional include list.
    Primary-key columns are never dropped (parity with the reference, which
    refuses to strip keys).
    """

    def __init__(self, include: Optional[list[str]] = None,
                 exclude: Optional[list[str]] = None,
                 tables: Optional[list[str]] = None):
        if bool(include) == bool(exclude):
            raise ValueError("filter_columns: exactly one of include/exclude")
        self.include = include
        self.exclude = set(exclude or [])
        self.tables = _parse_table_patterns(tables)

    def _keep(self, schema: TableSchema) -> list[str]:
        out = []
        for c in schema:
            if c.primary_key:
                out.append(c.name)
            elif self.include is not None:
                if c.name in self.include:
                    out.append(c.name)
            elif c.name not in self.exclude:
                out.append(c.name)
        return out

    def suitable(self, table: TableID, schema: TableSchema) -> bool:
        return _tables_match(self.tables, table) and \
            self._keep(schema) != schema.names()

    def result_schema(self, schema: TableSchema) -> TableSchema:
        return schema.project(self._keep(schema))

    def apply(self, batch: ColumnBatch) -> TransformResult:
        return TransformResult(batch.project(self._keep(batch.schema)))


@register_transformer("filter_rows")
class FilterRows(Transformer):
    """WHERE-predicate row filter (registry/filter_rows/filter_rows.go:22-40).

    config: filter: "price > 100 AND category IN ('a','b')";
            tables: optional include list.
    Evaluates one vectorized mask per batch.
    """

    def __init__(self, filter: str, tables: Optional[list[str]] = None):
        self.text = filter
        self.node = parse(filter)
        self.mask_fn = compile_mask(self.node)
        self.tables = _parse_table_patterns(tables)

    def suitable(self, table: TableID, schema: TableSchema) -> bool:
        if not _tables_match(self.tables, table):
            return False
        names = set(schema.names())
        if not self.node.columns() <= names:
            return False
        # a literal its column cannot be compared with (no date against
        # a DATE column, no number against a DECIMAL one) fails the plan
        coerce_literals(self.node, schema)
        return True

    def apply(self, batch: ColumnBatch) -> TransformResult:
        mask = self.mask_fn(batch)
        TELEMETRY.record_filter_rows("host", batch.n_rows)
        if mask.all():
            return TransformResult(batch)
        return TransformResult(batch.filter(mask))

    def describe(self) -> str:
        return f"filter_rows({self.text})"


@register_transformer("filter_rows_by_ids")
class FilterRowsByIds(Transformer):
    """Keep only rows whose key column matches one of the ids
    (registry/filter_rows_by_ids)."""

    def __init__(self, column: str, ids: list,
                 tables: Optional[list[str]] = None):
        self.column = column
        self.ids = set(ids)
        self.tables = _parse_table_patterns(tables)

    def suitable(self, table: TableID, schema: TableSchema) -> bool:
        return _tables_match(self.tables, table) and \
            schema.find(self.column) is not None

    def apply(self, batch: ColumnBatch) -> TransformResult:
        col = batch.column(self.column)
        if col.offsets is None:
            ids = np.array(sorted(
                i for i in self.ids if isinstance(i, (int, float, bool))
            ))
            mask = np.isin(col.data, ids)
            if col.validity is not None:
                mask &= col.validity
        else:
            mask = np.zeros(batch.n_rows, dtype=np.bool_)
            targets = {
                (s.encode() if isinstance(s, str) else bytes(s))
                for s in self.ids
            }
            for i in range(batch.n_rows):
                if col.is_valid(i):
                    raw = bytes(col.data[col.offsets[i]:col.offsets[i + 1]])
                    if raw in targets:
                        mask[i] = True
        return TransformResult(batch.filter(mask))
