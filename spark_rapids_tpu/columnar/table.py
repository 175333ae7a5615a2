"""Device table: an ordered set of equal-length Columns (a columnar batch).

Analog of the reference's cudf `Table` + Spark `ColumnarBatch` of
GpuColumnVector (reference: GpuColumnVector.java `from(Table)`).
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from . import dtypes as dt
from .column import Column

__all__ = ["Table", "Schema", "Field"]


class Field:
    def __init__(self, name: str, dtype: dt.DataType, nullable: bool = True):
        self.name = name
        self.dtype = dtype
        self.nullable = nullable

    def __repr__(self):
        return f"{self.name}:{self.dtype}"

    def __eq__(self, other):
        return (isinstance(other, Field) and other.name == self.name
                and other.dtype == self.dtype)


class Schema:
    def __init__(self, fields: Sequence[Field]):
        self.fields = list(fields)

    @property
    def names(self) -> List[str]:
        return [f.name for f in self.fields]

    def index_of(self, name: str) -> int:
        for i, f in enumerate(self.fields):
            if f.name == name:
                return i
        raise KeyError(name)

    def __getitem__(self, i):
        return self.fields[i]

    def __len__(self):
        return len(self.fields)

    def __repr__(self):
        return "Schema(" + ", ".join(map(repr, self.fields)) + ")"

    def __eq__(self, other):
        return isinstance(other, Schema) and other.fields == self.fields

    def to_arrow(self):
        import pyarrow as pa
        return pa.schema([(f.name, dt.to_arrow(f.dtype)) for f in self.fields])

    @staticmethod
    def from_arrow(schema) -> "Schema":
        return Schema([Field(f.name, dt.from_arrow(f.type), f.nullable)
                       for f in schema])


class Table:
    """Immutable batch of columns. All columns share `num_rows`."""

    def __init__(self, names: Sequence[str], columns: Sequence[Column]):
        assert len(names) == len(columns)
        if columns:
            n = columns[0].length
            for c in columns:
                assert c.length == n, "ragged table"
        self.names = list(names)
        self.columns = list(columns)

    # ------------------------------------------------------------------
    @property
    def num_rows(self) -> int:
        return self.columns[0].length if self.columns else 0

    @property
    def num_columns(self) -> int:
        return len(self.columns)

    @property
    def schema(self) -> Schema:
        return Schema([Field(n, c.dtype) for n, c in
                       zip(self.names, self.columns)])

    @property
    def nbytes(self) -> int:
        return sum(c.nbytes for c in self.columns)

    def column(self, key) -> Column:
        if isinstance(key, int):
            return self.columns[key]
        return self.columns[self.names.index(key)]

    def select(self, names: Sequence[str]) -> "Table":
        return Table(list(names), [self.column(n) for n in names])

    def with_column(self, name: str, col: Column) -> "Table":
        names, cols = list(self.names), list(self.columns)
        if name in names:
            cols[names.index(name)] = col
        else:
            names.append(name)
            cols.append(col)
        return Table(names, cols)

    def rename(self, names: Sequence[str]) -> "Table":
        return Table(list(names), self.columns)

    def __repr__(self):
        return f"Table({self.schema}, rows={self.num_rows})"

    # ------------------------------------------------------------------
    @staticmethod
    def from_pydict(data: Dict[str, Tuple[Sequence, dt.DataType]]) -> "Table":
        names, cols = [], []
        for name, (values, dtype) in data.items():
            names.append(name)
            cols.append(Column.from_pylist(values, dtype))
        return Table(names, cols)

    @staticmethod
    def from_arrow(at) -> "Table":
        """Build from a pyarrow Table or RecordBatch.

        All column buffers transfer in ONE device_put — per-transfer
        latency dominates small host->device copies, so batching transfers
        is the H2D analog of the reference's single readParquet H2D copy.
        """
        import jax
        names = list(at.schema.names)
        host = [Column.host_from_arrow(at.column(i))
                for i in range(len(names))]
        dev = jax.device_put([bufs for _, _, bufs in host])
        cols = [Column.build(dtype, n, d)
                for (dtype, n, _), d in zip(host, dev)]
        return Table(names, cols)

    def to_arrow(self):
        """One device_get for every buffer of every column (per-transfer
        latency dominates small device->host copies)."""
        import pyarrow as pa
        from ..utils.transfer import fetch
        host = fetch([c.device_buffers() for c in self.columns])
        arrs = [Column.arrow_from_host(c.dtype, c.length, b)
                for c, b in zip(self.columns, host)]
        return pa.Table.from_arrays(arrs, names=list(self.names))

    def to_pydict(self) -> Dict[str, list]:
        return {n: c.to_pylist() for n, c in zip(self.names, self.columns)}

    def to_pylist(self) -> List[tuple]:
        cols = [c.to_pylist() for c in self.columns]
        return list(zip(*cols)) if cols else []
