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


def _pad_like(trees, s):
    """Tree `s` of host buffer trees with every array padded to the
    longest of its position: zeros, and an offsets array with its last
    entry (so that padding rows stay empty)."""
    t = trees[s]
    if isinstance(t, dict):
        return {k: (_pad_offsets([u[k] for u in trees], s) if k == "offsets"
                    else _pad_like([u[k] for u in trees], s)) for k in t}
    if isinstance(t, (list, tuple)):
        return [_pad_like([u[i] for u in trees], s) for i in range(len(t))]
    if not isinstance(t, np.ndarray):
        return t
    cap = max(u.shape[0] for u in trees)
    if t.shape[0] == cap:
        return t
    return np.concatenate([t, np.zeros((cap - t.shape[0],) + t.shape[1:],
                                       t.dtype)])


def _pad_offsets(arrs, s):
    t = arrs[s]
    if t is None:
        return None
    cap = max(u.shape[0] for u in arrs)
    if t.shape[0] == cap:
        return t
    return np.concatenate([t, np.full(cap - t.shape[0], t[-1], t.dtype)])


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

    @staticmethod
    def sharded_from_arrow(at, devices, batch_rows: int):
        """The rows of `at` divided evenly over `devices` (a quarter each
        to within a row), each share in batches of `batch_rows`. Returns,
        for every batch position, one (Table, rows, mask) a device: the
        shares of one position are padded on the host to the same
        capacities, so the same program signature fits every device, and
        each share goes to its device in one device_put."""
        import jax
        n = len(devices)
        names = list(at.schema.names)
        base, extra = divmod(at.num_rows, n)
        lens = [base + (s < extra) for s in range(n)]
        starts = [sum(lens[:s]) for s in range(n)]
        per = max(1, int(batch_rows))
        out = []
        for j in range(max(1, -(-lens[0] // per))):
            rows = [max(0, min(per, lens[s] - j * per)) for s in range(n)]
            host = [[Column.host_from_arrow(at.column(i).slice(
                starts[s] + j * per, rows[s])) for i in range(len(names))]
                for s in range(n)]
            group = []
            for s, dev in enumerate(devices):
                bufs = [_pad_like([host[t][i][2] for t in range(n)], s)
                        for i in range(len(names))]
                cap = bufs[0]["validity"].shape[0] if bufs else 0
                dbufs, mask = jax.device_put(
                    (bufs, np.arange(cap) < rows[s]), dev)
                cols = [Column.build(host[s][i][0], rows[s], d)
                        for i, d in enumerate(dbufs)]
                group.append((Table(names, cols), rows[s], mask))
            out.append(group)
        return out

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
