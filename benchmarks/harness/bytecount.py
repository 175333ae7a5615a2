"""The least bytes a query has to move, from the schema and row counts alone.

Each column counts once at the narrowest power-of-two width that holds its
DECLARED type exactly at this scale, whatever the program stores: so the
count reads the same work whoever implements the query, and a later PR that
narrows a physical type cannot push a roofline share past 100 %.

  decimal(p,s)  4 bytes for p <= 9, 8 for p <= 18, 16 above
  date, int32   4        int64  8
  char(n)       n rounded up to a power of two (a one-character flag: 1)
  key:<table>   4 where that table's row count at this scale fits 31 bits,
                else 8
"""
import re


def _pow2(n):
    w = 1
    while w < n:
        w *= 2
    return w


def width(declared, cardinality):
    """Bytes of one value of a declared type; `cardinality` maps a table to
    its rows at this scale (for the range of a key)."""
    m = re.fullmatch(r"decimal\((\d+),(\d+)\)", declared)
    if m:
        p = int(m.group(1))
        return 4 if p <= 9 else 8 if p <= 18 else 16
    m = re.fullmatch(r"char\((\d+)\)", declared)
    if m:
        return _pow2(int(m.group(1)))
    m = re.fullmatch(r"key:(\w+)", declared)
    if m:
        return 4 if cardinality[m.group(1)] < 2 ** 31 else 8
    if declared in ("date", "int32"):
        return 4
    if declared == "int64":
        return 8
    raise ValueError(f"no width for declared type {declared!r}")


def least_bytes(schema, cardinality, reads, result):
    """Bytes of the columns in `reads` ({table: [column]}), each read once,
    plus the result (`{"rows": n, "columns": [declared type]}`)."""
    total = 0
    for table, columns in reads.items():
        row = sum(width(schema[table][c], cardinality) for c in columns)
        total += row * cardinality[table]
    total += result["rows"] * sum(width(t, cardinality)
                                  for t in result["columns"])
    return total


def scanned_rows(cardinality, reads):
    """Base-table rows the query scans: a constant of the configuration."""
    return sum(cardinality[t] for t in reads)
