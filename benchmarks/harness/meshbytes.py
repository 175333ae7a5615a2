"""The least bytes one chip has to send over the interconnect for a query
whose tables are row-sharded over `chips` chips, from the schema, the row
counts and the query's selectivities at its validation parameters alone:
never from the program's counters, so that the count reads the same work
whatever implements the exchange.

Q3 (clause 2.4.3, SEGMENT = BUILDING, DATE = 1995-03-15) joins customer to
orders on the customer key and the result to lineitem on the order key. No
two of the three tables are sharded on a common key, so each join's two
inputs cross the mesh once, hashed on the join key, after their own filters
and with only the columns the rest of the query reads, each at the
narrowest width that holds its declared type (harness/bytecount.py). Of
rows spread evenly over n chips and hashed uniformly, (n - 1) / n leave
their chip. The group-by needs no exchange of its own (its key holds the
order key, on which the second join already partitioned), so it counts 0:
a least count reads low, never high.
"""
import datetime

from benchmarks.harness import bytecount

# clause 4.2.3: o_orderdate uniform in [STARTDATE, ENDDATE - 151 days],
# l_shipdate = o_orderdate + uniform 1..121, c_mktsegment one of 5 segments
_START, _END = datetime.date(1992, 1, 1), datetime.date(1998, 8, 2)
_Q3_DATE = datetime.date(1995, 3, 15)
_SEGMENTS = 5
_SHIP_LAG = range(1, 122)


def q3_selectivities():
    """The share of each table's rows that survives Q3's own filter on it,
    and of the orders that survive, the share whose customer does too."""
    days = (_END - _START).days + 1
    before = (_Q3_DATE - _START).days           # order dates < DATE
    shipped_after = sum(
        min(max(days - (before + 1 - lag), 0), days) for lag in _SHIP_LAG
    ) / (len(_SHIP_LAG) * days)
    return {"customer": 1.0 / _SEGMENTS, "orders": before / days,
            "lineitem": shipped_after, "orders_of_segment": 1.0 / _SEGMENTS}


def q3_exchanged(schema, cardinality):
    """[(what crosses, rows, bytes a row)] for the four exchanges of Q3."""
    sel = q3_selectivities()

    def row(table, columns):
        return sum(bytecount.width(schema[table][c], cardinality)
                   for c in columns)
    orders = cardinality["orders"] * sel["orders"]
    return [
        ("customer on c_custkey", cardinality["customer"] * sel["customer"],
         row("customer", ["c_custkey"])),
        ("orders on o_custkey", orders,
         row("orders", ["o_custkey", "o_orderkey", "o_orderdate",
                        "o_shippriority"])),
        ("customer-orders on o_orderkey", orders * sel["orders_of_segment"],
         row("orders", ["o_orderkey", "o_orderdate", "o_shippriority"])),
        ("lineitem on l_orderkey", cardinality["lineitem"] * sel["lineitem"],
         row("lineitem", ["l_orderkey", "l_extendedprice", "l_discount"])),
    ]


def q3_least_bytes_per_chip(schema, cardinality, chips):
    """Bytes one of `chips` chips sends in one execution of Q3, at least."""
    if chips < 2:
        return 0.0
    total = sum(rows * width for _, rows, width in
                q3_exchanged(schema, cardinality))
    return total / chips * (chips - 1) / chips
