"""TPC-H Q3, shipping priority: SEGMENT of 5, DATE a day of March 1995."""
from repro.relational.expr import col
from repro.relational.plan import GroupBy, Join, Limit, Project, Scan, Sort
from repro.tpch.gen import SEGMENTS, date

VALIDATION = {"segment": "BUILDING", "date": "1995-03-15"}


def domain():
    return [{"segment": s, "date": f"1995-03-{d:02d}"}
            for s in SEGMENTS for d in range(1, 32)]


def plan(p):
    cutoff = date(p["date"])
    cust = Scan("customer", filter=col("c_mktsegment") == p["segment"])
    orders = Scan("orders", filter=col("o_orderdate") < cutoff)
    li = Scan("lineitem", filter=col("l_shipdate") > cutoff)
    j = Join(orders, cust, ["o_custkey"], ["c_custkey"])
    j = Join(li, j, ["l_orderkey"], ["o_orderkey"])
    j = Project(j, {
        "l_orderkey": col("l_orderkey"),
        "o_orderdate": col("o_orderdate"),
        "o_shippriority": col("o_shippriority"),
        "rev": col("l_extendedprice") * (1 - col("l_discount")),
    })
    g = GroupBy(j, ["l_orderkey", "o_orderdate", "o_shippriority"],
                [("revenue", "sum", "rev")])
    return Limit(Sort(g, [("revenue", False), ("o_orderdate", True)]), 10)
