"""TPC-H Q5, local supplier volume: REGION of 5, DATE 1 January of
1993-1997."""
from repro.relational.expr import col
from repro.relational.plan import GroupBy, Join, Project, Scan, Sort
from repro.tpch.gen import REGIONS, date

VALIDATION = {"region": "ASIA", "year": 1994}


def domain():
    return [{"region": r, "year": y}
            for r in REGIONS for y in range(1993, 1998)]


def plan(p):
    lo = date(f"{p['year']}-01-01")
    hi = date(f"{p['year'] + 1}-01-01")
    cust = Scan("customer")
    orders = Scan("orders", filter=(col("o_orderdate") >= lo)
                  & (col("o_orderdate") < hi))
    li = Scan("lineitem")
    supp = Scan("supplier")
    nat = Scan("nation")
    reg = Scan("region", filter=col("r_name") == p["region"])
    j = Join(orders, cust, ["o_custkey"], ["c_custkey"])
    j = Join(li, j, ["l_orderkey"], ["o_orderkey"])
    j = Join(j, supp, ["l_suppkey", "c_nationkey"],
             ["s_suppkey", "s_nationkey"])
    j = Join(j, nat, ["s_nationkey"], ["n_nationkey"])
    j = Join(j, reg, ["n_regionkey"], ["r_regionkey"])
    j = Project(j, {
        "n_name": col("n_name"),
        "rev": col("l_extendedprice") * (1 - col("l_discount")),
    })
    g = GroupBy(j, ["n_name"], [("revenue", "sum", "rev")])
    return Sort(g, [("revenue", False)])
