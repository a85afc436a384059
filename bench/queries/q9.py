"""TPC-H Q9, product type profit: COLOR of the 92 in P_NAME."""
from repro.relational.expr import col, like
from repro.relational.plan import GroupBy, Join, Project, Scan, Sort
from repro.tpch.gen import COLORS
from repro.tpch.queries import year_of

VALIDATION = {"color": "green"}


def domain():
    # the specification's 92 P_NAME words (clause 4.2.3); the
    # generator's word list adds "hotpink", which is not one of them
    return [{"color": c} for c in COLORS if c != "hotpink"]


def plan(p):
    part = Scan("part", filter=like(col("p_name"), f"%{p['color']}%"))
    li = Scan("lineitem")
    supp = Scan("supplier")
    ps = Scan("partsupp")
    orders = Scan("orders")
    nat = Scan("nation")
    j = Join(li, part, ["l_partkey"], ["p_partkey"])
    j = Join(j, supp, ["l_suppkey"], ["s_suppkey"])
    j = Join(j, ps, ["l_partkey", "l_suppkey"],
             ["ps_partkey", "ps_suppkey"])
    j = Join(j, orders, ["l_orderkey"], ["o_orderkey"])
    j = Join(j, nat, ["s_nationkey"], ["n_nationkey"])
    j = Project(j, {
        "nation": col("n_name"),
        "o_year": year_of(col("o_orderdate")),
        "amount": col("l_extendedprice") * (1 - col("l_discount"))
        - col("ps_supplycost") * col("l_quantity"),
    })
    g = GroupBy(j, ["nation", "o_year"], [("sum_profit", "sum", "amount")])
    return Sort(g, [("nation", True), ("o_year", False)])
