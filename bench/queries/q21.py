"""TPC-H Q21, suppliers who kept orders waiting: NATION of the 25."""
from repro.relational.expr import col
from repro.relational.plan import (
    GroupBy, Join, Limit, Project, Scan, Sort, SubqueryScan,
)
from repro.tpch.gen import NATIONS

VALIDATION = {"nation": "SAUDI ARABIA"}


def domain():
    return [{"nation": n} for n, _ in NATIONS]


def plan(p):
    # G2: suppliers per order (exists other supplier <=> nsupp >= 2)
    l2 = Scan("lineitem", alias="l2")
    g2 = Project(
        GroupBy(l2, ["l2_l_orderkey"], [("nsupp", "nunique", "l2_l_suppkey")],
                having=col("nsupp") >= 2),
        {"g2_orderkey": col("l2_l_orderkey")})
    # G3: late suppliers per order (no other late supplier <=> nlate == 1)
    l3 = Scan("lineitem", alias="l3",
              filter=col("l3_l_receiptdate") > col("l3_l_commitdate"))
    g3 = Project(
        GroupBy(l3, ["l3_l_orderkey"], [("nlate", "nunique", "l3_l_suppkey")],
                having=col("nlate") == 1),
        {"g3_orderkey": col("l3_l_orderkey")})
    li = Scan("lineitem",
              filter=col("l_receiptdate") > col("l_commitdate"))
    orders = Scan("orders", filter=col("o_orderstatus") == "F")
    supp = Scan("supplier")
    nat = Scan("nation", filter=col("n_name") == p["nation"])
    j = Join(li, orders, ["l_orderkey"], ["o_orderkey"])
    j = Join(j, supp, ["l_suppkey"], ["s_suppkey"])
    j = Join(j, nat, ["s_nationkey"], ["n_nationkey"])
    j = Join(j, SubqueryScan(g2, "multi_supp"), ["l_orderkey"],
             ["g2_orderkey"], how="semi")
    j = Join(j, SubqueryScan(g3, "one_late"), ["l_orderkey"],
             ["g3_orderkey"], how="semi")
    g = GroupBy(j, ["s_name"], [("numwait", "count", "")])
    return Limit(Sort(g, [("numwait", False), ("s_name", True)]), 100)
