"""TPC-H Q17, small-quantity-order revenue: BRAND Brand#MN with M and N
in 1-5, CONTAINER of the 40."""
from repro.relational.expr import col, lit
from repro.relational.plan import GroupBy, Join, Project, Scan, SubqueryScan
from repro.tpch.gen import CONT_S1, CONT_S2

VALIDATION = {"brand": "Brand#23", "container": "MED BOX"}


def domain():
    return [{"brand": f"Brand#{m}{n}", "container": f"{c1} {c2}"}
            for m in range(1, 6) for n in range(1, 6)
            for c1 in CONT_S1 for c2 in CONT_S2]


def plan(p):
    part = Scan("part", filter=(col("p_brand") == p["brand"])
                & (col("p_container") == p["container"]))
    li = Scan("lineitem")
    li2 = Scan("lineitem", alias="l2")
    avg_q = Project(
        GroupBy(li2, ["l2_l_partkey"], [("avg_qty", "mean", "l2_l_quantity")]),
        {"avg_partkey": col("l2_l_partkey"), "avg_qty": col("avg_qty")})
    sub = SubqueryScan(avg_q, "avgqty")
    j = Join(li, part, ["l_partkey"], ["p_partkey"])
    j = Join(j, sub, ["l_partkey"], ["avg_partkey"],
             extra=col("l_quantity") < lit(0.2) * col("avg_qty"))
    g = GroupBy(j, [], [("total", "sum", "l_extendedprice")])
    return Project(g, {"avg_yearly": col("total") / 7.0})
