"""Plain reference for queries/q3.sql: pyarrow Acero on the generated
columns (a copy of baseline_proxy.q3 and of chip_smoke.py's row
mapping)."""

from benchmarks.harness.reference_data import code, days

TABLES = {
    "customer": ["custkey", "mktsegment"],
    "orders": ["orderkey", "custkey", "orderdate", "shippriority"],
    "lineitem": ["orderkey", "extendedprice", "discount", "shipdate"],
}


def rows(t, gen):
    import pyarrow as pa
    import pyarrow.compute as pc

    seg = code(gen, "customer", "mktsegment", "BUILDING")
    cutoff = days("1995-03-15")
    cust = t["customer"]
    cust = cust.filter(pc.equal(cust["mktsegment"], seg)) \
               .select(["custkey"])
    orders = t["orders"]
    orders = orders.filter(pc.less(orders["orderdate"], cutoff)) \
                   .join(cust, "custkey", join_type="inner")
    li = t["lineitem"]
    li = li.filter(pc.greater(li["shipdate"], cutoff)) \
           .select(["orderkey", "extendedprice", "discount"])
    j = li.join(orders, "orderkey", join_type="inner")
    one = pa.scalar(1.0, j["discount"].type)
    j = j.append_column("rev", pc.multiply(
        j["extendedprice"], pc.subtract(one, j["discount"])))
    res = j.group_by(["orderkey", "orderdate", "shippriority"]) \
           .aggregate([("rev", "sum")]) \
           .sort_by([("rev_sum", "descending"),
                     ("orderdate", "ascending")]).slice(0, 10)
    return [(r["orderkey"], float(r["rev_sum"]), r["orderdate"],
             r["shippriority"]) for r in res.to_pylist()]
