"""Plain reference for queries/q18.sql: pyarrow Acero on the generated
columns (a copy of baseline_proxy.q18; customer.name decoded from its
dictionary code, as the wire carries the string)."""

from benchmarks.harness.reference_data import dictionary

TABLES = {
    "customer": ["custkey", "name"],
    "orders": ["orderkey", "custkey", "orderdate", "totalprice"],
    "lineitem": ["orderkey", "quantity"],
}


def rows(t, gen):
    import pyarrow.compute as pc

    li = t["lineitem"].select(["orderkey", "quantity"])
    big = li.group_by(["orderkey"]).aggregate([("quantity", "sum")])
    big = big.filter(pc.greater(big["quantity_sum"], 300.0)) \
             .select(["orderkey"])
    orders = t["orders"] \
        .select(["orderkey", "custkey", "orderdate", "totalprice"]) \
        .join(big, "orderkey", join_type="inner")
    cust = t["customer"].select(["custkey", "name"])
    orders = orders.join(cust, "custkey", join_type="inner")
    j = li.join(orders, "orderkey", join_type="inner")
    res = j.group_by(["name", "custkey", "orderkey", "orderdate",
                      "totalprice"]).aggregate([("quantity", "sum")]) \
           .sort_by([("totalprice", "descending"),
                     ("orderdate", "ascending")]).slice(0, 100)
    names = dictionary(gen, "customer", "name")
    return [(names[r["name"]], r["custkey"], r["orderkey"],
             r["orderdate"], float(r["totalprice"]),
             float(r["quantity_sum"])) for r in res.to_pylist()]
