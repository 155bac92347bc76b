"""Plain reference for queries/q5.sql: pyarrow Acero on the generated
columns (a copy of baseline_proxy.q5; nation.name decoded from its
dictionary code, as the wire carries the string)."""

from benchmarks.harness.reference_data import code, days, dictionary

TABLES = {
    "lineitem": ["orderkey", "suppkey", "extendedprice", "discount"],
    "orders": ["orderkey", "custkey", "orderdate"],
    "customer": ["custkey", "nationkey"],
    "supplier": ["suppkey", "nationkey"],
    "nation": ["nationkey", "name", "regionkey"],
    "region": ["regionkey", "name"],
}


def rows(t, gen):
    import pyarrow as pa
    import pyarrow.compute as pc

    asia = code(gen, "region", "name", "ASIA")
    region = t["region"]
    region = region.filter(pc.equal(region["name"], asia)) \
                   .select(["regionkey"])
    nation = t["nation"].select(["nationkey", "regionkey", "name"]) \
        .join(region, "regionkey", join_type="inner") \
        .select(["nationkey", "name"]) \
        .rename_columns(["nationkey", "n_name"])
    supp = t["supplier"].select(["suppkey", "nationkey"]) \
        .join(nation, "nationkey", join_type="inner")
    cust = t["customer"].select(["custkey", "nationkey"]) \
        .rename_columns(["custkey", "c_nationkey"])
    orders = t["orders"]
    orders = orders.filter(pc.and_(
        pc.greater_equal(orders["orderdate"], days("1994-01-01")),
        pc.less(orders["orderdate"], days("1995-01-01")))) \
        .select(["orderkey", "custkey"]) \
        .join(cust, "custkey", join_type="inner") \
        .select(["orderkey", "c_nationkey"])
    li = t["lineitem"].select(
        ["orderkey", "suppkey", "extendedprice", "discount"])
    j = li.join(orders, "orderkey", join_type="inner")
    # c.nationkey = s.nationkey: the supplier join's second key
    j = j.join(supp, keys=["suppkey", "c_nationkey"],
               right_keys=["suppkey", "nationkey"], join_type="inner")
    one = pa.scalar(1.0, j["discount"].type)
    j = j.append_column("rev", pc.multiply(
        j["extendedprice"], pc.subtract(one, j["discount"])))
    res = j.group_by(["n_name"]).aggregate([("rev", "sum")]) \
           .sort_by([("rev_sum", "descending")])
    names = dictionary(gen, "nation", "name")
    return [(names[r["n_name"]], float(r["rev_sum"]))
            for r in res.to_pylist()]
