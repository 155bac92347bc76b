"""The plain reference for TPC-H answers: the same five queries on the
same generated data, executed by pyarrow's Acero engine, independent
of the engine under test. The reference's own harness (presto-benchmark
BenchmarkSuite / HandTpchQuery1, see BASELINE.md) cannot run in this
image (no JVM, no network).

- dictionary-encoded VARCHAR filters compare int codes, not strings
  (what the Java engine's dictionary blocks do);
- dates stay int days.

Readers: tests/test_baseline_proxy.py cross-checks every query here
against the SQL engine at sf0_01; tests/test_mesh_served.py and
chip_smoke.py compare served answers with them. It times nothing.
"""

from __future__ import annotations

import datetime

import numpy as np

_EPOCH = datetime.date(1970, 1, 1)


def _days(iso: str) -> int:
    y, m, d = map(int, iso.split("-"))
    return (datetime.date(y, m, d) - _EPOCH).days


def _code(gen, table: str, column: str, value: str) -> int:
    """Dictionary code of `value` in a dict-encoded VARCHAR column."""
    for c in gen.schema(table).columns:
        if c.name == column:
            return list(c.dictionary).index(value)
    raise KeyError(f"{table}.{column}")


def load_tables(gen, names):
    """Materialize tables as pyarrow Tables (dict VARCHARs stay as int
    codes; dates stay as int days) — the same physical shapes the
    engine's scan produces, so neither side pays a decode the other
    doesn't."""
    import pyarrow as pa

    out = {}
    for name in names:
        n = gen.rows(name) if name != "lineitem" else None
        cols = {}
        if name == "lineitem":
            # generate() takes an ORDER range for lineitem (rows
            # expand ~4x per order)
            data = gen.generate("lineitem", 0, gen.rows("orders"))
        else:
            data = gen.generate(name, 0, n)
        for cname, arr in data.items():
            cols[cname] = pa.array(np.ascontiguousarray(arr))
        out[name] = pa.table(cols)
    return out


# --- the five suite queries, Acero-side ---------------------------------

def q1(t, gen):
    import pyarrow.compute as pc

    li = t["lineitem"]
    li = li.filter(pc.less_equal(li["shipdate"], _days("1998-09-02")))
    one_minus = pc.subtract(1.0, li["discount"])
    disc_price = pc.multiply(li["extendedprice"], one_minus)
    charge = pc.multiply(disc_price, pc.add(1.0, li["tax"]))
    li = li.append_column("disc_price", disc_price)
    li = li.append_column("charge", charge)
    res = li.group_by(["returnflag", "linestatus"]).aggregate([
        ("quantity", "sum"), ("extendedprice", "sum"),
        ("disc_price", "sum"), ("charge", "sum"),
        ("quantity", "mean"), ("extendedprice", "mean"),
        ("discount", "mean"), ("quantity", "count"),
    ])
    return res.sort_by([("returnflag", "ascending"),
                        ("linestatus", "ascending")])


def q3(t, gen):
    import pyarrow.compute as pc

    seg = _code(gen, "customer", "mktsegment", "BUILDING")
    cutoff = _days("1995-03-15")
    cust = t["customer"]
    cust = cust.filter(pc.equal(cust["mktsegment"], seg)) \
               .select(["custkey"])
    orders = t["orders"]
    orders = orders.filter(pc.less(orders["orderdate"], cutoff)) \
                   .select(["orderkey", "custkey", "orderdate",
                            "shippriority"])
    orders = orders.join(cust, "custkey", join_type="inner")
    li = t["lineitem"]
    li = li.filter(pc.greater(li["shipdate"], cutoff)) \
           .select(["orderkey", "extendedprice", "discount"])
    j = li.join(orders, "orderkey", join_type="inner")
    rev = pc.multiply(j["extendedprice"],
                      pc.subtract(1.0, j["discount"]))
    j = j.append_column("rev", rev)
    res = j.group_by(["orderkey", "orderdate", "shippriority"]) \
           .aggregate([("rev", "sum")])
    return res.sort_by([("rev_sum", "descending"),
                        ("orderdate", "ascending")]).slice(0, 10)


def q5(t, gen):
    import pyarrow.compute as pc

    asia = _code(gen, "region", "name", "ASIA")
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
        pc.greater_equal(orders["orderdate"], _days("1994-01-01")),
        pc.less(orders["orderdate"], _days("1995-01-01")))) \
        .select(["orderkey", "custkey"])
    orders = orders.join(cust, "custkey", join_type="inner") \
        .select(["orderkey", "c_nationkey"])
    li = t["lineitem"].select(
        ["orderkey", "suppkey", "extendedprice", "discount"])
    j = li.join(orders, "orderkey", join_type="inner")
    # c.nationkey = s.nationkey folds into the supplier join keys
    j = j.join(supp, keys=["suppkey", "c_nationkey"],
               right_keys=["suppkey", "nationkey"], join_type="inner")
    rev = pc.multiply(j["extendedprice"],
                      pc.subtract(1.0, j["discount"]))
    j = j.append_column("rev", rev)
    res = j.group_by(["n_name"]).aggregate([("rev", "sum")])
    return res.sort_by([("rev_sum", "descending")])


def q6(t, gen):
    import pyarrow.compute as pc

    li = t["lineitem"]
    m = pc.and_(
        pc.and_(pc.greater_equal(li["shipdate"], _days("1994-01-01")),
                pc.less(li["shipdate"], _days("1995-01-01"))),
        pc.and_(
            pc.and_(pc.greater_equal(li["discount"], 0.05),
                    pc.less_equal(li["discount"], 0.07)),
            pc.less(li["quantity"], 24.0)))
    li = li.filter(m)
    import pyarrow as pa
    s = pc.sum(pc.multiply(li["extendedprice"], li["discount"]))
    return pa.table({"revenue": [s.as_py()]})


def q18(t, gen):
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
                      "totalprice"]).aggregate([("quantity", "sum")])
    return res.sort_by([("totalprice", "descending"),
                        ("orderdate", "ascending")]).slice(0, 100)


QUERIES = {"q1": q1, "q3": q3, "q5": q5, "q6": q6, "q18": q18}
TABLES = ["lineitem", "orders", "customer", "supplier", "nation",
          "region"]
