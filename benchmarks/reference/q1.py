"""Plain reference for queries/q1.sql: pyarrow Acero on the generated
columns (a copy of baseline_proxy.q1 and of chip_smoke.py's row
mapping)."""

from benchmarks.harness.reference_data import days, dictionary

TABLES = {"lineitem": ["returnflag", "linestatus", "quantity",
                       "extendedprice", "discount", "tax", "shipdate"]}


def rows(t, gen):
    import pyarrow as pa
    import pyarrow.compute as pc

    li = t["lineitem"]
    li = li.filter(pc.less_equal(li["shipdate"], days("1998-09-02")))
    one = pa.scalar(1.0, li["discount"].type)
    disc_price = pc.multiply(li["extendedprice"],
                             pc.subtract(one, li["discount"]))
    charge = pc.multiply(disc_price, pc.add(one, li["tax"]))
    li = li.append_column("disc_price", disc_price)
    li = li.append_column("charge", charge)
    res = li.group_by(["returnflag", "linestatus"]).aggregate([
        ("quantity", "sum"), ("extendedprice", "sum"),
        ("disc_price", "sum"), ("charge", "sum"),
        ("quantity", "mean"), ("extendedprice", "mean"),
        ("discount", "mean"), ("quantity", "count"),
    ]).sort_by([("returnflag", "ascending"),
                ("linestatus", "ascending")])
    rf = dictionary(gen, "lineitem", "returnflag")
    ls = dictionary(gen, "lineitem", "linestatus")
    return [(rf[r["returnflag"]], ls[r["linestatus"]],
             float(r["quantity_sum"]), float(r["extendedprice_sum"]),
             float(r["disc_price_sum"]), float(r["charge_sum"]),
             float(r["quantity_mean"]), float(r["extendedprice_mean"]),
             float(r["discount_mean"]), r["quantity_count"])
            for r in res.to_pylist()]
