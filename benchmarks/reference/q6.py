"""Plain reference for queries/q6.sql: pyarrow Acero on the generated
columns (a copy of baseline_proxy.q6)."""

from benchmarks.harness.reference_data import days

TABLES = {"lineitem": ["shipdate", "discount", "quantity",
                       "extendedprice"]}


def rows(t, gen):
    import pyarrow as pa
    import pyarrow.compute as pc

    li = t["lineitem"]
    f = li["discount"].type

    def lit(x):
        return pa.scalar(x, f)
    m = pc.and_(
        pc.and_(pc.greater_equal(li["shipdate"], days("1994-01-01")),
                pc.less(li["shipdate"], days("1995-01-01"))),
        pc.and_(
            pc.and_(pc.greater_equal(li["discount"], lit(0.05)),
                    pc.less_equal(li["discount"], lit(0.07))),
            pc.less(li["quantity"], lit(24.0))))
    li = li.filter(m)
    s = pc.sum(pc.multiply(li["extendedprice"], li["discount"]))
    return [(float(s.as_py()),)]
