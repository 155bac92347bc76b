"""A seed-independent anchor for the generated data. The plain
reference reads the tables from the program's own generator, so a
fault in datagen would move both sides alike; what the source fixes
whatever the seed (table sizes, column domains: a rules file named by
the configuration's `data_rules`) is checked on the reference's tables
and counted in `data_rule_breaks`, limit 0."""

from __future__ import annotations

import numpy as np
import pyarrow.compute as pc

from benchmarks.harness.reference_data import days, dictionary


def rule_breaks(tables: dict, gen, scale: float, rules: dict) -> list:
    """The rules that the loaded `tables` ({table: pyarrow.Table}, as
    reference_data.load_tables makes them) break, as short names."""
    broken = []
    rows_of = {t: round(n * scale)
               for t, n in rules["rows_per_unit_scale"].items()}

    def check(name, ok):
        if not ok:
            broken.append(name)

    for table, t in tables.items():
        if table in rows_of:
            check(f"{table}.rows", t.num_rows == rows_of[table])
        per = rules["rows_per_row_of"].get(table)
        if per:
            n = rows_of[per["table"]]
            check(f"{table}.rows",
                  per["min"] * n <= t.num_rows <= per["max"] * n)
        for column, rule in rules["columns"].get(table, {}).items():
            if column not in t.column_names:
                continue
            # every rule but `unique` reads the distinct values alone
            v = pc.unique(t[column]).to_numpy()
            name = f"{table}.{column}"
            if not len(v):
                check(f"{name}.rows", False)
                continue
            if "values" in rule:
                names = dictionary(gen, table, column)
                seen = {names[c] if 0 <= c < len(names) else c
                        for c in v.tolist()}
                check(f"{name}.values", seen <= set(rule["values"]))
                continue
            lo, hi = rule.get("min"), rule.get("max")
            if "max_rows_of" in rule:
                hi = rows_of[rule["max_rows_of"]]
            lo, hi = (days(x) if isinstance(x, str) else x
                      for x in (lo, hi))
            if lo is not None:
                check(f"{name}.min", v.min() >= lo)
            if hi is not None:
                check(f"{name}.max", v.max() <= hi)
            if "step" in rule:
                steps = v / rule["step"]
                check(f"{name}.step",
                      np.abs(steps - np.rint(steps)).max() < 1e-6)
            if rule.get("unique"):
                check(f"{name}.unique", len(v) == t.num_rows)
    return broken
