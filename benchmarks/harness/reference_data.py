"""Data for the plain reference: the tables a cell's statements read,
made from the same seeded generator as the engine's tpch connector
and held as pyarrow Tables (dictionary VARCHARs as int codes, dates as
int days — a copy of baseline_proxy.load_tables, cut to the columns
asked for and made in parallel chunks so that sf10 fits the run)."""

from __future__ import annotations

import datetime
import importlib
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

_EPOCH = datetime.date(1970, 1, 1)
#: orders (lineitem) or rows (other tables) per generation task: a
#: whole number of the generator's canonical 8192-row chunks
_TASK = 8192 * 16


def days(iso: str) -> int:
    return (datetime.date.fromisoformat(iso) - _EPOCH).days


def dictionary(gen, table: str, column: str):
    for c in gen.schema(table).columns:
        if c.name == column:
            return list(c.dictionary)
    raise KeyError(f"{table}.{column}")


def code(gen, table: str, column: str, value: str) -> int:
    return dictionary(gen, table, column).index(value)


def load_tables(gen, needs: dict):
    """{table: [columns]} -> {table: pyarrow.Table}, doubles as the
    generator makes them (float64)."""
    import pyarrow as pa

    out = {}
    with ThreadPoolExecutor(max_workers=min(8, os.cpu_count() or 1)) as ex:
        for table, columns in needs.items():
            gen.schema(table)  # dictionaries made once, on this thread
            n = gen.rows("orders" if table == "lineitem" else table)

            def part(lo, table=table, columns=columns, n=n):
                data = gen.generate(table, lo, min(lo + _TASK, n))
                return [np.ascontiguousarray(data[c]) for c in columns]

            parts = list(ex.map(part, range(0, n, _TASK)))
            cols = {}
            for i, c in enumerate(columns):
                cols[c] = pa.array(np.concatenate([p[i] for p in parts]))
            out[table] = pa.table(cols)
    return out


def narrow(tables: dict) -> dict:
    """The same tables with every double column cast to float32: the
    control of the output check (tests/control.py), never the
    reference."""
    import pyarrow as pa

    out = {}
    for name, t in tables.items():
        fields = [pa.field(f.name, pa.float32()
                           if f.type == pa.float64() else f.type)
                  for f in t.schema]
        out[name] = t.cast(pa.schema(fields))
    return out


def references(queries: dict):
    """({statement: its reference module}, {table: [columns]} they
    read between them) for the statements' queries/*.json."""
    refs = {n: importlib.import_module(
        f"benchmarks.reference.{q['reference']}")
        for n, q in queries.items()}
    needs: dict = {}
    for mod in refs.values():
        for table, cols in mod.TABLES.items():
            have = needs.setdefault(table, [])
            have.extend(c for c in cols if c not in have)
    return refs, needs


def reference_rows(gen, queries: dict):
    """({statement: rows} of the plain references, the tables they
    read), loaded once for all and always in float64."""
    refs, needs = references(queries)
    tables = load_tables(gen, needs)
    return {n: mod.rows(tables, gen) for n, mod in refs.items()}, tables
