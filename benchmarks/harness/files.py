"""Finds a cell's files by the names BENCHMARK.json gives."""

from __future__ import annotations

import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def read_json(*parts):
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


def load_cell(name: str):
    """(BENCHMARK.json, the cell's entry, its configuration, its traffic
    mix, {statement: queries/<statement>.json}, {statement: SQL text}).
    KeyError when BENCHMARK.json has no such cell."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = {w["name"]: w for w in bench["workloads"]}[name]
    config = read_json("configs", f"{cell['config']}.json")
    traffic = read_json("workloads", f"{cell['traffic']}.json")
    queries = {n: read_json("queries", f"{n}.json")
               for n in dict.fromkeys(traffic["statements"])}
    sql_of = {}
    for n in queries:
        with open(os.path.join(BENCH_DIR, "queries", f"{n}.sql")) as f:
            sql_of[n] = f.read()
    return bench, cell, config, traffic, queries, sql_of
