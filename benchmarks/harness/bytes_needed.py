"""The least bytes a statement makes the device read: every column its
scans deliver, once. It counts the work, not any kernel's traffic, so
it reads the same whatever implements the statement."""

from __future__ import annotations

import functools

from benchmarks.harness.files import read_json


@functools.cache
def _widths():
    return read_json("harness", "column_widths.json")


def row_bytes(table: str, columns) -> int:
    """Bytes one row of `columns` of `table` takes on the device."""
    w = _widths()
    types = w["columns"][table]
    return sum(w["type_bytes"][types[c]] + w["mask_bytes"]
               for c in columns)


def bytes_needed(scans: dict, scan_rows: dict) -> int:
    """`scans`: {table: [columns]} of the statement (its queries/*.json);
    `scan_rows`: {table: rows its scan operators emitted}, a count from
    the statement's operator stats, so pushdown is respected."""
    return sum(row_bytes(t, cols) * scan_rows.get(t, 0)
               for t, cols in scans.items())


def scan_rows(stats: dict) -> dict:
    """{table: rows emitted} from a statement's server-side stats tree
    (operators named scan:<table>)."""
    out: dict = {}
    for task in (stats or {}).get("tasks", []):
        for pipeline in task.get("pipelines", []):
            for op in pipeline:
                name = op.get("name", "")
                if name.startswith("scan:"):
                    t = name[5:]
                    out[t] = out.get(t, 0) + int(op.get("output_rows", 0))
    return out
