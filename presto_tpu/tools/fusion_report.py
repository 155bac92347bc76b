"""Per-query whole-fragment fusion coverage report.

The fusion pass (planner/fusion.py) falls back SILENTLY by design —
an ineligible chain simply keeps its unfused operator pipeline, and
nothing fails. That makes coverage loss invisible: a planner change
that turns every serving-mix aggregation into a fallback would ship
green. This tool makes the coverage explicit: for each query it lists
every candidate fragment chain with either the fused operator name or
the fallback reason, exactly as the planner recorded them.

Usage:
    python -m presto_tpu.tools.fusion_report                 # mix
    python -m presto_tpu.tools.fusion_report --sql "SELECT ..."
    python -m presto_tpu.tools.fusion_report --schema sf0_1 \
        --mix q1,q3,q6,q13 --assert-fused --json

`--assert-fused` exits non-zero unless EVERY query fuses at least one
leaf fragment — the serving-mix regression guard (the same check runs
in the fast test tier; docs/FRAGMENT_COMPILATION.md)."""

from __future__ import annotations

import argparse
import json
from typing import Dict, List, Optional, Sequence

DEFAULT_MIX = ("q1", "q3", "q6", "q13")


def query_fusion(runner, sql: str) -> dict:
    """Execute `sql` and return its fusion report ({} when the pass
    was disabled — e.g. fragment_fusion_enabled=false)."""
    res = runner.execute(sql)
    return getattr(res, "fusion_report", None) or {
        "fragments": [], "fused": 0, "fallback": {}}


def build_report(runner, statements: Dict[str, str]) -> dict:
    """{query name -> fusion report} + roll-up totals."""
    queries = {}
    for name, sql in statements.items():
        queries[name] = query_fusion(runner, sql)
    fallback: Dict[str, int] = {}
    for r in queries.values():
        for reason, n in r["fallback"].items():
            fallback[reason] = fallback.get(reason, 0) + n
    return {
        "queries": queries,
        "fused_total": sum(r["fused"] for r in queries.values()),
        "fallback_total": fallback,
        "unfused_queries": sorted(
            n for n, r in queries.items() if r["fused"] == 0),
    }


def render(report: dict) -> str:
    lines: List[str] = []
    for name, r in report["queries"].items():
        lines.append(f"{name}: {r['fused']} fused fragment(s)")
        for e in r["fragments"]:
            chain = " -> ".join([e["source"]] + e["chain"]
                                + ([e["terminal"]] if e["terminal"]
                                   else []))
            if e["fused"] and e["reason"]:
                # partial: the chain collapsed but its fold terminal
                # was deliberately kept out (e.g. selective_chain)
                lines.append(f"  PARTIAL  {chain}  =>  {e['fused']}"
                             f"  [terminal kept: {e['reason']}]")
            elif e["fused"]:
                lines.append(f"  FUSED    {chain}  =>  {e['fused']}")
            else:
                lines.append(f"  fallback {chain}  "
                             f"[{e['reason']}]")
    lines.append(f"total fused: {report['fused_total']}; "
                 f"fallbacks: {report['fallback_total'] or 'none'}")
    if report["unfused_queries"]:
        lines.append("queries with NO fused fragment: "
                     + ", ".join(report["unfused_queries"]))
    return "\n".join(lines)


def _mix_statements(mix: Sequence[str]) -> Dict[str, str]:
    from presto_tpu.tools.verifier import load_suite
    suite = load_suite("tpch")
    missing = [m for m in mix if m not in suite]
    if missing:
        raise ValueError(f"unknown mix queries {missing}")
    return {m: suite[m] for m in mix}


def main(argv: Optional[List[str]] = None) -> int:
    p = argparse.ArgumentParser(
        description="Whole-fragment fusion coverage per query")
    p.add_argument("--catalog", default="tpch")
    p.add_argument("--schema", default="tiny")
    p.add_argument("--mix", default=",".join(DEFAULT_MIX),
                   help="TPC-H suite query names (default serving mix)")
    p.add_argument("--sql", default=None,
                   help="report a single ad-hoc statement instead")
    p.add_argument("--json", action="store_true")
    p.add_argument("--assert-fused", action="store_true",
                   help="exit 1 unless every query fuses >= 1 "
                        "fragment")
    args = p.parse_args(argv)

    from presto_tpu.runner.local import LocalRunner
    runner = LocalRunner(args.catalog, args.schema, properties={
        # the report must observe real planning, not cache replays
        "plan_cache_enabled": False,
        "fragment_result_cache_enabled": False,
        "page_source_cache_enabled": False,
    })
    if args.sql:
        statements = {"sql": args.sql}
    else:
        statements = _mix_statements(
            [m.strip() for m in args.mix.split(",") if m.strip()])
    report = build_report(runner, statements)
    print(json.dumps(report, indent=1) if args.json
          else render(report))
    if args.assert_fused and report["unfused_queries"]:
        print("ASSERTION FAILED: queries without fused fragments: "
              + ", ".join(report["unfused_queries"]))
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
