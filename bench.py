"""Headline benchmark: TPC-H suite (Q1, Q3, Q5, Q6, Q18) at SF1,
end-to-end through the SQL engine.

Prints ONE json line:
  {"metric", "value", "unit", "vs_baseline", "platform", "suite", ...}

- metric/value/vs_baseline keep the round-comparable headline: Q1
  rows/sec (the reference's canonical operator benchmark,
  presto-benchmark HandTpchQuery1.java — scan + filter + project +
  hash aggregation over lineitem).
- "suite" embeds per-query results: rows/sec (input rows / best warm
  wall), speedup vs the per-query Java estimate, and wall seconds. Q3
  and Q5 exercise the join kernels, Q6 the filter/project path
  (HandTpchQuery6.java), Q18 the high-cardinality (~1.5M groups)
  sort-path aggregation.
- "geomean_vs_baseline" is the geometric mean of the per-query
  speedups (the BASELINE.md north-star shape).

Baseline denominator: the reference
publishes no absolute numbers and its Java harness cannot run in this
image (no JVM). The denominator is therefore MEASURED by
baseline_proxy.py — the same five queries on the same generated data
through pyarrow's Acero C++ engine — and recorded in
BASELINE_MEASURED.json; the output line carries
"baseline": "measured:pyarrow-acero-<ver>@<schema>". Only if that
file is absent (or was measured at a different schema) does the old
per-query Java ESTIMATE table apply, and the line then says
"baseline": "estimate:java-guess" so nobody mistakes it for data.

Methodology: per query, the reported number is the WARM rows/s — timed
runs follow a warmup that compiles the kernels and populates the
connector's device-batch scan cache, so data generation and
host->device transfer are excluded (the Java baseline likewise
excludes data-load: the reference's benchmarks pre-load pages via
LocalQueryRunner before timing). "rows" is the sum of the base-table
rows the query scans.

One process, one backend: the suite needs a TPU and exits non-zero
without printing a result when jax finds none, when a query fails, or
when any phase raises. Nothing is retried on another backend and no
file is written.
"""

import json
import math
import os
import sys
import time

SCHEMA = "sf1"          # 6,001,215 lineitem rows at SF1 scaling
BATCH_ROWS = 1 << 20
METRIC = f"tpch_q1_{SCHEMA}_rows_per_sec"
WARM_RUNS = 2

#: per-query single-node Java estimates (input rows/sec) — the
#: UNMEASURED fallback, used only when BASELINE_MEASURED.json is absent
JAVA_BASELINE = {
    "q1": 1.0e7,
    "q3": 6.0e6,
    "q5": 5.0e6,
    "q6": 2.5e7,
    "q18": 5.0e6,
}


def _load_baseline():
    """(per-query rows/s denominators, label). Prefers the measured
    Acero proxy (baseline_proxy.py) at the bench schema."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "BASELINE_MEASURED.json")
    try:
        with open(path) as f:
            m = json.load(f)
        if m.get("schema") != SCHEMA:
            print(f"BASELINE_MEASURED.json schema={m.get('schema')!r} "
                  f"!= bench schema {SCHEMA!r}; falling back to "
                  f"estimates", file=sys.stderr)
        else:
            denom = {q: r["rows_per_sec"]
                     for q, r in m["queries"].items()}
            missing = [q for q in JAVA_BASELINE if q not in denom]
            if not missing:
                label = (f"measured:{m['engine']}-"
                         f"{m['engine_version']}@{m['schema']}")
                return denom, label
            print(f"BASELINE_MEASURED.json missing queries {missing}; "
                  f"falling back to estimates", file=sys.stderr)
    except (OSError, KeyError, json.JSONDecodeError) as e:
        print(f"no usable BASELINE_MEASURED.json ({e}); "
              f"falling back to estimates", file=sys.stderr)
    return dict(JAVA_BASELINE), "estimate:java-guess"


def _queries():
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tests"))
    from tpch_queries import QUERIES
    return {f"q{n}": QUERIES[n] for n in (1, 3, 5, 6, 18)}


def _scanned_rows(gen):
    """Base-table cardinalities, then per-query scanned-row totals."""
    import numpy as np
    L = int(gen.line_counts(np.arange(gen.rows("orders")) + 1).sum())
    O = gen.rows("orders")
    C = gen.rows("customer")
    S = gen.rows("supplier")
    return {
        "q1": L,
        "q3": L + O + C,
        "q5": L + O + C + S + 25 + 5,
        "q6": L,
        "q18": 2 * L + O + C,   # lineitem feeds both the HAVING
                                # subquery and the outer join
    }


def _run_suite(fusion_detail: bool) -> dict:
    """Run the suite in this process; {query -> result dict}. Any
    failure raises."""
    from presto_tpu.runner import LocalRunner
    from presto_tpu.telemetry.metrics import METRICS

    runner = LocalRunner("tpch", SCHEMA)
    runner.session.properties["batch_rows"] = BATCH_ROWS
    # this bench measures KERNEL EXECUTION throughput: the plan and
    # fragment-result caches would make warm runs replay stored
    # batches instead of executing anything. The page-source cache
    # stays ON — it is the successor of the tpch connector's internal
    # device-batch scan cache this methodology always relied on
    # ("warm runs exclude data generation"; serving-path throughput
    # is serving_bench's metric, not this one)
    runner.session.properties["plan_cache_enabled"] = False
    runner.session.properties["fragment_result_cache_enabled"] = False
    rows_of = _scanned_rows(runner.catalogs.connector("tpch")._gens[SCHEMA])

    per_query = {}
    for name, sql in _queries().items():
        fam0 = METRICS.by_label(
            "presto_tpu_kernel_compiles_total", "kernel")
        t0 = time.perf_counter()
        result = runner.execute(sql)  # warmup: compile + first run
        nrows = len(result.rows())    # forces the device fetch
        cold = time.perf_counter() - t0
        # whole-fragment fusion coverage of this query (planner
        # pass report; chains fused vs fallen back — see
        # tools/fusion_report.py for the per-fragment detail,
        # embedded wholesale under --fusion-report)
        fr = getattr(result, "fusion_report", None) or {}
        print(f"{name} cold (compile + datagen + transfer): "
              f"{cold:.3f}s, {nrows} result rows", file=sys.stderr)
        times = []
        for _ in range(WARM_RUNS):
            t0 = time.perf_counter()
            runner.execute(sql).rows()
            times.append(time.perf_counter() - t0)
            print(f"{name} run: {times[-1]:.3f}s", file=sys.stderr)
        best = min(times)
        line = {"rows_per_sec": round(rows_of[name] / best, 1),
                "wall_s": round(best, 3),
                # distinct_compiles per kernel family (cold + warm
                # runs): the compile-amortization trajectory (shape
                # bucketing should drive the warm-run share to zero)
                "distinct_compiles": METRICS.delta_by_label(
                    "presto_tpu_kernel_compiles_total", "kernel", fam0),
                "fused_fragments": fr.get("fused", 0)}
        if fusion_detail:
            line["fusion"] = fr
        per_query[name] = line
    return per_query


def _combine(per_query: dict, platform: str) -> dict:
    denom, baseline_label = _load_baseline()
    suite = {}
    speedups = []
    distinct_compiles = {}
    for name, r in per_query.items():
        sp = r["rows_per_sec"] / denom[name]
        suite[name] = {"rows_per_sec": r["rows_per_sec"],
                       "wall_s": r["wall_s"],
                       "vs_baseline": round(sp, 4)}
        if r.get("distinct_compiles"):
            suite[name]["distinct_compiles"] = r["distinct_compiles"]
            for fam, n in r["distinct_compiles"].items():
                distinct_compiles[fam] = \
                    distinct_compiles.get(fam, 0) + n
        if "fused_fragments" in r:
            suite[name]["fused_fragments"] = r["fused_fragments"]
        if "fusion" in r:
            suite[name]["fusion"] = r["fusion"]
        speedups.append(sp)
    q1 = per_query.get("q1", {"rows_per_sec": 0.0})
    line = {
        "metric": METRIC,
        "value": q1["rows_per_sec"],
        "unit": "rows/s",
        "vs_baseline": round(q1["rows_per_sec"] / denom["q1"], 4),
        "baseline": baseline_label,
        "platform": platform,
        "suite": suite,
        "distinct_compiles": distinct_compiles,
    }
    if speedups:
        line["geomean_vs_baseline"] = round(
            math.exp(sum(math.log(max(s, 1e-9)) for s in speedups)
                     / len(speedups)), 4)
    return line


def main() -> int:
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench.py needs a TPU; jax found "
              f"{devices[0].platform} — no result, no fallback",
              file=sys.stderr)
        return 2
    # --fusion-report: embed the per-query whole-fragment fusion
    # coverage (fused chains + fallback reasons, planner/fusion.py) in
    # each suite entry
    per_query = _run_suite("--fusion-report" in sys.argv[1:])
    line = _combine(per_query, devices[0].platform)
    line["device_kind"] = devices[0].device_kind
    line["device_count"] = len(devices)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
