"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1> [--rehearse]

Finds the cell in BENCHMARK.json, its deployment in configs/, its
traffic mix in workloads/, the statements in queries/ with their plain
references in reference/, and each metric's reader in metrics/ — by
name, so that a new cell, configuration, statement or metric is new
files and a new entry, never an edit here.

One process: an in-process single-node Coordinator serves
POST /v1/statement to the benchmark's own clients. Without a TPU it
exits non-zero before any result (except under --rehearse, which runs
the configuration's rehearsal schema on whatever backend there is and
marks its line as a rehearsal: form, never speed).
The last line of stdout is the result's JSON object.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()   # set-up is counted from here

import argparse                    # noqa: E402
import importlib.util              # noqa: E402
import json                        # noqa: E402
import os                          # noqa: E402
import shutil                      # noqa: E402
import sys                         # noqa: E402
import tempfile                    # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from benchmarks.harness.files import load_cell, read_json  # noqa: E402


def say(msg: str) -> None:
    print(f"[{time.perf_counter() - _T0:7.2f}] {msg}", file=sys.stderr,
          flush=True)


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def _function_reader(name: str):
    spec = importlib.util.spec_from_file_location(
        f"benchmarks.metrics.{name}",
        os.path.join(HERE, "metrics", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_metric(name: str, run):
    """One metric by its metrics/<name>.json: the sum of ledger
    categories per completed statement (ms), a program counter's
    growth over the window, or the function read(run) of a sibling
    <name>.py. None means nothing to read: the metric is left out."""
    spec = read_json("metrics", f"{name}.json")
    kind = spec["reader"]
    if kind == "ledger":
        if not run.completed:
            return None
        ns = sum(run.ledger_ns.get(c, 0.0) for c in spec["categories"])
        return ns / 1e6 / run.completed
    if kind == "counter":
        return run.counter(spec["counter"])
    if kind == "function":
        return _function_reader(name)(run)
    raise ValueError(f"metrics/{name}.json: reader {kind!r}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="the configuration's rehearsal schema, on any "
                         "backend; the line is marked as a rehearsal")
    args = ap.parse_args(argv)

    try:
        bench, cell, config, traffic, queries, sql_of = load_cell(
            args.workload)
    except KeyError:
        print(f"no cell {args.workload!r} in BENCHMARK.json",
              file=sys.stderr)
        return 2

    import jax
    devices = jax.devices()
    d0 = devices[0]
    say(f"device: platform={d0.platform} kind={d0.device_kind} "
        f"count={len(devices)}")
    if not args.rehearse and (d0.platform != "tpu"
                              or len(devices) < cell["chips"]):
        print(f"benchmark: cell {cell['name']} needs {cell['chips']} TPU "
              f"chip(s); found {len(devices)} x {d0.platform}. No "
              "fallback to another backend.", file=sys.stderr)
        return 2
    peaks = read_json("harness", "peaks.json").get(d0.device_kind)
    if peaks is None and not args.rehearse:
        print(f"benchmark: device kind {d0.device_kind!r} is not in "
              "harness/peaks.json", file=sys.stderr)
        return 2

    import presto_tpu  # noqa: F401 — enables x64 before any array
    from presto_tpu.cache import get_cache_manager
    from presto_tpu.connectors.tpch import TpchGenerator
    from presto_tpu.server.coordinator import Coordinator
    from presto_tpu.telemetry.metrics import METRICS

    from benchmarks.harness import (
        compare, data_rules, reference_data, trace_reduce)
    from benchmarks.harness.window import (
        RunRecord, Tracer, run_window, warm_up)

    schema, scale = (config["rehearse_schema"], config["rehearse_scale"]) \
        if args.rehearse else (config["schema"], config["scale"])
    run = RunRecord(cell["name"], traffic, config, queries, peaks=peaks)

    coord = Coordinator([], config["catalog"], schema, single_node=True,
                        properties=dict(config["properties"]))
    coord.start()
    trace_dir = None
    try:
        # data takes the place of weights: the connector's generator
        # for this schema is rebuilt from --seed before any statement
        # (metadata, split manager and page source share the dict)
        coord._runner().catalogs.connector(config["catalog"]) \
            ._gens[schema] = TpchGenerator(scale, seed=args.seed)

        def compiles_total():
            return METRICS.total("presto_tpu_kernel_compiles_total")

        warm_up(coord.url, traffic, sql_of, compiles_total, say)

        tracer = None
        if args.trace:
            trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
            tracer = Tracer(trace_dir, float(traffic["trace_span_s"]),
                            int(traffic["trace_min_statements"]))
        page = get_cache_manager().page
        counters0 = METRICS.snapshot()
        cache0 = (page.stats.hits, page.stats.misses)
        run.setup_s = time.perf_counter() - _T0
        say(f"window: {args.seconds:g} s, {traffic['clients']} client(s)")
        run.statements, run.window_s = run_window(
            coord.url, traffic, sql_of, args.seed, args.seconds, tracer)
        counters1 = METRICS.snapshot()
        run.counters = {k: v - counters0.get(k, 0.0)
                        for k, v in counters1.items()}
        prefix = 'presto_tpu_ledger_ns_total{category="'
        run.ledger_ns = {k[len(prefix):-2]: v
                         for k, v in run.counters.items()
                         if k.startswith(prefix)}
        run.page_cache = {"hits": page.stats.hits - cache0[0],
                          "misses": page.stats.misses - cache0[1],
                          "bytes": page.bytes}
        thirds = [0, 0, 0]
        for s in run.statements:
            thirds[min(2, int(3 * s.end_s / run.window_s))] += 1
        say(f"window closed: {len(run.statements)} statements in "
            f"{run.window_s:.3f} s (by third: {thirds}); page cache "
            f"{run.page_cache}")
        lat = sorted(s.latency_s for s in run.statements)
        if lat:
            say(f"latency s: least {lat[0]:.4f}, median "
                f"{lat[len(lat) // 2]:.4f}, most {lat[-1]:.4f}")
        if args.trace:
            for s in run.statements:
                q = coord.queries.get(s.qid)
                s.stats = q.stats if q is not None else None
        stats = d0.memory_stats() or {}
        run.memory_peak_bytes = stats.get("peak_bytes_in_use")
    finally:
        coord.stop()

    if args.trace:
        try:
            events = trace_reduce.load(trace_reduce.find_xplane(trace_dir))
            say("trace lines: " + json.dumps(events["lines"]))
            run.trace = trace_reduce.reduce(events)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        say("trace: " + json.dumps(
            {k: v for k, v in (run.trace or {}).items() if k != "marks"}))

    # the plain reference, once the window has closed and the device's
    # peak has been read: the same generated data through pyarrow Acero
    t_ref = time.perf_counter()
    gen = TpchGenerator(scale, seed=args.seed)
    want, tables = reference_data.reference_rows(gen, queries)
    answered = [s for s in run.statements if s.ok]
    numbers, correct, verdicts = compare.judge(
        [(s.name, compare.wire_rows(s.columns, s.data))
         for s in answered], want,
        len(run.statements) - len(answered),
        {n: q.get("order_by") for n, q in queries.items()})
    if "data_rules" in config:
        broken = data_rules.rule_breaks(
            tables, gen, scale,
            read_json("harness", f"{config['data_rules']}.json"))
        numbers["data_rule_breaks"] = {"value": len(broken), "limit": 0}
        correct = correct and not broken
        for name in broken:
            say(f"data rule broken: {name}")
    del tables
    for s, v in zip(answered, verdicts):
        s.correct = v
    say(f"reference and comparison: {time.perf_counter() - t_ref:.1f} s")
    for s in run.statements:
        if not s.ok:
            say(f"failed: {s.name}#{s.seq}: {s.error}")

    wanted = bench["per_layer"] if args.trace else bench["end_to_end"]
    metrics = {}
    for m in wanted:
        if not _reports(m, cell["name"]):
            continue
        value = read_metric(m["name"], run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices),
              "memory_peak_bytes": run.memory_peak_bytes}
    result = {"correct": correct, "attempted": len(run.statements),
              "failed": len(run.statements) - run.completed,
              "metrics": metrics, "device": device}
    if args.trace and run.trace is not None:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        result["breakdown"] = {"device_ops": run.trace["by_module"],
                               "idle_gaps": run.trace["idle_gaps"]}
    if args.rehearse:
        result["rehearsal"] = True
        device["kind"] = "rehearsal"
        device["memory_peak_bytes"] = "rehearsal"
    result["compared"] = numbers
    for name, n in numbers.items():
        print(f"compared {name}: {n['value']!r} (limit {n['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
