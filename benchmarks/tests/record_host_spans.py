"""Prove once, on the chip, that the host's spans and the device's
modules share jax.profiler's clock, and say what the host was doing
while the device sat idle:

    python3 benchmarks/tests/record_host_spans.py --workload <cell> --seed <n> [--out <dir>]

Drives a few statements of the cell as run.py does (the harness's own
Tracer and run_window), keeps the .xplane.pb under --out, and prints

 1. the host-to-device clock offset: for every `kernel:<family>` host
    span, the first device module of that family that starts after it
    (device clock) minus the span's start (host clock). A launch takes
    some microseconds and never negative time, so the least difference
    is the offset, give or take a launch;
 2. the device's idle seconds inside the traced window, each gap
    charged to the innermost `ledger:<category>` / `kernel:<family>` /
    `compile:<family>` span open on a host thread at the gap's middle
    (moved onto the host's clock by the offset; of several threads'
    spans the one opened last), and the share with no span open
    (between statements no ledger is installed).

trace_reduce.load keeps only the harness's own `bench:` marks, so the
benchmark cannot charge a gap this way yet; `charge_gaps` below is the
function a later benchmark issue can move into trace_reduce.reduce.
Its arithmetic is checked on hand-made tuples by
test_device_families.py. The last line of stdout is a JSON object."""

from __future__ import annotations

import argparse
import bisect
import json
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

SPAN_PREFIXES = ("ledger:", "kernel:", "compile:")
NO_SPAN = "(no span open)"


def innermost_timeline(spans):
    """([t], [(name, its start) or None]): from each t on, the
    innermost of one thread's `spans` [(start, end, name)] that is
    open. Spans of one thread nest (they are context managers), so the
    innermost is the one opened last."""
    times, opened, stack = [], [], []      # stack of (end, name, start)

    def close_until(t):
        while stack and stack[-1][0] <= t:
            end = stack.pop()[0]
            times.append(end)
            opened.append(stack[-1][1:] if stack else None)
    for start, end, name in sorted(spans, key=lambda x: (x[0], -x[1])):
        close_until(start)
        stack.append((end, name, start))
        times.append(start)
        opened.append((name, start))
    close_until(float("inf"))
    return times, opened


def charge_gaps(gaps, threads, offset_ns=0.0):
    """{label: ns}. Each of `gaps` [(start, end)] (device clock) is
    charged whole to one span: the innermost one open at the gap's
    middle, looked up at `middle - offset_ns` in `threads` {thread:
    [(start, end, name)]} (host clock; offset = device clock minus host
    clock). Where several threads have a span open, the one opened
    last takes the gap: a statement's runner thread sits in one long
    `ledger:driver.quantum` frame while an executor worker steps the
    driver, and it is the worker's span that says what the host was
    doing. With none open, the gap goes to NO_SPAN."""
    timelines = [innermost_timeline(spans) for spans in threads.values()]
    charged: dict = {}
    for start, end in gaps:
        mid = (start + end) / 2 - offset_ns
        best = None
        for times, opened in timelines:
            i = bisect.bisect_right(times, mid) - 1
            if i >= 0 and opened[i] is not None \
                    and (best is None or opened[i][1] > best[1]):
                best = opened[i]
        name = best[0] if best is not None else NO_SPAN
        charged[name] = charged.get(name, 0.0) + (end - start)
    return charged


def clock_offsets(threads, modules, family_of_module):
    """[device module start - host span start] (ns) for every
    `kernel:<family>` span and the first device module of that family
    starting after it. `modules`: sorted [(start, end, name)]."""
    by_family: dict = {}
    for s, _, name in modules:
        by_family.setdefault(family_of_module(name), []).append(s)
    out = []
    for spans in threads.values():
        for s, _, name in spans:
            if not name.startswith("kernel:"):
                continue
            starts = by_family.get(name[len("kernel:"):], ())
            i = bisect.bisect_left(starts, s)
            if i < len(starts):
                out.append(starts[i] - s)
    return out


def idle_gaps(ops, lo, hi):
    """[(start, end)] inside [lo, hi] where no operation of `ops`
    [(start, end, name)] runs."""
    from benchmarks.harness.trace_reduce import _clip, _union
    gaps, edge = [], lo
    for s, e in _union(_clip([(s, e) for s, e, _ in ops], lo, hi)) \
            + [[hi, hi]]:
        if s > edge:
            gaps.append((edge, s))
        edge = max(edge, e)
    return gaps


def host_spans(path):
    """{thread: [(start_ns, end_ns, name)]} of the program's spans on
    the host's planes."""
    from jax.profiler import ProfileData
    threads = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for i, line in enumerate(plane.lines):  # line names repeat
            spans = sorted(
                (e.start_ns, e.start_ns + e.duration_ns, e.name)
                for e in line.events
                if e.name.startswith(SPAN_PREFIXES))
            if spans:
                threads[(plane.name, i, line.name)] = spans
    return threads


def _quantile(sorted_values, q):
    return sorted_values[min(len(sorted_values) - 1,
                             int(q * len(sorted_values)))]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "host_spans"))
    args = ap.parse_args(argv)

    from benchmarks.harness.files import load_cell
    _, cell, config, traffic, _, sql_of = load_cell(args.workload)

    import jax
    d0 = jax.devices()[0]
    if d0.platform != "tpu":
        print("record_host_spans: needs a TPU (the device's planes "
              f"are what it reads); found {d0.platform}",
              file=sys.stderr)
        return 2

    import presto_tpu  # noqa: F401 — enables x64 before any array
    from presto_tpu.connectors.tpch import TpchGenerator
    from presto_tpu.server.coordinator import Coordinator
    from presto_tpu.telemetry.kernels import family_of_module
    from presto_tpu.telemetry.metrics import METRICS

    from benchmarks.harness import trace_reduce
    from benchmarks.harness.window import Tracer, run_window, warm_up

    def say(msg):
        print(msg, file=sys.stderr, flush=True)

    coord = Coordinator([], config["catalog"], config["schema"],
                        single_node=True,
                        properties=dict(config["properties"]))
    coord.start()
    trace_dir = tempfile.mkdtemp(prefix="host-spans-")
    try:
        coord._runner().catalogs.connector(config["catalog"]) \
            ._gens[config["schema"]] = TpchGenerator(
                config["scale"], seed=args.seed)
        warm_up(coord.url, traffic, sql_of,
                lambda: METRICS.total(
                    "presto_tpu_kernel_compiles_total"), say)
        span_s = float(traffic["trace_span_s"])
        tracer = Tracer(trace_dir, span_s,
                        int(traffic["trace_min_statements"]))
        statements, _ = run_window(coord.url, traffic, sql_of,
                                   args.seed, span_s + 2.0, tracer)
    finally:
        coord.stop()
    failed = [s for s in statements if not s.ok]

    path = trace_reduce.find_xplane(trace_dir)
    os.makedirs(args.out, exist_ok=True)
    kept = os.path.join(
        args.out, f"{cell['name']}-{args.seed}.xplane.pb")
    shutil.copy(path, kept)
    shutil.rmtree(trace_dir, ignore_errors=True)

    events = trace_reduce.load(kept)
    threads = host_spans(kept)
    lo, hi = trace_reduce.window_of(events)
    ops = next(v for _, v in sorted(events["ops"].items()) if v)
    modules = next(v for _, v in sorted(events["modules"].items()) if v)

    offsets = sorted(clock_offsets(threads, modules, family_of_module))
    offset_ns = offsets[0] if offsets else 0.0
    gaps = idle_gaps(ops, lo, hi)
    idle_ns = sum(e - s for s, e in gaps)
    charged = charge_gaps(gaps, threads, offset_ns)
    uncorrected = charge_gaps(gaps, threads)

    result = {
        "workload": cell["name"], "seed": args.seed,
        "statements": len(statements), "failed": len(failed),
        "trace": kept, "trace_bytes": os.path.getsize(kept),
        "window_s": (hi - lo) / 1e9, "idle_s": idle_ns / 1e9,
        "gaps": len(gaps),
        "host_threads_with_spans": len(threads),
        "host_spans": sum(len(v) for v in threads.values()),
        "clock_offset_us": {
            "pairs": len(offsets),
            "least": offset_ns / 1e3,
            "p10": _quantile(offsets, 0.1) / 1e3 if offsets else None,
            "median": _quantile(offsets, 0.5) / 1e3
            if offsets else None},
        "idle_s_by_span": {k: v / 1e9 for k, v in sorted(
            charged.items(), key=lambda kv: -kv[1])},
        "named_share": 1 - charged.get(NO_SPAN, 0.0) / idle_ns
        if idle_ns else None,
        "named_share_without_offset":
            1 - uncorrected.get(NO_SPAN, 0.0) / idle_ns
            if idle_ns else None,
    }
    say(f"{cell['name']} seed {args.seed}: window "
        f"{result['window_s']:.3f} s, idle {result['idle_s']:.3f} s in "
        f"{len(gaps)} gaps; clock offset (device - host) least "
        f"{result['clock_offset_us']['least']:.1f} us over "
        f"{len(offsets)} pairs")
    for name, seconds in result["idle_s_by_span"].items():
        say(f"  {seconds:9.4f} s  {100 * seconds * 1e9 / idle_ns:5.1f}%"
            f"  {name}")
    print(json.dumps(result), flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
