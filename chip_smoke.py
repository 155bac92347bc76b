"""The quickest proof that presto-tpu still starts on the chip.

    python chip_smoke.py            one TPU chip (what the driver runs)
    python chip_smoke.py --mesh 4   the four-chip all_to_all path, and
                                    only that

One process, no fallback: without a TPU it exits non-zero before it
prints any result. With one chip it starts the single-node coordinator
in-process, sends TPC-H Q6, Q1 and Q3 at sf1 over the HTTP client
protocol, each three times (cold = compile + datagen + transfer;
replanned = the history-based optimizer, on by default, re-fuses the
plan from the first run's measured selectivities and may compile the
new fragments; warm = must compile nothing), and checks every answer
against the same query on the same generated data through pyarrow
Acero (baseline_proxy.py).
The last line of stdout is the contract's JSON object.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
SCHEMA = "sf1"
BATCH_ROWS = 1 << 20
#: per-statement client timeout. The rehearsal compiles for a described
#: v5e (CHANGES.md, PR 22) put the coldest query, Q3, near 200 s of XLA
#: compile; the client's 600 s default is for a warm server. One
#: statement may take most of the script's 1200 s, never all of it.
STATEMENT_TIMEOUT_S = 900.0
#: float columns agree when they round to the same 4 decimals (the
#: rule of tests/test_baseline_proxy.py) or differ by less than this
#: relative amount (the rule of __graft_entry__'s mesh comparison): a
#: sum over 6M rows near 1e11 cannot meet an absolute 1e-4 in any
#: summation order
RTOL = 1e-9


def say(msg: str) -> None:
    print(msg, flush=True)


class phase:
    """Prints a line as a phase starts and as it ends, so that a run
    that is cut still shows where the time went."""

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        self.t0 = time.perf_counter()
        say(f"[phase] {self.name}: start")
        return self

    def __exit__(self, et, ev, tb):
        dt = time.perf_counter() - self.t0
        say(f"[phase] {self.name}: "
            f"{'FAILED' if et else 'done'} after {dt:.1f}s")
        return False


def _close(got, want) -> bool:
    if isinstance(got, float) or isinstance(want, float):
        if got is None or want is None:
            return got is want
        return round(got, 4) == round(want, 4) \
            or abs(got - want) <= RTOL * abs(want)
    return got == want


def check_rows(name: str, got, want) -> float:
    """Row-for-row comparison, order-insensitive; returns the largest
    relative error seen in a float column. Raises on any mismatch."""
    def exact_part(r):
        return tuple(str(v) for v in r if not isinstance(v, float))
    got = sorted((tuple(r) for r in got), key=exact_part)
    want = sorted((tuple(r) for r in want), key=exact_part)
    if len(got) != len(want):
        raise AssertionError(
            f"{name}: {len(got)} rows, reference has {len(want)}")
    worst = 0.0
    for g, w in zip(got, want):
        if len(g) != len(w) or not all(map(_close, g, w)):
            raise AssertionError(f"{name}: row {g} != reference {w}")
        for gv, wv in zip(g, w):
            if isinstance(wv, float) and wv:
                worst = max(worst, abs(gv - wv) / abs(wv))
    return worst


def _dictionary(gen, table: str, column: str):
    for c in gen.schema(table).columns:
        if c.name == column:
            return list(c.dictionary)
    raise KeyError(f"{table}.{column}")


def reference_rows(gen, tables):
    """Q6/Q1/Q3 through Acero, shaped like the engine's result rows
    (the column mapping of tests/test_baseline_proxy.py)."""
    import baseline_proxy
    rf = _dictionary(gen, "lineitem", "returnflag")
    ls = _dictionary(gen, "lineitem", "linestatus")
    return {
        6: [(r["revenue"],)
            for r in baseline_proxy.q6(tables, gen).to_pylist()],
        1: [(rf[r["returnflag"]], ls[r["linestatus"]],
             r["quantity_sum"], r["extendedprice_sum"],
             r["disc_price_sum"], r["charge_sum"], r["quantity_mean"],
             r["extendedprice_mean"], r["discount_mean"],
             r["quantity_count"])
            for r in baseline_proxy.q1(tables, gen).to_pylist()],
        3: [(r["orderkey"], r["rev_sum"], r["orderdate"],
             r["shippriority"])
            for r in baseline_proxy.q3(tables, gen).to_pylist()],
    }


def _engine_rows(columns, data):
    """Client-protocol rows -> python values comparable with Acero's
    (dates travel as ISO strings on the wire; the reference keeps int
    days)."""
    import datetime
    epoch = datetime.date(1970, 1, 1)
    out = []
    for row in data:
        vals = []
        for col, v in zip(columns, row):
            if col["type"] == "date" and isinstance(v, str):
                v = (datetime.date.fromisoformat(v) - epoch).days
            vals.append(v)
        out.append(tuple(vals))
    return out


def _compiles():
    from presto_tpu.telemetry.metrics import METRICS
    return METRICS.by_label("presto_tpu_kernel_compiles_total", "kernel")


def _compile_delta(before):
    from presto_tpu.telemetry.metrics import METRICS
    return METRICS.delta_by_label(
        "presto_tpu_kernel_compiles_total", "kernel", before)


def _assert_on_tpu(arrays, what: str) -> int:
    n = 0
    for a in arrays:
        platforms = {d.platform for d in a.devices()}
        assert platforms == {"tpu"}, f"{what} lives on {platforms}"
        n += 1
    assert n, f"no {what} found"
    return n


def one_chip(devices) -> None:
    from presto_tpu import native
    from presto_tpu.cache import get_cache_manager
    from presto_tpu.server.coordinator import Coordinator, StatementClient
    from presto_tpu.telemetry.metrics import METRICS
    from tpch_queries import QUERIES
    import baseline_proxy

    with phase("start coordinator"):
        # the warm run must EXECUTE on the chip, so the fragment-result
        # cache (which would replay the cold run's batches) is off; the
        # plan cache and the page-source cache (scanned columns stay on
        # the device) are what a server runs with
        coord = Coordinator(
            [], "tpch", SCHEMA, single_node=True,
            properties={"batch_rows": BATCH_ROWS,
                        "fragment_result_cache_enabled": False})
        coord.start()
    try:
        client = StatementClient(coord.url, user="chip_smoke")
        timings = {}
        answers = {}
        for q in (6, 1, 3):
            runs = []
            led0 = METRICS.by_label("presto_tpu_ledger_ns_total",
                                    "category")
            for label in ("cold", "replanned", "warm"):
                with phase(f"q{q} {label}"):
                    c0 = _compiles()
                    t0 = time.perf_counter()
                    cols, data = client.execute(
                        QUERIES[q], timeout=STATEMENT_TIMEOUT_S)
                    runs.append((time.perf_counter() - t0,
                                 _compile_delta(c0), data))
            led = METRICS.delta_by_label(
                "presto_tpu_ledger_ns_total", "category", led0)
            (cold, cold_compiles, _), (replan, replan_compiles, _), \
                (warm, warm_compiles, _) = runs
            assert all(d == data for _, _, d in runs), \
                f"q{q}: the three runs gave different answers"
            assert not warm_compiles, \
                f"q{q}: the warm run compiled {warm_compiles}"
            kernel_ns = {k: led.get(k, 0) for k in
                         ("compile", "dispatch", "device_wait")}
            assert all(kernel_ns.values()), \
                f"q{q}: a kernel category of the ledger is zero: " \
                f"{kernel_ns}"
            answers[q] = _engine_rows(cols, data)
            timings[q] = (cold, replan, warm)
            say(f"q{q}: cold {cold:.2f}s replanned {replan:.2f}s "
                f"warm {warm:.3f}s rows {len(data)}; compiles: cold "
                f"{sum(cold_compiles.values())} "
                f"{json.dumps(cold_compiles, sort_keys=True)} "
                f"replanned {sum(replan_compiles.values())} "
                f"{json.dumps(replan_compiles, sort_keys=True)} "
                f"warm 0")
            say(f"q{q}: ledger ms (three runs) " + json.dumps(
                {k: round(v / 1e6, 1) for k, v in sorted(led.items())}))

        with phase("device residency"):
            page = get_cache_manager().page
            arrays = [a for e in list(page._entries.values())
                      for b in e.value
                      for c in b.columns.values()
                      for a in (c.data, c.mask)]
            n = _assert_on_tpu(arrays, "cached scan column")
            say(f"page-source cache: {len(page)} splits, {n} arrays, "
                f"{page.bytes} bytes, all on "
                f"{devices[0].platform}:{devices[0].id}")

        with phase("reference (pyarrow Acero, same generated data)"):
            gen = coord._runner().catalogs.connector("tpch")._gens[SCHEMA]
            tables = baseline_proxy.load_tables(
                gen, ["lineitem", "orders", "customer"])
            want = reference_rows(gen, tables)
            for q in (6, 1, 3):
                worst = check_rows(f"q{q}", answers[q], want[q])
                say(f"q{q}: {len(answers[q])} rows equal the Acero "
                    f"reference (max relative error {worst:.3g})")
    finally:
        with phase("stop coordinator"):
            coord.stop()

    say("native datagen: " + (
        "built" if native.load_datagen() is not None
        else "python fallback") + "; native page codec: " + (
        "built" if native.load_pageserde() is not None
        else "python fallback"))
    say("peak_bytes_in_use: "
        f"{_device_peaks(devices[:1])[str(devices[0].id)]}")
    for q, (cold, replan, warm) in timings.items():
        say(f"summary q{q} {SCHEMA}: cold {cold:.2f}s replanned "
            f"{replan:.2f}s warm {warm:.3f}s")


def mesh(devices, n: int) -> None:
    """Q1 and Q3 at sf1 through MeshRunner on `n` chips, row for row
    against LocalRunner on one chip; then proof that the shuffle was an
    all_to_all and that every chip held shards and did work."""
    from presto_tpu.runner import LocalRunner, runner_for
    from presto_tpu.telemetry.metrics import METRICS
    from tpch_queries import QUERIES

    assert len(devices) >= n, f"need {n} chips, have {len(devices)}"
    props = {"batch_rows": BATCH_ROWS,
             "fragment_result_cache_enabled": False}
    with phase(f"build runners ({n}-chip mesh, one-chip local)"):
        # the one way to ask for a mesh: the property the served
        # coordinator reads (runner_for refuses more chips than visible)
        dist = runner_for("tpch", SCHEMA, {**props, "mesh_devices": n})
        local = LocalRunner("tpch", SCHEMA, dict(props))
    for q in (1, 3):
        with phase(f"q{q} mesh cold"):
            t0 = time.perf_counter()
            res = dist.execute(QUERIES[q])
            got = res.rows()
            cold = time.perf_counter() - t0
        with phase(f"q{q} mesh warm"):
            t0 = time.perf_counter()
            res = dist.execute(QUERIES[q])
            got2 = res.rows()
            warm = time.perf_counter() - t0
        with phase(f"q{q} local (one chip)"):
            want = local.execute(QUERIES[q]).rows()
        assert got2 == got, f"q{q}: warm mesh answer != cold"
        worst = check_rows(f"q{q} mesh vs local", got, want)
        per_dev = (res.query_stats.get("ledger") or {}).get(
            "per_device") or {}
        say(f"q{q} mesh: cold {cold:.2f}s warm {warm:.3f}s, "
            f"{len(got)} rows equal one-chip execution "
            f"(max relative error {worst:.3g}); per-device ledger ms "
            + json.dumps({d: round(sum(c.values()), 1)
                          for d, c in sorted(per_dev.items())}))
        assert len(per_dev) >= n and all(
            sum(c.values()) > 0 for c in per_dev.values()), \
            f"q{q}: per-device ledger shows work on {sorted(per_dev)}"
    waves = int(METRICS.total("presto_tpu_exchange_all_to_all_waves_total"))
    say(f"all_to_all waves: {waves}, rows: "
        f"{int(METRICS.total('presto_tpu_exchange_all_to_all_rows_total'))}")
    assert waves > 0, "the mesh ran no all_to_all wave"
    # each task's scan batches are made on its own chip and stay in
    # the page-source cache there (planner/local_planner.py); the
    # high-water mark also covers what a task held only mid-query
    peaks = _device_peaks(devices[:n])
    say("peak_bytes_in_use per device: " + json.dumps(peaks))
    assert all(peaks.values()), \
        f"a chip of the mesh never held a byte: {peaks}"


def _device_peaks(devices):
    return {str(d.id): (d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in devices}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--mesh", type=int, choices=[4], default=None,
                    help="run ONLY the four-chip mesh path and the "
                         "one-chip execution it is compared with")
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    d0 = devices[0]
    say(f"device: platform={d0.platform} kind={d0.device_kind} "
        f"count={len(devices)}")
    if d0.platform != "tpu":
        print("chip_smoke: no TPU — this script does not fall back "
              "to another backend", file=sys.stderr)
        return 2

    sys.path.insert(0, HERE)
    sys.path.insert(0, os.path.join(HERE, "tests"))
    import presto_tpu  # noqa: F401 — enables x64 before any array
    from presto_tpu.execution import compile_cache

    if args.mesh:
        mesh(devices, args.mesh)
    else:
        one_chip(devices)
    say("compile cache: " + str(
        compile_cache.configured_cache_dir()
        or os.environ.get(compile_cache.ENV_CACHE_DIR)))
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
