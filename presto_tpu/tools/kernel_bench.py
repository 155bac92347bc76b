"""JMH-style kernel microbenchmarks (reference: the 55 Benchmark*
classes under presto-main/src/test — BenchmarkGroupByHash.java,
BenchmarkPageProcessor.java, BenchmarkHashBuildAndJoinOperators.java).

Times each engine kernel in isolation at a canonical shape so a macro
regression (a TPC-H query losing to the baseline) can be localized to
one kernel and tracked per commit. Run:

    python -m presto_tpu.tools.kernel_bench [--rows N] [--out FILE]

writes BENCH_KERNELS.json at the repo root by default:
    {"platform": ..., "rows": N, "kernels": {name:
        {"ms": per-dispatch wall, "rows_per_sec": ...}}}
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Callable, Dict

import numpy as np


#: kernels every BENCH_KERNELS.json must carry (null on failure) — the
#: regression tracker's stable contract.
HEADLINE_KERNELS = ("join_probe", "semi_mark", "agg_hash_random")


def _bench(fn: Callable, block, warmup: int = 2, runs: int = 5) -> float:
    """Best wall seconds of `runs` timed calls (after `warmup`)."""
    for _ in range(warmup):
        block(fn())
    best = float("inf")
    for _ in range(runs):
        t0 = time.perf_counter()
        block(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def build_suite(rows: int):
    """(name -> zero-arg callable, block-until-ready fn) pairs over
    shared inputs: ~`rows`-row batches of TPC-H-like columns."""
    import jax
    import jax.numpy as jnp

    from presto_tpu.batch import Batch, Column, bucket_capacity
    from presto_tpu.ops import common, hashagg
    from presto_tpu.ops import join as join_ops
    from presto_tpu.types import BIGINT, DOUBLE

    cap = bucket_capacity(rows)
    rng = np.random.default_rng(7)

    def col(a, typ):
        d = jnp.zeros(cap, typ.np_dtype).at[:rows].set(a)
        m = jnp.zeros(cap, bool).at[:rows].set(True)
        return Column(d, m, typ, None)

    keys_sorted = np.sort(rng.integers(0, rows // 4, rows))
    keys_rand = rng.permutation(keys_sorted)
    vals_f = rng.random(rows)
    vals_i = rng.integers(0, 50, rows)

    probe = Batch({
        "k": col(keys_rand, BIGINT),
        "v": col(vals_f, DOUBLE),
        "q": col(vals_i, BIGINT),
    }, col(keys_rand, BIGINT).mask)
    sortedb = Batch({
        "k": col(keys_sorted, BIGINT),
        "v": col(vals_f, DOUBLE),
        "q": col(vals_i, BIGINT),
    }, probe.row_valid)

    # build side: rows//4 distinct keys (FK->PK shape)
    bn = rows // 4
    bcap = bucket_capacity(bn)
    bkeys = np.arange(bn, dtype=np.int64)
    bpay = rng.random(bn)
    buildb = Batch({
        "k": Column(jnp.zeros(bcap, jnp.int64).at[:bn].set(bkeys),
                    jnp.zeros(bcap, bool).at[:bn].set(True), BIGINT,
                    None),
        "p": Column(jnp.zeros(bcap, jnp.float64).at[:bn].set(bpay),
                    jnp.zeros(bcap, bool).at[:bn].set(True), DOUBLE,
                    None),
    }, jnp.zeros(bcap, bool).at[:bn].set(True))

    table = join_ops.build_for_backend(buildb, ("k",))
    jax.block_until_ready(table.sorted_hash)

    agg_sum = hashagg.make_sum(DOUBLE, DOUBLE)

    suite: Dict[str, tuple] = {}

    def blk(x):
        jax.block_until_ready(x)

    # --- filter + project (the PageProcessor analog) -----------------
    @jax.jit  # lint-ok: TS007 a bench body, no family to name it
    # lint-ok: TS005 bench measures the raw kernel; a wrapper would skew it
    def filter_project(b: Batch):
        k = b.columns["k"]
        v = b.columns["v"]
        keep = (v.data > 0.5) & v.mask
        return Batch({"k": k, "w": Column(v.data * 2.0 + 1.0, v.mask,
                                          DOUBLE, None)},
                     b.row_valid & keep)
    suite["filter_project"] = (lambda: filter_project(probe), blk, rows)

    # --- hash build --------------------------------------------------
    suite["hash_build"] = (lambda: join_ops.build_for_backend(buildb, ("k",)), blk,
                           bn)

    # --- join probe (counts + expand fused) --------------------------
    def probe_fn():
        out, ovf, live = join_ops.probe_join(
            table, probe, ("k",), cap, "inner", ("k", "v", "q"),
            ("p",), ("k",))
        return out
    suite["join_probe"] = (probe_fn, blk, rows)

    # --- semi mark ---------------------------------------------------
    suite["semi_mark"] = (
        lambda: join_ops.semi_mark(table, probe, ("k",)), blk, rows)

    # --- grouped aggregation: sort path (random keys) ----------------
    @jax.jit  # lint-ok: TS007 a bench body, no family to name it
    # lint-ok: TS005 bench measures the raw kernel; a wrapper would skew it
    def agg_sorted_path(b: Batch):
        k = b.columns["k"].astuple()
        v = b.columns["v"].data
        return hashagg.batch_aggregate(
            b.row_valid, [k], [v], [b.row_valid], (agg_sum,), cap)
    suite["agg_hash_random"] = (lambda: agg_sorted_path(probe), blk,
                                rows)

    # --- grouped aggregation: presorted path (streaming) -------------
    @jax.jit  # lint-ok: TS007 a bench body, no family to name it
    # lint-ok: TS005 bench measures the raw kernel; a wrapper would skew it
    def agg_presorted(b: Batch):
        k = b.columns["k"].astuple()
        v = b.columns["v"].data
        return hashagg.presorted_aggregate(
            b.row_valid, [k], [v], [b.row_valid], (agg_sum,), cap)
    suite["agg_presorted"] = (lambda: agg_presorted(sortedb), blk, rows)

    # --- variadic row sort ------------------------------------------
    @jax.jit  # lint-ok: TS007 a bench body, no family to name it
    # lint-ok: TS005 bench measures the raw kernel; a wrapper would skew it
    def row_sort(b: Batch):
        keys = [b.columns["k"].astuple()]
        pay = [b.columns["v"].data, b.columns["q"].data]
        return common.sort_rows(keys, valid=b.row_valid, payloads=pay)
    suite["row_sort"] = (lambda: row_sort(probe), blk, rows)

    # --- selective compaction (semi-join drain shape) ----------------
    sel = probe.filter(probe.columns["v"].data > 0.999)
    target = bucket_capacity(max(int(rows * 0.002), 1024))
    suite["compact_selective"] = (
        lambda: sel.compact(target, known_valid=target), blk, rows)

    # --- shuffle wave: hash partition across the device mesh ---------
    if len(jax.devices()) >= 2:
        try:
            from presto_tpu.parallel.mesh import make_mesh
            from presto_tpu.parallel import shuffle as shuf
            w = min(8, len(jax.devices()))
            mesh = make_mesh(w)
            per = rows // w
            pcap = bucket_capacity(per)
            wave_in = []
            for i in range(w):
                sl = slice(i * per, (i + 1) * per)
                wave_in.append(Batch({
                    "k": Column(
                        jnp.zeros(pcap, jnp.int64).at[:per].set(
                            keys_rand[sl]),
                        jnp.zeros(pcap, bool).at[:per].set(True),
                        BIGINT, None),
                    "v": Column(
                        jnp.zeros(pcap, jnp.float64).at[:per].set(
                            vals_f[sl]),
                        jnp.zeros(pcap, bool).at[:per].set(True),
                        DOUBLE, None),
                }, jnp.zeros(pcap, bool).at[:per].set(True)))

            def wave():
                return shuf.wave_repartition(mesh, wave_in, ["k"])
            suite["shuffle_wave"] = (wave, blk, rows)
        except Exception as e:
            print(f"shuffle_wave skipped: {e}", file=sys.stderr)

    return suite


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=1 << 20)
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), "BENCH_KERNELS.json"))
    args = ap.parse_args(argv)

    import jax
    from presto_tpu.telemetry.metrics import METRICS
    results = {}
    suite = build_suite(args.rows)
    for name, (fn, blk, nrows) in suite.items():
        try:
            # distinct_compiles: instrumented-kernel compiles this
            # bench entry triggered (warmup included) — the compile-
            # amortization trajectory is tracked per round like
            # rows_per_sec. 0 = fully served from warm caches.
            fam0 = METRICS.by_label(
                "presto_tpu_kernel_compiles_total", "kernel")
            secs = _bench(fn, blk)
            distinct = METRICS.delta_by_label(
                "presto_tpu_kernel_compiles_total", "kernel", fam0)
            results[name] = {
                "ms": round(secs * 1e3, 2),
                "rows_per_sec": round(nrows / secs, 1),
                "distinct_compiles": distinct,
            }
            print(f"{name:18s} {secs * 1e3:9.2f} ms  "
                  f"{nrows / secs / 1e6:8.1f}M rows/s", file=sys.stderr)
        except Exception as e:  # keep the suite going
            results[name] = {"error": f"{type(e).__name__}: {e}"}
            print(f"{name:18s} FAILED: {e}", file=sys.stderr)
    # STABLE shape for CI/regression tracking: the headline kernels are
    # always present (rows_per_sec: null on failure), so a tracker can
    # `jq .kernels.join_probe.rows_per_sec` across every round without
    # guarding against missing keys.
    for name in HEADLINE_KERNELS:
        entry = results.setdefault(name, {})
        entry.setdefault("ms", None)
        entry.setdefault("rows_per_sec", None)
    out = {
        "platform": jax.default_backend(),
        "rows": args.rows,
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "kernels": results,
    }
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    # one grep-stable summary line for the headline kernels
    print("KERNELS " + " ".join(
        f"{n}_rows_per_sec={results[n].get('rows_per_sec')}"
        for n in HEADLINE_KERNELS), file=sys.stderr)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
