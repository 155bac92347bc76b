"""The control of the output check: the plain reference computed in
float32 (the nearest precision below the configurations' float64), put
in the program's place, has to come out as NOT correct.

    python3 benchmarks/tests/control.py --workload <cell> --seeds 1 2 3 [--rehearse]

prints, per seed, each number compared beside its limit, at the cell's
own scale (needs no chip: both sides are the reference; run on the
chip's machine so that the size is the cell's own). The benchmark's own
runs never run it; test_control.py keeps it at a size a test can hold."""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)


def control_numbers(scale: float, seed: int, queries: dict):
    """The comparison's numbers for float32 reference rows judged
    against the float64 reference's, one answer per statement."""
    import presto_tpu  # noqa: F401
    from presto_tpu.connectors.tpch import TpchGenerator

    from benchmarks.harness import compare, reference_data

    gen = TpchGenerator(scale, seed=seed)
    refs, needs = reference_data.references(queries)
    tables = reference_data.load_tables(gen, needs)
    want = {n: mod.rows(tables, gen) for n, mod in refs.items()}
    tables = reference_data.narrow(tables)
    low = {n: mod.rows(tables, gen) for n, mod in refs.items()}
    per_statement = {n: compare.answer_gap(low[n], want[n])
                     for n in queries}
    numbers, correct, _ = compare.judge(
        list(low.items()), want, 0,
        {n: q.get("order_by") for n, q in queries.items()})
    return numbers, correct, per_statement


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    from benchmarks.harness.files import load_cell
    _, _, config, _, queries, _ = load_cell(args.workload)
    scale = config["rehearse_scale"] if args.rehearse else config["scale"]
    for seed in args.seeds:
        numbers, correct, per = control_numbers(scale, seed, queries)
        print(json.dumps({"cell": args.workload, "seed": seed,
                          "scale": scale, "control_correct": correct,
                          "compared": numbers,
                          "per_statement": {
                              n: {"differs": d, "max_rel_err": g}
                              for n, (d, g) in per.items()}}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
