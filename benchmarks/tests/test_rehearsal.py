"""`--rehearse` of each cell ends in one well-formed last line on the
CPU backend; without it (and without a TPU) the command exits non-zero
with no result line. Then the run with the timed path broken
underneath: `correct` has to come out false.

    python -m pytest benchmarks/tests        (not part of tier-1)
"""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
CELLS = [w["name"] for w in BENCH["workloads"]]


def _run(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"), *args],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)


def _reported(kind, cell):
    return {m["name"] for m in BENCH[kind]
            if "workloads" not in m or cell in m["workloads"]}


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_line(cell, trace):
    p = _run("--workload", cell, "--seed", "3000000019", "--seconds", "2",
             "--trace", str(trace), "--rehearse")
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert {"correct", "attempted", "failed", "metrics",
            "device"} <= set(line)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["rehearsal"] is True
    assert list(line)[-1] == "compared"
    kind = "per_layer" if trace else "end_to_end"
    got = set(line["metrics"])
    assert got <= _reported(kind, cell)
    device_metrics = {m["name"] for m in BENCH["per_layer"]
                      if m["source"] == "device_trace"} | {"peak_hbm_share"}
    # no device on the CPU backend: a reader that finds nothing to
    # read leaves its metric out, it does not report 0
    assert got == _reported(kind, cell) - device_metrics
    for m in line["metrics"].values():
        assert isinstance(m["value"], (int, float)) and m["unit"]
    for name, n in line["compared"].items():
        assert f"compared {name}:" in p.stderr


def test_every_metric_has_its_reader_and_they_agree():
    """BENCHMARK.json is the index; metrics/<name>.json carries the
    reader. What both state (unit, source, layer, moves) is the same."""
    for kind in ("end_to_end", "per_layer"):
        for m in BENCH[kind]:
            with open(os.path.join(ROOT, "benchmarks", "metrics",
                                   f"{m['name']}.json")) as f:
                spec = json.load(f)
            assert spec["reader"] in ("ledger", "counter", "function")
            for key in ("unit", "source", "layer", "moves"):
                if key in m:
                    assert spec[key] == m[key], (m["name"], key)


def test_no_chip_no_number():
    p = _run("--workload", CELLS[0], "--seed", "1", "--seconds", "1",
             "--trace", "0")
    assert p.returncode != 0
    assert not p.stdout.strip()


def _rehearse_in_process(monkeypatch, capsys, cell):
    """run.main in this process (it skips nothing but the subprocess),
    with whatever the caller has patched underneath."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    spec = importlib.util.spec_from_file_location(
        "bench_run", os.path.join(ROOT, "benchmarks", "run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    rc = run.main(["--workload", cell, "--seed", "77", "--seconds", "1",
                   "--trace", "0", "--rehearse"])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def _broken_run(monkeypatch, capsys, cell, break_rows):
    """A whole run with the coordinator's answers altered where they
    are produced (the result's rows, before the client protocol
    serialises them)."""
    from presto_tpu.server import coordinator as C

    real = C.Coordinator.execute

    def execute(self, sql, **kw):
        result = real(self, sql, **kw)
        rows = result.rows

        class Altered:
            def __getattr__(self, name):
                return getattr(result, name)

            def rows(self):
                return break_rows([list(r) for r in rows()])
        return Altered()

    monkeypatch.setattr(C.Coordinator, "execute", execute)
    return _rehearse_in_process(monkeypatch, capsys, cell)


def _nudge_float(rows):
    for r in rows:
        for i, v in enumerate(r):
            if isinstance(v, float):
                r[i] = v * (1 + 1e-6)
                return rows
    return rows


FAULTS = {
    "a float answer off by 1e-6": (_nudge_float, "max_rel_err"),
    "a row left out": (lambda rows: rows[1:], "rows_differ"),
    "a key altered": (lambda rows: [
        [v + 1 if isinstance(v, int) and not isinstance(v, bool) else v
         for v in r] for r in rows], "rows_differ"),
    "the answer's rows reordered": (lambda rows: rows[::-1],
                                    "rows_misordered"),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", CELLS)
def test_altered_answer_is_not_correct(monkeypatch, capsys, cell, fault):
    break_rows, number = FAULTS[fault]
    line = _broken_run(monkeypatch, capsys, cell, break_rows)
    assert line["correct"] is False
    n = line["compared"][number]
    assert n["value"] > n["limit"]
    assert line["failed"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_datagen_fault_is_not_correct(monkeypatch, capsys, cell):
    """The reference reads the program's generator, so a fault there
    moves both sides alike and every answer still agrees: only the
    source's own rules for the data (harness/tpch_rules.json) see it."""
    from presto_tpu.connectors.tpch import TpchGenerator

    real = TpchGenerator._gen_lineitem

    def gen_lineitem(self, olo, ohi):
        out = real(self, olo, ohi)
        out["discount"] = out["discount"] + 0.005
        return out

    monkeypatch.setattr(TpchGenerator, "_gen_lineitem", gen_lineitem)
    line = _rehearse_in_process(monkeypatch, capsys, cell)
    assert line["compared"]["rows_differ"]["value"] == 0
    assert line["compared"]["data_rule_breaks"]["value"] > 0
    assert line["correct"] is False
