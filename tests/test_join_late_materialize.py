"""The aligned join probe, materialized late (ops/join.py:
aligned_front / aligned_back; operators/join_ops.py: ProbeKernel).

A probe batch above COMPACT_FLOOR whose build has unique keys runs as
two programs: the front searches at the batch's width and counts, the
back gathers both sides' columns at the width the count allows. Every
case drives LookupJoinOperator over two 16,384-lane batches and
compares what it emits with a plain oracle, row for row and in row
order; the kernel's three entry points are spied on, so each case
also says which programs ran and at which static widths.
"""

import numpy as np
import pytest

from presto_tpu.batch import COMPACT_FLOOR, Batch
from presto_tpu.expr import ir
from presto_tpu.expr.compile import compile_expression
from presto_tpu.operators.base import DriverContext
from presto_tpu.operators.join_ops import (
    JoinBridge, LookupJoinOperatorFactory, make_probe_kernel,
)
from presto_tpu.ops import join
from presto_tpu.schema import ColumnSchema
from presto_tpu.telemetry.metrics import METRICS
from presto_tpu.types import BIGINT, BOOLEAN, VARCHAR
from test_join_direct import _build

I64 = np.iinfo(np.int64)
#: one probe batch: above COMPACT_FLOOR, on the kernel ladder (4096,
#: 16384, ...), so a count of up to 4096 shrinks and a larger one stays
LANES = 16384
ROWS = 12000
WORDS = ("ash", "birch", "cedar", "elm", "fir")
_NO_RESULT_REPLAY = {"fragment_result_cache_enabled": False}


def _lanes():
    snap = METRICS.snapshot()
    return {s: snap.get(
        f'presto_tpu_join_probe_lanes_total{{stage="{s}"}}', 0)
        for s in ("searched", "materialized")}


def _build_side(n_build=3000):
    """Unique keys 0, 3, 6, ... in shuffled arrival order, one NULL
    key, NULL values in `bv`, a dictionary-coded `bs`."""
    rng = np.random.default_rng(5)
    keys = (rng.permutation(n_build) * 3).tolist()
    keys[17] = None
    bv = [None if i % 11 == 3 else 10 * i for i in range(n_build)]
    bs = [WORDS[i % len(WORDS)] for i in range(n_build)]
    batch = Batch.from_pydict({"bk": (keys, BIGINT), "bv": (bv, BIGINT),
                               "bs": (bs, VARCHAR)})
    return batch, list(zip(keys, bv, bs))


def _probe_keys(count, seed, n_build=3000):
    """ROWS probe keys whose match rate decides the back's width."""
    rng = np.random.default_rng(seed)
    hit = (rng.integers(0, n_build, ROWS) * 3)
    miss = hit + 1                       # never a multiple of three
    share = {"shrinks": 0.2, "stays": 0.8, "empty": 0.0}[count]
    keys = np.where(rng.random(ROWS) < share, hit, miss).tolist()
    # NULL keys and keys outside [min, max], where key - min wraps
    for i, k in ((5, None), (6, -1), (7, int(I64.min)),
                 (8, int(I64.max)), (9, 3 * n_build + 300),
                 (ROWS - 1, None)):
        keys[i] = k
    return keys


def _probe_batch(keys, base):
    return Batch.from_pydict(
        {"pk": (keys, BIGINT),
         "pv": ([base + i for i in range(len(keys))], BIGINT)},
        capacity=LANES)


def _oracle(build_rows, probe_rows, join_type):
    """[(pk, pv, bv, bs)] in probe order: the join by dictionary."""
    by_key = {k: (bv, bs) for k, bv, bs in build_rows if k is not None}
    out = []
    for k, pv in probe_rows:
        if k is not None and k in by_key:
            out.append((k, pv) + by_key[k])
        elif join_type in ("left", "full"):
            out.append((k, pv, None, None))
    return out


def _table(layout, batch, join_type):
    if layout == "direct":
        table = _build(batch, ("bk",), join_type)
    else:
        table = join.build_for_backend(batch, ("bk",))
    assert table.layout == layout and table.unique_runs
    return table


class _Spy:
    """A ProbeKernel's three programs, each call recorded with the
    static width it ran at."""

    def __init__(self, kernel):
        self.calls = []
        self.kernel = kernel._replace(
            whole=self._record("whole", kernel.whole, 3),
            front=self._record("front", kernel.front, None),
            back=self._record("back", kernel.back, 5))

    def _record(self, name, fn, width_arg):
        def call(*args):
            self.calls.append((name, args[1].capacity
                               if width_arg is None else args[width_arg]))
            return fn(*args)
        return call


def _drive(table, batches, join_type, spy, **operator_args):
    """Every batch the operator emits for `batches`, in order."""
    bridge = JoinBridge()
    bridge.table = table
    factory = LookupJoinOperatorFactory(
        2, bridge, ("pk",), join_type, ("pk", "pv"), ("bv", "bs"),
        build_keys=("bk",),
        probe_schema=[("pk", BIGINT, None), ("pv", BIGINT, None)])
    factory._kernels = (spy.kernel, None)
    op = factory.create(DriverContext())
    for name, value in operator_args.items():
        setattr(op, name, value)
    out = []

    def drain():
        while (b := op.get_output()) is not None:
            out.append(b)
    for b in batches:
        assert op.needs_input()
        op.add_input(b)
        drain()
    op.finish()
    while not op.is_finished():
        drain()
    return op, out


def _kernel(join_type, verify="hash", **fused):
    return _Spy(make_probe_kernel(
        ("pk",), join_type, ("pk", "pv"), ("bv", "bs"), ("bk",),
        verify=verify, **fused))


@pytest.mark.parametrize("count", ("shrinks", "stays", "empty"))
@pytest.mark.parametrize("verify", join.VERIFY_MODES)
@pytest.mark.parametrize("layout, join_type", [
    ("direct", "inner"), ("direct", "left"), ("sorted", "inner"),
    ("sorted", "left"), ("sorted", "full")])
def test_split_probe_equals_the_oracle(layout, join_type, verify, count):
    build, build_rows = _build_side()
    table = _table(layout, build, join_type)
    keys = [_probe_keys(count, seed) for seed in (1, 2)]
    probe_rows = [list(zip(k, range(base, base + ROWS)))
                  for k, base in zip(keys, (0, 10 ** 6))]
    batches = [_probe_batch(k, base)
               for k, base in zip(keys, (0, 10 ** 6))]
    spy = _kernel(join_type, verify)
    before = _lanes()
    op, out = _drive(table, batches, join_type, spy)

    expected = [_oracle(build_rows, rows, join_type)
                for rows in probe_rows]
    assert [b.to_pylist() for b in out[:2]] == expected
    # the back ran at the bucket of each batch's count, never wider
    widths = [4096 if len(rows) <= 4096 else LANES for rows in expected]
    assert spy.calls == [("front", LANES), ("front", LANES),
                         ("back", widths[0]), ("back", widths[1])]
    assert [b.capacity for b in out[:2]] == widths
    assert {"shrinks": join_type == "inner", "stays": False,
            "empty": join_type == "inner"}[count] == (widths[0] < LANES)
    after = _lanes()
    assert after["searched"] - before["searched"] == 2 * LANES
    assert after["materialized"] - before["materialized"] == sum(widths)
    assert not bool(op._overflow)

    if join_type != "full":
        assert len(out) == 2
        return
    # FULL: the flags the fronts scattered are the build rows some
    # probe row matched, and the tail is every other live build row
    probed = {k for ks in keys for k in ks if k is not None}
    bk = table.batch.columns["bk"]
    live = np.asarray(table.batch.row_valid) & np.asarray(bk.mask)
    want = live & np.isin(np.asarray(bk.data), sorted(probed))
    assert np.array_equal(np.asarray(op._matched), want)
    flags = np.zeros_like(want)
    for b in batches:     # the one-dispatch probe marks the same rows
        *_, flags = join.probe_join_full(
            table, b, ("pk",), flags, LANES, ("pk", "pv"),
            ("bv", "bs"), ("bk",), verify)
    assert np.array_equal(np.asarray(flags), want)
    (tail,) = out[2:]
    assert sorted(tail.to_pylist(), key=repr) == sorted(
        ((None, None, bv, bs) for k, bv, bs in build_rows
         if k not in probed), key=repr)


@pytest.mark.parametrize("layout", join.LAYOUTS)
def test_dictionary_coded_build_column_keeps_its_dictionary(layout):
    build, _ = _build_side()
    spy = _kernel("inner")
    _, (out,) = _drive(_table(layout, build, "inner"),
                       [_probe_batch(_probe_keys("shrinks", 3), 0)],
                       "inner", spy)
    assert out.capacity == 4096
    assert out.columns["bs"].dictionary == build.columns["bs"].dictionary
    assert out.columns["bs"].type == VARCHAR


def _fused():
    """A filter that reads a build column, a projection over both
    sides: `bv > 9000`, then (pk, pv + bv, bs)."""
    schema = {"pk": ColumnSchema("pk", BIGINT),
              "pv": ColumnSchema("pv", BIGINT),
              "bv": ColumnSchema("bv", BIGINT),
              "bs": ColumnSchema("bs", VARCHAR, tuple(sorted(WORDS)))}
    keep = compile_expression(ir.call(
        "greater_than", BOOLEAN, ir.ref("bv", BIGINT),
        ir.lit(9000, BIGINT)), schema)
    total = compile_expression(ir.call(
        "add", BIGINT, ir.ref("pv", BIGINT), ir.ref("bv", BIGINT)),
        schema)
    return dict(
        fused_filter=keep,
        fused_projections=[
            ("pk", compile_expression(ir.ref("pk", BIGINT), schema)),
            ("total", total),
            ("bs", compile_expression(ir.ref("bs", VARCHAR), schema))])


@pytest.mark.parametrize("lanes", (4096, LANES))
@pytest.mark.parametrize("layout, join_type", [
    ("direct", "inner"), ("direct", "left"), ("sorted", "inner")])
def test_fused_filter_and_projection_run_in_the_back(layout, join_type,
                                                     lanes):
    """The filter reads a build column, so it can only run where the
    build columns are: at the back's width when the probe is split, in
    the one program when the batch is at or under COMPACT_FLOOR."""
    assert 4096 <= COMPACT_FLOOR < LANES
    build, build_rows = _build_side()
    table = _table(layout, build, join_type)
    rows = min(ROWS, lanes - 96)
    keys = _probe_keys("shrinks", 4)[:rows]
    batch = Batch.from_pydict(
        {"pk": (keys, BIGINT), "pv": (list(range(rows)), BIGINT)},
        capacity=lanes)
    spy = _kernel(join_type, **_fused())
    before = _lanes()
    _, (out,) = _drive(table, [batch], join_type, spy)
    joined = _oracle(build_rows, list(zip(keys, range(rows))), join_type)
    assert out.to_pylist() == [
        (k, pv + bv, bs) for k, pv, bv, bs in joined
        if bv is not None and bv > 9000]
    after = _lanes()
    if lanes <= COMPACT_FLOOR:            # one dispatch, nothing awaited
        assert spy.calls == [("whole", lanes)]
        width = lanes
    else:                   # sized by the join's count, not the filter's
        width = 4096 if len(joined) <= 4096 else LANES
        assert spy.calls == [("front", lanes), ("back", width)]
        assert out.capacity == width
    assert after["searched"] - before["searched"] == lanes
    assert after["materialized"] - before["materialized"] == width


def _flag_packs():
    snap = METRICS.snapshot()
    return {f: snap.get(
        f'presto_tpu_flag_pack_lanes_total{{form="{f}"}}', 0)
        for f in ("scatter", "search")}


#: a probe batch wide enough that a back on the ladder's floor takes
#: the search: 4,096 slots x 19 rounds < 262,144 lanes
WIDE_LANES = 1 << 18


@pytest.mark.parametrize("matches, width, form", [
    (2000, 4096, "search"), (10000, 16384, "scatter")])
def test_the_back_counts_its_flag_pack_form(matches, width, form):
    """The back packs the live rows of a 262,144-lane probe batch into
    the bucket of their count, and counts its output lanes under the
    form first_true_indices takes there; the answer is the oracle's."""
    build, build_rows = _build_side()
    table = _table("direct", build, "inner")
    keys = [3 * (i % 2990) for i in range(matches)] + \
        [3 * i + 1 for i in range(2 * matches)]
    batch = Batch.from_pydict(
        {"pk": (keys, BIGINT), "pv": (list(range(len(keys))), BIGINT)},
        capacity=WIDE_LANES)
    spy = _kernel("inner")
    before = _flag_packs()
    _, (out,) = _drive(table, [batch], "inner", spy)
    assert spy.calls == [("front", WIDE_LANES), ("back", width)]
    assert out.to_pylist() == _oracle(
        build_rows, list(zip(keys, range(len(keys)))), "inner")
    grown = {f: v - before[f] for f, v in _flag_packs().items()}
    assert grown == {form: width,
                     ({"scatter", "search"} - {form}).pop(): 0}


@pytest.mark.parametrize("layout", join.LAYOUTS)
def test_fused_upstream_chain_runs_in_the_front(layout):
    """The scan-side chain (here: keep even `pv`) decides which probe
    rows are alive before the search: the front hands the back the
    batch after the chain."""
    build, build_rows = _build_side()
    table = _table(layout, build, "left")
    keys = _probe_keys("shrinks", 6)
    spy = _Spy(make_probe_kernel(
        ("pk",), "left", ("pk", "pv"), ("bv", "bs"), ("bk",),
        pre=lambda b: b.filter(b.columns["pv"].data % 2 == 0),
        pre_key="even_pv"))
    _, (out,) = _drive(table, [_probe_batch(keys, 0)], "left", spy,
                       pre_fused=True)
    assert out.to_pylist() == _oracle(
        build_rows, [(k, pv) for pv, k in enumerate(keys)
                     if pv % 2 == 0], "left")
    assert spy.calls == [("front", LANES), ("back", LANES)]


def test_duplicate_key_build_still_expands_in_one_program():
    """Not aligned: the general expansion and the deferred shrink, as
    before."""
    rng = np.random.default_rng(9)
    bkeys = rng.integers(0, 50, 200).tolist()
    build = Batch.from_pydict({"bk": (bkeys, BIGINT),
                               "bv": (list(range(200)), BIGINT),
                               "bs": (["elm"] * 200, VARCHAR)})
    table = join.build_for_backend(build, ("bk",))
    assert not table.unique_runs
    keys = rng.integers(0, 2000, ROWS).tolist()
    spy = _kernel("inner")
    op, (out,) = _drive(table, [_probe_batch(keys, 0)], "inner", spy,
                        expansion_factor=4)
    assert spy.calls == [("whole", 4 * LANES)]
    assert not bool(op._overflow)
    by_key = {}
    for k, bv in zip(bkeys, range(200)):
        by_key.setdefault(k, []).append(bv)
    assert sorted(out.to_pylist()) == sorted(
        (k, pv, bv, "elm") for pv, k in enumerate(keys)
        for bv in by_key.get(k, ()))


PLANNED = {
    # 656 of lineitem's 5,990 rows find an order: the back packs them
    "inner": ("select l.orderkey, l.linenumber, o.totalprice from "
              "lineitem l join orders o on l.orderkey = o.orderkey "
              "where o.totalprice > 400000 order by 1, 2", 4096),
    # every probe row is kept: nothing to shrink, the back stays wide
    "left": ("select l.orderkey, l.linenumber, o.totalprice from "
             "lineitem l left join (select * from orders where "
             "totalprice > 400000) o on l.orderkey = o.orderkey "
             "order by 1, 2", LANES),
}


@pytest.mark.parametrize("join_type", sorted(PLANNED))
def test_planned_join_splits_above_the_floor(join_type, monkeypatch):
    """Through the planner and the driver at tiny scale: lineitem's
    one batch is 16,384 lanes, above COMPACT_FLOOR, so the probe
    splits; with the floor lifted out of reach it runs whole, and the
    answer is the same."""
    from presto_tpu.operators import join_ops as join_ops_mod
    from presto_tpu.runner import LocalRunner
    sql, width = PLANNED[join_type]

    def run():
        before = _lanes()
        rows = LocalRunner(
            "tpch", "tiny", properties=_NO_RESULT_REPLAY
        ).execute(sql).rows()
        return rows, {s: v - before[s] for s, v in _lanes().items()}
    rows, lanes = run()
    assert len(rows) == (656 if join_type == "inner" else 5990)
    assert lanes == {"searched": LANES, "materialized": width}
    monkeypatch.setattr(join_ops_mod, "COMPACT_FLOOR", 1 << 30)
    assert run() == (rows, {"searched": LANES, "materialized": LANES})
