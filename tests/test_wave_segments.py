"""The exchange wave's segment form (parallel/shuffle._wave_body) held
to two references over the suite's virtual CPU devices:

* a plain numpy one: group each chip's live rows by abs(hash) % W,
  consumer d receives source chip 0's rows, then chip 1's, ..., source
  order kept inside a chip's rows;
* the form it replaced, kept HERE and nowhere else: a scatter of every
  row into a zeroed [W + 1, rows] send buffer and a partition_perm
  gather over the received W * rows lanes.

Live lanes must agree lane for lane, row_valid must be `count` ones
and then zeros, counts must be equal, and the dead lanes must be
zeroed (the pad-invariance contract). The uniform case is the one that
catches a clamped dynamic_slice: with a window as long as its operand
every bucket would read bucket 0's rows.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from presto_tpu.batch import Batch, Column
from presto_tpu.ops import common
from presto_tpu.parallel import shuffle
from presto_tpu.parallel.mesh import make_mesh, worker_axis
from presto_tpu.types import BIGINT, DOUBLE, VARCHAR


# -- the replaced form: the reference, not a second path ---------------


def _old_bucketize(dest, valid, n_parts, arrays):
    rows = dest.shape[0]
    dest = jnp.where(valid, dest, n_parts)
    order = common.stable_argsort(dest)
    sdest = dest[order]
    counts = jax.ops.segment_sum(jnp.ones_like(sdest), sdest,
                                 num_segments=n_parts + 1)
    offsets = jnp.concatenate([jnp.zeros(1, counts.dtype),
                               jnp.cumsum(counts)[:-1]])
    pos = jnp.arange(rows) - offsets[sdest]
    out = []
    for a in arrays:
        buf = jnp.zeros((n_parts + 1, rows), a.dtype)
        buf = buf.at[sdest, pos].set(a[order], mode="drop")
        out.append(buf[:n_parts])
    return out


def _old_wave_body(n_parts, axis, row_valid, key_datas, key_masks,
                   datas, masks):
    h = common.row_hash(list(zip(key_datas, key_masks)))
    dest = jnp.abs(h) % n_parts
    send = _old_bucketize(dest.astype(jnp.int32), row_valid, n_parts,
                          list(datas) + list(masks) + [row_valid])
    recv = [jax.lax.all_to_all(b, axis, 0, 0, tiled=True) for b in send]
    flat = [b.reshape(-1) for b in recv]
    nd = len(datas)
    valid = flat[2 * nd]
    order = common.partition_perm(valid)
    return (tuple(f[order] for f in flat[:nd]),
            tuple(f[order] for f in flat[nd:2 * nd]),
            valid[order], jnp.sum(valid).reshape(1))


# -- inputs ------------------------------------------------------------


def _keys_to_one_chip(w, n, rng):
    """n distinct keys that all hash to one destination (found by
    hashing a candidate range with the engine's own row hash)."""
    cand = np.arange(1, 64 * max(n, 64), dtype=np.int64)
    h = np.asarray(common.row_hash(
        [(jnp.asarray(cand), jnp.ones(cand.shape, bool))]))
    dest = np.abs(h) % w
    pool = cand[dest == 1 % w]
    assert len(pool) >= n
    return rng.choice(pool, size=n, replace=False)


def _inputs(kind, w, cap, seed=7):
    """Per-chip (k int64, k mask, v float64, v mask, row_valid), each
    [w, cap]; dead lanes carry garbage on purpose."""
    rng = np.random.default_rng(seed + 1000 * w + cap)
    k = rng.integers(0, 1 << 40, (w, cap)).astype(np.int64)
    v = rng.normal(size=(w, cap))
    km = np.ones((w, cap), bool)
    vm = rng.random((w, cap)) < 0.9
    rv = np.ones((w, cap), bool)
    if kind == "one_destination":
        # every live row of every chip goes to one consumer: its
        # bucket holds a whole shard from each source
        k = np.stack([_keys_to_one_chip(w, cap, rng) for _ in range(w)])
    elif kind == "empty_destination":
        # few distinct keys: some consumer receives nothing
        few = rng.integers(0, 1 << 40, max(1, w // 2)).astype(np.int64)
        k = few[rng.integers(0, len(few), (w, cap))]
    elif kind == "pad_producer":
        # exchange_ops._pad_batch: zeros everywhere, nothing valid
        k[w - 1] = 0
        v[w - 1] = 0.0
        km[w - 1] = vm[w - 1] = rv[w - 1] = False
    elif kind == "half_dead":
        rv = rng.random((w, cap)) < 0.5
    elif kind == "null_keys":
        km = rng.random((w, cap)) < 0.7
    else:
        assert kind == "uniform", kind
    return k, km, v, vm, rv


def _dest_of(k, km, w):
    h = np.asarray(common.row_hash([(jnp.asarray(k), jnp.asarray(km))]))
    return np.abs(h) % w


def _numpy_reference(k, km, v, vm, rv, w, dest=None):
    """Per consumer: (k, km, v, vm) of the rows it receives, in order."""
    out = []
    dests = [_dest_of(k[s], km[s], w) if dest is None else dest[s]
             for s in range(w)]
    for d in range(w):
        parts = [np.flatnonzero(rv[s] & (dests[s] == d))
                 for s in range(w)]
        out.append(tuple(
            np.concatenate([a[s][parts[s]] for s in range(w)])
            for a in (k, km, v, vm)))
    return out


def _run_body(body, mesh, w, k, km, v, vm, rv):
    spec = P(worker_axis)
    fn = jax.jit(jax.shard_map(
        functools.partial(body, w, worker_axis), mesh=mesh,
        in_specs=(spec,) * 5, out_specs=(spec,) * 4))
    flat = [jnp.asarray(a.reshape(-1)) for a in (k, km, v, vm, rv)]
    gk, gkm, gv, gvm, grv = flat
    (ok, ov), (okm, ovm), valid, count = fn(
        grv, (gk,), (gkm,), (gk, gv), (gkm, gvm))
    per = w * k.shape[1]
    shard = lambda a: np.asarray(a).reshape(w, per)  # noqa: E731
    return (shard(ok), shard(okm), shard(ov), shard(ovm), shard(valid),
            np.asarray(count))


def _check_against_reference(got, want, w):
    ok, okm, ov, ovm, valid, count = got
    for d in range(w):
        rk, rkm, rv_, rvm = want[d]
        n = len(rk)
        assert int(count[d]) == n
        assert valid[d][:n].all() and not valid[d][n:].any()
        np.testing.assert_array_equal(okm[d][:n], rkm)
        np.testing.assert_array_equal(ovm[d][:n], rvm)
        # data under a false mask is the producer's garbage, moved with
        # its row: equal all the same
        np.testing.assert_array_equal(ok[d][:n], rk)
        np.testing.assert_array_equal(ov[d][:n], rv_)
        # dead lanes are zeroed after the pack
        assert not ok[d][n:].any() and not ov[d][n:].any()
        assert not okm[d][n:].any() and not ovm[d][n:].any()


# -- the cases (one parametrised test, so each counts) -----------------

BODY_CASES = [
    # (kind, W, shard capacity)
    ("uniform", 4, 4096),
    ("uniform", 2, 4096),
    ("uniform", 8, 4096),
    ("uniform", 4, 16384),
    ("one_destination", 4, 4096),
    ("one_destination", 8, 4096),
    ("empty_destination", 4, 4096),
    ("empty_destination", 8, 4096),
    ("pad_producer", 4, 4096),
    ("pad_producer", 2, 16384),
    ("half_dead", 4, 4096),
    ("half_dead", 8, 16384),
    ("null_keys", 4, 4096),
    ("null_keys", 2, 4096),
]


@pytest.mark.parametrize(
    "kind,w,cap", BODY_CASES,
    ids=[f"{k}-w{w}-cap{c}" for k, w, c in BODY_CASES])
def test_wave_body_against_numpy_and_old_form(eight_devices, kind, w,
                                              cap):
    mesh = make_mesh(w)
    k, km, v, vm, rv = _inputs(kind, w, cap)
    want = _numpy_reference(k, km, v, vm, rv, w)
    if kind == "one_destination":
        assert sorted(len(r[0]) for r in want) == [0] * (w - 1) + [w * cap]
    if kind == "empty_destination":
        assert min(len(r[0]) for r in want) == 0
    new = _run_body(shuffle._wave_body, mesh, w, k, km, v, vm, rv)
    _check_against_reference(new, want, w)
    old = _run_body(_old_wave_body, mesh, w, k, km, v, vm, rv)
    np.testing.assert_array_equal(new[5], old[5])      # counts
    np.testing.assert_array_equal(new[4], old[4])      # row_valid
    for a, b in zip(new[:4], old[:4]):
        for d in range(w):
            n = int(new[5][d])
            np.testing.assert_array_equal(a[d][:n], b[d][:n])


def _batches(w, cap, k, km, v, vm, rv, mesh):
    devs = list(mesh.devices.reshape(-1))
    out = []
    for s in range(w):
        b = Batch({"x": Column(jnp.asarray(k[s]), jnp.asarray(km[s]),
                               BIGINT, None),
                   "y": Column(jnp.asarray(v[s]), jnp.asarray(vm[s]),
                               DOUBLE, None)},
                  jnp.asarray(rv[s]))
        out.append(jax.device_put(b, devs[s]))
    return out


def _check_consumer_batches(outs, counts, want, w):
    for d in range(w):
        rk, rkm, rv_, rvm = want[d]
        n = len(rk)
        assert int(counts[d]) == n
        b = outs[d]
        valid = np.asarray(b.row_valid)
        assert valid[:n].all() and not valid[n:].any()
        np.testing.assert_array_equal(np.asarray(b.columns["x"].data)[:n], rk)
        np.testing.assert_array_equal(np.asarray(b.columns["x"].mask)[:n], rkm)
        np.testing.assert_array_equal(np.asarray(b.columns["y"].data)[:n], rv_)
        np.testing.assert_array_equal(np.asarray(b.columns["y"].mask)[:n], rvm)


def _filter_chain(x_type=BIGINT, dictionary=None):
    """project x, y where y < 0.5: the chain of the spmd_fragment
    contract point, absorbed into the wave."""
    from presto_tpu.expr import ir
    from presto_tpu.expr.compile import compile_expression
    from presto_tpu.operators.fused_fragment import ChainStage
    from presto_tpu.schema import ColumnSchema
    from presto_tpu.types import BOOLEAN
    schema = {"x": ColumnSchema("x", x_type, dictionary),
              "y": ColumnSchema("y", DOUBLE)}
    filt = compile_expression(
        ir.call("less_than", BOOLEAN, ir.ref("y", DOUBLE),
                ir.lit(0.5, DOUBLE)), schema)
    stages = (ChainStage(
        filt, (("x", compile_expression(ir.ref("x", x_type), schema)),
               ("y", compile_expression(ir.ref("y", DOUBLE), schema))),
        None),)
    return shuffle.WaveChain(
        stages, ("test_wave_segments", "y<0.5", x_type.name),
        "fused[filter_project+all_to_all]")


WAVE_CASES = [
    # (program, kind, W, shard capacity)
    ("plain", "uniform", 4, 4096),
    ("plain", "pad_producer", 8, 4096),
    ("plain", "half_dead", 2, 16384),
    ("chained", "uniform", 4, 4096),
    ("chained", "null_keys", 8, 4096),
    ("chained", "pad_producer", 2, 16384),
]


@pytest.mark.parametrize(
    "program,kind,w,cap", WAVE_CASES,
    ids=[f"{p}-{k}-w{w}-cap{c}" for p, k, w, c in WAVE_CASES])
def test_wave_programs_against_numpy(eight_devices, program, kind, w,
                                     cap):
    """wave_repartition end to end: the plain program (spmd_shuffle)
    and the chained one (spmd_fragment, a filter traced inside the
    wave ahead of the hash)."""
    mesh = make_mesh(w)
    k, km, v, vm, rv = _inputs(kind, w, cap, seed=11)
    chain = None
    live = rv
    if program == "chained":
        chain = _filter_chain()
        live = rv & vm & (v < 0.5)        # a NULL y fails the filter
    want = _numpy_reference(k, km, v, vm, live, w)
    outs, counts = shuffle.wave_repartition(
        mesh, _batches(w, cap, k, km, v, vm, rv, mesh), ["x"],
        chain=chain, return_counts=True)
    assert sum(int(c) for c in counts) == int(live.sum())
    _check_consumer_batches(outs, counts, want, w)


@pytest.mark.parametrize("program", ["plain", "chained"])
def test_wave_varchar_key_with_remap_tables(eight_devices, program):
    """A VARCHAR key crosses as the producer's dictionary codes while
    the hash reads the unified dictionary's codes through the remap
    table: equal strings land on one consumer, payload codes unchanged."""
    w, cap = 4, 4096
    mesh = make_mesh(w)
    rng = np.random.default_rng(5)
    words = ("africa", "america", "asia", "europe", "oceania")
    codes = rng.integers(0, len(words), (w, cap)).astype(np.int32)
    v = rng.normal(size=(w, cap))
    cm = rng.random((w, cap)) < 0.8
    vm = np.ones((w, cap), bool)
    rv = rng.random((w, cap)) < 0.9
    # the unified hash dictionary orders the words another way
    table = np.array([3, 0, 4, 1, 2], np.int32)
    devs = list(mesh.devices.reshape(-1))
    batches = [jax.device_put(
        Batch({"x": Column(jnp.asarray(codes[s]), jnp.asarray(cm[s]),
                           VARCHAR, words),
               "y": Column(jnp.asarray(v[s]), jnp.asarray(vm[s]),
                           DOUBLE, None)}, jnp.asarray(rv[s])),
        devs[s]) for s in range(w)]
    live = rv
    chain = None
    if program == "chained":
        chain = _filter_chain(VARCHAR, words)
        live = rv & (v < 0.5)
    dest = [_dest_of(table[codes[s]], cm[s], w) for s in range(w)]
    want = _numpy_reference(codes, cm, v, vm, live, w, dest=dest)
    outs, counts = shuffle.wave_repartition(
        mesh, batches, ["x"], key_remaps=[jnp.asarray(table)],
        chain=chain, return_counts=True)
    _check_consumer_batches(outs, counts, want, w)
    owner = {}
    for d in range(w):
        b = outs[d]
        assert b.columns["x"].dictionary == words
        n = int(counts[d])
        x = np.asarray(b.columns["x"].data)[:n]
        m = np.asarray(b.columns["x"].mask)[:n]
        for code in np.unique(x[m]):
            assert owner.setdefault(int(code), d) == d


def test_masks_cross_as_bits_of_shared_lanes():
    """33 masks take two lanes and come back as they went."""
    rng = np.random.default_rng(3)
    masks = [jnp.asarray(rng.random(257) < 0.5) for _ in range(33)]
    lanes = shuffle._pack_masks(masks)
    assert len(lanes) == 2 and lanes[0].dtype == jnp.uint32
    back = shuffle._unpack_masks(lanes, len(masks))
    for a, b in zip(masks, back):
        assert b.dtype == jnp.bool_
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert shuffle._pack_masks([]) == []
