"""Ordering kernels (reference: OrderByOperator.java:44, TopNOperator.java:35,
MergeOperator.java:44 sorted-merge).

Full sort accumulates batches then runs one device lex sort; TopN keeps a
bounded running state (state ++ batch -> sort -> first N), so unbounded
inputs use constant memory — the analog of TopNOperator's bounded heap,
but expressed as a functional fold the compiler can fuse.
"""

from __future__ import annotations

import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from presto_tpu.batch import Batch, Column
from presto_tpu.ops import common
from presto_tpu.telemetry import kernels as _kernels


def _sort_batch_impl(batch: Batch, key_names: Tuple[str, ...],
                     descending: Tuple[bool, ...],
                     nulls_first: Tuple[bool, ...]) -> Batch:
    """Reorder rows into key order, invalid rows compacted to the end.

    One key sort (common.sort_rows), then every column (data + mask)
    follows the permutation by gather."""
    keys = [batch.columns[k].astuple() for k in key_names]
    other = [n for n in batch.names if n not in key_names]
    payloads: list = []
    for n in other:
        payloads.extend(batch.columns[n].astuple())
    skeys, svalid, spay = common.sort_rows(
        keys, list(descending), list(nulls_first),
        valid=batch.row_valid, payloads=payloads)
    cols = {}
    for name, (d, m) in zip(key_names, skeys):
        c = batch.columns[name]
        cols[name] = Column(d, m, c.type, c.dictionary)
    for i, name in enumerate(other):
        c = batch.columns[name]
        cols[name] = Column(spay[2 * i], spay[2 * i + 1], c.type,
                            c.dictionary)
    return Batch({n: cols[n] for n in batch.names}, svalid)


#: the jit (internal callers compose the impl inside their own traces)
_sort_batch = _kernels.jit(_sort_batch_impl, "sort",
                           static_argnums=(1, 2, 3))


def _topn_step_impl(state: Batch, batch: Batch, n,
                    key_names: Tuple[str, ...],
                    descending: Tuple[bool, ...],
                    nulls_first: Tuple[bool, ...]) -> Batch:
    """Fold step: keep the N smallest (per ordering) of state ++ batch.

    `state` has capacity >= n; output reuses that capacity. `n` is a
    TRACED operand (not a static arg): every distinct top-k constant
    used to mint a fresh trace — now LIMIT 10 and LIMIT 50 share one
    compiled kernel per shape (the state capacity, which does depend
    on n, stays a shape)."""
    cap = state.capacity
    merged_cols = {}
    for name, sc in state.columns.items():
        bc = batch.columns[name]
        merged_cols[name] = Column(
            jnp.concatenate([sc.data, bc.data.astype(sc.data.dtype)]),
            jnp.concatenate([sc.mask, bc.mask]), sc.type, sc.dictionary)
    merged = Batch(merged_cols,
                   jnp.concatenate([state.row_valid, batch.row_valid]))
    s = _sort_batch_impl(merged, key_names, descending, nulls_first)
    keep = jnp.arange(merged.capacity) < n
    live = s.row_valid & keep
    cols = {n_: Column(c.data[:cap], c.mask[:cap] & live[:cap], c.type,
                       c.dictionary)
            for n_, c in s.columns.items()}
    return Batch(cols, live[:cap])


_topn_step = _kernels.jit(_topn_step_impl, "topn",
                          static_argnums=(3, 4, 5))


def _limit_batch_impl(batch: Batch, n, already_emitted) -> Batch:
    """Keep the first (n - already_emitted) live rows of this batch.
    Both `n` and `already_emitted` are traced scalars so neither the
    LIMIT constant nor per-batch progress triggers a recompile."""
    rank = common.prefix_sum(batch.row_valid) - 1  # rank among live
    keep = batch.row_valid & (rank < (n - already_emitted))
    return Batch(batch.columns, keep)


_limit_batch = _kernels.jit(_limit_batch_impl, "limit")


def distinct_state(schema_cols, capacity: int) -> Batch:
    cols = {name: Column(jnp.zeros(capacity, typ.np_dtype),
                         jnp.zeros(capacity, bool), typ, dic)
            for name, typ, dic in schema_cols}
    return Batch(cols, jnp.zeros(capacity, bool))


def _distinct_step_impl(state: Batch, batch: Batch) -> Batch:
    """Fold step for SELECT DISTINCT / set-union dedup: re-group
    state ++ batch by all columns, keep one representative per group
    (hashagg._group_reduce with zero aggregates — one hash sort,
    packed representatives). Kept as a
    plain traceable body so the whole-fragment compiler can chain a
    filter/project forest ahead of it inside ONE trace
    (operators/fused_fragment.py)."""
    from presto_tpu.ops import hashagg
    cap = state.capacity
    names = state.names
    merged_cols = {}
    for name, sc in state.columns.items():
        bc = batch.columns[name]
        merged_cols[name] = Column(
            jnp.concatenate([sc.data, bc.data.astype(sc.data.dtype)]),
            jnp.concatenate([sc.mask, bc.mask]), sc.type, sc.dictionary)
    valid = jnp.concatenate([state.row_valid, batch.row_valid])
    keys = [merged_cols[n].astuple() for n in names]
    gr = hashagg._group_reduce(keys, valid, [], [], cap)
    cols = {}
    for name, (d, m) in zip(names, gr.keys):
        sc = merged_cols[name]
        cols[name] = Column(d, m, sc.type, sc.dictionary)
    return Batch(cols, gr.valid)


_distinct_step_jit = _kernels.jit(_distinct_step_impl, "distinct")


# -- instrumented public entry points ---------------------------------
#
# Operators call these; compile-vs-execute attribution (and the
# retrace counter) ride the wrapper exactly like the three engine
# kernel-cache families — closing the "module-level jits land in
# execute" gap flagged after the telemetry PR. The *_impl bodies above
# stay importable so operators/fused_fragment.py can compose them into
# whole-fragment traces.
_instr = _kernels.instrument_kernel

sort_batch = _instr(_sort_batch, "sort")
topn_step = _instr(_topn_step, "topn")
limit_batch = _instr(_limit_batch, "limit")
distinct_step = _instr(_distinct_step_jit, "distinct")


# -- kernel contracts (tools/kernelcheck.py; docs/KERNEL_CONTRACTS.md) -
#
# Each family is abstract-interpreted at >= 3 points of the
# power-of-four bucket ladder: pad-invariance taint walk, retrace
# fingerprints (LIMIT/top-k values MUST share one compile per bucket
# — they ride as traced operands), purity, output-schema dtypes.
from presto_tpu.analysis.contracts import (
    KernelContract, TracePoint, abstract_batch, register_contract,
)


def _contract_schema(variant):
    """Key/payload schema per dtype-lattice point (types.py)."""
    from presto_tpu.types import (
        BIGINT, BOOLEAN, DOUBLE, INTEGER, REAL, VARCHAR,
    )
    if variant.get("dtypes") == "float":
        return [("k1", DOUBLE), ("k2", REAL), ("p", BOOLEAN)]
    if variant.get("dtypes") == "mixed":
        return [("k1", VARCHAR, ("a", "b")), ("k2", INTEGER),
                ("p", DOUBLE)]
    return [("k1", BIGINT), ("k2", DOUBLE), ("p", BIGINT)]


def _state_batch(cap, schema):
    """(state batch, roles): accumulator state is garbage-free by the
    modular contract (its own producing step is checked), but its
    masks still carry dead-lanes-False polarity."""
    from presto_tpu.batch import Batch, Column
    from presto_tpu.analysis.contracts import abstract_column, sds
    import numpy as np
    cols, roles = {}, {}
    for entry in schema:
        name, typ = entry[0], entry[1]
        dic = entry[2] if len(entry) > 2 else None
        col, _ = abstract_column(cap, typ, dic)
        cols[name] = col
        roles[name] = Column("clean", "mask", typ, dic)
    return (Batch(cols, sds((cap,), np.bool_)),
            Batch(roles, "mask"))


def _sort_point(cap, variant):
    schema = _contract_schema(variant)
    b, rb = abstract_batch(cap, schema)
    keys, desc, nf = ("k1", "k2"), (False, True), (False, True)
    return TracePoint(
        lambda batch: _sort_batch_impl(batch, keys, desc, nf),
        (b,), (rb,))


def _topn_point(cap, variant):
    import numpy as np
    schema = _contract_schema(variant)
    state, rstate = _state_batch(4096, schema)
    b, rb = abstract_batch(cap, schema)
    # n is passed exactly as the operator passes it — a host scalar
    # that must trace as an OPERAND; a kernel that baked it static
    # would fingerprint differently per variant and fail KC002
    n = np.int64(variant.get("n", 10))
    return TracePoint(
        lambda s, batch, nn: _topn_step_impl(
            s, batch, nn, ("k1",), (False,), (False,)),
        (state, b, n), (rstate, rb, "clean"))


def _limit_point(cap, variant):
    import numpy as np
    b, rb = abstract_batch(cap, _contract_schema(variant))
    n = np.int64(variant.get("n", 10))
    return TracePoint(
        lambda batch, nn, em: _limit_batch_impl(batch, nn, em),
        (b, n, np.int64(0)), (rb, "clean", "clean"))


def _distinct_point(cap, variant):
    schema = _contract_schema(variant)
    state, rstate = _state_batch(4096, schema)
    b, rb = abstract_batch(cap, schema)
    return TracePoint(
        lambda s, batch: _distinct_step_impl(s, batch),
        (state, b), (rstate, rb))


# dtype lattice: one contract per point (distinct dtypes are distinct
# compiles BY DESIGN — they must not be conflated with the operand
# variants of one compile, which KC002 requires to share a trace)
register_contract(KernelContract(
    family="sort", module=__name__, build=_sort_point))
register_contract(KernelContract(
    family="sort", module=__name__,
    build=lambda cap, v: _sort_point(cap, {"dtypes": "float"}),
    notes="dtype-lattice point: float/real keys, boolean payload"))
register_contract(KernelContract(
    family="sort", module=__name__,
    build=lambda cap, v: _sort_point(cap, {"dtypes": "mixed"}),
    notes="dtype-lattice point: varchar dictionary + integer keys"))
register_contract(KernelContract(
    family="topn", module=__name__, build=_topn_point,
    variants=({"n": 10}, {"n": 50})))
register_contract(KernelContract(
    family="limit", module=__name__, build=_limit_point,
    variants=({"n": 10}, {"n": 1000})))
register_contract(KernelContract(
    family="distinct", module=__name__, build=_distinct_point))
