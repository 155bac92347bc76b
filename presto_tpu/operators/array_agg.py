"""array_agg / map_agg collection operator (reference:
operator/aggregation/ArrayAggregationFunction.java +
MapAggregationFunction — re-designed for static shapes: each group's
collected elements land in a fixed-width [groups, W] block, emitted as
W scalar slot columns plus a length column under the
<out>__a{j}/<out>__len convention the planner's value forms read; see
nodes.Field.form).

Single-step only (NO_SPLIT: groups are co-located by a gather/
repartition exchange before this operator). The operator buffers
input batches and collects at finish() in one jitted kernel: sort rows
by (group keys, arrival order), detect group boundaries, compute each
contributing row's within-group position, and scatter values into the
[out_cap, W] block — arrival order is preserved inside every group, so
parallel array_agg/map_agg calls see pairwise-consistent orders (what
makes the map_agg key/value zip correct).

A group collecting more than W elements trips an ON-DEVICE overflow
flag checked once at drain; ArrayAggWidthExceeded then retries the
query with array_agg_width x4 (the GroupLimitExceeded protocol).
"""

from __future__ import annotations

import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from presto_tpu.batch import Batch, Column, bucket_capacity
from presto_tpu.expr.compile import CompiledExpr
from presto_tpu.operators.base import (
    DriverContext, Operator, OperatorContext, OperatorFactory,
)
from presto_tpu.ops import common
from presto_tpu.telemetry import kernels as _kernels
from presto_tpu.types import BIGINT, Type


class ArrayAggWidthExceeded(Exception):
    """A group collected more than array_agg_width elements; the
    runner retries with the suggested width."""

    def __init__(self, suggested: int):
        super().__init__(
            f"array_agg exceeded its element capacity; retry with "
            f"array_agg_width {suggested}")
        self.suggested = suggested


class CollectSpec:
    """One collection call: array_agg (value only) or map_agg
    (key + value)."""

    def __init__(self, out_name: str, value: CompiledExpr,
                 map_value: Optional[CompiledExpr] = None,
                 mask: Optional[CompiledExpr] = None):
        self.out_name = out_name
        self.value = value
        self.map_value = map_value  # set for map_agg
        self.mask = mask            # FILTER (WHERE ...)


@functools.partial(_kernels.jit, family="array_agg", part="collect",
                   static_argnums=(1, 2, 3, 4))
def _collect_kernel(batch: Batch, key_syms: Tuple[str, ...],
                    specs_meta: Tuple, out_cap: int, width: int):
    """(packed keys, per-spec [out_cap, W] blocks, lengths, overflow).

    specs_meta: per spec (value_sym, map_value_sym|None, mask_sym|None)
    — columns already evaluated into the batch by the factory's eval
    kernel."""
    n = batch.capacity
    valid = batch.row_valid
    keys = [(batch.columns[s].data, batch.columns[s].mask)
            for s in key_syms]
    # sort by keys, arrival order as tiebreak (iota payload carries it
    # implicitly: stable sort preserves input order within equal keys)
    payloads = [jnp.arange(n)]
    skeys, svalid, spay = common.sort_rows(keys, valid=valid,
                                           payloads=payloads)
    order = spay[0]
    bnd = common.boundaries(skeys, svalid)
    gid_m = jnp.cumsum(bnd.astype(jnp.int64)) - 1
    num_groups = jnp.sum(bnd)
    gid = jnp.clip(gid_m, 0, out_cap)
    gid = jnp.where(svalid, gid, out_cap)

    outputs = []
    overflow = num_groups > out_cap
    for (vsym, msym, masksym) in specs_meta:
        vcol = batch.columns[vsym]
        contributing = svalid
        if masksym is not None:
            fcol = batch.columns[masksym]
            fd, fm = fcol.data[order], fcol.mask[order]
            contributing = contributing & fd.astype(bool) & fm
        if msym is not None:
            # map_agg drops NULL keys (reference: MapAggregation
            # skips null keys)
            contributing = contributing & vcol.mask[order]
        # within-group position among CONTRIBUTING rows
        c = jnp.cumsum(contributing.astype(jnp.int64))
        seg_first = jnp.where(bnd, c - contributing.astype(jnp.int64),
                              0)
        seg_base = jax.ops.segment_max(
            jnp.where(bnd, seg_first, -1), gid.astype(jnp.int32),
            num_segments=out_cap + 1)[:out_cap]
        pos = c - 1 - seg_base[jnp.clip(gid, 0, out_cap - 1)]
        lens = jax.ops.segment_sum(
            contributing.astype(jnp.int64), gid.astype(jnp.int32),
            num_segments=out_cap + 1)[:out_cap]
        overflow = overflow | (jnp.max(lens) > width)
        put = contributing & (pos < width)
        # non-contributing rows (FILTER-excluded, NULL map keys, dead
        # lanes) share their predecessor's `pos`; clipping them into
        # range would scatter onto LIVE slots — and XLA scatter order
        # is unspecified, so an excluded row FOLLOWING a contributor
        # in the same group could clobber it. Route them out of
        # bounds instead: mode="drop" discards them entirely.
        posc = jnp.where(put, jnp.clip(pos, 0, width - 1), width)
        gidc = jnp.where(put, jnp.clip(gid, 0, out_cap - 1), out_cap)

        def scatter(col):
            d = col.data[order]
            m = col.mask[order]
            block = jnp.zeros((out_cap, width), d.dtype)
            bmask = jnp.zeros((out_cap, width), bool)
            block = block.at[gidc, posc].set(d, mode="drop")
            bmask = bmask.at[gidc, posc].set(m, mode="drop")
            return block, bmask
        vblock, vmask = scatter(vcol)
        if msym is not None:
            mblock, mmask = scatter(batch.columns[msym])
            outputs.append((vblock, vmask, mblock, mmask, lens))
        else:
            outputs.append((vblock, vmask, None, None, lens))

    slots = jnp.arange(out_cap)
    first_row = jnp.clip(
        jax.ops.segment_min(
            jnp.where(bnd, jnp.arange(n), n),
            jnp.clip(gid_m, 0, out_cap).astype(jnp.int32),
            num_segments=out_cap + 1)[:out_cap], 0, n - 1)
    gvalid = slots < num_groups
    gkeys = [(d[first_row], m[first_row] & gvalid) for d, m in skeys]
    return gkeys, gvalid, outputs, overflow


# compile-vs-execute attribution for the array_agg/map_agg family —
# previously an uninstrumented module-level jit
_instr = _kernels.instrument_kernel

_collect_kernel = _instr(_collect_kernel, "array_agg")


class ArrayAggOperator(Operator):
    def __init__(self, ctx: OperatorContext, key_names: Sequence[str],
                 key_exprs: Sequence[CompiledExpr],
                 specs: Sequence[CollectSpec], width: int,
                 eval_kernel):
        super().__init__(ctx)
        self.key_names = list(key_names)
        self.key_exprs = list(key_exprs)
        self.specs = list(specs)
        self.width = width
        self._eval = eval_kernel
        self._batches: List[Batch] = []
        self._finishing = False
        self._emitted = False

    def needs_input(self) -> bool:
        return not self._finishing

    def add_input(self, batch: Batch) -> None:
        self._count_in(batch)
        # evaluate keys + args NOW (one dispatch) so buffered batches
        # hold only the needed columns
        self._batches.append(self._eval(batch))
        self.ctx.reserve_batch(self._batches[-1])

    def get_output(self) -> Optional[Batch]:
        if not self._finishing or self._emitted:
            return None
        self._emitted = True
        if not self._batches:
            return self._empty_output()
        cap = bucket_capacity(
            max(sum(b.capacity for b in self._batches), 1))
        big = Batch.concat(self._batches, cap)
        self._batches = []
        key_syms = tuple(f"__k{i}" for i in range(len(self.key_exprs)))
        specs_meta = tuple(
            (f"__v{i}",
             f"__m{i}" if s.map_value is not None else None,
             f"__f{i}" if s.mask is not None else None)
            for i, s in enumerate(self.specs))
        gkeys, gvalid, outputs, overflow = _collect_kernel(
            big, key_syms, specs_meta, cap, self.width)
        if bool(np.asarray(overflow)):
            raise ArrayAggWidthExceeded(self.width * 4)
        live = int(np.asarray(jnp.sum(gvalid)))
        out_cap2 = bucket_capacity(max(live, 1))

        cols = {}
        for name, ke, (kd, km) in zip(self.key_names, self.key_exprs,
                                      gkeys):
            cols[name] = Column(kd[:out_cap2], km[:out_cap2],
                                ke.type, ke.dictionary)
        for s, (vb, vm, mb, mm, lens) in zip(self.specs, outputs):
            et = s.value.type
            if s.map_value is not None:
                # map_agg: value carries the KEY expr, map_value the
                # value expr (k slots, v slots)
                for j in range(self.width):
                    cols[f"{s.out_name}__k{j}"] = Column(
                        vb[:out_cap2, j], vm[:out_cap2, j], et,
                        s.value.dictionary)
                    cols[f"{s.out_name}__v{j}"] = Column(
                        mb[:out_cap2, j], mm[:out_cap2, j],
                        s.map_value.type, s.map_value.dictionary)
            else:
                for j in range(self.width):
                    cols[f"{s.out_name}__a{j}"] = Column(
                        vb[:out_cap2, j], vm[:out_cap2, j], et,
                        s.value.dictionary)
            cols[f"{s.out_name}__len"] = Column(
                lens[:out_cap2], gvalid[:out_cap2], BIGINT, None)
        out = Batch(cols, gvalid[:out_cap2])
        return self._count_out(out)

    def _empty_output(self) -> Batch:
        import jax.numpy as jnp
        cap = bucket_capacity(1)
        cols = {}
        for name, ke in zip(self.key_names, self.key_exprs):
            cols[name] = Column(jnp.zeros(cap, ke.type.np_dtype),
                                jnp.zeros(cap, bool), ke.type,
                                ke.dictionary)
        for s in self.specs:
            if s.map_value is not None:
                for j in range(self.width):
                    cols[f"{s.out_name}__k{j}"] = Column(
                        jnp.zeros(cap, s.value.type.np_dtype),
                        jnp.zeros(cap, bool), s.value.type,
                        s.value.dictionary)
                    cols[f"{s.out_name}__v{j}"] = Column(
                        jnp.zeros(cap, s.map_value.type.np_dtype),
                        jnp.zeros(cap, bool), s.map_value.type,
                        s.map_value.dictionary)
            else:
                for j in range(self.width):
                    cols[f"{s.out_name}__a{j}"] = Column(
                        jnp.zeros(cap, s.value.type.np_dtype),
                        jnp.zeros(cap, bool), s.value.type,
                        s.value.dictionary)
            cols[f"{s.out_name}__len"] = Column(
                jnp.zeros(cap, np.int64), jnp.zeros(cap, bool),
                BIGINT, None)
        return self._count_out(Batch(cols, jnp.zeros(cap, bool)))

    def finish(self) -> None:
        self._finishing = True

    def is_finished(self) -> bool:
        return self._finishing and self._emitted

    def close(self) -> None:
        self._batches = []
        self.ctx.release_all()


class ArrayAggOperatorFactory(OperatorFactory):
    def __init__(self, operator_id: int, key_names: Sequence[str],
                 key_exprs: Sequence[CompiledExpr],
                 specs: Sequence[CollectSpec], width: int):
        super().__init__(operator_id, "array_agg")
        self.key_names = key_names
        self.key_exprs = key_exprs
        self.specs = specs
        self.width = width

        kx = list(key_exprs)
        sp = list(specs)

        @functools.partial(_kernels.jit, family="array_agg",
                           part="eval")
        def eval_kernel(batch: Batch) -> Batch:
            env = {n: (c.data, c.mask)
                   for n, c in batch.columns.items()}
            cap = batch.capacity

            def as_col(ce, tag):
                d, m = ce.fn(env)
                return Column(jnp.broadcast_to(d, (cap,)),
                              jnp.broadcast_to(m, (cap,)), ce.type,
                              ce.dictionary)
            cols = {}
            for i, ke in enumerate(kx):
                cols[f"__k{i}"] = as_col(ke, f"k{i}")
            for i, s in enumerate(sp):
                cols[f"__v{i}"] = as_col(s.value, f"v{i}")
                if s.map_value is not None:
                    cols[f"__m{i}"] = as_col(s.map_value, f"m{i}")
                if s.mask is not None:
                    cols[f"__f{i}"] = as_col(s.mask, f"f{i}")
            return Batch(cols, batch.row_valid)
        # per-factory eval jit: registered under the same family so
        # its (per plan shape) compiles attribute to array_agg too
        self._eval = _instr(eval_kernel, "array_agg")

    def create(self, driver_context: DriverContext) -> Operator:
        return ArrayAggOperator(
            OperatorContext(self.operator_id, self.name,
                            driver_context),
            self.key_names, self.key_exprs, self.specs, self.width,
            self._eval)


# -- kernel contract (tools/kernelcheck.py) ----------------------------
from presto_tpu.analysis.contracts import (
    KernelContract, TracePoint, abstract_batch, register_contract,
)


def _collect_point(cap, variant):
    from presto_tpu.types import BIGINT, DOUBLE
    b, rb = abstract_batch(cap, [("g", BIGINT), ("x", DOUBLE)])
    return TracePoint(
        lambda bb: _collect_kernel.__wrapped__(
            bb, ("g",), (("x", None, None),), 1024, 16),
        (b,), (rb,))


register_contract(KernelContract(
    family="array_agg", module=__name__, build=_collect_point))
