"""Dynamic filtering (reference: operator/DynamicFilterSourceOperator
+ the dynamic-filter planner rules under sql/planner/iterative/rule/
and server/DynamicFilterService.java).

TPU-native shape, two tiers:

- Co-fragment (broadcast/star joins): the join BUILD operator keeps
  running per-key min/max as DEVICE scalars (two tiny fused reductions
  per batch, no host sync) and, at finish, a bounded DISTINCT SET of
  build keys (one sort + dedupe of the already-merged build column).
  Probe-side scans in the same fragment consult the registry per batch
  and narrow `row_valid` with one fused compare + membership probe.
  Because a probe operator blocks on its bridge, the driver never
  pulls the probe-side scan before the build finishes, so the filter
  is always ready by the time scan batches flow.

- Cross-fragment (repartitioned joins, mesh runner): every build task
  (x every lifespan generation) publishes its PARTIAL filter to a
  query-wide DynamicFilterService; scans in other fragments apply the
  filter only once ALL expected partials arrived and were merged — a
  partial union applied early would wrongly prune rows belonging to
  build partitions that have not reported yet. Scans that finish
  before completion simply go unpruned (the join still verifies).

The distinct set is the remedy for the min/max blind spot the
reference's DynamicFilterService also addresses: surrogate-key
dimension filters often span the whole key range (bounds prune
nothing) while their distinct set prunes hard.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from presto_tpu import sanitize
from presto_tpu.batch import Batch
from presto_tpu.ops import common
from presto_tpu.telemetry import kernels as _kernels

#: Max distinct build keys carried as a set; more degrades to bounds
#: only (reference: dynamic-filtering.max-distinct-values-per-driver).
DF_SET_MAX = 4096


class DynamicFilterRegistry:
    """Per-plan handoff for CO-FRAGMENT filters: df_id -> filter.
    One publisher per id; lifespan generations each get a fresh
    planner (and so a fresh registry), so stale cross-generation
    bounds cannot leak."""

    def __init__(self):
        self._filters: Dict[int, "DFilter"] = {}
        self._seq = 0

    def new_id(self) -> int:
        self._seq += 1
        return self._seq

    def publish(self, df_id: int, mn, mx, dset=None) -> None:
        self._filters[df_id] = DFilter(mn, mx, dset)

    def get(self, df_id: int) -> Optional["DFilter"]:
        return self._filters.get(df_id)


class DFilter:
    """One published filter: bounds + optional (values, count) set."""

    def __init__(self, mn, mx, dset=None):
        self.mn = mn
        self.mx = mx
        self.dset = dset  # (sorted values [DF_SET_MAX], count) | None


class DynamicFilterService:
    """Query-wide CROSS-FRAGMENT filter collection (reference:
    DynamicFilterService.java — collected on the coordinator; here the
    mesh runner's fragments share one process, so the service is an
    in-memory meeting point). `expect()` arms an id with its publisher
    count (build tasks x lifespan generations); `get()` returns the
    merged filter only once complete."""

    def __init__(self):
        self._lock = sanitize.lock("execution.dynamic_filters")
        self._expected: Dict[int, int] = {}
        #: df_id -> {publisher token: DFilter}. Keyed by token so a
        #: RETRIED recoverable generation re-publishing its partial
        #: REPLACES it instead of over-counting toward `expected` —
        #: an over-count would complete the filter while later
        #: generations' partials are missing and wrongly prune rows.
        self._parts: Dict[int, Dict] = {}
        self._merged: Dict[int, DFilter] = {}
        self._seq = 0

    def new_id(self) -> int:
        with self._lock:
            self._seq += 1
            return self._seq

    def expect(self, df_id: int, publishers: int) -> None:
        with self._lock:
            self._expected[df_id] = publishers

    def publish(self, df_id: int, mn, mx, dset=None,
                token=None) -> None:
        with self._lock:
            d = self._parts.setdefault(df_id, {})
            if token is None:
                token = ("anon", len(d))
            d[token] = DFilter(mn, mx, dset)

    def get(self, df_id: int) -> Optional[DFilter]:
        with self._lock:
            hit = self._merged.get(df_id)
            if hit is not None:
                return hit
            parts = list(self._parts.get(df_id, {}).values())
            expected = self._expected.get(df_id)
            if expected is None or len(parts) < expected:
                return None
        # merge ON THE HOST: the partials were published by build
        # tasks pinned to DIFFERENT devices, and a cross-device
        # jnp.minimum is an error. The merged (numpy) filter is
        # uncommitted, so apply_filter follows each scan batch's own
        # device. Happens once per filter, tiny data.
        import numpy as np

        import jax
        host = jax.device_get([(p.mn, p.mx) for p in parts])
        mn = np.min(np.asarray([h[0] for h in host]))
        mx = np.max(np.asarray([h[1] for h in host]))
        dset = None
        if all(p.dset is not None for p in parts):
            chunks = []
            for p in parts:
                v, c = jax.device_get(p.dset)
                chunks.append(np.asarray(v)[:int(c)])
            u = np.unique(np.concatenate(chunks)) if chunks else \
                np.zeros(0, np.asarray(mn).dtype)
            if len(u) <= DF_SET_MAX:
                info = _ident(u.dtype)
                padded = np.full(DF_SET_MAX, info.max, dtype=u.dtype)
                padded[:len(u)] = u
                dset = (padded, np.int64(len(u)))
        merged = DFilter(mn, mx, dset)
        with self._lock:
            self._merged[df_id] = merged
        return merged


class BoundPublisher:
    """A DynamicFilterService facade carrying the publisher's stable
    identity (task index, lifespan generation): build operators
    publish through it without knowing about tokens, and a retried
    generation's re-publication replaces rather than double-counts."""

    def __init__(self, svc: DynamicFilterService, token):
        self._svc = svc
        self._token = token

    def publish(self, df_id: int, mn, mx, dset=None) -> None:
        self._svc.publish(df_id, mn, mx, dset, token=self._token)

    def get(self, df_id: int):
        return self._svc.get(df_id)


def _ident(dtype):
    info = jnp.iinfo(dtype) if jnp.issubdtype(dtype, jnp.integer) \
        else jnp.finfo(dtype)
    return info


@functools.partial(_kernels.jit, family="dynamic_filter", part="bounds")
def bounds_step(state, data, mask):
    """Fold one batch's column into running (min, max) IN THE KEY'S OWN
    DTYPE — no float widening, so int64 key domains stay exact.
    NULL/dead rows contribute identity; NaN keys are masked out (they
    can never satisfy an equi-join here, and one NaN would otherwise
    poison the bounds into pruning EVERY probe row)."""
    mn, mx = state
    if jnp.issubdtype(data.dtype, jnp.floating):
        mask = mask & ~jnp.isnan(data)
    info = _ident(data.dtype)
    mn = jnp.minimum(mn, jnp.min(jnp.where(mask, data,
                                           jnp.asarray(info.max,
                                                       data.dtype))))
    mx = jnp.maximum(mx, jnp.max(jnp.where(mask, data,
                                           jnp.asarray(info.min,
                                                       data.dtype))))
    return mn, mx


def bounds_init(dtype):
    info = _ident(dtype)
    return (jnp.asarray(info.max, dtype), jnp.asarray(info.min, dtype))


_instr = _kernels.instrument_kernel

# compile-vs-execute attribution for the dynamic-filter family —
# previously uninstrumented module-level jits whose compiles landed
# in join-build/scan busy time
bounds_step = _instr(bounds_step, "dynamic_filter")


@functools.partial(_kernels.jit, family="dynamic_filter", part="distinct_set")
def distinct_set(data, mask):
    """Bounded distinct set of a (merged) build key column: ONE sort +
    boundary dedupe, packed into DF_SET_MAX slots. Returns
    (sorted values [DF_SET_MAX], count, overflow) — on overflow the
    caller publishes bounds only. Dead lanes sort strictly after valid
    ones via a leading ~mask key (a legit dtype-max key must not
    dedupe against padding); unused slots hold the dtype max so the
    membership searchsorted stays within the sorted prefix."""
    info = _ident(data.dtype)
    if jnp.issubdtype(data.dtype, jnp.floating):
        mask = mask & ~jnp.isnan(data)  # NaN never equi-matches
    # dead lanes share one key value, so the order is a function of
    # live data only
    perm = common.lex_perm(
        [~mask, jnp.where(mask, data, jnp.zeros((), data.dtype))])
    nm, sk = ~mask[perm], data[perm]
    sv = ~nm
    first = jnp.concatenate([
        jnp.asarray([True]),
        (sk[1:] != sk[:-1]) | (nm[1:] != nm[:-1])])
    keep = first & sv
    n = jnp.sum(keep)
    # pack the first DF_SET_MAX distinct values to the front, still in
    # ascending key order
    pk = sk[common.first_true_indices(keep, DF_SET_MAX, 0)]
    out = jnp.where(jnp.arange(DF_SET_MAX) < n, pk,
                    jnp.asarray(info.max, data.dtype))
    return out, n, n > DF_SET_MAX


@functools.partial(_kernels.jit, family="dynamic_filter", part="apply",
                   static_argnums=(1, 4))
def apply_filter(batch: Batch, col: str, mn, mx, has_set: bool,
                 dset_vals=None, dset_count=None) -> Batch:
    """Narrow row_valid to rows whose key can possibly match the build
    side: bounds always, set membership when a set survived
    (inner-join semantics: NULL keys never match, so they drop
    too)."""
    c = batch.columns[col]
    keep = (c.data >= mn.astype(c.data.dtype)) \
        & (c.data <= mx.astype(c.data.dtype)) & c.mask
    if has_set:
        idx = jnp.searchsorted(dset_vals, c.data)
        idx = jnp.clip(idx, 0, dset_vals.shape[0] - 1)
        keep = keep & (dset_vals[idx] == c.data) \
            & (idx < dset_count)
    return Batch(batch.columns, batch.row_valid & keep)


distinct_set = _instr(distinct_set, "dynamic_filter")
apply_filter = _instr(apply_filter, "dynamic_filter")


def apply(batch: Batch, col: str, f: DFilter) -> Batch:
    if f.dset is not None:
        return apply_filter(batch, col, f.mn, f.mx, True,
                            f.dset[0], f.dset[1])
    return apply_filter(batch, col, f.mn, f.mx, False)


# back-compat alias (pre-set callers)
def apply_bounds(batch: Batch, col: str, mn, mx) -> Batch:
    return apply_filter(batch, col, mn, mx, False)


# -- kernel contracts (tools/kernelcheck.py) ---------------------------
from presto_tpu.analysis.contracts import (
    KernelContract, TracePoint, register_contract, sds,
)


def _bounds_point(cap, variant):
    import numpy as np
    dt = np.int64
    return TracePoint(
        lambda s, d, m: bounds_step.__wrapped__(s, d, m),
        ((sds((), dt), sds((), dt)), sds((cap,), dt),
         sds((cap,), np.bool_)),
        (("clean", "clean"), "data", "mask"))


def _distinct_set_point(cap, variant):
    import numpy as np
    return TracePoint(
        lambda d, m: distinct_set(d, m),
        (sds((cap,), np.int64), sds((cap,), np.bool_)),
        ("data", "mask"))


register_contract(KernelContract(
    family="dynamic_filter", module=__name__, build=_bounds_point))
register_contract(KernelContract(
    family="dynamic_filter", module=__name__,
    build=_distinct_set_point,
    structure_varies=True,
    structure_reason="first_true_indices scatters the DF_SET_MAX "
                     "packed slots by rank over inputs under about "
                     "53K lanes and binary-searches the rank above "
                     "(flag_pack_form): log2(capacity) unrolled rounds "
                     "on the CPU side of fast_searchsorted",
    notes="bounded distinct-set build (sort + boundary dedupe)"))
