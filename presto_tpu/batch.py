"""Columnar batch data model (reference: presto-common Page.java:33,
block/Block.java:24, PageBuilder.java:29).

A `Batch` is the unit of data flow between operators, like Presto's `Page`,
but designed for XLA's static-shape world:

- Every column is a fixed-`capacity` device array plus a validity (non-null)
  mask. Capacities are power-of-two buckets so the set of compiled kernel
  shapes stays small (SURVEY.md §7 step 1).
- Row liveness is a separate `row_valid` mask: a filter just ANDs into it
  (selection-vector execution, no compaction, no dynamic shape). Presto's
  positionCount becomes "number of True lanes in row_valid".
- VARCHAR columns hold int32 dictionary codes; the dictionary itself (a
  tuple of python strings, sorted ascending so code order == collation
  order) lives host-side in the column's static metadata. This replaces
  Presto's DictionaryBlock (block/DictionaryBlock.java:37) and makes
  string predicates compile to tiny device lookup tables.

Batch/Column are registered pytrees so whole batches flow through jit /
shard_map directly.
"""

from __future__ import annotations

import dataclasses
import functools
import threading
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from presto_tpu.telemetry import kernels as _kernels
from presto_tpu.types import Type, VARCHAR, BOOLEAN, DOUBLE, BIGINT

MIN_CAPACITY = 16
#: Default target rows per batch fed to kernels (like Presto's ~1MB pages).
DEFAULT_BATCH_ROWS = 64 * 1024


def quantized_capacity(n: int) -> int:
    """Power-of-FOUR capacity ladder with a 4096 floor.

    Exchange waves and their outputs land on this ladder instead of
    the exact power-of-two bucket: every distinct capacity is a fresh
    XLA compile of the shard_map collective (and of each downstream
    kernel it feeds) at ~2s apiece, so a handful of coarse steps beats
    exact sizing — at a bounded <=4x padding cost."""
    cap = 4096
    while cap < n:
        cap *= 4
    return cap


def bucket_capacity(n: int) -> int:
    """Round up to a power of two (>= MIN_CAPACITY) to bound recompiles."""
    cap = MIN_CAPACITY
    while cap < n:
        cap *= 2
    return cap


# -- kernel shape bucketing (the compile-wall lever) -------------------
#
# Every distinct batch capacity a kernel sees is a fresh XLA trace +
# compile; splits, scale factors, and intermediate live counts mint
# capacities freely. When the gate is on, every batch entering an
# operator kernel is padded up to the coarse `quantized_capacity`
# ladder (power-of-4, floor 4096) with dead lanes — masked-lane
# semantics already hold everywhere (selection-vector execution; the
# build-side invalid-tail clip of ops/join.py is the template), so
# padded rows are indistinguishable from post-filter dead rows. The
# whole TPC-H serving mix then compiles against a handful of shapes
# instead of one per (split x query x scale factor).

#: process default for kernel shape bucketing; per-statement override
#: rides a thread-local set by the runner from the
#: `kernel_shape_buckets` session property
SHAPE_BUCKETS_DEFAULT = True
_SHAPE_TL = threading.local()


def set_shape_buckets(on: Optional[bool]):
    """Set this thread's bucket gate (None = revert to the process
    default). Returns the previous override so callers can restore."""
    prev = getattr(_SHAPE_TL, "on", None)
    _SHAPE_TL.on = on
    return prev


def shape_buckets_on() -> bool:
    on = getattr(_SHAPE_TL, "on", None)
    return SHAPE_BUCKETS_DEFAULT if on is None else bool(on)


def shape_buckets_override():
    """This thread's raw override (None = process default) — the task
    executor captures it at statement submit and re-installs it around
    every quantum, so pool workers honor the statement's
    `kernel_shape_buckets` exactly like the submitting thread did."""
    return getattr(_SHAPE_TL, "on", None)


def kernel_capacity(n: int) -> int:
    """THE capacity ladder kernel-facing shapes land on when bucketing
    is enabled (quantized_capacity: power-of-4, floor 4096)."""
    return quantized_capacity(max(int(n), 1))


def operator_capacity(n: int, floor: int = MIN_CAPACITY) -> int:
    """THE gate-aware capacity choice for operator-built shapes
    (build tables, sort/window concats, compaction targets): the
    kernel ladder when bucketing is on, the exact power-of-two bucket
    (not below `floor`) when off. One definition so the ladder policy
    can never drift per operator."""
    if shape_buckets_on():
        return kernel_capacity(n)
    return max(floor, bucket_capacity(max(n, 1)))


@functools.partial(_kernels.jit, family="pad", static_argnums=(1,))
def _pad_batch_jit(batch: "Batch", pad: int) -> "Batch":
    """Append `pad` dead lanes (mask False, row_valid False, data 0)
    to every column. One fused kernel per (schema, pad) pair, its own
    family (`pad`): shape plumbing, but a tenth of the device's time
    in the scan cells (PERF.md), so it is counted like any kernel."""
    cols = {
        n: Column(jnp.pad(c.data, (0, pad)), jnp.pad(c.mask, (0, pad)),
                  c.type, c.dictionary)
        for n, c in batch.columns.items()
    }
    return Batch(cols, jnp.pad(batch.row_valid, (0, pad)))


_pad_batch = _kernels.instrument_kernel(_pad_batch_jit, "pad")


def pad_for_kernel(batch: "Batch") -> "Batch":
    """Round a batch up to its kernel-capacity bucket (no-op when the
    gate is off or the capacity is already on the ladder). The pad
    lanes are dead rows; every operator kernel treats them exactly
    like filtered-out rows."""
    if not shape_buckets_on():
        return batch
    tgt = kernel_capacity(batch.capacity)
    if tgt <= batch.capacity:
        return batch
    return _pad_batch(batch, tgt - batch.capacity)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Column:
    """One column: data + validity mask, plus static type/dictionary metadata.

    `dictionary` is only set for string types: a tuple of distinct values,
    sorted ascending, such that `data` holds indices into it. A code of -1
    never appears for valid rows.
    """

    data: jnp.ndarray
    mask: jnp.ndarray  # bool, True = value present (not NULL)
    type: Type
    dictionary: Optional[Tuple[str, ...]] = None

    def tree_flatten(self):
        return (self.data, self.mask), (self.type, self.dictionary)

    @classmethod
    def tree_unflatten(cls, aux, children):
        data, mask = children
        typ, dictionary = aux
        return cls(data, mask, typ, dictionary)

    @property
    def capacity(self) -> int:
        return int(self.data.shape[0])

    def astuple(self):
        return (self.data, self.mask)

    @classmethod
    def from_numpy(cls, values: np.ndarray, mask: Optional[np.ndarray],
                   typ: Type, capacity: int,
                   dictionary: Optional[Tuple[str, ...]] = None) -> "Column":
        # pad host-side into fresh capacity-bucket buffers, then move
        # them onto the device via the page layer's dlpack doorway
        # (zero-copy on the CPU backend; the fresh buffers are ceded)
        from presto_tpu.native import pages
        data, m = pages.pad_to_capacity(values, mask, capacity,
                                        typ.np_dtype)
        return cls(pages.to_device(data), pages.to_device(m), typ,
                   dictionary)

    @classmethod
    def from_pylist(cls, values: Sequence[Any], typ: Type,
                    capacity: Optional[int] = None) -> "Column":
        """Build from python values; None means NULL. Strings are
        dictionary-encoded here (sorted so codes preserve collation)."""
        n = len(values)
        capacity = capacity or bucket_capacity(n)
        mask = np.array([v is not None for v in values], dtype=bool)
        if typ.is_string:
            present = sorted({v for v in values if v is not None})
            dictionary = tuple(present)
            index = {v: i for i, v in enumerate(present)}
            data = np.array([index[v] if v is not None else 0 for v in values],
                            dtype=np.int32)
            return cls.from_numpy(data, mask, typ, capacity, dictionary)
        if typ.is_decimal:
            data = np.array(
                [_to_unscaled(v, typ.scale) if v is not None else 0
                 for v in values], dtype=np.int64)
            return cls.from_numpy(data, mask, typ, capacity)
        data = np.array([v if v is not None else 0 for v in values],
                        dtype=typ.np_dtype)
        return cls.from_numpy(data, mask, typ, capacity)

    def to_pylist(self, row_valid: Optional[np.ndarray] = None,
                  _data: Optional[np.ndarray] = None,
                  _mask: Optional[np.ndarray] = None) -> List[Any]:
        data = np.asarray(self.data) if _data is None else _data
        mask = np.asarray(self.mask) if _mask is None else _mask
        n = self.capacity
        rows = range(n) if row_valid is None else np.nonzero(row_valid)[0]
        out: List[Any] = []
        for i in rows:
            if not mask[i]:
                out.append(None)
            elif self.dictionary is not None:
                out.append(self.dictionary[int(data[i])])
            elif self.type.is_decimal:
                out.append(int(data[i]) / (10 ** self.type.scale))
            elif self.type.name == "boolean":
                out.append(bool(data[i]))
            elif self.type.is_floating:
                out.append(float(data[i]))
            else:
                out.append(int(data[i]))
        return out


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Batch:
    """An ordered set of named columns sharing `row_valid` (cf. Page.java:33).

    Invariants: all columns and row_valid share the same capacity; column
    order is meaningful (operators address columns by name, output order is
    the dict insertion order).
    """

    columns: Dict[str, Column]
    row_valid: jnp.ndarray  # bool[capacity]

    def tree_flatten(self):
        names = tuple(self.columns.keys())
        children = tuple(self.columns[n] for n in names) + (self.row_valid,)
        return children, names

    @classmethod
    def tree_unflatten(cls, names, children):
        cols = dict(zip(names, children[:-1]))
        return cls(cols, children[-1])

    @property
    def capacity(self) -> int:
        return int(self.row_valid.shape[0])

    @property
    def names(self) -> List[str]:
        return list(self.columns.keys())

    def column(self, name: str) -> Column:
        return self.columns[name]

    def num_valid(self) -> int:
        """Host-syncing count of live rows (Presto's positionCount).
        The int() blocks on every dispatch the mask depends on, so
        this wall is a drain point — `device_wait`, not the enclosing
        frame's self time (the async-dispatch undercount)."""
        from presto_tpu.telemetry import ledger as _ledger
        with _ledger.span("device_wait"):
            return int(jnp.sum(self.row_valid))

    # -- construction ------------------------------------------------------

    @classmethod
    def from_pydict(cls, data: Dict[str, Tuple[Sequence[Any], Type]],
                    capacity: Optional[int] = None) -> "Batch":
        lengths = {len(v) for v, _ in data.values()}
        assert len(lengths) == 1, "all columns must have equal length"
        n = lengths.pop()
        capacity = capacity or bucket_capacity(n)
        cols = {name: Column.from_pylist(vals, typ, capacity)
                for name, (vals, typ) in data.items()}
        from presto_tpu.native import pages
        rv = np.zeros(capacity, dtype=bool)
        rv[:n] = True
        return cls(cols, pages.to_device(rv))

    @classmethod
    def from_numpy(cls, arrays: Dict[str, np.ndarray],
                   types: Dict[str, Type],
                   masks: Optional[Dict[str, np.ndarray]] = None,
                   dictionaries: Optional[Dict[str, Tuple[str, ...]]] = None,
                   capacity: Optional[int] = None) -> "Batch":
        n = len(next(iter(arrays.values())))
        capacity = capacity or bucket_capacity(n)
        cols = {}
        for name, arr in arrays.items():
            mask = masks.get(name) if masks else None
            dic = dictionaries.get(name) if dictionaries else None
            cols[name] = Column.from_numpy(arr, mask, types[name], capacity, dic)
        from presto_tpu.native import pages
        rv = np.zeros(capacity, dtype=bool)
        rv[:n] = True
        return cls(cols, pages.to_device(rv))

    # -- host-side materialization ----------------------------------------

    def to_pydict(self) -> Dict[str, List[Any]]:
        # one device->host transfer for the whole batch: column-by-column
        # np.asarray costs one blocking RPC roundtrip per array on remote
        # backends, which dominates small-result latency
        host = jax.device_get(
            ([(c.data, c.mask) for c in self.columns.values()],
             self.row_valid))
        pairs, rv = host
        out: Dict[str, List[Any]] = {}
        for (name, col), (data, mask) in zip(self.columns.items(), pairs):
            out[name] = col.to_pylist(rv, _data=data, _mask=mask)
        return out

    def to_pylist(self) -> List[Tuple[Any, ...]]:
        d = self.to_pydict()
        if not d:
            return [()] * int(np.sum(np.asarray(self.row_valid)))
        return list(zip(*d.values()))

    def to_pandas(self):
        import pandas as pd
        return pd.DataFrame(self.to_pydict())

    # -- transformations ---------------------------------------------------

    def with_columns(self, columns: Dict[str, Column]) -> "Batch":
        return Batch(columns, self.row_valid)

    def select(self, names: Sequence[str]) -> "Batch":
        return Batch({n: self.columns[n] for n in names}, self.row_valid)

    def rename(self, mapping: Dict[str, str]) -> "Batch":
        return Batch({mapping.get(n, n): c for n, c in self.columns.items()},
                     self.row_valid)

    def filter(self, keep: jnp.ndarray) -> "Batch":
        """Selection-vector filter: just narrows row_valid. O(n) mask AND."""
        return Batch(self.columns, self.row_valid & keep)

    def compact(self, capacity: Optional[int] = None,
                known_valid: Optional[int] = None) -> "Batch":
        """Pack live rows to the front; optionally resize to `capacity`.

        Used at rebatch points (before joins/output) where padding waste
        matters; the hot filter path never compacts. Shrinking syncs to
        the host to check the live rows fit — pass `known_valid` when the
        caller already counted to avoid the extra device roundtrip.
        """
        if capacity is not None and capacity < self.capacity:
            n = known_valid if known_valid is not None \
                else self.num_valid()
            assert n <= capacity, f"compact overflow: {n} > {capacity}"
            # selective shrink: gather just `capacity` live-row indices
            # (a bounded nonzero) instead of argsort-packing the full
            # batch — the full pack is O(cap log cap) + a full-width
            # gather PER COLUMN, which dominated semi-join/filter
            # drains at high selectivity (600k-row batches packing to
            # 1k slots)
            return _compact_shrink(self, capacity)
        out = _compact(self)
        if capacity is None or capacity == self.capacity:
            return out
        pad = capacity - self.capacity
        cols = {name: Column(jnp.pad(c.data, (0, pad)),
                             jnp.pad(c.mask, (0, pad)), c.type, c.dictionary)
                for name, c in out.columns.items()}
        return Batch(cols, jnp.pad(out.row_valid, (0, pad)))

    @staticmethod
    def _concatenated(batches: Sequence["Batch"]) -> "Batch":
        """Every lane of compatible batches, in arrival order: one
        eager concatenate per column's data and mask, and row_valid's.
        Nothing moves within a batch; the capacity is the inputs' sum."""
        assert batches
        names = batches[0].names
        first = batches[0]
        dics = {n: first.columns[n].dictionary for n in names}
        for b in batches:
            for n in names:
                if b.columns[n].dictionary != dics[n]:
                    raise ValueError(
                        f"concat with mismatched dictionaries on {n!r}; "
                        "unify dictionaries first")
        cols: Dict[str, Column] = {}
        for n in names:
            typ = first.columns[n].type
            data = jnp.concatenate(
                [b.columns[n].data for b in batches])
            mask = jnp.concatenate(
                [b.columns[n].mask for b in batches])
            cols[n] = Column(data, mask, typ, dics[n])
        rv = jnp.concatenate([b.row_valid for b in batches])
        return Batch(cols, rv)

    @staticmethod
    def concat_lanes(batches: Sequence["Batch"],
                     capacity: int) -> "Batch":
        """Concatenate compatible batches WITHOUT packing: every lane
        stays where it arrived (dead lanes between the live ones
        included), and `capacity - sum(b.capacity)` dead lanes follow.
        For a consumer that reads rows through `row_valid` or by index
        and so has no use for a packed prefix (the join build); the
        inputs must fit `capacity`."""
        big = Batch._concatenated(batches)
        if big.capacity > capacity:
            raise ValueError(
                f"concat_lanes: {big.capacity} input lanes do not fit "
                f"{capacity}")
        if big.capacity == capacity:
            return big
        return _pad_batch(big, capacity - big.capacity)

    @staticmethod
    def concat(batches: Sequence["Batch"], capacity: int,
               live_rows: Optional[int] = None) -> "Batch":
        """Concatenate live rows of compatible batches into one batch.

        Fully device-side: concatenate every (padded) batch, then
        compact live rows to the front — no host materialization. A
        device->host roundtrip here costs a full pipeline flush, which
        used to dominate ORDER BY.
        """
        big = Batch._concatenated(batches)
        if big.capacity == capacity:
            return _compact(big)
        return big.compact(capacity, known_valid=live_rows)


def empty_batch(schema_cols: Sequence[Tuple],
                capacity: int = MIN_CAPACITY) -> "Batch":
    """An all-invalid batch for a (name, type, dictionary) schema —
    the stand-in when a source legitimately yields zero batches
    (pruned scans, blackhole reads, empty build sides)."""
    cols = {
        name: Column(jnp.zeros(capacity, t.np_dtype),
                     jnp.zeros(capacity, bool), t, dic)
        for name, t, dic in schema_cols
    }
    return Batch(cols, jnp.zeros(capacity, bool))


@functools.partial(_kernels.jit, family="compact")
def _compact_jit(batch: Batch) -> Batch:
    from presto_tpu.ops.common import partition_perm
    order = partition_perm(batch.row_valid)
    cols = {
        n: Column(c.data[order], c.mask[order] & batch.row_valid[order],
                  c.type, c.dictionary)
        for n, c in batch.columns.items()
    }
    return Batch(cols, batch.row_valid[order])


@functools.partial(_kernels.jit, family="compact", part="shrink",
                   static_argnums=(1,))
def _compact_shrink_jit(batch: Batch, capacity: int) -> Batch:
    """Pack live rows into a SMALLER batch: indices of the first
    `capacity` live rows (bounded nonzero), then a capacity-sized
    gather per column (the caller guarantees live <= capacity)."""
    from presto_tpu.ops.common import first_true_indices
    idx = first_true_indices(batch.row_valid, capacity,
                             batch.capacity - 1)
    live = jnp.arange(capacity) < jnp.sum(batch.row_valid)
    cols = {
        n: Column(c.data[idx], c.mask[idx] & live, c.type, c.dictionary)
        for n, c in batch.columns.items()
    }
    return Batch(cols, live)


# compile-vs-execute attribution for the compaction family (module-
# level jits previously landed in "execute" via operator busy time)
_instr = _kernels.instrument_kernel

_compact = _instr(_compact_jit, "compact")
_compact_shrink = _instr(_compact_shrink_jit, "compact",
                         jits=[_compact_shrink_jit])


# -- kernel contracts (tools/kernelcheck.py) ---------------------------
from presto_tpu.analysis.contracts import (
    KernelContract, TracePoint, abstract_batch as _abstract_batch,
    register_contract as _register_contract,
)


def _compact_contract_schema():
    from presto_tpu.types import BIGINT, DOUBLE, VARCHAR
    return [("a", BIGINT), ("b", DOUBLE), ("s", VARCHAR, ("x", "y"))]


def _compact_point(cap, variant):
    b, rb = _abstract_batch(cap, _compact_contract_schema())
    return TracePoint(lambda batch: _compact_jit(batch), (b,), (rb,))


def _compact_shrink_point(cap, variant):
    b, rb = _abstract_batch(cap, _compact_contract_schema())
    return TracePoint(
        lambda batch: _compact_shrink_jit(batch, cap // 4),
        (b,), (rb,))


def _pad_point(cap, variant):
    b, rb = _abstract_batch(cap, _compact_contract_schema())
    return TracePoint(
        lambda batch: _pad_batch_jit(batch, 3 * cap), (b,), (rb,))


_register_contract(KernelContract(
    family="pad", module=__name__, build=_pad_point,
    notes="pad_for_kernel's lift to the next power-of-four bucket"))
_register_contract(KernelContract(
    family="compact", module=__name__, build=_compact_point))
_register_contract(KernelContract(
    family="compact", module=__name__, build=_compact_shrink_point,
    structure_varies=True,
    structure_reason="first_true_indices binary-searches the rank "
                     "prefix: log2(capacity) unrolled rounds on the "
                     "CPU side of fast_searchsorted",
    notes="the bounded-nonzero shrink entry point"))


#: Outputs at or under this capacity skip the deferred count/compact
#: round entirely — the padding is too small to matter downstream.
COMPACT_FLOOR = 8192
#: Smallest capacity a deferred compaction shrinks to (keeps the
#: compiled-shape set small: tiny outputs all land on one bucket).
COMPACT_MIN = 1024


def start_async_copy(x):
    """Kick off the device->host transfer of a scalar/array so a later
    blocking read is a cache hit, not a fresh roundtrip. No-op off
    jax.Array (host values, tracers)."""
    try:
        x.copy_to_host_async()
    except (AttributeError, RuntimeError):
        pass
    return x


def begin_deferred_compact(batch: "Batch", total=None):
    """Start the one-round-delayed compaction protocol on a selective
    operator's output: kick off an async device->host copy of the live
    count NOW, so that when the batch is emitted one driver round later
    the count is already on the host and `end_deferred_compact` can
    shrink the batch without a blocking roundtrip (reference seam: the
    page-compaction policy of OptimizedPartitionedOutputOperator).
    Pass `total` when the producing kernel already computed the live
    count (the lookup-join probe does); otherwise one is dispatched
    here. Returns (batch, count_token) — token None when the batch is
    already small."""
    if batch.capacity <= COMPACT_FLOOR:
        return batch, None
    return batch, start_async_copy(
        jnp.sum(batch.row_valid) if total is None else total)


def end_deferred_compact(batch: "Batch", total) -> "Batch":
    """Consume the count started by begin_deferred_compact (normally a
    cache hit, not a fresh roundtrip) and pack the batch down to its
    live bucket. Under kernel shape bucketing the shrink target sits
    on the coarse kernel ladder, so downstream operators never re-pad
    what this just shrank."""
    if total is None:
        return batch
    from presto_tpu.native.pages import to_host
    n = int(to_host(total))
    cap = operator_capacity(n, floor=COMPACT_MIN)
    if cap < batch.capacity:
        return batch.compact(cap, known_valid=n)
    return batch


def unify_dictionaries(cols: Sequence[Column]) -> List[Column]:
    """Re-encode string columns onto a shared sorted dictionary so their
    codes are directly comparable (needed before joins/set-ops on VARCHAR).
    Host-side; O(total dictionary size)."""
    for c in cols:
        if c.dictionary is None:
            raise ValueError(
                "unify_dictionaries: string column without a dictionary; "
                "from_numpy callers must supply one for varchar columns")
    merged = sorted(set().union(*[set(c.dictionary) for c in cols]))
    dic = tuple(merged)
    index = {v: i for i, v in enumerate(merged)}
    out = []
    for c in cols:
        if c.dictionary == dic:
            out.append(Column(c.data, c.mask, c.type, dic))
            continue
        remap = np.array([index[v] for v in c.dictionary] or [0],
                         dtype=np.int32)
        out.append(Column(jnp.asarray(remap)[c.data], c.mask, c.type, dic))
    return out


def union_dictionary(a: Optional[Tuple[str, ...]],
                     b: Optional[Tuple[str, ...]]) -> Tuple[str, ...]:
    """The union dictionary two string join-key sides re-encode onto
    (sorted, so code order = string order). THE one definition: the
    analyzer tags join output fields with it and the local planner
    builds runtime remap tables from it — computed differently they
    would silently decode garbage downstream."""
    return tuple(sorted(set(a or ()) | set(b or ())))


def remap_column(col: Column, target: Tuple[str, ...]) -> Column:
    """Re-encode a string column onto `target` (a superset dictionary,
    sorted). Used to align join-key codes across tables."""
    if col.dictionary == target:
        return col
    if col.dictionary is None:
        raise ValueError("remap_column: column has no dictionary")
    index = {v: i for i, v in enumerate(target)}
    remap = np.array([index[v] for v in col.dictionary] or [0],
                     dtype=np.int32)
    return Column(jnp.asarray(remap)[col.data], col.mask, col.type,
                  target)


def _to_unscaled(v, scale: int) -> int:
    """Exact decimal encoding: ints and Decimals never pass through float."""
    import decimal as _dec
    if isinstance(v, bool):
        raise TypeError("boolean is not a decimal value")
    if isinstance(v, int):
        return v * (10 ** scale)
    if isinstance(v, _dec.Decimal):
        return int((v * (10 ** scale)).to_integral_value(
            rounding=_dec.ROUND_HALF_UP))
    return int(round(float(v) * (10 ** scale)))
