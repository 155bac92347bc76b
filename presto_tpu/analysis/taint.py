"""Pad-invariance taint walk over a traced jaxpr.

THE invariant of shape bucketing (batch.pad_for_kernel): a kernel's
LIVE outputs — lanes its output masks/row_valid mark True, and every
scalar it returns — must not depend on the garbage a padded batch
carries in its dead lanes. Runtime byte-identity oracles sample this
at a handful of shapes; this walk PROVES it per traced program, by
abstract-interpreting the jaxpr over a three-point taint lattice:

    CLEAN   value nowhere depends on dead-lane garbage
    PAD     lane-aligned array: live lanes clean, dead lanes may carry
            garbage (the state of every raw input data column)
    POISON  garbage may have escaped into a live position or a scalar
            — a pad-invariance violation if it reaches an output

plus a POLARITY fact for boolean arrays (`dead_false` = the value at
every dead lane is definitely False — a mask; `dead_true` — an
inverted mask). Polarity is what recognizes the engine's neutralizing
idioms as proofs:

    jnp.where(mask, x, sentinel)   select_n on a dead_false predicate
                                   picks the CLEAN branch on dead
                                   lanes -> result CLEAN
    rv & expr                      AND with a dead_false CLEAN operand
                                   pins dead lanes False -> CLEAN
    lax.sort((h, *payloads))       all-CLEAN keys => the permutation
                                   is garbage-independent: each output
                                   keeps its own input taint

and what makes the canonical leak loud: `jnp.sum(x)` over a PAD array
reduces garbage into a scalar -> POISON, reported with the offending
eqn and its source line.

Soundness stance: this is a LINTER-grade analysis, not a verifier.
Two deliberate approximations are documented here and in
docs/KERNEL_CONTRACTS.md: (1) PAD survives lane-permuting ops (gather
by clean indices, all-clean-key sorts) on the assumption that masks
travel through the SAME permutation as their data — true of every
engine kernel, not checked per-pair; (2) polarity is preserved through
those same permutations. Unknown primitives over tainted operands are
conservatively POISON, so new jaxpr surface fails loud, not silent."""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

CLEAN, PAD, POISON = 0, 1, 2
_TAINT_NAME = {CLEAN: "CLEAN", PAD: "PAD", POISON: "POISON"}

#: input roles a contract assigns to flattened argument leaves
ROLE_DATA = "data"    # raw column data: garbage at dead lanes
ROLE_MASK = "mask"    # validity/row_valid: CLEAN, dead lanes False
ROLE_CLEAN = "clean"  # scalars, tables, state: garbage-free upstream


@dataclasses.dataclass
class AV:
    """Abstract value of one jaxpr var."""
    taint: int = CLEAN
    pol: Optional[str] = None   # "dead_false" | "dead_true" | None
    origin: Optional[str] = None  # where POISON was introduced

    def poisoned(self, origin: str) -> "AV":
        return AV(POISON, None, self.origin or origin)


@dataclasses.dataclass
class Leak:
    """One garbage escape: the eqn that turned PAD into POISON."""
    primitive: str
    source: str          # "file:line (fn)" from jax source info
    detail: str

    def __str__(self) -> str:
        return f"{self.primitive} at {self.source}: {self.detail}"


def av_for_role(role: str) -> AV:
    if role == ROLE_DATA:
        return AV(PAD)
    if role == ROLE_MASK:
        return AV(CLEAN, "dead_false")
    return AV(CLEAN)


def _source_of(eqn) -> str:
    try:
        from jax._src import source_info_util
        return source_info_util.summarize(eqn.source_info)
    except Exception:  # noqa: BLE001 — source info is best-effort
        return "<unknown>"


def _join(a: AV, b: AV) -> AV:
    """Lattice join (for loop fixpoints / cond branches)."""
    return AV(max(a.taint, b.taint),
              a.pol if a.pol == b.pol else None,
              a.origin or b.origin)


# -- primitive classes -------------------------------------------------

#: lane-preserving elementwise/structural ops: output taint is the max
#: of input taints, lane alignment (and with it PAD confinement) holds
_ELEMENTWISE = frozenset({
    "add", "sub", "mul", "div", "rem", "pow", "integer_pow", "neg",
    "abs", "sign", "floor", "ceil", "round", "exp", "log", "log1p",
    "expm1", "sqrt", "rsqrt", "square", "tanh", "logistic", "erf",
    "erf_inv", "sin", "cos", "tan", "atan2", "max", "min", "nextafter",
    "and", "or", "xor", "not", "shift_left", "shift_right_logical",
    "shift_right_arithmetic", "eq", "ne", "lt", "le", "gt", "ge",
    "clamp", "is_finite", "population_count", "clz",
    "convert_element_type", "bitcast_convert_type", "reduce_precision",
    "stop_gradient", "copy", "real", "imag", "exp2", "cbrt", "asin",
    "acos", "atan", "sinh", "cosh", "asinh", "acosh", "atanh",
    "erfc", "lgamma", "digamma", "device_put",
})

#: structural ops that move/duplicate lanes without mixing values;
#: PAD stays PAD, polarity is dropped (lane positions shift)
_STRUCTURAL = frozenset({
    "reshape", "squeeze", "expand_dims", "transpose", "rev", "slice",
    "dynamic_slice", "concatenate", "pad", "broadcast_in_dim", "tie_in",
    # all_to_all moves whole lane blocks between shards without mixing
    # values: PAD stays confined to the lanes that carried it (the
    # sharded shuffle's collective — parallel/shuffle.py)
    "all_to_all",
})

#: cross-lane escapes: a reduction over the lane axis pulls dead-lane
#: values into a result consumed as live
_REDUCTIONS = frozenset({
    "reduce_sum", "reduce_prod", "reduce_max", "reduce_min",
    "reduce_and", "reduce_or", "reduce_xor", "argmax", "argmin",
    "reduce_window_sum", "reduce_window_max", "reduce_window_min",
})

#: prefix scans smear a dead lane's garbage into every later lane
_CUMULATIVE = frozenset({
    "cumsum", "cumprod", "cummax", "cummin", "cumlogsumexp",
})

#: value-mixing contractions: garbage anywhere contaminates everything
_CONTRACTIONS = frozenset({"dot_general", "conv_general_dilated"})

#: side-effecting / host-boundary primitives (the purity contract —
#: checked separately in checker.py, but the taint walk also treats
#: their results as CLEAN-but-opaque)
IMPURE_PRIMITIVES = frozenset({
    "pure_callback", "io_callback", "callback", "debug_callback",
    "debug_print", "infeed", "outfeed", "host_callback_call",
    "outside_call",
})

#: call-like params whose value is a (Closed)Jaxpr to recurse into
_JAXPR_PARAMS = ("jaxpr", "call_jaxpr", "cond_jaxpr", "body_jaxpr")


class _Interp:
    def __init__(self):
        self.leaks: List[Leak] = []

    # -- env helpers ---------------------------------------------------

    def _read(self, env: Dict, v) -> AV:
        from jax.extend import core as jc
        if isinstance(v, jc.Literal):
            return AV(CLEAN)
        return env.get(v, AV(CLEAN))

    def _leak(self, eqn, ins: Sequence[AV], detail: str) -> AV:
        src = _source_of(eqn)
        origin = f"{eqn.primitive.name} at {src}"
        # only record the FIRST escape along a dataflow path — the
        # downstream propagation of an existing POISON is noise
        if not any(a.taint == POISON for a in ins):
            self.leaks.append(Leak(eqn.primitive.name, src, detail))
        worst = max((a for a in ins), key=lambda a: a.taint,
                    default=AV(CLEAN))
        return AV(POISON, None, worst.origin or origin)

    # -- the transfer function -----------------------------------------

    def run(self, jaxpr, in_avs: Sequence[AV],
            const_avs: Optional[Sequence[AV]] = None) -> List[AV]:
        env: Dict = {}
        for var, av in zip(jaxpr.invars, in_avs):
            env[var] = av
        for var, av in zip(jaxpr.constvars,
                           const_avs or [AV(CLEAN)] * len(
                               jaxpr.constvars)):
            env[var] = av
        for eqn in jaxpr.eqns:
            ins = [self._read(env, v) for v in eqn.invars]
            outs = self._eqn(eqn, ins)
            for var, av in zip(eqn.outvars, outs):
                env[var] = av
        return [self._read(env, v) for v in jaxpr.outvars]

    def _eqn(self, eqn, ins: List[AV]) -> List[AV]:
        name = eqn.primitive.name
        n_out = len(eqn.outvars)

        if name == "select_n":
            return [self._select(eqn, ins)]
        if name == "sort":
            return self._sort(eqn, ins)
        if name == "gather":
            return [self._gather(eqn, ins)]
        if name.startswith("scatter"):
            return [self._scatter(eqn, ins, name)]
        if name == "dynamic_update_slice":
            return [self._dus(eqn, ins)]
        if name == "while":
            return self._while(eqn, ins)
        if name == "scan":
            return self._scan(eqn, ins)
        if name == "cond":
            return self._cond(eqn, ins)
        if name in ("jit", "closed_call", "core_call",
                    "custom_jvp_call", "custom_vjp_call", "remat",
                    "remat2", "checkpoint", "custom_vjp_call_jaxpr",
                    # shard_map carries its body as the `jaxpr` param;
                    # lane alignment holds per shard, so recursing is
                    # exact (the sharded kernel families' KC001 path)
                    "shard_map"):
            return self._call(eqn, ins, n_out)
        if name in IMPURE_PRIMITIVES:
            # purity is its own contract; taint-wise the result is
            # opaque host data — treat tainted operands as escaping
            if any(a.taint >= PAD for a in ins):
                return [self._leak(eqn, ins,
                                   "tainted operand crosses the host "
                                   "callback boundary")] * n_out
            return [AV(CLEAN)] * n_out

        if name in _REDUCTIONS:
            return [self._reduce(eqn, ins)] * n_out
        if name in _CUMULATIVE:
            return [self._cumulative(eqn, ins)] * n_out
        if name in _CONTRACTIONS:
            if any(a.taint >= PAD for a in ins):
                return [self._leak(
                    eqn, ins, "contraction mixes pad-tainted lanes "
                    "into every output element")] * n_out
            return [AV(CLEAN)] * n_out

        if name in _ELEMENTWISE:
            return [self._elementwise(name, eqn, ins)] * n_out
        if name in _STRUCTURAL:
            if any(a.taint == POISON for a in ins):
                return [AV(POISON, None, ins[0].origin)] * n_out
            t = max((a.taint for a in ins), default=CLEAN)
            return [AV(t, self._structural_pol(eqn, name, ins)
                       if t == CLEAN else None)] * n_out
        if name == "iota":
            return [AV(CLEAN)] * n_out

        # unknown primitive: loud, not silent
        if any(a.taint >= PAD for a in ins):
            return [self._leak(
                eqn, ins,
                f"primitive {name!r} has no transfer rule; "
                "pad-tainted operands are conservatively a leak "
                "(teach analysis/taint.py about it if it is lane-"
                "preserving)")] * n_out
        return [AV(CLEAN)] * n_out

    # -- rules ---------------------------------------------------------

    def _structural_pol(self, eqn, name: str,
                        ins: List[AV]) -> Optional[str]:
        """Polarity through lane-moving structural ops: every output
        lane copies exactly one input lane, so a fact true at every
        dead lane of every input survives — concat of two masks is a
        mask. `pad` additionally appends constant lanes: the fact only
        survives when the padding value is the polarity's constant
        (False lanes for dead_false — exactly what _pad_batch
        appends)."""
        from jax.extend import core as jc
        pols = {a.pol for a in ins if a.pol is not None}
        if len(pols) != 1 or any(a.pol is None and a.taint != CLEAN
                                 for a in ins):
            return None
        pol = pols.pop()
        if any(a.pol is None for a in ins):
            # unpolarized CLEAN operands: fine for pad's fill value /
            # dynamic_slice's start indices (scalars — they contribute
            # no lanes), unsafe for concatenate (whole lane blocks)
            if name == "concatenate":
                return None
            if name == "pad":
                fill = eqn.invars[1] if len(eqn.invars) > 1 else None
                ok = isinstance(fill, jc.Literal) and not bool(
                    getattr(fill, "val", True))
                if not (ok and pol == "dead_false"):
                    return None
            elif name not in ("dynamic_slice", "broadcast_in_dim"):
                return None
        return pol

    def _elementwise(self, name: str, eqn, ins: List[AV]) -> AV:
        if any(a.taint == POISON for a in ins):
            return AV(POISON, None,
                      next(a.origin for a in ins
                           if a.taint == POISON))
        if name == "not" and len(ins) == 1:
            flip = {"dead_false": "dead_true", "dead_true": "dead_false"}
            return AV(ins[0].taint, flip.get(ins[0].pol))
        if name == "and":
            a, b = ins
            # AND with a dead-lanes-False CLEAN operand pins dead
            # lanes to False: kills the other side's pad garbage
            for x, y in ((a, b), (b, a)):
                if x.pol == "dead_false" and x.taint == CLEAN \
                        and y.taint <= PAD:
                    return AV(CLEAN, "dead_false")
            t = max(a.taint, b.taint)
            pol = "dead_true" if t == CLEAN \
                and a.pol == b.pol == "dead_true" else None
            return AV(t, pol)
        if name == "or":
            a, b = ins
            for x, y in ((a, b), (b, a)):
                if x.pol == "dead_true" and x.taint == CLEAN \
                        and y.taint <= PAD:
                    return AV(CLEAN, "dead_true")
            t = max(a.taint, b.taint)
            pol = "dead_false" if t == CLEAN \
                and a.pol == b.pol == "dead_false" else None
            return AV(t, pol)
        if name == "convert_element_type" and len(ins) == 1:
            # a bool widened to an integer lane (common.lex_perm's
            # 32-bit sort keys) is as deterministic on dead lanes as
            # the bool it came from
            new = eqn.params.get("new_dtype")
            widened_bool = str(eqn.invars[0].aval.dtype) == "bool" \
                and np.issubdtype(new, np.integer)
            keep = ins[0].pol if str(new) == "bool" or widened_bool \
                else None
            return AV(ins[0].taint, keep)
        t = max((a.taint for a in ins), default=CLEAN)
        return AV(t)

    def _select(self, eqn, ins: List[AV]) -> AV:
        pred, cases = ins[0], ins[1:]
        if any(a.taint == POISON for a in ins):
            return AV(POISON, None,
                      next((a.origin for a in ins
                            if a.taint == POISON), None))
        dead_sel = None
        if pred.taint == CLEAN and pred.pol == "dead_false":
            dead_sel = cases[0]       # False selects case 0
        elif pred.taint == CLEAN and pred.pol == "dead_true":
            dead_sel = cases[-1]
        if dead_sel is not None:
            # live lanes come from live lanes (clean for <= PAD
            # cases); dead lanes from the selected case's dead lanes
            return AV(dead_sel.taint, dead_sel.pol)
        t = max((a.taint for a in ins), default=CLEAN)
        return AV(t)

    def _sort(self, eqn, ins: List[AV]) -> List[AV]:
        num_keys = eqn.params.get("num_keys", 1)
        keys, payloads = ins[:num_keys], ins[num_keys:]
        if any(a.taint == POISON for a in ins):
            return [AV(POISON, None, a.origin) for a in ins]
        if all(a.taint == CLEAN for a in keys):
            # garbage-independent permutation applied to every
            # operand: each output keeps its own taint AND polarity
            # (alignment approximation — see module docstring)
            return [AV(a.taint, a.pol) for a in ins]
        lead = keys[0]
        if lead.taint == CLEAN and lead.pol in ("dead_false",
                                                "dead_true"):
            # leading key partitions live/dead rows deterministically
            # (the ~valid-leading idiom): garbage keys only permute
            # rows WITHIN the dead block. The leading key's own output
            # is deterministic; every other operand's dead block
            # becomes garbage-ordered -> PAD
            out = [AV(CLEAN, lead.pol)]
            out.extend(AV(max(a.taint, PAD)) for a in ins[1:])
            return out
        return [self._leak(
            eqn, ins, "sort keyed on pad-tainted values reorders "
            "live rows by dead-lane garbage (canonicalize keys with "
            "jnp.where(mask, v, sentinel) or lead with ~valid)")] \
            * len(ins)

    def _gather(self, eqn, ins: List[AV]) -> AV:
        data, idx = ins[0], ins[1]
        if data.taint == POISON or idx.taint == POISON:
            return AV(POISON, None, data.origin or idx.origin)
        if idx.taint == PAD or data.taint == PAD:
            return AV(PAD, data.pol if idx.taint == CLEAN else None)
        return AV(CLEAN, data.pol)

    def _scatter(self, eqn, ins: List[AV], name: str) -> AV:
        base, idx, upd = ins[0], ins[1], ins[2] if len(ins) > 2 \
            else AV(CLEAN)
        if any(a.taint == POISON for a in ins):
            return AV(POISON, None, base.origin or idx.origin
                      or upd.origin)
        combining = name != "scatter"  # scatter-add/min/max/mul/...
        if idx.taint == PAD:
            return self._leak(
                eqn, ins, "scatter indexed by pad-tainted positions "
                "can overwrite live lanes")
        if combining and upd.taint == PAD:
            return self._leak(
                eqn, ins, f"{name} folds pad-tainted updates into "
                "its operand (gate updates with the contribute mask "
                "first: jnp.where(w, v, identity))")
        return AV(max(base.taint, upd.taint))

    def _dus(self, eqn, ins: List[AV]) -> AV:
        base, upd, starts = ins[0], ins[1], ins[2:]
        if any(a.taint == POISON for a in ins):
            return AV(POISON, None, base.origin or upd.origin)
        if any(a.taint >= PAD for a in starts):
            return self._leak(eqn, ins,
                              "dynamic_update_slice at a pad-tainted "
                              "offset")
        return AV(max(base.taint, upd.taint))

    def _reduce(self, eqn, ins: List[AV]) -> AV:
        if any(a.taint == POISON for a in ins):
            return AV(POISON, None,
                      next(a.origin for a in ins if a.taint == POISON))
        axes = eqn.params.get("axes", None)
        lane_axis_reduced = axes is None or 0 in tuple(axes)
        worst = max((a.taint for a in ins), default=CLEAN)
        if worst == PAD and lane_axis_reduced:
            return self._leak(
                eqn, ins,
                f"{eqn.primitive.name} over the lane axis of a "
                "pad-tainted array folds dead-lane garbage into the "
                "result (mask first: jnp.where(valid, x, identity))")
        return AV(worst if not lane_axis_reduced else CLEAN)

    def _cumulative(self, eqn, ins: List[AV]) -> AV:
        if any(a.taint == POISON for a in ins):
            return AV(POISON, None, ins[0].origin)
        if any(a.taint == PAD for a in ins):
            return self._leak(
                eqn, ins,
                f"{eqn.primitive.name} smears dead-lane garbage into "
                "every later lane (neutralize dead lanes first)")
        return AV(CLEAN)

    # -- higher-order --------------------------------------------------

    def _closed(self, cj):
        """(jaxpr, const avs) of a ClosedJaxpr-or-Jaxpr param."""
        inner = getattr(cj, "jaxpr", cj)
        consts = getattr(cj, "consts", ())
        return inner, [AV(CLEAN)] * len(getattr(inner, "constvars", ()))

    def _call(self, eqn, ins: List[AV], n_out: int) -> List[AV]:
        for key in _JAXPR_PARAMS:
            cj = eqn.params.get(key)
            if cj is not None:
                inner, consts = self._closed(cj)
                return self.run(inner, ins, consts)
        # a call-like primitive without a visible jaxpr: conservative
        if any(a.taint >= PAD for a in ins):
            return [self._leak(eqn, ins,
                               "opaque call over tainted operands")] \
                * n_out
        return [AV(CLEAN)] * n_out

    def _while(self, eqn, ins: List[AV]) -> List[AV]:
        p = eqn.params
        cn, bn = p["cond_nconsts"], p["body_nconsts"]
        cond_consts = ins[:cn]
        body_consts = ins[cn:cn + bn]
        carry = list(ins[cn + bn:])
        body, _ = self._closed(p["body_jaxpr"])
        cond, _ = self._closed(p["cond_jaxpr"])
        for _ in range(8):  # lattice height bounds convergence
            out = self.run(body, body_consts + carry)
            nxt = [_join(a, b) for a, b in zip(carry, out)]
            if all(a.taint == b.taint and a.pol == b.pol
                   for a, b in zip(carry, nxt)):
                break
            carry = nxt
        pred = self.run(cond, cond_consts + carry)
        if pred and pred[0].taint >= PAD:
            leak = self._leak(
                eqn, [pred[0]],
                "while_loop trip count depends on pad-tainted data "
                "(every carried value becomes garbage-dependent)")
            return [leak for _ in carry]
        return carry

    def _scan(self, eqn, ins: List[AV]) -> List[AV]:
        p = eqn.params
        nc, ncar = p["num_consts"], p["num_carry"]
        consts = ins[:nc]
        carry = list(ins[nc:nc + ncar])
        xs = ins[nc + ncar:]
        body, _ = self._closed(p["jaxpr"])
        ys: List[AV] = []
        for _ in range(8):
            out = self.run(body, consts + carry + xs)
            car_out, ys = out[:ncar], out[ncar:]
            nxt = [_join(a, b) for a, b in zip(carry, car_out)]
            if all(a.taint == b.taint and a.pol == b.pol
                   for a, b in zip(carry, nxt)):
                break
            carry = nxt
        return carry + list(ys)

    def _cond(self, eqn, ins: List[AV]) -> List[AV]:
        idx, ops = ins[0], ins[1:]
        branches = eqn.params["branches"]
        outs: Optional[List[AV]] = None
        for br in branches:
            inner, consts = self._closed(br)
            got = self.run(inner, ops, consts)
            outs = got if outs is None \
                else [_join(a, b) for a, b in zip(outs, got)]
        outs = outs or []
        if idx.taint >= PAD:
            leak = self._leak(eqn, [idx],
                              "cond branch selection depends on "
                              "pad-tainted data")
            return [leak for _ in outs]
        return outs


def analyze(closed_jaxpr, in_avs: Sequence[AV]
            ) -> Tuple[List[AV], List[Leak]]:
    """Run the taint walk over a ClosedJaxpr (jax.make_jaxpr output).
    Returns (output abstract values, leaks recorded along the way).
    A kernel satisfies pad-invariance iff no output is POISON — PAD
    outputs are legal (dead output lanes travel with their masks and
    are never read downstream)."""
    interp = _Interp()
    jaxpr = closed_jaxpr.jaxpr
    const_avs = [AV(CLEAN)] * len(jaxpr.constvars)
    outs = interp.run(jaxpr, list(in_avs), const_avs)
    return outs, interp.leaks
