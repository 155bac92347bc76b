"""Structural jaxpr fingerprints — the retrace-budget evidence.

jax compiles once per (program structure, input signature): two calls
whose traces produce byte-identical jaxprs against identical avals
share one executable. So distinct-compile counts are STATICALLY
predictable from trace fingerprints:

  * exact fingerprint — the canonical rendering of the whole jaxpr:
    primitives, params, avals (shapes + dtypes), literal constants.
    Distinct exact fingerprints over a sampled grid = predicted
    distinct compiles.
  * normalized fingerprint — the same rendering with every digit run
    squashed to '#': shape constants, iota sizes, literal values all
    collapse. Two points whose exact fingerprints differ while their
    normalized ones MATCH differ only in baked-in numbers — the
    signature of an operand value (a LIMIT, a top-k n) minting traces,
    exactly the compile-wall class PR 6 eliminated by making such
    operands traced.

Canonicalization guards against process-dependent reprs: memory
addresses are masked, sub-jaxprs recurse structurally, and constants
hash by content."""

from __future__ import annotations

import hashlib
import re
from typing import List

_ADDR = re.compile(r"0x[0-9a-fA-F]+")
_DIGITS = re.compile(r"\d+")


def _const_token(c) -> str:
    import numpy as np
    try:
        arr = np.asarray(c)
        if arr.size <= 1 << 16:
            h = hashlib.blake2b(arr.tobytes(), digest_size=8)
            h.update(str(arr.dtype).encode())
            return f"const[{arr.dtype}{arr.shape}#{h.hexdigest()}]"
        return f"const[{arr.dtype}{arr.shape}]"
    except Exception:  # noqa: BLE001 — opaque const
        return f"const[{type(c).__name__}]"


def _render_param(v, depth: int) -> str:
    # sub-jaxprs recurse; everything else reprs with addresses masked
    if hasattr(v, "jaxpr") or hasattr(v, "eqns"):
        return "{" + _render_jaxpr(getattr(v, "jaxpr", v), depth + 1) \
            + "}"
    if isinstance(v, (tuple, list)):
        return "(" + ",".join(_render_param(x, depth) for x in v) + ")"
    return _ADDR.sub("0x#", repr(v))


def _render_jaxpr(jaxpr, depth: int = 0) -> str:
    if depth > 16:
        return "<deep>"
    from jax.extend import core as jc
    ids = {}

    def vid(v) -> str:
        if isinstance(v, jc.Literal):
            return f"lit({_ADDR.sub('0x#', repr(v.val))}:" \
                   f"{getattr(v, 'aval', '')})"
        if v not in ids:
            ids[v] = len(ids)
        return f"v{ids[v]}"

    lines: List[str] = []
    lines.append("in:" + ",".join(
        f"{vid(v)}:{v.aval}" for v in jaxpr.invars))
    lines.append("const:" + ",".join(
        f"{vid(v)}:{v.aval}" for v in jaxpr.constvars))
    for eqn in jaxpr.eqns:
        params = ";".join(
            f"{k}={_render_param(v, depth)}"
            for k, v in sorted(eqn.params.items()))
        lines.append(
            f"{eqn.primitive.name}[{params}]"
            + "(" + ",".join(vid(v) for v in eqn.invars) + ")->"
            + ",".join(f"{vid(v)}:{v.aval}" for v in eqn.outvars))
    lines.append("out:" + ",".join(vid(v) for v in jaxpr.outvars))
    return "\n".join(lines)


def exact_fingerprint(closed_jaxpr) -> str:
    """Content digest of the canonical rendering + constants."""
    body = _render_jaxpr(closed_jaxpr.jaxpr)
    consts = ",".join(_const_token(c) for c in closed_jaxpr.consts)
    h = hashlib.blake2b(digest_size=16)
    h.update(body.encode())
    h.update(consts.encode())
    return h.hexdigest()


def normalized_fingerprint(closed_jaxpr) -> str:
    """Digest with every number squashed — shape/value-blind
    structure."""
    body = _DIGITS.sub("#", _render_jaxpr(closed_jaxpr.jaxpr))
    consts = ",".join(
        _DIGITS.sub("#", _const_token(c).split("#")[0])
        for c in closed_jaxpr.consts)
    h = hashlib.blake2b(digest_size=16)
    h.update(body.encode())
    h.update(consts.encode())
    return h.hexdigest()
