"""Compile RowExpressions into jax-traceable functions.

This is the XLA replacement for the reference's expression codegen
(sql/gen/PageFunctionCompiler.java:118): a fully-typed RowExpression tree
becomes a closure `env -> (data, mask)` over `{name: (data, mask)}`
column environments. XLA fuses the whole tree (plus the surrounding
filter/project kernel) into one program — there is no interpreter at
batch time.

Null semantics: every value is a (data, mask) pair, mask True = present.
Functions default to "null if any input null" (the reference's
RETURN_NULL_ON_NULL calling convention); AND/OR implement Kleene
three-valued logic; IF/CASE treat NULL conditions as false.

Strings: VARCHAR data is dictionary codes. String predicates (LIKE, IN,
comparisons against literals) are evaluated host-side over the (tiny,
static) dictionary at *compile* time, becoming boolean/int lookup tables
the device just gathers from. String-producing functions (substr, upper,
...) map the dictionary host-side and re-encode codes through a remap
table, preserving the sorted-unique dictionary invariant.
"""

from __future__ import annotations

import dataclasses
import math
import re
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from presto_tpu.expr import dates as D
from presto_tpu.expr.ir import (
    Call, InputRef, Literal, RowExpression, SpecialForm,
)
from presto_tpu.ops.common import float64_bits
from presto_tpu.schema import ColumnSchema
from presto_tpu.types import (
    BIGINT, BOOLEAN, DATE, DOUBLE, INTEGER, INTERVAL_DAY, INTERVAL_YEAR,
    REAL, Type, UNKNOWN, VARCHAR, decimal_type,
)

CVal = Tuple[jnp.ndarray, jnp.ndarray]  # (data, mask)
Env = Dict[str, CVal]


@dataclasses.dataclass
class CompiledExpr:
    """fn(env) -> (data, mask); `dictionary` set when type is a string.

    `ir` is the source RowExpression — frozen/hashable, used as the cache
    key that lets operators reuse jit-compiled kernels across queries
    (the analog of the reference's generated-class cache in
    PageFunctionCompiler.java:118's CacheBuilder)."""
    fn: Callable[[Env], CVal]
    type: Type
    dictionary: Optional[Tuple[str, ...]] = None
    ir: Optional[RowExpression] = None


class ExpressionCompileError(Exception):
    pass


def compile_expression(expr: RowExpression,
                       schema: Dict[str, ColumnSchema]) -> CompiledExpr:
    # host-side closure building is the non-XLA share of plan->kernel
    # cost; telemetry splits it out from jit compile/execute so EXPLAIN
    # ANALYZE and /v1/metrics can attribute all three
    import time as _time

    from presto_tpu.telemetry import kernels as _tk
    if not _tk.ENABLED:
        ce = _Compiler(schema).compile(expr)
        ce.ir = expr
        return ce
    t0 = _time.perf_counter_ns()
    ce = _Compiler(schema).compile(expr)
    ce.ir = expr
    _tk.record_expr_compile(_time.perf_counter_ns() - t0)
    return ce


# ---------------------------------------------------------------------------

_TRUE = (jnp.asarray(True), jnp.asarray(True))


def _scalar(value, typ: Type) -> CVal:
    if value is None:
        return (jnp.zeros((), typ.np_dtype), jnp.asarray(False))
    return (jnp.asarray(value, typ.np_dtype), jnp.asarray(True))


def _like_to_regex(pattern: str, escape: Optional[str] = None) -> str:
    out = []
    i = 0
    esc = escape
    while i < len(pattern):
        ch = pattern[i]
        if esc and ch == esc and i + 1 < len(pattern):
            out.append(re.escape(pattern[i + 1]))
            i += 2
            continue
        if ch == "%":
            out.append(".*")
        elif ch == "_":
            out.append(".")
        else:
            out.append(re.escape(ch))
        i += 1
    return "^" + "".join(out) + "$"


#: per-evaluation memo for SHARED IR subtrees: the analyzer emits DAGs
#: (decorrelated plans, lambda reduce() chains where the accumulator
#: appears in both branches of every step's IF) — without sharing, a
#: width-W reduce would trace 2^W accumulator evaluations. Thread-local
#: because compiled closures may evaluate concurrently across drivers.
import threading as _threading

_EVAL_MEMO = _threading.local()


def _share(fn, key: int):
    """Wrap a compiled closure so one EVALUATION of a shared node runs
    once per env (trace-time sharing == shared HLO subgraph)."""
    def wrapped(env):
        memo = getattr(_EVAL_MEMO, "m", None)
        top = memo is None
        if top:
            memo = {}
            _EVAL_MEMO.m = memo
        try:
            k = (id(env), key)
            hit = memo.get(k)
            if hit is None:
                hit = fn(env)
                memo[k] = hit
            return hit
        finally:
            if top:
                _EVAL_MEMO.m = None
    return wrapped


class _Compiler:
    def __init__(self, schema: Dict[str, ColumnSchema]):
        self.schema = schema
        #: id(node) -> CompiledExpr. Safe: the root expression keeps
        #: every child alive for the compiler's lifetime, so ids
        #: cannot be recycled mid-compilation.
        self._memo: Dict[int, CompiledExpr] = {}

    def compile(self, expr: RowExpression) -> CompiledExpr:
        hit = self._memo.get(id(expr))
        if hit is not None:
            return hit
        if isinstance(expr, Literal):
            out = self._literal(expr)
        elif isinstance(expr, InputRef):
            out = self._input(expr)
        elif isinstance(expr, SpecialForm):
            out = self._special(expr)
        elif isinstance(expr, Call):
            out = self._call(expr)
        else:
            raise ExpressionCompileError(
                f"unknown expression node: {expr!r}")
        out = CompiledExpr(_share(out.fn, id(expr)), out.type,
                           out.dictionary, out.ir)
        self._memo[id(expr)] = out
        return out

    # -- leaves ------------------------------------------------------------

    def _literal(self, e: Literal) -> CompiledExpr:
        if e.type.is_string:
            # A bare string literal only materializes through a parent that
            # consumes it (comparison/LIKE/IN); encode as 1-value dictionary.
            if e.value is None:
                return CompiledExpr(lambda env: _scalar(None, e.type),
                                    e.type, ())
            return CompiledExpr(lambda env: _scalar(0, e.type),
                                e.type, (e.value,))
        val = e.value
        return CompiledExpr(lambda env: _scalar(val, e.type), e.type)

    def _input(self, e: InputRef) -> CompiledExpr:
        cs = self.schema.get(e.name)
        if cs is None:
            raise ExpressionCompileError(f"unknown input column {e.name!r}")
        name = e.name
        return CompiledExpr(lambda env: env[name], cs.type, cs.dictionary)

    # -- special forms -----------------------------------------------------

    def _special(self, e: SpecialForm) -> CompiledExpr:
        form = e.form
        if form == "and":
            parts = [self.compile(a) for a in e.args]

            def f_and(env):
                d, m = _TRUE
                for p in parts:
                    pd, pm = p.fn(env)
                    # Kleene: false wins over null
                    new_d = d & pd
                    new_m = (m & pm) | (m & ~d) | (pm & ~pd)
                    d, m = new_d, new_m
                return d, m
            return CompiledExpr(f_and, BOOLEAN)
        if form == "or":
            parts = [self.compile(a) for a in e.args]

            def f_or(env):
                d = jnp.asarray(False)
                m = jnp.asarray(True)
                for p in parts:
                    pd, pm = p.fn(env)
                    new_d = d | pd
                    new_m = (m & pm) | (m & d) | (pm & pd)
                    d, m = new_d, new_m
                return d, m
            return CompiledExpr(f_or, BOOLEAN)
        if form == "not":
            a = self.compile(e.args[0])

            def f_not(env):
                d, m = a.fn(env)
                return ~d, m
            return CompiledExpr(f_not, BOOLEAN)
        if form == "is_null":
            a = self.compile(e.args[0])
            return CompiledExpr(
                lambda env: (~a.fn(env)[1], jnp.asarray(True)), BOOLEAN)
        if form == "is_not_null":
            a = self.compile(e.args[0])
            return CompiledExpr(
                lambda env: (a.fn(env)[1], jnp.asarray(True)), BOOLEAN)
        if form == "if":
            cond = self.compile(e.args[0])
            then = self.compile(e.args[1])
            els = self.compile(e.args[2])
            dic = _merge_result_dicts(e.type, then, els)
            if dic is not None:
                then = _remap_to(then, dic)
                els = _remap_to(els, dic)

            def f_if(env):
                cd, cm = cond.fn(env)
                take_then = cd & cm  # NULL condition -> false branch
                td, tm = then.fn(env)
                ed, em = els.fn(env)
                td, ed = _common_broadcast(td, ed)
                tm, em = _common_broadcast(tm, em)
                return (jnp.where(take_then, td, ed),
                        jnp.where(take_then, tm, em))
            return CompiledExpr(f_if, e.type, dic)
        if form == "coalesce":
            parts = [self.compile(a) for a in e.args]
            dic = _merge_result_dicts(e.type, *parts)
            if dic is not None:
                parts = [_remap_to(p, dic) for p in parts]

            def f_coalesce(env):
                d, m = parts[0].fn(env)
                for p in parts[1:]:
                    pd, pm = p.fn(env)
                    d, pd = _common_broadcast(d, pd)
                    m, pm = _common_broadcast(m, pm)
                    d = jnp.where(m, d, pd)
                    m = m | pm
                return d, m
            return CompiledExpr(f_coalesce, e.type, dic)
        if form == "between":
            lo = Call("greater_than_or_equal", (e.args[0], e.args[1]), BOOLEAN)
            hi = Call("less_than_or_equal", (e.args[0], e.args[2]), BOOLEAN)
            return self._special(SpecialForm("and", (lo, hi), BOOLEAN))
        if form == "in":
            return self._in(e)
        if form == "cast":
            return self._cast(e)
        raise ExpressionCompileError(f"unsupported special form {form!r}")

    def _in(self, e: SpecialForm) -> CompiledExpr:
        value = self.compile(e.args[0])
        items = e.args[1:]
        if value.type.is_string:
            if not all(isinstance(i, Literal) for i in items):
                raise ExpressionCompileError(
                    "IN over varchar requires literal list")
            dic = value.dictionary or ()
            wanted = {i.value for i in items}
            table = np.array([v in wanted for v in dic] or [False], bool)
            tbl = jnp.asarray(table)
            fn = value.fn
            return CompiledExpr(lambda env: _apply_lookup(fn, tbl, env),
                                BOOLEAN)
        parts = [self.compile(i) for i in items]

        def f_in(env):
            vd, vm = value.fn(env)
            hit = jnp.zeros_like(vd, dtype=bool)
            any_null = jnp.zeros_like(vd, dtype=bool)
            for p in parts:
                pd, pm = p.fn(env)
                hit = hit | ((vd == pd) & pm)
                any_null = any_null | ~pm
            # x IN (...) is NULL if no hit and some item was NULL
            return hit, vm & (hit | ~any_null)
        return CompiledExpr(f_in, BOOLEAN)

    def _cast(self, e: SpecialForm) -> CompiledExpr:
        src = self.compile(e.args[0])
        to = e.type
        frm = src.type
        if frm == to:
            return src
        if to.is_string and frm.is_string:
            return CompiledExpr(src.fn, to, src.dictionary)
        if frm.is_string:
            # cast(varchar as T): parse the dictionary host-side.
            dic = src.dictionary or ()
            if to == DATE:
                vals = np.array([D.parse_date_literal(v) for v in dic]
                                or [0], np.int32)
            elif to.is_decimal:
                from presto_tpu.batch import _to_unscaled
                vals = np.array([_to_unscaled(float(v), to.scale)
                                 for v in dic] or [0], np.int64)
            elif to.is_numeric:
                vals = np.array([float(v) for v in dic] or [0],
                                to.np_dtype)
            else:
                raise ExpressionCompileError(f"cast varchar -> {to}")
            tbl = jnp.asarray(vals)
            fn = src.fn
            return CompiledExpr(
                lambda env: _apply_lookup(fn, tbl, env), to)
        if to.is_string:
            raise ExpressionCompileError(
                f"cast {frm} -> varchar not yet supported")

        def f_cast(env):
            d, m = src.fn(env)
            return _cast_data(d, frm, to), m
        return CompiledExpr(f_cast, to)

    # -- calls -------------------------------------------------------------

    def _call(self, e: Call) -> CompiledExpr:
        name = e.name
        args = [self.compile(a) for a in e.args]

        if name in _COMPARISONS:
            return self._comparison(name, e, args)
        if name == "like":
            return self._like(e, args)
        if name in _STRING_TO_STRING or name in _STRING_TO_INT \
                or name in _STRING_TO_BOOL \
                or name in _STRING_TO_STRING_NULL \
                or name in _STRING_TO_INT_NULL:
            return self._string_fn(name, e, args)
        if name == "concat":
            return self._concat(e, args)
        if name == "date_trunc":
            return self._date_trunc(e, args)
        if name in ("add", "subtract", "multiply", "divide", "modulus"):
            return self._arith(name, e, args)
        if name == "negate":
            a = args[0]

            def f_neg(env):
                d, m = a.fn(env)
                return -d, m
            return CompiledExpr(f_neg, e.type)
        if name in _MATH_FNS:
            impl = _MATH_FNS[name]
            typed = _numeric_prep(args)

            def f_math(env, impl=impl, typed=typed):
                vals = [t(env) for t in typed]
                m = vals[0][1]
                for _, pm in vals[1:]:
                    m = m & pm
                return impl(*[v for v, _ in vals]), m
            return CompiledExpr(f_math, e.type)
        if name in _DATE_EXTRACT:
            impl = _DATE_EXTRACT[name]
            a = args[0]

            def f_date(env, impl=impl, a=a):
                d, m = a.fn(env)
                return impl(d).astype(jnp.int64), m
            return CompiledExpr(f_date, BIGINT)
        if name == "nullif":
            a, b = args

            def f_nullif(env):
                ad, am = a.fn(env)
                bd, bm = b.fn(env)
                eq = (ad == bd) & am & bm
                return ad, am & ~eq
            return CompiledExpr(f_nullif, e.type, a.dictionary)
        if name in ("greatest", "least"):
            cmpf = jnp.maximum if name == "greatest" else jnp.minimum

            def f_gl(env):
                vals = [a.fn(env) for a in args]
                d = vals[0][0]
                m = vals[0][1]
                for vd, vm in vals[1:]:
                    d = cmpf(d, vd)
                    m = m & vm
                return d, m
            return CompiledExpr(f_gl, e.type)
        if name == "hash_code":
            parts = args

            def f_hash(env):
                h = None
                for p in parts:
                    d, m = p.fn(env)
                    h_i = _hash64(d, m)
                    h = h_i if h is None else _combine_hash(h, h_i)
                return h, jnp.asarray(True)
            return CompiledExpr(f_hash, BIGINT)
        if name in ("second", "minute", "hour", "millisecond"):
            a = args[0]
            div, mod = {"millisecond": (1, 1000),
                        "second": (1000, 60),
                        "minute": (60_000, 60),
                        "hour": (3_600_000, 24)}[name]

            def f_time(env, div=div, mod=mod):
                d, m = a.fn(env)
                return (d.astype(jnp.int64) // div) % mod, m
            return CompiledExpr(f_time, BIGINT)
        if name in ("date_add", "date_diff"):
            return self._date_arith(name, e, args)
        if name == "last_day_of_month":
            a = args[0]

            def f_ldom(env):
                d, m = a.fn(env)
                return D.last_day_of_month(d), m
            from presto_tpu.types import DATE as _DATE
            return CompiledExpr(f_ldom, _DATE)
        if name == "from_unixtime":
            a = args[0]

            def f_fut(env):
                d, m = a.fn(env)
                return jnp.round(d.astype(jnp.float64) * 1000.0) \
                    .astype(jnp.int64), m
            from presto_tpu.types import TIMESTAMP as _TS
            return CompiledExpr(f_fut, _TS)
        if name == "to_unixtime":
            a = args[0]

            def f_tut(env):
                d, m = a.fn(env)
                return d.astype(jnp.float64) / 1000.0, m
            return CompiledExpr(f_tut, DOUBLE)
        if name in ("is_nan", "is_finite", "is_infinite"):
            (a,) = args
            test = {"is_nan": jnp.isnan, "is_finite": jnp.isfinite,
                    "is_infinite": jnp.isinf}[name]

            def f_ieee(env):
                d, m = a.fn(env)
                return test(d.astype(jnp.float64)), m
            from presto_tpu.types import BOOLEAN as _B
            return CompiledExpr(f_ieee, _B)
        raise ExpressionCompileError(f"unknown scalar function {name!r}")

    def _date_arith(self, name: str, e: Call, args) -> CompiledExpr:
        """date_add(unit, n, x) / date_diff(unit, a, b) over DATE
        (days) or TIMESTAMP (ms) physical values (reference:
        DateTimeFunctions.java dateAdd/dateDiff; month-family units
        clamp the day of month)."""
        unit_lit = e.args[0]
        if not isinstance(unit_lit, Literal):
            raise ExpressionCompileError(f"{name} unit must be a "
                                         "literal")
        unit = str(unit_lit.value).lower()
        is_ts = e.args[1 if name == "date_diff" else 2].type.name \
            == "timestamp"
        a1, a2 = args[1], args[2]

        DAY_MS = 86_400_000
        if name == "date_add":
            if unit in _MONTH_UNITS:
                k = _MONTH_UNITS[unit]

                def f(env):
                    nd, nm = a1.fn(env)
                    xd, xm = a2.fn(env)
                    if is_ts:
                        days = jnp.floor_divide(xd, DAY_MS)
                        tod = xd - days * DAY_MS
                        out = D.add_months(days, nd * k) * DAY_MS + tod
                    else:
                        out = D.add_months(xd, nd * k)
                    return out, nm & xm
            else:
                units = _MS_UNITS if is_ts else _DAY_UNITS
                if unit not in units:
                    raise ExpressionCompileError(
                        f"date_add unit {unit!r} unsupported for "
                        f"{'timestamp' if is_ts else 'date'}")
                mult = units[unit]

                def f(env):
                    nd, nm = a1.fn(env)
                    xd, xm = a2.fn(env)
                    return xd + nd * mult, nm & xm
            return CompiledExpr(f, e.type)

        # date_diff(unit, a, b) = b - a in unit, truncated toward zero
        if unit in _MONTH_UNITS:
            k = _MONTH_UNITS[unit]

            def f(env):
                ad, am = a1.fn(env)
                bd, bm = a2.fn(env)
                if is_ts:
                    a_days = jnp.floor_divide(ad, DAY_MS)
                    b_days = jnp.floor_divide(bd, DAY_MS)
                    months = D.months_between(
                        a_days, b_days,
                        a_tie=ad - a_days * DAY_MS,
                        b_tie=bd - b_days * DAY_MS)
                else:
                    months = D.months_between(ad, bd)
                return jnp.trunc(months / k).astype(jnp.int64), am & bm
        else:
            units = _MS_UNITS if is_ts else _DAY_UNITS
            if unit not in units:
                raise ExpressionCompileError(
                    f"date_diff unit {unit!r} unsupported for "
                    f"{'timestamp' if is_ts else 'date'}")
            mult = units[unit]

            def f(env):
                ad, am = a1.fn(env)
                bd, bm = a2.fn(env)
                return jnp.trunc((bd - ad) / mult).astype(jnp.int64), \
                    am & bm
        return CompiledExpr(f, BIGINT)

    def _comparison(self, name: str, e: Call, args) -> CompiledExpr:
        a, b = args
        if a.type.is_string or b.type.is_string:
            return self._string_comparison(name, a, b)
        op = _COMPARISONS[name]
        fa, fb = _coerce_pair(a, b)

        def f_cmp(env):
            ad, am = fa(env)
            bd, bm = fb(env)
            return op(ad, bd), am & bm
        return CompiledExpr(f_cmp, BOOLEAN)

    def _string_comparison(self, name: str, a: CompiledExpr,
                           b: CompiledExpr) -> CompiledExpr:
        # literal vs column: compare codes against the literal's rank in
        # the (sorted) dictionary — no device strings ever.
        op = _COMPARISONS[name]
        a_lit = a.dictionary is not None and len(a.dictionary) == 1
        b_lit = b.dictionary is not None and len(b.dictionary) == 1
        if a_lit and b_lit:
            # constant fold: both sides are single-value dictionaries
            va, vb = a.dictionary[0], b.dictionary[0]
            result = {"equal": va == vb, "not_equal": va != vb,
                      "less_than": va < vb, "less_than_or_equal": va <= vb,
                      "greater_than": va > vb,
                      "greater_than_or_equal": va >= vb}[name]
            fa, fb = a.fn, b.fn

            def f_const(env):
                _, am = fa(env)
                _, bm = fb(env)
                return jnp.asarray(result), am & bm
            return CompiledExpr(f_const, BOOLEAN)
        if b.dictionary is not None and len(b.dictionary) == 1 \
                and a.dictionary is not None and len(a.dictionary) != 1:
            lit_val = b.dictionary[0]
            dic = a.dictionary
            import bisect
            pos = bisect.bisect_left(dic, lit_val)
            present = pos < len(dic) and dic[pos] == lit_val
            fn = a.fn
            if name in ("equal", "not_equal"):
                if not present:
                    const = name == "not_equal"
                    return CompiledExpr(
                        lambda env: (jnp.full_like(fn(env)[0], const,
                                                   dtype=bool), fn(env)[1]),
                        BOOLEAN)
                code = pos

                def f_eq(env):
                    d, m = fn(env)
                    r = d == code
                    return (r if name == "equal" else ~r), m
                return CompiledExpr(f_eq, BOOLEAN)
            # range comparisons: codes order == collation order
            boundary = pos if present else pos  # insertion point

            def f_range(env):
                d, m = fn(env)
                if present:
                    return op(d, boundary), m
                # literal not in dict: d < boundary <=> value < literal
                if name in ("less_than", "less_than_or_equal"):
                    return d < boundary, m
                return d >= boundary, m
            return CompiledExpr(f_range, BOOLEAN)
        if a.dictionary is not None and len(a.dictionary) == 1:
            from presto_tpu.expr.ir import FLIP_COMPARISON
            return self._string_comparison(FLIP_COMPARISON[name], b, a)
        if a.dictionary is not None and a.dictionary == b.dictionary:
            fa, fb = a.fn, b.fn

            def f_cc(env):
                ad, am = fa(env)
                bd, bm = fb(env)
                return op(ad, bd), am & bm
            return CompiledExpr(f_cc, BOOLEAN)
        raise ExpressionCompileError(
            "varchar comparison requires a shared dictionary "
            "(planner must unify dictionaries first)")

    def _like(self, e: Call, args) -> CompiledExpr:
        col = args[0]
        pat = e.args[1]
        esc = None
        if len(e.args) > 2:
            if not isinstance(e.args[2], Literal):
                raise ExpressionCompileError("LIKE escape must be literal")
            esc = e.args[2].value
        if not isinstance(pat, Literal):
            raise ExpressionCompileError("LIKE pattern must be literal")
        rx = re.compile(_like_to_regex(pat.value, esc))
        dic = col.dictionary or ()
        table = np.array([rx.match(v) is not None for v in dic] or [False],
                         bool)
        tbl = jnp.asarray(table)
        fn = col.fn
        return CompiledExpr(lambda env: _apply_lookup(fn, tbl, env), BOOLEAN)

    def _string_fn(self, name: str, e: Call, args) -> CompiledExpr:
        col = args[0]
        dic = col.dictionary or ()
        lit_args = []
        for a in e.args[1:]:
            if not isinstance(a, Literal):
                raise ExpressionCompileError(
                    f"{name}: non-leading arguments must be literals")
            lit_args.append(a.value)
        if name in _STRING_TO_INT:
            impl = _STRING_TO_INT[name]
            vals = np.array([impl(v, *lit_args) for v in dic] or [0],
                            np.int64)
            tbl = jnp.asarray(vals)
            fn = col.fn
            return CompiledExpr(
                lambda env: _apply_lookup(fn, tbl, env), BIGINT)
        if name in _STRING_TO_BOOL:
            impl = _STRING_TO_BOOL[name]
            vals = np.array([impl(v, *lit_args) for v in dic] or [False],
                            bool)
            tbl = jnp.asarray(vals)
            fn = col.fn
            return CompiledExpr(
                lambda env: _apply_lookup(fn, tbl, env), BOOLEAN)
        if name in _STRING_TO_INT_NULL:
            impl = _STRING_TO_INT_NULL[name]
            mapped = [impl(v, *lit_args) for v in dic]
            vals = np.array([0 if v is None else v for v in mapped]
                            or [0], np.int64)
            nulls = np.array([v is None for v in mapped] or [True],
                             bool)
            tbl = jnp.asarray(vals)
            ntbl = jnp.asarray(nulls)
            fn = col.fn

            def f_int_nullable(env):
                d, m = fn(env)
                idx = jnp.clip(d.astype(jnp.int32), 0,
                               tbl.shape[0] - 1)
                return tbl[idx], m & ~ntbl[idx]
            return CompiledExpr(f_int_nullable, BIGINT)
        if name in _STRING_TO_STRING_NULL:
            # functions that can yield SQL NULL per dictionary value
            # (regexp no-match, bad JSON path, out-of-range part): a
            # null table rides next to the code remap and narrows the
            # result mask
            impl = _STRING_TO_STRING_NULL[name]
            mapped = [impl(v, *lit_args) for v in dic]
            new_dic = tuple(sorted({m for m in mapped
                                    if m is not None}))
            index = {v: i for i, v in enumerate(new_dic)}
            remap = np.array([0 if v is None else index[v]
                              for v in mapped] or [0], np.int32)
            nulls = np.array([v is None for v in mapped] or [True],
                             bool)
            tbl = jnp.asarray(remap)
            ntbl = jnp.asarray(nulls)
            fn = col.fn

            def f_nullable(env):
                d, m = fn(env)
                idx = jnp.clip(d.astype(jnp.int32), 0,
                               tbl.shape[0] - 1)
                return tbl[idx], m & ~ntbl[idx]
            return CompiledExpr(f_nullable, VARCHAR, new_dic)
        impl = _STRING_TO_STRING[name]
        mapped = [impl(v, *lit_args) for v in dic]
        new_dic = tuple(sorted(set(mapped)))
        index = {v: i for i, v in enumerate(new_dic)}
        remap = np.array([index[v] for v in mapped] or [0], np.int32)
        tbl = jnp.asarray(remap)
        fn = col.fn
        return CompiledExpr(lambda env: _apply_lookup(fn, tbl, env),
                            VARCHAR, new_dic)

    #: safety cap on the product dictionary a multi-column concat builds
    _CONCAT_DICT_MAX = 1 << 16

    def _concat(self, e: Call, args) -> CompiledExpr:
        """N-ary string concatenation over dictionary-coded inputs: the
        result dictionary is the (sorted, deduped) cross product of the
        input dictionaries, and the kernel is one table lookup on the
        mixed-radix combination of input codes. Literal arguments are
        single-entry dictionaries, so concat(col, '-', col2) costs
        |dic1| * |dic2| table entries."""
        import itertools
        dics = []
        for a in args:
            if a.dictionary is None:
                raise ExpressionCompileError(
                    "concat argument has no dictionary (only varchar "
                    "inputs are supported)")
            dics.append(a.dictionary or ("",))
        total = 1
        for d in dics:
            total *= max(len(d), 1)
        if total > self._CONCAT_DICT_MAX:
            raise ExpressionCompileError(
                f"concat product dictionary too large ({total} > "
                f"{self._CONCAT_DICT_MAX}); reduce input cardinality")
        combos = ["".join(parts) for parts in itertools.product(*dics)]
        new_dic = tuple(sorted(set(combos)))
        index = {v: i for i, v in enumerate(new_dic)}
        remap = np.array([index[v] for v in combos] or [0], np.int32)
        tbl = jnp.asarray(remap)
        fns = [a.fn for a in args]
        strides = []
        s = 1
        for d in reversed(dics):
            strides.append(s)
            s *= max(len(d), 1)
        strides = list(reversed(strides))

        def f_concat(env):
            code = None
            mask = None
            for fn, stride in zip(fns, strides):
                d, m = fn(env)
                c = d.astype(jnp.int32) * stride
                code = c if code is None else code + c
                mask = m if mask is None else mask & m
            idx = jnp.clip(code, 0, tbl.shape[0] - 1)
            return tbl[idx], mask
        return CompiledExpr(f_concat, VARCHAR, new_dic)

    def _date_trunc(self, e: Call, args) -> CompiledExpr:
        if len(e.args) != 2:
            raise ExpressionCompileError(
                "date_trunc takes (unit, date)")
        unit_e = e.args[0]
        if not isinstance(unit_e, Literal):
            raise ExpressionCompileError("date_trunc unit must be a "
                                         "literal")
        unit = str(unit_e.value).lower()
        if unit not in ("day", "week", "month", "quarter", "year"):
            raise ExpressionCompileError(
                f"date_trunc: unsupported unit {unit!r}")
        col = args[1]
        fn = col.fn

        def f_trunc(env):
            d, m = fn(env)
            days = d.astype(jnp.int64)
            if unit == "day":
                out = days
            elif unit == "week":  # ISO week starts Monday
                out = days - (D.extract_dow(days) - 1)
            else:
                y, mo, _ = D.civil_from_days(days)
                if unit == "month":
                    out = D.days_from_civil(y, mo, 1)
                elif unit == "quarter":
                    out = D.days_from_civil(y, ((mo - 1) // 3) * 3 + 1, 1)
                elif unit == "year":
                    out = D.days_from_civil(y, 1, 1)
                else:
                    raise ExpressionCompileError(
                        f"date_trunc: unsupported unit {unit!r}")
            return out.astype(np.int32), m
        return CompiledExpr(f_trunc, DATE)

    def _arith(self, name: str, e: Call, args) -> CompiledExpr:
        a, b = args
        out = e.type
        if out.is_decimal or a.type.is_decimal or b.type.is_decimal:
            return self._decimal_arith(name, e, a, b)
        fa, fb = _coerce_pair(a, b)
        if name == "divide" and out.is_integer:
            def f_idiv(env):
                ad, am = fa(env)
                bd, bm = fb(env)
                safe = jnp.where(bd == 0, 1, bd)
                q = jnp.sign(ad) * jnp.sign(bd) * (abs(ad) // abs(safe))
                return q.astype(out.np_dtype), am & bm & (bd != 0)
            return CompiledExpr(f_idiv, out)
        if name == "modulus" and out.is_integer:
            def f_imod(env):
                ad, am = fa(env)
                bd, bm = fb(env)
                safe = jnp.where(bd == 0, 1, bd)
                r = jnp.sign(ad) * (abs(ad) % abs(safe))
                return r.astype(out.np_dtype), am & bm & (bd != 0)
            return CompiledExpr(f_imod, out)
        op = {"add": jnp.add, "subtract": jnp.subtract,
              "multiply": jnp.multiply, "divide": jnp.divide,
              "modulus": jnp.mod}[name]
        # date +/- interval day stays a date
        if a.type == DATE and b.type == INTERVAL_DAY:
            fa2, fb2 = a.fn, b.fn
            sign = 1 if name == "add" else -1

            def f_dint(env):
                ad, am = fa2(env)
                bd, bm = fb2(env)
                return (ad.astype(jnp.int64)
                        + sign * (bd // 86_400_000)).astype(np.int32), am & bm
            return CompiledExpr(f_dint, DATE)
        if a.type == DATE and b.type == INTERVAL_YEAR:
            fa2, fb2 = a.fn, b.fn
            sign = 1 if name == "add" else -1

            def f_dy(env):
                ad, am = fa2(env)
                bd, bm = fb2(env)
                y, m_, d_ = D.civil_from_days(ad)
                months = y * 12 + (m_ - 1) + sign * bd
                ny = jnp.floor_divide(months, 12)
                nm = months - ny * 12 + 1
                # clamp day to the target month's last day (Presto rule)
                next_m = jnp.where(nm == 12, 1, nm + 1)
                next_y = jnp.where(nm == 12, ny + 1, ny)
                days_in_month = (D.days_from_civil(next_y, next_m, 1)
                                 - D.days_from_civil(ny, nm, 1))
                return D.days_from_civil(
                    ny, nm, jnp.minimum(d_, days_in_month)) \
                    .astype(np.int32), am & bm
            return CompiledExpr(f_dy, DATE)

        def f_arith(env):
            ad, am = fa(env)
            bd, bm = fb(env)
            m = am & bm
            if name in ("divide", "modulus"):
                bd_safe = jnp.where(bd == 0, 1, bd) \
                    if out.is_integer else bd
                r = op(ad, bd_safe)
                return r.astype(out.np_dtype), m
            return op(ad, bd).astype(out.np_dtype), m
        return CompiledExpr(f_arith, out)

    def _decimal_arith(self, name, e, a, b) -> CompiledExpr:
        out = e.type
        if not out.is_decimal:
            # decimal op double -> double
            fa, fb = _coerce_pair(a, b)
            op = {"add": jnp.add, "subtract": jnp.subtract,
                  "multiply": jnp.multiply, "divide": jnp.divide,
                  "modulus": jnp.mod}[name]

            def f_dd(env):
                ad, am = fa(env)
                bd, bm = fb(env)
                return op(ad, bd).astype(out.np_dtype), am & bm
            return CompiledExpr(f_dd, out)
        sa = a.type.scale if a.type.is_decimal else 0
        sb = b.type.scale if b.type.is_decimal else 0
        so = out.scale
        fa, fb = a.fn, b.fn

        def to_unscaled(d, typ, target_scale):
            if typ.is_decimal:
                shift = target_scale - typ.scale
            else:
                shift = target_scale
            d = d.astype(jnp.int64)
            if shift > 0:
                return d * (10 ** shift)
            return d

        if name in ("add", "subtract"):
            s = max(sa, sb)
            op = jnp.add if name == "add" else jnp.subtract

            def f_as(env):
                ad, am = fa(env)
                bd, bm = fb(env)
                r = op(to_unscaled(ad, a.type, s), to_unscaled(bd, b.type, s))
                return _rescale(r, s, so), am & bm
            return CompiledExpr(f_as, out)
        if name == "multiply":
            s = sa + sb

            def f_mul(env):
                ad, am = fa(env)
                bd, bm = fb(env)
                r = ad.astype(jnp.int64) * bd.astype(jnp.int64)
                return _rescale(r, s, so), am & bm
            return CompiledExpr(f_mul, out)
        if name == "divide":
            # result = a / b at scale so, HALF_UP
            shift = so + sb - sa

            def f_div(env):
                ad, am = fa(env)
                bd, bm = fb(env)
                num = ad.astype(jnp.int64) * (10 ** max(shift, 0))
                den = bd.astype(jnp.int64) * (10 ** max(-shift, 0))
                ok = den != 0
                den_s = jnp.where(ok, den, 1)
                q = _div_half_up(num, den_s)
                return q, am & bm & ok
            return CompiledExpr(f_div, out)
        if name == "modulus":
            s = max(sa, sb)

            def f_mod(env):
                ad, am = fa(env)
                bd, bm = fb(env)
                an = to_unscaled(ad, a.type, s)
                bn = to_unscaled(bd, b.type, s)
                ok = bn != 0
                bs = jnp.where(ok, bn, 1)
                r = jnp.sign(an) * (abs(an) % abs(bs))
                return _rescale(r, s, so), am & bm & ok
            return CompiledExpr(f_mod, out)
        raise ExpressionCompileError(f"decimal op {name}")


# -- helpers ----------------------------------------------------------------

def _common_broadcast(a, b):
    """Broadcast two arrays (either may be scalar) to a common shape."""
    shape = jnp.broadcast_shapes(jnp.shape(a), jnp.shape(b))
    return jnp.broadcast_to(a, shape), jnp.broadcast_to(b, shape)


def _apply_lookup(fn, tbl, env) -> CVal:
    d, m = fn(env)
    idx = jnp.clip(d, 0, tbl.shape[0] - 1)
    return tbl[idx], m


def _rescale(unscaled, from_scale: int, to_scale: int):
    if to_scale == from_scale:
        return unscaled
    if to_scale > from_scale:
        return unscaled * (10 ** (to_scale - from_scale))
    return _div_half_up(unscaled, 10 ** (from_scale - to_scale))


def _div_half_up(num, den):
    """Integer division rounding half away from zero (SQL DECIMAL)."""
    num = num.astype(jnp.int64)
    den = jnp.asarray(den, jnp.int64)
    sign = jnp.sign(num) * jnp.sign(den)
    q = (2 * abs(num) + abs(den)) // (2 * abs(den))
    return sign * q


def _cast_data(d, frm: Type, to: Type):
    if frm.is_decimal and to.is_decimal:
        return _rescale(d, frm.scale, to.scale)
    if frm.is_decimal and (to.is_floating):
        return (d.astype(to.np_dtype)) / (10 ** frm.scale)
    if frm.is_decimal and to.is_integer:
        return _div_half_up(d, 10 ** frm.scale).astype(to.np_dtype)
    if to.is_decimal:
        if frm.is_integer or frm.name == "boolean":
            return d.astype(jnp.int64) * (10 ** to.scale)
        # float -> decimal: round half up
        scaled = d.astype(jnp.float64) * (10 ** to.scale)
        return jnp.round(scaled).astype(jnp.int64)
    if to.is_integer and frm.is_floating:
        return jnp.round(d).astype(to.np_dtype)
    return d.astype(to.np_dtype)


def _coerce_pair(a: CompiledExpr, b: CompiledExpr):
    """Coerce both sides to a common numeric representation lazily."""
    ta, tb = a.type, b.type

    def conv(x: CompiledExpr, tx: Type, other: Type):
        if tx.is_decimal and other.is_floating:
            scale = tx.scale

            def f(env):
                d, m = x.fn(env)
                return d.astype(jnp.float64) / (10 ** scale), m
            return f
        return x.fn
    return conv(a, ta, tb), conv(b, tb, ta)


def _numeric_prep(args):
    out = []
    for a in args:
        if a.type.is_decimal:
            scale = a.type.scale

            def f(env, a=a, scale=scale):
                d, m = a.fn(env)
                return d.astype(jnp.float64) / (10 ** scale), m
            out.append(f)
        else:
            out.append(a.fn)
    return out


def _merge_result_dicts(typ: Type, *parts) -> Optional[Tuple[str, ...]]:
    if not typ.is_string:
        return None
    merged = sorted(set().union(*[set(p.dictionary or ()) for p in parts]))
    return tuple(merged)


def _remap_to(p: CompiledExpr, dic: Tuple[str, ...]) -> CompiledExpr:
    if p.dictionary == dic:
        return p
    index = {v: i for i, v in enumerate(dic)}
    remap = np.array([index[v] for v in (p.dictionary or ())] or [0],
                     np.int32)
    tbl = jnp.asarray(remap)
    fn = p.fn
    return CompiledExpr(lambda env: _apply_lookup(fn, tbl, env),
                        p.type, dic)


# 64-bit splitmix-style hash for shuffle partitioning / group-by.
def _hash64(d, m):
    if d.dtype == jnp.float64 or d.dtype == jnp.float32:
        x = float64_bits(d)
    else:
        x = d.astype(jnp.int64)
    x = jnp.where(m, x, jnp.int64(-0x61c8864680b583eb))
    x = (x ^ (x >> 30)) * jnp.int64(-0x40a7b892e31b1a47)
    x = (x ^ (x >> 27)) * jnp.int64(-0x6b2fb644ecceee15)
    return x ^ (x >> 31)


def _combine_hash(a, b):
    return a * jnp.int64(31) + b


_COMPARISONS = {
    "equal": lambda a, b: a == b,
    "not_equal": lambda a, b: a != b,
    "less_than": lambda a, b: a < b,
    "less_than_or_equal": lambda a, b: a <= b,
    "greater_than": lambda a, b: a > b,
    "greater_than_or_equal": lambda a, b: a >= b,
}

_MATH_FNS = {
    "abs": jnp.abs,
    "ceiling": jnp.ceil,
    "floor": jnp.floor,
    "sqrt": jnp.sqrt,
    "cbrt": jnp.cbrt,
    "exp": jnp.exp,
    "ln": jnp.log,
    "log2": jnp.log2,
    "log10": jnp.log10,
    "power": jnp.power,
    "sign": jnp.sign,
    "round": lambda x, d=None: jnp.round(x) if d is None
    else jnp.round(x * 10.0 ** d) / 10.0 ** d,
    "sin": jnp.sin, "cos": jnp.cos, "tan": jnp.tan,
    "asin": jnp.arcsin, "acos": jnp.arccos, "atan": jnp.arctan,
    "atan2": jnp.arctan2,
    "mod": jnp.mod,
    "sinh": jnp.sinh, "cosh": jnp.cosh, "tanh": jnp.tanh,
    "degrees": jnp.degrees, "radians": jnp.radians,
    "log": lambda b, x: jnp.log(x) / jnp.log(b),
    "truncate": lambda x, d=None: jnp.trunc(x) if d is None
    else jnp.trunc(x * 10.0 ** d) / 10.0 ** d,
    # ascending OR descending bounds (reference: MathFunctions
    # widthBucket supports bound1 > bound2)
    "width_bucket": lambda x, lo, hi, n: jnp.where(
        hi >= lo,
        jnp.clip(jnp.floor((x - lo)
                           / jnp.where(hi != lo, hi - lo, 1.0) * n)
                 + 1, 0, n + 1),
        jnp.clip(jnp.floor((lo - x)
                           / jnp.where(hi != lo, lo - hi, 1.0) * n)
                 + 1, 0, n + 1)).astype(jnp.int64),
    "bitwise_and": jnp.bitwise_and,
    "bitwise_or": jnp.bitwise_or,
    "bitwise_xor": jnp.bitwise_xor,
    "bitwise_not": jnp.bitwise_not,
    "bitwise_left_shift": jnp.left_shift,
    "bitwise_right_shift": jnp.right_shift,
    "cot": lambda x: 1.0 / jnp.tan(x),
    "log1p": jnp.log1p, "expm1": jnp.expm1,
    # popcount of the low `bits` bits of x's two's complement
    # (reference: MathFunctions.bitCount)
    "bit_count": lambda x, bits: jax.lax.population_count(
        x.astype(jnp.uint64)
        & jnp.where(bits >= 64, jnp.uint64(0xFFFFFFFFFFFFFFFF),
                    (jnp.uint64(1) << bits.astype(jnp.uint64))
                    - jnp.uint64(1))).astype(jnp.int64),
}

_DATE_EXTRACT = {
    "year": D.extract_year,
    "month": D.extract_month,
    "day": D.extract_day,
    "quarter": D.extract_quarter,
    "day_of_week": D.extract_dow,
    "day_of_year": D.extract_doy,
    "week": D.extract_week,
    "week_of_year": D.extract_week,
    "day_of_month": D.extract_day,
    "year_of_week": D.extract_year_of_week,
}

#: date_add/date_diff unit multipliers on the DATE (days) axis
_DAY_UNITS = {"day": 1, "week": 7}
#: ... and on the TIMESTAMP (milliseconds) axis
_MS_UNITS = {"millisecond": 1, "second": 1000, "minute": 60_000,
             "hour": 3_600_000, "day": 86_400_000,
             "week": 7 * 86_400_000}
_MONTH_UNITS = {"month": 1, "quarter": 3, "year": 12}

def _pad(v: str, n, pad: str, left: bool) -> str:
    """Presto lpad/rpad: truncate to n when longer; multi-character pad
    strings repeat (str.rjust only accepts one char)."""
    n = int(n)
    if len(v) >= n:
        return v[:n]
    if not pad:
        raise ExpressionCompileError("pad string must not be empty")
    fill = (pad * n)[:n - len(v)]
    return fill + v if left else v + fill


def _substr(v: str, start, length=None) -> str:
    """Presto substr: 1-based; negative start counts from the end
    (substr('hello', -2) = 'lo'); start 0 yields ''."""
    start = int(start)
    if start == 0:
        return ""
    idx = start - 1 if start > 0 else len(v) + start
    if idx < 0:
        return ""
    if length is None:
        return v[idx:]
    return v[idx:idx + int(length)]


def _presto_replacement(repl: str) -> str:
    """Presto regexp_replace replacement -> Python re.sub template:
    $N group refs become \\N, \\$ is a literal dollar, bare $ stays a
    dollar, and literal backslashes are escaped."""
    out = []
    i = 0
    n = len(repl)
    while i < n:
        c = repl[i]
        if c == "\\" and i + 1 < n and repl[i + 1] in "$\\":
            out.append("\\\\" if repl[i + 1] == "\\" else "$")
            i += 2
        elif c == "$" and i + 1 < n and repl[i + 1].isdigit():
            j = i + 1
            while j < n and repl[j].isdigit():
                j += 1
            out.append("\\" + repl[i + 1:j])
            i = j
        elif c == "\\":
            out.append("\\\\")
            i += 1
        else:
            out.append(c)
            i += 1
    return "".join(out)


def _json_path_get(doc: str, path: str):
    """Minimal JSONPath for json_extract_scalar: $, $.k, $.a.b, $[i],
    $.a[i].b ... (reference: JsonFunctions' scalar subset)."""
    import json as _json
    try:
        cur = _json.loads(doc)
    except Exception:  # noqa: BLE001 — malformed JSON -> NULL
        return None
    if not path.startswith("$"):
        return None
    i = 1
    n = len(path)
    while i < n:
        if path[i] == ".":
            j = i + 1
            while j < n and path[j] not in ".[":
                j += 1
            key = path[i + 1:j]
            if not isinstance(cur, dict) or key not in cur:
                return None
            cur = cur[key]
            i = j
        elif path[i] == "[":
            j = path.index("]", i)
            try:
                idx = int(path[i + 1:j])
            except ValueError:
                return None
            if not isinstance(cur, list) or not (
                    -len(cur) <= idx < len(cur)):
                return None
            cur = cur[idx]
            i = j + 1
        else:
            return None
    return cur


def _json_extract_scalar(doc: str, path: str):
    v = _json_path_get(doc, path)
    if v is None or isinstance(v, (dict, list)):
        return None
    if isinstance(v, bool):
        return "true" if v else "false"
    return str(v)


def _regexp_extract(v: str, pattern: str, group: int = 0):
    import re as _re
    m = _re.search(pattern, v)
    if m is None:
        return None
    try:
        g = m.group(int(group))
    except IndexError:
        return None
    # a group that did not participate in the match is SQL NULL
    return g


def _split_part(v: str, delim: str, index: int):
    if not delim:
        return None
    parts = v.split(delim)
    i = int(index)
    if i < 1 or i > len(parts):
        return None
    return parts[i - 1]


def _url_part(v: str, part: str):
    from urllib.parse import urlparse
    try:
        u = urlparse(v)
    except Exception:  # noqa: BLE001
        return None
    got = {"host": u.hostname, "protocol": u.scheme, "path": u.path,
           "query": u.query, "fragment": u.fragment}[part]
    return got if got else ("" if part in ("path", "query", "fragment")
                            else None)


#: string -> string-or-NULL functions (a null table rides next to the
#: dictionary remap so no-match/out-of-range yields SQL NULL)
_STRING_TO_STRING_NULL = {
    "regexp_extract": _regexp_extract,
    "json_extract_scalar": _json_extract_scalar,
    "json_extract": lambda doc, path: (
        None if (r := _json_path_get(doc, path)) is None
        else __import__("json").dumps(r)),
    "split_part": _split_part,
    "url_extract_host": lambda v: _url_part(v, "host"),
    "url_extract_protocol": lambda v: _url_part(v, "protocol"),
    "url_extract_path": lambda v: _url_part(v, "path"),
    "url_extract_query": lambda v: _url_part(v, "query"),
    "url_extract_fragment": lambda v: _url_part(v, "fragment"),
}


_STRING_TO_STRING = {
    "substr": _substr,
    "upper": lambda v: v.upper(),
    "lower": lambda v: v.lower(),
    "trim": lambda v: v.strip(),
    "ltrim": lambda v: v.lstrip(),
    "rtrim": lambda v: v.rstrip(),
    "reverse": lambda v: v[::-1],
    "concat_lit": lambda v, suffix: v + suffix,
    "regexp_replace": lambda v, pat, repl="": __import__("re").sub(
        pat, _presto_replacement(repl), v),
    "translate": lambda v, frm, to: v.translate(
        {ord(f): (to[i] if i < len(to) else None)
         for i, f in enumerate(frm)}),
    "normalize": lambda v: __import__("unicodedata").normalize(
        "NFC", v),
    "split_join": lambda v, d, sep: sep.join(v.split(d)),
    "replace": lambda v, find, repl="": v.replace(find, repl),
    "lpad": lambda v, n, pad=" ": _pad(v, n, pad, left=True),
    "rpad": lambda v, n, pad=" ": _pad(v, n, pad, left=False),
}

def _levenshtein(a: str, b: str) -> int:
    if len(a) < len(b):
        a, b = b, a
    prev = list(range(len(b) + 1))
    for i, ca in enumerate(a, 1):
        cur = [i]
        for j, cb in enumerate(b, 1):
            cur.append(min(prev[j] + 1, cur[-1] + 1,
                           prev[j - 1] + (ca != cb)))
        prev = cur
    return prev[-1]


def _from_base(v: str, radix: int):
    try:
        return int(v, int(radix))
    except ValueError:
        return None  # deviation: Presto raises; we yield SQL NULL


def _json_array_length(doc: str):
    import json as _json
    try:
        arr = _json.loads(doc)
    except Exception:  # noqa: BLE001
        return None
    return len(arr) if isinstance(arr, list) else None


_STRING_TO_INT = {
    "length": lambda v: len(v),
    "strpos": lambda v, sub: v.find(sub) + 1,
    "codepoint": lambda v: ord(v[0]) if v else 0,
    "levenshtein_distance": lambda v, other: _levenshtein(v, other),
    "split_count": lambda v, d: len(v.split(d)),
    "bit_length": lambda v: len(v.encode()) * 8,
    "octet_length": lambda v: len(v.encode()),
    "crc32": lambda v: __import__("zlib").crc32(v.encode()),
}

#: string -> bigint-or-NULL (invalid input yields SQL NULL; where
#: Presto raises instead, the deviation is documented on the impl)
_STRING_TO_INT_NULL = {
    "json_array_length": _json_array_length,
    "from_base": _from_base,
    # deviation: Presto raises on unequal lengths; we yield NULL
    "hamming_distance": lambda v, other: sum(
        x != y for x, y in zip(v, other)) if len(v) == len(other)
        else None,
}

_STRING_TO_BOOL = {
    "starts_with": lambda v, prefix: v.startswith(prefix),
    "ends_with": lambda v, suffix: v.endswith(suffix),
    "contains_str": lambda v, sub: sub in v,
    "regexp_like": lambda v, pat: __import__("re").search(
        pat, v) is not None,
    "is_json_scalar": lambda v: (lambda r: not isinstance(
        r, (dict, list)))(_json_try(v)) if _json_try(v) is not _JSONERR
        else False,
}


_JSONERR = object()


def _json_try(v: str):
    import json as _json
    try:
        return _json.loads(v)
    except Exception:  # noqa: BLE001
        return _JSONERR


def fold_constants(expr: RowExpression,
                   _memo: Optional[dict] = None) -> RowExpression:
    """Evaluate literal-only subtrees host-side (reference analog:
    sql/planner ConstantExpressionVerifier + interpreter folding).
    E.g. `date '1998-12-01' - interval '90' day` becomes a DATE literal.

    Memoized by node identity: analyzer output is a DAG (a lambda
    reduce() references its accumulator twice per step), and a naive
    rebuild both blows up exponentially AND destroys the sharing the
    compiler's own memo depends on."""
    if isinstance(expr, (Literal, InputRef)):
        return expr
    if _memo is None:
        _memo = {}
    hit = _memo.get(id(expr))
    if hit is not None:
        return hit
    original = expr
    kids = tuple(fold_constants(c, _memo) for c in expr.children())
    if isinstance(expr, Call):
        expr = Call(expr.name, kids, expr.type)
    elif isinstance(expr, SpecialForm):
        expr = SpecialForm(expr.form, kids, expr.type)
    out = expr
    if all(isinstance(k, Literal) for k in kids) and kids \
            and not any(k.value is None for k in kids) \
            and not expr.type.is_string:
        try:
            compiled = compile_expression(expr, {})
            d, m = compiled.fn({})
            if not bool(np.asarray(m)):
                out = Literal(None, expr.type)
            else:
                out = Literal(np.asarray(d).item(), expr.type)
        except ExpressionCompileError:
            out = expr
    _memo[id(original)] = out
    return out
