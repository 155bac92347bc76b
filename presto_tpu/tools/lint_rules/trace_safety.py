"""Trace-safety rules (TS0xx): the JAX retrace/host-sync hazard
class. Scope: the kernel layer — `ops/`, `operators/`, `expr/`,
`batch.py`, `parallel/`, and the jitted parts of `execution/`.

Why these exist: silent per-shape retraces once held a compile wall
nobody had asked for, and the telemetry PR's
"uninstrumented module-level jit" gap (compile time booked as execute)
was found BY HAND. Every rule here makes one of those hazard shapes
machine-checked:

  TS001  Python branching on a traced value inside a jitted body —
         TracerBoolConversionError at best, silently baked-in branch
         at worst
  TS002  host syncs (.item()/.tolist(), float()/int()/bool() of a
         traced value) inside a jitted body — blocks dispatch, kills
         async overlap
  TS003  np.* calls inside a jitted body — silently fall out of the
         trace (constant-folded at trace time against tracer reprs,
         or force a sync)
  TS004  static_argnums/static_argnames pointing at parameters whose
         annotation/default is unhashable (list/dict/set) — every
         call raises or, worse, retraces
  TS005  a jitted callable never registered with a telemetry kernel
         family (instrument_kernel) — its compile time lands in
         operator busy time and the compile-wall attribution lies
         (the exact PR 5 gap class)
  TS007  a jax.jit that bypasses telemetry.kernels.jit — the device
         program is named after the Python closure (`jit_kernel`,
         `jit_fn`), so a device trace cannot say which kernel family
         the chip's time went to (PERF.md, PR 26)
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from presto_tpu.tools.lint_rules import (
    Finding, ModuleInfo, Project, dotted, jit_call_of,
    jit_decorator_of, rule, static_params_of, terminal_name,
)

#: attribute accesses on a traced value that are static metadata, not
#: data (shape/dtype plumbing never branches on row contents)
_STATIC_CALLS = {"len", "isinstance", "hasattr", "getattr", "type",
                 "issubclass"}


def _jit_bodies(mod: ModuleInfo) -> List[Tuple[ast.FunctionDef,
                                               Set[str], ast.AST]]:
    """Every function in this module that jax traces: decorated defs,
    plus defs wrapped at a binding site (`_x = jax.jit(f, ...)` /
    `functools.partial(jax.jit, ...) (f)`). Returns (fn, traced
    parameter names, the jit expression)."""
    defs: Dict[str, ast.FunctionDef] = {}
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.FunctionDef):
            defs[node.name] = node
    out: List[Tuple[ast.FunctionDef, Set[str], ast.AST]] = []
    seen: Set[int] = set()
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.FunctionDef):
            dec = jit_decorator_of(node)
            if dec is not None and id(node) not in seen:
                seen.add(id(node))
                static = static_params_of(dec, node)
                params = {a.arg for a in node.args.args}
                out.append((node, params - static, dec))
        call = jit_call_of(node) if isinstance(node, ast.Call) else None
        if call is not None and call.args:
            t = terminal_name(call.args[0])
            fn = defs.get(t) if t else None
            if fn is not None and id(fn) not in seen:
                seen.add(id(fn))
                static = static_params_of(call, fn)
                params = {a.arg for a in fn.args.args}
                out.append((fn, params - static, call))
    return out


def _traced_value_use(test: ast.AST, traced: Set[str]) -> bool:
    """Does `test` consume a traced parameter AS A VALUE? Bare names
    and subscripts of traced params count; attribute accesses
    (x.shape, x.dtype, x.capacity — static metadata) and args of
    len/isinstance/`is None` comparisons do not."""
    def value_use(node: ast.AST) -> bool:
        if isinstance(node, ast.Name):
            return node.id in traced
        if isinstance(node, ast.Subscript):
            return value_use(node.value)
        if isinstance(node, ast.Attribute):
            return False  # metadata access, not row data
        if isinstance(node, ast.Call):
            fn = terminal_name(node.func)
            if fn in _STATIC_CALLS:
                return False
            return any(value_use(a) for a in node.args)
        if isinstance(node, ast.Compare):
            if all(isinstance(op, (ast.Is, ast.IsNot))
                   for op in node.ops):
                return False  # `x is None` guards are host-static
            return any(value_use(x)
                       for x in [node.left] + node.comparators)
        if isinstance(node, ast.BoolOp):
            return any(value_use(v) for v in node.values)
        if isinstance(node, ast.UnaryOp):
            return value_use(node.operand)
        if isinstance(node, ast.BinOp):
            return value_use(node.left) or value_use(node.right)
        return False
    return value_use(test)


@rule("TS001", "Python branch on a traced value inside a jitted body")
def check_traced_branch(mod: ModuleInfo,
                        project: Project) -> List[Finding]:
    out: List[Finding] = []
    for fn, traced, _ in _jit_bodies(mod):
        for node in ast.walk(fn):
            tests: List[ast.AST] = []
            if isinstance(node, (ast.If, ast.While, ast.IfExp)):
                tests.append(node.test)
            elif isinstance(node, ast.Assert):
                tests.append(node.test)
            elif isinstance(node, ast.comprehension):
                tests.extend(node.ifs)
            for t in tests:
                if _traced_value_use(t, traced):
                    out.append(mod.finding(
                        "TS001", node,
                        f"jitted body {fn.name!r} branches on traced "
                        "value(s) "
                        f"{sorted(traced & _names_in(t))!r} — use "
                        "jnp.where / lax.cond, or declare the "
                        "argument static"))
    return out


def _names_in(node: ast.AST) -> Set[str]:
    return {n.id for n in ast.walk(node) if isinstance(n, ast.Name)}


@rule("TS002", "host sync (.item()/float()/bool()) inside a jitted "
               "body")
def check_host_sync(mod: ModuleInfo,
                    project: Project) -> List[Finding]:
    out: List[Finding] = []
    for fn, traced, _ in _jit_bodies(mod):
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            if isinstance(node.func, ast.Attribute) \
                    and node.func.attr in ("item", "tolist") \
                    and not node.args:
                out.append(mod.finding(
                    "TS002",
                    node,
                    f".{node.func.attr}() inside jitted body "
                    f"{fn.name!r} forces a device->host sync (and "
                    "fails under trace)"))
            elif isinstance(node.func, ast.Name) \
                    and node.func.id in ("float", "int", "bool") \
                    and len(node.args) == 1 \
                    and _is_traced_operand(node.args[0], traced):
                out.append(mod.finding(
                    "TS002", node,
                    f"{node.func.id}() of a traced value inside "
                    f"jitted body {fn.name!r} is a concretization "
                    "sync — keep it on-device (astype/jnp casts)"))
    return out


def _is_traced_operand(node: ast.AST, traced: Set[str]) -> bool:
    if isinstance(node, ast.Name):
        return node.id in traced
    if isinstance(node, ast.Subscript):
        return _is_traced_operand(node.value, traced)
    return False


@rule("TS003", "np.* call inside a jitted body")
def check_numpy_in_jit(mod: ModuleInfo,
                       project: Project) -> List[Finding]:
    out: List[Finding] = []
    for fn, _, _ in _jit_bodies(mod):
        for node in ast.walk(fn):
            if isinstance(node, ast.Call):
                d = dotted(node.func)
                if d and (d.startswith("np.")
                          or d.startswith("numpy.")):
                    out.append(mod.finding(
                        "TS003", node,
                        f"{d}() inside jitted body {fn.name!r} "
                        "escapes the trace — use jnp (or hoist the "
                        "host computation out of the jit)"))
    return out


_UNHASHABLE_ANNOT = {"list", "List", "dict", "Dict", "set", "Set"}


@rule("TS004", "static jit argument annotated/defaulted unhashable")
def check_unhashable_static(mod: ModuleInfo,
                            project: Project) -> List[Finding]:
    out: List[Finding] = []
    for fn, traced, jit_expr in _jit_bodies(mod):
        static = {a.arg for a in fn.args.args} - traced
        for arg in fn.args.args:
            if arg.arg not in static:
                continue
            ann = arg.annotation
            bad = None
            if ann is not None:
                base = ann.value if isinstance(ann, ast.Subscript) \
                    else ann
                name = terminal_name(base)
                if name in _UNHASHABLE_ANNOT:
                    bad = f"annotated {name}"
            # defaults align right-to-left with args
            defaults = fn.args.defaults
            if defaults:
                offset = len(fn.args.args) - len(defaults)
                idx = fn.args.args.index(arg) - offset
                if idx >= 0 and isinstance(
                        defaults[idx],
                        (ast.List, ast.Dict, ast.Set)):
                    bad = "mutable default"
            if bad:
                out.append(mod.finding(
                    "TS004", fn,
                    f"static jit argument {arg.arg!r} of "
                    f"{fn.name!r} is {bad}: static args are hashed "
                    "per call — pass a tuple/frozenset"))
    return out


@rule("TS005", "jitted callable not registered with a telemetry "
               "kernel family")
def check_unregistered_jit(mod: ModuleInfo,
                           project: Project) -> List[Finding]:
    """A jit bound to a name (or a decorated def) must flow through
    `instrument_kernel` — directly, via a `name = _instr(name, ...)`
    rebinding, or as a member of another kernel's `jits=[...]`
    executable-cache list (cross-module counts: the project-wide
    registration set is consulted)."""
    out: List[Finding] = []
    for node in ast.walk(mod.tree):
        # named bindings: X = jax.jit(...) / partial(jax.jit, ...)(f)
        if isinstance(node, ast.Assign):
            call = jit_call_of(node.value)
            if call is None:
                continue
            for tgt in node.targets:
                name = terminal_name(tgt)
                if name and name not in project.instrumented:
                    out.append(mod.finding(
                        "TS005", node,
                        f"jitted binding {name!r} is not registered "
                        "with a telemetry kernel family — wrap it "
                        "with instrument_kernel (or list it in a "
                        "wrapper's jits=[...])"))
        elif isinstance(node, ast.FunctionDef):
            if jit_decorator_of(node) is None:
                continue
            if node.name not in project.instrumented:
                out.append(mod.finding(
                    "TS005", node,
                    f"jit-decorated function {node.name!r} is not "
                    "registered with a telemetry kernel family — "
                    "its compiles will be booked as operator "
                    "execute/busy time"))
    return out


def _module_global_facts(mod: ModuleInfo):
    """(mutable globals, module-level assignment counts) for TS006:
    a module global is MUTABLE-RISKY when it is bound to a mutable
    literal/constructor, rebound more than once at module scope, or
    declared `global` and assigned inside any function."""
    assigns: Dict[str, int] = {}
    mutable: Set[str] = set()
    for node in mod.tree.body:
        targets = []
        if isinstance(node, ast.Assign):
            targets = [t for t in node.targets
                       if isinstance(t, ast.Name)]
            value = node.value
        elif isinstance(node, ast.AnnAssign) and isinstance(
                node.target, ast.Name) and node.value is not None:
            targets = [node.target]
            value = node.value
        else:
            continue
        is_mut = isinstance(value, (ast.List, ast.Dict, ast.Set,
                                    ast.ListComp, ast.DictComp,
                                    ast.SetComp))
        if isinstance(value, ast.Call):
            t = terminal_name(value.func)
            if t in ("dict", "list", "set", "OrderedDict",
                     "defaultdict", "deque"):
                is_mut = True
        for t in targets:
            assigns[t.id] = assigns.get(t.id, 0) + 1
            if is_mut:
                mutable.add(t.id)
    declared_global: Set[str] = set()
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Global):
            declared_global.update(node.names)
    rebound = {n for n, c in assigns.items() if c > 1}
    return (mutable | rebound | declared_global), set(assigns)


@rule("TS006", "jitted body reads a mutable module global or a "
               "rebound closure variable")
def check_mutable_capture(mod: ModuleInfo,
                          project: Project) -> List[Finding]:
    """A traced body that reads a MUTABLE module global (a dict/list
    cache, a rebound flag, a `global`-assigned counter) bakes the
    value it saw at FIRST trace into the compiled program: later
    mutations are silently ignored on cache hits (staleness) or mint
    fresh traces the retrace counters cannot attribute (the
    compile-wall class). Same hazard for a closure variable the
    enclosing function rebinds after the jitted def. The sanctioned
    patterns stay clean: reads through a thread-local install site
    (telemetry's set_current_op shape), single-assignment module
    CONSTANTS (MAX_RADIX_BITS), and statics passed as arguments."""
    risky, module_names = _module_global_facts(mod)
    tl_roots = project.threadlocal_roots
    out: List[Finding] = []
    for fn, traced, _ in _jit_bodies(mod):
        local: Set[str] = {a.arg for a in fn.args.args}
        local.update(a.arg for a in fn.args.kwonlyargs)
        if fn.args.vararg:
            local.add(fn.args.vararg.arg)
        for node in ast.walk(fn):
            if isinstance(node, (ast.Assign, ast.AugAssign,
                                 ast.AnnAssign)):
                tgts = node.targets if isinstance(node, ast.Assign) \
                    else [node.target]
                for t in tgts:
                    if isinstance(t, ast.Name):
                        local.add(t.id)
            elif isinstance(node, (ast.For, ast.comprehension)):
                tgt = node.target
                for sub in ast.walk(tgt):
                    if isinstance(sub, ast.Name):
                        local.add(sub.id)
            elif isinstance(node, (ast.FunctionDef,
                                   ast.AsyncFunctionDef)) \
                    and node is not fn:
                local.add(node.name)
            elif isinstance(node, ast.Lambda):
                local.update(a.arg for a in node.args.args)
        # closure variables rebound after the jitted def (staleness)
        rebound_closure: Set[str] = set()
        for anc in mod.ancestors(fn):
            if not isinstance(anc, (ast.FunctionDef,
                                    ast.AsyncFunctionDef)):
                continue
            counts: Dict[str, List[int]] = {}
            for sub in ast.walk(anc):
                if isinstance(sub, ast.Assign):
                    for t in sub.targets:
                        if isinstance(t, ast.Name):
                            counts.setdefault(t.id, []).append(
                                sub.lineno)
            for name_, lines in counts.items():
                if len(lines) > 1 or any(ln > fn.lineno
                                         for ln in lines):
                    rebound_closure.add(name_)
        for node in ast.walk(fn):
            if not isinstance(node, ast.Name) \
                    or not isinstance(node.ctx, ast.Load):
                continue
            name_ = node.id
            if name_ in local or name_ in tl_roots:
                continue
            hazard = None
            if name_ in risky and name_ in module_names:
                hazard = "mutable module global"
            elif name_ in rebound_closure \
                    and name_ not in module_names:
                hazard = "closure variable rebound in the " \
                         "enclosing function"
            if hazard:
                out.append(mod.finding(
                    "TS006", node,
                    f"jitted body {fn.name!r} reads {name_!r} — a "
                    f"{hazard}: the traced program froze one value "
                    "(stale on cache hits, an unattributable "
                    "retrace source otherwise); pass it as an "
                    "argument or route it through a registered "
                    "thread-local install site"))
    # dedupe repeated reads of the same name in the same body
    seen: Set[str] = set()
    uniq: List[Finding] = []
    for f in out:
        key = f.fingerprint()
        if key not in seen:
            seen.add(key)
            uniq.append(f)
    return uniq


@rule("TS007", "jax.jit that bypasses telemetry.kernels.jit (the "
               "device program keeps the Python function's name)")
def check_unnamed_jit(mod: ModuleInfo,
                      project: Project) -> List[Finding]:
    """Every device program is jitted through `kernels.jit(fn,
    family, part)`, which names the XLA module after its kernel
    family. Any other spelling of jax.jit — call, decorator, partial,
    `from jax import jit` — is a finding."""
    out: List[Finding] = []
    for node in ast.walk(mod.tree):
        raw = isinstance(node, ast.Attribute) \
            and dotted(node) == "jax.jit"
        imported = isinstance(node, ast.ImportFrom) \
            and node.module == "jax" \
            and any(a.name == "jit" for a in node.names)
        if raw or imported:
            out.append(mod.finding(
                "TS007", node,
                "jax.jit used directly: jit through "
                "telemetry.kernels.jit(fn, family, part=...) so the "
                "XLA module is named after its kernel family"))
    return out


TRACE_RULES = (check_traced_branch, check_host_sync,
               check_numpy_in_jit, check_unhashable_static,
               check_unregistered_jit, check_mutable_capture,
               check_unnamed_jit)
