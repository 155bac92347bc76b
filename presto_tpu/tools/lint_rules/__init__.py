"""Shared infrastructure of the presto-tpu static linter
(tools/lint.py is the CLI; trace_safety.py and concurrency.py hold the
rules). Everything here is plain `ast` analysis — no imports of the
checked modules, so the linter can run on a broken tree.

Key pieces:

  * Finding — one violation, with a line-number-free fingerprint
    (path, rule, enclosing qualname, normalized source line) so the
    baseline survives unrelated edits above the finding
  * ModuleInfo — one parsed file: tree, source lines, suppression
    comments, and parent links (ast has no parent pointers)
  * Project — the cross-file facts rules need: names registered with
    `instrument_kernel` (any module may register another module's
    kernel via a `jits=[...]` list), and thread-local attributes
    written anywhere (an attribute READ is only a bug when NO install
    site exists in the whole tree)

Suppression syntax (docs/STATIC_ANALYSIS.md):

    offending_line()  # lint-ok: TS003 reason why this is fine

A suppression must name the rule id and carry a non-empty reason; a
standalone `# lint-ok:` comment line suppresses the next code line.
"""

from __future__ import annotations

import ast
import dataclasses
import re
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

#: rule id -> one-line description (the catalogue; each rule's module
#: registers itself here at import)
RULES: Dict[str, str] = {}


def rule(rule_id: str, description: str):
    """Register a rule id in the catalogue (decorator form keeps the
    id next to its implementation)."""
    def deco(fn):
        RULES[rule_id] = description
        fn.rule_id = rule_id
        return fn
    return deco


@dataclasses.dataclass
class Finding:
    rule: str
    path: str            # repo-relative, forward slashes
    line: int            # 1-based
    context: str         # enclosing function qualname or "<module>"
    message: str
    snippet: str         # stripped source of the flagged line
    suppressed: Optional[str] = None   # reason text when suppressed

    def fingerprint(self) -> str:
        """Line-number-free identity for the baseline: stable across
        edits elsewhere in the file."""
        return f"{self.path}::{self.rule}::{self.context}::" \
               f"{self.snippet}"

    def render(self) -> str:
        sup = f"  [suppressed: {self.suppressed}]" \
            if self.suppressed else ""
        return (f"{self.path}:{self.line}: {self.rule} "
                f"[{self.context}] {self.message}{sup}")


_SUPPRESS_RE = re.compile(
    r"#\s*lint-ok:\s*([A-Z]{2}\d{3}(?:\s*,\s*[A-Z]{2}\d{3})*)"
    r"\s*(.*)$")


class ModuleInfo:
    """One parsed source file plus the lexical facts rules share."""

    def __init__(self, path: str, source: str,
                 display_path: Optional[str] = None):
        self.path = display_path or path
        self.source = source
        self.lines = source.splitlines()
        self.tree = ast.parse(source)
        # parent links + enclosing-function map
        self.parent: Dict[int, ast.AST] = {}
        for node in ast.walk(self.tree):
            for child in ast.iter_child_nodes(node):
                self.parent[id(child)] = node
        #: line -> [(rule_id | "*", reason)]
        self.suppressions: Dict[int, List[Tuple[str, str]]] = {}
        self._parse_suppressions()

    def _parse_suppressions(self) -> None:
        for i, text in enumerate(self.lines, start=1):
            m = _SUPPRESS_RE.search(text)
            if not m:
                continue
            ids = [x.strip() for x in m.group(1).split(",")]
            reason = m.group(2).strip()
            target = i
            if text.lstrip().startswith("#"):
                # standalone comment: applies to the next line
                target = i + 1
            for rid in ids:
                self.suppressions.setdefault(target, []).append(
                    (rid, reason))

    def suppression_for(self, rule_id: str,
                        line: int) -> Optional[str]:
        """The reason text when `rule_id` is suppressed on `line`
        (empty-reason suppressions do NOT count — a reason is part of
        the syntax)."""
        for rid, reason in self.suppressions.get(line, ()):
            if rid == rule_id and reason:
                return reason
        return None

    # -- lexical helpers ----------------------------------------------

    def qualname(self, node: ast.AST) -> str:
        parts: List[str] = []
        cur: Optional[ast.AST] = node
        while cur is not None:
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.ClassDef)):
                parts.append(cur.name)
            cur = self.parent.get(id(cur))
        return ".".join(reversed(parts)) or "<module>"

    def ancestors(self, node: ast.AST) -> Iterable[ast.AST]:
        cur = self.parent.get(id(node))
        while cur is not None:
            yield cur
            cur = self.parent.get(id(cur))

    def snippet(self, line: int) -> str:
        if 1 <= line <= len(self.lines):
            return self.lines[line - 1].strip()
        return ""

    def finding(self, rule_id: str, node: ast.AST,
                message: str) -> Finding:
        line = getattr(node, "lineno", 1)
        return Finding(
            rule=rule_id, path=self.path, line=line,
            context=self.qualname(node), message=message,
            snippet=self.snippet(line),
            suppressed=self.suppression_for(rule_id, line))


# ---------------------------------------------------------------------------
# shared AST pattern helpers


def dotted(node: ast.AST) -> Optional[str]:
    """'jax.jit' for Attribute/Name chains, else None."""
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        base = dotted(node.value)
        return f"{base}.{node.attr}" if base else None
    return None


def is_jax_jit(node: ast.AST) -> bool:
    """A RAW jax.jit: the device program it makes carries the Python
    function's name (TS007)."""
    return dotted(node) in ("jax.jit", "jit")


def is_kernel_jit(node: ast.AST) -> bool:
    """telemetry.kernels.jit under any module alias (`_kernels.jit`):
    jax.jit plus the device name, same jit keywords."""
    return (dotted(node) or "").endswith("kernels.jit")


def is_jit(node: ast.AST) -> bool:
    return is_jax_jit(node) or is_kernel_jit(node)


def partial_of_jit(call: ast.AST) -> Optional[ast.Call]:
    """The Call node when `call` is functools.partial(<jit>, ...)."""
    if isinstance(call, ast.Call) \
            and dotted(call.func) in ("functools.partial", "partial") \
            and call.args and is_jit(call.args[0]):
        return call
    return None


def jit_call_of(value: ast.AST) -> Optional[ast.Call]:
    """The jit-ish Call when `value` is <jit>(...) or
    functools.partial(<jit>, ...)(...) — i.e. an expression whose
    result is a jitted callable (<jit>: jax.jit or kernels.jit)."""
    if isinstance(value, ast.Call):
        if is_jit(value.func):
            return value
        if partial_of_jit(value.func) is not None:
            return value
    return None


def static_params_of(jit_expr: ast.AST,
                     fn: ast.FunctionDef) -> Set[str]:
    """Parameter names of `fn` declared static by the jit expression
    (static_argnums indices / static_argnames)."""
    kwargs: List[ast.keyword] = []
    if isinstance(jit_expr, ast.Call):
        kwargs.extend(jit_expr.keywords)
        p = partial_of_jit(jit_expr.func) \
            or partial_of_jit(jit_expr)
        if p is not None:
            kwargs.extend(p.keywords)
    names: Set[str] = set()
    params = [a.arg for a in fn.args.args]
    for kw in kwargs:
        if kw.arg == "static_argnums":
            for idx in _int_elements(kw.value):
                if 0 <= idx < len(params):
                    names.add(params[idx])
        elif kw.arg == "static_argnames":
            names.update(_str_elements(kw.value))
    return names


def _int_elements(node: ast.AST) -> List[int]:
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return [node.value]
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for e in node.elts:
            if isinstance(e, ast.Constant) and isinstance(e.value, int):
                out.append(e.value)
        return out
    return []


def _str_elements(node: ast.AST) -> List[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return [node.value]
    if isinstance(node, (ast.Tuple, ast.List)):
        return [e.value for e in node.elts
                if isinstance(e, ast.Constant)
                and isinstance(e.value, str)]
    return []


def jit_decorator_of(fn: ast.AST) -> Optional[ast.AST]:
    """The decorator expression when `fn` is decorated as a jit body
    (@jax.jit or @functools.partial(<jit>, ...))."""
    if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
        return None
    for dec in fn.decorator_list:
        if is_jax_jit(dec) or partial_of_jit(dec) is not None:
            return dec
    return None


#: terminal identifier of a Name or Attribute (`a.b.c` -> "c")
def terminal_name(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        return node.attr
    return None


_LOCKISH = re.compile(r"lock|cond|mutex", re.IGNORECASE)


def lockish_expr(node: ast.AST) -> bool:
    """Heuristic: does this `with` context expression look like a
    lock? (a Name/Attribute whose terminal mentions lock/cond/mutex,
    or a call on one — `self._cond`, `_PLUGIN_CACHE_LOCK`,
    `state["lock"]`)."""
    for sub in ast.walk(node):
        t = terminal_name(sub)
        if t and _LOCKISH.search(t):
            return True
        if isinstance(sub, ast.Constant) \
                and isinstance(sub.value, str) \
                and _LOCKISH.fullmatch(sub.value):
            return True
    return False


def in_locked_context(mod: ModuleInfo, node: ast.AST) -> bool:
    """Is `node` lexically under a with-lock, or inside a function
    following the `_locked` caller-holds-the-lock naming convention,
    or in a function that explicitly calls `.acquire()`?"""
    for anc in mod.ancestors(node):
        if isinstance(anc, ast.With):
            for item in anc.items:
                if lockish_expr(item.context_expr):
                    return True
        if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if anc.name.endswith("_locked"):
                return True
            for sub in ast.walk(anc):
                if isinstance(sub, ast.Call) \
                        and isinstance(sub.func, ast.Attribute) \
                        and sub.func.attr == "acquire":
                    return True
            return False  # nearest function decides
    return False


def is_threading_ctor(value: ast.AST, kinds=("Lock", "RLock",
                                             "Condition")) -> bool:
    return isinstance(value, ast.Call) \
        and dotted(value.func) in tuple(
            f"threading.{k}" for k in kinds) + kinds


def is_sanitize_factory(value: ast.AST) -> bool:
    """`sanitize.lock/rlock/condition(...)` (any alias whose terminal
    module name mentions sanitize) — the sanitizer's named drop-in
    primitives count as lock ownership for the CC rules, exactly like
    a raw threading ctor."""
    if not isinstance(value, ast.Call) \
            or not isinstance(value.func, ast.Attribute):
        return False
    if value.func.attr not in ("lock", "rlock", "condition"):
        return False
    base = terminal_name(value.func.value) or ""
    return "sanitize" in base


class Project:
    """Cross-file facts, built in one pass over every ModuleInfo
    before rules run."""

    def __init__(self, modules: Sequence[ModuleInfo]):
        self.modules = list(modules)
        #: identifier terminals registered with instrument_kernel
        #: anywhere (first arg, jits=[...] elements, rebinding call)
        self.instrumented: Set[str] = set()
        #: attribute names written on any thread-local root anywhere
        self.threadlocal_written: Set[str] = set()
        #: names bound to threading.local() in ANY module (TS006's
        #: exemption set — a read routed through a thread-local root
        #: is the sanctioned mutable-state pattern). Project-wide by
        #: the same cross-file argument as `instrumented`; the union
        #: is deliberately name-based, so a name that is a TL root in
        #: one module exempts reads of that name elsewhere too.
        self.threadlocal_roots: Set[str] = set()
        for m in self.modules:
            self._scan(m)

    def _scan(self, mod: ModuleInfo) -> None:
        tl_roots = threadlocal_roots(mod)
        self.threadlocal_roots |= tl_roots
        # name -> every value expression assigned to it (so a
        # `jits=jit_list` keyword resolves through the local
        # `jit_list = [stage0, stage2, ...]` bindings)
        assigned: Dict[str, List[ast.AST]] = {}
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Assign):
                for tgt in node.targets:
                    if isinstance(tgt, ast.Name):
                        assigned.setdefault(tgt.id, []).append(
                            node.value)
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Call):
                t = terminal_name(node.func)
                if t in ("instrument_kernel", "_instr"):
                    for arg in list(node.args) \
                            + [kw.value for kw in node.keywords]:
                        exprs = [arg]
                        if isinstance(arg, ast.Name):
                            exprs.extend(assigned.get(arg.id, ()))
                        for e in exprs:
                            for sub in ast.walk(e):
                                n = terminal_name(sub)
                                if n:
                                    self.instrumented.add(n)
                elif t == "setattr" and len(node.args) >= 2:
                    root = terminal_name(node.args[0])
                    if root in tl_roots and isinstance(
                            node.args[1], ast.Constant):
                        self.threadlocal_written.add(
                            node.args[1].value)
            elif isinstance(node, (ast.Assign, ast.AugAssign)):
                targets = node.targets \
                    if isinstance(node, ast.Assign) else [node.target]
                for tgt in targets:
                    if isinstance(tgt, ast.Attribute) \
                            and terminal_name(tgt.value) in tl_roots:
                        self.threadlocal_written.add(tgt.attr)


def threadlocal_roots(mod: ModuleInfo) -> Set[str]:
    """Names (module globals or self attrs) bound to
    threading.local() in this module."""
    roots: Set[str] = set()
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Assign) and isinstance(
                node.value, ast.Call) \
                and dotted(node.value.func) in ("threading.local",
                                                "local"):
            for tgt in node.targets:
                t = terminal_name(tgt)
                if t:
                    roots.add(t)
    return roots
