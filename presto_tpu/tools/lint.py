"""presto-tpu static linter: trace-safety + concurrency rules over the
engine tree (docs/STATIC_ANALYSIS.md has the full catalogue and the
workflow).

    python -m presto_tpu.tools.lint                 # full tree
    python -m presto_tpu.tools.lint --baseline      # fail on NEW only
    python -m presto_tpu.tools.lint --changed       # git-changed files
    python -m presto_tpu.tools.lint --write-baseline
    python -m presto_tpu.tools.lint path/to/file.py

Exit status: 0 = clean (or nothing beyond the baseline), 1 = findings
(or new-vs-baseline findings), 2 = usage/parse errors.

The baseline (`tools/lint_baseline.json`, checked in) holds the
fingerprints of accepted pre-existing findings so the fast test tier
(tests/test_static_analysis.py) fails only on NEW violations. Findings
fixed since the baseline show up as "stale" entries — prune them with
--write-baseline.

Rule scoping: trace-safety rules (TS0xx) run over the kernel layer,
concurrency rules (CC0xx) over the threaded layers; explicitly named
paths run EVERY rule (that is what the fixture self-tests use).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import subprocess
import sys
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from presto_tpu.tools.lint_rules import (
    Finding, ModuleInfo, Project, RULES,
)
from presto_tpu.tools.lint_rules.concurrency import CONCURRENCY_RULES
from presto_tpu.tools.lint_rules.trace_safety import TRACE_RULES

#: repo-relative prefixes the trace-safety rules cover (the kernel
#: layer: anything that builds or composes jitted programs)
TRACE_SCOPE = (
    "presto_tpu/ops/", "presto_tpu/operators/", "presto_tpu/expr/",
    "presto_tpu/parallel/", "presto_tpu/batch.py",
    "presto_tpu/execution/dynamic_filters.py",
)
#: prefixes the concurrency rules cover (layers crossed by many
#: threads: executor workers, HTTP handlers, shared caches)
CONC_SCOPE = (
    "presto_tpu/execution/", "presto_tpu/runner/",
    "presto_tpu/server/", "presto_tpu/telemetry/",
    "presto_tpu/cache/", "presto_tpu/sanitize/",
)

BASELINE_DEFAULT = os.path.join(
    os.path.dirname(__file__), "lint_baseline.json")


def repo_root() -> str:
    """The directory holding the presto_tpu package."""
    return os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))


def _rel(path: str, root: str) -> str:
    return os.path.relpath(os.path.abspath(path), root).replace(
        os.sep, "/")


def default_files(root: str) -> List[str]:
    out: List[str] = []
    seen = set()
    for scope in sorted(set(TRACE_SCOPE + CONC_SCOPE)):
        full = os.path.join(root, scope)
        if scope.endswith(".py"):
            if os.path.exists(full) and full not in seen:
                seen.add(full)
                out.append(full)
            continue
        for dirpath, _, names in os.walk(full):
            for n in sorted(names):
                p = os.path.join(dirpath, n)
                if n.endswith(".py") and p not in seen:
                    seen.add(p)
                    out.append(p)
    return out


def changed_files(root: str) -> List[str]:
    """git-changed + untracked .py files inside the lint scopes."""
    try:
        diff = subprocess.run(
            ["git", "diff", "--name-only", "HEAD"],
            cwd=root, capture_output=True, text=True, check=True,
        ).stdout.splitlines()
        untracked = subprocess.run(
            ["git", "ls-files", "--others", "--exclude-standard"],
            cwd=root, capture_output=True, text=True, check=True,
        ).stdout.splitlines()
    except (subprocess.CalledProcessError, FileNotFoundError):
        return default_files(root)
    picked: List[str] = []
    for rel in diff + untracked:
        rel = rel.strip()
        if not rel.endswith(".py"):
            continue
        if any(rel == s or (s.endswith("/") and rel.startswith(s))
               for s in TRACE_SCOPE + CONC_SCOPE):
            full = os.path.join(root, rel)
            if os.path.exists(full):
                picked.append(full)
    return picked


def rules_for(rel_path: str, explicit: bool):
    if explicit:
        return TRACE_RULES + CONCURRENCY_RULES
    rules = []
    if any(rel_path == s or (s.endswith("/") and rel_path.startswith(s))
           for s in TRACE_SCOPE):
        rules.extend(TRACE_RULES)
    if any(rel_path == s or (s.endswith("/") and rel_path.startswith(s))
           for s in CONC_SCOPE):
        rules.extend(CONCURRENCY_RULES)
    return tuple(rules)


@dataclasses.dataclass
class LintResult:
    findings: List[Finding]          # active (not suppressed)
    suppressed: List[Finding]
    errors: List[str]                # unparseable files


def run_lint(files: Optional[Sequence[str]] = None,
             explicit: bool = False,
             root: Optional[str] = None) -> LintResult:
    """Lint `files` (default: the full scoped tree). `explicit` runs
    every rule regardless of path scope (fixture mode)."""
    root = root or repo_root()
    file_list = list(files) if files is not None \
        else default_files(root)
    modules: List[Tuple[ModuleInfo, bool]] = []
    errors: List[str] = []
    for path in file_list:
        rel = _rel(path, root)
        try:
            with open(path, "r", encoding="utf-8") as f:
                src = f.read()
            modules.append((ModuleInfo(path, src, display_path=rel),
                            explicit))
        except (OSError, SyntaxError) as e:
            errors.append(f"{rel}: {e}")
    # the cross-file registration facts (TS005's instrument_kernel
    # set, CC003's thread-local install sites) must come from the
    # FULL scoped tree even when only a subset is being linted — a
    # kernel registered from another module must not become a false
    # finding in --changed / explicit-path mode
    project_modules = [m for m, _ in modules]
    if files is not None:
        linted = {m.path for m in project_modules}
        for path in default_files(root):
            rel = _rel(path, root)
            if rel in linted:
                continue
            try:
                with open(path, "r", encoding="utf-8") as f:
                    project_modules.append(
                        ModuleInfo(path, f.read(), display_path=rel))
            except (OSError, SyntaxError):
                pass  # context-only module; its own lint run reports
    project = Project(project_modules)
    findings: List[Finding] = []
    suppressed: List[Finding] = []
    for mod, is_explicit in modules:
        for check in rules_for(mod.path, is_explicit):
            for f in check(mod, project):
                (suppressed if f.suppressed else findings).append(f)
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    return LintResult(findings, suppressed, errors)


def lint_source(source: str, filename: str = "fixture.py",
                rules=None) -> List[Finding]:
    """Lint a source string with every rule (or the given subset) —
    the self-test surface for rule fixtures."""
    mod = ModuleInfo(filename, source, display_path=filename)
    project = Project([mod])
    out: List[Finding] = []
    for check in (rules or TRACE_RULES + CONCURRENCY_RULES):
        out.extend(f for f in check(mod, project)
                   if not f.suppressed)
    return out


# ---------------------------------------------------------------------------
# baseline


def load_baseline(path: str) -> Dict[str, int]:
    if not os.path.exists(path):
        return {}
    with open(path, "r", encoding="utf-8") as f:
        data = json.load(f)
    return {k: int(v) for k, v in data.get("findings", {}).items()}


def write_baseline(path: str, findings: Iterable[Finding]) -> None:
    counts: Dict[str, int] = {}
    for f in findings:
        counts[f.fingerprint()] = counts.get(f.fingerprint(), 0) + 1
    with open(path, "w", encoding="utf-8") as f:
        json.dump({"version": 1,
                   "findings": dict(sorted(counts.items()))},
                  f, indent=1, sort_keys=True)
        f.write("\n")


def diff_baseline(findings: Sequence[Finding],
                  baseline: Dict[str, int]
                  ) -> Tuple[List[Finding], List[str]]:
    """(new findings beyond the baselined counts, stale baseline
    fingerprints no current finding matches)."""
    remaining = dict(baseline)
    new: List[Finding] = []
    for f in findings:
        fp = f.fingerprint()
        if remaining.get(fp, 0) > 0:
            remaining[fp] -= 1
        else:
            new.append(f)
    stale = sorted(fp for fp, n in remaining.items() if n > 0)
    return new, stale


# ---------------------------------------------------------------------------
# CLI


def main(argv: Optional[Sequence[str]] = None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m presto_tpu.tools.lint",
        description="presto-tpu trace-safety + concurrency linter")
    p.add_argument("paths", nargs="*",
                   help="files to lint (default: the scoped tree); "
                        "explicit paths run EVERY rule")
    p.add_argument("--baseline", nargs="?", const=BASELINE_DEFAULT,
                   default=None, metavar="FILE",
                   help="compare against the checked-in baseline and "
                        "fail only on NEW findings")
    p.add_argument("--write-baseline", action="store_true",
                   help="write the current findings as the baseline")
    p.add_argument("--changed", action="store_true",
                   help="lint only git-changed files (quick local "
                        "runs)")
    p.add_argument("--format", choices=("text", "json"),
                   default="text")
    p.add_argument("--show-suppressed", action="store_true")
    p.add_argument("--list-rules", action="store_true")
    args = p.parse_args(argv)

    if args.list_rules:
        for rid in sorted(RULES):
            print(f"{rid}  {RULES[rid]}")
        return 0

    root = repo_root()
    explicit = bool(args.paths)
    files = args.paths or (changed_files(root) if args.changed
                           else None)
    result = run_lint(files, explicit=explicit, root=root)
    if result.errors:
        for e in result.errors:
            print(f"error: {e}", file=sys.stderr)
        return 2

    if args.write_baseline:
        path = args.baseline or BASELINE_DEFAULT
        write_baseline(path, result.findings)
        print(f"wrote {len(result.findings)} finding(s) to {path}")
        return 0

    to_report = result.findings
    stale: List[str] = []
    if args.baseline is not None:
        # --changed lints a subset; diffing that subset against the
        # full-tree baseline would report every untouched file's
        # baseline entry as stale, so stale reporting needs the full
        # run
        baseline = load_baseline(args.baseline)
        to_report, stale = diff_baseline(result.findings, baseline)
        if args.changed or explicit:
            stale = []

    if args.format == "json":
        print(json.dumps({
            "findings": [dataclasses.asdict(f) for f in to_report],
            "suppressed": [dataclasses.asdict(f)
                           for f in result.suppressed],
            "stale_baseline": stale,
        }, indent=1))
    else:
        for f in to_report:
            print(f.render())
        if args.show_suppressed:
            for f in result.suppressed:
                print(f.render())
        for fp in stale:
            print(f"stale baseline entry (fixed? prune with "
                  f"--write-baseline): {fp}")
        new = "new " if args.baseline is not None else ""
        print(f"{len(to_report)} {new}finding(s), "
              f"{len(result.suppressed)} suppressed"
              + (f", {len(stale)} stale baseline entr"
                 f"{'y' if len(stale) == 1 else 'ies'}"
                 if stale else ""))
    return 1 if to_report else 0


if __name__ == "__main__":
    sys.exit(main())
