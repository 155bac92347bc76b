"""Static-analysis tier gate + linter self-tests.

`test_tree_is_lint_clean` IS the CI wiring: tier-1 fails when the
linter finds anything beyond the checked-in baseline
(presto_tpu/tools/lint_baseline.json). Every rule id has a fixture
self-test proving it fires (and does not fire on the clean variant),
plus tests of the suppression syntax and the baseline workflow
(docs/STATIC_ANALYSIS.md)."""

import json
import textwrap

import pytest

from presto_tpu.tools.lint import (
    BASELINE_DEFAULT, changed_files, diff_baseline, load_baseline,
    lint_source, main, repo_root, run_lint, write_baseline,
)
from presto_tpu.tools.lint_rules import RULES


def _rules(src, rule_id=None):
    findings = lint_source(textwrap.dedent(src))
    if rule_id is None:
        return findings
    return [f for f in findings if f.rule == rule_id]


# ---------------------------------------------------------------------------
# THE tier gate: zero non-baselined findings on the tree


def test_tree_is_lint_clean():
    result = run_lint()
    assert not result.errors, result.errors
    new, _stale = diff_baseline(result.findings,
                                load_baseline(BASELINE_DEFAULT))
    assert not new, "new lint findings (fix, suppress with a " \
        "reason, or re-baseline):\n" + "\n".join(
            f.render() for f in new)


def test_every_suppression_carries_a_reason():
    """Suppressed findings exist only with reasons (the parser drops
    reason-less ones back into the active set, so this also proves
    the syntax is in actual use)."""
    result = run_lint()
    for f in result.suppressed:
        assert f.suppressed and f.suppressed.strip()


def test_mesh_drive_loop_has_lifecycle_checkpoints():
    """The PR satellite: runner/mesh.py's phased drive loop carries
    the shared check_lifecycle checkpoints — CC004 verifies it."""
    import os
    path = os.path.join(repo_root(), "presto_tpu/runner/mesh.py")
    result = run_lint([path], explicit=True)
    cc004 = [f for f in result.findings if f.rule == "CC004"]
    assert not cc004, "\n".join(f.render() for f in cc004)


# ---------------------------------------------------------------------------
# rule fixtures: every id fires on its fixture, not on the clean twin


def test_rule_catalogue_complete():
    assert set(RULES) == {"TS001", "TS002", "TS003", "TS004", "TS005",
                          "TS006", "TS007",
                          "CC001", "CC002", "CC003", "CC004",
                          "CC005", "CC006"}


def test_ts001_traced_branch():
    bad = """
    import functools, jax

    @functools.partial(jax.jit, static_argnums=(1,))
    def kernel(x, n):
        if x > 0:
            return x
        return x + n
    """
    assert _rules(bad, "TS001")
    clean = """
    import functools, jax

    @functools.partial(jax.jit, static_argnums=(1,))
    def kernel(x, n):
        if n > 0:  # static argument: host branch is fine
            return x
        if x is None:  # identity guard, not a traced branch
            return x
        return x + n
    """
    assert not _rules(clean, "TS001")


def test_ts001_traced_while():
    bad = """
    import jax

    @jax.jit
    def kernel(x):
        while x > 0:
            x = x - 1
        return x
    """
    assert _rules(bad, "TS001")


def test_ts002_host_sync():
    bad = """
    import jax

    @jax.jit
    def kernel(x):
        total = x.sum().item()
        return float(x)
    """
    found = _rules(bad, "TS002")
    assert len(found) == 2  # .item() AND float(traced)
    clean = """
    import jax

    @jax.jit
    def kernel(x):
        return x.sum()

    def host_side(x):
        return x.item()  # not a jit body
    """
    assert not _rules(clean, "TS002")


def test_ts003_numpy_in_jit():
    bad = """
    import jax
    import numpy as np

    @jax.jit
    def kernel(x):
        return np.sum(x)
    """
    assert _rules(bad, "TS003")
    clean = """
    import jax
    import jax.numpy as jnp
    import numpy as np

    @jax.jit
    def kernel(x):
        return jnp.sum(x)

    def host(x):
        return np.sum(x)
    """
    assert not _rules(clean, "TS003")


def test_ts004_unhashable_static():
    bad = """
    import functools, jax

    @functools.partial(jax.jit, static_argnums=(1,))
    def kernel(x, keys: list):
        return x
    """
    assert _rules(bad, "TS004")
    clean = """
    import functools, jax
    from typing import Tuple

    @functools.partial(jax.jit, static_argnums=(1,))
    def kernel(x, keys: Tuple[str, ...]):
        return x
    """
    assert not _rules(clean, "TS004")


def test_ts005_unregistered_jit():
    bad = """
    import jax

    _kern = jax.jit(lambda x: x)

    @jax.jit
    def other(x):
        return x
    """
    assert len(_rules(bad, "TS005")) == 2
    clean = """
    import jax
    from presto_tpu.telemetry.kernels import instrument_kernel

    def _impl(x):
        return x

    _kern = jax.jit(_impl)
    _kern = instrument_kernel(_kern, "fixture")

    @jax.jit
    def component(x):
        return x

    wrapped = instrument_kernel(lambda x: component(x), "fam",
                                jits=[component])
    """
    assert not _rules(clean, "TS005")


def test_ts007_raw_jit_bypasses_the_device_name():
    bad = """
    import functools, jax
    from jax import jit

    @functools.partial(jax.jit, static_argnums=(1,))
    def kernel(x, n):  # lint-ok: TS005 fixture kernel
        return x + n

    other = jax.jit(lambda x: x)  # lint-ok: TS005 fixture kernel
    """
    assert len(_rules(bad, "TS007")) == 3
    clean = """
    import functools
    from presto_tpu.telemetry import kernels as _kernels

    @functools.partial(_kernels.jit, family="fixture", part="add",
                       static_argnums=(1,))
    def kernel(x, n):
        return x + n

    def _impl(x):
        return x

    other = _kernels.jit(_impl, "fixture")
    kernel = _kernels.instrument_kernel(kernel, "fixture")
    other = _kernels.instrument_kernel(other, "fixture")
    """
    assert not _rules(clean, "TS007")
    # the named jit is still a jit to every other rule
    assert not _rules(clean, "TS005")
    branching = """
    import functools
    from presto_tpu.telemetry import kernels as _kernels

    @functools.partial(_kernels.jit, family="fixture",
                       static_argnums=(1,))
    def kernel(x, n):  # lint-ok: TS005 fixture kernel
        if x > 0:
            return x
        return x + n
    """
    assert _rules(branching, "TS001")


def test_ts005_jits_list_variable_resolves():
    """A `jits=jit_list` keyword resolves through the local list
    binding (the operators/join_ops.make_probe_kernel shape)."""
    clean = """
    import jax
    from presto_tpu.telemetry.kernels import instrument_kernel

    def factory(flag):
        @jax.jit
        def stage0(x):
            return x
        jit_list = None
        if flag:
            jit_list = [stage0]
        k = instrument_kernel(lambda x: stage0(x), "fam",
                              jits=jit_list)
        return k
    """
    assert not _rules(clean, "TS005")


def test_ts006_mutable_global_read_in_jit():
    bad = """
    import jax

    _CACHE = {}

    @jax.jit
    def kernel(x):  # lint-ok: TS005 fixture kernel
        return x + len(_CACHE)
    """
    assert _rules(bad, "TS006")
    # rebound module global (a flag flipped at runtime)
    rebound = """
    import jax

    SCALE = 1
    SCALE = 2

    @jax.jit
    def kernel(x):  # lint-ok: TS005 fixture kernel
        return x * SCALE
    """
    assert _rules(rebound, "TS006")
    # global-assigned counter
    declared = """
    import jax

    _N = 0

    def bump():
        global _N
        _N += 1

    @jax.jit
    def kernel(x):  # lint-ok: TS005 fixture kernel
        return x + _N
    """
    assert _rules(declared, "TS006")
    # single-assignment module constant: the sanctioned pattern
    clean = """
    import jax

    MAX_BITS = 18

    @jax.jit
    def kernel(x):  # lint-ok: TS005 fixture kernel
        return x + MAX_BITS
    """
    assert not _rules(clean, "TS006")


def test_ts006_rebound_closure_variable():
    bad = """
    import jax

    def factory():
        scale = 1.0

        @jax.jit
        def kernel(x):  # lint-ok: TS005 fixture kernel
            return x * scale

        scale = 2.0
        return kernel
    """
    assert _rules(bad, "TS006")
    clean = """
    import jax

    def factory(scale):
        @jax.jit
        def kernel(x):  # lint-ok: TS005 fixture kernel
            return x * scale
        return kernel
    """
    assert not _rules(clean, "TS006")


def test_ts006_threadlocal_install_site_is_exempt():
    """Reads routed through a registered thread-local install site
    are the sanctioned pattern (telemetry's set_current_op shape)."""
    src = """
    import jax, threading

    _TL = threading.local()

    def install(v):
        _TL.v = v

    @jax.jit
    def kernel(x):  # lint-ok: TS005 fixture kernel
        return x + getattr(_TL, "v", 0)
    """
    assert not _rules(src, "TS006")


def test_cc001_unlocked_global_mutation():
    bad = """
    _CACHE = {}

    def put(k, v):
        _CACHE[k] = v
    """
    assert _rules(bad, "CC001")
    clean = """
    import threading

    _CACHE = {}
    _LOCK = threading.Lock()
    _CACHE["init"] = 1  # import-time init is single-threaded

    def put(k, v):
        with _LOCK:
            _CACHE[k] = v

    def _evict_locked(k):
        _CACHE.pop(k, None)  # *_locked: caller holds the lock
    """
    assert not _rules(clean, "CC001")


def test_cc002_bare_counter():
    bad = """
    import threading

    class Executor:
        def __init__(self):
            self._lock = threading.Lock()
            self.quanta = 0

        def bump(self):
            self.quanta += 1
    """
    assert _rules(bad, "CC002")
    clean = """
    import threading

    class Executor:
        def __init__(self):
            self._lock = threading.Lock()
            self.quanta = 0

        def bump(self):
            with self._lock:
                self.quanta += 1
    """
    assert not _rules(clean, "CC002")


def test_cc003_threadlocal_read_without_install():
    bad = """
    import threading

    _TL = threading.local()

    def read():
        return getattr(_TL, "never_installed", None)
    """
    assert _rules(bad, "CC003")
    clean = """
    import threading

    _TL = threading.local()

    def install(v):
        _TL.value = v

    def read():
        return getattr(_TL, "value", None)
    """
    assert not _rules(clean, "CC003")


def test_cc004_drive_loop_without_checkpoint():
    bad = """
    def drive(drivers):
        while True:
            done = True
            for d in drivers:
                if not d.is_finished():
                    done = False
                    d.process()
            if done:
                break
    """
    assert _rules(bad, "CC004")
    clean = """
    from presto_tpu.runner.local import check_lifecycle

    def drive(drivers, cancel, deadline):
        while True:
            check_lifecycle(cancel, deadline)
            done = True
            for d in drivers:
                if not d.is_finished():
                    done = False
                    d.process()
            if done:
                break
    """
    assert not _rules(clean, "CC004")


def test_cc005_raw_lock_ctor():
    """CC005 closes the static half of the sanitizer loop: every raw
    threading primitive in a covered layer escapes the armed
    lock-order detector."""
    bad = """
    import threading

    class Cache:
        def __init__(self):
            self._lock = threading.Lock()
    """
    assert _rules(bad, "CC005")
    # aliased module import (the runner/local.py `_threading` shape)
    aliased = """
    import threading as _threading

    _LOCK = _threading.RLock()
    _COND = _threading.Condition()
    """
    assert len(_rules(aliased, "CC005")) == 2
    # from-import binding
    from_import = """
    from threading import Lock

    _LOCK = Lock()
    """
    assert _rules(from_import, "CC005")
    clean = """
    from presto_tpu import sanitize

    class Cache:
        def __init__(self):
            self._lock = sanitize.lock("cache.fixture")
            self._cond = sanitize.condition("cache.fixture_cond")
    """
    assert not _rules(clean, "CC005")
    suppressed = """
    import threading

    _META = threading.Lock()  # lint-ok: CC005 fixture meta-lock
    """
    assert not _rules(suppressed, "CC005")
    # threading.Event is NOT a lock: no finding
    event = """
    import threading

    _EV = threading.Event()
    """
    assert not _rules(event, "CC005")


def test_cc006_raw_thread_ctor():
    bad = """
    import threading

    def spawn(fn):
        t = threading.Thread(target=fn, daemon=True)
        t.start()
        return t
    """
    assert _rules(bad, "CC006")
    clean = """
    from presto_tpu import sanitize

    def spawn(fn, owner):
        t = sanitize.thread(target=fn, purpose="fixture",
                            owner=owner)
        t.start()
        return t
    """
    assert not _rules(clean, "CC006")
    suppressed = """
    import threading

    # lint-ok: CC006 fixture thread, joined by the caller
    t = threading.Thread(target=print)
    """
    assert not _rules(suppressed, "CC006")


def test_cc002_sanitize_factory_counts_as_lock_ownership():
    """A class whose lock comes from sanitize.lock() is still a
    lock-owning class for CC002 — adopting the factory must not
    silently retire the bare-counter rule."""
    bad = """
    from presto_tpu import sanitize

    class Executor:
        def __init__(self):
            self._lock = sanitize.lock("executor.fixture")
            self.quanta = 0

        def bump(self):
            self.quanta += 1
    """
    assert _rules(bad, "CC002")


def test_sanitize_package_is_lint_scoped():
    """The sanitizer's own tree is covered (its deliberate raw
    primitives ride suppressions with reasons, proving the
    CC005/CC006 escape hatch is exercised)."""
    import os
    from presto_tpu.tools.lint import CONC_SCOPE
    assert "presto_tpu/sanitize/" in CONC_SCOPE
    path = os.path.join(repo_root(), "presto_tpu/sanitize/locks.py")
    result = run_lint([path], explicit=True)
    cc005 = [f for f in result.findings if f.rule == "CC005"]
    assert not cc005, "\n".join(f.render() for f in cc005)
    assert any(f.rule == "CC005" for f in result.suppressed)


# ---------------------------------------------------------------------------
# suppression syntax


def test_suppression_with_reason():
    src = """
    import jax

    _kern = jax.jit(lambda x: x)  # lint-ok: TS005 fixture kernel
    """
    assert not _rules(src, "TS005")


def test_suppression_standalone_comment_line():
    src = """
    import jax

    # lint-ok: TS005 fixture kernel, compile attribution untested
    _kern = jax.jit(lambda x: x)
    """
    assert not _rules(src, "TS005")


def test_suppression_without_reason_does_not_count():
    src = """
    import jax

    _kern = jax.jit(lambda x: x)  # lint-ok: TS005
    """
    assert _rules(src, "TS005")


def test_suppression_wrong_rule_does_not_count():
    src = """
    import jax

    _kern = jax.jit(lambda x: x)  # lint-ok: TS001 wrong rule id
    """
    assert _rules(src, "TS005")


# ---------------------------------------------------------------------------
# baseline workflow


def test_baseline_roundtrip(tmp_path):
    src = """
    import jax

    _a = jax.jit(lambda x: x)
    _b = jax.jit(lambda x: x + 1)
    """
    findings = _rules(src, "TS005")
    assert len(findings) == 2
    path = str(tmp_path / "baseline.json")
    write_baseline(path, findings)
    loaded = load_baseline(path)
    assert sum(loaded.values()) == 2
    # identical run: nothing new, nothing stale
    new, stale = diff_baseline(findings, loaded)
    assert not new and not stale
    # one fixed: stale entry surfaces for pruning
    new, stale = diff_baseline(findings[:1], loaded)
    assert not new and len(stale) == 1
    # a fresh finding in another context is NEW
    other = _rules("""
    import jax

    _c = jax.jit(lambda y: y)
    """, "TS005")
    new, _ = diff_baseline(findings + other, loaded)
    assert len(new) == 1


def test_baseline_fingerprint_is_line_stable():
    a = _rules("""
    import jax

    _kern = jax.jit(lambda x: x)
    """, "TS005")
    b = _rules("""
    import jax

    # a comment shifting everything down


    _kern = jax.jit(lambda x: x)
    """, "TS005")
    assert a[0].fingerprint() == b[0].fingerprint()
    assert a[0].line != b[0].line


def test_checked_in_baseline_parses():
    data = load_baseline(BASELINE_DEFAULT)
    assert isinstance(data, dict)


# ---------------------------------------------------------------------------
# CLI / --changed


def test_cli_list_rules(capsys):
    assert main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rid in RULES:
        assert rid in out


def test_cli_baseline_mode(capsys):
    assert main(["--baseline"]) == 0


def test_changed_files_scoped():
    files = changed_files(repo_root())
    for f in files:
        assert f.endswith(".py")
