"""Test configuration: force an 8-device virtual CPU mesh.

This is the direct analog of the reference's in-JVM DistributedQueryRunner
(presto-tests DistributedQueryRunner.java:85): real multi-device semantics,
one host, no hardware requirement (SURVEY.md §4 adoption note (c)).
Must run before jax is imported anywhere.
"""

import os

# Tests run on the CPU backend with eight virtual devices; the chip is
# reached only through chip_smoke.py. (tests/test_tpu_compile.py
# compiles for a DESCRIBED TPU inside its own fixture — still no chip.)
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402
import pytest  # noqa: E402


def _probe_sqlite_full_join() -> bool:
    """Capability probe, run ONCE per session: does this container's
    sqlite support FULL/RIGHT OUTER JOIN (added in sqlite 3.39)?
    Oracle-checked full-join tests skip with an explicit reason when
    it doesn't — a missing oracle feature is not an engine regression,
    and 9 permanently-red tests would otherwise bury real failures."""
    import sqlite3
    try:
        sqlite3.connect(":memory:").execute(
            "select * from (select 1 a) x "
            "full outer join (select 2 b) y on x.a = y.b")
        return True
    except sqlite3.OperationalError:
        return False


SQLITE_HAS_FULL_JOIN = _probe_sqlite_full_join()


def require_sqlite_full_join(sql: str) -> None:
    """Skip the calling test when its sqlite ORACLE text needs FULL or
    RIGHT OUTER JOIN and this sqlite can't run it."""
    import re
    if not SQLITE_HAS_FULL_JOIN and re.search(
            r"\b(full|right)\s+(outer\s+)?join\b", sql, re.I):
        pytest.skip(
            f"sqlite {__import__('sqlite3').sqlite_version} lacks "
            "FULL/RIGHT OUTER JOIN — oracle cannot check this case "
            "(capability probe in conftest)")


@pytest.fixture(scope="session")
def eight_devices():
    import jax
    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs


# XLA:CPU segfaults once a process accumulates enough live compiled
# executables (the full suite crosses the threshold; the mesh battery
# hits it in isolation too — see test_mesh_tpch). Dropping compiled
# programs BETWEEN MODULES keeps the live-executable count bounded at
# the cost of some recompiles; in-module caching still applies.
_last_module = [None]


@pytest.fixture(autouse=True)
def _clear_xla_caches_between_modules(request):
    mod = request.module.__name__
    if _last_module[0] is not None and _last_module[0] != mod:
        jax.clear_caches()
        # the query-serving cache hierarchy is process-wide by design
        # (one budget per server); between test MODULES it resets so a
        # module asserting scan-level behavior (EXPLAIN ANALYZE rows,
        # connector remote logs) never observes another module's warm
        # entries — mirrors the compiled-executable cache handling
        from presto_tpu.cache import reset_cache_manager
        reset_cache_manager()
        # history-based optimization is process-wide like the caches:
        # reset between modules so a module asserting plan shapes or
        # fusion reports never observes another module's measured
        # history (and recorded entries never leak across modules)
        from presto_tpu import history
        history.reset_history_store()
        # fault-injection hygiene: a module that armed the registry
        # and crashed before its own cleanup must not leak faults
        # into every later module
        from presto_tpu.execution import faults
        faults.disarm()
        # armed full-suite audit runs (PRESTO_TPU_SANITIZE=1): every
        # module boundary is a quiescent checkpoint — ledgers must
        # balance and no thread may outlive its owner's shutdown
        # (this is how the coordinator-pruner leak was found). Inert
        # in the default tier-1 run (sanitize stays disarmed).
        from presto_tpu import sanitize
        if sanitize.ARMED:
            violations = sanitize.audit(raise_=False,
                                        coordinator_check=True)
            assert not violations, (
                f"sanitizer violations at the {_last_module[0]} -> "
                f"{mod} module boundary:\n"
                + "\n".join(str(v) for v in violations))
    _last_module[0] = mod
    yield


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: heavy battery members excluded from the tier-1 fast "
        "lane (run them with -m slow)")
    config.addinivalue_line(
        "markers",
        "chaos: fault-injection / lifecycle tests (cancellation, "
        "deadlines, exchange faults) — deterministic, seeded")
