"""Persistent XLA compilation cache + AOT prewarm
(execution/compile_cache.py, docs/COMPILATION.md).

The restart contract under test: wipe every in-process compiled-
kernel layer (engine kernel LRUs + jax jit caches — exactly what a
coordinator reboot loses), AOT-prewarm the workload's statements
against the on-disk cache, and the next real execution performs ZERO
fresh compiles."""

import os

import pytest

_NO_CACHES = {
    "plan_cache_enabled": False,
    "fragment_result_cache_enabled": False,
    "page_source_cache_enabled": False,
}


def test_configure_and_persist(tmp_path):
    from presto_tpu.execution import compile_cache
    from presto_tpu.runner.local import LocalRunner
    d = str(tmp_path / "xla")
    compile_cache.configure_compilation_cache(d)
    assert compile_cache.configured_cache_dir() == d
    r = LocalRunner("memory", "default", properties=dict(_NO_CACHES))
    r.execute("CREATE TABLE cc1 AS SELECT custkey ck1, acctbal cb1 "
              "FROM tpch.tiny.customer LIMIT 64")
    st = r.execute("SELECT ck1 % 5, sum(cb1) FROM cc1 "
                   "GROUP BY ck1 % 5 ORDER BY 1").query_stats
    assert st["kernel_compiles"] > 0
    # the compiled executables really landed on disk
    assert len(os.listdir(d)) > 0


@pytest.mark.parametrize("env_dir,backend,want", [
    # placed from outside: jax honors its own variable, no dir in code
    ("/somewhere/else", "tpu", None),
    ("/somewhere/else", "cpu", None),
    # not placed: the checkout's .jax_cache — but never on a CPU backend
    ("", "tpu", "default"),
    ("", "cpu", None),
])
def test_default_cache_rule(monkeypatch, env_dir, backend, want):
    """The one rule (compile_cache.configure): with
    JAX_COMPILATION_CACHE_DIR set the program sets no directory in
    code; unset, the cache is <checkout>/.jax_cache, except that a
    CPU backend does not default into it."""
    import jax
    from presto_tpu.execution import compile_cache
    if env_dir:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(compile_cache, "_CONFIGURED_DIR", None)
    set_dirs = []
    monkeypatch.setattr(compile_cache, "configure_compilation_cache",
                        set_dirs.append)
    compile_cache.configure()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    expect = [os.path.join(repo, ".jax_cache")] if want else []
    assert set_dirs == expect


def test_explicit_override_survives_default_rule(tmp_path, monkeypatch):
    """A `compilation_cache_dir=` given to one runner is not undone by
    the next runner built without it."""
    import jax
    from presto_tpu.execution import compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    d = str(tmp_path / "xla")
    compile_cache.configure_compilation_cache(d)
    compile_cache.configure()
    assert compile_cache.configured_cache_dir() == d
    assert jax.config.jax_compilation_cache_dir == d


def test_unusable_cache_dir_raises(tmp_path):
    """A cache that cannot be configured is an error, not a silent
    cold start."""
    from presto_tpu.execution import compile_cache
    blocker = tmp_path / "file"
    blocker.write_text("x")
    with pytest.raises(OSError):
        compile_cache.configure_compilation_cache(
            str(blocker / "sub"))


def test_restart_then_prewarm_serves_without_compiles(tmp_path):
    from presto_tpu.execution import compile_cache
    from presto_tpu.runner.local import LocalRunner
    d = str(tmp_path / "xla")
    compile_cache.configure_compilation_cache(d)
    r = LocalRunner("memory", "default", properties=dict(_NO_CACHES))
    r.execute("CREATE TABLE cc2 AS SELECT custkey ck2, acctbal cb2 "
              "FROM tpch.tiny.customer LIMIT 64")
    sql = "SELECT ck2 % 3, count(*), sum(cb2) FROM cc2 " \
          "WHERE cb2 > 0 GROUP BY ck2 % 3 ORDER BY 1 LIMIT 2"
    assert r.execute(sql).query_stats["kernel_compiles"] > 0

    # --- the restart ---
    compile_cache.clear_kernel_caches()
    # after the wipe, a bare re-run WOULD re-trace (that is what the
    # prewarm exists to absorb before traffic arrives)
    report = r.prewarm([sql])
    assert report["statements"] == 1 and report["failed"] == []
    assert report["compiles"] > 0          # prewarm paid the re-trace
    # serving traffic after prewarm compiles NOTHING
    st = r.execute(sql).query_stats
    assert st["kernel_compiles"] == 0
    assert st["compile_ms"] == 0.0


def test_restart_recompiles_classify_as_new_kernel():
    """clear_kernel_caches resets the retrace classifier: post-wipe
    compiles are first traces of a fresh process, NOT shape retraces
    (a dashboard must not read a restart as bucketing failure)."""
    from presto_tpu.execution import compile_cache
    from presto_tpu.runner.local import LocalRunner
    from presto_tpu.telemetry.metrics import METRICS
    r = LocalRunner("memory", "default", properties=dict(_NO_CACHES))
    r.execute("CREATE TABLE cc3 AS SELECT custkey ck3 "
              "FROM tpch.tiny.customer LIMIT 32")
    sql = "SELECT ck3 % 2, count(*) FROM cc3 GROUP BY ck3 % 2 " \
          "ORDER BY 1"
    r.execute(sql)
    compile_cache.clear_kernel_caches()
    before = METRICS.by_label("presto_tpu_kernel_retrace_total",
                              "reason")
    assert r.execute(sql).query_stats["kernel_compiles"] > 0
    delta = METRICS.delta_by_label(
        "presto_tpu_kernel_retrace_total", "reason", before)
    assert delta.get("new_kernel", 0) > 0
    assert delta.get("shape", 0) == 0, delta


def test_prewarm_failure_is_absorbed():
    from presto_tpu.runner.local import LocalRunner
    r = LocalRunner("memory", "default")
    report = r.prewarm(["SELECT definitely_broken FROM nowhere",
                        "SELECT 1"])
    assert report["statements"] == 2
    assert len(report["failed"]) == 1


def test_parse_prewarm_sql(tmp_path):
    from presto_tpu.execution.compile_cache import parse_prewarm_sql
    assert parse_prewarm_sql(None) == []
    assert parse_prewarm_sql("SELECT 1; SELECT 2") == [
        "SELECT 1", "SELECT 2"]
    f = tmp_path / "warmup.sql"
    f.write_text("-- dashboard mix\nSELECT 1;\n\nSELECT 2;\n")
    assert parse_prewarm_sql(f"@{f}") == ["SELECT 1", "SELECT 2"]


def test_prewarm_tables_compiles_generic_families():
    from presto_tpu.execution import compile_cache
    from presto_tpu.runner.local import LocalRunner
    r = LocalRunner("memory", "default")
    r.execute("CREATE TABLE pt1 AS SELECT custkey pk1 "
              "FROM tpch.tiny.customer LIMIT 8")
    warmed = compile_cache.prewarm_tables(r, "memory", "default")
    assert warmed >= 1


def test_coordinator_prewarm_surface():
    """Coordinator(prewarm_sql=...) replays the statements at start()
    and records the report."""
    from presto_tpu.server.coordinator import (
        Coordinator, StatementClient,
    )
    coord = Coordinator([], "tpch", "tiny", single_node=True,
                        prewarm_sql=["SELECT count(*) FROM nation"])
    coord.start()
    try:
        rep = coord.prewarm_report
        assert rep is not None and rep["failed"] == []
        c = StatementClient(coord.url, user="t")
        _, data = c.execute("SELECT count(*) FROM nation")
        assert data == [[25]]
    finally:
        coord.stop()
