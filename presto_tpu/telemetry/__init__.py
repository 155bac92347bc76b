"""Engine telemetry (reference: presto-main's OperatorStats /
TaskStats / QueryStats hierarchy, server/QueryResource, and the
/v1/jmx-style metrics surface, collapsed to three small modules):

  trace    — hierarchical spans (query -> stage -> task -> driver ->
             operator, plus exchange push/pop, cache get/put, and
             transport backoff sleeps) with a zero-overhead-when-
             disabled recorder, exported as Chrome ``trace_event`` JSON
             (GET /v1/query/{id}/trace, tools/trace_viewer.py)
  metrics  — process-wide Prometheus-text counters/gauges served on
             GET /v1/metrics by every node (coordinator and workers)
  kernels  — XLA compile-vs-execute attribution at the jit-kernel
             cache boundary: a kernel call that grew the jit cache was
             a COMPILE (cache-miss trace), anything else is dispatch/
             execute — credited to the operator whose add_input/
             get_output was running (see operators/driver.py); also
             the one place a device program is jitted and named after
             its kernel family (`kernels.jit`), which is what a
             jax.profiler device trace groups by
  stats    — plain-dict OperatorStats snapshots and the shared
             EXPLAIN ANALYZE / task-status renderer
  ledger   — the per-query wall-clock attribution ledger: a
             non-overlapping decomposition of wall into named
             categories with a machine-checked coverage invariant
             (Σ categories + unattributed == wall)
  flight   — the always-on fixed-size flight recorder: lifecycle
             events (sheds, retries, demotions, membership, compiles)
             in a per-process ring, snapshotted into error payloads
             and served on GET /v1/flight
  critical_path — blocking-chain extraction over a query's trace
             spans: which spans DETERMINED the wall, decomposed into
             the ledger's categories (EXPLAIN ANALYZE's "critical
             path" section, GET /v1/query/{id}, query_doctor)

Every hot-path hook is gated on a module-level bool (``trace.ACTIVE``,
``kernels.ENABLED``) exactly like execution/faults.ARMED, so disabled
telemetry costs one attribute load + branch per site."""

from presto_tpu.telemetry import (  # noqa: F401
    critical_path, flight, kernels, ledger, metrics, trace,
)
from presto_tpu.telemetry.stats import (  # noqa: F401
    build_query_stats, count_streamed_rows, render_operator_stats,
    snapshot_drivers,
)
