"""The client protocol timed from inside the coordinator
(presto_tpu_protocol_ns_total{phase}: accept, result_wait, encode),
where protocol_ms_per_query is client latency minus server wall."""

from __future__ import annotations


def protocol_ms_per_statement(run, phases) -> float | None:
    """ms per completed statement of the coordinator's own protocol
    clock (presto_tpu_protocol_ns_total{phase}); None where the
    program has no such counter."""
    keys = [f'presto_tpu_protocol_ns_total{{phase="{p}"}}'
            for p in phases]
    if not run.completed or not any(k in run.counters for k in keys):
        return None
    ns = sum(run.counters.get(k, 0.0) for k in keys)
    return ns / 1e6 / run.completed
