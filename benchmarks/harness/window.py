"""One run of one cell: warm-up, the measured window of closed-loop
clients, and the record the metric readers read."""

from __future__ import annotations

import contextlib
import dataclasses
import threading
import time

from benchmarks.harness import client as _client


@dataclasses.dataclass
class Statement:
    name: str
    client: int
    seq: int
    start_s: float          # from the window's start
    end_s: float
    ok: bool                # an answer came
    qid: str = ""
    columns: list | None = None
    data: list | None = None
    error: str = ""
    correct: bool = False   # set by the comparison
    stats: dict | None = None   # the server's stats tree, traced runs

    @property
    def latency_s(self) -> float:
        return self.end_s - self.start_s


@dataclasses.dataclass
class RunRecord:
    """What a metric reader may read. Times in seconds, host clock."""
    cell: str
    traffic: dict
    config: dict
    queries: dict                       # {statement: its queries/*.json}
    setup_s: float = 0.0
    window_s: float = 0.0
    statements: list = dataclasses.field(default_factory=list)
    ledger_ns: dict = dataclasses.field(default_factory=dict)
    counters: dict = dataclasses.field(default_factory=dict)
    page_cache: dict = dataclasses.field(default_factory=dict)
    memory_peak_bytes: int | None = None
    peaks: dict | None = None           # the device kind's row of peaks.json
    trace: dict | None = None           # trace_reduce.reduce's result

    @property
    def completed(self) -> int:
        return sum(s.correct for s in self.statements)

    def counter(self, name: str) -> float:
        """Growth over the window of a program counter, summed over
        its labels."""
        return sum(v for k, v in self.counters.items()
                   if k == name or k.startswith(name + "{"))


def _call(server, traffic, sql, user):
    return _client.execute(
        server, sql, user=user,
        timeout_s=float(traffic["statement_timeout_s"]),
        first_s=traffic["poll_first_ms"] / 1e3,
        cap_s=traffic["poll_cap_ms"] / 1e3)


def warm_up(server, traffic, sql_of, compiles_total, say):
    """Each statement of the cell, until one execution adds nothing to
    the program's compile counter (and at least `warmup_min_runs`
    times: the history-based optimizer re-plans after the first)."""
    for name in dict.fromkeys(traffic["statements"]):
        for run in range(1, traffic["warmup_max_runs"] + 1):
            before = compiles_total()
            t0 = time.perf_counter()
            _call(server, traffic, sql_of[name], "bench-warmup")
            added = compiles_total() - before
            say(f"warm-up {name} #{run}: {time.perf_counter() - t0:.3f} s,"
                f" {int(added)} compiles")
            if not added and run >= traffic["warmup_min_runs"]:
                break
        else:
            raise RuntimeError(
                f"{name} still compiles after "
                f"{traffic['warmup_max_runs']} executions")


class Tracer:
    """Starts the profiler before one of client 0's statements and
    stops it after another, so the traced span holds whole statements:
    `trace_min_statements` of them at least, and `trace_span_s`."""

    def __init__(self, log_dir: str, span_s: float, min_statements: int):
        self.log_dir = log_dir
        self.span_s = span_s
        self.min_statements = min_statements
        self.started_at = None
        self.done = False
        self.seen = 0

    def between_statements(self, closing: bool = False) -> None:
        import jax
        if self.done:
            return
        if self.started_at is None:
            if closing:
                self.done = True
                return
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.log_dir, profiler_options=opts)
            self.started_at = time.perf_counter()
            return
        self.seen += 1
        if closing or (
                self.seen >= self.min_statements and
                time.perf_counter() - self.started_at >= self.span_s):
            jax.profiler.stop_trace()
            self.done = True

    def mark(self, name: str, seq: int):
        import jax
        return jax.profiler.TraceAnnotation(f"bench:{name}#{seq}")


def run_window(server, traffic, sql_of, seed: int, seconds: float,
               tracer: Tracer | None):
    """Closed loop: each of `clients` threads sends its next statement
    when the last one's answer is in, and stops sending at `seconds`.
    Statements in flight then finish and count. Client i's k-th
    statement is statements[(i + seed + k) % len]: every seed sends the
    same set, in another order. Returns (statements, window_s)."""
    names = traffic["statements"]
    n_clients = int(traffic["clients"])
    out: list = []
    lock = threading.Lock()
    seq = iter(range(1 << 62))
    go = threading.Event()
    t0 = [0.0]

    def loop(i: int) -> None:
        go.wait()
        k = 0
        while True:
            if tracer is not None and i == 0:
                tracer.between_statements()
            start = time.perf_counter() - t0[0]
            if start >= seconds:
                break
            name = names[(i + seed + k) % len(names)]
            k += 1
            with lock:
                n = next(seq)
            st = Statement(name, i, n, start, start, False)
            mark = tracer.mark(name, n) if tracer is not None \
                else contextlib.nullcontext()
            try:
                with mark:
                    st.qid, st.columns, st.data = _call(
                        server, traffic, sql_of[name], f"bench-{i}")
                st.ok = True
            except Exception as e:  # noqa: BLE001 — a failed statement
                # is a result of the run, counted in `failed`
                st.error = f"{type(e).__name__}: {e}"
            st.end_s = time.perf_counter() - t0[0]
            with lock:
                out.append(st)
        if tracer is not None and i == 0:
            tracer.between_statements(closing=True)

    threads = [threading.Thread(target=loop, args=(i,), daemon=True,
                                name=f"bench-client-{i}")
               for i in range(n_clients)]
    for t in threads:
        t.start()
    t0[0] = time.perf_counter()
    go.set()
    for t in threads:
        t.join()
    out.sort(key=lambda s: s.seq)
    window_s = max((s.end_s for s in out), default=0.0)
    return out, window_s
