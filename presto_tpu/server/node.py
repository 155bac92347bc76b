"""Worker/coordinator HTTP node: task RPC + exchange data plane
(reference: server/TaskResource.java:93 task create/update + results
long-poll, server/remotetask/HttpRemoteTask.java:128 on the caller
side, AsyncPageTransportServlet.java:68 for the page hot path).

Design notes for the TPU deployment shape:
  - one worker process per HOST; the chips inside a host/slice stay on
    the MeshRunner's ICI collectives. THIS tier is the DCN fallback:
    batches that must cross processes travel as compacted npz pages
    over HTTP, pushed to the consuming node (the reference pulls;
    push keeps the skeleton free of result-token state)
  - plans are not serialized: a task spec carries the original SQL +
    session and the worker re-derives the (deterministic) fragment
    plan, executing only its fragment — the presto-on-spark trick of
    shipping work by description, not by object graph
"""

from __future__ import annotations

import collections
import json
import random
import threading
import time
import traceback
import urllib.error
import urllib.parse
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Dict, List, Optional, Tuple

from presto_tpu import sanitize
from presto_tpu.batch import Batch
from presto_tpu.execution import faults
from presto_tpu.operators.exchange_ops import edge_key_dicts
from presto_tpu.server.serde import batch_from_bytes, batch_to_bytes
from presto_tpu.telemetry import flight as _flight
from presto_tpu.telemetry import ledger as _ledger
from presto_tpu.telemetry import trace as _trace
from presto_tpu.telemetry.metrics import METRICS

#: transport retry budget for the exchange data plane and task RPCs —
#: the tier BELOW elastic whole-query retry (reference: Trino's
#: fault-tolerant exchange, "Project Tardigrade"): a transient network
#: blip is absorbed here with backoff, so the expensive re-run tier
#: only sees real node loss
TRANSPORT_RETRIES = 4
_BACKOFF_BASE_S = 0.05
_BACKOFF_CAP_S = 1.0


def _retry_transient(fn, retries: int, base_s: float = _BACKOFF_BASE_S,
                     cap_s: float = _BACKOFF_CAP_S):
    """Run `fn` with bounded exponential backoff + jitter on
    TRANSPORT-level failures (refused/reset/timeout). HTTP error
    RESPONSES (4xx/5xx) are application errors — the server spoke, it
    said no — and are never retried here."""
    attempt = 0
    while True:
        try:
            return fn()
        except urllib.error.HTTPError:
            raise
        except (urllib.error.URLError, ConnectionError, TimeoutError,
                OSError):
            attempt += 1
            if attempt > retries:
                raise
            delay = min(base_s * (2 ** (attempt - 1)), cap_s)
            # jitter keeps a fleet of retriers from re-colliding
            sleep_s = delay * (0.5 + random.random() * 0.5)
            METRICS.inc("presto_tpu_transport_retries_total")
            METRICS.inc("presto_tpu_backoff_sleep_ns_total",
                        sleep_s * 1e9)
            # the backoff sleep is its own ledger category (a leaf:
            # the enclosing exchange/dispatch span must not absorb
            # it), and every transport retry leaves a flight event
            _ledger.add("retry_backoff", int(sleep_s * 1e9))
            if _flight.ENABLED:
                _flight.record("retry", "transport", attempt)
            if _trace.ACTIVE:
                # retry/backoff sleeps show up as spans in a traced
                # query's timeline (the faults tier's visible cost)
                with _trace.span("transport.backoff", "retry",
                                 attempt=attempt):
                    time.sleep(sleep_s)
            else:
                time.sleep(sleep_s)


def http_post(url: str, body: bytes, timeout: float = 60.0,
              headers: Optional[dict] = None,
              retries: int = 0) -> bytes:
    def send():
        req = urllib.request.Request(url, data=body, method="POST")
        for k, v in (headers or {}).items():
            req.add_header(k, v)
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.read()
    return _retry_transient(send, retries) if retries else send()


def http_get(url: str, timeout: float = 60.0,
             retries: int = 0) -> bytes:
    def send():
        with urllib.request.urlopen(url, timeout=timeout) as r:
            return r.read()
    return _retry_transient(send, retries) if retries else send()


def http_delete(url: str, timeout: float = 60.0,
                retries: int = 0) -> bytes:
    def send():
        req = urllib.request.Request(url, method="DELETE")
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.read()
    return _retry_transient(send, retries) if retries else send()


class ExchangeRegistry:
    """Incoming side of every exchange this node consumes: queues per
    (exchange_key, consumer_task) plus end-of-stream accounting.
    Exchange keys are "<query_id>:<exchange_id>" — plain exchange ids
    restart at 0 for every query, and the registry outlives queries."""

    _RELEASED_MAX = 4096

    def __init__(self):
        self._lock = sanitize.lock("exchange.registry")
        sanitize.track("exchange_registry", self)
        self._queues: Dict[Tuple[str, int], collections.deque] = \
            collections.defaultdict(collections.deque)
        self._eos: Dict[Tuple[str, int], set] = \
            collections.defaultdict(set)
        self._expected: Dict[str, int] = {}
        # query ids whose state was dropped: straggler pages from their
        # surviving producers are discarded instead of re-creating
        # entries no one will ever pop (bounded FIFO)
        self._released: "collections.OrderedDict[str, None]" = \
            collections.OrderedDict()
        #: highest sequence number accepted per (exchange key,
        #: consumer, producer) — a producer retries a timed-out push
        #: with the SAME seq, so a push that actually landed before
        #: its response was lost is dropped here instead of
        #: double-delivering (at-least-once transport + dedup =
        #: exactly-once delivery)
        self._last_seq: Dict[Tuple[str, int, int], int] = {}

    def _is_released(self, key: str) -> bool:
        # fault-tolerant task attempts namespace their exchange keys
        # as "<query_id>.<fragment>.<slot>.<attempt>:<xid>" — releasing
        # the base query id must cover every attempt namespace, and a
        # released single attempt must not shadow its siblings
        qpart = key.split(":", 1)[0]
        return qpart in self._released \
            or qpart.split(".", 1)[0] in self._released

    def expect_producers(self, key: str, count: int) -> None:
        with self._lock:
            self._expected[key] = count

    def receive(self, key: str, consumer: int, payload: bytes,
                producer: Optional[int] = None,
                seq: Optional[int] = None) -> None:
        with self._lock:
            if self._is_released(key):
                return
            if producer is not None and seq is not None:
                sk = (key, consumer, producer)
                if self._last_seq.get(sk, -1) >= seq:
                    return  # duplicate delivery of a retried push
                # pushes per (producer, consumer) are sequential (one
                # drive thread per producer task), so marking before
                # decode cannot skip a gap
                self._last_seq[sk] = seq
        METRICS.inc("presto_tpu_exchange_pages_total",
                    direction="recv")
        METRICS.inc("presto_tpu_exchange_bytes_total", len(payload),
                    direction="recv")
        batch = batch_from_bytes(payload)
        with self._lock:
            if not self._is_released(key):
                self._queues[(key, consumer)].append(batch)

    def receive_eos(self, key: str, consumer: int,
                    producer: int) -> None:
        with self._lock:
            if not self._is_released(key):
                self._eos[(key, consumer)].add(producer)

    def receive_local(self, key: str, consumer: int,
                      batch: Batch) -> None:
        """Same-process delivery: enqueue the batch object directly —
        no serde, no HTTP, no copy (the self-delivery short circuit)."""
        with self._lock:
            if not self._is_released(key):
                self._queues[(key, consumer)].append(batch)

    def pop(self, key: str, consumer: int) -> Optional[Batch]:
        if faults.ARMED:
            faults.fire("exchange.pop", key=key, consumer=consumer)
        with self._lock:
            q = self._queues[(key, consumer)]
            batch = q.popleft() if q else None
        if batch is not None:
            METRICS.inc("presto_tpu_exchange_pages_total",
                        direction="pop")
            if _trace.ACTIVE and _trace.current() is not None:
                _trace.current().instant("exchange.pop", "exchange",
                                         {"key": key,
                                          "consumer": consumer})
        return batch

    def has_output(self, key: str, consumer: int) -> bool:
        with self._lock:
            return bool(self._queues[(key, consumer)])

    def finished(self, key: str, consumer: int) -> bool:
        with self._lock:
            done = len(self._eos[(key, consumer)]) \
                >= self._expected.get(key, 1 << 30)
            return done and not self._queues[(key, consumer)]

    def drop_query(self, query_id: str) -> None:
        """Release every queue/eos/expectation of a finished or failed
        query (keys are "<query_id>:<exchange_id>", plus the
        fault-tolerant attempt namespaces "<query_id>.<task>…:<xid>")
        and remember the id so straggler pages still in flight are
        discarded on arrival."""
        prefixes = (f"{query_id}:", f"{query_id}.")
        with self._lock:
            self._released[query_id] = None
            while len(self._released) > self._RELEASED_MAX:
                self._released.popitem(last=False)
            for d in (self._queues, self._eos):
                for k in [k for k in d
                          if k[0].startswith(prefixes)]:
                    del d[k]
            for k in [k for k in self._expected
                      if k.startswith(prefixes)]:
                del self._expected[k]
            for k in [k for k in self._last_seq
                      if k[0].startswith(prefixes)]:
                del self._last_seq[k]


def _host_segment(host: Batch, lo: int, hi: int) -> Batch:
    """Numpy slice [lo, hi) of a host-side batch whose live rows are a
    prefix-packed run, padded up to the power-of-two capacity bucket
    (downstream jitted operators keep their small compiled-shape set)."""
    import numpy as np

    from presto_tpu.batch import Column, bucket_capacity
    n = hi - lo
    cap = bucket_capacity(max(n, 1))
    cols = {}
    for name, c in host.columns.items():
        d = np.zeros(cap, dtype=np.asarray(c.data).dtype)
        m = np.zeros(cap, dtype=bool)
        d[:n] = np.asarray(c.data)[lo:hi]
        m[:n] = np.asarray(c.mask)[lo:hi]
        cols[name] = Column(d, m, c.type, c.dictionary)
    rv = np.zeros(cap, dtype=bool)
    rv[:n] = np.asarray(host.row_valid)[lo:hi]
    return Batch(cols, rv)


class HttpExchange:
    """MeshExchange-compatible facade over the DCN data plane: pushes
    route batches to consumer NODES over HTTP; pops read this node's
    registry queues (filled by the HTTP handler thread).

    Cost discipline (the round-3 lesson): a hash repartition is ONE
    jitted dispatch (destination-sorted batch + segment bounds), ONE
    device->host transfer, then host-side numpy slices per consumer —
    not O(consumers) mask/compact/serialize rounds. Consumers that live
    in THIS process (self_url match) receive the batch object through
    the registry directly: no serde, no localhost HTTP — which also
    collapses a mesh-per-worker node's intra-node shuffle legs."""

    def __init__(self, exchange_key: str, scheme: str,
                 partition_keys, hash_dicts, key_dictionaries,
                 consumer_urls: List[str], n_producers: int,
                 registry: ExchangeRegistry,
                 self_url: Optional[str] = None,
                 spool_to: Optional[dict] = None,
                 canonical_key: Optional[str] = None):
        from presto_tpu.operators.exchange_ops import build_remap_tables
        self.exchange_id = exchange_key
        self.scheme = scheme
        self.partition_keys = list(partition_keys)
        self.consumer_urls = consumer_urls
        self.n_consumers = len(consumer_urls)
        self.registry = registry
        self.self_url = self_url
        #: fault-tolerant mode (server/scheduler.py): pushes go to the
        #: coordinator's TaskOutputSpool — {"url", "task", "attempt"}
        #: — tagged so a failed attempt's pages are discardable and a
        #: committed task's pages are replayable to any worker. The
        #: spool is addressed by the CANONICAL exchange key while pops
        #: keep the task attempt's private key namespace.
        self.spool_to = spool_to
        self.canonical_key = canonical_key or exchange_key
        registry.expect_producers(exchange_key, n_producers)
        self._rr = 0
        self._remaps = build_remap_tables(hash_dicts, key_dictionaries)
        #: outgoing page sequence per (producer, consumer): rides the
        #: push URL so a retried POST is deduplicated by the receiver
        #: (pushes per pair are sequential — one drive thread per
        #: producer task)
        self._seq: Dict[Tuple[int, int], int] = {}

    # -- producer side (outgoing HTTP) -------------------------------------

    def _is_local(self, consumer: int) -> bool:
        # a spooling producer NEVER short-circuits locally: its pages
        # must land in the durable spool (tagged by task attempt), not
        # in this process's live queues — even when the coordinator
        # itself runs the producing fragment
        if self.spool_to is not None:
            return False
        return self.self_url is not None \
            and self.consumer_urls[consumer] == self.self_url

    def _post(self, consumer: int, payload: bytes,
              producer: int) -> None:
        """One page push: sequence-numbered, retried with backoff.
        The fault sites sit INSIDE the retry loop so an injected
        "before" fault models a page that never left (the retry
        delivers it) and an "after" fault models a page that landed
        with its response lost (the retry re-sends; the receiver's
        seq dedup drops the duplicate)."""
        sk = (producer, consumer)
        seq = self._seq.get(sk, -1) + 1
        self._seq[sk] = seq
        if self.spool_to is not None:
            store = self.spool_to.get("store")
            if store is not None:
                # the spool lives in THIS process (coordinator-run
                # fragments): put directly — durability is the spool
                # object, the loopback HTTP hop + re-parse buys
                # nothing (the self-delivery lesson, applied to the
                # durable tier)
                METRICS.inc("presto_tpu_exchange_pages_total",
                            direction="push")
                METRICS.inc("presto_tpu_exchange_bytes_total",
                            len(payload), direction="push")
                store.put(self.canonical_key, consumer,
                          self.spool_to["task"],
                          self.spool_to["attempt"], producer, seq,
                          payload)
                return
            url = (f"{self.spool_to['url']}/v1/spool/"
                   f"{self.canonical_key}/{consumer}"
                   f"?task={self.spool_to['task']}"
                   f"&attempt={self.spool_to['attempt']}"
                   f"&producer={producer}&seq={seq}")
        else:
            url = (f"{self.consumer_urls[consumer]}/v1/exchange/"
                   f"{self.exchange_id}/{consumer}"
                   f"?producer={producer}&seq={seq}")

        def send():
            if faults.ARMED:
                faults.fire("exchange.push", phase="before", url=url,
                            seq=seq)
            http_post(url, payload)
            if faults.ARMED:
                faults.fire("exchange.push", phase="after", url=url,
                            seq=seq)
        METRICS.inc("presto_tpu_exchange_pages_total",
                    direction="push")
        METRICS.inc("presto_tpu_exchange_bytes_total", len(payload),
                    direction="push")
        # ledger: the push's transport wall is `exchange` — backoff
        # sleeps inside the retry loop subtract into retry_backoff
        if _trace.ACTIVE and _trace.current() is not None:
            with _trace.span("exchange.push", "exchange",
                             consumer=consumer, bytes=len(payload)):
                with _ledger.span("exchange"):
                    _retry_transient(send, TRANSPORT_RETRIES)
        else:
            with _ledger.span("exchange"):
                _retry_transient(send, TRANSPORT_RETRIES)

    def _deliver_whole(self, consumers: List[int], batch: Batch,
                       producer: int) -> None:
        """Route one un-split batch to each listed consumer: local ones
        share the compacted host batch, remote ones share ONE
        serialization."""
        import jax

        from presto_tpu.batch import bucket_capacity
        local = [c for c in consumers if self._is_local(c)]
        remote = [c for c in consumers if not self._is_local(c)]
        if local:
            n = batch.num_valid()
            with _ledger.span("d2h"):
                host = jax.device_get(
                    batch.compact(bucket_capacity(max(n, 1)),
                                  known_valid=n))
            from presto_tpu.execution.memory import batch_bytes
            METRICS.inc("presto_tpu_transfer_bytes_total",
                        batch_bytes(host), direction="d2h")
            for c in local:
                # local short-circuit deliveries still count as pages
                # (else pop > push + recv and the direction label is
                # unusable for in-flight math)
                METRICS.inc("presto_tpu_exchange_pages_total",
                            direction="local")
                self.registry.receive_local(self.exchange_id, c, host)
            if remote:
                payload = batch_to_bytes(host, assume_compact=True)
        elif remote:
            payload = batch_to_bytes(batch)
        for c in remote:
            self._post(c, payload, producer)

    def push(self, producer: int, batch: Batch) -> None:
        if self.scheme == "gather":
            self._deliver_whole([0], batch, producer)
        elif self.scheme == "broadcast":
            self._deliver_whole(list(range(self.n_consumers)), batch,
                                producer)
        elif self.scheme == "passthrough":
            self._deliver_whole([producer], batch, producer)
        elif self.scheme == "repartition" and not self.partition_keys:
            c = self._rr % self.n_consumers
            self._rr += 1
            self._deliver_whole([c], batch, producer)
        else:
            import jax

            from presto_tpu.operators.exchange_ops import (
                partition_segments,
            )
            dev_sorted, bounds = partition_segments(
                batch, tuple(self.partition_keys), self._remaps,
                self.n_consumers)
            with _ledger.span("d2h"):
                host, hbounds = jax.device_get((dev_sorted, bounds))
            from presto_tpu.execution.memory import batch_bytes
            METRICS.inc("presto_tpu_transfer_bytes_total",
                        batch_bytes(host), direction="d2h")
            for c in range(self.n_consumers):
                lo, hi = int(hbounds[c]), int(hbounds[c + 1])
                if lo == hi:
                    continue  # nothing for this consumer
                seg = _host_segment(host, lo, hi)
                if self._is_local(c):
                    METRICS.inc("presto_tpu_exchange_pages_total",
                                direction="local")
                    self.registry.receive_local(self.exchange_id, c, seg)
                else:
                    self._post(c, batch_to_bytes(seg,
                                                 assume_compact=True),
                               producer)

    def producer_done(self, producer: int) -> None:
        if self.spool_to is not None:
            # spooled streams complete by TASK COMMIT (the scheduler
            # observes the finished status and commits the attempt's
            # pages atomically); replay synthesizes consumer-side eos
            # for every producer slot, so no eos travels here
            return
        # eos is naturally idempotent (producer-set union), so the
        # retried POST needs no sequence number
        for c in range(self.n_consumers):
            if self._is_local(c):
                self.registry.receive_eos(self.exchange_id, c, producer)
                continue
            http_post(
                f"{self.consumer_urls[c]}/v1/exchange/"
                f"{self.exchange_id}/{c}/eos?producer={producer}",
                b"", retries=TRANSPORT_RETRIES)

    # -- consumer side (local registry) ------------------------------------

    def pop(self, consumer: int) -> Optional[Batch]:
        return self.registry.pop(self.exchange_id, consumer)

    def has_output(self, consumer: int) -> bool:
        return self.registry.has_output(self.exchange_id, consumer)

    def finished(self, consumer: int) -> bool:
        return self.registry.finished(self.exchange_id, consumer)


class TaskState:
    def __init__(self):
        self.state = "running"
        self.error: Optional[str] = None
        #: distributed tracing (spec["trace"]): the live recorder of a
        #: running traced task (GET /v1/task/{id}/trace drains it) and
        #: the final undrained spans shipped with terminal status —
        #: attached BEFORE the state flips so a poll that observes
        #: "finished"/"failed" always sees the spans too
        self.trace_recorder = None
        self.trace: Optional[list] = None
        #: {"wall_s", "pipelines": per-operator snapshot dicts} of the
        #: finished task — shipped in the /v1/task/{tid} status
        #: response so the coordinator can roll TaskStats into
        #: QueryStats
        self.stats: Optional[dict] = None
        #: structured retry protocol: the engine's sync-free overflow
        #: errors (join capacity / group limit) are not failures — the
        #: COORDINATOR must re-run the whole query with the suggested
        #: setting, so they travel as (kind, suggested) over the status
        #: RPC instead of opaque text
        self.error_kind: Optional[str] = None
        self.suggested: Optional[int] = None
        self.cancel = threading.Event()
        self.done_at: Optional[float] = None  # set at terminal state


class NodeHandler(BaseHTTPRequestHandler):
    node: "Node" = None  # bound by serve()

    def log_message(self, fmt, *args):  # quiet
        pass

    def _reply(self, code: int, body: bytes = b"",
               ctype: str = "application/json") -> None:
        self.send_response(code)
        self.send_header("Content-Type", ctype)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def _read_body(self) -> bytes:
        n = int(self.headers.get("Content-Length", "0"))
        return self.rfile.read(n)

    def do_GET(self):
        try:
            body = self.node.handle_get(self.path)
        except KeyError:
            self._reply(404, b'{"error": "not found"}')
            return
        except Exception as e:  # noqa: BLE001 — surface to caller
            self._reply(500, json.dumps(
                {"error": f"{type(e).__name__}: {e}",
                 "trace": traceback.format_exc(limit=5)}).encode())
            return
        ctype = "text/html" if self.path.startswith("/ui") \
            else "text/plain; version=0.0.4" \
            if self.path == "/v1/metrics" else "application/json"
        self._reply(200, body, ctype)

    def do_POST(self):
        try:
            body = self.node.handle_post(self.path, self._read_body(),
                                         dict(self.headers))
            self._reply(200, body)
        except Exception as e:  # noqa: BLE001 — surface to caller
            self._reply(500, json.dumps(
                {"error": f"{type(e).__name__}: {e}",
                 "trace": traceback.format_exc(limit=5)}).encode())

    def do_DELETE(self):
        try:
            body = self.node.handle_delete(self.path)
        except KeyError:
            self._reply(404, b'{"error": "not found"}')
            return
        except Exception as e:  # noqa: BLE001 — surface to caller
            self._reply(500, json.dumps(
                {"error": f"{type(e).__name__}: {e}",
                 "trace": traceback.format_exc(limit=5)}).encode())
            return
        self._reply(200, body)


class Node:
    """Shared HTTP node: exchange receipt + task RPC. The coordinator
    subclass adds the client protocol.

    `n_devices` > 1 turns the worker into a MESH-PER-WORKER node (the
    reference's one-worker-per-host shape mapped to TPU: one process
    per host/slice, the chips inside it device-parallel): each
    dispatched fragment task expands into one subtask per local device
    and the exchange consumer space is GLOBAL over
    sum(worker devices) — DCN pages route straight to (worker, device)
    by key hash, ICI-local work stays on its chip (reference seam:
    presto-spark's scheduling-outside/operators-inside split,
    PrestoSparkTaskExecutorFactory.java:121)."""

    def __init__(self, host: str = "127.0.0.1", port: int = 0,
                 n_devices: int = 1):
        self.registry = ExchangeRegistry()
        self.n_devices = max(1, int(n_devices))
        self.tasks: Dict[str, TaskState] = {}
        #: compile_cache.prewarm report of the last /v1/prewarm replay
        #: (the distributed prewarm path), served on /v1/info
        self.prewarm_report: Optional[dict] = None
        self._prewarm_lock = sanitize.lock("node.prewarm")
        handler = type("BoundHandler", (NodeHandler,), {"node": self})

        class _Server(ThreadingHTTPServer):
            # socketserver's default listen backlog is 5: at 64+
            # concurrent clients the SYN queue overflows and the
            # kernel RESETS connections — the exact collapse mode the
            # overload story exists to prevent. Admission control is
            # the real gate; the listener must be deep enough that
            # every client REACHES it
            request_queue_size = 1024
            daemon_threads = True
        self.httpd = _Server((host, port), handler)
        self.port = self.httpd.server_address[1]
        self.url = f"http://{host}:{self.port}"
        self._stopped = False
        # weakref-bound stop signal: the closure must not pin the node
        # (the leak auditor's owner-collected check needs the owner
        # collectable)
        import weakref
        self._thread = sanitize.thread(
            target=self.httpd.serve_forever, daemon=True,
            owner=self,
            stop_signal=lambda ref=weakref.ref(self):
                ref() is not None and ref()._stopped,
            purpose="http-server")

    def start(self) -> None:
        self._thread.start()

    def stop(self) -> None:
        # shutdown() blocks until serve_forever exits; joining the
        # thread afterwards is the leak-auditor contract (a stopped
        # node must leave no live thread behind)
        self.httpd.shutdown()
        self._stopped = True
        if self._thread.is_alive():
            self._thread.join(timeout=5)

    # -- routing -----------------------------------------------------------

    def handle_get(self, path: str) -> bytes:
        if path == "/v1/info":
            info = {"state": "active", "devices": self.n_devices,
                    # clock handshake for fleet trace merge: the
                    # caller samples its own clock around this GET and
                    # estimates offset = midpoint - clock_ns (best
                    # estimate rides the smallest-RTT heartbeat probe)
                    "clock_ns": time.perf_counter_ns(),
                    # load feedback for the heartbeat tier: the
                    # scheduler prefers lightly-loaded members and the
                    # fleet memory enforcer gates dispatch on the
                    # reported reservations
                    "load": self._load_report(),
                    "memory": {"reserved_bytes":
                               self._memory_reserved()}}
            if self.prewarm_report is not None:
                # per-worker prewarm compile counts (the distributed
                # prewarm satellite): /v1/prewarm stores the report,
                # /v1/info serves it so the coordinator and benches
                # can prove workers start warm
                info["prewarm"] = self.prewarm_report
            if faults.ARMED:
                # observability for env-armed subprocess workers:
                # chaos tests assert the fault FIRED, not just that
                # the query survived (a never-firing test is vacuous)
                info["faults"] = faults.counters()
            return json.dumps(info).encode()
        if path == "/v1/metrics":
            # Prometheus text scrape surface: every node — worker or
            # coordinator — serves its own process counters + live
            # cache/memory gauges (telemetry/metrics.py)
            from presto_tpu.telemetry.metrics import render_prometheus
            return render_prometheus().encode()
        if path == "/v1/tasks":
            # observability + test support (reference: /v1/task listing)
            return json.dumps({
                tid: {"state": t.state, "error": t.error}
                for tid, t in list(self.tasks.items())}).encode()
        if path == "/v1/flight":
            # the always-on flight recorder's live ring — the
            # no-one-pre-armed-anything post-mortem surface
            return json.dumps({
                **_flight.stats(),
                "events": _flight.snapshot_dicts(),
            }).encode()
        if path.startswith("/v1/task/") and path.endswith("/trace"):
            # span drain for LONG tasks: returns the spans buffered so
            # far and removes them from the recorder — the terminal
            # status ships only what was never drained
            tid = path.split("/")[3]
            t = self.tasks[tid]
            rec = t.trace_recorder
            events = rec.drain() if rec is not None else []
            return json.dumps({"taskId": tid,
                               "traceEvents": events}).encode()
        if path.startswith("/v1/task/"):
            tid = path.rsplit("/", 1)[1]
            t = self.tasks[tid]
            return json.dumps({"state": t.state, "error": t.error,
                               "error_kind": t.error_kind,
                               "suggested": t.suggested,
                               "stats": t.stats,
                               "trace": t.trace}).encode()
        raise KeyError(path)

    def handle_post(self, path: str, body: bytes,
                    headers: Optional[dict] = None) -> bytes:
        if path.startswith("/v1/exchange/"):
            rest = path[len("/v1/exchange/"):]
            params: Dict[str, str] = {}
            if "?" in rest:
                rest, qs = rest.split("?", 1)
                params = dict(urllib.parse.parse_qsl(qs))
            if rest.endswith("/eos"):
                xid_s, consumer_s = rest[:-len("/eos")].rsplit("/", 1)
                self.registry.receive_eos(xid_s, int(consumer_s),
                                          int(params["producer"]))
                return b"{}"
            xid_s, consumer_s = rest.rsplit("/", 1)
            producer = params.get("producer")
            seq = params.get("seq")
            self.registry.receive(
                xid_s, int(consumer_s), body,
                producer=int(producer) if producer is not None else None,
                seq=int(seq) if seq is not None else None)
            return b"{}"
        if path == "/v1/task":
            spec = json.loads(body.decode())
            self.create_task(spec)
            return json.dumps({"taskId": spec["task_id"]}).encode()
        if path == "/v1/prewarm":
            # distributed AOT prewarm (closes the "workers start
            # cold" gap): the coordinator forwards its prewarm_sql
            # here at start; this node replays it through a local
            # runner so ITS kernel caches are warm before traffic.
            # Serialized under a lock — two coordinators prewarming
            # one worker must not interleave reports
            spec = json.loads(body.decode()) if body else {}
            with self._prewarm_lock:
                report = self._prewarm(spec)
            return json.dumps(report).encode()
        if path.startswith("/v1/query/") and path.endswith("/release"):
            # end-of-query resource release (reference: TaskResource
            # DELETE /v1/task/{taskId}): abort the query's tasks and
            # drop its exchange state
            qid = path.split("/")[3]
            self.release_query(qid)
            return b"{}"
        raise KeyError(path)

    def handle_delete(self, path: str) -> bytes:
        if path.startswith("/v1/task/"):
            # task abort (reference: TaskResource DELETE
            # /v1/task/{taskId}): set the cancel flag the drive loop
            # polls each round. Idempotent — a second DELETE, or one
            # racing natural completion, just reports the state
            tid = path.rsplit("/", 1)[1]
            t = self.tasks[tid]
            t.cancel.set()
            return json.dumps({"taskId": tid,
                               "state": t.state}).encode()
        raise KeyError(path)

    def _load_report(self) -> dict:
        """Live load gauges for the heartbeat: running tasks on this
        node plus the shared executor's queue depth (when one exists
        in this process) — the scheduler's placement feedback."""
        out = {"tasks_running": sum(
            1 for t in list(self.tasks.values())
            if t.state == "running")}
        try:
            from presto_tpu.execution.task_executor import (
                get_task_executor,
            )
            ex = get_task_executor(create=False)
            if ex is not None:
                snap = ex.snapshot()
                out["executor_running"] = snap["running_drivers"]
                out["executor_queued"] = sum(snap["queued_drivers"])
        except Exception:  # noqa: BLE001 — load report is best-effort
            pass
        return out

    def _memory_reserved(self) -> int:
        """Total reserved bytes across this process's tracked memory
        pools (per-query pools + the cache pool) — the heartbeat's
        fleet-memory report."""
        total = 0
        for pool in sanitize.tracked("memory_pool"):
            try:
                total += int(pool.reserved)
            except Exception:  # noqa: BLE001 — a dying pool mid-sweep
                pass
        return total

    def _prewarm(self, spec: dict) -> dict:
        from presto_tpu.execution import compile_cache
        from presto_tpu.runner.local import LocalRunner
        statements = list(spec.get("statements") or [])
        runner = LocalRunner(spec.get("catalog", "tpch"),
                             spec.get("schema", "tiny"),
                             dict(spec.get("properties") or {}))
        self.prewarm_report = compile_cache.prewarm(runner, statements)
        return self.prewarm_report

    # -- task execution ----------------------------------------------------

    def create_task(self, spec: dict) -> None:
        self._prune_tasks()
        tid = spec["task_id"]
        state = TaskState()
        # idempotent create: a dispatch POST whose response was lost
        # gets retried by the coordinator — the task must not run
        # twice (reference: TaskResource's create-or-update).
        # setdefault is atomic under the GIL, so concurrent retries
        # can't both win
        if self.tasks.setdefault(tid, state) is not state:
            return
        sanitize.thread(target=self._run_task, args=(spec, state),
                        daemon=True, purpose="fragment-task").start()

    def _prune_tasks(self, ttl_s: float = 600.0) -> None:
        """Evict tasks `ttl_s` after they reached a terminal state (the
        clock starts at completion, not creation — a finished task of a
        still-running query must stay observable by the coordinator's
        watcher). pop() keeps concurrent handler threads from
        double-deleting."""
        now = time.monotonic()
        for tid in [tid for tid, t in list(self.tasks.items())
                    if t.done_at is not None
                    and now - t.done_at > ttl_s]:
            self.tasks.pop(tid, None)

    def release_query(self, query_id: str) -> None:
        for tid, t in list(self.tasks.items()):
            if tid.startswith(f"{query_id}."):
                t.cancel.set()
        self.registry.drop_query(query_id)

    def _run_task(self, spec: dict, state: TaskState) -> None:
        # distributed tracing: a traced task records its OWN spans
        # (driver/operator/kernel/exchange — the executor re-installs
        # this recorder per quantum) and ships them with terminal
        # status; the coordinator merges them into the query timeline
        # with this node's clock offset applied. The trace context
        # (query id + parent span + attempt) rides the spec.
        rec = prev_rec = None
        ctx = spec.get("trace_ctx") or {}
        if spec.get("trace"):
            rec = _trace.TraceRecorder(ctx.get("query_id", ""))
            state.trace_recorder = rec
            prev_rec = _trace.activate(rec)
        t0_ns = time.perf_counter_ns()

        def _close_trace(failed: bool) -> None:
            if rec is None:
                return
            rec.add("task", "task", t0_ns,
                    time.perf_counter_ns() - t0_ns,
                    {"task": spec.get("task_id", ""),
                     "attempt": ctx.get("attempt"),
                     "parent": ctx.get("parent_span"),
                     "failed": failed})
            state.trace = rec.drain()
        try:
            stats = self.execute_fragment(spec, state.cancel)
            _close_trace(False)
            state.stats = stats
            state.state = "finished"
        except Exception as e:  # noqa: BLE001
            _close_trace(True)
            if state.cancel.is_set():
                state.state = "aborted"
            else:
                from presto_tpu.operators.aggregation import (
                    GroupLimitExceeded,
                )
                from presto_tpu.operators.join_ops import (
                    JoinCapacityExceeded,
                )
                if isinstance(e, JoinCapacityExceeded):
                    state.error_kind = "join_capacity"
                    state.suggested = e.suggested
                elif isinstance(e, GroupLimitExceeded):
                    state.error_kind = "group_limit"
                    state.suggested = e.suggested
                state.state = "failed"
                state.error = f"{type(e).__name__}: {e}\n" \
                              f"{traceback.format_exc(limit=8)}"
        finally:
            if rec is not None:
                _trace.deactivate(prev_rec)
            state.done_at = time.monotonic()

    def execute_fragment(self, spec: dict,
                         cancel: Optional[threading.Event] = None
                         ) -> dict:
        """Re-derive the fragment plan from SQL (deterministic) and run
        this node's task(s) of fragment `fragment_id` — one subtask per
        local device when the spec carries `local_count` > 1 (mesh-per-
        worker), all driven in one round-robin loop. Returns
        {"wall_s", "pipelines": per-operator snapshot dicts} — the
        TaskStats the coordinator rolls into QueryStats;
        `spec["profile"]` adds device row counters + device-inclusive
        timing, the distributed EXPLAIN ANALYZE mode."""
        # the task spec carries the statement's full session
        # properties; the kernel shape-bucket gate rides a THREAD-
        # LOCAL that LocalRunner.execute normally sets — this task
        # thread drives pipelines directly, so set it here or remote
        # tasks silently follow the process default instead of the
        # statement's kernel_shape_buckets (the PR 6 gap)
        from presto_tpu import batch as _batch
        from presto_tpu.session_properties import get_property
        prev_sb = _batch.set_shape_buckets(
            bool(get_property(spec["session"]["properties"],
                              "kernel_shape_buckets")))
        try:
            return self._execute_fragment_inner(spec, cancel)
        finally:
            _batch.set_shape_buckets(prev_sb)

    def _execute_fragment_inner(self, spec: dict,
                                cancel: Optional[threading.Event]
                                ) -> dict:
        from presto_tpu.planner.local_planner import (
            LocalExecutionPlanner, TaskContext,
        )
        from presto_tpu.runner.local import LocalRunner
        runner = LocalRunner(spec["session"]["catalog"],
                             spec["session"]["schema"],
                             spec["session"]["properties"])
        fplan = derive_fragments(runner, spec["sql"])
        fid = spec["fragment_id"]
        fragment = fplan.fragments[fid]
        exchanges = build_http_exchanges(
            spec["query_id"], fplan,
            spec.get("consumer_urls_by_edge"), spec["worker_urls"],
            spec["coordinator_url"], self.registry,
            n_producers_by_edge=spec.get("n_producers_by_edge"),
            self_url=self.url,
            # fault-tolerant task specs (server/scheduler.py) carry a
            # private key namespace per attempt and spool their output
            # pages at the coordinator instead of streaming downstream
            key_ns=spec.get("exchange_ns"),
            spool=spec.get("spool"))
        k = int(spec.get("local_count", 1))
        base = int(spec.get("local_base", spec.get("task_index", 0)))
        devices = [None] * k
        if k > 1:
            import jax
            devs = jax.devices()
            if len(devs) < k:
                raise RuntimeError(
                    f"task wants {k} local devices, node has "
                    f"{len(devs)}")
            devices = list(devs[:k])
        pipelines = []
        sinks_edges = fplan.producer_edges(fid)
        for local in range(k):
            task = TaskContext(index=base + local,
                               count=spec["n_tasks"],
                               device=devices[local],
                               exchanges=exchanges)
            planner = LocalExecutionPlanner(
                runner.catalogs, runner.session, task=task)
            sinks = [exchanges[e.exchange_id] for e in sinks_edges]
            pipelines.extend(
                planner.plan_fragment(fragment.root, sinks))
        t0 = time.perf_counter()
        # worker tasks time-share the node's executor pool too (the
        # session property gates per statement, like shape buckets)
        from presto_tpu.execution.task_executor import (
            executor_for_session,
        )
        from presto_tpu.session_properties import get_property
        props = spec["session"].get("properties") or {}
        # history recording tap (worker tier): only a SINGLE-task
        # fragment's rows are whole-node cardinalities — a task of a
        # wider fragment sees its split slice, which must never be
        # recorded as the node's truth. Fault-armed nodes record
        # nothing (chaos batteries truncate rows mid-stream).
        from presto_tpu import history as _history
        from presto_tpu.execution import faults as _faults
        hist_ops = None
        if k == 1 and int(spec["n_tasks"]) == 1 \
                and _history.enabled(props) and not _faults.ARMED:
            hist_ops = _history.interesting_ops(
                fragment.root, planner.node_ops_prefusion,
                id_remap=(planner.fusion_report or {}).get(
                    "id_remap"),
                catalogs=runner.catalogs)
        drivers = LocalRunner.drive_pipelines(
            pipelines,
            profile=bool(spec.get("profile")),
            cancel=cancel.is_set if cancel is not None else None,
            executor=executor_for_session(props),
            quantum_ms=get_property(props,
                                    "task_executor_quantum_ms"),
            count_rows_ops=hist_ops)
        snap = LocalRunner.snapshot_driver_stats(drivers)
        if hist_ops is not None and not _faults.ARMED:
            runner._record_history(fragment.root, planner, snap)
        return {"wall_s": round(time.perf_counter() - t0, 6),
                "pipelines": snap}


def derive_fragments(runner, sql: str, stmt=None):
    """SQL -> the same FragmentedPlan on every node (symbol allocation
    and fragment numbering are deterministic). An EXPLAIN [ANALYZE]
    wrapper is unwrapped here — a distributed EXPLAIN ANALYZE ships
    the ORIGINAL text, and every node plans the inner query. `stmt`
    lets a caller that already parsed the text skip the second
    lex+parse walk."""
    from presto_tpu.parser import parse_statement
    from presto_tpu.parser import tree as T
    from presto_tpu.planner.exchanges import (
        add_exchanges, fragment_plan,
    )
    from presto_tpu.planner.local_planner import prune_unused_columns
    from presto_tpu.planner.optimizer import optimize
    with _ledger.span("planning"):
        if stmt is None:
            stmt = parse_statement(sql)
        if isinstance(stmt, T.Explain):
            stmt = stmt.statement
        from presto_tpu.planner.validation import (
            validate, validate_fragments,
        )
        plan = runner.create_plan(sql, stmt=stmt)
        validate(plan, "analysis", session=runner.session)
        plan = optimize(plan, runner.catalogs, session=runner.session)
        validate(plan, "optimizer", session=runner.session,
                 catalogs=runner.catalogs)
        prune_unused_columns(plan)
        plan = add_exchanges(plan, runner.catalogs, runner.session)
        validate(plan, "exchanges", session=runner.session)
        fplan = fragment_plan(plan)
        validate_fragments(fplan, "exchanges", session=runner.session)
        return fplan


def build_http_exchanges(query_id: str, fplan,
                         consumer_urls_by_edge,
                         worker_urls: List[str],
                         coordinator_url: str,
                         registry: ExchangeRegistry,
                         n_producers_by_edge=None,
                         self_url: Optional[str] = None,
                         key_ns: Optional[str] = None,
                         spool: Optional[dict] = None
                         ) -> Dict[int, HttpExchange]:
    """One HttpExchange per edge. The coordinator pre-computes a
    GLOBAL consumer URL table per edge (one slot per consumer TASK —
    a mesh-per-worker node's url appears once per device) plus the
    global producer count, and ships both in the task spec so every
    node agrees; when absent (legacy/single-device callers) the table
    degenerates to one slot per worker.

    Fault-tolerant mode (server/scheduler.py): `key_ns` namespaces
    the CONSUMER-side registry keys per task attempt (a retried task
    must never see a failed sibling's half-drained queues) while
    `spool` = {"url", "task", "attempt"} redirects every producer
    push into the coordinator's TaskOutputSpool under the canonical
    "<query_id>:<xid>" key."""
    out: Dict[int, HttpExchange] = {}
    ns = key_ns or query_id
    for xid, edge in fplan.edges.items():
        consumer = fplan.fragments[edge.consumer]
        producer = fplan.fragments[edge.producer]
        if consumer_urls_by_edge is not None:
            consumer_urls = consumer_urls_by_edge[
                str(xid) if str(xid) in consumer_urls_by_edge else xid]
        else:
            consumer_urls = [coordinator_url] \
                if consumer.partitioning == "single" \
                else list(worker_urls)
        if n_producers_by_edge is not None:
            n_producers = n_producers_by_edge[
                str(xid) if str(xid) in n_producers_by_edge else xid]
        else:
            n_producers = 1 if producer.partitioning == "single" \
                else len(worker_urls)
        out[xid] = HttpExchange(
            f"{ns}:{xid}", edge.scheme, edge.partition_keys,
            edge.hash_dicts, edge_key_dicts(edge), consumer_urls,
            n_producers, registry, self_url=self_url,
            spool_to=spool, canonical_key=f"{query_id}:{xid}")
    return out


def worker_main() -> None:
    """Entry point for a worker process:
    python -m presto_tpu.server.node --port 8081"""
    import argparse
    p = argparse.ArgumentParser()
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=0)
    p.add_argument("--devices", default="1",
                   help="local device count for mesh-per-worker "
                        "('auto' = jax.local_device_count())")
    args = p.parse_args()
    if args.devices == "auto":
        import jax
        n_devices = jax.local_device_count()
    else:
        n_devices = int(args.devices)
    node = Node(args.host, args.port, n_devices=n_devices)
    node.start()
    print(json.dumps({"url": node.url}), flush=True)
    try:
        threading.Event().wait()
    except KeyboardInterrupt:
        node.stop()


if __name__ == "__main__":
    worker_main()
