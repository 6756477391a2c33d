"""HTTP serving front for :class:`~autodist_tpu.serving.engine.DecodeEngine`.

The engine is a host-side continuous-batching scheduler; this module puts a
network boundary in front of it so the framework's serving story runs end to
end: model → engine → deployable server.  Stdlib only (``http.server`` +
``threading``) — no web-framework dependency to gate on.

The reference has no serving subsystem at all (its execution layer stops at
``WrappedSession.run``, ``autodist/runner.py:78-132``); this is beyond-parity
scope layered on the engine.

Design: ONE driver thread owns the decode loop (``engine.step()`` under the
server lock — the engine is not thread-safe), handler threads submit/cancel/
stream under the same lock (released and handed over between chunks) and
block on a per-request Event until their request id is harvested.  Sampling
knobs are engine-wide trace-time constants (see
``DecodeEngine``), so the per-request surface is ``prompt`` ×
``max_new_tokens`` × ``stream``.

Endpoints
---------
- ``POST /v1/completions`` — body ``{"prompt_tokens": [ints],
  "max_new_tokens": N, "stream": false}``; with a tokenizer installed,
  ``"prompt": "text"`` is accepted and ``"text"`` is returned.  Streaming
  responses are Server-Sent Events, one ``data:`` JSON per new-token delta.
- ``POST /v1/cancel`` — body ``{"id": N}``.
- ``POST /admin/drain`` — stop admitting (429 + ``"draining": true``),
  finish in-flight work; ``POST /admin/undrain`` reverses it.  SIGTERM
  triggers the same drain when :func:`install_drain_on_sigterm` is
  installed (``serve()`` does, best-effort), then exits
  ``DRAINED_EXIT_CODE`` once idle — the supervisor's budget-free
  preemption relaunch path, which is what makes
  ``SupervisedReplicaPool.rolling_restart()`` drop nothing.
- ``GET /v1/stats`` — engine counters + server counters (+ request
  latency p50/p99 estimated from the latency histogram) + the
  ``draining`` flag the router's candidate filter reads.
- ``GET /metrics`` — Prometheus text exposition
  (``autodist_serving_*``: request latency + queue-depth histograms,
  served/failed counters, outstanding gauge — docs/observability.md).
- ``GET /healthz``.
"""
from __future__ import annotations

import json
import os
import signal
import threading
import time
import uuid
from dataclasses import asdict
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Dict, Optional

import numpy as np

from autodist_tpu.resilience.chaos import ServingChaos
from autodist_tpu.serving.engine import (AdmissionError, DeadlineError,
                                         DecodeEngine)
from autodist_tpu.telemetry import timeline as tl
from autodist_tpu.telemetry.registry import (
    DEPTH_BUCKETS,
    MetricsRegistry,
    TIME_BUCKETS,
    render_prometheus,
)
from autodist_tpu.utils import logging

_MAX_BODY_BYTES = 8 << 20
_CANCELLED = object()   # sentinel in the done-map for cancelled requests
_DEADLINE = object()    # ... and for deadline-expired requests (504)


class EngineServer:
    """Serve a :class:`DecodeEngine` over HTTP.

    ``tokenizer`` (optional) is any object with ``encode(str) -> list[int]``
    and ``decode(seq[int]) -> str``; installing one enables the ``"prompt"``
    string form and ``"text"`` in responses.

    ``request_timeout_s`` bounds how long a completion request may wait
    end-to-end before the handler answers 504 and cancels the request
    (freeing its slot).
    """

    def __init__(self, engine: DecodeEngine, *, host: str = "127.0.0.1",
                 port: int = 0, tokenizer=None,
                 request_timeout_s: float = 600.0):
        self._engine = engine
        if getattr(engine, "_replicate", None) is not None:
            # A multi-PROCESS engine requires every process to drive the
            # scheduler in SPMD lockstep (identical submissions -> its
            # host pulls are cross-process collectives).  HTTP requests
            # land on ONE process, so serving it here would hang the
            # other processes in the first collective — fail at
            # construction instead.  Multi-process serving is driven by
            # a lockstep harness (tests/integration/dist_train.py).
            raise ValueError(
                "EngineServer cannot drive a multi-process DecodeEngine: "
                "HTTP requests arrive on one process while the engine's "
                "host pulls are cross-process collectives requiring SPMD "
                "lockstep; run the server on a single-process mesh, or "
                "drive the multi-process engine from a lockstep script")
        self._tokenizer = tokenizer
        tok_vocab = getattr(tokenizer, "vocab_size", None)
        if tok_vocab is not None and tok_vocab < engine._vocab:
            # Fail at construction, not mid-response: the model can
            # sample ids the tokenizer cannot decode.
            raise ValueError(
                f"tokenizer vocab_size {tok_vocab} < model vocab "
                f"{engine._vocab}: generated ids would not decode")
        self._timeout = float(request_timeout_s)
        # DRAINING: stop admitting (429 + "draining": true), finish
        # in-flight work.  Set by POST /admin/drain or SIGTERM (see
        # install_drain_on_sigterm); the router's candidate filter
        # reads the flag off /v1/stats and skips the replica.
        self._draining = False
        self._n_submitted = 0
        self._deadline_info: Dict[int, Dict[str, Any]] = {}
        # Serving-plane chaos (AUTODIST_CHAOS kill_replica/slow_replica/
        # drop_response/stale_stats), clocked by the driver loop on
        # serving progress; empty spec = no-op.
        self._chaos = ServingChaos.from_env()
        self._stale_stats: Optional[Dict[str, Any]] = None

        self._lock = threading.Lock()
        self._work = threading.Condition(self._lock)        # new submits
        # Handlers wanting the lock bump this; the driver yields to them
        # between iterations.  Python locks are NOT fair — the driver
        # releasing and immediately re-acquiring would otherwise starve
        # handler threads for the whole drain (a submit could not join a
        # running batch).  The counter has its own tiny lock: '+=' is
        # not atomic, and a lost update would drift the count
        # permanently.  The driver READS it unlocked — a stale read is
        # transient and harmless.
        self._meta_lock = threading.Lock()   # waiter count + counters
        self._handler_waiters = 0
        self._outstanding: set = set()
        self._done: Dict[int, Any] = {}          # rid -> tokens | _CANCELLED
        # Completion signalling is per-request Events, NOT a shared
        # condition: a condition waiter re-acquires the unfair lock on
        # notify and can starve behind the driver; Event.wait holds no
        # lock at all.
        self._events: Dict[int, threading.Event] = {}
        self._engine_error: Optional[BaseException] = None
        self._stop = False
        self.requests_served = 0
        self.requests_failed = 0
        # Telemetry (docs/observability.md): an EXPLICIT registry — the
        # /metrics endpoint is a server feature, live regardless of the
        # AUTODIST_TELEMETRY instrumentation switch.  Fixed-bound
        # histograms so a multi-replica deployment's scrapes merge
        # exactly.
        self._registry = MetricsRegistry()
        self._m_latency = self._registry.histogram(
            "autodist_serving_request_latency_seconds",
            "end-to-end completion latency (submit to final token)",
            buckets=TIME_BUCKETS)
        self._m_queue = self._registry.histogram(
            "autodist_serving_queue_depth",
            "requests outstanding at submit time",
            buckets=DEPTH_BUCKETS)
        self._m_served = self._registry.counter(
            "autodist_serving_requests_served_total",
            "completion requests answered successfully")
        self._m_failed = self._registry.counter(
            "autodist_serving_requests_failed_total",
            "completion requests failed/cancelled/timed out")
        self._m_outstanding = self._registry.gauge(
            "autodist_serving_outstanding", "requests currently in flight")
        # Fault-tolerance surface (docs/serving.md "Fault tolerance").
        self._m_shed = self._registry.counter(
            "autodist_serving_shed_total",
            "requests shed at admission: measured service rates say "
            "the deadline cannot be met (503)")
        self._m_expired = self._registry.counter(
            "autodist_serving_deadline_expired_total",
            "admitted requests cancelled past their deadline (504)")
        self._m_drain_refused = self._registry.counter(
            "autodist_serving_drain_refused_total",
            "requests refused because the replica is draining (429)")
        self._m_timeouts = self._registry.counter(
            "autodist_serving_timeouts_total",
            "requests that hit request_timeout_s and were cancelled "
            "(504)")
        self._m_draining = self._registry.gauge(
            "autodist_serving_draining",
            "1 while the replica is draining, else 0")
        # Scheduler-backed engines (PagedDecodeEngine) report richer
        # latency + occupancy telemetry: time-to-first-token and
        # inter-token latency histograms (fixed bounds — multi-replica
        # scrapes merge exactly) fed from the engine's per-request
        # timings, plus live block-pool / queue-depth gauges refreshed
        # by the driver loop.
        self._paged = hasattr(engine, "scheduler_stats")
        if self._paged:
            self._m_ttft = self._registry.histogram(
                "autodist_serving_ttft_seconds",
                "submit to first generated token", buckets=TIME_BUCKETS)
            self._m_itl = self._registry.histogram(
                "autodist_serving_per_token_seconds",
                "mean inter-token latency after the first token",
                buckets=TIME_BUCKETS)
            self._m_queue_wait = self._registry.histogram(
                "autodist_serving_queue_wait_seconds",
                "submit to admission (slot + blocks assigned)",
                buckets=TIME_BUCKETS)
            self._m_occupancy = self._registry.gauge(
                "autodist_serving_block_occupancy",
                "fraction of the paged KV block pool in use")
            self._m_prefix_rate = self._registry.gauge(
                "autodist_serving_prefix_hit_rate",
                "fraction of prompt tokens served from the prefix cache")
            self._m_class_depth = {
                c: self._registry.gauge(
                    "autodist_serving_queue_depth_class",
                    "admission queue depth by SLO class",
                    labels={"slo": c})
                for c in ("latency", "throughput")}
            self._m_occ_target = self._registry.gauge(
                "autodist_serving_block_occupancy_target",
                "pool fraction holding TARGET-model KV blocks")
            self._m_occ_draft = self._registry.gauge(
                "autodist_serving_block_occupancy_draft",
                "pool fraction holding draft-model KV blocks "
                "(speculative decoding)")
        # Speculative-mode telemetry (engine built with a draft model):
        # fixed-bound histograms again, so the acceptance-length and
        # gamma distributions merge exactly across replicas.
        self._spec = getattr(engine, "_draft_spec", None) is not None
        if self._spec:
            self._m_accept_len = self._registry.histogram(
                "autodist_serving_spec_accept_len",
                "mean accepted draft tokens per verify round, per "
                "request", buckets=DEPTH_BUCKETS)
            self._m_gamma_hist = self._registry.histogram(
                "autodist_serving_spec_gamma",
                "SLO-adapted proposal depth, sampled per driver fold",
                buckets=DEPTH_BUCKETS)
            self._m_gamma = self._registry.gauge(
                "autodist_serving_spec_gamma_current",
                "current SLO-adapted proposal depth")

        self._httpd = ThreadingHTTPServer((host, port), _Handler)
        self._httpd.daemon_threads = True
        self._httpd.owner = self
        self._driver = threading.Thread(target=self._drive,
                                        name="engine-server-driver",
                                        daemon=True)
        self._http_thread = threading.Thread(
            target=self._httpd.serve_forever, name="engine-server-http",
            daemon=True)

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> "EngineServer":
        self._driver.start()
        self._http_thread.start()
        logging.info("EngineServer listening on %s:%d", *self.address)
        return self

    def close(self) -> None:
        """Stop serving.  In-flight handler threads are woken and answer
        503; the engine object stays usable by the caller."""
        with self._lock:
            self._stop = True
            self._work.notify_all()
            for ev in self._events.values():
                ev.set()
            self._events.clear()
        self._httpd.shutdown()
        self._httpd.server_close()
        self._driver.join(timeout=10)

    def __enter__(self) -> "EngineServer":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.close()

    @property
    def address(self):
        return self._httpd.server_address[:2]

    @property
    def engine(self):
        """The engine behind the server.  The driver thread owns it while
        the server runs: read its counters, pool and trie once the
        requests of interest have been answered."""
        return self._engine

    # -- driver loop -------------------------------------------------------

    def _drive(self) -> None:
        # The lock is RELEASED between iterations: a handler thread must
        # be able to submit into (or stream from) the RUNNING batch —
        # holding the lock across the whole busy loop would serialize
        # the server into one batch per drain, defeating continuous
        # batching across concurrent HTTP requests.
        while True:
            if self._chaos:
                # Serving-chaos clock: fire on progress (submissions /
                # generated tokens), journal-before-execute.  Outside
                # the lock — an injected slow_replica sleep must not
                # also block handler submits.
                self._chaos.on_tick(
                    requests=self._n_submitted,
                    generated=int(getattr(self._engine.stats,
                                          "generated_tokens", 0)))
                if self._chaos.slow_s > 0:
                    time.sleep(self._chaos.slow_s)
            with tl.host_span(tl.SERVER_LOCK_WAIT):
                self._lock.acquire()
            try:
                if self._stop:
                    return
                if not self._outstanding:
                    self._work.wait(timeout=0.25)
                    continue
                try:
                    self._engine.step()
                except Exception as e:   # poisoned engine, device loss
                    self._engine_error = e
                    logging.error("EngineServer: engine failed: %r", e)
                for rid, toks in self._engine.results().items():
                    if rid in self._outstanding:
                        self._outstanding.discard(rid)
                        self._done[rid] = toks
                        ev = self._events.pop(rid, None)
                        if ev is not None:
                            ev.set()
                if self._paged:
                    # Deadline sweep results: resolve the waiters of
                    # requests the scheduler cancelled past-deadline
                    # (504 + Retry-After) instead of letting them ride
                    # to the request timeout.
                    for rid, info in self._engine.pop_expired().items():
                        if rid in self._outstanding:
                            self._outstanding.discard(rid)
                            self._done[rid] = _DEADLINE
                            self._deadline_info[rid] = info
                            self._m_expired.inc()
                            ev = self._events.pop(rid, None)
                            if ev is not None:
                                ev.set()
                    with tl.host_span(tl.SERVER_OBSERVE):
                        self._observe_paged()
                if self._engine_error is not None:
                    # In-flight work is lost (donated buffers); fail the
                    # waiters loudly rather than hang them to timeout.
                    self._outstanding.clear()
                    for ev in self._events.values():
                        ev.set()
                    self._events.clear()
                    return
            finally:
                self._lock.release()
            if self._handler_waiters:
                time.sleep(0.001)   # hand the lock to a waiting handler

    def _observe_paged(self) -> None:
        """Fold the scheduler's per-request timings and live occupancy
        into the server registry (driver thread, under the lock)."""
        for timing in self._engine.pop_timings().values():
            self._m_ttft.observe(timing["ttft_s"])
            self._m_queue_wait.observe(timing["queue_wait_s"])
            if timing.get("per_token_s"):
                self._m_itl.observe(timing["per_token_s"])
            if self._spec and timing.get("spec_rounds"):
                self._m_accept_len.observe(timing["accept_len_mean"])
        sched = self._engine.scheduler_stats()
        self._m_occupancy.set(sched["block_occupancy"])
        self._m_prefix_rate.set(sched["prefix_hit_rate"])
        self._m_occ_target.set(sched["block_occupancy_target"])
        self._m_occ_draft.set(sched["block_occupancy_draft"])
        for c, depth in sched["queue_depth"].items():
            g = self._m_class_depth.get(c)
            if g is not None:
                g.set(depth)
        if self._spec:
            gamma = sched["speculative"]["gamma"]
            self._m_gamma.set(gamma)
            self._m_gamma_hist.observe(float(gamma))

    # -- graceful drain ----------------------------------------------------

    @property
    def draining(self) -> bool:
        return self._draining

    def drain(self) -> None:
        """Enter DRAINING: new submits answer 429 with ``"draining":
        true``; in-flight work runs to completion.  Lock-free (a bool
        flip) so it is safe from a signal handler."""
        if not self._draining:
            self._draining = True
            self._m_draining.set(1)
            from autodist_tpu.telemetry import emit_event
            emit_event("serving/drain", phase="start",
                       outstanding=len(self._outstanding))
            logging.info("EngineServer: draining (%d in flight)",
                         len(self._outstanding))

    def undrain(self) -> None:
        """Leave DRAINING and admit again (rollback of an aborted
        rolling restart)."""
        if self._draining:
            self._draining = False
            self._m_draining.set(0)
            from autodist_tpu.telemetry import emit_event
            emit_event("serving/drain", phase="undrain")

    def idle(self) -> bool:
        """True when nothing is in flight (the drained-exit condition)."""
        return not self._outstanding

    # -- request plumbing (called from handler threads) --------------------

    def _locked(self):
        """Handler-side lock acquisition, counted so the driver loop
        yields to it (see ``_handler_waiters``)."""
        return _CountedLock(self)

    def _submit(self, prompt: np.ndarray, max_new: int,
                temperature=None, eos_id=None,
                use_prefix: bool = False, slo: Optional[str] = None,
                trace_id: str = "", gamma: Optional[int] = None,
                deadline_s: Optional[float] = None) -> int:
        with self._locked():
            if self._stop or self._engine_error is not None:
                raise _Unavailable()
            if self._draining:
                raise _Draining(self._drain_retry_hint())
            self._m_queue.observe(float(len(self._outstanding)))
            kwargs = dict(temperature=temperature, eos_id=eos_id,
                          use_prefix=use_prefix)
            if trace_id and self._paged:
                # Only the paged scheduler records per-request spans;
                # the slot engine ignores trace ids (its submit has no
                # per-request lifecycle timestamps to span).
                kwargs["trace_id"] = trace_id
            if gamma is not None:
                if not self._spec:
                    raise ValueError(
                        "this server's engine is not speculative; "
                        "drop the gamma field")
                kwargs["gamma"] = gamma
            if slo is not None:
                if not self._paged:
                    raise ValueError(
                        "this server's engine has no SLO classes "
                        "(slot engine); drop the slo field")
                kwargs["slo"] = slo
            if deadline_s is not None:
                if not self._paged:
                    raise ValueError(
                        "this server's engine has no deadline support "
                        "(slot engine); drop the deadline_s field")
                kwargs["deadline_s"] = deadline_s
            rid = self._engine.submit(prompt, max_new, **kwargs)
            self._n_submitted += 1
            self._outstanding.add(rid)
            self._m_outstanding.set(len(self._outstanding))
            self._events[rid] = threading.Event()
            self._work.notify()
            return rid

    def _drain_retry_hint(self) -> float:
        """Retry-After for drain rejections: long enough for the
        rolling restart's relaunch, short enough that the router's next
        attempt lands on the fresh process."""
        hint = getattr(self._engine, "_retry_hint", None)
        return float(hint()) if callable(hint) else 1.0

    def _wait(self, rid: int, timeout_s: float) -> Any:
        """Block until ``rid`` is harvested; returns its tokens.  Waits
        on the request's own Event (no shared-lock contention)."""
        with self._locked():
            ev = self._events.get(rid)
        if ev is not None and not ev.wait(timeout=timeout_s):
            with self._locked():
                # Re-check under the lock: the driver may have set the
                # event between the timeout and here.
                if rid not in self._done:
                    # Nobody is waiting any more: cancel (frees the
                    # slot instead of decoding unread tokens) and drop
                    # the bookkeeping so a racing harvest is discarded,
                    # not leaked.
                    self._engine.cancel(rid)
                    self._outstanding.discard(rid)
                    self._events.pop(rid, None)
                    self._m_timeouts.inc()
                    from autodist_tpu.telemetry import emit_event
                    emit_event("serving/timeout", request_id=rid,
                               timeout_s=float(timeout_s))
                    raise _Timeout()
        with self._locked():
            if rid not in self._done:
                raise _Unavailable()   # stop or engine failure
            return self._done.pop(rid)

    def _cancel(self, rid: int) -> bool:
        with self._locked():
            ok = self._engine.cancel(rid)
            if ok and rid in self._outstanding:
                self._outstanding.discard(rid)
                self._done[rid] = _CANCELLED
                ev = self._events.pop(rid, None)
                if ev is not None:
                    ev.set()
            return ok

    def _snapshot(self, rid: int):
        """Streaming read: (tokens_so_far, done) for an in-flight rid."""
        with self._locked():
            if rid in self._done:
                return self._done[rid], True
            if self._engine_error is not None or self._stop:
                raise _Unavailable()
            part = self._engine.partial(rid)
            return part, False

    def _finish_stream(self, rid: int) -> Any:
        with self._locked():
            self._events.pop(rid, None)
            return self._done.pop(rid, None)

    def count_request(self, *, served: bool,
                      latency_s: Optional[float] = None) -> None:
        """Bump the served/failed counter (handler threads race here;
        '+=' alone loses updates); ``latency_s`` feeds the request
        latency histogram when the terminal path knows it."""
        with self._meta_lock:
            if served:
                self.requests_served += 1
            else:
                self.requests_failed += 1
        (self._m_served if served else self._m_failed).inc()
        if latency_s is not None:
            self._m_latency.observe(latency_s)
        self._m_outstanding.set(len(self._outstanding))

    def stats(self) -> Dict[str, Any]:
        if self._chaos and self._chaos.stats_stale \
                and self._stale_stats is not None:
            # stale_stats chaos: the router keeps scoring off this
            # frozen snapshot — the load-balancing-blind drill.
            return dict(self._stale_stats)
        with self._locked():
            # Counters accumulate numpy scalars (+= np.int32); coerce so
            # json.dumps never trips on a dtype.
            st = {k: int(v) for k, v in asdict(self._engine.stats).items()
                  if not k.startswith("_")}
            st["slot_utilization"] = round(
                self._engine.stats.slot_utilization, 4)
            st["outstanding"] = len(self._outstanding)
            st["requests_served"] = self.requests_served
            st["requests_failed"] = self.requests_failed
            st["engine_failed"] = self._engine_error is not None
            p50 = self._m_latency.percentile(0.5)
            p99 = self._m_latency.percentile(0.99)
            if p50 is not None:
                st["latency_p50_ms"] = round(p50 * 1e3, 3)
                st["latency_p99_ms"] = round(p99 * 1e3, 3)
            if self._paged:
                # scheduler surface: per-class queue depth, block-pool
                # occupancy, prefix hit rate (the router's load score
                # reads these)
                st.update(self._engine.scheduler_stats())
                p50 = self._m_ttft.percentile(0.5)
                if p50 is not None:
                    st["ttft_p50_ms"] = round(p50 * 1e3, 3)
                    st["ttft_p99_ms"] = round(
                        self._m_ttft.percentile(0.99) * 1e3, 3)
            st["draining"] = self._draining
            if self._chaos and self._chaos.stats_stale:
                self._stale_stats = dict(st)
            return st

    def render_metrics(self) -> str:
        """Prometheus text exposition of the server registry (the
        ``/metrics`` scrape body)."""
        return render_prometheus(self._registry)

    # -- body parsing ------------------------------------------------------

    def parse_prompt(self, body: Dict[str, Any]) -> np.ndarray:
        if "prompt_tokens" in body:
            toks = body["prompt_tokens"]
            # type(t) is int, not isinstance: bool subclasses int, and
            # true/false must be a 400, not token ids 1/0.
            if (not isinstance(toks, list) or not toks
                    or not all(type(t) is int for t in toks)):
                raise ValueError(
                    "prompt_tokens must be a non-empty list of ints")
            return np.asarray(toks, np.int32)
        if "prompt" in body:
            if self._tokenizer is None:
                raise ValueError(
                    "server has no tokenizer: send prompt_tokens "
                    "(a list of token ids) instead of prompt text")
            return np.asarray(self._tokenizer.encode(body["prompt"]),
                              np.int32)
        raise ValueError("body needs prompt_tokens (or prompt, "
                         "with a tokenizer installed)")

    def render(self, rid: int, tokens: np.ndarray,
               prompt_len: int) -> Dict[str, Any]:
        out: Dict[str, Any] = {
            "id": rid,
            "tokens": [int(t) for t in tokens],
            "new_tokens": [int(t) for t in tokens[prompt_len:]],
        }
        if self._tokenizer is not None:
            out["text"] = self._tokenizer.decode(out["tokens"])
        return out


class _CountedLock:
    """Context manager acquiring the server lock with the handler-waiter
    count bumped, so the driver loop yields between iterations."""

    def __init__(self, srv: "EngineServer"):
        self._srv = srv

    def __enter__(self):
        with self._srv._meta_lock:
            self._srv._handler_waiters += 1
        try:
            self._srv._lock.acquire()
        finally:
            with self._srv._meta_lock:
                self._srv._handler_waiters -= 1

    def __exit__(self, *exc):
        self._srv._lock.release()


class _Unavailable(Exception):
    pass


class _Timeout(Exception):
    pass


class _Draining(Exception):
    """Submit refused: the replica is draining (429 + draining flag)."""

    def __init__(self, retry_after_s: float = 1.0):
        super().__init__("replica is draining")
        self.retry_after_s = float(retry_after_s)


class _Handler(BaseHTTPRequestHandler):
    # Quiet the default per-request stderr lines; route to our logger.
    def log_message(self, fmt, *args):   # noqa: N802 (stdlib name)
        logging.debug("EngineServer http: " + fmt, *args)

    def _json(self, code: int, payload: Dict[str, Any],
              headers: Optional[Dict[str, str]] = None) -> None:
        data = json.dumps(payload).encode()
        self.send_response(code)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(data)))
        for k, v in (headers or {}).items():
            self.send_header(k, v)
        self.end_headers()
        self.wfile.write(data)

    def _body(self) -> Dict[str, Any]:
        n = int(self.headers.get("Content-Length", 0))
        if n > _MAX_BODY_BYTES:
            raise ValueError(f"body too large ({n} bytes)")
        body = json.loads(self.rfile.read(n) or b"{}")
        if not isinstance(body, dict):
            raise ValueError("body must be a JSON object")
        return body

    def _text(self, code: int, body: str,
              content_type: str = "text/plain; version=0.0.4") -> None:
        data = body.encode()
        self.send_response(code)
        self.send_header("Content-Type", content_type)
        self.send_header("Content-Length", str(len(data)))
        self.end_headers()
        self.wfile.write(data)

    def do_GET(self) -> None:   # noqa: N802
        srv: EngineServer = self.server.owner
        if self.path == "/healthz":
            self._json(200, {"ok": srv._engine_error is None
                             and not srv._stop})
        elif self.path == "/v1/stats":
            self._json(200, srv.stats())
        elif self.path == "/metrics":
            self._text(200, srv.render_metrics())
        else:
            self._json(404, {"error": f"unknown path {self.path}"})

    def do_POST(self) -> None:   # noqa: N802
        srv: EngineServer = self.server.owner
        try:
            body = self._body()
        except (ValueError, json.JSONDecodeError) as e:
            self._json(400, {"error": str(e)})
            return
        if self.path == "/v1/completions":
            self._completions(srv, body)
        elif self.path == "/v1/cancel":
            rid = body.get("id")
            if not isinstance(rid, int):
                self._json(400, {"error": "cancel needs an integer id"})
            else:
                self._json(200, {"id": rid,
                                 "cancelled": srv._cancel(rid)})
        elif self.path == "/admin/drain":
            srv.drain()
            self._json(200, {"draining": True,
                             "outstanding": len(srv._outstanding)})
        elif self.path == "/admin/undrain":
            srv.undrain()
            self._json(200, {"draining": False})
        else:
            self._json(404, {"error": f"unknown path {self.path}"})

    def _completions(self, srv: EngineServer, body: Dict[str, Any]) -> None:
        t0 = time.perf_counter()
        t0_unix = time.time()
        # Trace propagation (docs/observability.md): the router stamps
        # X-Autodist-Trace; a bare client gets a fresh id.  The id rides
        # to the scheduler (queue-wait/prefill/decode spans) and back in
        # the response, so one request correlates across hosts in the
        # exported trace.
        trace_id = str(self.headers.get("X-Autodist-Trace", "")
                       or uuid.uuid4().hex[:16])
        try:
            prompt = srv.parse_prompt(body)
            max_new = body.get("max_new_tokens", 16)
            if type(max_new) is not int:   # bool is an int subclass
                raise ValueError("max_new_tokens must be an int")
            temperature = body.get("temperature")
            if temperature is not None and \
                    type(temperature) not in (int, float):
                raise ValueError("temperature must be a number")
            eos_id = body.get("eos_id")
            if eos_id is not None and type(eos_id) is not int:
                raise ValueError("eos_id must be an int")
            use_prefix = body.get("use_prefix", False)
            if type(use_prefix) is not bool:
                raise ValueError("use_prefix must be a bool")
            slo = body.get("slo")
            if slo is not None and not isinstance(slo, str):
                raise ValueError("slo must be a string")
            gamma = body.get("gamma")
            if gamma is not None and type(gamma) is not int:
                raise ValueError("gamma must be an int")
            deadline_s = body.get("deadline_s")
            if deadline_s is not None:
                if type(deadline_s) not in (int, float) \
                        or deadline_s <= 0:
                    raise ValueError("deadline_s must be a number > 0")
                deadline_s = float(deadline_s)
            rid = srv._submit(prompt, max_new, temperature=temperature,
                              eos_id=eos_id, use_prefix=use_prefix,
                              slo=slo, trace_id=trace_id, gamma=gamma,
                              deadline_s=deadline_s)
        except _Unavailable:
            self._json(503, {"error": "engine unavailable"})
            return
        except _Draining as e:
            # Graceful drain: refuse with the draining flag so the
            # router routes elsewhere WITHOUT marking the replica
            # down — it is healthy, just leaving rotation.
            srv.count_request(served=False)
            srv._m_drain_refused.inc()
            retry = max(round(e.retry_after_s, 3), 0.1)
            self._json(429, {"error": "replica is draining",
                             "draining": True, "retry_after_s": retry},
                       headers={"Retry-After": str(int(retry) + 1)})
            return
        except DeadlineError as e:
            # Deadline shed: measured service rates say this request
            # cannot finish in time.  503 + shed flag: route-elsewhere
            # territory (another replica may be less loaded), not a
            # health failure.
            srv.count_request(served=False)
            srv._m_shed.inc()
            retry = max(round(e.retry_after_s, 3), 0.1)
            self._json(503, {"error": str(e), "shed": True,
                             "retry_after_s": retry},
                       headers={"Retry-After": str(int(retry) + 1)})
            return
        except AdmissionError as e:
            # Typed backpressure: the bounded queue rejected the
            # request.  429 + Retry-After so well-behaved clients (and
            # the router) back off or route elsewhere instead of
            # piling on.
            srv.count_request(served=False)
            retry = max(round(e.retry_after_s, 3), 0.1)
            self._json(429, {"error": str(e), "retry_after_s": retry},
                       headers={"Retry-After": str(int(retry) + 1)})
            return
        except ValueError as e:   # engine/body validation, loud and typed
            srv.count_request(served=False)
            self._json(400, {"error": str(e)})
            return
        if body.get("stream"):
            self._stream(srv, rid, prompt.size, t0)
            return
        try:
            tokens = srv._wait(rid, srv._timeout)
        except _Timeout:
            srv.count_request(served=False,
                              latency_s=time.perf_counter() - t0)
            retry = max(round(srv._drain_retry_hint(), 3), 0.1)
            # Retry-After on 504 too: a timed-out-and-cancelled request
            # is load shedding just like the 429 path — tell the
            # client when the replica expects headroom.
            self._json(504, {"error": f"request {rid} timed out and was "
                             f"cancelled", "id": rid,
                             "retry_after_s": retry},
                       headers={"Retry-After": str(int(retry) + 1)})
            return
        except _Unavailable:
            srv.count_request(served=False,
                              latency_s=time.perf_counter() - t0)
            self._json(503, {"error": "engine unavailable", "id": rid})
            return
        if tokens is _DEADLINE:
            info = srv._deadline_info.pop(rid, {})
            srv.count_request(served=False,
                              latency_s=time.perf_counter() - t0)
            retry = max(round(srv._drain_retry_hint(), 3), 0.1)
            self._json(504, {"error": f"request {rid} missed its "
                             f"deadline and was cancelled", "id": rid,
                             "deadline_exceeded": True,
                             "phase": info.get("phase", ""),
                             "retry_after_s": retry},
                       headers={"Retry-After": str(int(retry) + 1)})
            return
        if tokens is _CANCELLED:
            # counted as failed so served+failed covers every handled
            # completion request
            srv.count_request(served=False,
                              latency_s=time.perf_counter() - t0)
            self._json(409, {"error": f"request {rid} was cancelled",
                             "id": rid})
            return
        if srv._chaos and srv._chaos.take_drop():
            # drop_response chaos: the engine finished the work but the
            # client never hears — sever the connection so the caller
            # sees a mid-request transport failure (the retry-
            # idempotence drill).
            srv.count_request(served=False,
                              latency_s=time.perf_counter() - t0)
            self.close_connection = True
            return
        latency = time.perf_counter() - t0
        srv.count_request(served=True, latency_s=latency)
        from autodist_tpu.telemetry.profiler import record_span
        record_span("request", start_unix=t0_unix, dur_s=latency,
                    trace_id=trace_id, request_id=rid)
        payload = srv.render(rid, tokens, prompt.size)
        payload["trace_id"] = trace_id
        self._json(200, payload, headers={"X-Autodist-Trace": trace_id})

    def _stream(self, srv: EngineServer, rid: int, prompt_len: int,
                t0: Optional[float] = None) -> None:
        """SSE: one ``data:`` event per new-token delta, final event
        carries the full result.  Deltas surface at chunk boundaries
        (the engine's streaming granularity, ``DecodeEngine.partial``).
        ``request_timeout_s`` applies here too: an expired stream is
        cancelled (slot freed) with a final timeout event."""
        self.send_response(200)
        self.send_header("Content-Type", "text/event-stream")
        self.send_header("Cache-Control", "no-cache")
        self.end_headers()

        def emit(payload: Dict[str, Any]) -> None:
            self.wfile.write(b"data: " + json.dumps(payload).encode()
                             + b"\n\n")
            self.wfile.flush()

        sent = prompt_len
        deadline = time.monotonic() + srv._timeout
        t0 = time.perf_counter() if t0 is None else t0
        # Exactly-once counting: each terminal path counts, and the
        # OSError handler counts only if no terminal path did (a final
        # emit that fails AFTER counting must not count again).
        counted = False

        def count(*, served: bool) -> None:
            nonlocal counted
            if not counted:
                counted = True
                srv.count_request(served=served,
                                  latency_s=time.perf_counter() - t0)

        try:
            # Announce the request id before any decode progress: a
            # router-side hedger needs the rid EARLY to cancel the
            # losing attempt, and a recovery client uses it to
            # correlate partial tokens (docs/serving.md).
            emit({"id": rid, "done": False, "new_tokens": []})
            while True:
                try:
                    with tl.host_span(tl.SERVER_SSE_POLL, request_id=rid):
                        snap, done = srv._snapshot(rid)
                except _Unavailable:
                    count(served=False)
                    emit({"id": rid, "error": "engine unavailable"})
                    return
                if not done and time.monotonic() > deadline:
                    srv._cancel(rid)
                    srv._finish_stream(rid)
                    count(served=False)
                    srv._m_timeouts.inc()
                    from autodist_tpu.telemetry import emit_event
                    emit_event("serving/timeout", request_id=rid,
                               timeout_s=srv._timeout, stream=True)
                    emit({"id": rid, "done": True, "timeout": True})
                    return
                if done:
                    tokens = srv._finish_stream(rid)
                    if tokens is _DEADLINE:
                        srv._deadline_info.pop(rid, None)
                        count(served=False)
                        emit({"id": rid, "done": True,
                              "deadline_exceeded": True})
                    elif tokens is _CANCELLED or tokens is None:
                        count(served=False)
                        emit({"id": rid, "done": True, "cancelled": True})
                    elif srv._chaos and srv._chaos.take_drop():
                        count(served=False)
                        self.close_connection = True
                    else:
                        count(served=True)
                        final = srv.render(rid, tokens, prompt_len)
                        final["done"] = True
                        emit(final)
                    return
                if snap is not None and snap.size > sent:
                    emit({"id": rid, "done": False,
                          "new_tokens": [int(t) for t in snap[sent:]]})
                    sent = int(snap.size)
                time.sleep(0.02)   # poll cadence between chunk boundaries
        except OSError:
            # Any socket write failure — hang-up, abort, timeout — frees
            # the slot instead of decoding tokens nobody will read (and
            # drains the harvested result so it can't leak in _done).
            srv._cancel(rid)
            srv._finish_stream(rid)
            count(served=False)


def install_drain_on_sigterm(server: EngineServer, *,
                             exit_code: Optional[int] = None,
                             settle_s: float = 0.25) -> None:
    """SIGTERM → graceful drain: stop admitting, let in-flight work
    finish, then ``os._exit`` once idle (plus ``settle_s`` for the last
    responses to flush).  The default exit code is the supervisor's
    ``PREEMPTED_EXIT_CODE`` (75): a drained replica relaunches WITHOUT
    consuming restart budget, which is what lets
    ``SupervisedReplicaPool.rolling_restart()`` cycle a whole pool.
    Must be called from the main thread (the ``signal`` module rule —
    raises ``ValueError`` otherwise)."""
    from autodist_tpu.resilience.supervisor import PREEMPTED_EXIT_CODE

    code = PREEMPTED_EXIT_CODE if exit_code is None else int(exit_code)

    def _on_term(signum, frame):
        server.drain()

        def _exit_when_idle():
            while not server.idle():
                time.sleep(0.05)
            time.sleep(settle_s)
            from autodist_tpu.telemetry import emit_event
            emit_event("serving/drain", phase="exit", code=code)
            os._exit(code)

        threading.Thread(target=_exit_when_idle, daemon=True,
                         name="drain-exit").start()

    signal.signal(signal.SIGTERM, _on_term)


def serve(spec, params, *, host: str = "127.0.0.1", port: int = 8000,
          tokenizer=None, prefix_tokens=None, prefix_text=None,
          paged: bool = False, speculative=None,
          drain_on_sigterm: Optional[bool] = None,
          **engine_kwargs) -> EngineServer:
    """Build an engine over ``(spec, params)`` and start an
    :class:`EngineServer` on it.  ``paged=True`` selects the
    paged-KV continuous-batching :class:`PagedDecodeEngine`
    (``serving/scheduler.py``: SLO queues, prefix trie, block pool);
    the default stays the slot engine.  ``engine_kwargs`` pass through
    to the engine (slots, window, chunk, sampling knobs, mesh, ...).  A
    tokenizer with a registered ``<eos>`` special token supplies the
    engine's ``eos_id`` automatically (explicit ``eos_id=`` wins).
    ``prefix_tokens`` (ids) or ``prefix_text`` (tokenizer required)
    registers the shared cached system prompt; requests opt in with
    ``"use_prefix": true``.

    ``speculative`` turns on speculative decoding (docs/serving.md):
    a dict with ``spec`` and ``params`` for the draft model, plus
    optional ``gamma`` (proposal depth, default 4) and ``adapt_gamma``
    (SLO adaptation, default True).  Speculation is a mode of the
    paged scheduler, so it implies ``paged=True``.

    ``drain_on_sigterm`` installs :func:`install_drain_on_sigterm`
    (graceful drain + exit 75 on SIGTERM).  The default (``None``)
    installs it only when the process looks like a supervised replica
    (``AUTODIST_REPLICA_NAME`` in the environment) — a test process
    embedding a server keeps its own signal handling."""
    if "eos_id" not in engine_kwargs:
        eos = getattr(tokenizer, "eos_id", None)
        if eos is not None:
            engine_kwargs["eos_id"] = int(eos)
    if speculative is not None:
        unknown = set(speculative) - {"spec", "params", "gamma",
                                      "adapt_gamma"}
        if unknown or not {"spec", "params"} <= set(speculative):
            raise ValueError(
                "speculative= takes a dict with 'spec' and 'params' "
                f"(optional 'gamma', 'adapt_gamma'); got "
                f"{sorted(speculative)}")
        paged = True
        engine_kwargs["draft_spec"] = speculative["spec"]
        engine_kwargs["draft_params"] = speculative["params"]
        for k in ("gamma", "adapt_gamma"):
            if k in speculative:
                engine_kwargs[k] = speculative[k]
    if paged:
        from autodist_tpu.serving.scheduler import PagedDecodeEngine

        eng = PagedDecodeEngine(spec, params, **engine_kwargs)
    else:
        eng = DecodeEngine(spec, params, **engine_kwargs)
    if prefix_text is not None:
        if tokenizer is None:
            raise ValueError("prefix_text needs a tokenizer; pass "
                             "prefix_tokens instead")
        if prefix_tokens is not None:
            raise ValueError("pass prefix_tokens OR prefix_text")
        prefix_tokens = tokenizer.encode(prefix_text)
    if prefix_tokens is not None:
        eng.set_prefix(prefix_tokens)
    srv = EngineServer(eng, host=host, port=port,
                       tokenizer=tokenizer).start()
    if drain_on_sigterm is None:
        drain_on_sigterm = bool(os.environ.get("AUTODIST_REPLICA_NAME"))
    if drain_on_sigterm:
        try:
            install_drain_on_sigterm(srv)
        except ValueError:   # not the main thread: skip, best-effort
            logging.warning("serve(): cannot install the SIGTERM drain "
                            "handler off the main thread")
    return srv
