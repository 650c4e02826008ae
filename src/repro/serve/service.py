"""The transform service: async queue -> buckets -> batched dispatch.

One worker thread owns the device: it pulls requests off the queue,
groups them by executable (:mod:`repro.serve.batcher`), resolves plans
through the :mod:`repro.serve.plan_cache`, stacks/pads the payloads, and
dispatches the batched transform with donated buffers.  Clients get
``concurrent.futures.Future``s; results materialize on the host so
latency includes the D2H hop.

The loop is continuous batching in the transform setting: while the
device runs one batch, the queue keeps filling, so the next batch forms
from whatever arrived meanwhile — occupancy rises with offered load
instead of being fixed at a static batch size.

    with TransformService(mesh, max_batch=8) as svc:
        fut = svc.submit(field, problem="r2c")
        spectrum = fut.result().value
"""

from __future__ import annotations

import dataclasses
import logging
import queue
import threading
import time
from typing import Optional

import jax
import numpy as np

from repro.obs import metrics as metrics_lib
from repro.obs import tracer as tracer_lib
from repro.resil import inject as inject_lib
from repro.serve.batcher import Batcher, Bucket, padded_size, stack_and_pad
from repro.serve.plan_cache import PlanCache
from repro.serve.request import (PRIORITY_NORMAL, ShedResult,
                                 TransformRequest, TransformResult,
                                 bucket_key)

_log = logging.getLogger("repro.serve")


@dataclasses.dataclass
class _Pending:
    req: TransformRequest
    future: "object"  # concurrent.futures.Future[TransformResult]


class TransformService:
    """Plan-cached, continuously batched spectral transform service."""

    def __init__(self, mesh=None, *, max_batch: int = 8,
                 max_wait_ms: float = 2.0,
                 cache: Optional[PlanCache] = None,
                 wisdom_path: Optional[str] = None,
                 max_plans: int = 16,
                 measure_after: Optional[int] = None,
                 tune_kw: Optional[dict] = None,
                 latency_window: int = 4096,
                 registry: Optional[metrics_lib.MetricsRegistry] = None,
                 max_queue: Optional[int] = None,
                 dispatch_retries: int = 2,
                 retry_backoff_s: float = 0.01,
                 nan_guard: bool = True,
                 quarantine_after: int = 3,
                 preemption=None):
        self.mesh = mesh
        self.max_batch = max_batch
        self.max_wait_s = max_wait_ms / 1e3
        #: bounded-queue load shedding: when more than ``max_queue``
        #: requests are pending in the batcher, the least-important one
        #: (highest priority value, newest first) resolves with a typed
        #: ShedResult instead of waiting (None = unbounded)
        self.max_queue = max_queue
        self.dispatch_retries = dispatch_retries
        self.retry_backoff_s = retry_backoff_s
        self.nan_guard = nan_guard
        #: resil.fault.PreemptionHandler (or None): when its flag flips
        #: (SIGTERM), the worker drains pending buckets and stops cleanly
        self.preemption = preemption
        # every serving number lives in the metrics registry (repro.obs);
        # stats() below is a thin compatibility view over it.  Each
        # service owns its registry by default so two services never mix
        # counters; pass registry= to share one exposition endpoint.
        self.registry = registry if registry is not None \
            else metrics_lib.MetricsRegistry()
        self.cache = cache if cache is not None else PlanCache(
            mesh, wisdom_path=wisdom_path, max_plans=max_plans,
            measure_after=measure_after, tune_kw=tune_kw,
            registry=self.registry, quarantine_after=quarantine_after)
        self._queue: "queue.Queue" = queue.Queue()
        self._batcher = Batcher(max_batch, self.max_wait_s)
        self._worker: Optional[threading.Thread] = None
        self._running = False
        self._lock = threading.Lock()
        del latency_window  # kept for API compat; quantiles now come
        #                     from the registry's log-bucketed histogram
        self._m_submitted = self.registry.counter(
            "serve_requests_submitted", "requests accepted by submit()")
        self._m_requests = self.registry.counter(
            "serve_requests", "requests served successfully")
        self._m_batches = self.registry.counter(
            "serve_batches", "batched dispatches")
        self._m_real_rows = self.registry.counter(
            "serve_real_rows", "real rows across dispatched batches")
        self._m_padded_rows = self.registry.counter(
            "serve_padded_rows", "padded rows across dispatched batches")
        self._m_waste_rows = self.registry.counter(
            "serve_padding_waste_rows",
            "padded slots that carried no request (dead collective weight)")
        self._m_failures = self.registry.counter(
            "serve_failures", "requests resolved with ok=False")
        self._m_batch_hist = self.registry.histogram(
            "serve_batch_size", "real batch size per dispatch",
            bounds=range(1, max_batch + 1))
        self._m_latency = self.registry.histogram(
            "serve_latency_s", "submit-to-result seconds")
        self._m_queue_wait = self.registry.histogram(
            "serve_queue_wait_s", "submit-to-dispatch seconds")
        # resilience counters (ISSUE 10): every shed/retry/poison event
        # is counted exactly once so chaos gates can assert equality
        self._m_shed = self.registry.counter(
            "serve_shed_requests",
            "requests rejected by bounded-queue load shedding")
        self._m_deadline = self.registry.counter(
            "serve_deadline_misses",
            "requests whose dispatch deadline passed before their batch")
        self._m_retries = self.registry.counter(
            "serve_dispatch_retries",
            "transient dispatch faults retried with backoff")
        self._m_poisoned = self.registry.counter(
            "serve_poisoned_requests",
            "requests isolated for non-finite payloads")
        self._m_redispatch = self.registry.counter(
            "serve_poison_redispatches",
            "healthy batch-mates re-dispatched individually after a "
            "poisoned co-batched dispatch")
        self._m_nan_outputs = self.registry.counter(
            "serve_nan_outputs",
            "dispatches producing non-finite output from finite input")
        self._m_preempt = self.registry.counter(
            "serve_preemption_drains",
            "graceful drains triggered by the preemption handler")
        self._m_leaked = self.registry.counter(
            "serve_leaked_upgrade_threads",
            "upgrade threads still alive after stop()'s join timeout")

    # -- lifecycle ----------------------------------------------------------
    def start(self) -> "TransformService":
        with self._lock:
            if self._running:
                return self
            self._running = True
        if self.preemption is not None:
            self.preemption.install()  # SIGTERM -> flag; worker drains
        self._worker = threading.Thread(target=self._run, daemon=True,
                                        name="transform-service")
        self._worker.start()
        return self

    def stop(self, drain: bool = True) -> None:
        """Stop the worker; ``drain=True`` serves everything already
        queued first (in-flight futures never dangle)."""
        with self._lock:
            if not self._running:
                return
            self._running = False
        self._queue.put(None)  # wake the worker
        if self._worker is not None:
            self._worker.join()
            self._worker = None
        if drain:
            self._drain_all()
        else:
            self._fail_pending("service stopped")
        if not self.cache.wait_idle(timeout=30.0):
            leaked = self.cache.alive_upgrades()
            self._m_leaked.inc(leaked)
            tracer_lib.get_tracer().instant(
                "service:leaked-upgrade-threads", "plan", {"n": leaked})
            _log.warning("stop(): %d upgrade thread(s) still running "
                         "after join timeout (daemon threads; they die "
                         "with the process)", leaked)

    def __enter__(self) -> "TransformService":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # -- client API ---------------------------------------------------------
    def submit(self, x, *, problem: str = "c2c", direction: str = "forward",
               h=None, shape=None, dtype=None,
               priority: int = PRIORITY_NORMAL,
               deadline_s: Optional[float] = None):
        """Enqueue one transform; returns a Future[TransformResult].

        Payloads are host arrays (the wire format); validation happens
        here, synchronously, so a malformed request raises at the call
        site instead of poisoning a batch.  ``priority`` and
        ``deadline_s`` are the request-lifecycle knobs: priority decides
        who sheds first under a bounded queue and which ready bucket
        dispatches first; a passed deadline resolves the future with a
        typed :class:`~repro.serve.request.ShedResult` instead of
        running stale work."""
        req = TransformRequest(
            x=np.asarray(x), problem=problem, direction=direction,
            h=None if h is None else np.asarray(h), shape=shape,
            dtype=np.complex64 if dtype is None else dtype,
            priority=priority, deadline_s=deadline_s)
        req.validate_payload()
        import concurrent.futures
        fut = concurrent.futures.Future()
        # check-and-enqueue under the lifecycle lock: stop() flips
        # _running under the same lock, so no request can slip in after
        # _fail_pending has swept the queue (its future would never
        # resolve and the caller would hang on fut.result()).
        with self._lock:
            if not self._running:
                raise RuntimeError("service not started (use `with "
                                   "service:` or service.start())")
            self._queue.put(_Pending(req, fut))
        self._m_submitted.inc()
        tracer_lib.get_tracer().instant(
            "request:submit", "queue",
            {"req_id": req.req_id, "problem": req.problem,
             "direction": req.direction})
        return fut

    def transform(self, x, **kw) -> np.ndarray:
        """Synchronous convenience: submit, wait, unwrap (raises on a
        failed request)."""
        res = self.submit(x, **kw).result()
        if not res.ok:
            raise RuntimeError(f"transform failed: {res.error}")
        return res.value

    # -- worker -------------------------------------------------------------
    def _run(self) -> None:
        while True:
            if (self.preemption is not None
                    and self.preemption.preemption_requested):
                self._preempt_drain()
                return
            deadline = self._batcher.next_deadline()
            timeout = 0.05 if deadline is None else min(deadline, 0.05)
            try:
                item = self._queue.get(timeout=timeout)
            except queue.Empty:
                item = False  # timeout tick: check wait budgets below
            if item is None:
                return  # stop() sentinel; stop() handles the remainder
            if item is not False:
                self._batcher.add(self._bucket_key(item.req), item)
                self._shed_overflow()
            for bucket in self._batcher.pop_ready():
                self._dispatch(bucket)

    def _shed_overflow(self) -> None:
        """Bounded-queue load shedding: evict the least-important pending
        request (see ``Batcher.shed_lowest``) until back under
        ``max_queue``.  Evicted futures resolve immediately with a typed
        ShedResult — a shed request can never hang."""
        if self.max_queue is None:
            return
        while self._batcher.pending > self.max_queue:
            item = self._batcher.shed_lowest()
            if item is None:
                return
            self._m_shed.inc()
            tracer_lib.get_tracer().instant(
                "request:shed", "queue",
                {"req_id": item.req.req_id, "priority": item.req.priority})
            item.future.set_result(ShedResult(
                req_id=item.req.req_id, value=None, ok=False,
                error=f"shed: queue full (max_queue={self.max_queue})",
                shed_reason="queue-full", t_submit=item.req.t_submit))

    def _preempt_drain(self) -> None:
        """Preemption (SIGTERM): flip to not-running so new submits are
        refused, then serve everything already pending — a preempted
        service finishes its work, it does not drop it."""
        with self._lock:
            self._running = False
        self._m_preempt.inc()
        tracer_lib.get_tracer().instant("service:preempt-drain", "queue")
        self._drain_all()

    def _bucket_key(self, req: TransformRequest) -> str:
        # token_for (not key_for): once a plan is built the bucket key
        # carries its pipeline token, so requests never co-batch across
        # an upgrade that swapped in a different (e.g. searched) pipeline
        return bucket_key(req, self.cache.token_for(
            req.shape, req.dtype, req.plan_problem))

    def _drain_all(self) -> None:
        """Serve every queued/pending request (shutdown, tests)."""
        while True:
            try:
                item = self._queue.get_nowait()
            except queue.Empty:
                break
            if item is not None and item is not False:
                self._batcher.add(self._bucket_key(item.req), item)
        # buckets here can exceed max_batch (leftover partial bucket plus
        # late arrivals); chunk them, since padded_size rejects oversize
        # and stop(drain=True) promises every queued request is served
        for bucket in self._batcher.pop_all():
            reqs = bucket.requests
            for i in range(0, len(reqs), self.max_batch):
                self._dispatch(Bucket(bucket.key,
                                      reqs[i:i + self.max_batch]))

    def _fail_pending(self, msg: str) -> None:
        while True:
            try:
                item = self._queue.get_nowait()
            except queue.Empty:
                break
            if item is not None and item is not False:
                item.future.set_result(TransformResult(
                    req_id=item.req.req_id, value=None, ok=False, error=msg))
        for bucket in self._batcher.pop_all():
            for p in bucket.requests:
                p.future.set_result(TransformResult(
                    req_id=p.req.req_id, value=None, ok=False, error=msg))

    # -- dispatch -----------------------------------------------------------
    def _dispatch(self, bucket, _isolate: bool = True) -> None:
        tracer = tracer_lib.get_tracer()
        t_dispatch = time.monotonic()
        # deadline enforcement: a request whose dispatch deadline passed
        # while it queued resolves typed and never runs (stale work is
        # dead weight for every batch-mate's collective)
        pendings = []
        for p in bucket.requests:
            if p.req.expired(t_dispatch):
                self._m_deadline.inc()
                tracer.instant("request:deadline-miss", "queue",
                               {"req_id": p.req.req_id,
                                "deadline_s": p.req.deadline_s})
                p.future.set_result(ShedResult(
                    req_id=p.req.req_id, value=None, ok=False,
                    error=f"deadline exceeded ({p.req.deadline_s}s)",
                    shed_reason="deadline", t_submit=p.req.t_submit))
            else:
                pendings.append(p)
        if not pendings:
            return
        req0 = pendings[0].req
        n = len(pendings)
        # retroactive queue-wait spans: started on the client thread at
        # submit (req.t_submit is on the same monotonic clock), ended now
        for p in pendings:
            tracer.complete("request:queue", "queue", p.req.t_submit,
                            t_dispatch, {"req_id": p.req.req_id,
                                         "reason": bucket.reason})
            self._m_queue_wait.observe(t_dispatch - p.req.t_submit)
        cp = None
        try:
            with tracer.span("batch:dispatch", "queue", n=n,
                             reason=bucket.reason, bucket=bucket.key):
                cp = self.cache.get(req0.shape, req0.dtype,
                                    req0.plan_problem)
                out = self._run_batch(cp, pendings, bucket)
            if self.nan_guard and not np.isfinite(out[:n]).all():
                self._handle_nonfinite(cp, bucket, pendings, t_dispatch,
                                       _isolate)
                return
            t_done = time.monotonic()
            padded = out.shape[0]
            for i, p in enumerate(pendings):
                p.future.set_result(TransformResult(
                    req_id=p.req.req_id, value=out[i],
                    latency_s=t_done - p.req.t_submit, batch_size=n,
                    padded_size=padded, plan_state=cp.state,
                    plan_key=cp.key, t_submit=p.req.t_submit,
                    t_dispatch=t_dispatch, t_done=t_done))
            self._m_requests.inc(n)
            self._m_batches.inc()
            self._m_real_rows.inc(n)
            self._m_padded_rows.inc(padded)
            self._m_waste_rows.inc(padded - n)
            self._m_batch_hist.observe(n)
            for p in pendings:
                self._m_latency.observe(t_done - p.req.t_submit)
        except Exception as e:  # resolve futures, never kill the worker
            msg = f"{type(e).__name__}: {e}"
            self._m_failures.inc(n)
            if cp is not None:
                # count toward quarantine: quarantine_after consecutive
                # failed dispatches re-route the bucket to the next
                # degradation-ladder rung (repro.resil.degrade)
                self.cache.report_dispatch_failure(cp.key)
            for p in pendings:
                if not p.future.done():
                    p.future.set_result(TransformResult(
                        req_id=p.req.req_id, value=None, ok=False,
                        error=msg))

    def _run_batch(self, cp, pendings, bucket) -> np.ndarray:
        """Execute with retry-with-backoff for *transient* dispatch
        faults (typed ``resil.TransientFault`` — real device errors are
        not transient-classifiable and fail straight through)."""
        attempt = 0
        while True:
            try:
                inject_lib.fire("serve.dispatch", bucket.key)
                return self._execute(cp.plan, pendings)
            except inject_lib.TransientFault:
                if attempt >= self.dispatch_retries:
                    raise
                self._m_retries.inc()
                tracer_lib.get_tracer().instant(
                    "batch:retry", "queue",
                    {"bucket": bucket.key, "attempt": attempt})
                if self.retry_backoff_s:
                    time.sleep(self.retry_backoff_s * (2 ** attempt))
                attempt += 1

    def _handle_nonfinite(self, cp, bucket, pendings, t_dispatch,
                          isolate: bool) -> None:
        """A dispatch produced NaN/Inf rows.  If any *input* was
        non-finite, this is payload poisoning: the poisoned requests
        resolve as typed failures and every healthy batch-mate
        re-dispatches individually — one bad request must not corrupt
        its neighbors (donated buffers and shared collectives make
        row-level containment unverifiable).  All-finite inputs mean the
        *plan* produced garbage: every request fails typed and the
        failure counts toward the plan's quarantine."""
        poisoned = [p for p in pendings if not p.req.payload_finite()]
        if not poisoned:
            self._m_nan_outputs.inc()
            self._m_failures.inc(len(pendings))
            self.cache.report_dispatch_failure(cp.key)
            for p in pendings:
                p.future.set_result(TransformResult(
                    req_id=p.req.req_id, value=None, ok=False,
                    error="non-finite output from finite input (plan "
                          "poisoned; counted toward quarantine)",
                    plan_key=cp.key, t_submit=p.req.t_submit,
                    t_dispatch=t_dispatch))
            return
        bad = {id(p) for p in poisoned}
        self._m_poisoned.inc(len(poisoned))
        self._m_failures.inc(len(poisoned))
        for p in poisoned:
            tracer_lib.get_tracer().instant(
                "request:poisoned", "queue", {"req_id": p.req.req_id})
            p.future.set_result(TransformResult(
                req_id=p.req.req_id, value=None, ok=False,
                error="poisoned payload: non-finite input",
                plan_key=cp.key, t_submit=p.req.t_submit,
                t_dispatch=t_dispatch))
        healthy = [p for p in pendings if id(p) not in bad]
        if not healthy:
            return
        if not isolate:  # already a 1-request redispatch; don't recurse
            for p in healthy:
                p.future.set_result(TransformResult(
                    req_id=p.req.req_id, value=None, ok=False,
                    error="non-finite output on isolated redispatch",
                    plan_key=cp.key, t_submit=p.req.t_submit,
                    t_dispatch=t_dispatch))
            return
        self._m_redispatch.inc(len(healthy))
        for p in healthy:
            self._dispatch(Bucket(bucket.key, [p], reason="redispatch"),
                           _isolate=False)

    def _execute(self, plan, pendings) -> np.ndarray:
        """Stack, pad, place, run the batched executable, fetch to host.

        Phase spans (h2d -> compute -> d2h) are emitted when tracing is
        enabled; the compute span then pays one extra
        ``block_until_ready`` so the d2h span measures only the fetch.
        With the no-op tracer the call sequence is byte-identical to the
        untraced path."""
        req0 = pendings[0].req
        tracer = tracer_lib.get_tracer()
        n = len(pendings)
        padded = padded_size(n, self.max_batch)
        forward = req0.direction == "forward"
        in_dtype = (plan.input_dtype if forward else plan.dtype)
        with tracer.span("batch:h2d", "h2d/d2h", rows=padded):
            xs = stack_and_pad([p.req.x for p in pendings],
                               padded).astype(in_dtype, copy=False)
            xd = self._place(xs, plan.batched_sharding(
                "input" if forward else "output"))
            hd = None
            if req0.h is not None:
                hs = stack_and_pad([p.req.h for p in pendings],
                                   padded).astype(plan.dtype, copy=False)
                hd = self._place(hs, plan.batched_sharding("output"))
        with tracer.span("batch:compute", "fft", rows=padded,
                         direction=req0.direction, problem=req0.problem):
            if hd is not None:
                out = plan.forward_filtered_batched(xd, hd)
            elif forward:
                out = plan.forward_batched(xd)
            else:
                out = plan.inverse_batched(xd)
            if tracer.enabled:
                jax.block_until_ready(out)
        with tracer.span("batch:d2h", "h2d/d2h", rows=padded):
            return np.asarray(jax.device_get(out))

    @staticmethod
    def _place(host: np.ndarray, sharding):
        if sharding is None:
            return jax.numpy.asarray(host)
        return jax.device_put(host, sharding)

    # -- stats --------------------------------------------------------------
    def stats(self) -> dict:
        """Compatibility view over the metrics registry: the dict shape
        predates ``repro.obs`` and is kept for callers/benches; new code
        should read ``service.registry`` directly (``snapshot()`` /
        ``to_prometheus()``)."""
        n_requests = int(self._m_requests.value)
        n_batches = int(self._m_batches.value)
        real_rows = int(self._m_real_rows.value)
        padded_rows = int(self._m_padded_rows.value)

        # exact batch-size histogram back out of the explicit-bounds
        # buckets (cumulative -> per-size counts keyed by int size)
        batch_hist = {}
        prev = 0
        for edge, cum in self._m_batch_hist.buckets()[:-1]:
            if cum > prev:
                batch_hist[int(edge)] = cum - prev
            prev = cum

        def q(p):
            v = self._m_latency.quantile(p)
            return None if v is None else v * 1e3

        return {
            "requests": n_requests,
            "batches": n_batches,
            "mean_batch": (n_requests / n_batches if n_batches else 0.0),
            "real_rows": real_rows,
            "padded_rows": padded_rows,
            "padding_waste_rows": int(self._m_waste_rows.value),
            "occupancy": (real_rows / padded_rows if padded_rows else 0.0),
            "batch_hist": batch_hist,
            "pending": self._batcher.pending + self._queue.qsize(),
            "latency_ms": {"p50": q(0.50), "p90": q(0.90), "p99": q(0.99)},
            "plan_cache": self.cache.snapshot(),
        }
