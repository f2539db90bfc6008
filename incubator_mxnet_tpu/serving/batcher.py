"""DynamicBatcher — coalesce concurrent inference requests into one
compiled dispatch.

Requests land in a bounded FIFO queue; a single worker thread pops the
head and keeps gathering compatible requests (same per-example shapes
and dtypes — FIFO order is never reordered past an incompatible head)
until the group reaches ``max_batch_size`` rows or the head request's
``max_delay_ms`` deadline expires.  The group is concatenated along the
batch axis, padded up to the engine's next bucket, dispatched as ONE
compiled program, and the output rows are scattered back to the waiting
callers.

Operational behavior is wired into the runtime's existing planes:

* **backpressure** — a full queue rejects immediately with
  :class:`QueueFullError` (``mxtpu_serve_rejected``); the client sees a
  429 from the HTTP front-end instead of unbounded latency.
* **deadlines** — a request may carry an end-to-end budget
  (``timeout_ms``; env default ``MXNET_SERVE_TIMEOUT_MS``).  Admission
  rejects a request whose queue-wait estimate already busts it, the
  gather loop sheds requests that expired while queued, and the caller's
  wait is bounded by the remaining budget — all three raise
  ``lifecycle.DeadlineExceeded`` (HTTP 504,
  ``mxtpu_serve_deadline_exceeded``), so a stuck dispatch can never pin
  an HTTP handler thread forever.
* **circuit breaker** — consecutive dispatch-after-retry failures (the
  :meth:`_fallback` path) trip the model's ``lifecycle.CircuitBreaker``
  CLOSED→OPEN; while OPEN, admission fast-fails with
  ``lifecycle.BreakerOpen`` (HTTP 503 + ``Retry-After``) until a
  half-open probe succeeds.
* **watchdog** — the worker heartbeats; :meth:`check_worker` (driven by
  ``lifecycle.Watchdog``) detects a dead or hung worker, fails that
  group's riders with ``lifecycle.RequestAborted``, restarts the worker
  on a fresh generation, trips the breaker and marks the model
  DEGRADED until the next successful dispatch.
* **faults** — ``serving.queue`` is polled at submit and
  ``serving.infer`` inside the batched dispatch (``MXNET_FAULT_PLAN``
  site grammar, docs/robustness.md; the ``hang`` kind drills the
  watchdog).  A failed batch dispatch retries under
  :func:`fault.retry_call`; on exhaustion the batcher publishes a
  ``fallback`` FAULT event, bumps ``mxtpu_serve_fallbacks``, and
  executes each request individually so one poisoned batch cannot fail
  every rider.
* **graceful drain** — :meth:`close` stops intake, lets the worker
  drain everything already queued (coalescing without waiting out the
  delay deadline), then joins the worker; if the worker cannot finish
  inside the join budget, every still-pending request is failed with a
  clear error instead of being stranded on an event nobody will set.
* **telemetry** — ``serve.request`` (submit-to-result) and
  ``serve.batch`` spans, queue-wait / batch-size / end-to-end latency
  histograms, per-model queue-depth gauge, breaker/watchdog series.
* **request tracing** — every request carries a request id (client's
  ``x-request-id`` via the HTTP front-end, else generated here) that is
  stamped on its ``serve.request`` span, on every FAULT event it
  triggers (deadline sheds, injected faults, watchdog aborts, worker
  crashes), and on the ``serve.batch`` span's ``links`` attr, so one id
  greps a failed request end to end — HTTP response header → span tree
  → flight-recorder dump (docs/observability.md).  The caller's span
  context is captured at submit and re-attached in the worker thread,
  so the batch span nests under the request that headed the batch.
* **SLO accounting** — every synchronous :meth:`submit` outcome lands
  in ``serving.slo``'s per-model rolling window (good/bad + latency),
  feeding the ``mxtpu_slo_*`` series and ``/slo`` burn-rate math.
"""
from __future__ import annotations

import contextlib
import threading
import time
from collections import deque
from typing import List, Optional, Sequence

from ..base import MXNetError, getenv, getenv_int
from ..ndarray.ndarray import NDArray
from .. import fault as _fault
from .. import health as _health
from .. import telemetry as _telemetry
from .. import telemetry_device as _tdev
from . import lifecycle as _lc
from . import metrics as _m
from . import slo as _slo
from .sampling import (SamplingParams, JsonMaskMachine, stop_trim,
                       derive_candidate_seed)

__all__ = ["DynamicBatcher", "ContinuousBatcher", "QueueFullError"]


class QueueFullError(MXNetError):
    """The batcher's bounded queue is full — backpressure, not failure.
    ``retry_after`` (seconds) rides to the HTTP surface as a
    ``Retry-After`` header."""

    def __init__(self, msg: str, retry_after: float = 1.0):
        super().__init__(msg)
        self.retry_after = float(retry_after)


class _Request:
    """One submitted batch: arrays + a latch the caller waits on."""

    __slots__ = ("arrays", "n", "sig", "event", "outputs", "error",
                 "t_submit", "deadline", "model", "request_id",
                 "trace_ctx")

    def __init__(self, arrays, n, sig, deadline=None, model="?",
                 request_id=None, trace_ctx=None):
        self.arrays = arrays
        self.n = n
        self.sig = sig
        self.event = threading.Event()
        self.outputs = None
        self.error = None
        self.t_submit = time.monotonic()
        self.deadline = deadline        # absolute monotonic, or None
        self.model = model
        self.request_id = request_id or _telemetry.new_request_id()
        self.trace_ctx = trace_ctx      # submitter's span, for the worker

    def fail(self, err: Exception) -> None:
        """Finish this request with ``err`` (idempotent).  The ONE
        protocol the batcher/watchdog/drain paths use to fail a request
        — subclasses with richer consumer channels (the generation
        request's token queue) override it so every waiter wakes, not
        just ``result()``."""
        if self.event.is_set():
            return
        self.error = err
        self.event.set()

    def result(self, timeout: Optional[float] = None) -> List:
        """Block for the scattered outputs; re-raises dispatch errors.
        The wait is additionally bounded by the request's own deadline —
        crossing it raises ``lifecycle.DeadlineExceeded`` (HTTP 504),
        a caller-supplied ``timeout`` alone raises ``TimeoutError``."""
        wait = timeout
        if self.deadline is not None:
            remaining = max(0.0, self.deadline - time.monotonic())
            wait = remaining if timeout is None else min(timeout, remaining)
        if not self.event.wait(wait):
            if self.deadline is not None \
                    and time.monotonic() >= self.deadline:
                _m.DEADLINE_EXCEEDED.inc(model=self.model, stage="wait")
                _telemetry.FAULT.publish(
                    site="serving.deadline", event="deadline", kind="wait",
                    model=self.model, request_id=self.request_id)
                raise _lc.DeadlineExceeded(
                    f"{self.model}: request {self.request_id} deadline "
                    f"exceeded after "
                    f"{time.monotonic() - self.t_submit:.3f}s")
            raise TimeoutError("inference request timed out")
        if self.error is not None:
            raise self.error
        return self.outputs


class DynamicBatcher:
    """Batch-coalescing front-end over one :class:`InferenceEngine`.

    Defaults come from the serving env knobs (``MXNET_SERVE_MAX_BATCH``
    = 32, ``MXNET_SERVE_MAX_DELAY_MS`` = 5.0, ``MXNET_SERVE_QUEUE`` =
    128, ``MXNET_SERVE_TIMEOUT_MS`` = 0 → deadline-free;
    docs/env_var.md)."""

    def __init__(self, engine, *, max_batch_size: Optional[int] = None,
                 max_delay_ms: Optional[float] = None,
                 queue_size: Optional[int] = None,
                 name: Optional[str] = None, retry_policy=None,
                 breaker: Optional[_lc.CircuitBreaker] = None,
                 default_timeout_ms: Optional[float] = None):
        self.engine = engine
        self.name = str(name or engine.name)
        if max_batch_size is None:
            max_batch_size = getenv_int("MXNET_SERVE_MAX_BATCH", 32)
        if engine.max_batch_size:
            max_batch_size = min(int(max_batch_size),
                                 int(engine.max_batch_size))
        self.max_batch_size = max(1, int(max_batch_size))
        if max_delay_ms is None:
            max_delay_ms = float(getenv("MXNET_SERVE_MAX_DELAY_MS", 5.0))
        self.max_delay = max(0.0, float(max_delay_ms)) / 1000.0
        if queue_size is None:
            queue_size = getenv_int("MXNET_SERVE_QUEUE", 128)
        self.queue_size = max(1, int(queue_size))
        if default_timeout_ms is None:
            default_timeout_ms = _lc.default_timeout_ms()
        self.default_timeout_ms = float(default_timeout_ms)
        self.retry_policy = retry_policy
        self.breaker = breaker if breaker is not None \
            else _lc.CircuitBreaker(self.name)
        self._queue: deque = deque()
        self._cv = threading.Condition()
        self._closed = False
        # worker health plane (all guarded by _cv): the generation
        # counter lets the watchdog replace a wedged worker — the old
        # thread notices its generation is stale and exits when (if) it
        # ever wakes up
        self._worker_gen = 0
        self._heartbeat = time.monotonic()
        self._busy_since: Optional[float] = None
        self._inflight: Optional[list] = None
        self._restarts = 0
        self._degraded = False
        self._avg_batch_seconds = 0.0
        self._thread = self._start_worker()

    def _start_worker(self) -> threading.Thread:
        # _cv NOT required; called from __init__ and (under _cv) from
        # check_worker/close — Thread.start is thread-safe either way
        t = threading.Thread(
            target=self._worker, args=(self._worker_gen,),
            name=f"mxtpu-serve-{self.name}-g{self._worker_gen}",
            daemon=True)
        t.start()
        return t

    # -- submit ---------------------------------------------------------
    @staticmethod
    def _signature(arrays):
        return tuple((tuple(a.shape[1:]), str(getattr(a, "dtype", "?")))
                     for a in arrays)

    def _estimate_wait_locked(self) -> float:
        """Queue-wait estimate for a newly admitted request, from the
        rows already queued and the EWMA batch service time (_cv held).
        0 until the first batch has been measured — admission control
        only ever sheds on *evidence* of a slow model."""
        if self._avg_batch_seconds <= 0.0:
            return 0.0
        rows = sum(r.n for r in self._queue)
        batches_ahead = rows // self.max_batch_size
        if self._busy_since is not None:    # current dispatch finishes first
            batches_ahead += 1
        return batches_ahead * self._avg_batch_seconds

    def submit_async(self, arrays: Sequence,
                     timeout_ms: Optional[float] = None,
                     request_id: Optional[str] = None) -> _Request:
        """Enqueue one request batch; returns a latch whose
        ``result()`` blocks for the outputs.  Raises
        :class:`QueueFullError` under backpressure,
        ``lifecycle.BreakerOpen`` while the model's breaker is OPEN,
        ``lifecycle.DeadlineExceeded`` when the queue-wait estimate
        already busts the request's budget, and ``MXNetError`` after
        :meth:`close`.  ``request_id`` (generated when absent) rides on
        every FAULT event the request triggers."""
        if request_id is None:
            request_id = _telemetry.new_request_id()
        _fault.inject("serving.queue", model=self.name,
                      request_id=request_id)
        self.breaker.allow()
        arrays = list(arrays)
        n = int(arrays[0].shape[0])
        if timeout_ms is None:
            timeout_ms = self.default_timeout_ms
        req = _Request(arrays, n, self._signature(arrays),
                       deadline=_lc.deadline_from_ms(timeout_ms),
                       model=self.name, request_id=request_id,
                       trace_ctx=_telemetry.tracer.current())
        with self._cv:
            if self._closed:
                raise MXNetError(f"batcher {self.name!r} is closed")
            if len(self._queue) >= self.queue_size:
                _m.REJECTED.inc(model=self.name)
                raise QueueFullError(
                    f"{self.name}: queue full ({self.queue_size} "
                    "pending) — backpressure")
            if req.deadline is not None:
                est = self._estimate_wait_locked()
                if time.monotonic() + est > req.deadline:
                    _m.DEADLINE_EXCEEDED.inc(model=self.name,
                                             stage="admission")
                    _telemetry.FAULT.publish(
                        site="serving.deadline", event="deadline",
                        kind="admission", model=self.name,
                        request_id=req.request_id)
                    raise _lc.DeadlineExceeded(
                        f"{self.name}: estimated queue wait {est:.3f}s "
                        "already exceeds the deadline of request "
                        f"{req.request_id}")
            self._queue.append(req)
            _m.QUEUE_DEPTH.set(len(self._queue), model=self.name)
            self._cv.notify_all()
        _m.REQUESTS.inc(model=self.name)
        return req

    def submit(self, arrays: Sequence,
               timeout: Optional[float] = None,
               timeout_ms: Optional[float] = None,
               request_id: Optional[str] = None) -> List:
        """Synchronous request: enqueue, wait, return per-row outputs
        (jax arrays, sliced to this request's rows).  ``timeout_ms`` is
        the end-to-end deadline budget (defaults from
        ``MXNET_SERVE_TIMEOUT_MS``); ``timeout`` additionally bounds
        just the wait.  Every outcome (including rejections and
        deadline busts) is recorded against the model's SLO window."""
        if request_id is None:
            request_id = _telemetry.new_request_id()
        t0 = time.monotonic()
        with _telemetry.trace_span("serve.request", cat="serving",
                                   model=self.name,
                                   request_id=request_id):
            try:
                out = self.submit_async(
                    arrays, timeout_ms=timeout_ms,
                    request_id=request_id).result(timeout)
            except Exception:
                _slo.tracker.record(self.name,
                                    time.monotonic() - t0, ok=False)
                raise
            _slo.tracker.record(self.name, time.monotonic() - t0, ok=True)
            return out

    # -- worker ---------------------------------------------------------
    def _current_gen(self) -> int:
        with self._cv:
            return self._worker_gen

    def _worker(self, gen: int):
        while True:
            if self._current_gen() != gen:
                return                  # replaced by the watchdog
            group = self._gather(gen)
            if group is None:
                return
            with self._cv:
                if gen == self._worker_gen:
                    self._busy_since = time.monotonic()
                    self._inflight = group
            self._run_group(group)
            with self._cv:
                if gen == self._worker_gen:
                    self._busy_since = None
                    self._inflight = None

    def _expire_locked(self, req: _Request) -> None:
        """Shed one already-expired request at gather time (_cv held;
        event.set() under the lock is fine — waiters wake after we
        release)."""
        _m.DEADLINE_EXCEEDED.inc(model=self.name, stage="queue")
        _telemetry.FAULT.publish(site="serving.deadline", event="deadline",
                                 kind="queue", model=self.name,
                                 request_id=req.request_id)
        req.fail(_lc.DeadlineExceeded(
            f"{self.name}: request {req.request_id} expired in queue "
            f"after {time.monotonic() - req.t_submit:.3f}s"))

    def _gather(self, gen: int):
        """Block for the head request, then coalesce until the batch is
        full, the head's delay deadline passes, or the next queued
        request is shape-incompatible (FIFO preserved).  Requests whose
        end-to-end deadline already expired are shed here (504), never
        dispatched.  Returns None when closed and drained or when this
        worker generation has been replaced."""
        with self._cv:
            while True:
                self._heartbeat = time.monotonic()
                while self._queue and self._queue[0].deadline is not None \
                        and self._queue[0].deadline <= self._heartbeat:
                    self._expire_locked(self._queue.popleft())
                if self._queue:
                    break
                if self._closed:
                    return None
                if gen != self._worker_gen:
                    return None
                self._cv.wait(0.05)
            head = self._queue.popleft()
            group, total = [head], head.n
            deadline = time.monotonic() + self.max_delay
            while total < self.max_batch_size:
                if self._queue:
                    nxt = self._queue[0]
                    if nxt.deadline is not None \
                            and nxt.deadline <= time.monotonic():
                        self._expire_locked(self._queue.popleft())
                        continue
                    if nxt.sig != head.sig \
                            or total + nxt.n > self.max_batch_size:
                        break
                    group.append(self._queue.popleft())
                    total += nxt.n
                    continue
                if self._closed:        # drain fast: no deadline wait
                    break
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    break
                self._cv.wait(remaining)
            _m.QUEUE_DEPTH.set(len(self._queue), model=self.name)
        return group

    def _run_group(self, group):
        import jax.numpy as jnp
        t0 = time.monotonic()
        for r in group:
            _m.QUEUE_WAIT.observe(t0 - r.t_submit)
        total = sum(r.n for r in group)
        _m.BATCH_SIZE.observe(total)
        _m.BATCHES.inc(model=self.name)
        rids = [r.request_id for r in group]
        # nest the batch span under the span of the request that headed
        # the batch (cross-thread attach); `links` carries EVERY rider's
        # request id so one grep finds the dispatch a request rode on
        head_ctx = group[0].trace_ctx
        attach = _telemetry.tracer.attach(head_ctx) \
            if head_ctx is not None else contextlib.nullcontext()
        with attach, \
                _telemetry.trace_span("serve.batch", cat="serving",
                                      model=self.name,
                                      requests=len(group), rows=total,
                                      links=rids):
            try:
                def _val(a):
                    return a._data if isinstance(a, NDArray) \
                        else jnp.asarray(a)
                if len(group) == 1:
                    ins = group[0].arrays
                else:
                    ins = [jnp.concatenate(
                        [_val(r.arrays[i]) for r in group], axis=0)
                        for i in range(len(group[0].arrays))]

                def run():
                    _fault.inject("serving.infer", model=self.name,
                                  request_ids=rids)
                    return self.engine.predict(ins)

                try:
                    outs = _fault.retry_call(run, site="serving.infer",
                                             policy=self.retry_policy)
                except Exception as e:
                    self._fallback(group, e)
                    return
                off = 0
                for r in group:
                    r.outputs = [o[off:off + r.n] for o in outs]
                    off += r.n
                dt = time.monotonic() - t0
                self._avg_batch_seconds = dt \
                    if self._avg_batch_seconds <= 0.0 \
                    else 0.8 * self._avg_batch_seconds + 0.2 * dt
                self._degraded = False
                self.breaker.record_success()
            except Exception as e:      # worker must survive anything
                _telemetry.FAULT.publish(
                    site="serving.worker", event="crash",
                    kind=type(e).__name__, model=self.name,
                    request_ids=rids)
                for r in group:
                    r.error = e
            finally:
                done = time.monotonic()
                for r in group:
                    # the watchdog may already have failed (and woken)
                    # this rider — never double-count or clobber it
                    if not r.event.is_set():
                        _m.LATENCY.observe(done - r.t_submit)
                        r.event.set()

    def _fallback(self, group, err):
        """Batched dispatch failed after retries: run each request on
        its own so one poisoned batch can't fail every rider.  Singles
        bypass the ``serving.infer`` fault site — the plan already fired
        on the batch attempts.  Counts one consecutive failure on the
        circuit breaker (enough of these in a row trip it OPEN)."""
        _telemetry.FAULT.publish(site="serving.infer", event="fallback",
                                 kind=type(err).__name__,
                                 requests=len(group), model=self.name,
                                 request_ids=[r.request_id
                                              for r in group])
        _m.FALLBACKS.inc(model=self.name)
        self.breaker.record_failure(f"batch dispatch failed: "
                                    f"{type(err).__name__}")
        for r in group:
            try:
                r.outputs = self.engine.predict(r.arrays)
            except Exception as e:
                r.error = e

    # -- watchdog plane -------------------------------------------------
    def check_worker(self, hang_seconds: Optional[float] = None):
        """Detect a dead or hung worker (driven by
        ``lifecycle.Watchdog``, callable directly).  On detection: fail
        the in-flight group's riders with ``lifecycle.RequestAborted``,
        restart the worker on a fresh generation, trip the breaker and
        mark the model DEGRADED.  Returns the reason (``"died"`` /
        ``"hung"``) when a restart happened, else None.

        ``hang_seconds <= 0`` disables hang detection (dead-worker
        detection stays on)."""
        if hang_seconds is None:
            hang_seconds = _lc.default_hang_seconds()
        now = time.monotonic()
        with self._cv:
            if self._closed:
                return None
            if not self._thread.is_alive():
                reason = "died"
            elif hang_seconds > 0 and self._busy_since is not None \
                    and now - self._busy_since > float(hang_seconds):
                reason = "hung"
            else:
                return None
            failed = self._inflight or []
            self._inflight = None
            self._busy_since = None
            self._worker_gen += 1
            self._restarts += 1
            self._degraded = True
            self._thread = self._start_worker()
            self._cv.notify_all()
        for r in failed:
            r.fail(_lc.RequestAborted(
                f"{self.name}: batcher worker {reason}; request "
                f"{r.request_id} failed by the watchdog — retry on "
                "another replica"))
        # the watchdog event goes out BEFORE the breaker trip: the
        # flight recorder dumps on both, and the restart (with its rider
        # request ids) is the primary artifact of this incident
        _m.WATCHDOG_RESTARTS.inc(model=self.name)
        _telemetry.FAULT.publish(site="serving.worker", event="watchdog",
                                 kind=reason, model=self.name,
                                 riders=len(failed),
                                 request_ids=[r.request_id
                                              for r in failed])
        self.breaker.trip(f"worker {reason}")
        return reason

    @property
    def state(self) -> str:
        """This model's serving state (``lifecycle.SERVING`` /
        ``DEGRADED`` / ``UNHEALTHY`` / ``DRAINING``)."""
        with self._cv:
            if self._closed:
                return _lc.DRAINING
            worker_dead = not self._thread.is_alive()
        bs = self.breaker.state
        if worker_dead or bs == _lc.OPEN:
            return _lc.UNHEALTHY
        if self._degraded or bs == _lc.HALF_OPEN:
            return _lc.DEGRADED
        return _lc.SERVING

    @property
    def restarts(self) -> int:
        with self._cv:
            return self._restarts

    # -- lifecycle ------------------------------------------------------
    @property
    def pending(self) -> int:
        """Requests queued or riding the in-flight dispatch."""
        with self._cv:
            return len(self._queue) + len(self._inflight or ())

    @property
    def idle(self) -> bool:
        with self._cv:
            return not self._queue and self._busy_since is None

    def active_request_ids(self) -> dict:
        """Request ids currently queued / riding the in-flight dispatch
        (the flight recorder's "active requests" dump section)."""
        with self._cv:
            return {"queued": [r.request_id for r in self._queue],
                    "inflight": [r.request_id
                                 for r in (self._inflight or ())]}

    def close(self, drain: bool = True, timeout: float = 30.0) -> None:
        """Stop intake.  ``drain=True`` (default) lets the worker finish
        everything already queued; ``drain=False`` fails pending
        requests immediately.  If the worker cannot finish inside
        ``timeout`` seconds (a wedged dispatch), every still-pending
        request is failed with a clear error instead of being left
        blocked on an event nobody will ever set.  Idempotent."""
        with self._cv:
            self._closed = True
            dropped = []
            if not drain:
                dropped = list(self._queue)
                self._queue.clear()
            self._cv.notify_all()
        for r in dropped:
            r.fail(MXNetError(f"batcher {self.name!r} closed"))
        self._thread.join(timeout=timeout)
        if self._thread.is_alive():
            # drain budget blown: the worker is wedged in a dispatch.
            # Strand nobody — fail everything still pending and retire
            # this worker generation so the zombie exits if it wakes.
            with self._cv:
                self._worker_gen += 1
                stranded = list(self._queue)
                self._queue.clear()
                stranded.extend(self._inflight or ())
                self._inflight = None
                self._busy_since = None
            for r in stranded:
                r.fail(_lc.RequestAborted(
                    f"batcher {self.name!r}: drain timed out after "
                    f"{timeout}s; request {r.request_id} abandoned"))
        with self._cv:
            _m.QUEUE_DEPTH.set(0, model=self.name)

    @property
    def closed(self) -> bool:
        return self._closed

    def stats(self) -> dict:
        with self._cv:
            depth = len(self._queue)
            restarts = self._restarts
        return {"model": self.name, "queue_depth": depth,
                "queue_size": self.queue_size,
                "max_batch_size": self.max_batch_size,
                "max_delay_ms": self.max_delay * 1000.0,
                "default_timeout_ms": self.default_timeout_ms,
                "closed": self._closed,
                "state": self.state,
                "breaker": self.breaker.state,
                "watchdog_restarts": restarts,
                "buckets": list(self.engine.buckets),
                "compiled_programs": self.engine.compiled_programs()}


# ===========================================================================
# ContinuousBatcher — per-slot join/leave generation serving
# ===========================================================================

class _GenRequest:
    """One generation request: a prompt, a token budget, and a stream of
    emitted tokens.  Unlike :class:`_Request` (one dispatch, one latch),
    a generation request spans MANY dispatches: tokens arrive one per
    decode step on ``_q`` and accumulate in ``tokens_out``; ``event``
    fires once, at finish (done / error / cancel)."""

    __slots__ = ("tokens", "n", "budget", "eos_id", "event", "error",
                 "tokens_out", "t_submit", "t_first", "t_emit",
                 "deadline", "model", "request_id", "trace_ctx",
                 "slot", "_q", "_cancelled",
                 "accepted_tokens", "draft_tokens",
                 "sampling", "seed", "logprobs_n", "logprobs_out",
                 "stops", "_machine")

    def __init__(self, tokens, budget, eos_id=None, deadline=None,
                 model="?", request_id=None, trace_ctx=None,
                 sampling=None):
        import queue as _pyqueue
        self.tokens = tokens            # prompt, np int32 1-D
        self.n = int(tokens.shape[0])
        self.budget = int(budget)       # max tokens to emit
        self.eos_id = eos_id
        # sampling plane (serving/sampling.py): the validated
        # SamplingParams (None: greedy), the EFFECTIVE seed (client's or
        # server-generated — echoed so any sampled response replays),
        # the clamped per-token logprobs top-N with its output list
        # (entry i describes tokens_out[i]; appended BEFORE the token is
        # queued so the streaming thread may index it immediately), the
        # stop token-id sequences, and the constrained-output machine
        self.sampling = sampling
        self.seed = sampling.seed if sampling is not None else None
        self.logprobs_n = int(sampling.logprobs) if sampling else 0
        self.logprobs_out: List[dict] = []
        self.stops = tuple(sampling.stop) if sampling else ()
        self._machine: Optional[JsonMaskMachine] = None
        self.event = threading.Event()
        self.error = None
        self.tokens_out: List[int] = []
        self.t_submit = time.monotonic()
        self.t_first: Optional[float] = None
        self.t_emit = self.t_submit     # last emission (token latency)
        self.deadline = deadline
        self.model = model
        self.request_id = request_id or _telemetry.new_request_id()
        self.trace_ctx = trace_ctx
        self.slot: Optional[int] = None
        self._q = _pyqueue.Queue()
        self._cancelled = False
        # speculative-decoding accounting (stay 0 on the plain path):
        # draft_tokens counts tokens the draft proposed for THIS request,
        # accepted_tokens counts how many of those the target kept
        self.accepted_tokens = 0
        self.draft_tokens = 0

    # -- producer side (worker thread) ----------------------------------
    def _emit(self, tok: int) -> float:
        now = time.monotonic()
        if self.t_first is None:
            self.t_first = now
        gap = now - self.t_emit
        _m.TOKEN_LATENCY.observe(gap)
        self.t_emit = now
        self.tokens_out.append(int(tok))
        self._q.put(("tok", int(tok)))
        return gap

    def _emit_burst(self, toks) -> float:
        """Append a whole decode burst and flush it to the stream as
        individual ``("tok", t)`` events under ONE queue-lock
        acquisition — a k-token burst costs one notify pass instead of
        k ``put()`` round-trips on the consumer's mutex.  ``Queue`` is
        unbounded here so skipping ``not_full`` is safe; the manual
        bookkeeping mirrors ``Queue.put`` exactly (``not_empty`` shares
        ``mutex``).  The token-latency histogram observes what the
        client receives: the whole gap since the last emission on the
        burst's first token and none on the rest (as the speculative
        path's bursts do).  Returns that whole gap."""
        now = time.monotonic()
        if self.t_first is None:
            self.t_first = now
        n = len(toks)
        gap = now - self.t_emit
        if n:
            _m.TOKEN_LATENCY.observe(gap)
            for _ in range(n - 1):
                _m.TOKEN_LATENCY.observe(0.0)
        self.t_emit = now
        self.tokens_out.extend(toks)
        q = self._q
        with q.mutex:
            q.queue.extend(("tok", t) for t in toks)
            q.unfinished_tasks += n
            q.not_empty.notify(n)
        return gap

    def _finish(self, error=None) -> None:
        if self.event.is_set():
            return
        self.error = error
        self.event.set()
        self._q.put(("end", error))

    def fail(self, err: Exception) -> None:
        self._finish(err)

    # -- consumer side --------------------------------------------------
    def cancel(self) -> None:
        """Ask the worker to free this request's slot at the next decode
        step boundary.  Safe from any thread; idempotent."""
        self._cancelled = True

    @property
    def done(self) -> bool:
        return self.event.is_set()

    def _bounded_wait(self, timeout):
        wait = timeout
        if self.deadline is not None:
            # small grace so the worker's own boundary check (which
            # frees the slot and stamps stage="decode") wins the race
            remaining = max(0.0, self.deadline - time.monotonic()) + 0.25
            wait = remaining if timeout is None else min(timeout,
                                                         remaining)
        return wait

    def result(self, timeout: Optional[float] = None) -> List[int]:
        """Block until generation finishes; returns ALL emitted tokens.
        Re-raises worker-side errors (deadline, abort, dispatch
        failure); a bare ``timeout`` raises ``TimeoutError``."""
        if not self.event.wait(self._bounded_wait(timeout)):
            if self.deadline is not None \
                    and time.monotonic() >= self.deadline:
                raise _lc.DeadlineExceeded(
                    f"{self.model}: generation request {self.request_id} "
                    "deadline exceeded")
            raise TimeoutError("generation request timed out")
        if self.error is not None:
            raise self.error
        return list(self.tokens_out)

    def stream(self, timeout: Optional[float] = None):
        """Yield tokens as the worker emits them.  Closing the generator
        before the end (client disconnect) cancels the request — the
        slot frees on the next step boundary.  Worker-side errors
        re-raise here; ``lifecycle.Cancelled`` is swallowed (the
        consumer asked for it)."""
        import queue as _pyqueue
        try:
            while True:
                try:
                    kind, val = self._q.get(
                        timeout=self._bounded_wait(timeout))
                except _pyqueue.Empty:
                    if self.deadline is not None \
                            and time.monotonic() >= self.deadline:
                        raise _lc.DeadlineExceeded(
                            f"{self.model}: generation request "
                            f"{self.request_id} deadline exceeded")
                    raise TimeoutError("generation stream timed out")
                if kind == "tok":
                    yield val
                    continue
                if val is not None and not isinstance(val, _lc.Cancelled):
                    raise val
                return
        finally:
            if not self.event.is_set():
                self.cancel()


class _MultiGenRequest:
    """n>1 candidate fan-out: one handle over ``n`` independent child
    :class:`_GenRequest` streams, each decoding in its own slot under a
    derived seed (candidate 0 keeps the request seed, so an ``n=1``
    replay of the echoed seed reproduces it byte-for-byte).  The
    ``result()``/``request_id`` surface stays _GenRequest-shaped for
    back-compat — ``result()`` returns candidate 0's tokens,
    ``results()`` all of them."""

    def __init__(self, children, request_id: str):
        self.children = list(children)
        self.request_id = request_id

    @property
    def seed(self):
        return self.children[0].seed

    @property
    def request_ids(self):
        return [r.request_id for r in self.children]

    @property
    def accepted_tokens(self) -> int:
        return sum(r.accepted_tokens for r in self.children)

    @property
    def draft_tokens(self) -> int:
        return sum(r.draft_tokens for r in self.children)

    @property
    def logprobs_n(self) -> int:
        return self.children[0].logprobs_n

    @property
    def logprobs_out(self):
        return self.children[0].logprobs_out

    @property
    def done(self) -> bool:
        return all(r.done for r in self.children)

    def cancel(self) -> None:
        for r in self.children:
            r.cancel()

    def result(self, timeout: Optional[float] = None) -> List[int]:
        return self.results(timeout)[0]

    def results(self, timeout: Optional[float] = None) -> List[List[int]]:
        """Block for every candidate; returns their token lists in
        candidate order.  The first child error re-raises (remaining
        candidates are cancelled — a half-failed fan-out has no
        well-defined response)."""
        out = []
        try:
            for r in self.children:
                out.append(r.result(timeout))
        except Exception:
            self.cancel()
            raise
        return out


class ContinuousBatcher(DynamicBatcher):
    """Continuous-batching front-end over one
    :class:`serving.engine.GenerationEngine`.

    The parent's core invariant — gather a FIFO group, dispatch ONCE,
    scatter — cannot serve autoregressive decode: requests finish at
    different times and new ones must not wait for the batch to drain.
    This subclass replaces the worker loop with per-slot join/leave over
    the engine's preallocated KV cache:

    * each iteration is one STEP BOUNDARY: free every slot whose request
      finished, was cancelled, or crossed its deadline
      (``mxtpu_serve_deadline_exceeded{stage="decode"}``); admit queued
      requests into the freed slots (one ``prefill`` dispatch each,
      emitting the first token); then advance ALL live slots with a
      single ``decode`` dispatch — one token per step, or up to
      ``engine.scan_steps`` tokens when :meth:`_burst_gate` sees
      steady state (no queued joins, cancels, or near deadlines) and
      the scanned ``decode_burst`` program takes over;
    * tokens stream back per-request as they are produced
      (:meth:`_GenRequest.stream`), so a late-arriving request emits its
      first token while earlier requests are still decoding — the
      continuous-admission property ``generate_smoke`` asserts;
    * everything the one-shot path had keeps working: backpressure,
      breaker, ``serving.queue``/``serving.infer`` fault sites (a
      ``hang`` during decode drills the watchdog; the restarted worker
      RESETS the cache — donated buffers a dying dispatch consumed are
      not trusted), request ids on every event, SLO accounting per
      finished generation, and ``serve.batch`` spans per decode step
      with ``slot.join``/``slot.leave`` child events so ``/trace``
      shows a request's whole decode lifetime;
    * the worker thread is in exactly one named phase of its loop at
      any time (``metrics.PHASES``: wait, admit, prefill_host,
      prefill_wait, operands, decode_wait, emit), and inside a host
      phase in at most one named step (``metrics.loop_step``).  The loop
      is cut once, where the work happens, and every boundary feeds
      ``mxtpu_serve_loop_seconds{phase}``, the thread's CPU time beside
      it, ``mxtpu_serve_loop_step_seconds{phase,step}`` and — while the
      tracer is active — a ``serve.*`` span, which a profiler capture
      also puts into the device trace (docs/observability.md
      "Worker-loop phases").
    """

    def __init__(self, engine, token_strs=None, **kw):
        kw.setdefault("max_batch_size", engine.max_slots)
        self._slots: List[Optional[_GenRequest]] = \
            [None] * int(engine.max_slots)
        self._step = 0
        self._tokens_emitted = 0
        # tokens by the dispatch that produced them, and the worker
        # thread's seconds by phase of its loop (metrics.LoopClock):
        # wall, CPU, and by (phase, step) inside a phase
        self._tokens_by_path = dict.fromkeys(
            ("prefill", "step", "burst", "spec"), 0)
        self._loop_seconds = dict.fromkeys(_m.PHASES, 0.0)
        self._loop_cpu_seconds = dict.fromkeys(_m.PHASES, 0.0)
        self._loop_step_seconds = {}
        self._peak_slots = 0
        # sampling plane: token id -> string mapping for the
        # constrained-output (json_mode) machine (default: byte-level,
        # materialized lazily on the first constrained request), stop
        # limits, and host-side stop/trim accounting
        self._token_strs = list(token_strs) if token_strs is not None \
            else None
        self._max_stops = max(1, getenv_int("MXNET_SAMPLING_MAX_STOPS",
                                            4))
        self._stop_hits = 0
        self._stop_trimmed = 0
        # speculative decoding totals (see serving/metrics.py): verify
        # dispatches, tokens emitted from them, and draft proposals made
        self._spec_dispatches = 0
        self._spec_slot_steps = 0   # (live slot, dispatch) pairs
        self._spec_emitted = 0
        self._spec_accepted = 0
        self._spec_drafted = 0
        # dispatch economy: one batcher step = ONE target-model dispatch
        # (draft decodes ride on the draft model's own ledger).  Tokens
        # are per-slot-normalized, so per-step decode reads exactly 1.0,
        # the scanned burst path approaches 1/scan_steps at steady
        # state, and speculation reads 1/tokens-per-slot-per-dispatch
        # (< 1.0 when the draft earns its keep) — docs/observability.md.
        self._dpt_dispatches = 0
        self._dpt_tokens = 0.0
        # multi-token burst dispatches taken (engine.scan_steps >= 1 and
        # _burst_ready said steady state) — drives dispatches_per_token
        # toward 1/k; docs/serving.md "Multi-token decode bursts"
        self._burst_dispatches = 0
        self._kv_starved_sweeps = 0
        self._kv_starve_threshold = max(1, getenv_int(
            "MXNET_SERVE_KV_STARVE_SWEEPS", 3))
        # health plane (health.py): last folded decode-step stats and the
        # running nonfinite-generation count, surfaced in stats()/health
        self._decode_health_last: Optional[dict] = None
        self._nonfinite_generations = 0
        super().__init__(engine, **kw)

    # -- KV-capacity starvation (the ``kv:<model>`` readiness blocker) --
    def check_worker(self, hang_seconds: Optional[float] = None):
        """The watchdog sweep doubles as the KV-starvation sampler: a
        paged pool with zero free blocks for
        ``MXNET_SERVE_KV_STARVE_SWEEPS`` consecutive sweeps flips
        :attr:`kv_starved`, which surfaces as a ``kv:<model>`` blocker
        on ``/readyz`` — the router routes generation to replicas with
        capacity instead of eating this replica's 429s.  One free block
        resets the count (starvation must be sustained, not a blip)."""
        pool = getattr(self.engine, "pool", None)
        if pool is not None:
            if pool.free_blocks == 0:
                self._kv_starved_sweeps += 1
                if self._kv_starved_sweeps == self._kv_starve_threshold:
                    _telemetry.FAULT.publish(
                        site="serving.kv", event="starved",
                        kind="exhausted", model=self.name,
                        sweeps=self._kv_starved_sweeps)
            else:
                self._kv_starved_sweeps = 0
        return super().check_worker(hang_seconds)

    @property
    def kv_starved(self) -> bool:
        """True while the paged BlockPool has been fully exhausted for
        ``MXNET_SERVE_KV_STARVE_SWEEPS`` consecutive watchdog sweeps."""
        return self._kv_starved_sweeps >= self._kv_starve_threshold

    # admission control: the parent's rows//max_batch estimate is
    # meaningless for multi-dispatch requests — deadlines are enforced
    # at queue-shed and at every decode boundary instead
    def _estimate_wait_locked(self) -> float:
        return 0.0

    # -- submit ---------------------------------------------------------
    def _token_strings(self):
        """Token id -> string mapping for the constrained-output
        machine (ctor ``token_strs``; default byte-level, materialized
        on the first constrained request)."""
        if self._token_strs is None:
            vs = int(getattr(self.engine, "vocab_size", 0) or 0)
            self._token_strs = [chr(i) for i in range(vs)]
        return self._token_strs

    def submit_async(self, tokens, max_new_tokens: int = 32,
                     timeout_ms: Optional[float] = None,
                     request_id: Optional[str] = None,
                     eos_id: Optional[int] = None,
                     sampling: Optional[SamplingParams] = None):
        """Enqueue one generation request; returns a handle whose
        ``stream()`` yields tokens as they are produced and whose
        ``result()`` blocks for the full list.  Raises
        :class:`QueueFullError` under backpressure, ``BreakerOpen``
        while the breaker is OPEN, ``ValueError`` for an unservable
        prompt/budget or out-of-range sampling parameters.

        ``sampling`` (None: greedy) is validated here, its ``logprobs``
        clamped to the engine's baked top-N, and — for a sampled
        request without a client seed — an effective seed is generated
        and stored on the handle (``req.seed``) so the response is
        replayable.  ``sampling.n > 1`` fans out into ``n`` independent
        single-candidate children over distinct slots (derived seeds;
        candidate 0 keeps the request seed) behind one
        :class:`_MultiGenRequest` handle."""
        from dataclasses import replace as _dc_replace
        if request_id is None:
            request_id = _telemetry.new_request_id()
        if sampling is not None:
            sampling = sampling.validate(
                max_stops=self._max_stops,
                max_n=int(self.engine.max_slots))
            lp_cap = int(getattr(self.engine, "logprobs_topn", 0) or 0)
            if sampling.logprobs > lp_cap:
                sampling = _dc_replace(sampling, logprobs=lp_cap)
            if sampling.sampled and sampling.seed is None:
                import os as _os
                sampling = _dc_replace(
                    sampling,
                    seed=int.from_bytes(_os.urandom(8), "big") >> 1)
        if sampling is not None and sampling.n > 1:
            base = sampling.seed
            children: List[_GenRequest] = []
            try:
                for i in range(sampling.n):
                    child = _dc_replace(
                        sampling, n=1,
                        seed=derive_candidate_seed(base, i)
                        if base is not None else None)
                    children.append(self._submit_one(
                        tokens, max_new_tokens, timeout_ms=timeout_ms,
                        request_id=f"{request_id}.{i}", eos_id=eos_id,
                        sampling=child))
            except Exception:
                for c in children:   # no half-admitted fan-outs
                    c.cancel()
                raise
            return _MultiGenRequest(children, request_id)
        return self._submit_one(tokens, max_new_tokens,
                                timeout_ms=timeout_ms,
                                request_id=request_id, eos_id=eos_id,
                                sampling=sampling)

    def _submit_one(self, tokens, max_new_tokens: int = 32,
                    timeout_ms: Optional[float] = None,
                    request_id: Optional[str] = None,
                    eos_id: Optional[int] = None,
                    sampling: Optional[SamplingParams] = None) \
            -> _GenRequest:
        import numpy as _np
        _fault.inject("serving.queue", model=self.name,
                      request_id=request_id)
        self.breaker.allow()
        toks = _np.asarray(tokens, _np.int32).reshape(-1)
        n = int(toks.shape[0])
        max_len = int(self.engine.max_len)
        if n < 1:
            raise ValueError(f"{self.name}: empty prompt")
        if n > max_len - 1:
            raise ValueError(
                f"{self.name}: prompt length {n} leaves no room to "
                f"generate (max_len {max_len})")
        budget = min(int(max_new_tokens), max_len - n)
        if budget < 1:
            raise ValueError(
                f"{self.name}: max_new_tokens must be >= 1")
        if timeout_ms is None:
            timeout_ms = self.default_timeout_ms
        req = _GenRequest(toks, budget, eos_id=eos_id,
                          deadline=_lc.deadline_from_ms(timeout_ms),
                          model=self.name, request_id=request_id,
                          trace_ctx=_telemetry.tracer.current(),
                          sampling=sampling)
        if sampling is not None and sampling.json_mode:
            req._machine = JsonMaskMachine(self._token_strings())
            _m.SAMPLE_CONSTRAINED.inc(model=self.name)
        with self._cv:
            if self._closed:
                raise MXNetError(f"batcher {self.name!r} is closed")
            # capacity-aware backpressure: a queue the KV cache can never
            # drain fast enough is just a slow 504 — bound admissions by
            # how many streams of THIS request's footprint the cache
            # sustains, and tell the client when to come back.  The
            # footprint is counted as if nothing were shared (a prompt's
            # cached prefix is known only once it is hashed, at
            # admission), so the bound never falls under one request a
            # slot: those the engine has a slot for, and a deployment
            # whose streams share most of a long prompt holds them all
            allowed = self.queue_size
            cap_fn = getattr(self.engine, "kv_capacity_tokens", None)
            if cap_fn is not None:
                slots = int(self.engine.max_slots)
                streams = max(1, min(slots, int(cap_fn()) // (n + budget)))
                allowed = min(allowed, max(4 * streams, slots))
            if len(self._queue) >= allowed:
                _m.REJECTED.inc(model=self.name)
                retry = max(1.0, min(30.0,
                                     self._avg_batch_seconds * budget))
                raise QueueFullError(
                    f"{self.name}: queue full ({len(self._queue)} "
                    f"pending, {allowed} admitted for this request "
                    "size) — backpressure", retry_after=retry)
            self._queue.append(req)
            _m.QUEUE_DEPTH.set(len(self._queue), model=self.name)
            self._cv.notify_all()
        _m.REQUESTS.inc(model=self.name)
        _m.SAMPLED_REQUESTS.inc(
            model=self.name,
            mode="sampled" if (sampling is not None and sampling.sampled)
            else "greedy")
        return req

    def submit(self, tokens, max_new_tokens: int = 32,
               timeout: Optional[float] = None,
               timeout_ms: Optional[float] = None,
               request_id: Optional[str] = None,
               eos_id: Optional[int] = None,
               sampling: Optional[SamplingParams] = None) -> List[int]:
        """Synchronous generation: enqueue, wait, return all emitted
        tokens.  (SLO accounting happens worker-side at finish, for the
        streaming and sync paths alike; admission failures are recorded
        here.)"""
        if request_id is None:
            request_id = _telemetry.new_request_id()
        with _telemetry.trace_span("serve.request", cat="serving",
                                   model=self.name,
                                   request_id=request_id):
            try:
                req = self.submit_async(
                    tokens, max_new_tokens, timeout_ms=timeout_ms,
                    request_id=request_id, eos_id=eos_id,
                    sampling=sampling)
            except Exception:
                _slo.tracker.record(self.name, 0.0, ok=False)
                raise
            return req.result(timeout)

    # -- worker: the continuous loop ------------------------------------
    def _worker(self, gen: int):
        # this thread's phase clock; a replaced worker brings its own,
        # so one that comes back from a hang cannot disturb it
        _m.LoopClock(self.name, self._loop_seconds, self._loop_cpu_seconds,
                     self._loop_step_seconds).bind()
        # a replaced worker's slots (and the donated cache a dying
        # dispatch may have consumed) are not trusted: start clean
        with self._cv:
            stale = [r for r in self._slots if r is not None]
            self._slots = [None] * int(self.engine.max_slots)
        if stale or gen > 0:
            self.engine.reset()
        for r in stale:     # watchdog already failed inflight riders
            r._finish(_lc.RequestAborted(
                f"{self.name}: worker replaced; request {r.request_id} "
                "aborted"))
        while True:
            leavers, joins, live = self._boundary(gen)
            if leavers is None:
                return
            if not (leavers or joins or live):
                continue    # woke empty; next wait happens in _boundary
            self._run_step(gen, leavers, joins)
            with self._cv:
                if gen == self._worker_gen:
                    self._busy_since = None
                    self._inflight = None

    def _boundary(self, gen: int):
        """One step boundary, under ``_cv``: collect slots to free
        (finished requests were freed eagerly in ``_run_step``; here we
        catch cancels and deadline expiries), admit queued requests into
        free slots, and decide whether there is work.  Returns
        ``(leavers, joins, live)`` — or ``(None, None, None)`` when this
        worker generation is done (closed+drained or replaced).  With
        nothing to do the worker sleeps on ``_cv``: the loop's ``wait``
        phase, the only one in which it is idle (a ``serve.wait`` span a
        poll while a profiler capture runs).  Taking ``_cv`` is the
        ``lock`` step of ``admit``: handler threads hold it to submit."""
        with _m.loop_step("lock", "serve.admit.lock"):
            self._cv.acquire()
        try:
            while True:
                out = self._admit_locked(gen)
                if out is not None:
                    return out
                # the idle worker's span exists for a capture's idle gaps
                # alone: a root every 50 ms would push the last requests'
                # spans out of /trace and the flight ring on a quiet
                # server whose telemetry is on
                with _m.loop_phase(
                        "wait", "serve.wait"
                        if _telemetry.tracer.annotate is not None else None):
                    self._cv.wait(0.05)
        finally:
            self._cv.release()

    def _admit_locked(self, gen: int):
        """One pass of :meth:`_boundary`; None when there is nothing to
        do but wait.  The loop's ``admit`` phase — a pass that finds a
        queue or a live slot runs under a ``serve.admit`` span; the idle
        worker's polls open none."""
        busy = bool(self._queue) or any(r is not None for r in self._slots)
        with _m.loop_phase("admit", "serve.admit" if busy else None,
                           step=self._step + 1):
            if gen != self._worker_gen:
                return None, None, None
            now = time.monotonic()
            self._heartbeat = now
            leavers = []
            for s, r in enumerate(self._slots):
                if r is None:
                    continue
                if r._cancelled:
                    leavers.append((s, r, "cancelled"))
                    self._slots[s] = None
                elif r.deadline is not None and r.deadline <= now:
                    leavers.append((s, r, "deadline"))
                    self._slots[s] = None
            while self._queue \
                    and self._queue[0].deadline is not None \
                    and self._queue[0].deadline <= now:
                self._expire_locked(self._queue.popleft())
            joins = []
            free = [s for s, r in enumerate(self._slots)
                    if r is None]
            can = getattr(self.engine, "can_admit", None)
            est = getattr(self.engine, "reserve_estimate", None)
            reserved = 0    # blocks promised to earlier admits
            while self._queue and free:
                req = self._queue[0]
                if can is not None and not can(
                        req.tokens, req.n + req.budget, reserved):
                    break   # head-of-line waits for blocks to free
                self._queue.popleft()
                if est is not None:
                    reserved += est(req.n + req.budget)
                slot = free.pop(0)
                req.slot = slot
                self._slots[slot] = req
                joins.append((slot, req))
                self._observe_queue_wait(req, now)
            live = [(s, r) for s, r in enumerate(self._slots)
                    if r is not None]
            _m.QUEUE_DEPTH.set(len(self._queue), model=self.name)
            _m.SLOTS_IN_USE.set(len(live), model=self.name)
            self._peak_slots = max(self._peak_slots, len(live))
            if leavers or joins or live:
                self._busy_since = now
                self._inflight = [r for _, r in live]
                return leavers, joins, live
            if self._closed and not self._queue:
                return None, None, None
            return None

    def _observe_queue_wait(self, req: _GenRequest, now: float):
        """Admission: the request's wait in the queue, into the
        histogram and — for a traced request — as a ``serve.queue`` span
        under its ``serve.request`` (no thread sat in that interval, so
        it is recorded with explicit times)."""
        wait = now - req.t_submit
        _m.QUEUE_WAIT.observe(wait)
        if req.trace_ctx is not None:
            t1 = time.perf_counter()
            _telemetry.tracer.record(
                "serve.queue", t1 - wait, t1, parent=req.trace_ctx,
                cat="serving", model=self.name,
                request_id=req.request_id)

    def _run_step(self, gen: int, leavers, joins):
        """One continuous-batching step OUTSIDE the lock: emit
        ``slot.leave`` events for boundary leavers, prefill the joins
        (first token each), then ONE decode dispatch advancing every
        live slot.  The ``serve.batch`` span wraps the whole step; its
        ``links`` carry every live request id, ``path`` the decode
        dispatch taken (``burst|step|spec``) and, when that was not a
        burst, ``gate`` the reason the burst gate said no."""
        self._step += 1
        with self._cv:
            live = [(s, r) for s, r in enumerate(self._slots)
                    if r is not None]
        rids = [r.request_id for _, r in live]
        head_ctx = live[0][1].trace_ctx if live else None
        attach = _telemetry.tracer.attach(head_ctx) \
            if head_ctx is not None else contextlib.nullcontext()
        with attach, \
                _telemetry.trace_span("serve.batch", cat="serving",
                                      model=self.name, step=self._step,
                                      slots=len(live),
                                      links=rids) as batch:
            if leavers:
                with _m.loop_phase("emit", "serve.emit", step=self._step), \
                        _m.loop_step("finish", "serve.emit.finish"):
                    for slot, req, reason in leavers:
                        self._leave(slot, req, reason)
            for slot, req in joins:
                self._join(slot, req, gen)
            with self._cv:
                live = [(s, r) for s, r in enumerate(self._slots)
                        if r is not None]
            if not live:
                return
            if getattr(self.engine, "draft", None) is not None \
                    and not any(r._machine is not None for _, r in live):
                path, gate = "spec", None
            else:
                gate = self._burst_gate(live)
                path = "step" if gate else "burst"
            if gate:
                _m.BURST_GATE.inc(model=self.name, reason=gate)
            if batch is not None:
                batch.attrs["path"] = path
                if gate:
                    batch.attrs["gate"] = gate
            {"spec": self._spec_once, "burst": self._decode_burst_once,
             "step": self._decode_once}[path](gen, live)

    def _join(self, slot: int, req: _GenRequest, gen: int):
        """Admit one request mid-flight: its prefill dispatch runs
        between decode steps and emits the first token.  Loop phases:
        ``prefill_host`` up to the program's enqueue, ``prefill_wait``
        (inside the engine) for the pull of the first token, ``emit``
        for handing it to the stream."""
        with _telemetry.trace_span("slot.join", cat="serving",
                                   model=self.name, slot=slot,
                                   request_id=req.request_id,
                                   prompt_tokens=req.n):
            try:
                with _m.loop_phase("prefill_host"):
                    # sampling state rides the slot: params (and the
                    # constraint mask row, for json_mode) must be
                    # installed BEFORE prefill so the first sampled
                    # token is keyed
                    with _m.loop_step("sampling", "serve.join.sampling"):
                        self.engine.set_slot_sampling(slot, req.sampling)
                        if req._machine is not None:
                            self.engine.update_slot_bias(
                                slot, req._machine.mask(budget=req.budget))
                    first = self.engine.prefill(
                        req.tokens, slot,
                        reserve_tokens=req.n + req.budget,
                        request_id=req.request_id)
            except Exception as e:
                with self._cv:
                    if self._slots[slot] is req:
                        self._slots[slot] = None
                self._fail(req, e)
                return
            with _m.loop_phase("emit", "serve.emit", slot=slot,
                               request_id=req.request_id), \
                    _m.loop_step("fanout", "serve.emit.fanout"):
                lp = getattr(self.engine, "last_prefill_logprobs",
                             lambda: None)()
                if lp is not None:
                    self._push_logprobs(req, lp[0], lp[1])
                self._emit(req, first, "prefill")
                self._advance_machine(slot, req, first)
                if self._maybe_finished(req):
                    self._free_slot(slot, req, "finished")

    # mxtpu-lint: hot-path
    def _decode_once(self, gen: int, live):
        """ONE decode dispatch for every slot (free slots ride along at
        position 0); emit each live slot's token and free finished slots
        immediately.  Loop phases: ``operands`` until the program is
        enqueued, ``decode_wait`` (the engine switches) while the host
        blocks on its tokens, then ``emit``."""
        import numpy as _np
        with _m.loop_phase("operands", "serve.operands", step=self._step):
            with _m.loop_step("carry", "serve.carry"):
                S = int(self.engine.max_slots)
                last = _np.zeros(S, _np.int32)
                pos = _np.zeros(S, _np.int32)
                for s, r in live:
                    last[s] = r.tokens_out[-1]
                    pos[s] = r.n + len(r.tokens_out) - 1
                rids = [r.request_id for _, r in live]

            def run():
                _fault.inject("serving.infer", model=self.name,
                              request_ids=rids)
                if self._current_gen() != gen:
                    raise _lc.RequestAborted(
                        f"{self.name}: stale worker generation")
                return self.engine.decode(last, pos)

            t0 = time.monotonic()
            try:
                nxt = _fault.retry_call(run, site="serving.infer",
                                        policy=self.retry_policy)
            except Exception as e:
                self._decode_failed(gen, live, e)
                return
            dt = time.monotonic() - t0
        with _m.loop_phase("emit", "serve.emit", step=self._step):
            self._dispatch_ok(dt)
            self._dpt_dispatches += 1
            self._dpt_tokens += 1.0     # one token per live slot, per slot
            _m.DISPATCHES_PER_TOKEN.set(
                self._dpt_dispatches / max(self._dpt_tokens, 1e-9),
                model=self.name)
            self._fold_decode_health(live)
            lp = self.engine.last_logprobs()    # (S, N) pair or None
            with _m.loop_step("fanout", "serve.emit.fanout"):
                for s, r in live:
                    if lp is not None:
                        self._push_logprobs(r, lp[0][s], lp[1][s])
                    # the stream boundary: ONE scalar pull per emitted token
                    tok = int(nxt[s])  # mxtpu-lint: disable=host-sync-in-hot-path
                    self._emit(r, tok, "step")
                    self._advance_machine(s, r, tok)
                    if self._maybe_finished(r):
                        self._free_slot(s, r, "finished")

    def _dispatch_ok(self, dt: float):
        """A decode dispatch of any path came back after ``dt`` seconds."""
        _m.DECODE_STEP.observe(dt)
        self._avg_batch_seconds = dt if self._avg_batch_seconds <= 0.0 \
            else 0.8 * self._avg_batch_seconds + 0.2 * dt
        self._degraded = False
        self.breaker.record_success()

    def _burst_gate(self, live) -> Optional[str]:
        """Steady-state gate for the multi-token burst path: None when
        the burst may be taken, else the reason it may not
        (``mxtpu_serve_burst_gate{reason}``).  The k-step scanned
        dispatch is opaque to the scheduler — no join, cancel, or
        deadline check can land mid-burst — so only take it when none
        of that boundary work could be pending: the queue is empty
        (``queue``: an admit would otherwise wait up to k tokens for its
        slot), no rider has asked to cancel (``cancel``), and every live
        deadline clears a conservative k×(per-dispatch EWMA) worst case
        (``deadline``).  Any reason falls back to the per-step path,
        which is always correct — the gate only trades throughput for
        boundary granularity."""
        k = int(getattr(self.engine, "scan_steps", 0) or 0)
        if k < 1:
            return "disabled"
        with self._cv:
            if self._queue:
                return "queue"
        horizon = time.monotonic() \
            + k * max(self._avg_batch_seconds, 1e-4)
        for _, r in live:
            if r._cancelled:
                return "cancel"
            if r.deadline is not None and r.deadline <= horizon:
                return "deadline"
            # a constrained slot needs its mask refreshed at EVERY emit
            # boundary — the k-step scan can't see host-side updates
            if r._machine is not None:
                return "constrained"
        return None

    # mxtpu-lint: hot-path
    def _decode_burst_once(self, gen: int, live):
        """ONE scanned dispatch advances every live slot by up to
        ``engine.scan_steps`` tokens with in-program termination (a
        finished slot freezes inside the scan — see
        ``GenerationEngine.decode_burst``); fan each slot's emitted
        prefix out to its SSE queue as a batch and free finished slots.
        Token-for-token identical to k calls of :meth:`_decode_once` —
        only the dispatch grouping and the emit batching change.  Loop
        phases as in :meth:`_decode_once`."""
        import numpy as _np
        with _m.loop_phase("operands", "serve.operands", step=self._step):
            with _m.loop_step("carry", "serve.carry"):
                S = int(self.engine.max_slots)
                last = _np.zeros(S, _np.int32)
                pos = _np.zeros(S, _np.int32)
                bud = _np.ones(S, _np.int32)
                eos = _np.full(S, -1, _np.int32)
                act = _np.zeros(S, bool)
                for s, r in live:
                    last[s] = r.tokens_out[-1]
                    pos[s] = r.n + len(r.tokens_out) - 1
                    bud[s] = r.budget - len(r.tokens_out)
                    if r.eos_id is not None:
                        eos[s] = int(r.eos_id)
                    act[s] = True
                rids = [r.request_id for _, r in live]

            def run():
                _fault.inject("serving.infer", model=self.name,
                              request_ids=rids)
                if self._current_gen() != gen:
                    raise _lc.RequestAborted(
                        f"{self.name}: stale worker generation")
                return self.engine.decode_burst(last, pos, bud, eos, act)

            t0 = time.monotonic()
            try:
                toks, emitted = _fault.retry_call(
                    run, site="serving.infer", policy=self.retry_policy)
            except Exception as e:
                self._decode_failed(gen, live, e)
                return
            dt = time.monotonic() - t0
        with _m.loop_phase("emit", "serve.emit", step=self._step):
            self._dispatch_ok(dt)
            self._fold_decode_health(live)
            self._burst_dispatches += 1
            lp = self.engine.last_logprobs()    # (k, S, N) pair or None
            total = 0
            with _m.loop_step("fanout", "serve.emit.fanout"):
                for s, r in live:
                    # the stream boundary: one bounded pull per rider burst
                    n = int(emitted[s])  # mxtpu-lint: disable=host-sync-in-hot-path
                    if n < 1:
                        continue
                    # mxtpu-lint: disable=host-sync-in-hot-path
                    new = [int(t) for t in toks[:n, s]]
                    stopped = False
                    if r.stops:
                        # stop sequences are detected host-side AT the emit
                        # boundary: keep through the stop, discard the
                        # over-generated tail BEFORE anything reaches the
                        # client's stream
                        kept, stopped = stop_trim(r.tokens_out, new, r.stops)
                        if stopped:
                            self._stop_hits += 1
                            self._stop_trimmed += n - kept
                            _m.SAMPLE_STOP_HITS.inc(model=self.name)
                            _m.SAMPLE_STOP_TRIMMED.inc(n - kept,
                                                       model=self.name)
                            new = new[:kept]
                            n = kept
                    if lp is not None:
                        for j in range(n):
                            self._push_logprobs(r, lp[0][j, s], lp[1][j, s])
                    self._emit_burst(r, new)
                    total += n
                    # `stopped` already counted the hit — bypass the
                    # endswith re-check in _maybe_finished to keep the
                    # counter honest
                    if stopped or self._maybe_finished(r):
                        self._free_slot(s, r, "finished")
            _m.DECODE_BURST_TOKENS.observe(total)
            # dispatch economy: ONE dispatch bought up to k tokens per slot
            self._dpt_dispatches += 1
            self._dpt_tokens += total / max(1, len(live))
            _m.DISPATCHES_PER_TOKEN.set(
                self._dpt_dispatches / max(self._dpt_tokens, 1e-9),
                model=self.name)

    def _fold_decode_health(self, live):
        """Health plane: fold the dispatch's device-side logit stats
        (``engine.last_decode_health``) into the ``mxtpu_health_*``
        series and — on a non-finite row — a ``nonfinite_generation``
        anomaly naming the implicated request ids.  The token pull in
        ``engine.decode`` already synced this dispatch, so these reads
        retire without a device round-trip."""
        hd = getattr(self.engine, "last_decode_health", lambda: None)()
        if hd is None or not live:
            return
        import numpy as _np
        lmax, ent, fin = hd
        # same emit boundary as the token pull above
        lmax = _np.asarray(lmax)  # mxtpu-lint: disable=host-sync-in-hot-path
        ent = _np.asarray(ent)    # mxtpu-lint: disable=host-sync-in-hot-path
        fin = _np.asarray(fin)    # mxtpu-lint: disable=host-sync-in-hot-path
        slots = [s for s, _ in live]
        self._decode_health_last = {
            "step": self._step,
            "logit_max": float(lmax[slots].max()),
            "entropy_mean": float(ent[slots].mean()),
            "finite": bool(fin[slots].all()),
        }
        _m.HEALTH_LOGIT_MAX.set(self._decode_health_last["logit_max"],
                                model=self.name)
        _m.HEALTH_DECODE_ENTROPY.set(
            self._decode_health_last["entropy_mean"], model=self.name)
        bad = [r.request_id for s, r in live if not bool(fin[s])]
        if bad:
            self._nonfinite_generations += 1
            _m.NONFINITE_GENERATIONS.inc(model=self.name)
            _health.serving_anomaly(
                self.name, self._step, bad,
                detail=f"non-finite decode logits at step {self._step} "
                       f"for request(s) {', '.join(bad)}")

    # mxtpu-lint: hot-path
    def _spec_once(self, gen: int, live):
        """ONE speculative step for every slot: k draft dispatches plus
        ONE k+1-wide verify advance each live slot by 1..k+1 tokens.
        Token-for-token identical to :meth:`_decode_once` — only the
        grouping into dispatches changes.  Join/leave stays at step
        boundaries, so a stream that joined mid-flight never observes a
        neighbor's rejected-token rollback (rollback happens inside
        ``spec_step``, before any rider's next dispatch).  Loop phases:
        ``operands`` and ``decode_wait`` alternate inside (once for the
        draft's dispatch, once for the verify), then ``emit``."""
        import numpy as _np
        with _m.loop_phase("operands", "serve.operands", step=self._step):
            with _m.loop_step("carry", "serve.carry"):
                S = int(self.engine.max_slots)
                k = int(self.engine.spec_k)
                last = _np.zeros(S, _np.int32)
                pos = _np.zeros(S, _np.int32)
                for s, r in live:
                    last[s] = r.tokens_out[-1]
                    pos[s] = r.n + len(r.tokens_out) - 1
                rids = [r.request_id for _, r in live]

            def run():
                _fault.inject("serving.infer", model=self.name,
                              request_ids=rids)
                if self._current_gen() != gen:
                    raise _lc.RequestAborted(
                        f"{self.name}: stale worker generation")
                return self.engine.spec_step(last, pos)

            t0 = time.monotonic()
            try:
                burst, accepted = _fault.retry_call(
                    run, site="serving.infer", policy=self.retry_policy)
            except Exception as e:
                self._decode_failed(gen, live, e)
                return
            dt = time.monotonic() - t0
        with _m.loop_phase("emit", "serve.emit", step=self._step):
            self._dispatch_ok(dt)
            _m.SPEC_STEP.observe(dt)
            # accounting lives HERE, not in the engine: free slots ride
            # along in the dispatch at position 0 and their accepts are
            # meaningless.  Of a request's emitted burst, everything past
            # the first token is a draft proposal the target kept — a
            # budget/eos cut mid-burst caps the accepted count to match.
            self._spec_dispatches += 1
            lp = getattr(self.engine, "last_verify_logprobs",
                         lambda: None)()     # (S, Q, N) pair or None
            step_emitted = 0
            step_accepted = 0
            with _m.loop_step("fanout", "serve.emit.fanout"):
                for s, r in live:
                    n_emit = 0
                    # the stream boundary: scalar pulls gate each emitted token
                    # mxtpu-lint: disable=host-sync-in-hot-path
                    for j in range(int(accepted[s]) + 1):
                        if lp is not None:
                            self._push_logprobs(r, lp[0][s, j], lp[1][s, j])
                        # mxtpu-lint: disable=host-sync-in-hot-path
                        self._emit(r, int(burst[s, j]), "spec")
                        n_emit += 1
                        if self._maybe_finished(r):
                            self._free_slot(s, r, "finished")
                            break
                    r.draft_tokens += k
                    r.accepted_tokens += n_emit - 1
                    step_emitted += n_emit
                    step_accepted += n_emit - 1
            self._spec_emitted += step_emitted
            self._spec_accepted += step_accepted
            self._spec_drafted += len(live) * k
            self._spec_slot_steps += len(live)
            _m.SPEC_DISPATCHES.inc(model=self.name)
            _m.SPEC_DRAFT_TOKENS.inc(len(live) * k, model=self.name)
            _m.SPEC_ACCEPTED_TOKENS.inc(step_accepted, model=self.name)
            # per live slot per verify dispatch: 1.0 means the draft never
            # helps, k+1 is the ceiling (full accept + bonus token)
            _m.SPEC_TOKENS_PER_DISPATCH.set(
                self._spec_emitted / self._spec_slot_steps,
                model=self.name)
            _m.SPEC_ACCEPT_RATE.set(
                self._spec_accepted / max(1, self._spec_drafted),
                model=self.name,
                mode="sampled" if any(
                    r.sampling is not None and r.sampling.sampled
                    for _, r in live) else "greedy")
            self._dpt_dispatches += 1
            self._dpt_tokens += step_emitted / max(1, len(live))
            _m.DISPATCHES_PER_TOKEN.set(
                self._dpt_dispatches / max(self._dpt_tokens, 1e-9),
                model=self.name)

    # -- step-boundary helpers ------------------------------------------
    def _push_logprobs(self, req: _GenRequest, vals, ids):
        """Append one per-token top-N logprobs record (sliced to the
        request's clamp) alongside the token about to be emitted."""
        n = req.logprobs_n
        if n < 1 or vals is None:
            return
        # the engine stashed these as host numpy at the dispatch's own
        # sync point (see engine.last_logprobs) — no device round-trip
        req.logprobs_out.append({
            "token_ids": [int(i) for i in ids[:n]],    # mxtpu-lint: disable=host-sync-in-hot-path
            "logprobs": [float(v) for v in vals[:n]],  # mxtpu-lint: disable=host-sync-in-hot-path
        })

    def _advance_machine(self, slot: int, req: _GenRequest, tok: int):
        """Constrained-output emit boundary: feed the token just
        emitted to the request's grammar machine and install the next
        step's vocab mask (a traced operand of the NEXT dispatch)."""
        m = req._machine
        if m is None:
            return
        # tok is the already-pulled host scalar from the emit boundary
        m.advance(int(tok))  # mxtpu-lint: disable=host-sync-in-hot-path
        if not m.done:
            self.engine.update_slot_bias(
                slot, m.mask(budget=req.budget - len(req.tokens_out)))

    def _emit(self, req: _GenRequest, tok: int, path: str):
        """Hand one token of the ``path`` dispatch
        (``prefill|step|spec``) to the request's stream."""
        gap = req._emit(tok)
        self._tokens_emitted += 1
        self._tokens_by_path[path] += 1
        _m.GENERATE_TOKENS.inc(model=self.name, path=path)
        if req.sampling is not None and req.sampling.sampled:
            _m.SAMPLE_TOKENS.inc(model=self.name)
        # feed the token-latency SLI (MXNET_SERVE_SLO_TOKEN_P99_MS)
        _slo.tracker.record_token(self.name, gap)

    def _emit_burst(self, req: _GenRequest, toks):
        """Burst-path twin of :meth:`_emit`: one queue flush for the
        whole burst; counters and the SLI window stay per-token, and the
        gaps are those the client sees — the whole wait on the burst's
        first token, none on the rest (``ModelSLO.record_token``)."""
        n = len(toks)
        if not n:
            return
        gap = req._emit_burst(toks)
        self._tokens_emitted += n
        self._tokens_by_path["burst"] += n
        _m.GENERATE_TOKENS.inc(n, model=self.name, path="burst")
        if req.sampling is not None and req.sampling.sampled:
            _m.SAMPLE_TOKENS.inc(n, model=self.name)
        _slo.tracker.record_token(self.name, gap)
        for _ in range(n - 1):
            _slo.tracker.record_token(self.name, 0.0)

    def _maybe_finished(self, req: _GenRequest) -> bool:
        if len(req.tokens_out) >= req.budget:
            return True
        if req.eos_id is not None \
                and req.tokens_out[-1] == int(req.eos_id):
            return True
        if req._machine is not None and req._machine.done:
            return True
        if req.stops:
            out = req.tokens_out
            for stop in req.stops:
                if len(out) >= len(stop) \
                        and tuple(out[-len(stop):]) == stop:
                    self._stop_hits += 1
                    _m.SAMPLE_STOP_HITS.inc(model=self.name)
                    return True
        return False

    def _free_slot(self, slot: int, req: _GenRequest, reason: str):
        with _m.loop_step("finish", "serve.emit.finish"):
            with self._cv:
                if self._slots[slot] is req:
                    self._slots[slot] = None
                _m.SLOTS_IN_USE.set(
                    sum(1 for r in self._slots if r is not None),
                    model=self.name)
            self._leave(slot, req, reason)

    def _leave(self, slot: int, req: _GenRequest, reason: str):
        """Emit the ``slot.leave`` event and settle the request: ok for
        ``finished``, ``Cancelled`` for a client that went away,
        ``DeadlineExceeded`` (stage=decode) for a budget bust.  Paged
        engines get the slot's KV blocks back here (decref — shared
        prefix blocks survive for other readers)."""
        rel = getattr(self.engine, "release_slot", None)
        if rel is not None:
            rel(slot)
        with _telemetry.trace_span("slot.leave", cat="serving",
                                   model=self.name, slot=slot,
                                   request_id=req.request_id,
                                   reason=reason,
                                   tokens=len(req.tokens_out)):
            pass
        dt = time.monotonic() - req.t_submit
        if reason == "finished":
            _m.LATENCY.observe(dt)
            _slo.tracker.record(self.name, dt, ok=True)
            req._finish(None)
        elif reason == "cancelled":
            _m.CANCELLED.inc(model=self.name)
            _telemetry.FAULT.publish(
                site="serving.generate", event="cancelled",
                model=self.name, request_id=req.request_id,
                tokens=len(req.tokens_out))
            # a cancel is the client's choice, not an SLO burn
            req._finish(_lc.Cancelled(
                f"{self.name}: request {req.request_id} cancelled after "
                f"{len(req.tokens_out)} tokens"))
        elif reason == "deadline":
            _m.DEADLINE_EXCEEDED.inc(model=self.name, stage="decode")
            _telemetry.FAULT.publish(
                site="serving.deadline", event="deadline", kind="decode",
                model=self.name, request_id=req.request_id,
                tokens=len(req.tokens_out))
            _slo.tracker.record(self.name, dt, ok=False)
            req._finish(_lc.DeadlineExceeded(
                f"{self.name}: request {req.request_id} deadline "
                f"exceeded mid-decode after {len(req.tokens_out)} "
                "tokens"))
        else:
            _slo.tracker.record(self.name, dt, ok=False)
            req._finish(_lc.RequestAborted(
                f"{self.name}: request {req.request_id} aborted "
                f"({reason})"))

    def _fail(self, req: _GenRequest, err: Exception):
        _slo.tracker.record(self.name,
                            time.monotonic() - req.t_submit, ok=False)
        _telemetry.FAULT.publish(
            site="serving.generate", event="error",
            kind=type(err).__name__, model=self.name,
            request_id=req.request_id)
        req._finish(err)

    def _decode_failed(self, gen: int, live, err: Exception):
        """A decode dispatch failed after retries.  There is no per-slot
        fallback — the cache is shared and may have been consumed by
        donation — so fail every rider, free all slots, and reset the
        cache so the next admission starts clean."""
        if _tdev.is_oom(err):
            # RESOURCE_EXHAUSTED: name the implicated requests on the
            # oom flight dump (the engine funnel already reported the
            # failure itself, but only the batcher knows the riders)
            _tdev.report_oom(
                "serving.infer", err, model=self.name,
                request_ids=[r.request_id for _, r in live])
        _telemetry.FAULT.publish(
            site="serving.infer", event="fallback",
            kind=type(err).__name__, model=self.name,
            requests=len(live),
            request_ids=[r.request_id for _, r in live])
        _m.FALLBACKS.inc(model=self.name)
        self.breaker.record_failure(
            f"decode dispatch failed: {type(err).__name__}")
        with self._cv:
            for s, r in live:
                if self._slots[s] is r:
                    self._slots[s] = None
            _m.SLOTS_IN_USE.set(0, model=self.name)
            current = gen == self._worker_gen
        # reset OUTSIDE _cv: it dispatches to the device and can wedge,
        # and the watchdog needs _cv to even diagnose a wedged worker.
        # A superseded worker (gen bumped after the check) skips reset
        # anyway; the restart path re-warms the engine itself.
        if current:
            self.engine.reset()
        for _, r in live:
            self._fail(r, err)

    # -- introspection ---------------------------------------------------
    @property
    def idle(self) -> bool:
        with self._cv:
            return not self._queue \
                and all(r is None for r in self._slots)

    @property
    def pending(self) -> int:
        with self._cv:
            return len(self._queue) \
                + sum(1 for r in self._slots if r is not None)

    def active_request_ids(self) -> dict:
        with self._cv:
            return {"queued": [r.request_id for r in self._queue],
                    "inflight": [r.request_id for r in self._slots
                                 if r is not None]}

    def slots_in_use(self) -> int:
        with self._cv:
            return sum(1 for r in self._slots if r is not None)

    def _steps_by_phase(self) -> dict:
        """The clock's step totals as ``{phase: {step: seconds}}``."""
        out = {}
        # the worker adds keys as steps first run: copy before iterating
        for (phase, step), s in dict(self._loop_step_seconds).items():
            out.setdefault(phase, {})[step] = s
        return out

    def stats(self) -> dict:
        out = super().stats()
        with self._cv:
            out.update({
                "kind": "generation",
                "max_slots": int(self.engine.max_slots),
                "max_len": int(self.engine.max_len),
                "slots_in_use": sum(1 for r in self._slots
                                    if r is not None),
                "decode_steps": self._step,
                "decode_scan_steps":
                    int(getattr(self.engine, "scan_steps", 0) or 0),
                "decode_burst_dispatches": self._burst_dispatches,
                "tokens_emitted": self._tokens_emitted,
                "tokens_by_path": dict(self._tokens_by_path),
                "loop_seconds": dict(self._loop_seconds),
                "loop_cpu_seconds": dict(self._loop_cpu_seconds),
                "loop_step_seconds": self._steps_by_phase(),
                "peak_slots_in_use": self._peak_slots,
                "prefill_buckets": list(self.engine.prefill_buckets),
                "kv_cache_bytes": int(self.engine.cache_bytes),
                "kv_starved": self.kv_starved,
                "dispatches_per_token":
                    self._dpt_dispatches
                    / max(self._dpt_tokens, 1e-9)
                    if self._dpt_dispatches else None,
                "logprobs_topn":
                    int(getattr(self.engine, "logprobs_topn", 0) or 0),
                "stop_hits": self._stop_hits,
                "stop_trimmed_tokens": self._stop_trimmed,
            })
            if getattr(self.engine, "draft", None) is not None:
                out.update({
                    "spec_k": int(self.engine.spec_k),
                    "spec_draft_model": self.engine.draft.name,
                    "spec_dispatches": self._spec_dispatches,
                    "accepted_tokens_per_dispatch":
                        self._spec_emitted
                        / max(1, self._spec_slot_steps),
                    "spec_accept_rate":
                        self._spec_accepted
                        / max(1, self._spec_drafted),
                })
            ks = getattr(self.engine, "kv_stats", None)
            if ks is not None:
                out.update(ks())
            dc = getattr(self.engine, "decode_counters", None)
            if dc is not None:
                out.update(dc())
            ops = getattr(self.engine, "operand_sources", None)
            if ops is not None:
                out["operands"] = ops()
            sd = getattr(self.engine, "sample_dispatches", None)
            if sd is not None:
                out["sample_dispatches"] = sd()
            ed = getattr(self.engine, "expert_dispatches", None)
            paths = ed() if ed is not None else None
            if paths:           # a model with an expert layer
                out["expert_dispatches"] = paths
            if self._decode_health_last is not None:
                out["decode_health"] = dict(self._decode_health_last)
                out["nonfinite_generations"] = \
                    self._nonfinite_generations
        out.pop("max_delay_ms", None)
        return out
