"""Inference serving subsystem — dynamic-batching model server over
shape-bucketed compiled engines (see docs/serving.md).

Four layers, importable à la carte:

* :class:`InferenceEngine` (``engine.py``) — a model (Gluon block,
  Module, or exported symbol+params) as donated jitted forward
  programs keyed by batch-size bucket; requests pad up to the next
  bucket so the compile cache stays bounded.
* :class:`DynamicBatcher` (``batcher.py``) — bounded queue coalescing
  concurrent requests into ONE dispatch per batch, with backpressure,
  per-request deadlines, retry + single-request fallback, a per-model
  circuit breaker, and graceful drain.
* :mod:`lifecycle` — the fault-domain plane shared by batcher and
  server: serving states (SERVING/DEGRADED/…), :class:`CircuitBreaker`,
  the worker :class:`Watchdog`, deadline helpers, and the SIGTERM-safe
  shutdown machinery (``install_signal_handler`` /
  ``run_until_shutdown``); docs/robustness.md.
* :class:`ModelServer` (``server.py``) — stdlib HTTP front-end
  (``/v1/models/<name>:predict``, multi-model registry, ``/healthz``,
  ``/readyz``, ``/metrics``) sharing plumbing with the telemetry
  exporter.  CLI: ``mxtpu-serve``.

Above the single process sits :class:`Router` (``router.py``) — the
``mxtpu-router`` front tier spreading ``:predict``/``:generate`` over
N replicas with health-aware least-loaded balancing, breaker-based
outlier ejection, retry-with-failover, SSE passthrough, zero-downtime
drain orchestration, and rendezvous-hash prefix-affine routing for
the paged KV prefix cache (docs/serving.md "Serving a fleet").
Membership is dynamic (``POST``/``DELETE /admin/replicas``), and
:class:`Supervisor` (``supervisor.py``, CLI ``mxtpu-supervise``)
closes the loop: it owns the replica processes — spawn, ``/readyz``
health-gating, crash/hang detection, restart-with-backoff, flap
quarantine — and autoscales the fleet off the router's own federated
signals through the pure :func:`scale_decision` policy
(docs/robustness.md "Self-healing fleet").

Generation serving rides the same layers: :class:`GenerationEngine`
(paged KV cache over a :class:`~.kvcache.BlockPool` — fixed-size
blocks, per-slot block tables, refcounted prefix sharing — with a
prefill/decode split) behind a
:class:`ContinuousBatcher` (per-slot join/leave, one decode dispatch
per step over all live requests, pool-capacity admission) behind
``POST /v1/models/<name>:generate`` with SSE streaming.  The sampling
plane (``sampling.py``) threads per-slot :class:`SamplingParams`
through those same compiled programs as traced operands — stochastic
decoding, seeded replay, speculative sampling, per-token logprobs,
multi-token stop sequences, and JSON-mode constrained output
(docs/serving.md "Sampling").

Importing this package registers the ``mxtpu_serve_*`` metrics on the
shared telemetry registry, so they appear on every exporter
automatically.
"""
from . import metrics
from . import lifecycle
from .lifecycle import (
    CircuitBreaker, Watchdog, DeadlineExceeded, BreakerOpen, Draining,
    RequestAborted, Cancelled, SERVING, STARTING, DEGRADED, UNHEALTHY,
    DRAINING,
)
from .engine import InferenceEngine, GenerationEngine, derive_buckets, \
    derive_prefill_buckets
from .kvcache import BlockPool, blocks_for
from .sampling import SamplingParams, JsonMaskMachine
from .batcher import ContinuousBatcher, DynamicBatcher, QueueFullError
from .server import ModelServer
from .router import Router, Replica, UpstreamError, NoReplicaAvailable
from .supervisor import (Supervisor, AutoscalePolicy, ScaleSignals,
                         ScaleAction, scale_decision, FlapBreaker)

__all__ = ["InferenceEngine", "GenerationEngine", "derive_buckets",
           "derive_prefill_buckets", "BlockPool", "blocks_for",
           "SamplingParams", "JsonMaskMachine",
           "DynamicBatcher",
           "ContinuousBatcher", "QueueFullError", "ModelServer",
           "Router", "Replica", "UpstreamError", "NoReplicaAvailable",
           "Supervisor", "AutoscalePolicy", "ScaleSignals",
           "ScaleAction", "scale_decision", "FlapBreaker",
           "metrics", "lifecycle",
           "CircuitBreaker", "Watchdog", "DeadlineExceeded",
           "BreakerOpen", "Draining", "RequestAborted", "Cancelled",
           "SERVING", "STARTING", "DEGRADED", "UNHEALTHY", "DRAINING"]
