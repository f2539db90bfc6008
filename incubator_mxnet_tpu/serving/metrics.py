"""Serving metrics — registered on the SHARED telemetry registry at
import, so they ride every existing exporter (``/metrics`` Prometheus
scrape via ``telemetry_http``/the serving server, ``telemetry.snapshot``
JSON, ``mxtpu-stats``, profiler counter tracks) with no extra wiring.

Counters/gauges are labeled by ``model`` so a multi-model server stays
legible on one scrape; histograms are registry-wide (bounded reservoir,
p50/p95/max in the summary exposition).
"""
from __future__ import annotations

import threading
import time

from .. import telemetry as _telemetry

# counters -----------------------------------------------------------------
REQUESTS = _telemetry.registry.counter(
    "mxtpu_serve_requests",
    "inference requests accepted into a DynamicBatcher queue")
BATCHES = _telemetry.registry.counter(
    "mxtpu_serve_batches",
    "coalesced batch dispatches (one compiled forward per batch)")
REJECTED = _telemetry.registry.counter(
    "mxtpu_serve_rejected",
    "requests rejected with QueueFullError (backpressure)")
FALLBACKS = _telemetry.registry.counter(
    "mxtpu_serve_fallbacks",
    "batched dispatches that failed after retries and fell back to "
    "single-request execution")
DEADLINE_EXCEEDED = _telemetry.registry.counter(
    "mxtpu_serve_deadline_exceeded",
    "requests shed because their end-to-end deadline expired "
    "(stage=admission|queue|wait|decode)")
GENERATE_TOKENS = _telemetry.registry.counter(
    "mxtpu_generate_tokens",
    "tokens emitted by the continuous-batching generation path, by the "
    "dispatch that produced them (path=prefill|step|burst|spec)")
LOOP_SECONDS = _telemetry.registry.counter(
    "mxtpu_serve_loop_seconds",
    "seconds the generation worker thread spent in each phase of its "
    "loop (phase=wait|admit|prefill_host|prefill_wait|operands|"
    "decode_wait|emit); the phases partition the thread's time")
LOOP_CPU_SECONDS = _telemetry.registry.counter(
    "mxtpu_serve_loop_cpu_seconds",
    "of mxtpu_serve_loop_seconds, the seconds the generation worker "
    "thread was running on a CPU (time.thread_time), by the same phase; "
    "wall minus CPU is time the thread was off the CPU: the interpreter "
    "lock given away, a lock waited for, a blocking call into the runtime")
LOOP_STEP_SECONDS = _telemetry.registry.counter(
    "mxtpu_serve_loop_step_seconds",
    "of mxtpu_serve_loop_seconds, the seconds inside a named step of a "
    "phase (step=lock|hash|alloc|sampling|params|edit|enqueue|carry|"
    "fanout|finish); steps subdivide a phase and what none covers is the "
    "phase's remainder")
BURST_GATE = _telemetry.registry.counter(
    "mxtpu_serve_burst_gate",
    "decode dispatches that were not a burst, by the reason the gate "
    "said no (reason=queue|cancel|deadline|constrained|disabled)")
SERVE_OPERANDS = _telemetry.registry.counter(
    "mxtpu_serve_operands",
    "decode, burst and verify dispatches by where their per-slot "
    "operands (the engine's slot state on the device) came from: "
    "source=carried (as the last dispatch returned them: nothing "
    "uploaded), patched (rows edited since: a join, a leave, a bias "
    "row, a host-side stop), rebuilt (whole, from the host's rows: "
    "start, reset, after a failed dispatch)")
SERVE_OPERAND_ROWS = _telemetry.registry.counter(
    "mxtpu_serve_operand_rows",
    "slot rows of the slot state edited on the device ahead of the "
    "dispatches mxtpu_serve_operands counts as patched")
MOE_PAIRS_TOTAL = _telemetry.registry.counter(
    "mxtpu_moe_pairs_total",
    "(token, expert) pairs the decode programs' expert layers routed, "
    "over all published experts: live slots x experts per token, summed "
    "over steps and expert layers")
MOE_PAIRS_HELD = _telemetry.registry.counter(
    "mxtpu_moe_pairs_held",
    "of mxtpu_moe_pairs_total, the pairs whose expert this replica "
    "holds: the rows its grouped expert product computed")
MOE_EXPERTS_TOUCHED = _telemetry.registry.counter(
    "mxtpu_moe_experts_touched",
    "experts held here that got at least one token, summed over decode "
    "steps and expert layers: the expert weights a step had to read")
DECODE_CONTEXT_TOKENS = _telemetry.registry.counter(
    "mxtpu_decode_context_tokens",
    "written positions of the live slots (write head + 1), summed over "
    "decode steps: the context the step's attention had behind it")
DECODE_WINDOW_TOKENS = _telemetry.registry.counter(
    "mxtpu_decode_window_tokens",
    "of mxtpu_decode_context_tokens, the positions a windowed layer "
    "reads: min(written positions, window) of each live slot, summed "
    "over decode steps; only for a model with windowed layers")
PAGED_GROUPS = _telemetry.registry.counter(
    "mxtpu_paged_groups_total",
    "steps of the grouped paged kernel's work list (a layer that takes "
    "it, a live slot and a decode step: the 128-key groups from the "
    "window's first to the write head's) by fetch=run (the table names "
    "the group's live blocks in a row: one copy) or blocks (a copy a "
    "block); the kernel's predicate on the host's tables; only for a "
    "model whose paged calls take that kernel")
INDEX_KEYS_SCORED = _telemetry.registry.counter(
    "mxtpu_index_keys_scored",
    "cached index keys the decode programs' indexers scored: the written "
    "positions of the live slots, summed over decode steps and over the "
    "layers that choose the keys they read; only for a model with such "
    "layers")
INDEX_KEYS_SELECTED = _telemetry.registry.counter(
    "mxtpu_index_keys_selected",
    "of mxtpu_index_keys_scored, the positions chosen and attended over: "
    "min(written positions, the layer's top-k) of each live slot, summed "
    "over decode steps and those layers")
KV_BYTES_PER_TOKEN = _telemetry.registry.gauge(
    "mxtpu_kv_bytes_per_token",
    "bytes the model's layers keep a cached position, as its layout "
    "states them (KVLayout.block_bytes(1)): K and V of a grouped-query "
    "layer, the latent row and index key of a latent one; lanes a device "
    "pads a row with are not counted")
STATE_ROWS_IN_USE = _telemetry.registry.gauge(
    "mxtpu_state_rows_in_use",
    "rows of a recurrent model's state store in use: one a slot that "
    "holds a request, one a snapshot kept for the prefix cache")
STATE_SNAPSHOTS = _telemetry.registry.counter(
    "mxtpu_state_snapshots",
    "state snapshots of a recurrent model by event=kept (a prefill was "
    "given a row for the state at a snapshot boundary), restored (a "
    "prefix hit started from one), evicted (its block's registration "
    "went, or the rows ran out and it was used longest ago)")
PREFILL_TOKENS = _telemetry.registry.counter(
    "mxtpu_prefill_tokens",
    "prompt positions the prefill programs computed, by path=miss (a "
    "whole prompt) or hit (the part of a prompt past its cached prefix)")
PREFIX_HIT_TOKENS = _telemetry.registry.counter(
    "mxtpu_prefix_hit_tokens",
    "prompt positions a join did not compute because cached blocks — "
    "and, for a recurrent model, the snapshot they end at — held them")
SSM_STEP_ROWS = _telemetry.registry.counter(
    "mxtpu_ssm_step_rows_total",
    "state rows the decode programs' one-token step of the state-space "
    "layers updated: a live slot a decode step (a row is one sequence's "
    "state over all those layers: mxtpu_ssm_state_bytes, read once and "
    "written once); only for a model with such layers")
SSM_STEP_ROWS_SKIPPED = _telemetry.registry.counter(
    "mxtpu_ssm_step_rows_skipped_total",
    "state rows the one-token step's work list left out: a slot that was "
    "not live a decode step of a dispatch (free, or ended inside the "
    "burst) — max_slots x the dispatch's steps less "
    "mxtpu_ssm_step_rows_total; the kernel's own predicate on the host's "
    "rows; only for a model with such layers")
SSM_PREFILL_TOKENS = _telemetry.registry.counter(
    "mxtpu_ssm_prefill_tokens_total",
    "live prompt positions the prefill programs took through the "
    "state-space layers' chunked scan (every position a prefill "
    "computes, miss or hit); only for a model with such layers")
SSM_STATE_BYTES = _telemetry.registry.gauge(
    "mxtpu_ssm_state_bytes",
    "bytes of ONE sequence's state over the model's state-space layers "
    "(the matrices and the convolution's tail), whatever its context")
#: counters a served model's layers return from the decode programs
#: (``block.serve_counters``), by the model's name for each
MODEL_COUNTERS = {"moe_pairs_total": MOE_PAIRS_TOTAL,
                  "moe_pairs_held": MOE_PAIRS_HELD,
                  "moe_experts_touched": MOE_EXPERTS_TOUCHED}
CANCELLED = _telemetry.registry.counter(
    "mxtpu_serve_cancelled",
    "generation requests cancelled mid-decode (client disconnect); the "
    "slot frees on the next step boundary")
WATCHDOG_RESTARTS = _telemetry.registry.counter(
    "mxtpu_serve_watchdog_restarts",
    "batcher workers restarted by the serving watchdog (dead or hung)")
BREAKER_TRIPS = _telemetry.registry.counter(
    "mxtpu_serve_breaker_trips",
    "per-model circuit breaker CLOSED/HALF_OPEN -> OPEN transitions")
SLO_BAD = _telemetry.registry.counter(
    "mxtpu_slo_bad_requests",
    "requests that burned error budget (any failure surfaced to the "
    "caller: backpressure, breaker, deadline, abort, dispatch error)")
PREFIX_CACHE_HITS = _telemetry.registry.counter(
    "mxtpu_prefix_cache_hits",
    "KV blocks reused from the prefix cache instead of being "
    "re-prefilled (one increment per shared block)")
PREFIX_CACHE_EVICTIONS = _telemetry.registry.counter(
    "mxtpu_prefix_cache_evictions",
    "idle cached KV blocks evicted (LRU) to satisfy new allocations")
SPEC_DISPATCHES = _telemetry.registry.counter(
    "mxtpu_spec_verify_dispatches",
    "speculative-decoding verify dispatches (one k+1-wide target "
    "forward scoring all drafted positions at once)")
SPEC_DRAFT_TOKENS = _telemetry.registry.counter(
    "mxtpu_spec_draft_tokens",
    "tokens proposed by the draft model, per target model")
SPEC_ACCEPTED_TOKENS = _telemetry.registry.counter(
    "mxtpu_spec_accepted_tokens",
    "drafted tokens the target model accepted and emitted (excludes "
    "the guaranteed bonus token per dispatch)")
NONFINITE_GENERATIONS = _telemetry.registry.counter(
    "mxtpu_health_nonfinite_generations",
    "decode steps whose logits contained a non-finite value for at "
    "least one live slot (health plane, MXNET_HEALTH_PLANE=1)")

# sampling plane (serving/sampling.py; docs/serving.md "Sampling") ----------
SAMPLED_REQUESTS = _telemetry.registry.counter(
    "mxtpu_sample_requests",
    "generation requests admitted, by mode=greedy|sampled "
    "(sampled: temperature > 0)")
SAMPLE_TOKENS = _telemetry.registry.counter(
    "mxtpu_sample_tokens",
    "tokens emitted by stochastically sampled (temperature > 0) "
    "requests, per model")
SAMPLE_DISPATCHES = _telemetry.registry.counter(
    "mxtpu_sample_dispatches",
    "decode, burst and verify dispatches by the branch their sampling "
    "step takes: branch=greedy (no live slot has a temperature: the "
    "argmax alone) or full (at least one does: sort, filters and "
    "Gumbel noise over every slot's logits); the program's own "
    "predicate, evaluated on the host's rows at dispatch")
MOE_EXPERT_DISPATCHES = _telemetry.registry.counter(
    "mxtpu_moe_expert_dispatches",
    "prefill, decode, burst and verify dispatches of a model with an "
    "expert layer by what its grouped expert product is: path=kernel "
    "(one Pallas kernel a layer, over the touched experts or a "
    "prompt's sorted rows: a TPU) or loop (the lax loop: the CPU); "
    "held_experts_impl's answer when the program was traced")
SAMPLE_CONSTRAINED = _telemetry.registry.counter(
    "mxtpu_sample_constrained_requests",
    "generation requests decoded under a constrained-output grammar "
    "mask (json_mode), per model")
SAMPLE_STOP_HITS = _telemetry.registry.counter(
    "mxtpu_sample_stop_hits",
    "generation requests finished by a multi-token stop sequence at "
    "an emit boundary, per model")
SAMPLE_STOP_TRIMMED = _telemetry.registry.counter(
    "mxtpu_sample_stop_trimmed_tokens",
    "over-generated burst-tail tokens discarded host-side past a stop "
    "sequence (their K/V writes were already null-block-redirected)")

# router (serving/router.py; labeled by replica where it matters) ----------
ROUTER_REQUESTS = _telemetry.registry.counter(
    "mxtpu_router_requests",
    "client requests accepted by the mxtpu-router front tier")
ROUTER_RETRIES = _telemetry.registry.counter(
    "mxtpu_router_retries",
    "upstream attempts beyond the first (connect error / 503 / 429 "
    "re-routed under the per-request retry budget)")
ROUTER_FAILOVERS = _telemetry.registry.counter(
    "mxtpu_router_failovers",
    "requests that ultimately succeeded on a different replica than "
    "the first one tried")
ROUTER_EJECTIONS = _telemetry.registry.counter(
    "mxtpu_router_ejections",
    "replica ejections (health-loop breaker CLOSED/HALF_OPEN -> OPEN)")
ROUTER_AFFINITY = _telemetry.registry.counter(
    "mxtpu_router_affinity_routed",
    "generation requests routed to their rendezvous-hash prefix owner")
ROUTER_SPILLS = _telemetry.registry.counter(
    "mxtpu_router_spills",
    "generation requests spilled off their prefix owner because it was "
    "overloaded, draining, or ejected")
ROUTER_STREAM_ERRORS = _telemetry.registry.counter(
    "mxtpu_router_stream_errors",
    "streams terminated with an SSE error event after a mid-stream "
    "replica death (tokens already on the wire - no silent failover)")
ROUTER_REPLICA_STATE = _telemetry.registry.gauge(
    "mxtpu_router_replica_state",
    "per-replica router view (0 READY, 1 UNREADY, 2 DRAINING, "
    "3 EJECTED, 4 DOWN)")
ROUTER_REPLICAS_ELIGIBLE = _telemetry.registry.gauge(
    "mxtpu_router_replicas_eligible",
    "replicas currently eligible for new work")
ROUTER_INFLIGHT = _telemetry.registry.gauge(
    "mxtpu_router_inflight",
    "client requests in flight through the router, per replica")
ROUTER_INCIDENTS = _telemetry.registry.counter(
    "mxtpu_router_incidents",
    "correlated incident bundles written (ejection / "
    "failover-exhaustion / drain-timeout), by reason")
ROUTER_FEDERATION_STALE = _telemetry.registry.gauge(
    "mxtpu_router_federation_stale",
    "replicas whose cached metrics snapshot has aged past the "
    "staleness horizon and is excluded from fleet totals")
ROUTER_TRACE_FANOUT = _telemetry.registry.counter(
    "mxtpu_router_trace_fanout",
    "replica /trace fetches made while stitching fleet traces")
ROUTER_MEMBERSHIP = _telemetry.registry.counter(
    "mxtpu_router_membership_changes",
    "fleet membership changes (POST/DELETE /admin/replicas), by "
    "action=join|leave")

# supervisor + autoscaler (serving/supervisor.py; control-plane series,
# rendered once on the router /metrics — docs/observability.md) -----------
SUPERVISE_SPAWNS = _telemetry.registry.counter(
    "mxtpu_supervise_spawns",
    "replica processes spawned by mxtpu-supervise (first launches and "
    "restarts alike)")
SUPERVISE_RESTARTS = _telemetry.registry.counter(
    "mxtpu_supervise_restarts",
    "replica restarts after a detected crash or hang (exit, /healthz "
    "timeout), per replica slot")
SUPERVISE_QUARANTINES = _telemetry.registry.counter(
    "mxtpu_supervise_quarantines",
    "replica slots quarantined by the flap breaker "
    "(MXNET_SUPERVISE_MAX_RESTARTS within the window)")
SUPERVISE_REPLICAS = _telemetry.registry.gauge(
    "mxtpu_supervise_replicas",
    "supervised replica processes currently alive")
AUTOSCALE_EVENTS = _telemetry.registry.counter(
    "mxtpu_autoscale_events",
    "executed scale actions, by action=up|down (scale-down always "
    "routes through /admin/drain)")
AUTOSCALE_DECISIONS = _telemetry.registry.counter(
    "mxtpu_autoscale_decisions",
    "autoscale policy evaluations, by action=up|down|hold")
AUTOSCALE_TARGET = _telemetry.registry.gauge(
    "mxtpu_autoscale_target_replicas",
    "fleet size the autoscaler is currently steering toward")
AUTOSCALE_BURN = _telemetry.registry.gauge(
    "mxtpu_autoscale_burn_rate",
    "worst-model fleet SLO burn rate the last policy evaluation saw")
AUTOSCALE_QUEUE = _telemetry.registry.gauge(
    "mxtpu_autoscale_queue_depth",
    "fleet-summed serve queue depth the last policy evaluation saw")
AUTOSCALE_KV = _telemetry.registry.gauge(
    "mxtpu_autoscale_kv_utilization",
    "worst-replica KV-cache utilization the last policy evaluation saw")

# histograms ---------------------------------------------------------------
BATCH_SIZE = _telemetry.registry.histogram(
    "mxtpu_serve_batch_size",
    "rows per coalesced dispatch (before bucket padding)")
QUEUE_WAIT = _telemetry.registry.histogram(
    "mxtpu_serve_queue_wait_seconds",
    "seconds a request waited in the queue before its batch dispatched")
LATENCY = _telemetry.registry.histogram(
    "mxtpu_serve_latency_seconds",
    "end-to-end seconds from submit to scattered result")
TOKEN_LATENCY = _telemetry.registry.histogram(
    "mxtpu_generate_token_seconds",
    "seconds between consecutive emitted tokens of one generation "
    "request (first sample: submit -> first token)")
DECODE_STEP = _telemetry.registry.histogram(
    "mxtpu_generate_decode_step_seconds",
    "seconds per continuous-batching decode dispatch (all live slots "
    "advance one token)")
DECODE_BURST_TOKENS = _telemetry.registry.histogram(
    "mxtpu_decode_burst_tokens",
    "tokens emitted per scanned decode-burst dispatch, summed across "
    "live slots (ceiling is scan_steps x slots; a thin tail means "
    "in-program termination is cutting bursts short)")
SPEC_STEP = _telemetry.registry.histogram(
    "mxtpu_spec_step_seconds",
    "seconds per speculative step (k draft dispatches plus one verify; "
    "compare with mxtpu_generate_decode_step_seconds for the draft "
    "overhead per accepted-token burst)")
ROUTER_UPSTREAM = _telemetry.registry.histogram(
    "mxtpu_router_upstream_seconds",
    "seconds per upstream attempt (router -> replica), successful or "
    "not")

# gauges -------------------------------------------------------------------
QUEUE_DEPTH = _telemetry.registry.gauge(
    "mxtpu_serve_queue_depth",
    "requests currently queued, per model")
SLOTS_IN_USE = _telemetry.registry.gauge(
    "mxtpu_serve_cache_slots_in_use",
    "KV-cache slots occupied by live generation requests, per model")
KV_BLOCKS_TOTAL = _telemetry.registry.gauge(
    "mxtpu_kv_blocks_total",
    "allocatable KV-cache blocks in the paged BlockPool, per model")
KV_BLOCKS_IN_USE = _telemetry.registry.gauge(
    "mxtpu_kv_blocks_in_use",
    "KV-cache blocks held by live slots or pinned in the prefix "
    "cache with a nonzero refcount, per model")
MODELS_LOADED = _telemetry.registry.gauge(
    "mxtpu_serve_models_loaded",
    "models registered on the ModelServer")
BREAKER_STATE = _telemetry.registry.gauge(
    "mxtpu_serve_breaker_state",
    "per-model circuit breaker state (0 CLOSED, 1 HALF_OPEN, 2 OPEN)")
MODEL_STATE = _telemetry.registry.gauge(
    "mxtpu_serve_model_state",
    "per-model serving state (0 SERVING, 1 STARTING, 2 DEGRADED, "
    "3 UNHEALTHY, 4 DRAINING)")
SPEC_TOKENS_PER_DISPATCH = _telemetry.registry.gauge(
    "mxtpu_spec_accepted_tokens_per_dispatch",
    "tokens emitted per verify dispatch, cumulative per model "
    "(1.0 would mean the draft never helps; k+1 is the ceiling)")
SPEC_ACCEPT_RATE = _telemetry.registry.gauge(
    "mxtpu_spec_accept_rate",
    "fraction of drafted tokens the target accepted, cumulative per "
    "model and by mode=greedy|sampled (sampled: any live slot decoding "
    "at temperature > 0; tune MXNET_SPEC_K down when this drops)")
HEALTH_LOGIT_MAX = _telemetry.registry.gauge(
    "mxtpu_health_logit_max",
    "max final-position logit across live slots in the most recent "
    "decode dispatch (health plane; drifting up signals divergence)")
HEALTH_DECODE_ENTROPY = _telemetry.registry.gauge(
    "mxtpu_health_decode_entropy",
    "mean final-position softmax entropy (nats) across live slots in "
    "the most recent decode dispatch (health plane; near-zero = "
    "degenerate repetition, near log(vocab) = noise)")
DISPATCHES_PER_TOKEN = _telemetry.registry.gauge(
    "mxtpu_dispatches_per_token",
    "target-model dispatches per emitted token, cumulative per model "
    "(per-slot normalized: exactly 1.0 for per-step decode, <= "
    "1/scan_steps at steady state on the scanned burst path, and "
    "1/(accepted burst) when speculation amortizes the verify "
    "dispatch)")

# SLO plane (serving/slo.py; docs/observability.md) -------------------------
SLO_AVAILABILITY = _telemetry.registry.gauge(
    "mxtpu_slo_availability",
    "rolling-window availability SLI, per model")
SLO_P99 = _telemetry.registry.gauge(
    "mxtpu_slo_p99_seconds",
    "rolling-window p99 end-to-end latency SLI, per model")
SLO_BURN = _telemetry.registry.gauge(
    "mxtpu_slo_burn_rate",
    "error-budget burn rate (1.0 = spending exactly the budget the "
    "objective allows), per model")
SLO_BUDGET = _telemetry.registry.gauge(
    "mxtpu_slo_error_budget_remaining",
    "fraction of the error budget left in the rolling window "
    "(0 = exhausted -> readiness blocker), per model")


# the generation worker's loop, cut into phases -----------------------------
#: every phase of the ContinuousBatcher worker's loop; the thread is in
#: exactly one at any time (docs/observability.md "Worker-loop phases")
PHASES = ("wait", "admit", "prefill_host", "prefill_wait", "operands",
          "decode_wait", "emit")

_loop_tl = threading.local()


class LoopClock:
    """Phase accounting of ONE generation worker thread, cut once where
    the work happens and read two ways: each boundary adds the elapsed
    time to ``mxtpu_serve_loop_seconds{model, phase}`` (always) and
    opens a ``telemetry.trace_span`` (while the tracer is active, which
    during a profiler capture also puts it into the device trace).

    Phases nest as ``with`` blocks and time is exclusive: a phase is
    charged only while it is the innermost.  Outside any block the
    thread is in ``admit`` — the batcher's own scheduling between
    phases.  A *step* (:func:`loop_step`) is a block inside whatever
    phase is open, exclusive in the same way: its time stays in the
    phase's seconds and is also added to
    ``mxtpu_serve_loop_step_seconds{model, phase, step}``, so the steps
    subdivide a phase and what none covers is its remainder.  Each
    boundary between two phases also reads the thread's CPU time
    (``mxtpu_serve_loop_cpu_seconds{model, phase}``): wall minus CPU is
    time the thread was not running.  The batcher binds a clock to its
    worker thread (:meth:`bind`); the engine and the block pool reach it
    through :func:`loop_phase`, :func:`loop_phase_switch` and
    :func:`loop_step`, which do nothing on a thread that has none (an
    engine driven directly)."""

    def __init__(self, model: str, seconds: dict, cpu_seconds: dict,
                 step_seconds: dict):
        self.model = model
        # the batcher's totals: wall and CPU by PHASES, steps by
        # (phase, step)
        self.seconds = seconds
        self.cpu_seconds = cpu_seconds
        self.step_seconds = step_seconds
        self.phase = "admit"
        self.step = None
        self._t = time.perf_counter()
        self._cpu = time.thread_time()
        self._open = []             # the nested _Phase blocks
        self._series = {}           # (phase, step) -> the registry's adders

    def bind(self) -> None:
        _loop_tl.clock = self

    def _adders(self, phase: str, step: str):
        """The three series a turn out of ``(phase, step)`` adds to, their
        label keys built once (a turn costs the loop a few microseconds,
        a dozen times a dispatch)."""
        labels = {"model": self.model, "phase": phase}
        return (LOOP_SECONDS.bound(**labels),
                LOOP_CPU_SECONDS.bound(**labels),
                LOOP_STEP_SECONDS.bound(step=step, **labels)
                if step is not None else None)

    def _turn(self, phase: str, step: str = None) -> None:
        now = time.perf_counter()
        dt, self._t = now - self._t, now
        was = (self.phase, self.step)
        adders = self._series.get(was)
        if adders is None:
            adders = self._series[was] = self._adders(*was)
        wall, cpu_s, in_step = adders
        self.seconds[was[0]] += dt
        wall(dt)
        if in_step is not None:
            self.step_seconds[was] = self.step_seconds.get(was, 0.0) + dt
            in_step(dt)
        if phase != was[0]:
            # CPU time is by phase: the thread clock (a system call, where
            # the wall clock is not) is read where the phase changes only
            cpu = time.thread_time()
            dcpu, self._cpu = cpu - self._cpu, cpu
            self.cpu_seconds[was[0]] += dcpu
            cpu_s(dcpu)
        self.phase, self.step = phase, step


class _Phase:
    """One ``with`` block of the loop: a phase (``step`` False: ``name``
    is the phase, and no step is open inside it until one begins) or a
    step of the phase that is open (``name`` is the step)."""
    __slots__ = ("clock", "name", "span_name", "attrs", "span", "outer",
                 "step")

    def __init__(self, clock, name, span_name, attrs, step=False):
        self.clock = clock
        self.name = name
        self.span_name = span_name
        self.attrs = attrs
        self.span = None
        self.outer = None
        self.step = step

    def _begin_span(self):
        if self.span_name is not None and _telemetry.tracer.active:
            attrs = dict(self.attrs, model=self.clock.model)
            self.span = _telemetry.tracer._begin(self.span_name, "serving",
                                                 attrs=attrs)

    def _end_span(self):
        if self.span is not None:
            _telemetry.tracer._end(self.span)
            self.span = None

    def __enter__(self):
        clock = self.clock
        if clock is not None:
            self.outer = (clock.phase, clock.step)
            if self.step:
                clock._turn(clock.phase, self.name)
            else:
                clock._turn(self.name)
            clock._open.append(self)
            self._begin_span()
        return self

    def __exit__(self, *exc):
        clock = self.clock
        if clock is not None:
            self._end_span()
            clock._open.pop()       # ``with`` blocks close innermost first
            clock._turn(*self.outer)
        return False


def loop_phase(name: str, span: str = None, **attrs) -> _Phase:
    """``with loop_phase("emit", "serve.emit", step=n): ...`` — this
    thread's worker loop is in phase ``name`` for the block, under a
    span named ``span`` (None: counted only)."""
    return _Phase(getattr(_loop_tl, "clock", None), name, span, attrs)


def loop_step(name: str, span: str = None, **attrs) -> _Phase:
    """``with loop_step("hash", "serve.join.hash"): ...`` — the block is
    step ``name`` of whatever phase this thread's worker loop is in,
    under a span named ``span``.  Exclusive like the phases: a step is
    charged only while it is the innermost block, and a block that opens
    the step it is already inside is that step going on (no boundary, no
    second span)."""
    clock = getattr(_loop_tl, "clock", None)
    if clock is not None and clock.step == name:
        clock = None
    return _Phase(clock, name, span, attrs, step=True)


def loop_phase_switch(name: str, span: str = None, **attrs) -> None:
    """End the innermost open phase of this thread's loop here and begin
    ``name`` in its place, as its sibling; the ``with`` that opened the
    first then closes the second.  For a boundary that lies inside a
    callee: the batcher opens ``operands`` around the engine call, and
    the engine, once the program is enqueued, switches to
    ``decode_wait``.  Steps are closed by then: a switch made inside an
    open step does nothing."""
    clock = getattr(_loop_tl, "clock", None)
    if clock is None or not clock._open:
        return
    ph = clock._open[-1]
    if ph.step or ph.name == name:
        return
    ph._end_span()
    clock._turn(name)
    # the sibling goes on with the same work: it keeps the ids
    ph.name, ph.span_name, ph.attrs = name, span, attrs or ph.attrs
    ph._begin_span()
