"""Unified runtime telemetry: multi-subscriber event bus + cross-layer
metrics registry (reference analog: the reference's profiler counters +
``MXNET_PROFILER_*`` plane, generalized into an always-on, low-overhead
observability spine for the whole runtime).

Two cooperating pieces:

* **Event bus** — named :class:`Topic` objects that any number of
  subscribers can attach to concurrently.  This replaces the single-slot
  ``_op_observer`` hook in ``ndarray/ndarray.py``: the profiler and the
  telemetry collector (and any user code) can observe the same op stream
  at once.  Publishing to a topic with no subscribers is a single list
  truthiness check — the instrumented hot paths stay effectively free
  when nothing is listening.
* **Metrics registry** — process-wide :class:`Counter` / :class:`Gauge` /
  :class:`Histogram` (bounded reservoir with p50/p95/p99/max), exported
  three ways: :func:`render_prometheus` (text exposition format),
  :func:`snapshot` (JSON-ready dict: ``mxtpu-stats --format json``), and counter samples woven into the profiler's chrome-trace
  ``dump()`` as ``ph:"C"`` events.

Instrumented layers (see docs/observability.md):

* eager op dispatch — op counts per name, sync-block counts, host<->device
  transfer bytes (``ndarray/ndarray.py``)
* JIT/compile — compile count, cache hit/miss, compile seconds
  (``executor.py``, ``gluon/block.py`` _CachedGraph, ``parallel/spmd.py``,
  ``kvstore.py`` mesh reducer) via :func:`instrument_jit`
* kvstore — push/pull/pushpull calls, bytes, latency histograms
* gluon trainer — step/update timing
* dataloader — per-batch fetch-wait time
* device memory — gauges sampled from ``jax.live_arrays()`` /
  ``device.memory_stats()`` at export time
* resilience — injected faults, retries/give-ups, skipped steps and
  dataloader fallbacks (``fault.py``; FAULT topic, ``mxtpu_retries`` /
  ``mxtpu_giveups`` / ``mxtpu_skipped_steps`` counters)

Three further planes layered on the same spine (this file + satellites):

* **Span tracer** — hierarchical :class:`Span` trees with thread-local
  context propagation (``with trace_span("trainer.step"): ...``,
  ``@traced``).  The training path is instrumented end-to-end (trainer
  step → spmd dispatch → kvstore push/pull → dataloader fetch →
  executor/cached-op compile+dispatch), and finished spans render as
  nested ``ph:"X"`` events in the profiler's chrome-trace ``dump()`` —
  a proper flame graph next to the ``ph:"C"`` counter tracks.
* **Cost-analysis accountant** — :func:`instrument_jit` captures XLA's
  ``jit(...).lower(...).compile().cost_analysis()`` flops/bytes once per
  compiled executable and publishes them per call on the ``XLA_COST``
  topic; the collector accumulates them and, at each trainer-step
  boundary, computes **MFU** = step-window FLOPs / wall seconds /
  :func:`device_peak_flops` (TPU generation table, CPU estimate) into
  the ``mxtpu_mfu`` gauge and the ``mxtpu_step_seconds`` histogram.
* **HTTP exporter** (``telemetry_http.py``) — stdlib ``http.server``
  background thread serving ``/metrics`` (Prometheus text), ``/healthz``
  and ``/trace`` (live span tree as JSON, bounded by ``?limit=`` /
  ``?since=`` and searchable by ``?request_id=``).
* **Flight recorder** (``telemetry_ring.py``) — a lock-cheap bounded
  ring continuously recording recent FAULT events, finished spans and
  metric deltas; it auto-dumps a postmortem JSON on watchdog restarts,
  breaker trips, non-finite-guard skips, SIGTERM drain and worker
  crashes.  :func:`start`/:func:`stop` hold one reference on it.

Control plane: ``MXNET_TELEMETRY=1`` starts collection at import;
``MXNET_TELEMETRY_DUMP=/path`` additionally writes a dump at process exit
(Prometheus text if the path ends in ``.prom``/``.txt``, JSON otherwise);
``MXNET_TELEMETRY_PORT=<port>`` starts collection AND the HTTP exporter.
The ``mxtpu-stats`` console script (``_cli.py``) runs any script under
telemetry and prints the dump (``--serve`` adds the live endpoint).
"""
from __future__ import annotations

import atexit
import json
import os
import threading
import time
from collections import deque
from typing import Callable, Dict, List, Optional

from .base import MXNetError, getenv, getenv_bool

__all__ = [
    "Topic", "EventBus", "bus",
    "OP_DISPATCH", "OP_TIMED", "SYNC", "TRANSFER", "COMPILE", "KVSTORE",
    "TRAINER", "DATALOADER", "SPAN", "XLA_COST", "FAULT", "HEALTH",
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "registry",
    "counter", "gauge", "histogram",
    "merge_states", "render_prometheus_state",
    "Span", "Tracer", "tracer", "trace_span", "traced", "current_span",
    "new_request_id",
    "start", "stop", "enabled", "reset",
    "snapshot", "render_prometheus", "counters_flat", "dump",
    "instrument_jit", "sample_device_memory",
    "dispatch_ledger", "reset_dispatch_ledger",
    "StepHealthRing", "health_ring",
    "TPU_PEAK_FLOPS", "tpu_peak_flops", "cpu_peak_flops",
    "device_peak_flops",
]


# ---------------------------------------------------------------------------
# Event bus
# ---------------------------------------------------------------------------
class Topic:
    """A named event stream.  ``subscribers`` is copy-on-write: mutations
    build a NEW list under ``_lock`` and swap it in atomically, so
    ``publish`` fans out over a stable snapshot without ever taking the
    lock on the hot path — a subscribe/unsubscribe racing a concurrent
    publish can neither drop another subscriber's registration nor
    deliver an event to the same subscriber twice.  A subscriber that
    raises is counted in ``errors`` and skipped — an observer must never
    take the observed program down.

    ``forcing`` counts non-passive subscribers.  Publishers whose
    instrumentation is expensive (OP_TIMED forces a per-op device sync)
    key the decision to pay that cost on ``forcing``, so a passive
    listener (the telemetry collector) can ride along whenever an active
    one (the profiler) turns the firehose on, without turning it on
    itself."""

    __slots__ = ("name", "subscribers", "errors", "last_error", "forcing",
                 "_passive", "_lock")

    def __init__(self, name: str):
        self.name = name
        self.subscribers: List[Callable] = []
        self.errors = 0
        self.last_error: Optional[BaseException] = None
        self.forcing = 0
        self._passive = set()
        self._lock = threading.Lock()

    def subscribe(self, fn: Callable, passive: bool = False) -> Callable:
        with self._lock:
            if fn not in self.subscribers:
                self.subscribers = self.subscribers + [fn]
                if passive:
                    self._passive.add(id(fn))
                else:
                    self.forcing += 1
        return fn

    def unsubscribe(self, fn: Callable) -> None:
        with self._lock:
            # locate by EQUALITY, first occurrence: a re-created bound
            # method (obj.meth is a fresh object per access) must still
            # unsubscribe the one registered earlier — but the passive
            # bookkeeping is keyed on the REGISTERED object's id
            try:
                idx = self.subscribers.index(fn)
            except ValueError:
                return
            registered = self.subscribers[idx]
            fresh = list(self.subscribers)
            del fresh[idx]
            self.subscribers = fresh
            if id(registered) in self._passive:
                self._passive.discard(id(registered))
            else:
                self.forcing -= 1

    def publish(self, *args, **kwargs) -> None:
        # one atomic attribute read = the fan-out snapshot; mutations only
        # ever swap in fresh lists, never modify this one in place
        for fn in self.subscribers:
            try:
                fn(*args, **kwargs)
            except Exception as e:
                self.errors += 1
                self.last_error = e


class EventBus:
    """Registry of Topics; ``topic(name)`` is get-or-create."""

    def __init__(self):
        self._topics: Dict[str, Topic] = {}
        self._lock = threading.Lock()

    def topic(self, name: str) -> Topic:
        t = self._topics.get(name)
        if t is None:
            with self._lock:
                t = self._topics.setdefault(name, Topic(name))
        return t

    def subscribe(self, name: str, fn: Callable,
                  passive: bool = False) -> Callable:
        return self.topic(name).subscribe(fn, passive=passive)

    def unsubscribe(self, name: str, fn: Callable) -> None:
        self.topic(name).unsubscribe(fn)

    def publish(self, name: str, *args, **kwargs) -> None:
        self.topic(name).publish(*args, **kwargs)

    def topics(self) -> List[str]:
        return sorted(self._topics)


bus = EventBus()

# Canonical runtime topics.  Payload contracts:
#   OP_DISPATCH(name)                 — one eager op dispatched (not traced)
#   OP_TIMED(name, seconds)           — op with true synchronous duration;
#                                       subscribing FORCES per-op sync
#   SYNC(kind)                        — a blocking call (wait_to_read/asnumpy)
#   TRANSFER(direction, nbytes)       — "h2d" | "d2h" host<->device bytes
#   COMPILE(where=, event=, seconds=) — event in {"miss","hit"}; miss carries
#                                       trace+compile seconds when measurable
#   KVSTORE(op=, nbytes=, seconds=)   — op in {"push","pull","pushpull"}
#   TRAINER(phase=, seconds=)         — phase in {"step","update"}
#   DATALOADER(seconds=)              — consumer-side batch fetch wait
#   SPAN(span)                        — a finished ROOT span (full subtree)
#   XLA_COST(where=, flops=, nbytes=) — one dispatch of a compiled
#                                       executable, with its cost-analysis
#                                       flops / bytes-accessed
#   FAULT(site=, event=, kind=, ...)  — resilience plane (fault.py): event
#                                       in {"injected","retry","giveup",
#                                       "skipped_step","fallback","anomaly"};
#                                       retry adds attempt=/seconds=
#   HEALTH(kind=, step=, src=, ...)   — health plane (health.py): one
#                                       detected training/decode anomaly;
#                                       kind in {"nonfinite","loss_spike",
#                                       "grad_norm_explosion",
#                                       "nonfinite_generation"}, leaf=
#                                       names the first offending
#                                       parameter by tree path
OP_DISPATCH = bus.topic("op.dispatch")
OP_TIMED = bus.topic("op.timed")
SYNC = bus.topic("op.sync")
TRANSFER = bus.topic("transfer")
COMPILE = bus.topic("compile")
KVSTORE = bus.topic("kvstore")
TRAINER = bus.topic("trainer")
DATALOADER = bus.topic("dataloader")
SPAN = bus.topic("span")
XLA_COST = bus.topic("xla.cost")
FAULT = bus.topic("fault")
HEALTH = bus.topic("health")


# ---------------------------------------------------------------------------
# Metrics
# ---------------------------------------------------------------------------
def _label_key(labels: dict):
    return tuple(sorted(labels.items()))


def _fmt_num(v) -> str:
    f = float(v)
    return str(int(f)) if f == int(f) else repr(f)


class Counter:
    """Monotonic counter, optionally broken out by labels
    (``c.inc(3, op="dot")``)."""

    kind = "counter"

    def __init__(self, name: str, help: str = ""):
        self.name = name
        self.help = help
        self._lock = threading.Lock()
        self._values: Dict[tuple, float] = {}

    def inc(self, amount: float = 1.0, **labels) -> None:
        if amount < 0:
            raise MXNetError(f"counter {self.name}: negative increment")
        key = _label_key(labels)
        with self._lock:
            self._values[key] = self._values.get(key, 0.0) + amount

    def bound(self, **labels):
        """``add(amount)`` for ONE label set, its key built once — for a
        caller that adds to the same few series at every turn of a loop
        (``serving.metrics.LoopClock``); ``amount`` is not checked."""
        key = _label_key(labels)
        lock, values = self._lock, self._values

        def add(amount: float) -> None:
            with lock:
                values[key] = values.get(key, 0.0) + amount
        return add

    @property
    def value(self) -> float:
        return sum(self._values.values())

    def sample(self):
        """JSON-ready value: plain number when unlabeled, else
        ``{"total": t, "by": {"op=dot": n, ...}}``."""
        with self._lock:
            vals = dict(self._values)
        if not vals or set(vals) == {()}:
            return vals.get((), 0.0)
        return {
            "total": sum(vals.values()),
            "by": {",".join(f"{k}={v}" for k, v in key): val
                   for key, val in sorted(vals.items()) if key},
        }

    def _reset(self):
        with self._lock:
            self._values.clear()


class Gauge:
    """Last-write-wins value, optionally labeled."""

    kind = "gauge"

    def __init__(self, name: str, help: str = ""):
        self.name = name
        self.help = help
        self._lock = threading.Lock()
        self._values: Dict[tuple, float] = {}

    def set(self, value: float, **labels) -> None:
        with self._lock:
            self._values[_label_key(labels)] = float(value)

    def inc(self, amount: float = 1.0, **labels) -> None:
        key = _label_key(labels)
        with self._lock:
            self._values[key] = self._values.get(key, 0.0) + amount

    def dec(self, amount: float = 1.0, **labels) -> None:
        self.inc(-amount, **labels)

    @property
    def value(self) -> float:
        with self._lock:
            return self._values.get((), 0.0) if not self._values else \
                sum(self._values.values())

    def sample(self):
        with self._lock:
            vals = dict(self._values)
        if not vals or set(vals) == {()}:
            return vals.get((), 0.0)
        return {",".join(f"{k}={v}" for k, v in key) or "_": val
                for key, val in sorted(vals.items())}

    def _reset(self):
        with self._lock:
            self._values.clear()


class Histogram:
    """Bounded-reservoir histogram: keeps the last ``max_samples``
    observations for percentiles plus exact count/sum/max over the full
    stream.  Exported in Prometheus summary form (quantile series +
    ``_count``/``_sum``) with an extra ``_max`` series.

    The default reservoir holds 4096 samples so the p99 estimate rests
    on the ~41 largest observations of the window instead of the ~20 a
    2048-deep reservoir would give it — stable enough for the SLO
    engine (serving/slo.py) to alarm on."""

    kind = "histogram"

    def __init__(self, name: str, help: str = "", max_samples: int = 4096):
        self.name = name
        self.help = help
        self._lock = threading.Lock()
        self._samples = deque(maxlen=max_samples)
        self._count = 0
        self._sum = 0.0
        self._max = None

    def observe(self, value: float) -> None:
        v = float(value)
        with self._lock:
            self._samples.append(v)
            self._count += 1
            self._sum += v
            if self._max is None or v > self._max:
                self._max = v

    @property
    def count(self) -> int:
        return self._count

    def percentile(self, q: float) -> Optional[float]:
        with self._lock:
            data = sorted(self._samples)
        if not data:
            return None
        idx = min(len(data) - 1, max(0, int(round(q * (len(data) - 1)))))
        return data[idx]

    def stats(self) -> dict:
        with self._lock:
            data = sorted(self._samples)
            count, total, mx = self._count, self._sum, self._max
        if not data:
            return {"count": 0, "sum": 0.0, "p50": None, "p95": None,
                    "p99": None, "max": None}

        def pct(q):
            return data[min(len(data) - 1,
                            max(0, int(round(q * (len(data) - 1)))))]
        return {"count": count, "sum": total, "p50": pct(0.5),
                "p95": pct(0.95), "p99": pct(0.99), "max": mx}

    def sample(self):
        return self.stats()

    def state(self) -> dict:
        """Mergeable export: exact ``count``/``sum``/``max`` plus the raw
        reservoir, so another process can union distributions instead of
        averaging pre-computed quantiles (which under-merges the tail —
        a per-replica p99 of 10ms and 1s does NOT average to a fleet
        p99)."""
        with self._lock:
            return {"count": self._count, "sum": self._sum,
                    "max": self._max, "samples": list(self._samples)}

    @staticmethod
    def merge(states, max_samples: int = 4096) -> dict:
        """Union N :meth:`state` exports into one state.  count/sum/max
        merge exactly; reservoirs concatenate, and when the union
        overflows ``max_samples`` each source is downsampled to its
        proportional share by evenly-spaced picks over its SORTED
        samples — a deterministic quantile sketch (no RNG), so merged
        percentiles are reproducible across runs and processes."""
        srcs = [s for s in states if s and s.get("count")]
        count = sum(int(s["count"]) for s in srcs)
        total = sum(float(s["sum"]) for s in srcs)
        maxes = [s["max"] for s in srcs if s.get("max") is not None]
        pools = [sorted(float(v) for v in (s.get("samples") or ()))
                 for s in srcs]
        pools = [p for p in pools if p]
        kept = sum(len(p) for p in pools)
        if kept <= max_samples:
            merged = sorted(v for p in pools for v in p)
        else:
            merged = []
            for p in pools:
                k = max(1, int(round(max_samples * len(p) / kept)))
                k = min(k, len(p))
                if k == len(p):
                    merged.extend(p)
                elif k == 1:
                    merged.append(p[len(p) // 2])
                else:
                    step = (len(p) - 1) / (k - 1)
                    merged.extend(p[int(round(j * step))]
                                  for j in range(k))
            merged.sort()
            del merged[max_samples:]
        return {"count": count, "sum": total,
                "max": max(maxes) if maxes else None, "samples": merged}

    @staticmethod
    def stats_of(state: dict) -> dict:
        """The :meth:`stats` summary of a :meth:`state`/:meth:`merge`
        export (nearest-rank percentiles over its reservoir)."""
        data = sorted(float(v) for v in (state.get("samples") or ()))
        if not data:
            return {"count": 0, "sum": 0.0, "p50": None, "p95": None,
                    "p99": None, "max": None}

        def pct(q):
            return data[min(len(data) - 1,
                            max(0, int(round(q * (len(data) - 1)))))]
        return {"count": int(state.get("count") or 0),
                "sum": float(state.get("sum") or 0.0),
                "p50": pct(0.5), "p95": pct(0.95), "p99": pct(0.99),
                "max": state.get("max")}

    def _reset(self):
        with self._lock:
            self._samples.clear()
            self._count = 0
            self._sum = 0.0
            self._max = None


class MetricsRegistry:
    """Process-wide name → metric store with get-or-create accessors and
    the three exporters."""

    def __init__(self):
        self._metrics: Dict[str, object] = {}
        self._lock = threading.Lock()

    def _get(self, cls, name, help, **kw):
        m = self._metrics.get(name)
        if m is None:
            with self._lock:
                m = self._metrics.get(name)
                if m is None:
                    m = cls(name, help, **kw)
                    self._metrics[name] = m
        if not isinstance(m, cls):
            raise MXNetError(
                f"metric {name!r} already registered as {m.kind}")
        return m

    def counter(self, name: str, help: str = "") -> Counter:
        return self._get(Counter, name, help)

    def gauge(self, name: str, help: str = "") -> Gauge:
        return self._get(Gauge, name, help)

    def histogram(self, name: str, help: str = "",
                  max_samples: int = 4096) -> Histogram:
        return self._get(Histogram, name, help, max_samples=max_samples)

    def get(self, name: str):
        return self._metrics.get(name)

    def metrics(self):
        return [self._metrics[k] for k in sorted(self._metrics)]

    def reset(self) -> None:
        """Zero every metric (registrations survive)."""
        for m in list(self._metrics.values()):
            m._reset()

    # -- exporters ------------------------------------------------------
    def snapshot(self) -> dict:
        out = {"counters": {}, "gauges": {}, "histograms": {}}
        for m in self.metrics():
            out[m.kind + "s"][m.name] = m.sample()
        return out

    def counters_flat(self) -> Dict[str, float]:
        """name → total value for every counter and gauge (the chrome-trace
        ``ph:"C"`` feed used by profiler.dump())."""
        return {m.name: m.value for m in self.metrics()
                if m.kind in ("counter", "gauge")}

    def export_state(self) -> dict:
        """Lossless JSON-ready export for cross-process federation:
        counters/gauges keep their per-label-set values (label sets as
        ``"k=v,k2=v2"`` strings, ``""`` for unlabeled), histograms export
        their full :meth:`Histogram.state` reservoir.  The router fetches
        this from every replica and folds them with
        :func:`merge_states`."""
        out = {"counters": {}, "gauges": {}, "histograms": {}}
        for m in self.metrics():
            if m.kind in ("counter", "gauge"):
                with m._lock:
                    vals = dict(m._values)
                out[m.kind + "s"][m.name] = {
                    "help": m.help,
                    "values": {",".join(f"{k}={v}" for k, v in key): val
                               for key, val in sorted(vals.items())}}
            else:
                st = m.state()
                st["help"] = m.help
                out["histograms"][m.name] = st
        return out

    def render_prometheus(self) -> str:
        lines = []
        for m in self.metrics():
            if m.help:
                lines.append(f"# HELP {m.name} {m.help}")
            if m.kind in ("counter", "gauge"):
                lines.append(f"# TYPE {m.name} {m.kind}")
                with m._lock:
                    vals = dict(m._values)
                if not vals:
                    lines.append(f"{m.name} 0")
                for key, val in sorted(vals.items()):
                    label = "{" + ",".join(
                        f'{k}="{v}"' for k, v in key) + "}" if key else ""
                    lines.append(f"{m.name}{label} {_fmt_num(val)}")
            else:
                lines.append(f"# TYPE {m.name} summary")
                s = m.stats()
                for q, k in (("0.5", "p50"), ("0.95", "p95"),
                             ("0.99", "p99")):
                    if s[k] is not None:
                        lines.append(
                            f'{m.name}{{quantile="{q}"}} {repr(s[k])}')
                lines.append(f"{m.name}_sum {repr(float(s['sum']))}")
                lines.append(f"{m.name}_count {int(s['count'])}")
                if s["max"] is not None:
                    lines.append(f"{m.name}_max {repr(s['max'])}")
        return "\n".join(lines) + "\n"


registry = MetricsRegistry()


def merge_states(states, max_samples: int = 4096) -> dict:
    """Fold N :meth:`MetricsRegistry.export_state` exports into one
    state of the same shape: counters and gauges sum per label set,
    histograms union via :meth:`Histogram.merge`.  Summing gauges gives
    fleet totals for capacity-style gauges (inflight, queue depth); the
    ratio-style SLO gauges are federated properly by the router's fleet
    ``/slo`` from merged windows, not from here."""
    out = {"counters": {}, "gauges": {}, "histograms": {}}
    for kind in ("counters", "gauges"):
        for st in states:
            for name, m in (st or {}).get(kind, {}).items():
                dst = out[kind].setdefault(
                    name, {"help": m.get("help", ""), "values": {}})
                for label, val in (m.get("values") or {}).items():
                    dst["values"][label] = \
                        dst["values"].get(label, 0.0) + float(val)
    hist_names = {}
    for st in states:
        for name, hs in (st or {}).get("histograms", {}).items():
            hist_names.setdefault(name, []).append(hs)
    for name, parts in hist_names.items():
        merged = Histogram.merge(parts, max_samples=max_samples)
        merged["help"] = next(
            (p.get("help") for p in parts if p.get("help")), "")
        out["histograms"][name] = merged
    return out


def render_prometheus_state(state: dict, extra_labels: dict = None,
                            type_lines: bool = True) -> str:
    """Prometheus text exposition of an :func:`merge_states` /
    :meth:`MetricsRegistry.export_state` state.  ``extra_labels`` are
    appended to every series (the router renders per-replica series with
    ``replica="host:port"`` and stale snapshots with ``stale="true"``)."""
    extra = ",".join(f'{k}="{v}"' for k, v in (extra_labels or {}).items())
    lines = []

    def fmt_labels(label_str):
        parts = [f'{k}="{v}"' for k, v in
                 (kv.split("=", 1) for kv in label_str.split(",") if kv)]
        if extra:
            parts.append(extra)
        return "{" + ",".join(parts) + "}" if parts else ""

    for kind, ptype in (("counters", "counter"), ("gauges", "gauge")):
        for name in sorted((state or {}).get(kind, {})):
            m = state[kind][name]
            if type_lines:
                if m.get("help"):
                    lines.append(f"# HELP {name} {m['help']}")
                lines.append(f"# TYPE {name} {ptype}")
            vals = m.get("values") or {}
            if not vals:
                lines.append(f"{name}{fmt_labels('')} 0")
            for label, val in sorted(vals.items()):
                lines.append(f"{name}{fmt_labels(label)} {_fmt_num(val)}")
    for name in sorted((state or {}).get("histograms", {})):
        hs = state["histograms"][name]
        if type_lines:
            if hs.get("help"):
                lines.append(f"# HELP {name} {hs['help']}")
            lines.append(f"# TYPE {name} summary")
        s = Histogram.stats_of(hs)
        for q, k in (("0.5", "p50"), ("0.95", "p95"), ("0.99", "p99")):
            if s[k] is not None:
                lines.append(f'{name}{{quantile="{q}"'
                             + (f",{extra}" if extra else "")
                             + f'}} {repr(s[k])}')
        tail = fmt_labels("")
        lines.append(f"{name}_sum{tail} {repr(float(s['sum']))}")
        lines.append(f"{name}_count{tail} {int(s['count'])}")
        if s["max"] is not None:
            lines.append(f"{name}_max{tail} {repr(s['max'])}")
    return "\n".join(lines) + ("\n" if lines else "")


def counter(name: str, help: str = "") -> Counter:
    return registry.counter(name, help)


def gauge(name: str, help: str = "") -> Gauge:
    return registry.gauge(name, help)


def histogram(name: str, help: str = "",
              max_samples: int = 4096) -> Histogram:
    return registry.histogram(name, help, max_samples=max_samples)


# ---------------------------------------------------------------------------
# Span tracer
# ---------------------------------------------------------------------------
def new_request_id() -> str:
    """A fresh 16-hex request/trace id (the server-generated fallback
    when a client did not supply ``x-request-id``)."""
    import uuid
    return uuid.uuid4().hex[:16]


_span_seq = __import__("itertools").count(1)


class Span:
    """One timed region of the program: name, category, wall window
    (``time.perf_counter`` floats), free-form attrs, child spans, and the
    ident of the thread that opened it.  Spans form trees: a span opened
    while another is current on the same thread (or under an explicit
    ``parent=``) becomes its child.  ``sid`` is a process-unique hex id
    so a span can be referenced from outside its tree (batch-span links,
    ``/trace`` lookups).  ``ann`` holds the profiler annotation entered
    with the span while ``tracer.annotate`` is set (else None)."""

    __slots__ = ("name", "cat", "t0", "t1", "attrs", "children", "tid",
                 "parent", "sid", "ann")

    def __init__(self, name: str, cat: str = "span", attrs: dict = None):
        self.name = name
        self.cat = cat
        self.attrs = attrs or None
        self.t0 = None
        self.t1 = None
        self.tid = 0
        self.sid = f"{next(_span_seq):08x}"
        self.ann = None
        self.parent: Optional["Span"] = None
        self.children: List["Span"] = []

    @property
    def seconds(self) -> Optional[float]:
        if self.t0 is None or self.t1 is None:
            return None
        return self.t1 - self.t0

    def to_dict(self, epoch: float = 0.0, now: float = None) -> dict:
        d = {"name": self.name, "cat": self.cat, "id": self.sid,
             "start_s": None if self.t0 is None
             else round(self.t0 - epoch, 6)}
        if self.t1 is not None:
            d["duration_s"] = round(self.t1 - self.t0, 6)
        else:
            d["open"] = True
            if now is not None and self.t0 is not None:
                d["duration_s"] = round(now - self.t0, 6)
        if self.attrs:
            d["attrs"] = dict(self.attrs)
        if self.children:
            d["children"] = [c.to_dict(epoch, now)
                             for c in list(self.children)]
        return d


class _SpanCtx:
    """Context manager returned by :func:`trace_span` — a no-op when the
    tracer is inactive, so instrumented hot paths pay one attribute check
    (no generator frame) when nothing is tracing."""

    __slots__ = ("_name", "_cat", "_parent", "_attrs", "span")

    def __init__(self, name, cat, parent, attrs):
        self._name = name
        self._cat = cat
        self._parent = parent
        self._attrs = attrs
        self.span = None

    def __enter__(self):
        if tracer.active:
            self.span = tracer._begin(self._name, self._cat, self._parent,
                                      self._attrs)
        return self.span

    def __exit__(self, *exc):
        if self.span is not None:
            tracer._end(self.span)
        return False


class Tracer:
    """Hierarchical span recorder with thread-local context propagation.

    Enabled by refcount (:meth:`enable` / :meth:`disable`):
    ``telemetry.start()`` and ``profiler.set_state("run")`` each hold one
    reference, so tracing is on whenever either plane collects.  Finished
    ROOT spans (whole subtrees) land in a bounded deque and on the
    ``SPAN`` topic; open roots are tracked for the live ``/trace`` view.

    Cross-thread propagation: capture the current span in the parent
    thread (``ctx = tracer.current()``) and either open child spans with
    ``trace_span(..., parent=ctx)`` or wrap the worker's body in
    ``with tracer.attach(ctx): ...`` so its spans nest under ``ctx``.

    The profiler bridge: while :attr:`annotate` is set (to a factory
    ``(name, **ids) -> context manager``;
    ``telemetry_device.capture_profile`` sets it to
    ``jax.profiler.TraceAnnotation`` for the length of a capture), every
    span that opens also enters an annotation of the same name, on the
    opening thread, and exits it when the span ends — so the program's
    spans lie in the profiler's trace on the clock of the device's
    lines.  This module never imports jax: the router and the supervisor
    import it and hold no device."""

    #: span attrs that identify work and ride into the annotation
    ANNOTATION_IDS = ("request_id", "slot", "step", "model")

    def __init__(self, max_finished: int = 512):
        self._tl = threading.local()
        self._lock = threading.Lock()
        self._enable_count = 0
        self.annotate: Optional[Callable] = None
        self._live: Dict[int, Span] = {}
        self._finished = deque(maxlen=max_finished)
        self._epoch = time.perf_counter()
        self._main_tid = threading.main_thread().ident

    @property
    def active(self) -> bool:
        return self._enable_count > 0

    def enable(self) -> None:
        with self._lock:
            self._enable_count += 1

    def disable(self) -> None:
        with self._lock:
            self._enable_count = max(0, self._enable_count - 1)

    def clear(self) -> None:
        """Drop recorded spans (live roots stay: their owners still hold
        them open)."""
        with self._lock:
            self._finished.clear()

    # -- span lifecycle -------------------------------------------------
    def _stack(self) -> List[Span]:
        s = getattr(self._tl, "stack", None)
        if s is None:
            s = self._tl.stack = []
        return s

    def _begin(self, name, cat="span", parent=None, attrs=None) -> Span:
        stack = self._stack()
        par = parent if parent is not None else \
            (stack[-1] if stack else None)
        if par is None:
            # a remote parent (another process's span, delivered via
            # X-Trace-Id) can't be a tree edge — record it as linkage
            # attrs so the router's stitcher re-parents this subtree
            rc = getattr(self._tl, "remote", None)
            if rc is not None:
                attrs = dict(attrs) if attrs else {}
                attrs.setdefault("trace_id", rc[0])
                attrs.setdefault("remote_parent", rc[1])
        sp = Span(name, cat, attrs)
        sp.t0 = time.perf_counter()
        sp.tid = threading.get_ident()
        sp.parent = par
        if par is not None:
            par.children.append(sp)     # list.append: atomic under the GIL
        else:
            with self._lock:
                self._live[id(sp)] = sp
        stack.append(sp)
        factory = self.annotate
        if factory is not None:
            ids = {k: attrs[k] for k in self.ANNOTATION_IDS
                   if k in attrs} if attrs else {}
            try:
                ann = factory(name, **ids)
                ann.__enter__()
                sp.ann = ann
            except Exception:       # a profiler fault never fails the span
                pass
        return sp

    def _end(self, sp: Span) -> None:
        ann = sp.ann
        if ann is not None:     # also after the capture stopped: a no-op
            sp.ann = None
            try:
                ann.__exit__(None, None, None)
            except Exception:
                pass
        sp.t1 = time.perf_counter()
        stack = self._stack()
        if stack and stack[-1] is sp:
            stack.pop()
        elif sp in stack:               # mis-nested exit: still unwind
            stack.remove(sp)
        if sp.parent is None:
            with self._lock:
                self._live.pop(id(sp), None)
                self._finished.append(sp)
            if SPAN.subscribers:
                SPAN.publish(sp)

    def span(self, name: str, cat: str = "span", parent: Span = None,
             **attrs) -> _SpanCtx:
        return _SpanCtx(name, cat, parent, attrs)

    def record(self, name: str, t0: float, t1: float, parent: Span = None,
               cat: str = "span", **attrs) -> Optional[Span]:
        """A finished span with explicit ``time.perf_counter`` times, for
        an interval no thread sat inside (a request's wait in the queue).
        Lands under ``parent`` or, without one, among the finished roots;
        it carries no profiler annotation.  None while tracing is off."""
        if not self.active:
            return None
        sp = Span(name, cat, attrs)
        sp.t0, sp.t1 = float(t0), float(t1)
        sp.tid = threading.get_ident()
        sp.parent = parent
        if parent is not None:
            parent.children.append(sp)
        else:
            with self._lock:
                self._finished.append(sp)
            if SPAN.subscribers:
                SPAN.publish(sp)
        return sp

    def current(self) -> Optional[Span]:
        stack = getattr(self._tl, "stack", None)
        return stack[-1] if stack else None

    class _Attach:
        __slots__ = ("_span",)

        def __init__(self, span):
            self._span = span

        def __enter__(self):
            tracer._stack().append(self._span)
            return self._span

        def __exit__(self, *exc):
            stack = tracer._stack()
            if stack and stack[-1] is self._span:
                stack.pop()
            elif self._span in stack:
                stack.remove(self._span)
            return False

    class _RemoteAttach:
        __slots__ = ("_ctx", "_prev")

        def __init__(self, ctx):
            self._ctx = ctx
            self._prev = None

        def __enter__(self):
            self._prev = getattr(tracer._tl, "remote", None)
            tracer._tl.remote = self._ctx
            return self._ctx

        def __exit__(self, *exc):
            tracer._tl.remote = self._prev
            return False

    def remote(self, trace_id: str,
               parent_sid: str) -> "Tracer._RemoteAttach":
        """Adopt a REMOTE parent for root spans opened on this thread
        while the context is held: each such span gets ``trace_id`` and
        ``remote_parent`` attrs naming the upstream hop span it belongs
        under.  This is the replica half of cross-process trace
        propagation — ``serving/server.py`` wraps request handling in
        ``tracer.remote(*parsed_x_trace_id)`` and the router's
        ``GET /trace`` stitcher grafts the resulting subtree under the
        hop span whose sid matches ``remote_parent``."""
        return Tracer._RemoteAttach((str(trace_id), str(parent_sid)))

    def attach(self, span: Span) -> "Tracer._Attach":
        """Adopt ``span`` as this thread's current span (does not close
        it) — the worker-thread half of cross-thread propagation."""
        return Tracer._Attach(span)

    # -- exports --------------------------------------------------------
    def _roots(self) -> List[Span]:
        with self._lock:
            return list(self._finished) + list(self._live.values())

    def now(self) -> float:
        """The present on the clock of :meth:`tree`: seconds since
        tracer creation."""
        return time.perf_counter() - self._epoch

    def tree(self, max_finished: Optional[int] = 64,
             since: Optional[float] = None) -> dict:
        """JSON-ready view for the HTTP ``/trace`` endpoint: currently
        open root spans plus the most recent finished ones (None: every
        one still buffered).  Times are seconds since tracer creation;
        ``since`` (same clock) drops roots that started before it, so a
        long-running server can be polled incrementally instead of
        re-serialized whole."""
        now = time.perf_counter()
        with self._lock:
            live = list(self._live.values())
            fin = list(self._finished)
        if since is not None:
            cutoff = self._epoch + float(since)
            live = [s for s in live if s.t0 is None or s.t0 >= cutoff]
            fin = [s for s in fin if s.t0 is None or s.t0 >= cutoff]
        if max_finished is not None:
            fin = fin[max(0, len(fin) - max(0, int(max_finished))):]
        return {
            "epoch_perf_counter": self._epoch,
            "live": [s.to_dict(self._epoch, now) for s in live],
            "finished": [s.to_dict(self._epoch) for s in fin],
        }

    def find_spans(self, attr: str, value, limit: int = 32) -> List[dict]:
        """Bounded lookup: spans (any depth, newest roots first) whose
        ``attrs[attr] == value``, as JSON-ready subtrees.  The per-request
        ``/trace?request_id=`` view — cost is one walk over the bounded
        finished/live roots, never the whole history."""
        now = time.perf_counter()
        with self._lock:
            roots = list(self._live.values()) + list(self._finished)[::-1]
        out: List[dict] = []

        def walk(sp: Span):
            if len(out) >= limit:
                return
            if sp.attrs and sp.attrs.get(attr) == value:
                out.append(sp.to_dict(self._epoch, now))
                return                  # the subtree already rides along
            for ch in list(sp.children):
                walk(ch)

        for root in roots:
            if len(out) >= limit:
                break
            walk(root)
        return out

    def chrome_events(self, t0: float) -> List[dict]:
        """Finished spans (any depth) overlapping [t0, now) as chrome
        ``ph:"X"`` events with ts/dur in µs relative to ``t0`` — the
        profiler merges these into its ``dump()``.  The main thread maps
        to tid 0 so spans nest with the profiler's own op events."""
        out = []
        tid_map = {self._main_tid: 0}

        def walk(sp: Span):
            if sp.t0 is not None and sp.t1 is not None and sp.t1 >= t0:
                tid = tid_map.setdefault(sp.tid, len(tid_map))
                ev = {"name": sp.name, "ph": "X",
                      "ts": max(0.0, (sp.t0 - t0) * 1e6),
                      "dur": max(0.0, (sp.t1 - max(sp.t0, t0)) * 1e6),
                      "pid": 0, "tid": tid, "cat": sp.cat}
                if sp.attrs:
                    ev["args"] = dict(sp.attrs)
                out.append(ev)
            for ch in list(sp.children):
                walk(ch)

        for root in self._roots():
            walk(root)
        return out


tracer = Tracer()


def trace_span(name: str, cat: str = "span", parent: Span = None,
               **attrs) -> _SpanCtx:
    """``with trace_span("trainer.step"): ...`` — open a span under the
    thread's current one (no-op while tracing is off)."""
    return tracer.span(name, cat, parent, **attrs)


def current_span() -> Optional[Span]:
    return tracer.current()


def traced(arg=None, cat: str = "span"):
    """Decorator form: ``@traced`` or ``@traced("name", cat=...)`` wraps
    the function body in a span."""
    import functools

    def make(fn, name):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            if not tracer.active:
                return fn(*args, **kwargs)
            with tracer.span(name, cat=cat):
                return fn(*args, **kwargs)
        return wrapper

    if callable(arg):
        return make(arg, getattr(arg, "__qualname__", arg.__name__))
    return lambda fn: make(fn, arg or getattr(fn, "__qualname__",
                                              fn.__name__))


# ---------------------------------------------------------------------------
# Device peak FLOP/s detection (MFU denominator)
# ---------------------------------------------------------------------------
# bf16 peak FLOP/s PER CHIP by TPU generation (public specs: Google Cloud
# TPU documentation); longest key wins so 'v5 lite' beats 'v5'.  The
# package's ONE table (the benchmark keeps its own beside its rooflines,
# benchmark/chip/peaks.json), and a kind it does not list is an error.
TPU_PEAK_FLOPS = {
    "v2": 46e12,
    "v3": 123e12,
    "v4": 275e12,
    "v5 lite": 197e12,
    "v5litepod": 197e12,
    "v5e": 197e12,
    "v5p": 459e12,
    "v6 lite": 918e12,
    "v6e": 918e12,
}


def tpu_peak_flops(kind: str) -> float:
    """Per-chip bf16 peak for a jax ``device_kind`` string (e.g. 'TPU v5
    lite').  An unknown kind raises ``MXNetError``: a utilization computed
    against a guessed peak is a number about no device."""
    k = (kind or "").lower().replace("tpu", "").strip()
    best = None
    for key, val in TPU_PEAK_FLOPS.items():
        if key in k and (best is None or len(key) > len(best[0])):
            best = (key, val)
    if best is None:
        raise MXNetError(
            f"no peak FLOP/s on record for device kind {kind!r}; add it to "
            "telemetry.TPU_PEAK_FLOPS with its source")
    return best[1]


def cpu_peak_flops() -> float:
    """Order-of-magnitude host fp32 peak: cores x clock x 32 FLOPs/cycle
    (two 256-bit FMA ports).  An ESTIMATE — good enough to make CPU MFU
    finite and comparable across runs on the same box, not across
    machines (see docs/observability.md for the caveats)."""
    cores = os.cpu_count() or 1
    ghz = 2.0
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.lower().startswith("cpu mhz"):
                    ghz = max(ghz, float(line.split(":")[1]) / 1000.0)
                    break
    except Exception:
        pass
    return cores * ghz * 1e9 * 32.0


def device_peak_flops() -> Optional[float]:
    """Aggregate peak FLOP/s over the LOCAL devices — TPU: per-chip table
    x local chip count (bf16); CPU: one host-wide estimate regardless of
    virtual device count.  None when undetectable (unknown platform)."""
    try:
        import jax
        devs = jax.local_devices()
    except Exception:
        return None
    if not devs:
        return None
    platform = getattr(devs[0], "platform", "")
    if platform == "tpu":
        return tpu_peak_flops(getattr(devs[0], "device_kind", "")) \
            * len(devs)
    if platform == "cpu":
        return cpu_peak_flops()
    return None


# ---------------------------------------------------------------------------
# Device memory gauges
# ---------------------------------------------------------------------------
def sample_device_memory() -> None:
    """Refresh the device-memory gauges from the live jax client.  Never
    raises: backends without memory_stats (CPU) just contribute the
    live-array total.  A no-op in a process that holds no device yet
    (``context.backend_in_use``): a scrape must never be what opens the
    chip."""
    from . import context as _context
    if not _context.backend_in_use():
        return
    import jax
    g_live = registry.gauge(
        "mx_device_live_array_bytes",
        "total bytes of live jax arrays (all devices)")
    try:
        live = jax.live_arrays()
        g_live.set(sum(getattr(a, "nbytes", 0) or 0 for a in live))
    except Exception:
        pass
    try:
        g_use = registry.gauge("mx_device_bytes_in_use",
                               "per-device bytes in use (memory_stats)")
        g_peak = registry.gauge("mx_device_peak_bytes_in_use",
                                "per-device peak bytes (memory_stats)")
        for d in jax.devices():
            stats = None
            try:
                stats = d.memory_stats()
            except Exception:
                continue
            if not stats:
                continue
            dev = f"{d.platform}:{d.id}"
            if "bytes_in_use" in stats:
                g_use.set(stats["bytes_in_use"], device=dev)
            if "peak_bytes_in_use" in stats:
                g_peak.set(stats["peak_bytes_in_use"], device=dev)
    except Exception:
        pass


# ---------------------------------------------------------------------------
# Dispatch ledger (device-plane observability; docs/observability.md)
# ---------------------------------------------------------------------------
# One entry per instrument_jit site, ALWAYS on: per-dispatch count, a
# bounded wall-time reservoir, compile accounting (while the collector
# observes), the wall clock of the last dispatch, and a live handle to
# the pjit cache size.  This is the runtime program-set inventory — the
# dynamic counterpart of mxtpu-lint's static closed-program-set check:
# a site whose cache keeps growing after warmup, or a compiled program
# that is never dispatched, shows up here at runtime.
_LEDGER_RESERVOIR = 512


class _LedgerEntry:
    __slots__ = ("site", "dispatches", "seconds_sum", "seconds_max",
                 "samples", "compiles", "compile_seconds", "last_t",
                 "size_fn", "lock", "_key")

    def __init__(self, site: str):
        self.site = site
        self.dispatches = 0
        self.seconds_sum = 0.0
        self.seconds_max = 0.0
        self.samples = deque(maxlen=_LEDGER_RESERVOIR)
        self.compiles = 0
        self.compile_seconds = 0.0
        self.last_t: Optional[float] = None
        self.size_fn: Optional[Callable[[], int]] = None
        self.lock = threading.Lock()
        self._key = (("site", site),)   # precomputed counter label key

    def record(self, dt: float) -> None:
        c = _ledger_dispatches
        with c._lock:
            c._values[self._key] = c._values.get(self._key, 0.0) + 1.0
        _ledger_seconds.observe(dt)
        with self.lock:
            self.dispatches += 1
            self.seconds_sum += dt
            if dt > self.seconds_max:
                self.seconds_max = dt
            self.samples.append(dt)
            self.last_t = time.time()

    def record_compile(self, dt: float) -> None:
        with self.lock:
            self.compiles += 1
            self.compile_seconds += dt

    def _reset(self) -> None:
        with self.lock:
            self.dispatches = 0
            self.seconds_sum = 0.0
            self.seconds_max = 0.0
            self.samples.clear()
            self.compiles = 0
            self.compile_seconds = 0.0
            self.last_t = None


_ledger_dispatches = registry.counter(
    "mxtpu_dispatches_total",
    "compiled-program dispatches, by instrumented jit site")
_ledger_seconds = registry.histogram(
    "mxtpu_dispatch_seconds",
    "host wall seconds per compiled-program dispatch (all sites): the "
    "call that enqueues the program, not the device's time")
_ledger: Dict[str, _LedgerEntry] = {}
_ledger_lock = threading.Lock()


def _ledger_entry(site: str) -> _LedgerEntry:
    e = _ledger.get(site)
    if e is None:
        with _ledger_lock:
            e = _ledger.setdefault(site, _LedgerEntry(site))
    return e


def dispatch_ledger(prefix: Optional[str] = None) -> Dict[str, dict]:
    """JSON-ready snapshot of the per-site dispatch ledger: dispatch
    count, wall-time stats over the bounded reservoir, compile count and
    blocking seconds (counted while the collector observes), seconds
    since the last dispatch, and — when the wrapped pjit exposes its
    cache — the number of executables currently compiled at the site.
    ``prefix`` filters sites (e.g. ``"serving:gen"`` for one engine's
    programs)."""
    now = time.time()
    out: Dict[str, dict] = {}
    for site in sorted(_ledger):
        if prefix is not None and not site.startswith(prefix):
            continue
        e = _ledger[site]
        with e.lock:
            data = sorted(e.samples)
            d = {
                "site": site,
                "dispatches": e.dispatches,
                "seconds_sum": round(e.seconds_sum, 6),
                "seconds_max": round(e.seconds_max, 6),
                "compiles": e.compiles,
                "compile_seconds": round(e.compile_seconds, 6),
                "last_dispatch_age_s": None if e.last_t is None
                else round(now - e.last_t, 3),
            }
        if data:
            d["seconds_p50"] = round(
                data[min(len(data) - 1,
                         int(round(0.5 * (len(data) - 1))))], 6)
            d["seconds_p99"] = round(
                data[min(len(data) - 1,
                         int(round(0.99 * (len(data) - 1))))], 6)
        size_fn = e.size_fn
        compiled = None
        if size_fn is not None:
            try:
                compiled = int(size_fn())
            except Exception:
                compiled = None
        d["compiled"] = compiled
        out[site] = d
    return out


def reset_dispatch_ledger() -> None:
    """Zero every ledger entry in place (test hygiene; the entries stay
    registered — instrument_jit wrappers hold direct references)."""
    with _ledger_lock:
        entries = list(_ledger.values())
    for e in entries:
        e._reset()


# ---------------------------------------------------------------------------
# StepHealth ring (health plane, health.py)
# ---------------------------------------------------------------------------
class StepHealthRing:
    """Bounded ring of per-train-step health records.

    One record per inner step, folded in by :class:`health.HealthMonitor`
    at chunk/step boundaries (the stats themselves are computed inside
    the donated programs — see health.py).  A record is a JSON-ready
    dict: ``step``, ``src``, ``loss`` (None on the eager fused path,
    which never sees the loss), ``grad_norm``, ``max_update_ratio``,
    ``finite`` and — when not finite — ``nonfinite_leaf``, the first
    offending parameter by tree path.

    Capacity comes from ``MXNET_HEALTH_RING`` (default 256; re-read on
    :meth:`clear` so tests can resize)."""

    def __init__(self, size: Optional[int] = None):
        self._size = size
        self._lock = threading.Lock()
        self._ring: deque = deque(maxlen=self._capacity())

    def _capacity(self) -> int:
        from .base import getenv_int
        n = self._size if self._size is not None \
            else getenv_int("MXNET_HEALTH_RING", 256)
        return max(1, int(n))

    def record(self, rec: dict) -> None:
        with self._lock:
            self._ring.append(rec)

    def entries(self, last: Optional[int] = None) -> List[dict]:
        with self._lock:
            out = list(self._ring)
        return out[-int(last):] if last else out

    def last(self) -> Optional[dict]:
        with self._lock:
            return self._ring[-1] if self._ring else None

    def clear(self) -> None:
        with self._lock:
            self._ring = deque(maxlen=self._capacity())

    def __len__(self) -> int:
        with self._lock:
            return len(self._ring)


#: process-wide StepHealth ring — the training twin of the flight
#: recorder's activity ring; telemetry.reset() clears it
health_ring = StepHealthRing()


# ---------------------------------------------------------------------------
# Compile instrumentation + cost accountant
# ---------------------------------------------------------------------------
def _arg_signature(args, kwargs):
    """Hashable (treedef, leaf shapes/dtypes) key identifying which cached
    executable a call hits — python scalars key by type only (jit treats
    them as dynamic weak-typed args, one compilation per type)."""
    import jax
    leaves, treedef = jax.tree_util.tree_flatten((args, kwargs))
    return treedef, tuple(
        (tuple(leaf.shape), str(leaf.dtype))
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype")
        else (type(leaf).__name__,)
        for leaf in leaves)


def _cost_analysis_dict(compiled) -> dict:
    """Normalize ``compiled.cost_analysis()`` — a list of dicts on CPU
    backends, a plain dict elsewhere — to one dict (possibly empty)."""
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return ca or {}


def instrument_jit(where: str, jitted: Callable) -> Callable:
    """Wrap a ``jax.jit`` callable so the runtime can observe it three
    ways, each gated on its own consumer and free when nobody listens:

    * **COMPILE topic** — cache hit/miss per call.  When the pjit object
      exposes ``_cache_size``, per-shape recompiles are detected exactly
      (the cache grew across the call → miss, with the blocking
      trace+compile seconds); otherwise the first invocation counts as
      the one miss.
    * **XLA_COST topic** — cost-analysis FLOPs / bytes-accessed of the
      executable this call dispatches, captured once per argument
      signature via AOT ``lower(...).compile().cost_analysis()`` and
      republished on every call (the MFU numerator).  Capture runs
      BEFORE the real call: with ``donate_argnums`` the arguments are
      dead afterwards.  The AOT compile does not warm jit's call cache,
      so each new signature costs one extra trace+compile — only while a
      cost subscriber is attached.
    * **Span tracer** — the dispatch is wrapped in a ``jit:<where>`` span
      while tracing is active, so compiled-call time nests under the
      caller's step/forward span in the flame graph.

    Independent of all three consumers, every call lands in the
    process-wide **dispatch ledger** (:func:`dispatch_ledger`): per-site
    dispatch counts, host wall-time histograms and last-dispatch age —
    the always-on runtime program inventory.  The wall time is that of
    the ``jitted(...)`` call, i.e. of the enqueue: jax returns before
    the device has done the work, and the wait is wherever the caller
    pulls the result.  Cost on the unobserved
    fast path: two ``perf_counter`` reads and two dict updates per
    dispatch."""
    size_fn = getattr(jitted, "_cache_size", None)
    lower_fn = getattr(jitted, "lower", None)
    state = {"first": True}
    costs: Dict[tuple, tuple] = {}
    span_name = "jit:" + where
    ledger = _ledger_entry(where)
    ledger.size_fn = size_fn       # latest wrapper wins (re-created jits)

    def _cost(args, kwargs):
        try:
            sig = _arg_signature(args, kwargs)
        except Exception:
            sig = None
        if sig is not None:
            hit = costs.get(sig)
            if hit is not None:
                return hit
        val = (0.0, 0.0)
        if lower_fn is not None:
            try:
                ca = _cost_analysis_dict(lower_fn(*args, **kwargs).compile())
                val = (float(ca.get("flops", 0.0) or 0.0),
                       float(ca.get("bytes accessed", 0.0) or 0.0))
            except Exception:
                pass
        if sig is not None:
            costs[sig] = val
        return val

    def call(*args, **kwargs):
        observing = bool(COMPILE.subscribers)
        costing = bool(XLA_COST.subscribers)
        tracing = tracer.active
        if not (observing or costing or tracing):
            t0 = time.perf_counter()
            out = jitted(*args, **kwargs)
            ledger.record(time.perf_counter() - t0)
            return out
        flops = nbytes = 0.0
        if costing:
            flops, nbytes = _cost(args, kwargs)
        before = None
        if observing and size_fn is not None:
            try:
                before = size_fn()
            except Exception:
                before = None
        sp = tracer._begin(span_name, "jit",
                           attrs={"flops": flops} if flops else None) \
            if tracing else None
        t0 = time.perf_counter()
        try:
            out = jitted(*args, **kwargs)
        finally:
            if sp is not None:
                tracer._end(sp)
        dt = time.perf_counter() - t0
        ledger.record(dt)
        if observing:
            grew = None
            if before is not None:
                try:
                    grew = size_fn() > before
                except Exception:
                    grew = None
            if grew is None:
                grew = state["first"]
            if grew:
                ledger.record_compile(dt)
                COMPILE.publish(where=where, event="miss", seconds=dt)
            else:
                COMPILE.publish(where=where, event="hit")
        state["first"] = False
        if costing:
            XLA_COST.publish(where=where, flops=flops, nbytes=nbytes)
        return out

    call.__wrapped__ = jitted
    return call


# ---------------------------------------------------------------------------
# Collector: the default subscribers that turn bus events into metrics
# ---------------------------------------------------------------------------
_started = False
_m: Dict[str, object] = {}


def _metrics_init():
    c, h = registry.counter, registry.histogram
    _m["ops"] = c("mx_op_dispatch_total",
                  "eager ops dispatched, by op name")
    _m["op_seconds"] = h("mx_op_seconds",
                         "synchronous per-op seconds (profiler-timed path)")
    _m["sync"] = c("mx_sync_block_total",
                   "blocking sync calls (wait_to_read/asnumpy), by kind")
    _m["h2d"] = c("mx_transfer_h2d_bytes_total",
                  "host->device transfer bytes")
    _m["d2h"] = c("mx_transfer_d2h_bytes_total",
                  "device->host transfer bytes")
    _m["compile"] = c("mx_compile_total", "XLA compiles, by site")
    _m["compile_hit"] = c("mx_compile_cache_hits_total",
                          "compiled-executable cache hits, by site")
    _m["compile_miss"] = c("mx_compile_cache_misses_total",
                           "compiled-executable cache misses, by site")
    _m["compile_seconds"] = h("mx_compile_seconds",
                              "blocking trace+compile seconds")
    _m["kv_calls"] = c("mx_kvstore_calls_total",
                       "kvstore calls, by op (push/pull/pushpull)")
    _m["kv_push_bytes"] = c("mx_kvstore_push_bytes_total",
                            "bytes pushed into the kvstore")
    _m["kv_pull_bytes"] = c("mx_kvstore_pull_bytes_total",
                            "bytes pulled out of the kvstore")
    _m["kv_push_seconds"] = h("mx_kvstore_push_seconds",
                              "kvstore push latency")
    _m["kv_pull_seconds"] = h("mx_kvstore_pull_seconds",
                              "kvstore pull latency")
    _m["kv_pushpull_seconds"] = h("mx_kvstore_pushpull_seconds",
                                  "kvstore fused push+pull latency")
    _m["steps"] = c("mx_trainer_steps_total", "trainer optimization steps")
    _m["step_seconds"] = h("mx_trainer_step_seconds",
                           "trainer step dispatch seconds")
    _m["update_seconds"] = h("mx_trainer_update_seconds",
                             "trainer update dispatch seconds")
    _m["batches"] = c("mx_dataloader_batches_total",
                      "dataloader batches fetched")
    _m["fetch_wait"] = h("mx_dataloader_fetch_wait_seconds",
                         "consumer wait per dataloader batch")
    g = registry.gauge
    _m["xla_flops"] = c("mx_xla_flops_total",
                        "cost-analysis FLOPs dispatched to compiled "
                        "executables, by site")
    _m["xla_bytes"] = c("mx_xla_bytes_total",
                        "cost-analysis bytes accessed by compiled "
                        "executables, by site")
    _m["step_wall"] = h("mxtpu_step_seconds",
                        "wall seconds between consecutive trainer step "
                        "boundaries (the MFU window)")
    _m["step_flops"] = g("mxtpu_step_flops",
                         "cost-analysis FLOPs in the last step window")
    _m["peak_flops"] = g("mxtpu_device_peak_flops",
                         "detected aggregate device peak FLOP/s")
    _m["mfu"] = g("mxtpu_mfu",
                  "model FLOPs utilization over the last step window")
    _m["faults"] = c("mxtpu_faults_injected",
                     "deterministic faults injected, by site/kind")
    _m["retries"] = c("mxtpu_retries",
                      "transient failures absorbed by retry, by site")
    _m["giveups"] = c("mxtpu_giveups",
                      "retries exhausted (max attempts/deadline), by site")
    _m["skipped_steps"] = c("mxtpu_skipped_steps",
                            "optimizer steps skipped on non-finite "
                            "gradients")
    _m["dl_fallbacks"] = c("mxtpu_dataloader_fallbacks",
                           "dataloader worker failures absorbed by "
                           "in-process fetch")
    _m["fused_updates"] = c("mxtpu_optimizer_fused_updates",
                            "whole-tree fused optimizer dispatches "
                            "(one jit call updating every parameter)")
    _m["dispatches_per_step"] = g("mxtpu_optimizer_dispatches_per_step",
                                  "optimizer-update dispatches in the "
                                  "last trainer step (1 = fused; "
                                  "num_params = per-param loop)")
    _m["loop_chunks"] = c("mxtpu_loop_chunks",
                          "CompiledLoop chunk dispatches (one donated "
                          "scanned program per k-step chunk)")
    _m["loop_chunk_seconds"] = h("mxtpu_loop_chunk_seconds",
                                 "CompiledLoop chunk dispatch seconds")
    _m["loop_steps_per_chunk"] = g("mxtpu_loop_steps_per_chunk",
                                   "train steps folded into the last "
                                   "CompiledLoop chunk")


_op_keys: Dict[str, tuple] = {}   # op name -> label key, spares the hot
                                  # path the kwargs/sort work of inc()


def _on_op_dispatch(name):
    key = _op_keys.get(name)
    if key is None:
        key = _op_keys[name] = (("op", name),)
    c = _m["ops"]
    with c._lock:
        c._values[key] = c._values.get(key, 0.0) + 1.0


def _on_op_timed(name, seconds):
    _m["op_seconds"].observe(seconds)


def _on_sync(kind):
    _m["sync"].inc(kind=kind)


def _on_transfer(direction, nbytes):
    _m["h2d" if direction == "h2d" else "d2h"].inc(nbytes)


def _on_compile(where="?", event="miss", seconds=None):
    if event == "miss":
        _m["compile"].inc(site=where)
        _m["compile_miss"].inc(site=where)
        if seconds is not None:
            _m["compile_seconds"].observe(seconds)
    else:
        _m["compile_hit"].inc(site=where)


def _on_kvstore(op="push", nbytes=0, seconds=0.0):
    _m["kv_calls"].inc(op=op)
    if op == "push" and nbytes:
        _m["kv_push_bytes"].inc(nbytes)
    elif op == "pull" and nbytes:
        _m["kv_pull_bytes"].inc(nbytes)
    key = f"kv_{op}_seconds"
    if key in _m:
        _m[key].observe(seconds)


# MFU accounting state.  FLOPs accumulate from XLA_COST as executables
# are dispatched; at each trainer-step boundary the window since the
# PREVIOUS boundary is closed: mfu = window FLOPs / wall seconds / peak.
# Wall time between boundaries (not the async dispatch seconds the
# TRAINER event carries) is the honest denominator — the device is busy
# long after dispatch returns.
_mfu = {"flops": 0.0, "last_t": None, "last_flops": 0.0, "peak": None}


def _on_xla_cost(where="?", flops=0.0, nbytes=0.0):
    if flops:
        _m["xla_flops"].inc(flops, site=where)
        _mfu["flops"] += flops
    if nbytes:
        _m["xla_bytes"].inc(nbytes, site=where)


def _on_trainer(phase="step", seconds=0.0, steps=1):
    if phase == "step":
        # steps > 1: a CompiledLoop chunk — k inner steps behind ONE
        # boundary.  Counters advance by k and per-step attribution
        # divides the window evenly; MFU itself is a window ratio, so
        # the formula is unchanged.
        n = max(int(steps), 1)
        _m["steps"].inc(n)
        _m["step_seconds"].observe(seconds / n)
        now = time.perf_counter()
        last_t = _mfu["last_t"]
        if last_t is not None and now > last_t:
            wall = now - last_t
            dflops = _mfu["flops"] - _mfu["last_flops"]
            _m["step_wall"].observe(wall / n)
            _m["step_flops"].set(dflops / n)
            peak = _mfu["peak"]
            if peak is None:
                peak = _mfu["peak"] = device_peak_flops() or 0.0
                _m["peak_flops"].set(peak)
            if peak > 0 and dflops > 0:
                _m["mfu"].set(dflops / wall / peak)
        _mfu["last_t"] = now
        _mfu["last_flops"] = _mfu["flops"]
    elif phase == "chunk":
        _m["loop_chunks"].inc()
        _m["loop_chunk_seconds"].observe(seconds)
        _m["loop_steps_per_chunk"].set(max(int(steps), 1))
    else:
        _m["update_seconds"].observe(seconds)


def _on_dataloader(seconds=0.0):
    _m["batches"].inc()
    _m["fetch_wait"].observe(seconds)


def _on_fault(site="?", event="injected", kind=None, **_kw):
    if event == "injected":
        _m["faults"].inc(site=site, kind=kind or "?")
    elif event == "retry":
        _m["retries"].inc(site=site)
    elif event == "giveup":
        _m["giveups"].inc(site=site)
    elif event == "skipped_step":
        _m["skipped_steps"].inc()
    elif event == "fallback":
        _m["dl_fallbacks"].inc(site=site)


_HANDLERS = (
    (OP_DISPATCH, _on_op_dispatch),
    (OP_TIMED, _on_op_timed),
    (SYNC, _on_sync),
    (TRANSFER, _on_transfer),
    (COMPILE, _on_compile),
    (KVSTORE, _on_kvstore),
    (TRAINER, _on_trainer),
    (DATALOADER, _on_dataloader),
    (XLA_COST, _on_xla_cost),
    (FAULT, _on_fault),
)


def start() -> None:
    """Begin collecting: subscribe the metric handlers to every runtime
    topic and turn the span tracer on.  Idempotent."""
    global _started
    if _started:
        return
    _metrics_init()
    for topic, fn in _HANDLERS:
        # OP_TIMED passively: the collector must never itself force the
        # per-op syncs that feed it — mx_op_seconds only fills while the
        # profiler (an active subscriber) has the timed path on
        topic.subscribe(fn, passive=topic is OP_TIMED)
    tracer.enable()
    _started = True
    # the black-box flight recorder rides whenever the collector does
    # (late import: telemetry_ring imports this module)
    from . import telemetry_ring
    telemetry_ring.recorder.start()


def stop() -> None:
    """Detach the collector (metric values are kept; see reset())."""
    global _started
    for topic, fn in _HANDLERS:
        topic.unsubscribe(fn)
    if _started:
        tracer.disable()
        from . import telemetry_ring
        telemetry_ring.recorder.stop()
    _started = False


def enabled() -> bool:
    return _started


def reset() -> None:
    """Zero all metric values, drop recorded spans, restart the MFU
    window, zero the dispatch ledger."""
    registry.reset()
    tracer.clear()
    reset_dispatch_ledger()
    health_ring.clear()
    _mfu.update(flops=0.0, last_t=None, last_flops=0.0, peak=None)


# ---------------------------------------------------------------------------
# Exporters (module-level conveniences over the default registry)
# ---------------------------------------------------------------------------
def snapshot(include_memory: bool = True) -> dict:
    """JSON-ready dict of every metric; refreshes device-memory gauges
    first (when collecting)."""
    if _started and include_memory:
        sample_device_memory()
    out = registry.snapshot()
    out["enabled"] = _started
    return out


def render_prometheus(include_memory: bool = True) -> str:
    """Prometheus text exposition of every metric."""
    if _started and include_memory:
        sample_device_memory()
    return registry.render_prometheus()


def counters_flat() -> Dict[str, float]:
    return registry.counters_flat()


def dump(path: str, fmt: Optional[str] = None) -> None:
    """Write the current metrics to ``path``: Prometheus text when ``fmt``
    is 'prometheus' (or the path ends in .prom/.txt), JSON otherwise."""
    if fmt is None:
        fmt = "prometheus" if path.endswith((".prom", ".txt")) else "json"
    with open(path, "w") as f:
        if fmt == "prometheus":
            f.write(render_prometheus())
        else:
            json.dump(snapshot(), f, indent=2, default=str)
            f.write("\n")


# ---------------------------------------------------------------------------
# Env autostart (reference parity with MXNET_PROFILER_AUTOSTART)
# ---------------------------------------------------------------------------
_dump_path = getenv("MXNET_TELEMETRY_DUMP")
if _dump_path:
    def _dump_at_exit(path=_dump_path):
        try:
            dump(path)
        except Exception:
            pass
    atexit.register(_dump_at_exit)

if getenv_bool("MXNET_TELEMETRY", False):
    start()

_port = getenv("MXNET_TELEMETRY_PORT")
if _port:
    try:
        start()
        from . import telemetry_http as _telemetry_http
        _telemetry_http.start_server(int(_port))
    except Exception as _e:                       # never break import
        import warnings
        warnings.warn(f"MXNET_TELEMETRY_PORT={_port}: exporter not "
                      f"started ({_e})")
