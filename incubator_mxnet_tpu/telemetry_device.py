"""Device-memory observability plane + on-demand profiler capture
(docs/observability.md "Device plane").

Three cooperating pieces, all riding the shared telemetry spine:

* **Per-owner HBM attribution** — the runtime already knew who owns
  device memory (the KV :class:`~.serving.kvcache.BlockPool`, engine
  parameters, the ZeRO-1 optimizer shard) but each exporter spoke its
  own dialect.  Owners register a byte-count callback here
  (:func:`register_owner`) and :func:`sample` folds them into one
  labeled gauge, ``mxtpu_device_owned_bytes{owner=...}``, next to the
  whole-process ``mx_device_*`` gauges telemetry already samples.  The
  remainder (live jax array bytes no owner claims) lands in
  ``mxtpu_device_unattributed_bytes`` — a growing unattributed share is
  the classic slow leak.
* **OOM forensics** — a ``RESOURCE_EXHAUSTED`` dispatch failure
  (detected by :func:`is_oom` at the engine dispatch funnel, or an
  injected ``serving.infer:ioerror:RESOURCE_EXHAUSTED...`` fault)
  publishes a FAULT ``event="oom"`` which triggers a debounced
  FlightRecorder dump (``telemetry_ring``).  This module registers the
  two providers that make such a dump actionable: ``device_memory``
  (:func:`memory_breakdown` — per-device stats + per-owner bytes) and
  ``programs`` (:func:`program_report` — the dispatch ledger plus every
  live engine's program inventory and per-slot KV occupancy).
* **Profiler capture** — :func:`capture_profile` wraps
  ``jax.profiler.start_trace``/``stop_trace`` with a single-capture
  guard, writing one artifact directory per capture under
  ``MXNET_PROFILE_DIR`` (default ``<tmpdir>/mxtpu_profile``): the
  device trace (the ``.xplane.pb`` alone, no ``trace.json.gz``) with
  the program's spans in it as annotations (Python tracer off) and
  ``spans.json``.  Works on the CPU backend, so the
  serving route (``POST /debug/profile``) and the router fan-out
  round-trip in tests without a TPU.

A background sampler (:func:`start_sampler`) refreshes the memory
gauges every ``MXNET_DEVICE_MEM_INTERVAL_SECONDS`` (0 = disabled, the
default); exporters also refresh on scrape, so the sampler only matters
for processes nobody scrapes.
"""
from __future__ import annotations

import json
import os
import tempfile
import threading
import time
from typing import Callable, Dict, Optional

from .base import MXNetError, getenv, getenv_float
from . import context as _context
from . import telemetry as _telemetry
from . import telemetry_ring as _ring

__all__ = [
    "register_owner", "unregister_owner", "owned_bytes",
    "register_inventory", "unregister_inventory",
    "memory_breakdown", "program_report", "sample",
    "start_sampler", "stop_sampler",
    "is_oom", "report_oom",
    "CaptureBusy", "capture_profile", "capture_active",
    "default_profile_dir", "default_sample_interval",
]


def default_profile_dir() -> str:
    """``MXNET_PROFILE_DIR``: where profiler capture artifacts land."""
    return getenv("MXNET_PROFILE_DIR") \
        or os.path.join(tempfile.gettempdir(), "mxtpu_profile")


def default_sample_interval() -> float:
    """``MXNET_DEVICE_MEM_INTERVAL_SECONDS``: background memory-gauge
    sampling cadence (0 disables the sampler thread)."""
    return getenv_float("MXNET_DEVICE_MEM_INTERVAL_SECONDS", 0.0)


_g_owned = _telemetry.registry.gauge(
    "mxtpu_device_owned_bytes",
    "attributed device bytes, by owner (kv:<model>/params:<model>/"
    "optimizer)")
_g_unattributed = _telemetry.registry.gauge(
    "mxtpu_device_unattributed_bytes",
    "live jax array bytes no registered owner claims")
_c_captures = _telemetry.registry.counter(
    "mxtpu_profile_captures",
    "completed on-demand profiler captures")
_c_oom = _telemetry.registry.counter(
    "mxtpu_oom_failures",
    "RESOURCE_EXHAUSTED dispatch failures, by site")

_lock = threading.Lock()
_owners: Dict[str, Callable[[], float]] = {}
_inventories: Dict[str, Callable[[], dict]] = {}


# ---------------------------------------------------------------------------
# Per-owner attribution
# ---------------------------------------------------------------------------
def register_owner(owner: str, fn: Callable[[], float]) -> None:
    """Register (or replace) a device-memory owner: ``fn()`` returns the
    bytes currently attributed to ``owner``.  Conventional owner names:
    ``kv:<model>`` (BlockPool-backed KV cache), ``params:<model>``,
    ``optimizer`` (ZeRO-1 local shard)."""
    with _lock:
        _owners[owner] = fn
    _register_flight_providers()


def unregister_owner(owner: str) -> None:
    with _lock:
        _owners.pop(owner, None)


def owned_bytes() -> Dict[str, float]:
    """owner → bytes for every registered owner (a failing callback
    reports 0 — attribution must never take the program down)."""
    with _lock:
        owners = dict(_owners)
    out = {}
    for name, fn in owners.items():
        try:
            out[name] = float(fn() or 0.0)
        except Exception:
            out[name] = 0.0
    return out


# ---------------------------------------------------------------------------
# Program inventory providers (engines register; flight dumps consume)
# ---------------------------------------------------------------------------
def register_inventory(name: str, fn: Callable[[], dict]) -> None:
    """Register (or replace) a per-engine program-inventory callback —
    ``fn()`` returns the engine's :meth:`program_inventory` dict
    (expected vs compiled programs, per-program dispatch counts,
    per-slot KV occupancy)."""
    with _lock:
        _inventories[name] = fn
    _register_flight_providers()


def unregister_inventory(name: str) -> None:
    with _lock:
        _inventories.pop(name, None)


def program_report() -> dict:
    """The runtime program-set inventory: the global dispatch ledger
    plus every registered engine's own accounting.  This is the payload
    behind ``GET /programs`` and the ``programs`` flight provider."""
    with _lock:
        inventories = dict(_inventories)
    engines = {}
    for name, fn in inventories.items():
        try:
            engines[name] = fn()
        except Exception as e:      # a sick engine is itself data
            engines[name] = {"error": repr(e)}
    return {"sites": _telemetry.dispatch_ledger(), "engines": engines}


# ---------------------------------------------------------------------------
# Memory breakdown + gauges
# ---------------------------------------------------------------------------
def memory_breakdown() -> dict:
    """JSON-ready device-memory forensics: per-device bytes-in-use /
    peak watermarks (``memory_stats()`` where the backend has it), the
    live-array total, and the per-owner attribution.  Never raises."""
    out = {"devices": {}, "owners": owned_bytes(),
           "live_array_bytes": 0.0}
    # a process that never placed anything (router, supervisor) has no
    # device memory, and asking jax would take the chip from the replica
    # that does
    if _context.backend_in_use():
        import jax
        try:
            out["live_array_bytes"] = float(sum(
                getattr(a, "nbytes", 0) or 0 for a in jax.live_arrays()))
        except Exception:
            pass
        try:
            for d in jax.devices():
                try:
                    stats = d.memory_stats()
                except Exception:
                    continue
                if not stats:
                    continue
                out["devices"][f"{d.platform}:{d.id}"] = {
                    k: stats[k] for k in
                    ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")
                    if k in stats}
        except Exception:
            pass
    total_owned = sum(out["owners"].values())
    out["owned_bytes"] = total_owned
    out["unattributed_bytes"] = max(
        0.0, out["live_array_bytes"] - total_owned)
    return out


def sample() -> dict:
    """Refresh every device-memory gauge (the ``mx_device_*`` trio plus
    the per-owner attribution) and return the breakdown."""
    _telemetry.sample_device_memory()
    bd = memory_breakdown()
    for owner, nbytes in bd["owners"].items():
        _g_owned.set(nbytes, owner=owner)
    _g_unattributed.set(bd["unattributed_bytes"])
    return bd


# ---------------------------------------------------------------------------
# Background sampler
# ---------------------------------------------------------------------------
_sampler_stop: Optional[threading.Event] = None


def start_sampler(interval: Optional[float] = None) -> bool:
    """Start the background gauge sampler at ``interval`` seconds
    (default ``MXNET_DEVICE_MEM_INTERVAL_SECONDS``); returns False (and
    starts nothing) when the interval is 0 or a sampler already runs."""
    global _sampler_stop
    iv = default_sample_interval() if interval is None \
        else float(interval)
    if iv <= 0:
        return False
    with _lock:
        if _sampler_stop is not None:
            return False
        stop = _sampler_stop = threading.Event()

    def loop():
        while not stop.wait(iv):
            try:
                sample()
            except Exception:
                pass

    threading.Thread(target=loop, name="mxtpu-device-mem",
                     daemon=True).start()
    return True


def stop_sampler() -> None:
    global _sampler_stop
    with _lock:
        stop = _sampler_stop
        _sampler_stop = None
    if stop is not None:
        stop.set()


# ---------------------------------------------------------------------------
# OOM forensics
# ---------------------------------------------------------------------------
def is_oom(err: BaseException) -> bool:
    """True when ``err`` is a device out-of-memory: jax surfaces these
    as ``XlaRuntimeError`` with a ``RESOURCE_EXHAUSTED:`` status prefix
    (message-matched so injected faults carrying the same marker drill
    the identical path)."""
    return "RESOURCE_EXHAUSTED" in f"{type(err).__name__}: {err}"


def report_oom(site: str, err: BaseException, **ctx) -> None:
    """Publish the FAULT ``oom`` event for a RESOURCE_EXHAUSTED dispatch
    failure.  The flight recorder's ``oom`` trigger turns it into one
    debounced postmortem dump whose ``device_memory`` and ``programs``
    providers carry the breakdown an operator needs; extra ``ctx``
    (``model=``, ``request_ids=``) rides along on the ring entry so the
    dump names the implicated requests."""
    _c_oom.inc(site=site)
    try:        # gauges first: the dump's metrics snapshot should show
        sample()        # the memory picture AT the failure, not stale
    except Exception:
        pass
    _telemetry.FAULT.publish(site=site, event="oom",
                             error=f"{type(err).__name__}: {err}"[:300],
                             **ctx)


# ---------------------------------------------------------------------------
# On-demand profiler capture
# ---------------------------------------------------------------------------
class CaptureBusy(MXNetError):
    """A profiler capture is already in flight (single-capture guard —
    ``jax.profiler`` supports one trace at a time per process)."""


_capture_lock = threading.Lock()
_capture_active = False
_capture_seq = 0

#: capture bounds: floor keeps a capture observable, ceiling keeps an
#: HTTP-triggered capture from parking a server thread for minutes
CAPTURE_MIN_SECONDS = 0.05
CAPTURE_MAX_SECONDS = 60.0


def capture_active() -> bool:
    return _capture_active


def capture_profile(seconds: float,
                    out_dir: Optional[str] = None) -> str:
    """Capture a ``jax.profiler`` trace for ``seconds`` (clamped to
    [0.05, 60]) into a fresh artifact directory under ``out_dir`` /
    ``MXNET_PROFILE_DIR`` and return its path.  Blocks for the capture
    window.  Raises :class:`CaptureBusy` while another capture runs —
    the serving route maps that to HTTP 409.

    The capture runs with the Python tracer off: the host's share of the
    trace is the program's own spans, which the tracer enters as
    ``TraceAnnotation``s for the length of the capture
    (``Tracer.annotate``), so they lie on the clock of the device's
    lines and the host code in question runs undisturbed.  The capture
    holds one ``tracer.enable()`` reference, so a plain server records
    spans during a capture and none outside it; the trees of the window
    are written to ``spans.json`` beside the device trace."""
    global _capture_active, _capture_seq
    import jax
    seconds = min(CAPTURE_MAX_SECONDS,
                  max(CAPTURE_MIN_SECONDS, float(seconds)))
    with _capture_lock:
        if _capture_active:
            raise CaptureBusy("profiler capture already in progress")
        _capture_active = True
        _capture_seq += 1
        seq = _capture_seq
    base = out_dir or default_profile_dir()
    path = os.path.join(base, f"capture_{os.getpid()}_{seq:03d}")
    os.makedirs(path, exist_ok=True)
    tracer = _telemetry.tracer
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    try:
        tracer.enable()
        since = tracer.now()
        spans = None
        try:
            jax.profiler.start_trace(path, profiler_options=options)
            tracer.annotate = jax.profiler.TraceAnnotation
            try:
                time.sleep(seconds)
                # the window's spans; stopping below also collects the
                # trace, which on a chip takes far longer than the window
                spans = tracer.tree(max_finished=None, since=since)
            finally:
                # annotate until the profiler has stopped: what the device
                # records last has its host span too
                try:
                    _stop_trace(path)
                finally:
                    tracer.annotate = None
        finally:
            tracer.disable()
        with open(os.path.join(path, "spans.json"), "w") as f:
            json.dump(spans, f, default=str)   # attrs are free-form
        _c_captures.inc()
    finally:
        with _capture_lock:
            _capture_active = False
    return path


def _stop_trace(log_dir: str) -> None:
    """``jax.profiler.stop_trace()`` that writes the ``.xplane.pb`` and
    nothing else, where ``stop_trace`` has it.  jax's export also turns
    every event into a gzipped ``trace.json``, which no reader of a
    capture opens and which costs more than collecting the trace: a 3 s
    capture of GPT-2-medium decode bursts on a v5e (1.15 M device events,
    97.5 MB) took 46.5 s to stop and 65.2 s more to export."""
    import socket
    import jax
    try:
        from jax._src.profiler import _profile_state as state
        session = state.profile_session
        stop, reset = session.stop, state.reset
    except (ImportError, AttributeError):    # another jax: its own export
        jax.profiler.stop_trace()
        return
    with state.lock:
        try:
            xspace = stop()
        finally:
            reset()
    # where jax's export puts it: <log_dir>/plugins/profile/<run>/<host>
    run = os.path.join(log_dir, "plugins", "profile",
                       time.strftime("%Y_%m_%d_%H_%M_%S"))
    os.makedirs(run, exist_ok=True)
    with open(os.path.join(run, socket.gethostname() + ".xplane.pb"),
              "wb") as f:
        f.write(xspace)


def _register_flight_providers() -> None:
    """The two providers every oom/watchdog/breaker flight dump of a
    DEVICE-HOLDING process should carry.  Registered with the first owner
    or inventory, not at import: the router and the supervisor import
    this package and dump through the same recorder, and they hold no
    device (idempotent — the recorder keys providers by name)."""
    _ring.recorder.register_provider("device_memory", memory_breakdown)
    _ring.recorder.register_provider("programs", program_report)
