#!/usr/bin/env python
"""Self-healing fleet smoke (ci/run_tests.sh autoscale_smoke).

Two drills over the ``mxtpu-supervise`` plane (docs/robustness.md
"Self-healing fleet"), each supervising real ``replica`` child
processes serving a tiny GPT through the full ``:generate`` SSE path:

* ``restart`` — lifecycle supervision without load: the supervisor's
  only replica is SIGKILLed and must come back through
  restart-with-backoff (a ``backoff`` FAULT event per death, restart
  counted in ``mxtpu_supervise_restarts``); killed again faster than
  the flap budget allows, the slot must be QUARANTINED — removed from
  the router, left dead, an incident bundle dumped through the flight
  recorder into ``MXNET_FLIGHT_DUMP_DIR``.
* ``diurnal`` — the closed loop under chaos: a supervised fleet starts
  at 1 replica under a synthetic diurnal load curve (24 streaming
  clients at peak, 2 in the trough).  Peak queue pressure must scale
  the fleet 1→4 (one ``mxtpu_autoscale_events{action="up"}`` step at a
  time, cooldown between), while a chaos thread SIGKILLs random
  replicas mid-stream; the trough must shrink it 4→1, every scale-down
  routed through the router's drain (asserted against the FAULT topic:
  no ``supervisor.autoscale`` ``down`` without a ``router.admin``
  drain ``begin`` for that replica).  Contract: ZERO failed client
  requests — no transport error, no 5xx, no zero-token terminal
  ``error`` event (a mid-stream death is a loud ``error`` the client
  re-issues, and the retry must succeed).

``all`` runs ``restart`` then ``diurnal`` (the first warms the compile
cache the second's fleet spawns from — cold-start itself is
``router_smoke coldstart``'s business).
"""
import argparse
import http.client
import json
import os
import random
import re
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

PEAK_CLIENTS = 24
TROUGH_CLIENTS = 2
MAX_FLEET = 4
TOKENS_PER_REQUEST = 64     # heavy enough that peak load actually queues


# ------------------------------------------------------------ replica child
def run_replica(port, slots=2):
    import numpy as np
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.models.gpt import GPTModel
    from incubator_mxnet_tpu.serving import (GenerationEngine, ModelServer,
                                             lifecycle)
    mx.random.seed(3)
    net = GPTModel(vocab_size=50, units=32, hidden_size=64, num_layers=2,
                   num_heads=2, max_length=256, dropout=0.0)
    net.initialize(init=mx.init.Normal(0.6))
    net(mx.nd.array(np.zeros((1, 2), np.int32)))
    # few slots on purpose: the diurnal drill wants peak load to QUEUE
    # (mxtpu_serve_queue_depth is the autoscaler's up-pressure signal)
    eng = GenerationEngine(net, name="gen", max_slots=slots, max_len=256)
    srv = ModelServer(port=port, host="127.0.0.1")
    srv.add_model("gen", eng, warmup=True)
    srv.start()
    print(f"PORT {srv.port}", flush=True)
    sys.exit(lifecycle.run_until_shutdown(srv))


def _replica_command(cache_dir):
    """The supervisor's replica argv — the supervisor substitutes the
    slot's allocated port for ``{port}``."""
    return [sys.executable, os.path.abspath(__file__), "replica",
            "--port", "{port}"], {
        "JAX_PLATFORMS": "cpu",
        "JAX_COMPILATION_CACHE_DIR": cache_dir,
        "MXNET_DRAIN_SECONDS": "5",
        # The drill torches the error budget on purpose (queue-full
        # 429s drive the scale-up).  Park the replica-side SLO
        # readiness gate the same way run_diurnal parks the
        # autoscaler's burn thresholds: without this a lone replica
        # wedges — rejects exhaust its budget, ``slo:<model>`` pulls
        # it from rotation, and with zero traffic the window never
        # recovers.
        "MXNET_SERVE_SLO_MIN_REQUESTS": str(10 ** 9),
    }


def _prewarm(cache_dir):
    """Populate the shared compile cache once so every supervised spawn
    (including mid-drill scale-ups) is a warm start."""
    if os.listdir(cache_dir):
        return
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=cache_dir)
    child = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "replica", "--port", "0"],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, env=env,
        text=True)
    try:
        line = child.stdout.readline().strip()
        assert line.startswith("PORT "), \
            f"prewarm replica handshake failed: {line!r}"
        _wait_ready(int(line.split()[1]), timeout=300, what="prewarm replica")
    finally:
        child.kill()
        child.wait()
    assert os.listdir(cache_dir), "prewarm never populated the compile cache"


# ------------------------------------------------------------ http helpers
def _wait_ready(port, timeout=90, what="replica"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/readyz", timeout=5) as r:
                if r.status == 200:
                    return
        except (urllib.error.URLError, OSError, http.client.HTTPException):
            pass
        time.sleep(0.1)
    raise AssertionError(f"{what} on :{port} never became ready")


def _metrics_text(port):
    with urllib.request.urlopen(f"http://127.0.0.1:{port}/metrics",
                                timeout=10) as r:
        return r.read().decode()


def _scrape(text, name):
    """Sum a prometheus family across label sets from scraped text."""
    total = 0.0
    for line in text.splitlines():
        m = re.match(rf"{name}(?:{{[^}}]*}})?\s+([0-9.eE+-]+)$", line)
        if m:
            total += float(m.group(1))
    return total


def _scrape_labeled(text, name, **labels):
    """Sum a family restricted to label sets carrying every given pair."""
    want = [f'{k}="{v}"' for k, v in labels.items()]
    total = 0.0
    for line in text.splitlines():
        m = re.match(rf"{name}{{([^}}]*)}}\s+([0-9.eE+-]+)$", line)
        if m and all(w in m.group(1) for w in want):
            total += float(m.group(2))
    return total


# ------------------------------------------------------- streaming client
class StreamStats:
    def __init__(self):
        self.lock = threading.Lock()
        self.done = 0               # streams that reached event: done
        self.retried = 0            # loud mid-stream errors, re-issued
        self.hard = []              # contract breaches


def _stream_once(router_port, prompt, rid, timeout=120):
    """One streaming :generate through the router.  Returns
    ('done'|'error_event'|'http_N'|'eof', tokens_seen) or raises on
    transport error."""
    conn = http.client.HTTPConnection("127.0.0.1", router_port,
                                      timeout=timeout)
    try:
        conn.request("POST", "/v1/models/gen:generate",
                     body=json.dumps({"tokens": prompt,
                                      "max_new_tokens": TOKENS_PER_REQUEST,
                                      "stream": True}),
                     headers={"Content-Type": "application/json",
                              "X-Request-Id": rid})
        resp = conn.getresponse()
        if resp.status != 200:
            return (f"http_{resp.status}", 0)
        tokens, event = 0, None
        for raw in resp:
            line = raw.strip()
            if line.startswith(b"event:"):
                event = line.split(b":", 1)[1].strip()
            elif line.startswith(b"data:"):
                if event == b"token":
                    tokens += 1
                elif event == b"done":
                    return ("done", tokens)
                elif event == b"error":
                    return ("error_event", tokens)
        return ("eof", tokens)      # stream ended with no terminal event
    finally:
        conn.close()


def _client_loop(idx, router_port, stop, stats, active):
    """One diurnal client: issues requests only while the load curve
    says at least ``idx + 1`` clients are on duty, idles otherwise."""
    seq = 0
    while not stop.is_set():
        if idx >= active[0]:
            time.sleep(0.2)         # off-peak: this client is asleep
            continue
        seq += 1
        rid = f"c{idx}-{seq}"
        prompt = [(3 + idx) % 50, (7 + seq) % 50, (11 + idx * seq) % 50, 1]
        for attempt in range(4):
            try:
                outcome, tokens = _stream_once(router_port, prompt, rid)
            except (OSError, http.client.HTTPException) as e:
                with stats.lock:
                    stats.hard.append(f"{rid}: transport error {e!r}")
                return
            if outcome == "done":
                with stats.lock:
                    stats.done += 1
                break
            if outcome == "error_event" and tokens > 0:
                # loud mid-stream death: allowed, client re-issues
                with stats.lock:
                    stats.retried += 1
                continue
            with stats.lock:        # zero-token error / 5xx / silent EOF
                stats.hard.append(
                    f"{rid}: {outcome} after {tokens} tokens "
                    f"(attempt {attempt})")
            return
        else:
            with stats.lock:
                stats.hard.append(f"{rid}: retries exhausted")
            return


def _run_load(router_port, active, body):
    """PEAK_CLIENTS diurnal client threads; ``active[0]`` is the load
    curve's current amplitude; loop until ``body(stats)`` returns."""
    stop, stats = threading.Event(), StreamStats()
    threads = [threading.Thread(target=_client_loop,
                                args=(i, router_port, stop, stats, active),
                                daemon=True)
               for i in range(PEAK_CLIENTS)]
    for t in threads:
        t.start()
    try:
        body(stats)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=120)
    return stats


# --------------------------------------------------------- fault listener
class FaultLog:
    """Passive FAULT-topic tap: the drill runs the supervisor in-process,
    so supervisor/router control-plane events are directly observable."""

    def __init__(self):
        self.lock = threading.Lock()
        self.events = []

    def __call__(self, *args, **kw):
        with self.lock:
            self.events.append(kw)

    def select(self, **want):
        with self.lock:
            return [e for e in self.events
                    if all(e.get(k) == v for k, v in want.items())]


def _wait_for(predicate, timeout, what):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return
        time.sleep(0.2)
    raise AssertionError(f"timed out after {timeout:.0f}s waiting for {what}")


# -------------------------------------------------------- drill: restart
def run_restart(cache_dir, log_dir):
    from incubator_mxnet_tpu import telemetry
    from incubator_mxnet_tpu.serving import Supervisor
    from incubator_mxnet_tpu.serving import supervisor as sup_mod
    _prewarm(cache_dir)
    dump_dir = os.path.join(log_dir, "incidents")
    os.makedirs(dump_dir, exist_ok=True)
    os.environ["MXNET_FLIGHT_DUMP_DIR"] = dump_dir
    command, child_env = _replica_command(cache_dir)
    faults = FaultLog()
    telemetry.FAULT.subscribe(faults, passive=True)
    sup = Supervisor(command, replicas=1, autoscale=False,
                     child_env=child_env, log_dir=log_dir,
                     interval_seconds=0.1, ready_timeout=180,
                     backoff_base=0.2, backoff_max=2.0,
                     max_restarts=2, restart_window_seconds=60)
    try:
        sup.start()
        slot = sup.slots()[0]
        router_port = sup.router.port

        # three SIGKILLs: the first two must restart with backoff, the
        # third blows the flap budget (2 restarts / 60s) → quarantine
        for kill in range(3):
            _wait_for(lambda: slot.state == sup_mod.RUNNING, 120,
                      f"slot RUNNING before kill {kill + 1}")
            os.kill(slot.proc.pid, signal.SIGKILL)
            if kill < 2:
                _wait_for(lambda k=kill: slot.restarts == k + 1, 60,
                          f"restart {kill + 1} after SIGKILL")
        _wait_for(lambda: slot.state == sup_mod.QUARANTINED, 60,
                  "quarantine after the third SIGKILL")

        backoffs = faults.select(site="supervisor.replica", event="backoff")
        assert len(backoffs) >= 2, \
            f"expected >=2 backoff events, saw {len(backoffs)}"
        delays = [e["seconds"] for e in backoffs[:2]]
        assert delays[1] > delays[0], \
            f"backoff not exponential: {delays}"
        assert faults.select(site="supervisor.replica", event="quarantined",
                             replica=slot.id), "no quarantined FAULT event"
        # the corpse must be OUT of the router (removed, not drained)
        reps = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{router_port}/replicas",
            timeout=5).read())["replicas"]
        assert all(r["id"] != slot.id for r in reps), \
            f"quarantined replica still a member: {reps}"
        text = _metrics_text(router_port)
        assert _scrape(text, "mxtpu_supervise_restarts") >= 2, \
            "mxtpu_supervise_restarts did not count the restarts"
        assert _scrape(text, "mxtpu_supervise_quarantines") >= 1, \
            "mxtpu_supervise_quarantines did not count the quarantine"
        assert _scrape(text, "mxtpu_supervise_spawns") >= 3, \
            "mxtpu_supervise_spawns did not count the spawns"
        bundles = os.listdir(dump_dir)
        assert bundles, f"no incident bundle dumped into {dump_dir}"
        print(f"autoscale_smoke restart ok: 2 restarts (backoff "
              f"{delays[0]:.2f}s→{delays[1]:.2f}s), quarantined on the 3rd "
              f"kill, incident bundle {sorted(bundles)[-1]}")
    finally:
        telemetry.FAULT.unsubscribe(faults)
        sup.stop()


# -------------------------------------------------------- drill: diurnal
def run_diurnal(cache_dir, log_dir):
    from incubator_mxnet_tpu import telemetry
    from incubator_mxnet_tpu.serving import AutoscalePolicy, Supervisor
    from incubator_mxnet_tpu.serving import supervisor as sup_mod
    _prewarm(cache_dir)
    # the supervisor's lazily-created router reads these at construction:
    # queued peaks must wait out backpressure, not surface as 503s — the
    # retry DEADLINE must be the binding constraint, so the attempt
    # budget is parked out of its way (the default 2 retries burn out in
    # ~0.2s of 429s, long before a scale-up can land)
    os.environ["MXNET_ROUTER_RETRY_DEADLINE_SECONDS"] = "90"
    os.environ["MXNET_ROUTER_RETRIES"] = "500"
    os.environ["MXNET_ROUTER_HEALTH_INTERVAL_SECONDS"] = "0.25"
    os.environ["MXNET_ROUTER_FEDERATE_SECONDS"] = "0.5"
    command, child_env = _replica_command(cache_dir)
    faults = FaultLog()
    telemetry.FAULT.subscribe(faults, passive=True)
    # queue depth drives this drill (2 slots/replica vs 16 peak clients);
    # chaos deliberately torches the error budget, so the burn thresholds
    # are parked out of the way — burn precedence is test_supervisor.py's
    # table, not this drill's
    policy = AutoscalePolicy(min_replicas=1, max_replicas=MAX_FLEET,
                             burn_up=1e9, burn_down=1e9,
                             queue_up=3.0, queue_down=1.0,
                             cooldown_seconds=6.0)
    sup = Supervisor(command, replicas=1, policy=policy,
                     child_env=child_env, log_dir=log_dir,
                     interval_seconds=0.15, autoscale_interval_seconds=1.0,
                     ready_timeout=180, backoff_base=0.2, backoff_max=2.0,
                     max_restarts=4, restart_window_seconds=20)
    chaos_stop = threading.Event()
    chaos_kills = []

    def chaos():
        """SIGKILL a random RUNNING replica, twice, spaced well inside
        the flap budget (4 restarts / 20s) so chaos drills restart, not
        quarantine — quarantine is the restart drill's assertion."""
        rng = random.Random(11)
        while not chaos_stop.is_set() and len(chaos_kills) < 2:
            if chaos_stop.wait(10.0):
                return
            victims = [s for s in sup.slots()
                       if s.state == sup_mod.RUNNING and s.alive()]
            if len(victims) < 2:
                continue            # never behead a one-replica fleet
            slot = rng.choice(victims)
            os.kill(slot.proc.pid, signal.SIGKILL)
            chaos_kills.append(slot.id)

    try:
        sup.start()
        router_port = sup.router.port
        active = [PEAK_CLIENTS]     # the load curve's amplitude
        chaos_thread = threading.Thread(target=chaos, daemon=True)

        def body(stats):
            chaos_thread.start()    # chaos rides the whole peak
            _wait_for(lambda: sup.active_count() >= MAX_FLEET, 420,
                      f"peak load to scale the fleet 1→{MAX_FLEET}")
            _wait_for(lambda: sup.alive_count() >= MAX_FLEET, 180,
                      "the full fleet to come ready")
            _wait_for(lambda: len(chaos_kills) >= 2, 60,
                      "the chaos thread's two SIGKILLs")
            chaos_stop.set()
            chaos_thread.join(timeout=30)
            time.sleep(3.0)         # let post-chaos restarts settle
            active[0] = TROUGH_CLIENTS      # dusk: the curve drops
            _wait_for(lambda: sup.active_count() <= 1, 420,
                      f"trough load to shrink the fleet {MAX_FLEET}→1")

        stats = _run_load(router_port, active, body)
        assert not stats.hard, \
            "diurnal contract breached:\n  " + "\n  ".join(stats.hard[:10])
        assert stats.done >= PEAK_CLIENTS, \
            f"suspiciously few completions ({stats.done})"

        # active_count() drops the moment a scale-down marks its victim
        # STOPPED, but the ``down`` event only lands after the router
        # finishes draining the member — give in-flight drains a moment
        # to settle before reading the event counters
        settle = time.monotonic() + 30
        while time.monotonic() < settle and _scrape_labeled(
                _metrics_text(router_port), "mxtpu_autoscale_events",
                action="down") < MAX_FLEET - 1:
            time.sleep(0.5)

        text = _metrics_text(router_port)
        ups = _scrape_labeled(text, "mxtpu_autoscale_events", action="up")
        downs = _scrape_labeled(text, "mxtpu_autoscale_events",
                                action="down")
        assert ups >= MAX_FLEET - 1, f"expected >=3 scale-ups, saw {ups}"
        assert downs >= MAX_FLEET - 1, \
            f"expected >=3 scale-downs, saw {downs}"
        restarts = _scrape(text, "mxtpu_supervise_restarts")
        assert restarts >= len(chaos_kills) > 0, \
            f"chaos killed {len(chaos_kills)} replicas but only " \
            f"{restarts} restarts were counted"
        for family in ("mxtpu_supervise_spawns", "mxtpu_supervise_restarts",
                       "mxtpu_supervise_quarantines",
                       "mxtpu_supervise_replicas",
                       "mxtpu_autoscale_events", "mxtpu_autoscale_decisions",
                       "mxtpu_autoscale_target_replicas",
                       "mxtpu_autoscale_burn_rate",
                       "mxtpu_autoscale_queue_depth",
                       "mxtpu_autoscale_kv_utilization"):
            assert re.search(rf"^{family}(?:{{|\s)", text, re.M), \
                f"{family} missing from the router's /metrics"

        # zero-downtime by construction: every executed scale-down must
        # have routed through the router's drain for that replica
        drained = {e.get("replica") for e in faults.select(
            site="router.admin", event="drain", kind="begin")}
        down_events = faults.select(site="supervisor.autoscale",
                                    event="scale", kind="down")
        assert down_events, "no supervisor.autoscale down FAULT events"
        undrained = [e["replica"] for e in down_events
                     if e.get("replica") not in drained]
        assert not undrained, \
            f"scale-down skipped the drain for {undrained}"
        print(f"autoscale_smoke diurnal ok: 1→{MAX_FLEET}→"
              f"{sup.active_count()} fleet cycle, {int(ups)} ups / "
              f"{int(downs)} downs (all drained), chaos SIGKILLed "
              f"{len(chaos_kills)} replicas ({int(restarts)} restarts), "
              f"{stats.done} streams completed, {stats.retried} loud "
              f"mid-stream retries, 0 failed requests")
    finally:
        chaos_stop.set()
        telemetry.FAULT.unsubscribe(faults)
        sup.stop()


DRILLS = {"restart": run_restart, "diurnal": run_diurnal}


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("drill", choices=sorted(DRILLS) + ["all", "replica"])
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--cache-dir", default="/tmp/mxtpu_autoscale_smoke_cc")
    ap.add_argument("--log-dir", default="/tmp/mxtpu_autoscale_smoke_logs")
    args = ap.parse_args()
    if args.drill == "replica":
        run_replica(args.port, slots=args.slots)
        return
    os.makedirs(args.cache_dir, exist_ok=True)
    os.makedirs(args.log_dir, exist_ok=True)
    drills = ["restart", "diurnal"] if args.drill == "all" else [args.drill]
    for name in drills:
        DRILLS[name](args.cache_dir, args.log_dir)


if __name__ == "__main__":
    main()
