#!/usr/bin/env python
"""Fleet-routing smoke (ci/run_tests.sh router_smoke).

Four drills over the ``mxtpu-router`` front tier (docs/serving.md
"Serving a fleet"), each against real ``replica`` child processes
serving a tiny GPT through the full ``:generate`` SSE path:

* ``coldstart`` — ``JAX_COMPILATION_CACHE_DIR`` drill: first replica
  pays the jit compiles into a fresh cache dir; a second process with
  the populated cache must reach its first ``:generate`` 200 at least
  1.5x faster (typically several times).  Side effect: warms the cache
  the remaining drills' fleets spawn from.
* ``failover`` — 3 replicas under 16 looping streaming clients when
  one replica is SIGKILLed.  Contract: ZERO failed client requests —
  no transport error, no 5xx, and no terminal ``error`` event before
  the first token (zero-token replica death MUST fail over
  transparently).  A death after tokens streamed surfaces as a loud
  terminal ``error`` SSE event carrying the request id (never a silent
  hang); the client re-issues and that retry must succeed.
* ``drain`` — rolling update: each replica in turn is drained through
  ``POST /admin/drain`` on the router, SIGTERMed, restarted on the
  same port and undrained — all under the same 16-client load, with
  zero downtime: every request succeeds, not one ``error`` event or
  5xx reaches a client.
* ``affinity`` — 16 shared-prefix prompt families replayed twice,
  once through an affinity router and once through a ``--no-affinity``
  (least-loaded) router; the fleet-wide ``mxtpu_prefix_cache_hits``
  delta under affinity must beat random placement (the point of
  rendezvous routing: one replica owns a prefix, so its paged-KV
  prefix cache actually gets hit).

``all`` runs them in order (coldstart first so the others spawn warm).
"""
import argparse
import http.client
import json
import os
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

N_CLIENTS = 16
BLOCK = 16                      # MXNET_KV_BLOCK_SIZE default


# ------------------------------------------------------------ replica child
def run_replica(port):
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
    eng = GenerationEngine(net, name="gen", max_slots=8, max_len=256)
    srv = ModelServer(port=port, host="127.0.0.1")
    srv.add_model("gen", eng, warmup=True)
    srv.start()
    print(f"PORT {srv.port}", flush=True)
    sys.exit(lifecycle.run_until_shutdown(srv))


# ------------------------------------------------------------ fleet helpers
def _spawn(cache_dir, port=0):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=cache_dir,
               MXNET_DRAIN_SECONDS="5")
    child = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "replica",
         "--port", str(port)],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, env=env,
        text=True)
    line = child.stdout.readline().strip()
    assert line.startswith("PORT "), \
        f"replica child handshake failed: {line!r}"
    return child, int(line.split()[1])


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


def _fleet(cache_dir, n=3):
    kids = [_spawn(cache_dir) for _ in range(n)]
    for _, port in kids:
        _wait_ready(port)
    return kids


def _kill_fleet(kids):
    for child, _ in kids:
        if child.poll() is None:
            child.kill()
    for child, _ in kids:
        child.wait()


def _generate_json(port, tokens, n=2, timeout=60):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/models/gen:generate",
        data=json.dumps({"tokens": tokens,
                         "max_new_tokens": n}).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, json.loads(r.read())


def _scrape_counter(port, name):
    """Sum a prometheus counter across label sets on one replica."""
    with urllib.request.urlopen(f"http://127.0.0.1:{port}/metrics",
                                timeout=5) as r:
        text = r.read().decode()
    total = 0.0
    for line in text.splitlines():
        m = re.match(rf"{name}(?:{{[^}}]*}})?\s+([0-9.eE+-]+)$", line)
        if m:
            total += float(m.group(1))
    return total


def _fleet_hits(kids):
    return sum(_scrape_counter(port, "mxtpu_prefix_cache_hits")
               for _, port in kids)


# ------------------------------------------------------- streaming client
class StreamStats:
    def __init__(self):
        self.lock = threading.Lock()
        self.done = 0               # streams that reached event: done
        self.retried = 0            # loud mid-stream errors, re-issued
        self.hard = []              # contract breaches


def _stream_once(router_port, prompt, rid, timeout=60):
    """One streaming :generate through the router.  Returns
    ('done'|'error_event', tokens_seen) or raises on transport error."""
    conn = http.client.HTTPConnection("127.0.0.1", router_port,
                                      timeout=timeout)
    try:
        conn.request("POST", "/v1/models/gen:generate",
                     body=json.dumps({"tokens": prompt,
                                      "max_new_tokens": 24,
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


def _client_loop(idx, router_port, stop, stats, prompts):
    seq = 0
    while not stop.is_set():
        seq += 1
        rid = f"c{idx}-{seq}"
        prompt = prompts(idx, seq)
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


def _run_load(router_port, prompts, body):
    """16 client threads loop until ``body(stats)`` returns."""
    stop, stats = threading.Event(), StreamStats()
    threads = [threading.Thread(target=_client_loop,
                                args=(i, router_port, stop, stats, prompts),
                                daemon=True)
               for i in range(N_CLIENTS)]
    for t in threads:
        t.start()
    try:
        body(stats)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=90)
    return stats


def _varied_prompts(idx, seq):
    return [(3 + idx) % 50, (7 + seq) % 50, (11 + idx * seq) % 50, 1]


# ------------------------------------------------------ drill: coldstart
def run_coldstart(cache_dir):
    assert not os.listdir(cache_dir), \
        f"coldstart wants a fresh cache dir, {cache_dir} is populated"

    def first_200(tag):
        t0 = time.monotonic()
        child, port = _spawn(cache_dir)
        try:
            _wait_ready(port, what=f"{tag} replica")
            status, body = _generate_json(port, [3, 7, 11], n=2)
            assert status == 200 and body.get("tokens"), \
                f"{tag}: bad :generate reply {status} {body}"
            return time.monotonic() - t0
        finally:
            child.kill()
            child.wait()

    cold = first_200("cold")
    assert os.listdir(cache_dir), \
        "JAX_COMPILATION_CACHE_DIR never populated by the cold replica"
    warm = first_200("warm")
    ratio = cold / max(warm, 1e-9)
    assert warm * 1.5 <= cold, \
        (f"coldstart: populated compile cache did not speed warmup — "
         f"cold {cold:.2f}s vs warm {warm:.2f}s ({ratio:.1f}x)")
    print(f"router_smoke coldstart ok: cold {cold:.2f}s, warm {warm:.2f}s "
          f"({ratio:.1f}x faster with populated cache)")


# ------------------------------------------------------- drill: failover
def run_failover(cache_dir):
    from incubator_mxnet_tpu.serving import Router
    kids = _fleet(cache_dir, 3)
    router = Router([f"127.0.0.1:{p}" for _, p in kids], port=0,
                    host="127.0.0.1", health_interval=0.1,
                    retry_deadline=20.0)
    router.start()
    victim_child, victim_port = kids[0]
    try:
        def body(stats):
            time.sleep(1.5)     # let the fleet take load first
            victim_child.send_signal(signal.SIGKILL)
            time.sleep(4.0)     # keep the load on through the ejection

        stats = _run_load(router.port, _varied_prompts, body)
        assert not stats.hard, \
            "failover contract breached:\n  " + "\n  ".join(stats.hard[:10])
        assert stats.done >= N_CLIENTS, \
            f"failover: suspiciously few completions ({stats.done})"
        snap = {r["id"]: r["state"] for r in json.loads(
            urllib.request.urlopen(
                f"http://127.0.0.1:{router.port}/replicas",
                timeout=5).read())["replicas"]}
        assert snap[f"127.0.0.1:{victim_port}"] in ("EJECTED", "DOWN"), \
            f"killed replica not ejected: {snap}"
        print(f"router_smoke failover ok: {stats.done} streams completed, "
              f"{stats.retried} loud mid-stream retries, 0 failed "
              f"requests across SIGKILL of {victim_port} (now "
              f"{snap[f'127.0.0.1:{victim_port}']})")
    finally:
        router.stop()
        _kill_fleet(kids)


# ---------------------------------------------------------- drill: drain
def run_drain(cache_dir):
    from incubator_mxnet_tpu.serving import Router
    kids = _fleet(cache_dir, 3)
    router = Router([f"127.0.0.1:{p}" for _, p in kids], port=0,
                    host="127.0.0.1", health_interval=0.1,
                    retry_deadline=20.0)
    router.start()

    def admin(path, rid, **extra):
        req = urllib.request.Request(
            f"http://127.0.0.1:{router.port}{path}",
            data=json.dumps({"replica": rid, **extra}).encode(),
            headers={"Content-Type": "application/json"}, method="POST")
        with urllib.request.urlopen(req, timeout=30) as r:
            return json.loads(r.read())

    try:
        def body(stats):
            time.sleep(0.5)
            for i, (child, port) in enumerate(list(kids)):
                rid = f"127.0.0.1:{port}"
                out = admin("/admin/drain", rid, wait_seconds=30)
                assert out.get("drained"), f"drain of {rid} timed out: {out}"
                child.send_signal(signal.SIGTERM)
                assert child.wait(timeout=30) == 0, \
                    f"replica {rid} exited non-zero on SIGTERM"
                kids[i] = _spawn(cache_dir, port=port)  # rolling update
                _wait_ready(port, what=f"restarted replica {rid}")
                admin("/admin/undrain", rid)
                deadline = time.monotonic() + 15
                while time.monotonic() < deadline:
                    snap = {r["id"]: r["state"] for r in json.loads(
                        urllib.request.urlopen(
                            f"http://127.0.0.1:{router.port}/replicas",
                            timeout=5).read())["replicas"]}
                    if snap[rid] == "READY":
                        break
                    time.sleep(0.1)
                assert snap[rid] == "READY", \
                    f"{rid} never rejoined after undrain: {snap}"

        stats = _run_load(router.port, _varied_prompts, body)
        assert not stats.hard, \
            "drain downtime detected:\n  " + "\n  ".join(stats.hard[:10])
        assert stats.retried == 0, \
            f"drain: {stats.retried} mid-stream errors — drain must let " \
            f"in-flight streams finish"
        assert stats.done >= N_CLIENTS
        print(f"router_smoke drain ok: rolled all 3 replicas under load, "
              f"{stats.done} streams completed, zero downtime")
    finally:
        router.stop()
        _kill_fleet(kids)


# ------------------------------------------------------- drill: affinity
def run_affinity(cache_dir):
    from incubator_mxnet_tpu.serving import Router
    kids = _fleet(cache_dir, 3)

    def workload(base):
        """16 prompt families: a family shares a 2-block (32-token)
        prefix; 3 requests per family with distinct suffixes."""
        out = []
        for fam in range(16):
            prefix = [(base + fam) % 50] * (2 * BLOCK)
            for s in range(3):
                out.append(prefix + [(base + fam + s) % 50, 2])
        return out

    def replay(prompts, affinity):
        router = Router([f"127.0.0.1:{p}" for _, p in kids], port=0,
                        host="127.0.0.1", health_interval=0.1,
                        affinity=affinity)
        router.start()
        try:
            before = _fleet_hits(kids)
            for i, prompt in enumerate(prompts):
                outcome, _ = _stream_once(router.port, prompt, f"aff-{i}")
                assert outcome == "done", f"affinity workload: {outcome}"
            return _fleet_hits(kids) - before
        finally:
            router.stop()

    try:
        # distinct token bases so phase B's prefixes are cold even
        # though phase A already populated the replica caches
        random_hits = replay(workload(1), affinity=False)
        affine_hits = replay(workload(20), affinity=True)
        assert affine_hits > random_hits, \
            (f"prefix-affine routing did not raise fleet prefix-cache "
             f"hits: affine {affine_hits} vs random {random_hits}")
        print(f"router_smoke affinity ok: mxtpu_prefix_cache_hits "
              f"+{affine_hits:.0f} blocks with affinity vs "
              f"+{random_hits:.0f} random")
    finally:
        _kill_fleet(kids)


DRILLS = {"coldstart": run_coldstart, "failover": run_failover,
          "drain": run_drain, "affinity": run_affinity}


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("drill", choices=sorted(DRILLS) + ["all", "replica"])
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("--cache-dir", default="/tmp/mxtpu_router_smoke_cc")
    args = ap.parse_args()
    if args.drill == "replica":
        run_replica(args.port)
        return
    os.makedirs(args.cache_dir, exist_ok=True)
    drills = ["coldstart", "failover", "drain", "affinity"] \
        if args.drill == "all" else [args.drill]
    for name in drills:
        DRILLS[name](args.cache_dir)


if __name__ == "__main__":
    main()
