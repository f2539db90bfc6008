#!/usr/bin/env python
"""Fleet observability smoke (ci/run_tests.sh fleet_obs_smoke).

One drill over the ``mxtpu-router`` observability plane
(docs/observability.md "Observing a fleet"): 3 telemetry-enabled
replica child processes behind a router, 16 looping streaming clients,
and a ``MXNET_FAULT_PLAN=serving.infer:hang`` wedge on one replica —
the classic "one box goes quiet" incident.  Asserts the three tentpole
contracts end to end:

* **Stitched traces** — some request must have failed over off the
  hung replica; the router's ``GET /trace?request_id=`` answer for it
  shows BOTH legs (the failed hop and the ok hop), with the surviving
  replica's ``serve.request`` span grafted under the hop whose span id
  it names in ``remote_parent``.
* **Metrics federation** — the fleet sums on the router's federated
  ``GET /metrics`` equal the arithmetic sum of the replicas' own
  counters (scraped directly from each ``/metrics.json``) within one
  federation interval.
* **Incident bundles** — the hang storm ejects the wedged replica and
  writes EXACTLY ONE incident bundle directory, whose manifest names
  request ids that actually failed on that replica.
"""
import argparse
import http.client
import json
import os
import shutil
import sys
import threading
import time
import urllib.error
import urllib.request

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

N_CLIENTS = 16


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


def _spawn(cache_dir, fault_plan=None):
    import subprocess
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=cache_dir,
               MXNET_TELEMETRY="1",         # spans + /trace on replicas
               MXNET_DRAIN_SECONDS="5")
    if fault_plan:
        env["MXNET_FAULT_PLAN"] = fault_plan
    child = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "replica"],
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
        except (urllib.error.URLError, OSError,
                http.client.HTTPException):
            pass
        time.sleep(0.1)
    raise AssertionError(f"{what} on :{port} never became ready")


def _get_json(port, path, timeout=10):
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                timeout=timeout) as r:
        return r.status, json.loads(r.read())


def _counter_total(state, name):
    m = (state.get("counters") or {}).get(name) or {}
    return sum(float(v) for v in (m.get("values") or {}).values())


# ------------------------------------------------------- streaming client
def _stream_once(router_port, prompt, rid, timeout=60):
    conn = http.client.HTTPConnection("127.0.0.1", router_port,
                                      timeout=timeout)
    try:
        conn.request("POST", "/v1/models/gen:generate",
                     body=json.dumps({"tokens": prompt,
                                      "max_new_tokens": 8,
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
        return ("eof", tokens)
    finally:
        conn.close()


def _client_loop(idx, router_port, stop, results):
    seq = 0
    while not stop.is_set():
        seq += 1
        rid = f"obs-c{idx}-{seq}"
        prompt = [(3 + idx) % 50, (7 + seq) % 50, 1]
        try:
            outcome, tokens = _stream_once(router_port, prompt, rid)
        except (OSError, http.client.HTTPException) as e:
            outcome, tokens = f"transport:{e!r}", 0
        with results["lock"]:
            results["by_rid"][rid] = outcome
            if outcome == "done":
                results["done"] += 1
            elif not (outcome == "error_event" and tokens > 0):
                results["hard"].append(f"{rid}: {outcome}")


# ----------------------------------------------------------------- drill
def run_drill(cache_dir, incident_dir):
    from incubator_mxnet_tpu.serving import Router

    kids = [_spawn(cache_dir),
            _spawn(cache_dir),
            # the wedge: every batched dispatch on this replica stalls
            # for an hour — requests routed here time out and fail over
            _spawn(cache_dir, fault_plan="serving.infer:hang")]
    ports = [p for _, p in kids]
    hung_id = f"127.0.0.1:{ports[2]}"
    for _, port in kids:
        _wait_ready(port)

    router = Router([f"127.0.0.1:{p}" for p in ports], port=0,
                    host="127.0.0.1", health_interval=0.1,
                    upstream_timeout=2.0, retry_deadline=20.0,
                    eject_threshold=3, eject_cooldown_seconds=60.0,
                    federate_seconds=0.5, incident_dir=incident_dir)
    router.start()
    results = {"lock": threading.Lock(), "by_rid": {}, "done": 0,
               "hard": []}
    stop = threading.Event()
    threads = [threading.Thread(target=_client_loop,
                                args=(i, router.port, stop, results),
                                daemon=True)
               for i in range(N_CLIENTS)]
    try:
        for t in threads:
            t.start()
        # run load until the hang storm has ejected the wedged replica
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            snap = {r.id: r.snapshot()["state"] for r in router.replicas}
            if snap[hung_id] == "EJECTED":
                break
            time.sleep(0.2)
        assert snap[hung_id] == "EJECTED", \
            f"hung replica never ejected: {snap}"
        time.sleep(0.5)             # let in-flight failovers finish

        # -- contract 1: stitched both-leg trace --------------------------
        # checked NOW, newest failover first: the replica tracer keeps a
        # bounded ring of finished roots, so the spans behind the legs
        # that triggered ejection age out if we keep streaming first
        failover_rid = None
        for rec in reversed(router._hops.recent(limit=512)):
            hops = rec["hops"]
            if len(hops) >= 2 and hops[0]["replica"] == hung_id \
                    and hops[0]["outcome"] not in (None, "ok") \
                    and hops[-1]["outcome"] == "ok":
                failover_rid = rec["request_id"]
                break
        assert failover_rid, \
            "no request observed failing over off the hung replica"
        status, stitched = _get_json(
            router.port, f"/trace?request_id={failover_rid}")
        assert status == 200 and stitched["stitched"]
        legs = stitched["hops"]
        assert legs[0]["replica"] == hung_id and \
            legs[0]["outcome"] not in (None, "ok")
        ok_leg = legs[-1]
        assert ok_leg["outcome"] == "ok" and ok_leg["replica"] != hung_id
        kids_spans = ok_leg.get("children") or []
        assert any(s.get("attrs", {}).get("remote_parent")
                   == ok_leg["id"] for s in kids_spans), \
            (f"stitched trace {failover_rid}: surviving leg carries no "
             f"replica span naming hop {ok_leg['id']}: {kids_spans}")

        stop.set()
        for t in threads:
            t.join(timeout=90)
        assert not results["hard"], \
            ("fleet_obs_smoke: client-visible failures under the hang "
             "drill:\n  " + "\n  ".join(results["hard"][:10]))
        assert results["done"] >= N_CLIENTS, \
            f"suspiciously few completions ({results['done']})"

        # -- contract 2: fleet counters = sum of replica counters ---------
        # (the wedge hangs the batcher worker, not the HTTP plane — the
        # ejected replica still answers /metrics.json, so all three are
        # scrapeable and no serve traffic moves the counters any more)
        router._federate_maybe(force=True)
        fleet = router.fleet_metrics_state()
        name = "mxtpu_serve_requests"
        direct = 0.0
        for port in ports:
            _, state = _get_json(port, "/metrics.json")
            direct += _counter_total(state, name)
        fleet_total = sum(
            v for labels, v in
            fleet["counters"][name]["values"].items()
            if not labels.startswith("replica="))
        assert abs(fleet_total - direct) < 1e-6, \
            (f"federated {name} fleet sum {fleet_total} != arithmetic "
             f"sum of replica counters {direct}")

        # -- contract 3: exactly one incident bundle ----------------------
        deadline = time.monotonic() + 10
        bundles = []
        while time.monotonic() < deadline:
            if os.path.isdir(incident_dir):
                bundles = sorted(b for b in os.listdir(incident_dir)
                                 if not b.startswith("."))
            if bundles:
                break
            time.sleep(0.1)
        time.sleep(1.0)             # window for any spurious extras
        bundles = sorted(b for b in os.listdir(incident_dir)
                         if not b.startswith("."))
        assert len(bundles) == 1, \
            f"expected exactly one incident bundle, got {bundles}"
        bdir = os.path.join(incident_dir, bundles[0])
        manifest = json.load(open(os.path.join(bdir, "incident.json")))
        assert manifest["reason"] == "ejected"
        assert manifest["replica"] == hung_id
        assert manifest["request_ids"], "incident names no request ids"
        # the live hop log is LRU-bounded and long since moved on —
        # the bundle's own stitched snapshot is the evidence of record
        stitched_at_incident = json.load(
            open(os.path.join(bdir, "stitched_traces.json")))
        for rid in manifest["request_ids"]:
            t = stitched_at_incident.get(rid)
            assert t and any(h["replica"] == hung_id
                             and h["outcome"] != "ok"
                             for h in t["hops"]), \
                (f"incident request id {rid} shows no failed hop on "
                 f"{hung_id}: {t}")
        for fname in manifest["files"]:
            assert os.path.exists(os.path.join(bdir, fname)), fname

        print(f"fleet_obs_smoke ok: {results['done']} streams completed "
              f"through the hang drill; stitched both-leg trace for "
              f"{failover_rid}; federated {name} sum {fleet_total:.0f} "
              f"matches replicas; one incident bundle "
              f"({bundles[0]}) naming "
              f"{len(manifest['request_ids'])} request ids")
    finally:
        stop.set()
        router.stop()
        for child, _ in kids:
            if child.poll() is None:
                child.kill()
        for child, _ in kids:
            child.wait()


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("drill", nargs="?", default="all",
                    choices=["all", "replica"])
    ap.add_argument("--cache-dir", default="/tmp/mxtpu_fleet_obs_cc")
    ap.add_argument("--incident-dir",
                    default="/tmp/mxtpu_fleet_obs_incidents")
    args = ap.parse_args()
    if args.drill == "replica":
        run_replica(0)
        return
    os.makedirs(args.cache_dir, exist_ok=True)
    shutil.rmtree(args.incident_dir, ignore_errors=True)
    run_drill(args.cache_dir, args.incident_dir)


if __name__ == "__main__":
    main()
