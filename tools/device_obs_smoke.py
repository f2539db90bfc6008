#!/usr/bin/env python
"""Device-plane observability smoke (ci/run_tests.sh device_obs_smoke).

One drill over the device-observability plane (docs/observability.md
"Device plane"): 3 telemetry-enabled replica child processes behind a
router — two plain, one serving with an attached draft model
(speculative decoding) — under 16 looping streaming clients.  Asserts
the tentpole contracts end to end, over HTTP:

* **Dispatch economy** — the per-step replica
  (``MXNET_DECODE_SCAN_STEPS=0``) reads exactly 1.0 on
  ``mxtpu_dispatches_per_token`` (one decode dispatch advances every
  live slot by one token); the burst replica (default scan_steps)
  reads < 1.0 (scanned bursts amortize dispatches over up to k
  tokens); the spec replica's reads < 1.0 (accepted draft bursts
  amortize target dispatches).
* **Closed program set at runtime** — the router's ``GET /programs``
  fan-out shows every replica's engine with ``compiled_programs ==
  expected_programs`` after warmup, and dispatch-ledger rows for the
  programs that actually ran.
* **Federated HBM attribution** — the ``GET /memory`` fan-out reports
  a positive ``kv:gen`` owner on every replica, and the federated
  router ``GET /metrics`` carries the ``mxtpu_device_owned_bytes``
  series in its fleet sums.
* **Profiler fan-out** — one ``POST /debug/profile`` through the
  router triggers a capture on EVERY replica and answers with one
  on-disk artifact directory per replica.
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
COMPLETIONS = 48


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
    if os.environ.get("MXNET_SMOKE_SPEC") == "1":
        # the draft IS the target: accept rate 1, so every verify
        # dispatch lands spec_k+1 tokens per slot and the replica's
        # dispatches-per-token sits far below 1.0
        drf = GenerationEngine(net, name="drf", max_slots=8, max_len=256)
        eng.attach_draft(drf, spec_k=3)
    srv = ModelServer(port=port, host="127.0.0.1")
    srv.add_model("gen", eng, warmup=True)
    srv.start()
    print(f"PORT {srv.port}", flush=True)
    sys.exit(lifecycle.run_until_shutdown(srv))


def _spawn(cache_dir, profile_dir, spec=False, scan0=False):
    import subprocess
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=cache_dir,
               MXNET_PROFILE_DIR=profile_dir,
               MXNET_TELEMETRY="1",
               MXNET_DRAIN_SECONDS="5")
    if spec:
        env["MXNET_SMOKE_SPEC"] = "1"
    if scan0:
        env["MXNET_DECODE_SCAN_STEPS"] = "0"
    child = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "replica"],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, env=env,
        text=True)
    line = child.stdout.readline().strip()
    assert line.startswith("PORT "), \
        f"replica child handshake failed: {line!r}"
    return child, int(line.split()[1])


def _wait_ready(port, timeout=120, what="replica"):
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


def _gauge_value(state, name, labels):
    m = (state.get("gauges") or {}).get(name) or {}
    return (m.get("values") or {}).get(labels)


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
        rid = f"dev-c{idx}-{seq}"
        prompt = [(3 + idx) % 50, (7 + seq) % 50, (idx * seq) % 50, 1]
        try:
            outcome, tokens = _stream_once(router_port, prompt, rid)
        except (OSError, http.client.HTTPException) as e:
            outcome, tokens = f"transport:{e!r}", 0
        with results["lock"]:
            if outcome == "done":
                results["done"] += 1
            else:
                results["hard"].append(f"{rid}: {outcome}")


# ----------------------------------------------------------------- drill
def run_drill(cache_dir, profile_dir):
    from incubator_mxnet_tpu.serving import Router

    kids = [_spawn(cache_dir, profile_dir, scan0=True),
            _spawn(cache_dir, profile_dir),
            _spawn(cache_dir, profile_dir, spec=True)]
    ports = [p for _, p in kids]
    spec_id = f"127.0.0.1:{ports[2]}"
    step_id = f"127.0.0.1:{ports[0]}"   # per-step: scan_steps=0
    burst_id = f"127.0.0.1:{ports[1]}"  # scanned bursts, default k
    for _, port in kids:
        _wait_ready(port)

    router = Router([f"127.0.0.1:{p}" for p in ports], port=0,
                    host="127.0.0.1", health_interval=0.1,
                    upstream_timeout=30.0, retry_deadline=30.0,
                    federate_seconds=0.5)
    router.start()
    results = {"lock": threading.Lock(), "done": 0, "hard": []}
    stop = threading.Event()
    threads = [threading.Thread(target=_client_loop,
                                args=(i, router.port, stop, results),
                                daemon=True)
               for i in range(N_CLIENTS)]
    try:
        for t in threads:
            t.start()
        # run load until the quota is met AND every replica has served
        # (rendezvous affinity spreads the varied prompts; the
        # per-replica gauge only exists once a replica decoded)
        deadline = time.monotonic() + 120
        dpt = {}
        while time.monotonic() < deadline:
            with results["lock"]:
                done = results["done"]
            for port in ports:
                try:
                    _, state = _get_json(port, "/metrics.json")
                except (urllib.error.URLError, OSError):
                    continue
                v = _gauge_value(state, "mxtpu_dispatches_per_token",
                                 "model=gen")
                if v is not None:
                    dpt[f"127.0.0.1:{port}"] = v
            if done >= COMPLETIONS and len(dpt) == len(ports):
                break
            time.sleep(0.2)
        stop.set()
        for t in threads:
            t.join(timeout=90)
        assert not results["hard"], \
            ("device_obs_smoke: client-visible failures:\n  "
             + "\n  ".join(results["hard"][:10]))
        assert results["done"] >= COMPLETIONS, \
            f"suspiciously few completions ({results['done']})"
        assert len(dpt) == len(ports), \
            f"some replica never decoded: {dpt}"

        # -- contract 1: dispatch economy, per replica --------------------
        assert abs(dpt[step_id] - 1.0) < 1e-6, \
            (f"per-step replica {step_id}: dispatches-per-token "
             f"{dpt[step_id]} != 1.0")
        assert dpt[burst_id] < 0.999, \
            (f"burst replica {burst_id}: dispatches-per-token "
             f"{dpt[burst_id]} not < 1.0 — bursts never engaged")
        assert dpt[spec_id] < 0.999, \
            (f"spec replica {spec_id}: dispatches-per-token "
             f"{dpt[spec_id]} not < 1.0 — the draft earned nothing")

        # -- contract 2: closed program set at runtime --------------------
        _, progs = _get_json(router.port, "/programs")
        assert set(progs["replicas"]) == set(dpt)
        for rid, rep in progs["replicas"].items():
            inv = rep["engines"]["gen"]
            assert inv["compiled_programs"] == inv["expected_programs"], \
                (f"{rid}: compiled {inv['compiled_programs']} != "
                 f"expected {inv['expected_programs']}")
            ran = [s for s, row in inv["programs"].items()
                   if row["dispatches"] > 0]
            assert any(s.endswith((":decode", ":decode_burst",
                                   ":verify"))
                       for s in ran), f"{rid}: no decode ran: {ran}"

        # -- contract 3: federated HBM attribution ------------------------
        _, mem = _get_json(router.port, "/memory")   # refreshes gauges
        for rid, rep in mem["replicas"].items():
            assert rep["owners"].get("kv:gen", 0) > 0, \
                f"{rid}: no kv:gen owner bytes: {rep['owners']}"
        router._federate_maybe(force=True)
        fleet = router.fleet_metrics_state()
        owned = fleet["gauges"].get("mxtpu_device_owned_bytes") or {}
        kv_sum = sum(v for labels, v in
                     (owned.get("values") or {}).items()
                     if "owner=kv:gen" in labels
                     and not labels.startswith("replica="))
        assert kv_sum > 0, \
            f"no federated kv:gen bytes on the router: {owned}"

        # -- contract 4: profiler capture fan-out -------------------------
        req = urllib.request.Request(
            f"http://127.0.0.1:{router.port}/debug/profile?seconds=0.2",
            data=b"{}", method="POST",
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=60) as r:
            prof = json.loads(r.read())
        assert set(prof["replicas"]) == set(dpt)
        artifacts = []
        for rid, rep in prof["replicas"].items():
            assert "profile" in rep, f"{rid}: capture failed: {rep}"
            assert os.path.isdir(rep["profile"]), rep["profile"]
            artifacts.append(rep["profile"])
        assert len(set(artifacts)) == len(artifacts), \
            f"replicas shared a capture artifact: {artifacts}"

        print(f"device_obs_smoke ok: {results['done']} streams; "
              f"dispatches-per-token per-step={dpt[step_id]:.4f} "
              f"burst={dpt[burst_id]:.4f} "
              f"spec={dpt[spec_id]:.4f}; closed program set verified on "
              f"{len(progs['replicas'])} replicas; federated kv:gen "
              f"bytes {kv_sum:.0f}; {len(artifacts)} profile artifacts")
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
    ap.add_argument("--cache-dir", default="/tmp/mxtpu_device_obs_cc")
    ap.add_argument("--profile-dir",
                    default="/tmp/mxtpu_device_obs_profiles")
    args = ap.parse_args()
    if args.drill == "replica":
        run_replica(0)
        return
    os.makedirs(args.cache_dir, exist_ok=True)
    shutil.rmtree(args.profile_dir, ignore_errors=True)
    run_drill(args.cache_dir, args.profile_dir)


if __name__ == "__main__":
    main()
