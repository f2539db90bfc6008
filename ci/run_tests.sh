#!/usr/bin/env bash
# Canonical "how to run everything" script (reference analog:
# ci/docker/runtime_functions.sh).  All suites run on a virtual
# 8-device CPU mesh unless a TPU tier is requested.
set -euo pipefail
cd "$(dirname "$0")/.."

usage() {
    cat <<EOF
usage: ci/run_tests.sh <function>
  unittest_cpu          full CPU suite (single run; ~30 min on 1 core)
  unittest_cpu_chunked  CPU suite in two halves (for constrained runners)
  unittest_tpu          TPU tier (tests_tpu/: op sweep on the chip, CPU-vs-
                        TPU consistency, Pallas kernels vs lax; errors
                        without a TPU — run it through the chip tool)
  smoke                 60-second end-to-end slice (gluon MNIST)
  telemetry_smoke       MNIST slice under MXNET_TELEMETRY=1; asserts the
                        Prometheus dump has nonzero op/step/compile counters
  trace_smoke           MNIST slice with the profiler+tracer on; asserts the
                        chrome trace is valid JSON with NESTED ph:"X" spans
                        and the snapshot reports a finite mfu > 0
  fused_smoke           fused-optimizer drill: short training run under
                        telemetry; asserts ONE optimizer dispatch per
                        step, fused_updates == steps, and the fused jit
                        cache stops missing after warmup
  loop_smoke            whole-step capture drill: CompiledLoop run with a
                        slow (sleeping) batch source behind the device
                        prefetcher; asserts ONE dispatch per k-step
                        chunk (loop jit cache), chunk/step counters,
                        and that the trace shows fetch+h2d overlapped
                        compute (prefetch.wait << loop.chunk time)
  zero1_smoke           ZeRO-1 drill: short training run on the
                        8-virtual-device dp mesh with zero1=1; asserts
                        params equal to rounding to the replicated fused
                        golden, ONE dispatch per step (zero1 jit cache
                        stops missing after warmup), the state-bytes
                        gauge at ~1/8 of the replicated gauge, and a
                        nonzero all-gather volume gauge
  fault_smoke           resilience drill: tiny run with an injected
                        transient kvstore fault, a mid-run kill (exit 17)
                        and a checkpoint resume; asserts retries > 0, the
                        resumed params are bit-identical to an
                        uninterrupted golden run, and losses stay
                        continuous across the kill
  serve_smoke           serving drill: in-process ModelServer, concurrent
                        HTTP clients; asserts batched dispatches << request
                        count, per-request outputs match the direct engine,
                        serve histograms on /metrics, and a clean drain
  obs_smoke             observability drill: 16 traced clients against a
                        server with a serving.infer:hang fault; asserts
                        every response (200 and 5xx) echoed its
                        x-request-id, the watchdog's flight-recorder dump
                        names the hung requests' ids, /slo reports the
                        budget burn, and mxtpu_slo_* series are on
                        /metrics
  generate_smoke        continuous-batching drill: staggered streaming
                        clients against a GenerationEngine model; asserts
                        the late request emits tokens BEFORE the first
                        finishes (mid-flight join), streamed outputs are
                        token-identical to solo decode, X-Request-Id
                        rides the SSE headers, a serving.infer:hang
                        during decode fails the rider (id on the error
                        event) and recovers via the watchdog, and
                        mxtpu_generate_* series are on /metrics
  spec_smoke            speculative-decoding drill: 16 streaming clients
                        against a preloaded paged target+draft server with
                        MXNET_SPEC_K=4; asserts every stream is
                        bit-identical to a no-draft golden run,
                        mxtpu_spec_accepted_tokens_per_dispatch > 1.0 on
                        /metrics, and a serving.infer:hang wedged
                        mid-verify fails its riders with ids on the
                        terminal SSE error and recovers via the watchdog
  decode_scan_smoke     scanned decode-burst drill: 16 streaming clients
                        through a router over a preloaded replica with
                        default MXNET_DECODE_SCAN_STEPS=8; asserts every
                        stream is bit-identical to a no-scan golden run,
                        the router-federated mxtpu_dispatches_per_token
                        reads < 0.2, and a serving.infer:hang wedged
                        mid-burst fails its rider (id on the terminal
                        SSE error) and recovers via the watchdog
  sampling_smoke        sampling-plane drill: 16 streaming sampled
                        clients through a router over a preloaded
                        burst replica — every done event echoes its
                        seed, two identical-seed requests are
                        byte-identical, a stop sequence completed
                        mid-burst trims the over-generated tail, and
                        sampled speculative decoding is bit-identical
                        to the no-draft run with the
                        mxtpu_spec_accept_rate{mode="sampled"} gauge
                        federated on the router /metrics
  paged_smoke           paged KV-cache drill: under a cache-byte budget
                        of 32x16-token blocks (4 rows of max_len 128),
                        16 streaming clients with a shared 32-token
                        system prompt; asserts every stream is
                        token-identical to the cache-free re-forward,
                        the pool sustains >= 2x as many concurrent
                        streams as rows, prefix-cache hits > 0 with
                        the kv/prefix
                        series on /metrics, and a child server drains
                        in-flight streams cleanly on SIGTERM (exit 0)
  lifecycle_smoke       lifecycle drill (three parts): SIGTERM a serving
                        child under 16 concurrent clients — zero reset
                        connections, /readyz flips 503 before the port
                        closes, clean exit 0; a serving.infer:hang fault
                        trips the watchdog + breaker and recovers to
                        SERVING without a process restart; SIGTERM a
                        training loop — emergency checkpoint at the step
                        boundary, resume bit-identical to golden
  router_smoke          fleet drill (four parts): a fresh
                        JAX_COMPILATION_CACHE_DIR makes a second replica's
                        warmup-to-first-200 >= 1.5x faster; SIGKILL one
                        of 3 replicas under 16 streaming clients — zero
                        failed requests (zero-token deaths fail over
                        transparently, mid-stream deaths end in a loud
                        terminal SSE error the client re-issues);
                        rolling drain/restart of all 3 replicas — zero
                        downtime, zero mid-stream errors; prefix-affine
                        routing beats random placement on fleet-wide
                        mxtpu_prefix_cache_hits
  autoscale_smoke       self-healing fleet drill (two parts): the
                        supervisor's replica is SIGKILLed — restart
                        with exponential backoff, counted in
                        mxtpu_supervise_restarts, then quarantined
                        (flap breaker) with an incident bundle on the
                        third kill; a supervised fleet rides a diurnal
                        load curve 1→4→1 while a chaos thread SIGKILLs
                        random replicas — zero failed client requests,
                        every scale-down routed through the router's
                        drain, mxtpu_supervise_*/mxtpu_autoscale_*
                        series on the router /metrics
  fleet_obs_smoke       observability drill: 3 telemetry-enabled
                        replicas + router, 16 streaming clients, a
                        serving.infer:hang wedge on one replica —
                        stitched GET /trace shows both failover legs
                        with the surviving replica's spans grafted
                        under their hop; federated /metrics fleet sums
                        equal the arithmetic sum of replica counters;
                        exactly ONE incident bundle written, naming the
                        request ids that failed on the hung replica
  device_obs_smoke      device-plane drill: 3 replicas (one with an
                        attached draft model) + router under 16
                        streaming clients — mxtpu_dispatches_per_token
                        reads exactly 1.0 on the plain replicas and
                        < 1.0 on the spec replica; GET /programs
                        fan-out shows compiled == expected on every
                        replica; federated kv:gen owner bytes on the
                        router /metrics; one POST /debug/profile
                        fan-out returns an artifact per replica
  health_smoke          health-plane drill (three parts): a golden
                        poisoned run plane-OFF (skip guard eats an
                        injected gradient NaN), the same run under
                        MXNET_HEALTH_PLANE=1 — the detector names the
                        first non-finite leaf at the exact poisoned
                        step and the flight recorder writes exactly
                        ONE debounced training_anomaly dump carrying
                        the attribution — then a bit-identical param
                        compare across the two runs
  multichip_dryrun      8-virtual-device full-train-step compile+run
  static                mxtpu-lint static analysis (host-sync, donation,
                        closed-program-set, lock-discipline,
                        registry-drift; see docs/static_analysis.md)
                        plus the numpy-API audit — fails on any
                        unsuppressed finding
EOF
    exit 1
}

static() {
    # stdlib-only: runs without jax. Lint first (includes the
    # code<->docs registry-drift pass), then the numpy surface audit.
    python tools/mxtpu_lint.py incubator_mxnet_tpu
    python tools/np_audit.py --check
}

unittest_cpu() {
    python -m pytest tests/ -q
}

unittest_cpu_chunked() {
    mapfile -t files < <(ls tests/test_*.py | sort)
    half=$(( (${#files[@]} + 1) / 2 ))
    python -m pytest "${files[@]:0:half}" -q -p no:cacheprovider
    python -m pytest "${files[@]:half}" -q -p no:cacheprovider
}

unittest_tpu() {
    python -m pytest tests_tpu/ -q
}

smoke() {
    python example/gluon/mnist.py --cpu --epochs 1
}

telemetry_smoke() {
    local dump=/tmp/mxtpu_telemetry_smoke.prom
    rm -f "$dump"
    MXNET_TELEMETRY=1 MXNET_TELEMETRY_DUMP="$dump" \
        python example/gluon/mnist.py --cpu --epochs 1 --hybridize
    python - "$dump" <<'EOF'
import sys

vals = {}
for line in open(sys.argv[1]):
    line = line.strip()
    if not line or line.startswith("#"):
        continue
    name, _, val = line.rpartition(" ")
    base = name.split("{")[0]
    try:
        vals[base] = vals.get(base, 0.0) + float(val)
    except ValueError:
        pass

for metric in ("mx_op_dispatch_total", "mx_trainer_steps_total",
               "mx_compile_total", "mx_trainer_step_seconds_count"):
    assert vals.get(metric, 0) > 0, \
        f"telemetry_smoke: {metric} is zero/absent; got {sorted(vals)}"
print("telemetry_smoke ok:",
      {k: vals[k] for k in ("mx_op_dispatch_total",
                            "mx_trainer_steps_total", "mx_compile_total")})
EOF
}

trace_smoke() {
    local trace=/tmp/mxtpu_trace_smoke.json
    local snap=/tmp/mxtpu_trace_smoke_snapshot.json
    rm -f "$trace" "$snap"
    TRACE_OUT="$trace" SNAP_OUT="$snap" python - <<'EOF'
import json, os, runpy, sys

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import telemetry

telemetry.start()
mx.profiler.set_config(filename=os.environ["TRACE_OUT"])
mx.profiler.set_state("run")
sys.argv = ["mnist.py", "--cpu", "--epochs", "1", "--hybridize"]
runpy.run_path("example/gluon/mnist.py", run_name="__main__")
mx.profiler.set_state("stop")
mx.profiler.dump()
with open(os.environ["SNAP_OUT"], "w") as f:
    json.dump(telemetry.snapshot(include_memory=False), f)
EOF
    python - "$trace" "$snap" <<'EOF'
import json, math, sys

trace = json.load(open(sys.argv[1]))          # must be valid JSON
spans = [e for e in trace["traceEvents"] if e["ph"] == "X"]
assert spans, "trace_smoke: no ph:X events at all"

def contains(outer, inner):
    return (outer is not inner
            and outer.get("pid") == inner.get("pid")
            and outer.get("tid") == inner.get("tid")
            and outer["ts"] <= inner["ts"]
            and inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"])

nested = [(o["name"], i["name"]) for o in spans for i in spans
          if contains(o, i)]
assert nested, "trace_smoke: no nested ph:X spans in the trace"

snap = json.load(open(sys.argv[2]))
mfu = snap["gauges"].get("mxtpu_mfu")
assert mfu is not None and math.isfinite(mfu) and mfu > 0, \
    f"trace_smoke: mfu not finite/positive: {mfu!r}"
assert snap["histograms"]["mxtpu_step_seconds"]["count"] > 0
print("trace_smoke ok: %d spans, %d nestings, mfu=%.3g"
      % (len(spans), len(nested), mfu))
EOF
}

fused_smoke() {
    JAX_PLATFORMS=cpu python - <<'EOF'
import numpy as np

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import autograd as ag
from incubator_mxnet_tpu import telemetry
from incubator_mxnet_tpu.gluon import Trainer, nn

telemetry.start()
mx.random.seed(0)
net = nn.HybridSequential()
for _ in range(3):
    net.add(nn.Dense(32, in_units=32, activation="relu"))
net.initialize(init=mx.init.Xavier())
net.hybridize()
x = mx.nd.array(np.random.default_rng(0).standard_normal(
    (8, 32)).astype(np.float32))

trainer = Trainer(net.collect_params(), "adam", {"learning_rate": 1e-3})
STEPS = 6
for _ in range(STEPS):
    with ag.record():
        loss = (net(x) ** 2).mean()
    loss.backward()
    trainer.step(8)
mx.nd.waitall()

assert trainer._fused is not None, \
    "fused_smoke: fused updater not engaged (default path regressed)"
flat = telemetry.counters_flat()
fused = flat.get("mxtpu_optimizer_fused_updates", 0)
assert fused == STEPS, \
    f"fused_smoke: fused_updates {fused} != steps {STEPS}"
g = telemetry.registry.get("mxtpu_optimizer_dispatches_per_step")
disp = sum(g._values.values())
assert disp == 1, \
    f"fused_smoke: {disp} optimizer dispatches in last step (wanted 1)"
key = (("site", "fused_update"),)
hits = telemetry.registry.get(
    "mx_compile_cache_hits_total")._values.get(key, 0)
miss = telemetry.registry.get(
    "mx_compile_cache_misses_total")._values.get(key, 0)
assert 1 <= miss <= 2 and hits + miss == STEPS, \
    f"fused_smoke: compile cache hits={hits} misses={miss} (steps {STEPS})"
print(f"fused_smoke ok: {STEPS} steps, 1 dispatch/step, "
      f"fused_updates={int(fused)}, cache hits={int(hits)} "
      f"misses={int(miss)}")
EOF
}

loop_smoke() {
    local trace=/tmp/mxtpu_loop_smoke_trace.json
    rm -f "$trace"
    TRACE_OUT="$trace" JAX_PLATFORMS=cpu python - <<'EOF'
import json
import os
import time

import numpy as np

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import telemetry
from incubator_mxnet_tpu.gluon import loss as gloss, nn
from incubator_mxnet_tpu.io.prefetch import DevicePrefetcher
from incubator_mxnet_tpu.parallel import CompiledLoop, make_mesh

telemetry.start()
mx.profiler.set_config(filename=os.environ["TRACE_OUT"])
mx.profiler.set_state("run")

mx.random.seed(0)
net = nn.HybridSequential()
net.add(nn.Dense(1024, in_units=1024, activation="relu"))
net.add(nn.Dense(1024, in_units=1024, activation="relu"))
net.add(nn.Dense(1024, in_units=1024))
net.initialize(init=mx.init.Xavier())

K, STEPS = 4, 12
loop = CompiledLoop(net, gloss.L2Loss(), "sgd",
                    {"learning_rate": 0.01, "momentum": 0.9},
                    loop_steps=K, mesh=make_mesh({"data": 1}))

rng = np.random.default_rng(0)
def batches():
    for _ in range(STEPS):
        time.sleep(0.003)        # a deliberately slow host-side source
        yield (rng.standard_normal((64, 1024)).astype(np.float32),
               rng.standard_normal((64, 1024)).astype(np.float32))

pf = DevicePrefetcher(batches(), placement=loop._shard_batch)
t0 = time.perf_counter()
losses = loop.run(pf)            # run() keeps an existing prefetcher
wall = time.perf_counter() - t0
st = pf.stats()

mx.profiler.set_state("stop")
mx.profiler.dump()

assert losses.shape == (STEPS,) and np.isfinite(losses).all(), losses
flat = telemetry.counters_flat()
chunks = flat.get("mxtpu_loop_chunks", 0)
assert chunks == STEPS // K, f"loop_smoke: {chunks} chunks (wanted 3)"
assert flat.get("mx_trainer_steps_total", 0) == STEPS
key = (("site", "loop"),)
hits = telemetry.registry.get(
    "mx_compile_cache_hits_total")._values.get(key, 0)
miss = telemetry.registry.get(
    "mx_compile_cache_misses_total")._values.get(key, 0)
assert miss == 1 and hits + miss == chunks, \
    f"loop_smoke: hits={hits} misses={miss} for {chunks} chunks — " \
    "wanted ONE compiled dispatch per k-step chunk"
assert not st["degraded"] and st["batches"] == STEPS

# overlap: the consumer barely waited for fetch+h2d even though every
# upstream batch slept — the pipeline hid it behind chunk compute
assert st["wait_seconds"] < 0.5 * wall, \
    f"loop_smoke: consumer waited {st['wait_seconds']:.3f}s " \
    f"of {wall:.3f}s — prefetch is not overlapping"

# same fact in the span trace: prefetch.wait time between chunk spans
# is a small fraction of chunk time (no fetch-wait gap)
trace = json.load(open(os.environ["TRACE_OUT"]))
spans = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
dur = {}
for e in spans:
    dur[e["name"]] = dur.get(e["name"], 0.0) + e["dur"]
assert dur.get("loop.chunk", 0) > 0, sorted(dur)
warm = max((e["dur"] for e in spans if e["name"] == "prefetch.wait"),
           default=0.0)          # first wait overlaps chunk-0 compile
steady = dur.get("prefetch.wait", 0.0) - warm
assert steady < 0.5 * dur["loop.chunk"], \
    f"loop_smoke: steady-state prefetch.wait {steady / 1e6:.3f}s vs " \
    f"loop.chunk {dur['loop.chunk'] / 1e6:.3f}s — fetch-wait gap visible"

telemetry.stop()
print(f"loop_smoke ok: {STEPS} steps in {chunks} dispatches "
      f"(hits={int(hits)} misses={int(miss)}), consumer waited "
      f"{st['wait_seconds']:.3f}s of {wall:.3f}s, steady prefetch.wait "
      f"{steady / 1e6:.3f}s vs chunk {dur['loop.chunk'] / 1e6:.3f}s")
EOF
}

zero1_smoke() {
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    JAX_PLATFORMS=cpu python - <<'EOF'
import numpy as np

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import autograd as ag
from incubator_mxnet_tpu import telemetry
from incubator_mxnet_tpu.gluon import Trainer, nn

STEPS = 6

def train(zero1):
    mx.random.seed(0)
    net = nn.HybridSequential()
    for _ in range(3):
        net.add(nn.Dense(64, in_units=64, activation="relu"))
    net.initialize(init=mx.init.Xavier())
    x = mx.nd.array(np.random.default_rng(0).standard_normal(
        (8, 64)).astype(np.float32))
    trainer = Trainer(net.collect_params(), "adam",
                      {"learning_rate": 1e-3, "wd": 1e-2},
                      fused=True, zero1=zero1)
    for _ in range(STEPS):
        with ag.record():
            loss = (net(x) ** 2).mean()
        loss.backward()
        trainer.step(8)
    mx.nd.waitall()
    params = [p.data().asnumpy()
              for p in net.collect_params().values()]
    return params, trainer

# golden: the replicated fused path (also records the full state bytes)
telemetry.start()
golden, _ = train(zero1=False)
full_bytes = telemetry.counters_flat()["mxtpu_optimizer_state_bytes"]
telemetry.stop()
telemetry.reset()

telemetry.start()
sharded, trainer = train(zero1=True)
assert trainer._fused is not None and trainer._fused._z_mesh is not None, \
    "zero1_smoke: zero1 fused updater not engaged"
assert trainer._fused._z_state is not None, \
    "zero1_smoke: flat sharded state never materialized"
n_dev = int(trainer._fused._z_mesh.shape["data"])
assert n_dev == 8, f"zero1_smoke: dp mesh has {n_dev} devices (wanted 8)"

# 1. parity with the replicated golden, to rounding (another compiled
#    program contracts other multiply-adds: docs/performance.md)
for a, b in zip(sharded, golden):
    assert np.allclose(a, b, rtol=2e-6, atol=1e-7), \
        "zero1_smoke: sharded params diverged from the replicated golden"

# 2. still ONE donated dispatch per step, compiled once
flat = telemetry.counters_flat()
assert flat["mxtpu_optimizer_fused_updates"] == STEPS
g = telemetry.registry.get("mxtpu_optimizer_dispatches_per_step")
disp = sum(g._values.values())
assert disp == 1, \
    f"zero1_smoke: {disp} optimizer dispatches in last step (wanted 1)"
key = (("site", "zero1_update"),)
hits = telemetry.registry.get(
    "mx_compile_cache_hits_total")._values.get(key, 0)
miss = telemetry.registry.get(
    "mx_compile_cache_misses_total")._values.get(key, 0)
assert 1 <= miss <= 2 and hits + miss == STEPS, \
    f"zero1_smoke: compile cache hits={hits} misses={miss} (steps {STEPS})"

# 3. the memory win: per-replica state bytes ~1/8 of replicated
shard_bytes = flat["mxtpu_optimizer_state_bytes"]
ratio = shard_bytes / full_bytes
assert ratio <= 0.25, \
    f"zero1_smoke: state ratio {ratio:.3f} > 0.25 " \
    f"({int(shard_bytes)}/{int(full_bytes)} bytes)"
assert shard_bytes * n_dev >= full_bytes, \
    "zero1_smoke: state gauge below 1/N — accounting is wrong"
ag_bytes = flat["mxtpu_zero1_allgather_bytes"]
assert ag_bytes > 0, "zero1_smoke: all-gather volume gauge not set"

print(f"zero1_smoke ok: {STEPS} steps equal to golden to rounding, "
      f"1 dispatch/step (hits={int(hits)} misses={int(miss)}), "
      f"state {int(shard_bytes)}/{int(full_bytes)} bytes "
      f"(ratio {ratio:.3f}), allgather {int(ag_bytes)} B/step")
EOF
}

fault_smoke() {
    local out=/tmp/mxtpu_fault_smoke
    rm -rf "$out"
    local plan="kvstore.push:ioerror@2"
    # golden: no faults, no kill — the reference trajectory
    env -u MXNET_FAULT_PLAN python tools/fault_smoke.py golden --out "$out"
    # kill: same run under an injected transient fault, preempted mid-run
    set +e
    MXNET_FAULT_PLAN="$plan" python tools/fault_smoke.py kill --out "$out"
    local rc=$?
    set -e
    [ "$rc" -eq 17 ] || {
        echo "fault_smoke: kill run exited $rc (wanted 17)"; exit 1; }
    # resume: restore the checkpoint, absorb the fault again, finish
    MXNET_FAULT_PLAN="$plan" python tools/fault_smoke.py resume --out "$out"
    # check: bit-identical params, continuous losses
    env -u MXNET_FAULT_PLAN python tools/fault_smoke.py check --out "$out"
}

serve_smoke() {
    JAX_PLATFORMS=cpu python - <<'EOF'
import json
import threading
import urllib.request

import numpy as np

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import telemetry
from incubator_mxnet_tpu.gluon import nn
from incubator_mxnet_tpu.serving import InferenceEngine, ModelServer
from incubator_mxnet_tpu.serving import metrics as smetrics

telemetry.start()
mx.random.seed(0)
net = nn.HybridSequential()
for _ in range(3):
    net.add(nn.Dense(64, in_units=64, activation="relu"))
net.initialize(init=mx.init.Xavier())

CLIENTS, REQS = 16, 4
engine = InferenceEngine.from_block(net, [(64,)], name="smoke",
                                    max_batch_size=CLIENTS)
rng = np.random.default_rng(0)
xs = [rng.standard_normal((1, 64)).astype(np.float32)
      for _ in range(CLIENTS)]
refs = [np.asarray(engine.predict([x])[0]) for x in xs]

srv = ModelServer(port=0, max_delay_ms=10.0)
srv.add_model("smoke", engine, warmup=True)
srv.start()
url = f"http://127.0.0.1:{srv.port}"
req0, bat0 = smetrics.REQUESTS.value, smetrics.BATCHES.value

errors = []
def client(i):
    try:
        body = json.dumps({"inputs": [xs[i].tolist()]}).encode()
        for _ in range(REQS):
            r = urllib.request.urlopen(urllib.request.Request(
                url + "/v1/models/smoke:predict", data=body), timeout=30)
            out = np.array(json.loads(r.read())["outputs"][0],
                           dtype=np.float32)
            np.testing.assert_allclose(out, refs[i], rtol=1e-4,
                                       atol=1e-5)
    except Exception as e:
        errors.append(f"client {i}: {e!r}")

threads = [threading.Thread(target=client, args=(i,))
           for i in range(CLIENTS)]
[t.start() for t in threads]
[t.join() for t in threads]
assert not errors, "serve_smoke: " + "; ".join(errors[:3])

n_req = smetrics.REQUESTS.value - req0
n_bat = smetrics.BATCHES.value - bat0
assert n_req == CLIENTS * REQS, \
    f"serve_smoke: {n_req} requests counted (wanted {CLIENTS * REQS})"
assert n_bat <= n_req / 2, \
    f"serve_smoke: {int(n_bat)} batches for {int(n_req)} requests — " \
    "dynamic batching is not coalescing"
prom = urllib.request.urlopen(url + "/metrics", timeout=10).read().decode()
for series in ("mxtpu_serve_batch_size", "mxtpu_serve_queue_wait_seconds",
               "mxtpu_serve_latency_seconds"):
    assert series in prom, f"serve_smoke: {series} missing from /metrics"
assert engine.compiled_programs() == len(engine.buckets), \
    f"serve_smoke: {engine.compiled_programs()} compiled programs for " \
    f"{len(engine.buckets)} buckets — the jit cache is not bounded"
srv.stop()                      # graceful drain + port release
assert srv.models() == [], "serve_smoke: registry not empty after stop"
print(f"serve_smoke ok: {int(n_req)} requests in {int(n_bat)} batches "
      f"(mean {n_req / n_bat:.1f} rows), "
      f"{engine.compiled_programs()} programs for "
      f"{len(engine.buckets)} buckets, clean shutdown")
EOF
}

obs_smoke() {
    local out=/tmp/mxtpu_obs_smoke
    rm -rf "$out" && mkdir -p "$out"
    MXNET_FAULT_PLAN="serving.infer:hang:30@1" \
    MXNET_SERVE_HANG_SECONDS=0.5 \
    MXNET_SERVE_BREAKER_COOLDOWN_SECONDS=0.3 \
    MXNET_SERVE_SLO_P99_MS=250 \
    MXNET_SERVE_SLO_AVAILABILITY=0.99 \
    MXNET_FLIGHT_DUMP_DIR="$out" \
    JAX_PLATFORMS=cpu python - <<'EOF'
import glob
import json
import os
import threading
import time
import urllib.error
import urllib.request

import numpy as np

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import telemetry
from incubator_mxnet_tpu.gluon import nn
from incubator_mxnet_tpu.serving import InferenceEngine, ModelServer

telemetry.start()
mx.random.seed(0)
net = nn.HybridSequential()
for _ in range(2):
    net.add(nn.Dense(32, in_units=32, activation="relu"))
net.initialize(init=mx.init.Xavier())

CLIENTS, REQS = 16, 3
engine = InferenceEngine.from_block(net, [(32,)], name="obs",
                                    max_batch_size=CLIENTS)
srv = ModelServer(port=0, max_delay_ms=10.0)
srv.add_model("obs", engine, warmup=True)
srv.start()
url = f"http://127.0.0.1:{srv.port}"

rng = np.random.default_rng(0)
xs = [rng.standard_normal((1, 32)).astype(np.float32)
      for _ in range(CLIENTS)]

# (sent_rid, status, echoed_header_rid) per response — including 5xx
results = []
res_lock = threading.Lock()

def client(i):
    body = json.dumps({"inputs": [xs[i].tolist()]}).encode()
    for k in range(REQS):
        rid = f"obs-{i}-{k}"
        req = urllib.request.Request(
            url + "/v1/models/obs:predict", data=body,
            headers={"x-request-id": rid})
        try:
            r = urllib.request.urlopen(req, timeout=30)
            status, echoed = r.status, r.headers.get("X-Request-Id")
            r.read()
        except urllib.error.HTTPError as e:
            status, echoed = e.code, e.headers.get("X-Request-Id")
            e.read()
        with res_lock:
            results.append((rid, status, echoed))
        time.sleep(0.05)        # let the breaker cooldown recover

threads = [threading.Thread(target=client, args=(i,))
           for i in range(CLIENTS)]
[t.start() for t in threads]
[t.join() for t in threads]

# recovery round: wait out the breaker cooldown, then probe until the
# model serves again (proves the restart actually healed the worker)
recovered = []
deadline = time.monotonic() + 10.0
k = 0
while time.monotonic() < deadline and not recovered:
    time.sleep(0.2)
    rid = f"obs-recover-{k}"
    k += 1
    req = urllib.request.Request(
        url + "/v1/models/obs:predict",
        data=json.dumps({"inputs": [xs[0].tolist()]}).encode(),
        headers={"x-request-id": rid})
    try:
        r = urllib.request.urlopen(req, timeout=30)
        status, echoed = r.status, r.headers.get("X-Request-Id")
        r.read()
    except urllib.error.HTTPError as e:
        status, echoed = e.code, e.headers.get("X-Request-Id")
        e.read()
    results.append((rid, status, echoed))
    if status == 200:
        recovered.append(rid)

# 1. every response, 200 and 5xx alike, echoed its x-request-id
assert len(results) >= CLIENTS * REQS
bad_echo = [(rid, st, ech) for rid, st, ech in results if ech != rid]
assert not bad_echo, f"obs_smoke: responses without echo: {bad_echo[:3]}"
failed = [rid for rid, st, _ in results if st >= 500]
ok = [rid for rid, st, _ in results if st == 200]
assert failed, "obs_smoke: the hang fault produced no 5xx responses"
assert recovered, "obs_smoke: nothing recovered after the watchdog restart"

# 2. the watchdog wrote a flight dump naming the hung requests' ids
dump_dir = os.environ["MXNET_FLIGHT_DUMP_DIR"]
deadline = time.monotonic() + 10.0
dumps = []
while time.monotonic() < deadline:
    dumps = glob.glob(os.path.join(dump_dir,
                                   "flight_*_watchdog_restart.json"))
    if dumps:
        break
    time.sleep(0.1)
assert dumps, f"obs_smoke: no watchdog flight dump in {dump_dir}"
dump = json.load(open(dumps[0]))
wd = [e for e in dump["ring"]
      if e["type"] == "fault" and e["event"] == "watchdog"]
assert wd, "obs_smoke: no watchdog fault entry in the dump ring"
hung = [r for e in wd for r in e.get("request_ids", ())]
assert hung and set(hung) <= {rid for rid, _, _ in results}, \
    f"obs_smoke: dump names unknown request ids: {hung[:3]}"
assert set(hung) <= set(failed), \
    "obs_smoke: a request the dump calls hung got a 200"
assert "serving" in dump, "obs_smoke: dump lacks the serving provider"

# 3. /slo reports the burn
slo = json.load(urllib.request.urlopen(url + "/slo", timeout=10))
m = slo["models"]["obs"]
assert m["bad"] >= len(failed) and m["burn_rate"] > 0.0, \
    f"obs_smoke: SLO window missed the failures: {m}"

# 4. SLO series on /metrics
prom = urllib.request.urlopen(url + "/metrics", timeout=10).read().decode()
for series in ("mxtpu_slo_error_budget_remaining", "mxtpu_slo_burn_rate",
               "mxtpu_slo_availability"):
    assert series in prom, f"obs_smoke: {series} missing from /metrics"

srv.stop()
telemetry.stop()
print(f"obs_smoke ok: {len(ok)}/{len(results)} ok, {len(failed)} failed "
      f"with ids echoed, {len(hung)} hung ids in "
      f"{os.path.basename(dumps[0])}, burn_rate={m['burn_rate']:.2f}, "
      f"budget={m['error_budget_remaining']:.2f}")
EOF
}

generate_smoke() {
    MXNET_SERVE_HANG_SECONDS=0.5 \
    MXNET_SERVE_BREAKER_COOLDOWN_SECONDS=0.3 \
    JAX_PLATFORMS=cpu python - <<'EOF'
import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import fault, telemetry
from incubator_mxnet_tpu.models.gpt import GPTModel
from incubator_mxnet_tpu.serving import GenerationEngine, ModelServer

telemetry.start()
mx.random.seed(3)
net = GPTModel(vocab_size=50, units=32, hidden_size=64, num_layers=2,
               num_heads=2, max_length=256, dropout=0.0)
net.initialize(init=mx.init.Normal(0.6))
net(mx.nd.array(np.zeros((1, 2), np.int32)))

engine = GenerationEngine(net, name="gen", max_slots=4, max_len=256)
LONG, LATE = [9, 9, 4, 1], [3, 7, 11]
solo_long = engine.generate(LONG, max_new_tokens=200)
solo_late = engine.generate(LATE, max_new_tokens=5)
engine.reset()

srv = ModelServer(port=0)
srv.add_model("gen", engine, warmup=True)
srv.start()
url = f"http://127.0.0.1:{srv.port}"

def stream(prompt, n, rid):
    """POST :generate with stream=true; returns (tokens-with-times,
    final events, echoed X-Request-Id header)."""
    req = urllib.request.Request(
        url + "/v1/models/gen:generate",
        data=json.dumps({"tokens": prompt, "max_new_tokens": n,
                         "stream": True}).encode(),
        headers={"x-request-id": rid})
    r = urllib.request.urlopen(req, timeout=60)
    toks, finals = [], []
    for line in r:
        line = line.strip()
        if line.startswith(b"data:"):
            d = json.loads(line.split(b":", 1)[1])
            if "token" in d:
                toks.append((d["token"], time.monotonic()))
            else:
                finals.append(d)
    return toks, finals, r.headers.get("X-Request-Id")

# -- 1. staggered streaming clients: the late request must emit tokens
#       while the first is STILL decoding (continuous admission) ------
results = {}
def run(key, prompt, n, rid):
    results[key] = stream(prompt, n, rid)

t1 = threading.Thread(target=run, args=("long", LONG, 200, "gen-long"))
t1.start()
time.sleep(0.08)
t2 = threading.Thread(target=run, args=("late", LATE, 5, "gen-late"))
t2.start()
t1.join(); t2.join()

toks_long, _, rid_long = results["long"]
toks_late, finals_late, rid_late = results["late"]
assert rid_long == "gen-long" and rid_late == "gen-late", \
    f"generate_smoke: streamed X-Request-Id not echoed: " \
    f"{rid_long!r}/{rid_late!r}"
assert [t for t, _ in toks_long] == solo_long, \
    "generate_smoke: interleaved long output != solo"
assert [t for t, _ in toks_late] == solo_late, \
    "generate_smoke: interleaved late output != solo"
assert finals_late and finals_late[-1]["request_id"] == "gen-late"
lead = toks_long[-1][1] - toks_late[0][1]
assert lead > 0, \
    "generate_smoke: late request emitted nothing before the first " \
    "request finished — no mid-flight join"

# -- 2. watchdog drill: hang the 5th decode dispatch mid-stream; the
#       rider must fail with its id on the stream, then the model
#       must recover after the restart + breaker cooldown -------------
fault.install_plan("serving.infer:hang:30@5")
toks_h, finals_h, rid_h = stream(LONG, 100, "gen-hang")
assert rid_h == "gen-hang"
assert 0 < len(toks_h) < 100, \
    f"generate_smoke: hang drill emitted {len(toks_h)} tokens"
assert finals_h and "error" in finals_h[-1], \
    f"generate_smoke: no terminal error event: {finals_h}"
assert finals_h[-1]["request_id"] == "gen-hang"
fault.clear_plan()

recovered = None
deadline = time.monotonic() + 15.0
while time.monotonic() < deadline and recovered is None:
    time.sleep(0.2)
    try:
        r = urllib.request.urlopen(urllib.request.Request(
            url + "/v1/models/gen:generate",
            data=json.dumps({"tokens": LATE,
                             "max_new_tokens": 5}).encode()), timeout=30)
        recovered = json.loads(r.read())["tokens"]
    except urllib.error.HTTPError as e:
        e.read()                # 503 while the breaker cools down
assert recovered == solo_late, \
    f"generate_smoke: post-restart output {recovered} != solo"

# -- 3. generation series on /metrics ---------------------------------
prom = urllib.request.urlopen(url + "/metrics", timeout=10).read().decode()
for series in ("mxtpu_generate_tokens", "mxtpu_serve_cache_slots_in_use",
               "mxtpu_generate_token_seconds",
               "mxtpu_generate_decode_step_seconds"):
    assert series in prom, f"generate_smoke: {series} missing from /metrics"

stats = json.load(urllib.request.urlopen(url + "/v1/models",
                                         timeout=10))["models"]["gen"]
assert stats["kind"] == "generation" and stats["watchdog_restarts"] == 1, stats
srv.stop()
telemetry.stop()
print(f"generate_smoke ok: late first-token led long last-token by "
      f"{lead:.3f}s, hang drill failed rider 'gen-hang' after "
      f"{len(toks_h)} tokens and recovered, "
      f"{stats['tokens_emitted']} tokens in {stats['decode_steps']} "
      f"decode steps")
EOF
}

spec_smoke() {
    MXNET_SPEC_K=4 \
    MXNET_SERVE_HANG_SECONDS=0.5 \
    MXNET_SERVE_BREAKER_COOLDOWN_SECONDS=0.3 \
    JAX_PLATFORMS=cpu python - <<'EOF'
import json
import re
import threading
import time
import urllib.error
import urllib.request

import numpy as np

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import fault, telemetry
from incubator_mxnet_tpu.models.gpt import GPTModel
from incubator_mxnet_tpu.serving import GenerationEngine, ModelServer

telemetry.start()
CLIENTS, NEW = 16, 24
SYSTEM = list(range(1, 33))            # shared 32-token system prompt
PROMPTS = [SYSTEM + [40 + i % 8, i % 5] for i in range(CLIENTS)]

def build(name, seed, max_slots):
    mx.random.seed(seed)
    net = GPTModel(vocab_size=50, units=32, hidden_size=64, num_layers=2,
                   num_heads=2, max_length=128, dropout=0.0)
    net.initialize(init=mx.init.Normal(0.6))
    net(mx.nd.array(np.zeros((1, 2), np.int32)))
    return GenerationEngine(net, name=name, max_slots=max_slots,
                            max_len=128, paged=True, block_size=16)

# -- golden: the SAME weights, no draft attached ----------------------
golden_eng = build("golden", 3, 1)
golden = [golden_eng.generate(p, max_new_tokens=NEW) for p in PROMPTS]
del golden_eng

# -- target + draft (identical weights => high accept rate) -----------
engine = build("gen", 3, 4)
draft = build("gen-draft", 3, 4)
engine.attach_draft(draft)             # k from MXNET_SPEC_K=4
assert engine.spec_k == 4, engine.spec_k

srv = ModelServer(port=0)
srv.add_model("gen", engine)
srv.preload()                          # all programs warm pre-bind
assert engine.warm and draft.warm, "spec_smoke: preload left a cold model"
srv.start()
url = f"http://127.0.0.1:{srv.port}"

def stream(prompt, n, rid):
    req = urllib.request.Request(
        url + "/v1/models/gen:generate",
        data=json.dumps({"tokens": prompt, "max_new_tokens": n,
                         "stream": True}).encode(),
        headers={"x-request-id": rid})
    r = urllib.request.urlopen(req, timeout=120)
    toks, finals = [], []
    for line in r:
        line = line.strip()
        if line.startswith(b"data:"):
            d = json.loads(line.split(b":", 1)[1])
            if "token" in d:
                toks.append(d["token"])
            else:
                finals.append(d)
    return toks, finals, r.headers.get("X-Request-Id")

# -- 1. 16 concurrent streaming clients, bit-identical to golden ------
results, errors = {}, []
def run(i):
    try:
        results[i] = stream(PROMPTS[i], NEW, f"spec-{i}")
    except Exception as e:
        errors.append(f"spec-{i}: {e!r}")

threads = [threading.Thread(target=run, args=(i,)) for i in range(CLIENTS)]
for t in threads:
    t.start()
    time.sleep(0.01)                   # staggered mid-flight joins
for t in threads:
    t.join()
assert not errors, "spec_smoke: " + "; ".join(errors[:3])
total_acc = total_drafted = 0
for i in range(CLIENTS):
    toks, finals, rid = results[i]
    assert rid == f"spec-{i}", f"spec_smoke: X-Request-Id lost: {rid!r}"
    assert toks == golden[i], \
        f"spec_smoke: client {i} diverged from no-draft golden: " \
        f"{toks[:8]}... != {golden[i][:8]}..."
    done = finals[-1]
    assert done["request_id"] == f"spec-{i}", done
    total_acc += done["accepted_tokens"]
    total_drafted += done["draft_tokens"]
assert total_drafted > 0 and total_acc > 0, (total_acc, total_drafted)

# -- 2. the amortization gauge must show the draft actually helping ---
prom = urllib.request.urlopen(url + "/metrics", timeout=10).read().decode()
m = re.search(
    r'mxtpu_spec_accepted_tokens_per_dispatch\{[^}]*\}\s+([0-9.eE+-]+)',
    prom)
assert m, "spec_smoke: spec gauge missing from /metrics"
tpd = float(m.group(1))
assert tpd > 1.0, \
    f"spec_smoke: accepted_tokens_per_dispatch {tpd} <= 1.0 — the " \
    f"draft never beat plain decode"

# -- 3. wedge a verify dispatch mid-stream; riders must fail loudly
#       with their ids, then the watchdog restart must recover --------
fault.install_plan("serving.infer:hang:30@3")
toks_h, finals_h, rid_h = stream(PROMPTS[0], 100, "spec-hang")
assert rid_h == "spec-hang"
assert 0 < len(toks_h) < 100, \
    f"spec_smoke: hang drill emitted {len(toks_h)} tokens"
assert finals_h and "error" in finals_h[-1], \
    f"spec_smoke: no terminal error event: {finals_h}"
assert finals_h[-1]["request_id"] == "spec-hang"
fault.clear_plan()

recovered = None
deadline = time.monotonic() + 15.0
while time.monotonic() < deadline and recovered is None:
    time.sleep(0.2)
    try:
        r = urllib.request.urlopen(urllib.request.Request(
            url + "/v1/models/gen:generate",
            data=json.dumps({"tokens": PROMPTS[1],
                             "max_new_tokens": NEW}).encode()), timeout=30)
        recovered = json.loads(r.read())["tokens"]
    except urllib.error.HTTPError as e:
        e.read()                       # 503 while the breaker cools down
assert recovered == golden[1], \
    f"spec_smoke: post-restart output != golden"

stats = json.load(urllib.request.urlopen(url + "/v1/models",
                                         timeout=10))["models"]["gen"]
assert stats["spec_k"] == 4 and stats["watchdog_restarts"] == 1, stats
srv.stop()
telemetry.stop()
print(f"spec_smoke ok: {CLIENTS} streams bit-identical to no-draft "
      f"golden, {tpd:.2f} accepted tokens/dispatch "
      f"(accept rate {stats['spec_accept_rate']:.2f}), hang drill "
      f"failed rider 'spec-hang' after {len(toks_h)} tokens and "
      f"recovered")
EOF
}

decode_scan_smoke() {
    MXNET_SERVE_HANG_SECONDS=0.5 \
    MXNET_SERVE_BREAKER_COOLDOWN_SECONDS=0.3 \
    JAX_PLATFORMS=cpu python - <<'EOF'
import json
import re
import threading
import time
import urllib.error
import urllib.request

import numpy as np

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import fault, telemetry
from incubator_mxnet_tpu.models.gpt import GPTModel
from incubator_mxnet_tpu.serving import (GenerationEngine, ModelServer,
                                         Router)

telemetry.start()
CLIENTS, NEW = 16, 48
SYSTEM = list(range(1, 33))            # shared 32-token system prompt
PROMPTS = [SYSTEM + [40 + i % 8, i % 5] for i in range(CLIENTS)]

def build(name, max_slots, scan_steps):
    mx.random.seed(3)
    net = GPTModel(vocab_size=50, units=32, hidden_size=64, num_layers=2,
                   num_heads=2, max_length=256, dropout=0.0)
    net.initialize(init=mx.init.Normal(0.6))
    net(mx.nd.array(np.zeros((1, 2), np.int32)))
    return GenerationEngine(net, name=name, max_slots=max_slots,
                            max_len=256, paged=True, block_size=16,
                            scan_steps=scan_steps)

# -- golden: the SAME weights, bursts disabled ------------------------
golden_eng = build("golden", 1, 0)
golden = [golden_eng.generate(p, max_new_tokens=NEW) for p in PROMPTS]
del golden_eng

# -- replica with the default burst depth + a router on top -----------
engine = build("gen", CLIENTS, 8)      # every client fits: steady state
assert engine.scan_steps == 8, engine.scan_steps
srv = ModelServer(port=0)
srv.add_model("gen", engine)
srv.preload()                          # burst program warm pre-bind
assert engine.warm, "decode_scan_smoke: preload left a cold model"
srv.start()
router = Router([f"127.0.0.1:{srv.port}"], port=0, host="127.0.0.1",
                health_interval=0.1, upstream_timeout=60.0,
                retry_deadline=60.0, federate_seconds=0.2)
router.start()
url = f"http://127.0.0.1:{router.port}"
direct = f"http://127.0.0.1:{srv.port}"

def stream(base, prompt, n, rid):
    req = urllib.request.Request(
        base + "/v1/models/gen:generate",
        data=json.dumps({"tokens": prompt, "max_new_tokens": n,
                         "stream": True}).encode(),
        headers={"x-request-id": rid})
    r = urllib.request.urlopen(req, timeout=180)
    toks, finals = [], []
    for line in r:
        line = line.strip()
        if line.startswith(b"data:"):
            d = json.loads(line.split(b":", 1)[1])
            if "token" in d:
                toks.append(d["token"])
            else:
                finals.append(d)
    return toks, finals, r.headers.get("X-Request-Id")

# -- 1. 16 streaming clients through the router, bit-identical --------
results, errors = {}, []
def run(i):
    try:
        results[i] = stream(url, PROMPTS[i], NEW, f"scan-{i}")
    except Exception as e:
        errors.append(f"scan-{i}: {e!r}")

threads = [threading.Thread(target=run, args=(i,)) for i in range(CLIENTS)]
for t in threads:
    t.start()
for t in threads:
    t.join()
assert not errors, "decode_scan_smoke: " + "; ".join(errors[:3])
for i in range(CLIENTS):
    toks, finals, rid = results[i]
    assert rid == f"scan-{i}", \
        f"decode_scan_smoke: X-Request-Id lost: {rid!r}"
    assert toks == golden[i], \
        f"decode_scan_smoke: client {i} diverged from no-scan golden: " \
        f"{toks[:8]}... != {golden[i][:8]}..."
st = json.load(urllib.request.urlopen(
    direct + "/v1/models", timeout=10))["models"]["gen"]
assert st["decode_scan_steps"] == 8, st
assert st["decode_burst_dispatches"] > 0, \
    "decode_scan_smoke: no burst dispatch was ever taken"

# -- 2. router-federated dispatch economy: < 0.2 at steady state ------
router._federate_maybe(force=True)
prom = urllib.request.urlopen(url + "/metrics", timeout=10).read().decode()
m = re.search(r'mxtpu_dispatches_per_token\{model="gen"\}'
              r'\s+([0-9.eE+-]+)', prom)
assert m, "decode_scan_smoke: dispatches-per-token not federated:\n" + \
    "\n".join(l for l in prom.splitlines() if "dispatches_per" in l)
dpt = float(m.group(1))
assert dpt < 0.2, \
    f"decode_scan_smoke: federated dispatches_per_token {dpt} >= 0.2 " \
    f"— the scan is not amortizing the host out of the token path"

# -- 3. wedge a burst dispatch mid-stream; the rider must fail loudly
#       with its id, then the watchdog restart must recover -----------
fault.install_plan("serving.infer:hang:30@3")
toks_h, finals_h, rid_h = stream(direct, PROMPTS[0], 100, "scan-hang")
assert rid_h == "scan-hang"
assert 0 < len(toks_h) < 100, \
    f"decode_scan_smoke: hang drill emitted {len(toks_h)} tokens"
assert finals_h and "error" in finals_h[-1], \
    f"decode_scan_smoke: no terminal error event: {finals_h}"
assert finals_h[-1]["request_id"] == "scan-hang"
fault.clear_plan()

recovered = None
deadline = time.monotonic() + 15.0
while time.monotonic() < deadline and recovered is None:
    time.sleep(0.2)
    try:
        r = urllib.request.urlopen(urllib.request.Request(
            direct + "/v1/models/gen:generate",
            data=json.dumps({"tokens": PROMPTS[1],
                             "max_new_tokens": NEW}).encode()), timeout=60)
        recovered = json.loads(r.read())["tokens"]
    except urllib.error.HTTPError as e:
        e.read()                       # 503 while the breaker cools down
assert recovered == golden[1], \
    "decode_scan_smoke: post-restart output != golden"
st = json.load(urllib.request.urlopen(
    direct + "/v1/models", timeout=10))["models"]["gen"]
assert st["watchdog_restarts"] == 1, st
router.stop()
srv.stop()
telemetry.stop()
print(f"decode_scan_smoke ok: {CLIENTS} streams bit-identical to "
      f"no-scan golden, federated dispatches_per_token {dpt:.3f} "
      f"(k=8), hang drill failed rider 'scan-hang' after "
      f"{len(toks_h)} tokens and recovered")
EOF
}

sampling_smoke() {
    MXNET_SPEC_K=4 \
    JAX_PLATFORMS=cpu python - <<'EOF'
import json
import re
import threading
import urllib.error
import urllib.request

import numpy as np

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import telemetry
from incubator_mxnet_tpu.models.gpt import GPTModel
from incubator_mxnet_tpu.serving import (GenerationEngine, ModelServer,
                                         Router, SamplingParams)

telemetry.start()
CLIENTS, NEW = 16, 24
SYSTEM = list(range(1, 17))            # shared 16-token system prompt
PROMPTS = [SYSTEM + [40 + i % 8, i % 5] for i in range(CLIENTS)]

def build(name, max_slots, scan_steps):
    mx.random.seed(3)
    net = GPTModel(vocab_size=50, units=32, hidden_size=64, num_layers=2,
                   num_heads=2, max_length=128, dropout=0.0)
    net.initialize(init=mx.init.Normal(0.6))
    net(mx.nd.array(np.zeros((1, 2), np.int32)))
    return GenerationEngine(net, name=name, max_slots=max_slots,
                            max_len=128, paged=True, block_size=16,
                            scan_steps=scan_steps)

# "gen": burst replica; "spec": target+draft (identical weights) ------
gen = build("gen", CLIENTS, 8)
tgt = build("spec", 4, 0)
dr = build("spec-draft", 4, 0)
tgt.attach_draft(dr)                   # k from MXNET_SPEC_K=4
srv = ModelServer(port=0)
srv.add_model("gen", gen)
srv.add_model("spec", tgt)
srv.preload()
srv.start()
router = Router([f"127.0.0.1:{srv.port}"], port=0, host="127.0.0.1",
                health_interval=0.1, upstream_timeout=60.0,
                retry_deadline=60.0, federate_seconds=0.2)
router.start()
url = f"http://127.0.0.1:{router.port}"
direct = f"http://127.0.0.1:{srv.port}"

def post(model, body, rid=None, base=None):
    req = urllib.request.Request(
        (base or url) + f"/v1/models/{model}:generate",
        data=json.dumps(body).encode(),
        headers={"x-request-id": rid} if rid else {})
    return urllib.request.urlopen(req, timeout=120)

def stream(model, body, rid):
    r = post(model, dict(body, stream=True), rid)
    toks, finals = [], []
    for line in r:
        line = line.strip()
        if line.startswith(b"data:"):
            d = json.loads(line.split(b":", 1)[1])
            if "token" in d:
                toks.append(d["token"])
            else:
                finals.append(d)
    return toks, finals, r.headers.get("X-Request-Id")

# -- 1. 16 streaming SAMPLED clients through the router ---------------
results, errors = {}, []
def run(i):
    try:
        results[i] = stream("gen", {
            "tokens": PROMPTS[i], "max_new_tokens": NEW,
            "temperature": 0.8, "top_p": 0.9, "seed": 1000 + i},
            f"smp-{i}")
    except Exception as e:
        errors.append(f"smp-{i}: {e!r}")

threads = [threading.Thread(target=run, args=(i,)) for i in range(CLIENTS)]
for t in threads:
    t.start()
for t in threads:
    t.join()
assert not errors, "sampling_smoke: " + "; ".join(errors[:3])
for i in range(CLIENTS):
    toks, finals, rid = results[i]
    assert rid == f"smp-{i}", f"sampling_smoke: X-Request-Id lost: {rid!r}"
    assert len(toks) == NEW and finals[-1].get("seed") == 1000 + i, \
        f"sampling_smoke: client {i} malformed: {len(toks)} toks, " \
        f"{finals[-1]}"
assert len({tuple(results[i][0]) for i in range(CLIENTS)}) > 1, \
    "sampling_smoke: every seed produced identical output"

# -- 2. two identical-seed requests are byte-identical ----------------
body0 = {"tokens": PROMPTS[0], "max_new_tokens": NEW,
         "temperature": 0.8, "top_p": 0.9, "seed": 1000}
r1 = json.loads(post("gen", body0).read())
r2 = json.loads(post("gen", body0).read())
assert r1["tokens"] == r2["tokens"] == results[0][0], \
    "sampling_smoke: identical-seed replay diverged"
assert r1["seed"] == 1000, r1

# -- 3. stop sequence completed mid-burst: tail trimmed ---------------
base = json.loads(post("gen", {"tokens": PROMPTS[1],
                               "max_new_tokens": NEW,
                               "temperature": 0.8,
                               "seed": 77}).read())["tokens"]
stopped = json.loads(post("gen", {"tokens": PROMPTS[1],
                                  "max_new_tokens": NEW,
                                  "temperature": 0.8, "seed": 77,
                                  "stop": [base[3:5]]}).read())["tokens"]
assert stopped == base[:5], \
    f"sampling_smoke: stop trim wrong: {stopped} vs {base[:5]}"
st = json.load(urllib.request.urlopen(
    direct + "/v1/models", timeout=10))["models"]["gen"]
assert st["stop_hits"] >= 1 and st["decode_burst_dispatches"] > 0, st

# -- 4. sampled spec preserves the no-draft stream; accept-rate gauge
#       carries mode="sampled" on the federated /metrics --------------
golden_eng = build("golden", 1, 0)
sp = SamplingParams(temperature=0.7, top_p=0.95, seed=4242)
want = golden_eng.generate(PROMPTS[2], NEW, sampling=sp)
got = json.loads(post("spec", {"tokens": PROMPTS[2],
                               "max_new_tokens": NEW,
                               "temperature": 0.7, "top_p": 0.95,
                               "seed": 4242}).read())
assert got["tokens"] == want, \
    f"sampling_smoke: sampled spec diverged from no-draft run: " \
    f"{got['tokens'][:8]}... != {want[:8]}..."
assert got["draft_tokens"] > 0, got
router._federate_maybe(force=True)
prom = urllib.request.urlopen(url + "/metrics", timeout=10).read().decode()
m = re.search(r'mxtpu_spec_accept_rate\{[^}]*mode="sampled"[^}]*\}'
              r'\s+([0-9.eE+-]+)', prom)
assert m, "sampling_smoke: no mode=\"sampled\" accept-rate gauge:\n" + \
    "\n".join(l for l in prom.splitlines() if "accept_rate" in l)
rate = float(m.group(1))
assert 0.0 <= rate <= 1.0, rate
assert re.search(r'mxtpu_sample_requests\{[^}]*mode="sampled"',
                 prom), "sampling_smoke: mxtpu_sample_requests missing"
router.stop()
srv.stop()
telemetry.stop()
print(f"sampling_smoke ok: {CLIENTS} sampled streams through the "
      f"router, identical-seed replay byte-identical, stop trimmed "
      f"{st['stop_trimmed_tokens']} burst-tail tokens, sampled spec "
      f"bit-identical to no-draft (accept rate {rate:.2f})")
EOF
}

paged_smoke() {
    # child server script for the SIGTERM-drain leg
    cat > /tmp/mxtpu_paged_child.py <<'CHILD'
import sys
import numpy as np
import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu.models.gpt import GPTModel
from incubator_mxnet_tpu.serving import (GenerationEngine, ModelServer,
                                         lifecycle)

mx.random.seed(7)
net = GPTModel(vocab_size=50, units=32, hidden_size=64, num_layers=2,
               num_heads=2, max_length=128, dropout=0.0)
net.initialize(init=mx.init.Normal(0.6))
net(mx.nd.array(np.zeros((1, 2), np.int32)))
eng = GenerationEngine(net, name="gen", max_slots=8, max_len=128)
srv = ModelServer(port=0)
srv.add_model("gen", eng, warmup=True)
srv.start()
print(f"PORT {srv.port}", flush=True)
sys.exit(lifecycle.run_until_shutdown(srv))
CHILD
    JAX_PLATFORMS=cpu python - <<'EOF'
import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import telemetry
from incubator_mxnet_tpu.models.gpt import GPTModel
from incubator_mxnet_tpu.serving import GenerationEngine, ModelServer

telemetry.start()
mx.random.seed(7)
net = GPTModel(vocab_size=50, units=32, hidden_size=64, num_layers=2,
               num_heads=2, max_length=128, dropout=0.0)
net.initialize(init=mx.init.Normal(0.6))
net(mx.nd.array(np.zeros((1, 2), np.int32)))

# The cache-byte budget: 32 usable blocks x 16 tokens == 512 cached
# token-positions, which as whole max_len rows would be ROWS streams.
SYSTEM = [7] * 32                       # shared system prompt: 2 blocks
N_CLIENTS, NEW, ROWS = 16, 12, 512 // 128


def prompt_for(i):
    return SYSTEM + [1 + (i % 40), 2 + (i % 37), 3, 4]


# -- 1. the oracle: each prompt's greedy continuation by the cache-free
#       re-forward (no engine, no KV cache) ---------------------------
solo = []
for i in range(N_CLIENTS):
    p = prompt_for(i)
    out = net.generate(mx.nd.array(np.asarray([p], np.int32)),
                       max_new_tokens=NEW, use_cache=False,
                       temperature=0.0)
    solo.append([int(t) for t in
                 np.asarray(out.asnumpy()).reshape(-1)[len(p):]])

# -- 2. the server under that byte budget: 16 streaming clients, more
#       concurrent streams than rows, prefix hits on the shared prompt
paged = GenerationEngine(net, name="gen", max_slots=16, max_len=128,
                         block_size=16, num_blocks=33)
srv = ModelServer(port=0)
srv.add_model("gen", paged, warmup=True)
srv.start()
url = f"http://127.0.0.1:{srv.port}"

outs, errors = [None] * N_CLIENTS, []


def client(i):
    try:
        req = urllib.request.Request(
            url + "/v1/models/gen:generate",
            data=json.dumps({"tokens": prompt_for(i),
                             "max_new_tokens": NEW,
                             "stream": True}).encode())
        toks = []
        with urllib.request.urlopen(req, timeout=120) as r:
            for line in r:
                line = line.strip()
                if line.startswith(b"data:"):
                    d = json.loads(line.split(b":", 1)[1])
                    if "token" in d:
                        toks.append(d["token"])
        outs[i] = toks
    except Exception as e:               # noqa: BLE001
        errors.append(f"client{i}: {e!r}")


threads = [threading.Thread(target=client, args=(i,))
           for i in range(N_CLIENTS)]
[t.start() for t in threads]
[t.join(timeout=180) for t in threads]
assert not errors, f"paged_smoke: stream failures: {errors[:5]}"
for i in range(N_CLIENTS):
    assert outs[i] == solo[i], \
        f"paged_smoke: paged stream {i} != cache-free solo"

stats = json.load(urllib.request.urlopen(
    url + "/v1/models", timeout=10))["models"]["gen"]
paged_peak = stats["peak_slots_in_use"]
assert paged_peak >= 2 * ROWS, \
    f"paged_smoke: paged peak {paged_peak} vs {ROWS} max_len rows — " \
    f"expected >= 2x under the same cache-byte budget"
assert stats["prefix_cache_hits"] > 0, \
    f"paged_smoke: no prefix hits on the shared system prompt: {stats}"

prom = urllib.request.urlopen(url + "/metrics", timeout=10).read().decode()
for series in ("mxtpu_kv_blocks_in_use", "mxtpu_kv_blocks_total",
               "mxtpu_prefix_cache_hits"):
    assert series in prom, f"paged_smoke: {series} missing from /metrics"
srv.stop()

# -- 3. SIGTERM drain: a child paged server finishes in-flight streams
#       and exits 0 ----------------------------------------------------
env = dict(os.environ, MXNET_DRAIN_SECONDS="10", JAX_PLATFORMS="cpu",
           PYTHONPATH=os.getcwd())
child = subprocess.Popen([sys.executable, "/tmp/mxtpu_paged_child.py"],
                         stdout=subprocess.PIPE,
                         stderr=subprocess.DEVNULL, env=env, text=True)
line = child.stdout.readline().strip()
assert line.startswith("PORT "), f"paged_smoke: bad handshake {line!r}"
port = int(line.split()[1])
curl = f"http://127.0.0.1:{port}"

drained, derrors = [None] * 4, []


def drain_client(i):
    try:
        req = urllib.request.Request(
            curl + "/v1/models/gen:generate",
            data=json.dumps({"tokens": prompt_for(i),
                             "max_new_tokens": NEW,
                             "stream": True}).encode())
        toks = []
        with urllib.request.urlopen(req, timeout=60) as r:
            for line in r:
                line = line.strip()
                if line.startswith(b"data:"):
                    d = json.loads(line.split(b":", 1)[1])
                    if "token" in d:
                        toks.append(d["token"])
        drained[i] = toks
    except Exception as e:               # noqa: BLE001
        derrors.append(f"drain client{i}: {e!r}")


dthreads = [threading.Thread(target=drain_client, args=(i,))
            for i in range(4)]
[t.start() for t in dthreads]
time.sleep(0.5)                          # streams in flight
child.send_signal(signal.SIGTERM)
rc = child.wait(timeout=30)
[t.join(timeout=30) for t in dthreads]
assert rc == 0, f"paged_smoke: child exited {rc} on SIGTERM, expected 0"
assert not derrors, f"paged_smoke: drain dropped streams: {derrors}"
for i in range(4):
    assert drained[i] == solo[i], \
        f"paged_smoke: drained stream {i} truncated or wrong"

telemetry.stop()
print(f"paged_smoke ok: equal 512-token budget sustained "
      f"{paged_peak} concurrent streams vs {ROWS} max_len rows, "
      f"{stats['prefix_cache_hits']} prefix-cache hits on the shared "
      f"system prompt, SIGTERM drained 4 in-flight streams cleanly")
EOF
}

lifecycle_smoke() {
    local out=/tmp/mxtpu_lifecycle_smoke
    rm -rf "$out"
    # SIGTERM-under-load: zero dropped in-flight requests, readyz-first
    JAX_PLATFORMS=cpu python tools/lifecycle_smoke.py serve --out "$out"
    # hung-worker drill: watchdog + breaker recover in-process
    JAX_PLATFORMS=cpu python tools/lifecycle_smoke.py hang --out "$out"
    # preemption drill: cooperative SIGTERM checkpoint, exact resume
    JAX_PLATFORMS=cpu python tools/lifecycle_smoke.py train --out "$out"
}

router_smoke() {
    local cc=/tmp/mxtpu_router_smoke_cc
    rm -rf "$cc"
    JAX_PLATFORMS=cpu python tools/router_smoke.py all --cache-dir "$cc"
}

autoscale_smoke() {
    local cc=/tmp/mxtpu_autoscale_smoke_cc
    local logs=/tmp/mxtpu_autoscale_smoke_logs
    rm -rf "$cc" "$logs"
    JAX_PLATFORMS=cpu python tools/autoscale_smoke.py all \
        --cache-dir "$cc" --log-dir "$logs"
}

fleet_obs_smoke() {
    local cc=/tmp/mxtpu_fleet_obs_cc
    rm -rf "$cc"
    JAX_PLATFORMS=cpu python tools/fleet_obs_smoke.py all \
        --cache-dir "$cc" \
        --incident-dir /tmp/mxtpu_fleet_obs_incidents
}

device_obs_smoke() {
    local cc=/tmp/mxtpu_device_obs_cc
    rm -rf "$cc"
    JAX_PLATFORMS=cpu python tools/device_obs_smoke.py all \
        --cache-dir "$cc" \
        --profile-dir /tmp/mxtpu_device_obs_profiles
}

health_smoke() {
    local dir=/tmp/mxtpu_health_smoke
    rm -rf "$dir"
    mkdir -p "$dir/flight"
    JAX_PLATFORMS=cpu python tools/health_smoke.py golden --out "$dir"
    MXNET_HEALTH_PLANE=1 MXNET_FLIGHT_DUMP_DIR="$dir/flight" \
        JAX_PLATFORMS=cpu python tools/health_smoke.py poisoned \
        --out "$dir"
    JAX_PLATFORMS=cpu python tools/health_smoke.py check --out "$dir"
}

multichip_dryrun() {
    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python -c "import __graft_entry__ as g; g.dryrun_multichip(8)"
}

[ $# -eq 1 ] || usage
declare -F "$1" >/dev/null || usage
"$1"
