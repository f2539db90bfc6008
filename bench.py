#!/usr/bin/env python
"""Benchmark: BERT-large pretraining samples/sec/chip + MFU, plus the
second judged metric's artifacts: ResNet-50 throughput and a DP-scaling
dryrun (BASELINE.md metric 2 — scaling efficiency — as far as a single
chip + virtual CPU mesh allow).

Prints ONE JSON line.  The primary record is the BERT anchor; "resnet50"
and "dp_scaling" sub-records carry the conv-net throughput and the 1→8
virtual-device weak-scaling efficiency.  Every record names the device jax
gave it, and nothing is substituted for a device that is missing or
unknown: a sub-bench that fails leaves its ``error`` in the record and the
process exits 1; an anchor that fails exits 1 with nothing else run.

Judged metric (BASELINE.md): BERT pretraining samples/sec/chip, north star
>= 35% MFU.  Anchor: published GluonNLP BERT-large phase-1 throughput
~O(100) seq/sec on 8x V100 => 12.5 samples/sec/chip.  NOTE the anchor is a
2019-era fp32 V100 number; vs_baseline is a cross-era reference point —
MFU is the honest efficiency metric.  The BERT step trains the FULL
pretrain objective (MLM + NSP heads), matching the anchor workload.
"""
import json
import os
import subprocess
import sys
import time

import numpy as np

BASELINE_SAMPLES_PER_SEC_PER_CHIP = 12.5
BASELINE_ANCHOR = "GluonNLP BERT-large phase-1, 8xV100 fp32 (2019 era)"

# ResNet-50 v1 224x224 forward FLOPs per image (mul+add), the standard
# 4.1 GFLOPs accounting; training ~= fwd + 2x bwd = 3x forward.
RESNET50_FWD_FLOPS = 4.1e9
# ResNet-18 v1 224x224 forward FLOPs per image (1.8 GFLOPs standard
# accounting); conv FLOPs scale with spatial area, so the CPU smoke at
# H x H uses 1.8e9 * (H/224)^2.
RESNET18_FWD_FLOPS_224 = 1.8e9


def _peak_flops(kind):
    """Per-chip bf16 peak for a jax ``device_kind`` — the ONE table lives in
    ``telemetry.TPU_PEAK_FLOPS``; a kind it does not list raises."""
    from incubator_mxnet_tpu import telemetry
    return telemetry.tpu_peak_flops(kind)


def _cpu_peak_flops():
    """Host peak-FLOP/s estimate (telemetry's cores x clock x SIMD-width
    model) so CPU smoke records report a finite mfu instead of null.  An
    order-of-magnitude denominator: comparable across runs on the same
    box, not across machines."""
    try:
        from incubator_mxnet_tpu import telemetry
        return telemetry.cpu_peak_flops()
    except Exception:
        return None


def _telemetry_snapshot():
    """Telemetry snapshot when MXNET_TELEMETRY is on, else None.  Env is
    checked first so the accel parent path never imports the framework
    (and with it a jax client) just to discover telemetry is off."""
    if not any(os.environ.get(k) not in (None, "", "0")
               for k in ("MXNET_TELEMETRY", "MXTPU_TELEMETRY")):
        return None
    try:
        from incubator_mxnet_tpu import telemetry
        if telemetry.enabled():
            return telemetry.snapshot()
    except Exception:
        pass
    return None


def _device():
    """``(platform, device_kind)`` of jax's default device, asked of a
    CHILD: on an accelerator this process must never open the chip its
    sub-benches need (one process per chip).  No device, no benchmark."""
    code = ("import jax; d = jax.devices()[0]; "
            "print(d.platform, '|', d.device_kind)")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=600)
    if out.returncode != 0 or not out.stdout.strip():
        tail = (out.stderr or "").strip().splitlines()
        sys.exit("bench.py: jax found no device: "
                 + (tail[-1][:300] if tail else f"rc={out.returncode}"))
    platform, _, kind = out.stdout.strip().splitlines()[-1].partition("|")
    return platform.strip(), kind.strip()


def _model_flops_per_step(cfg, batch, seqlen):
    """Training FLOPs per step: 6*N*tokens for the param matmuls
    (fwd 2N + bwd 4N per token) + 12*L*T^2*d per sequence for attention
    scores/context (fwd 4*T^2*d, x3 for bwd), + the vocab projection.
    (The NSP head adds only 6*2*d per sequence — negligible, excluded.)"""
    d, L, ffn, V = (cfg["units"], cfg["num_layers"], cfg["hidden_size"],
                    cfg["vocab_size"])
    n_block = L * (4 * d * d + 2 * d * ffn)   # qkv+out proj + 2 ffn mats
    tokens = batch * seqlen
    matmul = 6.0 * n_block * tokens
    attn = 12.0 * L * seqlen * seqlen * d * batch
    head = 6.0 * d * V * tokens               # tied-embedding MLM decoder
    return matmul + attn + head


def _bench_bert(on_accel, kind, dev, seq_len=None, batch_ladder=None,
                steps=None):
    """One BERT-pretrain throughput measurement.  Defaults are the phase-1
    anchor (seq 128); pass seq_len=512 + a smaller ladder for the phase-2
    config."""
    import jax
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import parallel
    from incubator_mxnet_tpu.models import bert as bert_mod

    if on_accel:
        # the anchor config itself: BERT-large
        cfg = dict(vocab_size=30522, units=1024, hidden_size=4096,
                   num_layers=24, num_heads=16, max_length=512)
        T = seq_len or 128
        # 128 first: B=64 fit WITHOUT remat in the r05 window (HBM
        # headroom observed), so a bigger batch may lift MFU; the OOM
        # ladder (remat retry, then halve) makes the attempt safe
        batch_ladder = batch_ladder or [128, 64, 32, 16, 8]
        steps, warmup = steps or 20, 3
    else:
        cfg = dict(vocab_size=1024, units=128, hidden_size=256,
                   num_layers=2, num_heads=2, max_length=128)
        T = 64
        batch_ladder = [4]
        steps, warmup = 5, 2

    mx.random.seed(0)
    net = bert_mod.BERTForPretrain(
        bert_mod.BERTModel(dropout=0.0, **cfg),
        vocab_size=cfg["vocab_size"])
    net.initialize(init=mx.init.Normal(0.02))
    if on_accel:
        net.cast("bfloat16")  # bf16 compute — the MXU-native dtype

    V = cfg["vocab_size"]
    rng = np.random.default_rng(0)
    mesh = parallel.make_mesh({"data": 1}, devices=[dev])

    def _attempt(B):
        """One measured run at batch size B.  Lives in its own frame so
        an OOM unwinds and releases the trainer/opt-state/arrays before
        the ladder retries at a smaller B."""
        ids = mx.nd.array(rng.integers(0, V, (B, T)), dtype=np.int32)
        types = mx.nd.array(np.zeros((B, T)), dtype=np.int32)
        with mx.autograd.pause():
            net(ids, types)  # settle deferred shapes
        trainer = parallel.SPMDTrainer(
            net, bert_mod.BERTPretrainLoss(V),
            "adam", {"learning_rate": 1e-4}, mesh=mesh, data_axis="data")
        x_ids = rng.integers(0, V, (B, T)).astype(np.int32)
        x_types = np.zeros((B, T), np.int32)
        # packed labels: T MLM targets + 1 NSP class per sequence
        labels = np.concatenate(
            [rng.integers(0, V, (B, T)), rng.integers(0, 2, (B, 1))],
            axis=1).astype(np.float32)
        for _ in range(warmup):
            loss = trainer.step(x_ids, x_types, labels)
        jax.block_until_ready(loss)
        t0 = time.perf_counter()
        for _ in range(steps):
            loss = trainer.step(x_ids, x_types, labels)
        jax.block_until_ready(loss)
        return steps * B / (time.perf_counter() - t0)

    # ladder: on OOM, first retry the SAME batch with layer remat
    # (MXNET_BACKWARD_DO_MIRROR — activations recomputed in the
    # backward), since a remat'd large batch usually beats a saved-
    # activation small one on MFU; only then step the batch down
    samples_per_sec, B_used, remat_used = None, None, False
    attempts = [(B, m) for B in batch_ladder
                for m in ((False, True) if on_accel else (False,))]
    for i, (B, mirror) in enumerate(attempts):
        try:
            if mirror:
                os.environ["MXNET_BACKWARD_DO_MIRROR"] = "1"
            else:
                os.environ.pop("MXNET_BACKWARD_DO_MIRROR", None)
            samples_per_sec, B_used, remat_used = _attempt(B), B, mirror
            break
        except Exception as e:  # OOM on this config -> next rung
            if "RESOURCE_EXHAUSTED" not in str(e) \
                    or i == len(attempts) - 1:
                raise
            import gc
            gc.collect()
        finally:
            os.environ.pop("MXNET_BACKWARD_DO_MIRROR", None)
    assert samples_per_sec is not None  # loop breaks or re-raises

    flops = _model_flops_per_step(cfg, B_used, T)
    peak = _peak_flops(kind) if on_accel else _cpu_peak_flops()
    mfu = (samples_per_sec / B_used) * flops / peak if peak else None
    return samples_per_sec, B_used, T, mfu, remat_used


def _bench_resnet50(on_accel, kind, dev):
    """ResNet-50 v1 ImageNet-shape training throughput (reference:
    example/image-classification/benchmark_score.py).  CPU fallback runs a
    tiny conv net purely to prove the path."""
    import jax
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import gluon, parallel
    from incubator_mxnet_tpu.gluon.model_zoo import vision as zoo

    if on_accel:
        net = zoo.resnet50_v1(classes=1000)
        H = 224
        batch_ladder = [64, 32, 16]
        steps, warmup = 10, 2
        flops_per_img = 3.0 * RESNET50_FWD_FLOPS
    else:
        net = zoo.resnet18_v1(classes=10)
        H = 32
        batch_ladder = [4]
        steps, warmup = 3, 1
        flops_per_img = 3.0 * RESNET18_FWD_FLOPS_224 * (H / 224.0) ** 2

    mx.random.seed(0)
    net.initialize(init=mx.init.Xavier())
    if on_accel:
        net.cast("bfloat16")
    rng = np.random.default_rng(0)
    mesh = parallel.make_mesh({"data": 1}, devices=[dev])

    class _CE(gluon.HybridBlock):
        def __init__(self, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.ce = gluon.loss.SoftmaxCrossEntropyLoss()

        def hybrid_forward(self, F, scores, labels):
            return self.ce(scores, labels).mean()

    def _attempt(B):
        with mx.autograd.pause():
            net(mx.nd.array(np.zeros((2, 3, H, H), np.float32)))
        trainer = parallel.SPMDTrainer(
            net, _CE(), "sgd",
            {"learning_rate": 0.1, "momentum": 0.9}, mesh=mesh,
            data_axis="data")
        x = rng.standard_normal((B, 3, H, H)).astype(np.float32)
        y = rng.integers(0, 10, (B,)).astype(np.float32)
        for _ in range(warmup):
            loss = trainer.step(x, y)
        jax.block_until_ready(loss)
        t0 = time.perf_counter()
        for _ in range(steps):
            loss = trainer.step(x, y)
        jax.block_until_ready(loss)
        return steps * B / (time.perf_counter() - t0)

    imgs_per_sec, B_used = None, None
    for B in batch_ladder:
        try:
            imgs_per_sec, B_used = _attempt(B), B
            break
        except Exception as e:
            if "RESOURCE_EXHAUSTED" not in str(e) or B == batch_ladder[-1]:
                raise
            import gc
            gc.collect()

    peak = _peak_flops(kind) if on_accel else _cpu_peak_flops()
    mfu = (imgs_per_sec * flops_per_img / peak
           if (peak and flops_per_img) else None)
    return {
        "metric": ("resnet50_v1_train_imgs_per_sec_per_chip" if on_accel
                   else "resnet18_cpu_smoke_imgs_per_sec"),
        "value": round(imgs_per_sec, 2),
        "unit": "imgs/s",
        "mfu": round(mfu, 4) if mfu is not None else None,
        "batch_size": B_used,
        "image_size": H,
        "dtype": "bfloat16" if on_accel else "float32",
    }


def _int8_ab_record(build, x, B, steps, warmup, rate_key):
    """Shared int8-vs-fp32 A/B harness: time a seeded fp32 net and its
    quantize_net'd twin on the same batch, record throughput + max rel
    deviation (a mis-calibrated int8 net must never masquerade as a
    valid speedup).  ``build`` makes a FRESH seeded net each call:
    quantize_net rewrites IN PLACE, and calibration hooks only fire on
    a net that has never compiled a _CachedGraph for the batch's key."""
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.contrib import quantization as q

    def rate(f):
        for _ in range(warmup):
            out = f(x)
        out.wait_to_read()
        t0 = time.perf_counter()
        for _ in range(steps):
            out = f(x)
        out.wait_to_read()
        return steps * B / (time.perf_counter() - t0)

    net = build()
    with mx.autograd.pause():
        ref_out = net(x).asnumpy()
    net.hybridize()
    fp32 = rate(net)

    qnet = q.quantize_net(build(), calib_data=[x], calib_mode="naive")
    with mx.autograd.pause():
        q_out = qnet(x).asnumpy()
    qnet.hybridize()
    int8 = rate(qnet)
    rel = float(np.max(np.abs(q_out - ref_out))
                / (np.max(np.abs(ref_out)) + 1e-9))
    return {f"fp32_{rate_key}": round(fp32, 1),
            f"int8_{rate_key}": round(int8, 1),
            "int8_speedup": round(int8 / fp32, 3),
            "int8_vs_fp32_max_rel_dev": round(rel, 5),
            "batch_size": B}


def _bench_int8(on_accel, kind, dev):
    """int8 vs fp32 inference throughput on a matmul-heavy MLP — the
    fork's headline focus area (reference: docs faq/perf.md MKL-DNN
    section, int8 ~3-4x fp32 on CPU; here the question is what XLA's
    int8 matmul path yields on the MXU)."""
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.gluon import nn

    D, B = (4096, 256) if on_accel else (256, 32)
    steps, warmup = (20, 3) if on_accel else (5, 2)

    def build():
        mx.random.seed(0)
        n = nn.HybridSequential()
        for _ in range(3):
            n.add(nn.Dense(D, in_units=D, activation="relu"))
        n.initialize(init=mx.init.Xavier())
        return n

    x = mx.nd.array(np.random.default_rng(0).standard_normal(
        (B, D)).astype(np.float32))
    rec = _int8_ab_record(build, x, B, steps, warmup, "samples_per_sec")
    rec["layers"] = "3x Dense(4096)" if on_accel else "3x Dense(256)"
    return rec


def _bench_int8_conv(on_accel, kind, dev):
    """int8 vs fp32 quantized-CNN inference — the claim the fork is
    actually famous for (reference: example/quantization/README.md,
    int8 resnet ~3-4x fp32 via oneDNN on CPU; here: XLA's int8
    convolution path, MXU when on accelerator).  Full resnet18_v1 at
    224^2 through contrib.quantization.quantize_net (QuantizedConv2D +
    QuantizedDense, BatchNorm/pooling stay fp32 like the reference's
    quantized graph)."""
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.gluon.model_zoo import vision as zoo

    H, B = (224, 32) if on_accel else (112, 4)
    steps, warmup = (20, 3) if on_accel else (8, 2)

    def build():
        mx.random.seed(0)
        n = zoo.resnet18_v1(classes=1000)
        n.initialize(init=mx.init.Xavier())
        return n

    x = mx.nd.array(np.random.default_rng(0).standard_normal(
        (B, 3, H, H)).astype(np.float32))
    rec = _int8_ab_record(build, x, B, steps, warmup, "imgs_per_sec")
    rec["model"] = "resnet18_v1 (QuantizedConv2D path)"
    rec["image_size"] = H
    # regression floor: the quantized conv path must stay within 20% of
    # fp32 (it was 17x slower before the one-compiled-call rewrite)
    rec["speedup_floor"] = 0.8
    rec["floor_ok"] = bool(rec["int8_speedup"] >= 0.8)
    if not rec["floor_ok"]:
        rec["regression"] = (
            f"int8 conv speedup {rec['int8_speedup']} < floor 0.8")
    return rec


def _bench_optim(on_accel, kind, dev):
    """Fused whole-tree optimizer step vs the per-param update loop:
    same net, same grads, adam; isolates the update cost by re-stepping
    on held grads (ignore_stale_grad) so forward/backward stays out of
    the timed region.  Records update throughput in param elements/sec
    and the dispatch count per step (1 fused jit call vs one call per
    parameter) — the dispatch reduction is the whole point."""
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import autograd as ag
    from incubator_mxnet_tpu import telemetry
    from incubator_mxnet_tpu.gluon import Trainer, nn

    D, L, B = (1024, 12, 32) if on_accel else (256, 8, 8)
    steps, warmup = (50, 5) if on_accel else (20, 3)

    def build():
        mx.random.seed(0)
        net = nn.HybridSequential()
        for _ in range(L):
            net.add(nn.Dense(D, in_units=D, activation="relu"))
        net.initialize(init=mx.init.Xavier())
        net.hybridize()
        return net

    x = mx.nd.array(np.random.default_rng(0).standard_normal(
        (B, D)).astype(np.float32))
    telemetry.start()

    def run(fused, zero1=False):
        net = build()
        tr = Trainer(net.collect_params(), "adam",
                     {"learning_rate": 1e-3}, fused=fused, zero1=zero1)
        params = list(net.collect_params().values())
        n_elems = sum(int(np.prod(p.shape)) for p in params)
        with ag.record():
            loss = (net(x) ** 2).mean()
        loss.backward()
        for _ in range(warmup):
            tr.step(B, ignore_stale_grad=True)
        mx.nd.waitall()
        t0 = time.perf_counter()
        for _ in range(steps):
            tr.step(B, ignore_stale_grad=True)
        mx.nd.waitall()
        rate = steps / (time.perf_counter() - t0)
        g = telemetry.registry.get("mxtpu_optimizer_dispatches_per_step")
        dispatches = int(sum(g._values.values())) if g is not None \
            and g._values else len(params)
        flat = telemetry.counters_flat()
        return (rate, n_elems, dispatches, len(params),
                flat.get("mxtpu_optimizer_state_bytes", 0),
                flat.get("mxtpu_zero1_allgather_bytes", 0))

    loop_rate, n_elems, loop_disp, n_tensors, _, _ = run(fused=False)
    fused_rate, _, fused_disp, _, full_state_bytes, _ = run(fused=True)
    rec = {
        "optimizer": "adam",
        "param_tensors": n_tensors,
        "param_elements": n_elems,
        "fused_updates_per_sec": round(fused_rate, 1),
        "loop_updates_per_sec": round(loop_rate, 1),
        "fused_param_elements_per_sec": round(fused_rate * n_elems),
        "loop_param_elements_per_sec": round(loop_rate * n_elems),
        "fused_dispatches_per_step": fused_disp,
        "loop_dispatches_per_step": loop_disp,
        "dispatch_reduction": round(loop_disp / max(fused_disp, 1), 1),
        "step_speedup": round(fused_rate / loop_rate, 3),
    }
    # ZeRO-1 weight-update sharding: same update measured with the flat
    # state + update partitioned across the data axis.  Needs >1 local
    # device to mean anything; on a single-device run the measurement
    # happens in a subprocess with 8 virtual CPU devices instead.
    import jax
    if len(jax.local_devices()) > 1:
        z_rate, _, z_disp, _, z_bytes, z_ag = run(fused=True, zero1=True)
        ratio = z_bytes / max(full_state_bytes, 1)
        rec["zero1"] = {
            "devices": len(jax.local_devices()),
            "updates_per_sec": round(z_rate, 1),
            "param_elements_per_sec": round(z_rate * n_elems),
            "dispatches_per_step": z_disp,
            "state_bytes_per_replica": int(z_bytes),
            "state_bytes_replicated": int(full_state_bytes),
            "state_ratio": round(ratio, 4),
            "allgather_bytes_per_step": int(z_ag),
            "floor": "state_ratio <= 0.25",
            "floor_ok": bool(ratio <= 0.25),
        }
    else:
        rec["zero1"] = _zero1_dryrun()
    return rec


def _bench_serve(on_accel, kind, dev):
    """Dynamic batching vs the unbatched per-request path, measured the
    way a server sees it: N closed-loop client threads each firing
    batch-1 requests.  The unbatched baseline drives the SAME bucketed
    engine directly (one compiled dispatch per request — what a naive
    server does); the batched run pushes through a DynamicBatcher that
    coalesces the concurrent stream into one dispatch per group.  The
    per-request outputs are asserted identical between the two paths
    (fp tolerance), and the speedup floor (>= 2x at >= 16 clients on
    the CPU config) is the acceptance bar of docs/serving.md."""
    import threading

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import telemetry
    from incubator_mxnet_tpu.gluon import nn
    from incubator_mxnet_tpu.serving import DynamicBatcher, InferenceEngine
    from incubator_mxnet_tpu.serving import metrics as smetrics

    D, L = (1024, 6) if on_accel else (512, 4)
    clients = 16
    reqs_per_client = 48 if on_accel else 24
    max_delay_ms = 2.0

    telemetry.start()
    mx.random.seed(0)
    net = nn.HybridSequential()
    for _ in range(L):
        net.add(nn.Dense(D, in_units=D, activation="relu"))
    net.initialize(init=mx.init.Xavier())
    engine = InferenceEngine.from_block(
        net, [(D,)], name="bench-serve", max_batch_size=clients)
    engine.warmup()

    rng = np.random.default_rng(0)
    xs = [rng.standard_normal((1, D)).astype(np.float32)
          for _ in range(clients)]
    refs = [np.asarray(engine.predict([x])[0]) for x in xs]

    def drive(fire):
        """closed loop: each client fires its next request the moment
        the previous one returns; per-request latencies in seconds."""
        lat = [[] for _ in range(clients)]
        errs = []

        def client(i):
            try:
                for _ in range(reqs_per_client):
                    t0 = time.perf_counter()
                    out = fire(xs[i])
                    lat[i].append(time.perf_counter() - t0)
                    if not np.allclose(np.asarray(out), refs[i],
                                       rtol=1e-4, atol=1e-5):
                        errs.append(f"client {i}: output mismatch")
                        return
            except Exception as e:
                errs.append(f"client {i}: {e!r}")
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(clients)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        if errs:
            raise RuntimeError("; ".join(errs[:3]))
        flat = sorted(s for per in lat for s in per)
        total = len(flat)
        return {"requests_per_sec": round(total / wall, 1),
                "p50_ms": round(flat[total // 2] * 1e3, 3),
                "p99_ms": round(flat[min(total - 1,
                                         int(total * 0.99))] * 1e3, 3),
                "wall_seconds": round(wall, 3)}

    # unbatched baseline: per-request compiled dispatch, warmed
    unbatched = drive(lambda x: engine.predict([x])[0])

    batcher = DynamicBatcher(engine, max_batch_size=clients,
                             max_delay_ms=max_delay_ms,
                             name="bench-serve")
    req0 = smetrics.REQUESTS.value
    bat0 = smetrics.BATCHES.value
    try:
        batched = drive(lambda x: batcher.submit([x])[0])
    finally:
        batcher.close()
    n_req = smetrics.REQUESTS.value - req0
    n_bat = max(1.0, smetrics.BATCHES.value - bat0)

    speedup = round(batched["requests_per_sec"]
                    / max(unbatched["requests_per_sec"], 1e-9), 3)
    # device-plane corroboration: the dispatch ledger's per-site counts
    # and wall-time percentiles for this engine, plus the per-owner
    # memory attribution (params:bench-serve registered at build)
    from incubator_mxnet_tpu import telemetry_device
    ledger = {
        site: {"dispatches": e["dispatches"],
               "seconds_p50": e["seconds_p50"],
               "seconds_p99": e["seconds_p99"],
               "compiled": e["compiled"]}
        for site, e in telemetry.dispatch_ledger(
            prefix="serving:bench-serve").items()}
    mem = telemetry_device.sample()
    # steady-state SLO view of the batched run (every submit() outcome
    # landed in the rolling window; serving/slo.py)
    from incubator_mxnet_tpu.serving import slo as _slo
    snap = _slo.tracker.model("bench-serve").snapshot()
    return {
        "model": f"mlp_{L}x{D}",
        "clients": clients,
        "requests": clients * reqs_per_client,
        "max_delay_ms": max_delay_ms,
        "buckets": list(engine.buckets),
        "compiled_programs": engine.compiled_programs(),
        "unbatched": unbatched,
        "batched": batched,
        "batches_dispatched": int(n_bat),
        "mean_batch_size": round(n_req / n_bat, 2),
        "dispatch_ledger": ledger,
        "device_memory": {
            "owners": {k: int(v) for k, v in mem["owners"].items()},
            "live_array_bytes": int(mem["live_array_bytes"]),
            "unattributed_bytes": int(mem["unattributed_bytes"]),
        },
        "speedup": speedup,
        "speedup_floor": 2.0,
        "floor_ok": bool(speedup >= 2.0),
        "slo": {
            "availability": round(snap["availability"], 6),
            "p99_seconds": snap["p99_seconds"],
            "burn_rate": round(snap["burn_rate"], 4),
            "error_budget_remaining":
                round(snap["error_budget_remaining"], 4),
            "window": snap["window"],
        },
    }


def _bench_generate(on_accel, kind, dev):
    """Continuous-batching generation vs the naive no-KV-cache server,
    measured open-loop: 16 clients submit one streamed generation
    request each on a fixed arrival schedule (arrivals do NOT wait for
    completions), so late requests join mid-flight while earlier ones
    are still decoding.  The naive baseline is the strongest honest
    version of a cacheless server: for EVERY token it re-runs prefill
    over the whole growing context through the SAME warmed, bucketed,
    compiled programs — one dispatch per token per request, O(n^2)
    attention work.  Both paths are greedy over the same engine, so the
    per-request token sequences are asserted IDENTICAL; the >= 3x
    tokens/sec floor on the CPU config is the acceptance bar of
    docs/serving.md.

    One paged-KV axis rides along (docs/serving.md "Paged KV cache"):
    ``prefix_prefill_savings`` measures the prefill FLOPs drop (XLA_COST
    plane) when a repeated prompt hits the prefix cache and only its
    suffix is prefilled, floor >= 1.3x.

    The second axis, ``speculative_decoding``, measures draft-verify
    decode: a 1-layer draft proposes k=4 tokens and the target scores
    all k+1 in one fixed-shape verify dispatch.  Greedy acceptance is
    exact (sequences asserted identical to plain decode); recorded are
    ``accepted_tokens_per_dispatch`` (floor > 1.0) and the spec-vs-plain
    per-stream tokens/sec speedup, floor >= 1.3x.  As of the decode-scan
    PR the draft's k proposal decodes run as ONE scanned burst dispatch
    (2 dispatches per spec round instead of k+1), so this axis
    re-records against the PR 14 host-loop-draft record (2.44x on CPU).
    The sampling plane re-records it once more at temperature 0.7:
    Gumbel-coupled stochastic acceptance is asserted bit-identical to
    the no-draft sampled run over the same key stream, and the sampled
    accept rate is recorded next to greedy's (accept rate vs
    temperature).

    The third axis, ``decode_scan``, measures the whole-decode-loop
    capture (docs/serving.md "Multi-token decode bursts"): the same
    16-client steady-state load through the same net with scan_steps=0
    (one dispatch per token) vs the default k-step ``lax.scan`` burst
    (one dispatch per up-to-k tokens, in-program termination).  Outputs
    are asserted bit-identical; recorded are tokens/sec for both legs
    plus each batcher's ``dispatches_per_token``, with floors
    speedup >= 1.2x and burst dispatches_per_token <= 0.2 (the
    docs/serving.md dispatch-economy bar for k=8).

    The fourth axis, ``sampling``, runs the same steady-state load
    greedy vs stochastically sampled (temperature 0.8, top-p 0.9,
    fixed per-request seeds).  Sampling operands are traced inputs of
    the SAME compiled programs, so the recorded ``overhead_pct`` floor
    is <= 10%; the fixed seeds double as a replay-contract assertion
    (identical outputs across repeats)."""
    import threading

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import telemetry
    from incubator_mxnet_tpu.models.gpt import GPTModel
    from incubator_mxnet_tpu.serving import ContinuousBatcher, \
        GenerationEngine, SamplingParams

    clients = 16
    if on_accel:
        V, U, H, L, heads, max_len, new_tokens = \
            512, 256, 1024, 4, 4, 256, 48
    else:
        V, U, H, L, heads, max_len, new_tokens = \
            128, 64, 128, 2, 2, 128, 32

    telemetry.start()
    mx.random.seed(0)
    net = GPTModel(vocab_size=V, units=U, hidden_size=H, num_layers=L,
                   num_heads=heads, max_length=max_len, dropout=0.0)
    net.initialize(init=mx.init.Normal(0.1))
    net(mx.nd.array(np.zeros((1, 2), np.int32)))
    # prefix_cache off HERE so the naive baseline stays honest: with
    # sharing on, its repeated full-context prefills would hit the
    # prefix cache and stop being the cacheless O(n^2) reference
    engine = GenerationEngine(net, name="bench-gen", max_slots=clients,
                              max_len=max_len, prefix_cache=False)
    engine.warmup()

    rng = np.random.default_rng(0)
    prompts = [[int(t) for t in
                rng.integers(1, V, size=int(rng.integers(4, 12)))]
               for _ in range(clients)]

    def stats(per_token, wall):
        flat = sorted(s for per in per_token for s in per)
        total = len(flat)
        return {"tokens_per_sec": round(total / wall, 1),
                "token_p50_ms": round(flat[total // 2] * 1e3, 3),
                "token_p99_ms": round(flat[min(total - 1,
                                               int(total * 0.99))]
                                      * 1e3, 3),
                "tokens": total,
                "wall_seconds": round(wall, 3)}

    # -- naive baseline (dispatches are serialized on the one device no
    # matter how many client threads fire them, so a sequential drive
    # measures the same wall a threaded naive server would) -----------
    naive_out = []
    naive_lat = []
    t0 = time.perf_counter()
    for toks in prompts:
        ctx = list(toks)
        out, lat = [], []
        budget = min(new_tokens, engine.max_len - len(toks))
        while len(out) < budget:
            t1 = time.perf_counter()
            nxt = int(engine.prefill(np.asarray(ctx, np.int32), 0))
            lat.append(time.perf_counter() - t1)
            out.append(nxt)
            ctx.append(nxt)
        naive_out.append(out)
        naive_lat.append(lat)
    naive = stats(naive_lat, time.perf_counter() - t0)
    engine.reset()

    # -- continuous batching: one decode dispatch per step advances
    # every live slot; arrivals join between steps --------------------
    batcher = ContinuousBatcher(engine, name="bench-gen")
    cont_out = [None] * clients
    cont_lat = [None] * clients
    errs = []

    def client(i):
        try:
            req = batcher.submit_async(prompts[i],
                                       max_new_tokens=new_tokens)
            toks, lat = [], []
            prev = time.perf_counter()
            for tok in req.stream(timeout=120.0):
                now = time.perf_counter()
                lat.append(now - prev)
                prev = now
                toks.append(int(tok))
            cont_out[i] = toks
            cont_lat[i] = lat
        except Exception as e:
            errs.append(f"client {i}: {e!r}")

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(clients)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
        time.sleep(0.005)       # open-loop arrival schedule
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    bstats = batcher.stats()
    batcher.close()
    if errs:
        raise RuntimeError("; ".join(errs[:3]))
    continuous = stats(cont_lat, wall)

    mismatch = [i for i in range(clients) if cont_out[i] != naive_out[i]]
    if mismatch:
        raise RuntimeError(
            f"continuous != naive token sequences for clients "
            f"{mismatch[:4]} (greedy decode must be exact)")

    speedup = round(continuous["tokens_per_sec"]
                    / max(naive["tokens_per_sec"], 1e-9), 3)

    system = [int(t) for t in rng.integers(1, V, size=32)]

    # -- prefix-cache prefill savings: the same prompt twice; the hit
    # run prefills only the suffix bucket, measured on the XLA_COST
    # plane (analytical FLOPs of each dispatched prefill program) -----
    pp_eng = GenerationEngine(net, name="bench-prefix", max_slots=4,
                              max_len=max_len)
    pp_prompt = system + [3, 1, 4]
    cost_events = []

    def on_cost(**kw):
        cost_events.append(kw)

    def prefill_flops():
        return sum(e["flops"] for e in cost_events
                   if "prefill" in e["where"])

    telemetry.XLA_COST.subscribe(on_cost)
    try:
        cold_out = pp_eng.generate(pp_prompt, max_new_tokens=4)
        cold_flops = prefill_flops()
        cost_events.clear()
        hit_out = pp_eng.generate(pp_prompt, max_new_tokens=4)
        hit_flops = prefill_flops()
    finally:
        telemetry.XLA_COST.unsubscribe(on_cost)
    if hit_out != cold_out:
        raise RuntimeError("prefix-hit generation != cold generation")
    savings = round(cold_flops / max(hit_flops, 1e-9), 3)
    prefix_axis = {
        "prompt_tokens": len(pp_prompt),
        "shared_prefix_tokens": (len(pp_prompt) // 16) * 16,
        "cold_prefill_gflops": round(cold_flops / 1e9, 5),
        "hit_prefill_gflops": round(hit_flops / 1e9, 5),
        "prefix_cache_hits": pp_eng.pool.hits,
        "savings": savings,
        "floor": "savings >= 1.3",
        "floor_ok": bool(savings >= 1.3),
    }

    # -- speculative decoding: a small draft proposes k tokens, the
    # target verifies all k+1 positions in ONE dispatch of the k-wide
    # decode program.  Greedy acceptance is exact, so the per-stream
    # token sequence is asserted identical to plain decode; the win is
    # tokens per TARGET dispatch > 1 whenever the draft agrees --------
    spec_k = 4
    if on_accel:
        sV, sU, sH, sL, sheads, s_len, s_new = \
            512, 256, 1024, 4, 4, 256, 64
        dU, dH, dL, dheads = 64, 128, 1, 2
    else:
        sV, sU, sH, sL, sheads, s_len, s_new = \
            128, 256, 1024, 4, 4, 128, 48
        dU, dH, dL, dheads = 32, 64, 1, 2
    mx.random.seed(7)
    tnet = GPTModel(vocab_size=sV, units=sU, hidden_size=sH,
                    num_layers=sL, num_heads=sheads, max_length=s_len,
                    dropout=0.0)
    tnet.initialize(init=mx.init.Normal(0.02))
    tnet(mx.nd.array(np.zeros((1, 2), np.int32)))
    mx.random.seed(11)
    dnet = GPTModel(vocab_size=sV, units=dU, hidden_size=dH,
                    num_layers=dL, num_heads=dheads, max_length=s_len,
                    dropout=0.0)
    dnet.initialize(init=mx.init.Normal(0.02))
    dnet(mx.nd.array(np.zeros((1, 2), np.int32)))
    spec_eng = GenerationEngine(tnet, name="bench-spec", max_slots=1,
                                max_len=s_len)
    draft_eng = GenerationEngine(dnet, name="bench-spec-draft",
                                 max_slots=1, max_len=s_len)
    spec_eng.attach_draft(draft_eng, spec_k=spec_k)
    spec_eng.warmup()

    spec_calls = {"n": 0, "accepted": 0}
    _orig_spec_step = spec_eng.spec_step

    def _counting_spec_step(last, pos):
        spec_calls["n"] += 1
        out = _orig_spec_step(last, pos)
        spec_calls["accepted"] += int(out[1][0])
        return out

    spec_eng.spec_step = _counting_spec_step
    spec_prompt = [int(t) for t in rng.integers(1, sV, size=8)]
    # one untimed pass each to settle the prefix cache and jit caches
    plain_seq = spec_eng.generate(spec_prompt, max_new_tokens=s_new,
                                  speculative=False)
    spec_seq = spec_eng.generate(spec_prompt, max_new_tokens=s_new,
                                 speculative=True)
    if spec_seq != plain_seq:
        raise RuntimeError(
            "speculative != plain token sequence (greedy draft-verify "
            "acceptance must be exact)")
    reps = 3
    t0 = time.perf_counter()
    for _ in range(reps):
        plain_seq = spec_eng.generate(spec_prompt, max_new_tokens=s_new,
                                      speculative=False)
    plain_dt = (time.perf_counter() - t0) / reps
    spec_calls["n"] = spec_calls["accepted"] = 0
    t0 = time.perf_counter()
    for _ in range(reps):
        spec_seq = spec_eng.generate(spec_prompt, max_new_tokens=s_new,
                                     speculative=True)
    spec_dt = (time.perf_counter() - t0) / reps
    if spec_seq != plain_seq:
        raise RuntimeError(
            "speculative != plain token sequence (greedy draft-verify "
            "acceptance must be exact)")
    # tokens per verify dispatch: everything after the prefill token
    # came out of a spec_step burst
    tpd = (len(spec_seq) - 1) * reps / max(spec_calls["n"], 1)
    greedy_accept = spec_calls["accepted"] / max(
        spec_calls["n"] * spec_k, 1)
    spec_speedup = round(plain_dt / max(spec_dt, 1e-9), 3)

    # stochastic spec at temperature 0.7: Gumbel-coupled acceptance
    # keys every draw off (seed, position), so the spec run emits the
    # SAME tokens as the no-draft sampled run at any accept rate --
    # asserted bit-identical, and the accept rate recorded next to
    # greedy's gives the accept-rate-vs-temperature picture
    samp = SamplingParams(temperature=0.7, top_p=0.95, seed=4242)
    samp_plain = spec_eng.generate(spec_prompt, max_new_tokens=s_new,
                                   speculative=False, sampling=samp)
    spec_calls["n"] = spec_calls["accepted"] = 0
    samp_spec = spec_eng.generate(spec_prompt, max_new_tokens=s_new,
                                  speculative=True, sampling=samp)
    if samp_spec != samp_plain:
        raise RuntimeError(
            "sampled speculative != no-draft sampled sequence (Gumbel-"
            "coupled acceptance must preserve the keyed sample stream)")
    samp_accept = spec_calls["accepted"] / max(
        spec_calls["n"] * spec_k, 1)
    spec_axis = {
        "spec_k": spec_k,
        # attach_draft sizes the draft's scanned proposal burst to
        # spec_k, so each spec round is 2 dispatches (draft burst +
        # verify) instead of the k+1 the PR 14 record (2.44x) paid
        "draft_scan_steps": int(draft_eng.scan_steps),
        "target_model": f"gpt_{sL}L_{sU}u_{sheads}h",
        "draft_model": f"gpt_{dL}L_{dU}u_{dheads}h",
        "new_tokens": len(spec_seq),
        "plain_tokens_per_sec": round(len(plain_seq) / plain_dt, 1),
        "spec_tokens_per_sec": round(len(spec_seq) / spec_dt, 1),
        "accepted_tokens_per_dispatch": round(tpd, 3),
        "accept_rate_greedy": round(greedy_accept, 3),
        "sampling": {"temperature": 0.7, "top_p": 0.95, "seed": 4242,
                     "accept_rate": round(samp_accept, 3),
                     "outputs_identical_to_no_draft": True},
        "outputs_identical": True,
        "speedup": spec_speedup,
        "speedup_floor": 1.3,
        "floor": "speedup >= 1.3 and accepted_tokens_per_dispatch > 1.0",
        "floor_ok": bool(spec_speedup >= 1.3 and tpd > 1.0),
    }

    # -- decode-scan bursts: the same 16-client load through the same
    # net, scan_steps=0 (one donated dispatch per token) vs the default
    # k-step lax.scan burst.  All clients are submitted at once so the
    # queue drains in one admission boundary and the burst gate holds
    # from the first decode step (steady state, no join churn) ---------
    scan_k = int(engine.scan_steps)
    step_eng = GenerationEngine(net, name="bench-step",
                                max_slots=clients, max_len=max_len,
                                prefix_cache=False, scan_steps=0)

    def steady_load(eng, tag):
        bat = ContinuousBatcher(eng, name=f"bench-{tag}")
        try:
            # one untimed pass to settle jit caches and the step EWMA
            for r in [bat.submit_async(p, max_new_tokens=new_tokens)
                      for p in prompts]:
                r.result(timeout=300)
            t1 = time.perf_counter()
            reqs = [bat.submit_async(p, max_new_tokens=new_tokens)
                    for p in prompts]
            outs = [r.result(timeout=300) for r in reqs]
            dt = time.perf_counter() - t1
            st = bat.stats()
            return outs, sum(len(o) for o in outs) / dt, st
        finally:
            bat.close()

    engine.reset()
    step_outs, step_tps, step_st = steady_load(step_eng, "step")
    scan_outs, scan_tps, scan_st = steady_load(engine, "scan")
    if scan_outs != step_outs:
        raise RuntimeError(
            "scanned-burst outputs != per-step outputs (greedy decode "
            "must be bit-identical at any scan_steps)")
    step_dpt = float(step_st["dispatches_per_token"])
    scan_dpt = float(scan_st["dispatches_per_token"])
    scan_speedup = round(scan_tps / max(step_tps, 1e-9), 3)
    scan_axis = {
        "scan_steps": scan_k,
        "per_step": {"tokens_per_sec": round(step_tps, 1),
                     "dispatches_per_token": round(step_dpt, 4)},
        "scan": {"tokens_per_sec": round(scan_tps, 1),
                 "dispatches_per_token": round(scan_dpt, 4),
                 "burst_dispatches":
                     int(scan_st["decode_burst_dispatches"])},
        "outputs_identical": True,
        "speedup": scan_speedup,
        "speedup_floor": 1.2,
        "floor": "speedup >= 1.2 and scan dispatches_per_token <= 0.2",
        "floor_ok": bool(scan_speedup >= 1.2 and scan_dpt <= 0.2),
    }

    # -- sampling: the same 16-client steady-state load, greedy vs
    # per-request stochastic sampling (temperature 0.8, top-p 0.9,
    # fixed per-request seeds).  The sampling operands ride the SAME
    # compiled programs as traced inputs — no new programs, no host
    # branching — so the only cost is the in-program Gumbel-max
    # epilogue; the floor holds sampled throughput within 10% of
    # greedy.  The legs alternate through ONE batcher, best-of-3 each
    # (sequential per-arm phases charge host drift to whichever arm
    # runs second — the train_loop health axis lesson), and the fixed
    # seeds double as a replay-contract assertion ---------------------
    engine.reset()
    samp_bat = ContinuousBatcher(engine, name="bench-sampling")

    def sampling_pass(sampler):
        t1 = time.perf_counter()
        reqs = [samp_bat.submit_async(p, max_new_tokens=new_tokens,
                                      sampling=sampler(i))
                for i, p in enumerate(prompts)]
        got = [r.result(timeout=300) for r in reqs]
        return got, sum(len(o) for o in got) / (time.perf_counter() - t1)

    def _samp(i):
        return SamplingParams(temperature=0.8, top_p=0.9, seed=9000 + i)

    def _greedy(i):
        return None

    try:
        sampling_pass(_greedy)          # settle jit caches / step EWMA
        sampling_pass(_samp)
        greedy_tps = sampled_tps = 0.0
        sam_outs = None
        for _ in range(3):
            _, g = sampling_pass(_greedy)
            got, s = sampling_pass(_samp)
            if sam_outs is not None and got != sam_outs:
                raise RuntimeError(
                    "seeded sampled outputs changed across repeats "
                    "(replay contract broken)")
            sam_outs = got
            greedy_tps = max(greedy_tps, g)
            sampled_tps = max(sampled_tps, s)
    finally:
        samp_bat.close()
    overhead_pct = round(
        (greedy_tps - sampled_tps) / max(greedy_tps, 1e-9) * 100, 2)
    sampling_axis = {
        "temperature": 0.8,
        "top_p": 0.9,
        "greedy_tokens_per_sec": round(greedy_tps, 1),
        "sampled_tokens_per_sec": round(sampled_tps, 1),
        "overhead_pct": overhead_pct,
        "distinct_outputs": len({tuple(o) for o in sam_outs}),
        "seeded_replay_identical": True,
        "floor": "overhead_pct <= 10.0",
        "floor_ok": bool(overhead_pct <= 10.0),
    }

    return {
        "model": f"gpt_{L}L_{U}u_{heads}h",
        "clients": clients,
        "max_new_tokens": new_tokens,
        "max_slots": engine.max_slots,
        "max_len": engine.max_len,
        "prefill_buckets": list(engine.prefill_buckets),
        "compiled_programs": engine.compiled_programs(),
        "kv_cache_mb": round(engine.cache_bytes / 2**20, 2),
        "naive_prefill_every_token": naive,
        "continuous": continuous,
        "decode_steps": bstats.get("decode_steps"),
        "outputs_identical": True,
        "speedup": speedup,
        "speedup_floor": 3.0,
        "prefix_prefill_savings": prefix_axis,
        "speculative_decoding": spec_axis,
        "decode_scan": scan_axis,
        "sampling": sampling_axis,
        "floor_ok": bool(speedup >= 3.0
                         and prefix_axis["floor_ok"]
                         and spec_axis["floor_ok"]
                         and scan_axis["floor_ok"]
                         and sampling_axis["floor_ok"]),
    }


def _bench_train_loop(on_accel, kind, dev):
    """Whole-step capture: CompiledLoop (k-step lax.scan, ONE dispatch
    per k-step chunk, double-buffered device prefetch) vs the per-step
    path it replaces — eager per-op forward/backward plus the fused
    in-place ``Trainer.step`` update — on the bert_tiny config.  Both
    runs consume the identical seeded batch stream from the identical
    init, and the final params are compared elementwise.  The >= 1.25x
    steps/sec floor is the acceptance bar of docs/performance.md."""
    import jax
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import parallel, telemetry
    from incubator_mxnet_tpu.models import bert as bert_mod
    from incubator_mxnet_tpu.parallel.loop import CompiledLoop

    cfg = dict(vocab_size=1024, units=128, hidden_size=256,
               num_layers=2, num_heads=2, max_length=128)
    if on_accel:
        B, T, K, warmup, steps = 32, 128, 8, 8, 24
    else:
        B, T, K, warmup, steps = 4, 64, 8, 8, 24
    V = cfg["vocab_size"]
    opt_args = {"learning_rate": 0.01, "momentum": 0.9}

    rng = np.random.default_rng(0)
    batches = []
    for _ in range(warmup + steps):
        ids = rng.integers(0, V, (B, T)).astype(np.int32)
        types = np.zeros((B, T), np.int32)
        labels = np.concatenate(
            [rng.integers(0, V, (B, T)), rng.integers(0, 2, (B, 1))],
            axis=1).astype(np.float32)
        batches.append((ids, types, labels))

    def build_net():
        mx.random.seed(0)
        net = bert_mod.BERTForPretrain(
            bert_mod.BERTModel(dropout=0.0, **cfg), vocab_size=V)
        net.initialize(init=mx.init.Normal(0.02))
        with mx.autograd.pause():
            net(mx.nd.array(batches[0][0], dtype=np.int32),
                mx.nd.array(batches[0][1], dtype=np.int32))
        return net

    def param_vals(net):
        # strip the per-instance auto prefix so the two nets compare
        return {n.split("_", 1)[1]: p.data().asnumpy()
                for n, p in net.collect_params().items()}

    # -- per-step baseline: eager per-op autograd + fused update ------
    net_e = build_net()
    trainer = mx.gluon.Trainer(net_e.collect_params(), "sgd",
                               dict(opt_args))
    loss_blk = bert_mod.BERTPretrainLoss(V)

    def eager_step(b):
        ids = mx.nd.array(b[0], dtype=np.int32)
        types = mx.nd.array(b[1], dtype=np.int32)
        labels = mx.nd.array(b[2])
        with mx.autograd.record():
            outs = net_e(ids, types)
            if not isinstance(outs, tuple):
                outs = (outs,)
            loss = loss_blk(*outs, labels).mean()
        loss.backward()
        trainer.step(1)
        return loss

    for b in batches[:warmup]:
        loss = eager_step(b)
    jax.block_until_ready(loss._data)
    t0 = time.perf_counter()
    for b in batches[warmup:]:
        loss = eager_step(b)
    jax.block_until_ready(loss._data)
    eager_sps = steps / (time.perf_counter() - t0)

    # -- CompiledLoop: same seed, same stream; warm chunk compiles the
    # scanned program, the timed run is pure chunk dispatch + prefetch -
    telemetry.start()
    net_l = build_net()
    loop = CompiledLoop(
        net_l, bert_mod.BERTPretrainLoss(V), "sgd", dict(opt_args),
        loop_steps=K,
        mesh=parallel.make_mesh({"data": 1}, devices=[dev]))
    loop.run(batches[:warmup], prefetch=False)
    t0 = time.perf_counter()
    losses = loop.run(batches[warmup:], prefetch=True)
    loop_sps = steps / (time.perf_counter() - t0)
    assert losses.shape == (steps,) and np.isfinite(losses).all()
    loop.sync_to_block()

    # -- parity: vs the per-step JITTED dispatch (same traced program,
    # k dispatches instead of 1) the loop must be BITWISE identical;
    # vs the eager per-op baseline XLA's whole-program fusion rounds
    # differently in the last ulp, so that is reported as a deviation
    net_j = build_net()
    spmd = parallel.SPMDTrainer(
        net_j, bert_mod.BERTPretrainLoss(V), "sgd", dict(opt_args),
        mesh=parallel.make_mesh({"data": 1}, devices=[dev]))
    for b in batches:
        spmd.step(*b)
    spmd.sync_to_block()

    pe, pl, pj = param_vals(net_e), param_vals(net_l), param_vals(net_j)
    identical = all(np.array_equal(pj[n], pl[n]) for n in pj)
    eager_abs_dev = max(float(np.max(np.abs(pe[n] - pl[n]))) for n in pe)

    # -- health plane: the same loop with MXNET_HEALTH_PLANE=1 — the
    # per-leaf stats ride the scanned program as extra ys behind an
    # optimization_barrier (health.py), so the acceptance bar is twofold:
    # steps/sec within 5% of the plane-off loop AND params bit-identical.
    # The stat cost is a fixed per-step pass over the params, so it is
    # measured at a compute-dense batch (the micro smoke config above
    # would charge the plane for work any real step amortizes); both
    # sides of the ratio run that same config
    Bh, Th = (B, T) if on_accel else (16, 128)
    hsteps = 16
    rngh = np.random.default_rng(1)
    hbatches = []
    for _ in range(warmup + hsteps):
        ids = rngh.integers(0, V, (Bh, Th)).astype(np.int32)
        types = np.zeros((Bh, Th), np.int32)
        labels = np.concatenate(
            [rngh.integers(0, V, (Bh, Th)),
             rngh.integers(0, 2, (Bh, 1))], axis=1).astype(np.float32)
        hbatches.append((ids, types, labels))

    class _plane:
        def __init__(self, on):
            self.on = on

        def __enter__(self):
            self.prior = os.environ.get("MXNET_HEALTH_PLANE")
            if self.on:
                os.environ["MXNET_HEALTH_PLANE"] = "1"
            else:
                os.environ.pop("MXNET_HEALTH_PLANE", None)

        def __exit__(self, *exc):
            if self.prior is None:
                os.environ.pop("MXNET_HEALTH_PLANE", None)
            else:
                os.environ["MXNET_HEALTH_PLANE"] = self.prior

    def build_health_axis(plane_on):
        with _plane(plane_on):
            mx.random.seed(0)
            net = bert_mod.BERTForPretrain(
                bert_mod.BERTModel(dropout=0.0, **cfg), vocab_size=V)
            net.initialize(init=mx.init.Normal(0.02))
            with mx.autograd.pause():
                net(mx.nd.array(hbatches[0][0], dtype=np.int32),
                    mx.nd.array(hbatches[0][1], dtype=np.int32))
            lp = CompiledLoop(
                net, bert_mod.BERTPretrainLoss(V), "sgd",
                dict(opt_args), loop_steps=K,
                mesh=parallel.make_mesh({"data": 1}, devices=[dev]))
            lp.run(hbatches[:warmup], prefetch=False)
            lp.sync_to_block()
        return lp, net

    def timed_health_run(lp, plane_on):
        with _plane(plane_on):
            t0 = time.perf_counter()
            lp.run(hbatches[warmup:], prefetch=True)
            lp.sync_to_block()
            return hsteps / (time.perf_counter() - t0)

    # both loops are built and warmed BEFORE any timing, then the two
    # arms alternate trials back-to-back (best-of-3 each): sequential
    # per-arm phases sit minutes apart on a busy host and charge the
    # drift to whichever arm ran second.  Both arms replay the same
    # batches the same number of times, so the bitwise check still
    # compares identical step sequences.
    base_lp, net_base = build_health_axis(False)
    health_lp, net_health = build_health_axis(True)
    base_sps = health_sps = 0.0
    for _ in range(3):
        base_sps = max(base_sps, timed_health_run(base_lp, False))
        health_sps = max(health_sps, timed_health_run(health_lp, True))
    p_base, p_health = param_vals(net_base), param_vals(net_health)
    health_identical = all(np.array_equal(p_base[n], p_health[n])
                           for n in p_base)
    health_ratio = round(health_sps / max(base_sps, 1e-9), 3)

    snap = telemetry.snapshot(include_memory=False)
    mfu = snap.get("gauges", {}).get("mxtpu_mfu") or None
    mfu_source = "telemetry (scanned-program cost analysis)"
    if mfu is None:
        flops = _model_flops_per_step(cfg, B, T)
        peak = _peak_flops(kind) if on_accel else _cpu_peak_flops()
        mfu = (loop_sps / B) * flops * B / peak if peak else None
        mfu_source = "analytic flops / host peak"

    speedup = round(loop_sps / max(eager_sps, 1e-9), 3)
    rec = {
        "model": "bert_tiny" if not on_accel else "bert_tiny_accel",
        "batch_size": B, "seq_len": T, "loop_steps": K,
        "steps_measured": steps,
        "eager_steps_per_sec": round(eager_sps, 2),
        "loop_steps_per_sec": round(loop_sps, 2),
        "speedup": speedup,
        "speedup_floor": 1.25,
        "floor_ok": bool(speedup >= 1.25),
        "params_bitwise_vs_per_step_jit": bool(identical),
        "eager_params_max_abs_dev": eager_abs_dev,
        "health_batch_size": Bh, "health_seq_len": Th,
        "health_base_steps_per_sec": round(base_sps, 2),
        "health_steps_per_sec": round(health_sps, 2),
        "health_overhead_ratio": health_ratio,
        "overhead_floor": 0.95,
        "health_floor_ok": bool(health_ratio >= 0.95),
        "health_params_bitwise": bool(health_identical),
        "chunks": int(telemetry.counters_flat().get(
            "mxtpu_loop_chunks", 0)),
        "mfu": round(mfu, 4) if mfu is not None else None,
        "mfu_source": mfu_source,
    }
    if not identical:
        rec["jit_params_max_abs_dev"] = max(
            float(np.max(np.abs(pj[n] - pl[n]))) for n in pj)
    return rec


_ZERO1_OPTIM_SCRIPT = r"""
import json, time
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import autograd as ag
from incubator_mxnet_tpu import telemetry
from incubator_mxnet_tpu.gluon import Trainer, nn

D, L, B = 256, 8, 8
STEPS, WARM = 20, 3

def run(zero1):
    mx.random.seed(0)
    net = nn.HybridSequential()
    for _ in range(L):
        net.add(nn.Dense(D, in_units=D, activation="relu"))
    net.initialize(init=mx.init.Xavier())
    net.hybridize()
    x = mx.nd.array(np.random.default_rng(0).standard_normal(
        (B, D)).astype(np.float32))
    tr = Trainer(net.collect_params(), "adam", {"learning_rate": 1e-3},
                 fused=True, zero1=zero1)
    with ag.record():
        loss = (net(x) ** 2).mean()
    loss.backward()
    for _ in range(WARM):
        tr.step(B, ignore_stale_grad=True)
    mx.nd.waitall()
    t0 = time.perf_counter()
    for _ in range(STEPS):
        tr.step(B, ignore_stale_grad=True)
    mx.nd.waitall()
    rate = STEPS / (time.perf_counter() - t0)
    n_elems = sum(int(np.prod(p.shape))
                  for p in net.collect_params().values())
    flat = telemetry.counters_flat()
    g = telemetry.registry.get("mxtpu_optimizer_dispatches_per_step")
    disp = int(sum(g._values.values()))
    return (rate, n_elems, disp,
            flat.get("mxtpu_optimizer_state_bytes", 0),
            flat.get("mxtpu_zero1_allgather_bytes", 0))

f_rate, n_elems, _, full_bytes, _ = run(zero1=False)
z_rate, _, z_disp, z_bytes, z_ag = run(zero1=True)
ratio = z_bytes / max(full_bytes, 1)
print(json.dumps({
    "devices": len(jax.local_devices()),
    "fused_updates_per_sec": round(f_rate, 1),
    "updates_per_sec": round(z_rate, 1),
    "param_elements_per_sec": round(z_rate * n_elems),
    "dispatches_per_step": z_disp,
    "state_bytes_per_replica": int(z_bytes),
    "state_bytes_replicated": int(full_bytes),
    "state_ratio": round(ratio, 4),
    "allgather_bytes_per_step": int(z_ag),
    "floor": "state_ratio <= 0.25",
    "floor_ok": bool(ratio <= 0.25)}))
"""


def _zero1_dryrun(timeout=600):
    """ZeRO-1 optimizer measurement on the virtual 8-device CPU mesh (a
    fresh process — the sharding needs devices the caller may not
    have): fused-replicated vs zero1-sharded adam update throughput,
    per-replica state bytes, and the all-gather volume the scheme pays
    for the 1/N state."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    try:
        out = subprocess.run(
            [sys.executable, "-c", _ZERO1_OPTIM_SCRIPT],
            capture_output=True, text=True, timeout=timeout, env=env,
            cwd=os.path.dirname(os.path.abspath(__file__)))
        line = out.stdout.strip().splitlines()[-1] if out.stdout.strip() \
            else ""
        rec = json.loads(line)
        rec["devices"] = "8 virtual CPU (subprocess; caller had 1 device)"
        return rec
    except Exception as e:
        return {"error": str(e)[:200]}


_SCALING_SCRIPT = r"""
import json, time
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import gluon, parallel
from incubator_mxnet_tpu.gluon.model_zoo import vision as zoo

PER_DEV_B, H, STEPS, WARM = 8, 32, 8, 2

class CE(gluon.HybridBlock):
    def __init__(self, **kw):
        super().__init__(**kw)
        with self.name_scope():
            self.ce = gluon.loss.SoftmaxCrossEntropyLoss()
    def hybrid_forward(self, F, scores, labels):
        return self.ce(scores, labels).mean()

def step_time(n_dev, reps=3, opt_params=None, zero1=None):
    mx.random.seed(0)
    net = zoo.resnet18_v1(classes=10)
    net.initialize(init=mx.init.Xavier())
    with mx.autograd.pause():
        net(mx.nd.array(np.zeros((2, 3, H, H), np.float32)))
    mesh = parallel.make_mesh({"data": n_dev},
                              devices=jax.devices()[:n_dev])
    tr = parallel.SPMDTrainer(net, CE(), "sgd",
                              opt_params or {"learning_rate": 0.1},
                              mesh=mesh, data_axis="data",
                              **({} if zero1 is None
                                 else {"zero1": zero1}))
    rng = np.random.default_rng(0)
    B = PER_DEV_B * n_dev
    x = rng.standard_normal((B, 3, H, H)).astype(np.float32)
    y = rng.integers(0, 10, (B,)).astype(np.float32)
    for _ in range(WARM):
        loss = tr.step(x, y)
    jax.block_until_ready(loss)
    # a MEASUREMENT, not a sample: repeat the timed loop and take the
    # median — single-shot numbers on a contended 1-core box swung the
    # judged ratio 0.987 -> 1.136 between rounds on unchanged code
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(STEPS):
            loss = tr.step(x, y)
        jax.block_until_ready(loss)
        times.append((time.perf_counter() - t0) / STEPS)
    return times, tr

ts1, _ = step_time(1)
ts8, _ = step_time(8)
t1, t8 = float(np.median(ts1)), float(np.median(ts8))
spread = lambda ts: (max(ts) - min(ts)) / float(np.median(ts))
# ZeRO-1 on the same 8-device mesh: a momentum run (plain sgd has no
# state to shard) sharded vs replicated — the apples-to-apples pair for
# the update-sharding overhead and the 1/N state-bytes floor.
MOM = {"learning_rate": 0.1, "momentum": 0.9}
tsm, _ = step_time(8, reps=2, opt_params=MOM)
tsz, trz = step_time(8, reps=2, opt_params=MOM, zero1=True)
tm, tz = float(np.median(tsm)), float(np.median(tsz))
from incubator_mxnet_tpu.parallel import zero1 as z1mod
shard_b = z1mod.per_replica_state_bytes(trz._opt_state)
full_b = sum(int(np.prod(l.shape)) * np.dtype(l.dtype).itemsize
             for l in jax.tree.leaves(trz._opt_state))
ratio = shard_b / max(full_b, 1)
ag_b = z1mod.zero1_allgather_bytes(trz._opt.spec)
# All 8 virtual devices share this host's cores, so wall-clock speedup is
# impossible; the honest number is the sharding-overhead ratio: the
# 8-device program doing 8x the work vs 8x the 1-device time.  <= 1.0
# means the sharded program adds no overhead (no hidden serialization,
# no collective blowup).
print(json.dumps({"t_step_1dev_s": round(t1, 4),
                  "t_step_8dev_s": round(t8, 4),
                  "runs": len(ts1),
                  "spread_1dev": round(spread(ts1), 3),
                  "spread_8dev": round(spread(ts8), 3),
                  "sharding_overhead_ratio": round(t8 / (8 * t1), 3),
                  "zero1": {
                      "t_step_8dev_s": round(tz, 4),
                      "replicated_t_step_8dev_s": round(tm, 4),
                      "overhead_ratio": round(tz / tm, 3),
                      "state_bytes_per_replica": int(shard_b),
                      "state_bytes_replicated": int(full_b),
                      "state_ratio": round(ratio, 4),
                      "allgather_bytes_per_step": int(ag_b),
                      "floor": "state_ratio <= 0.25",
                      "floor_ok": bool(ratio <= 0.25)}}))
"""


def _scaling_dryrun(timeout=900):
    """Weak-scaling DP dryrun on the virtual 8-device CPU mesh: fixed
    per-device batch, 1 vs 8 devices; efficiency = t(1)/t(8).  NOTE: the 8
    virtual devices share one host's cores, so this validates that the
    sharded program scales structurally (no hidden serialization), not ICI
    bandwidth — the honest limit of a single-chip environment."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    try:
        out = subprocess.run(
            [sys.executable, "-c", _SCALING_SCRIPT], capture_output=True,
            text=True, timeout=timeout, env=env,
            cwd=os.path.dirname(os.path.abspath(__file__)))
        line = out.stdout.strip().splitlines()[-1] if out.stdout.strip() \
            else ""
        rec = json.loads(line)
        rec["devices"] = ("8 virtual CPU sharing one host's cores (weak "
                          "scaling, per-dev batch 8; ratio <= 1.0 means "
                          "the sharded program adds no overhead)")
        return rec
    except Exception as e:
        return {"error": str(e)[:200]}


def main():
    # The anchor must measure the DEFAULT config: a pre-set fusion or
    # mirror flag (either spelling — base.getenv gives MXTPU_*
    # precedence) would silently change what the anchor measures (and a
    # preset MXTPU_BACKWARD_DO_MIRROR=0 would veto the ladder's own
    # remat retry).  Force-unset all; fusion_on measures the fused
    # config explicitly and the ladder owns the remat knob.
    _preset = {k: os.environ.pop(k) for k in
               ("MXNET_USE_FUSION", "MXTPU_USE_FUSION",
                "MXNET_BACKWARD_DO_MIRROR", "MXTPU_BACKWARD_DO_MIRROR")
               if k in os.environ}
    preset_fusion = ", ".join(f"{k}={v}" for k, v in _preset.items()) \
        or None
    try:
        _main(preset_fusion)
    finally:
        os.environ.update(_preset)   # in-process callers keep their env


# --jsonl journal: every sub-bench result is appended the moment it
# lands, so a bench run killed mid-round (wall-clock cap) keeps its
# finished measurements; --resume replays non-error records
# from the journal (marked "resumed": true) and re-runs only the rest.
_JOURNAL_PATH = None
_RESUME = False
_JOURNAL_CACHE = None


def _journal_lookup(name):
    global _JOURNAL_CACHE
    if not (_JOURNAL_PATH and _RESUME):
        return None
    if _JOURNAL_CACHE is None:
        _JOURNAL_CACHE = {}
        try:
            with open(_JOURNAL_PATH) as f:
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        rec = json.loads(line)
                    except ValueError:
                        continue  # torn tail line from a killed run
                    if isinstance(rec, dict) and "name" in rec:
                        _JOURNAL_CACHE[rec["name"]] = rec.get("record")
        except OSError:
            pass
    rec = _JOURNAL_CACHE.get(name)
    if isinstance(rec, dict) and "error" not in rec:
        return {**rec, "resumed": True}
    return None  # errors and misses re-run


def _journal_append(name, rec):
    if not _JOURNAL_PATH:
        return
    try:
        with open(_JOURNAL_PATH, "a") as f:
            f.write(json.dumps({"name": name,
                                "time_unix": round(time.time(), 3),
                                "record": rec}, default=str) + "\n")
            f.flush()
    except OSError:
        pass  # the journal must never sink the bench itself


def _cpu_bench(name, fn):
    """CPU-path sub-bench with the same journal semantics as the accel
    path's _run_sub: resume hit short-circuits, result appends."""
    cached = _journal_lookup(name)
    if cached is not None:
        return cached
    try:
        rec = fn()
    except Exception as e:
        rec = {"error": str(e)[:200]}
    _journal_append(name, rec)
    return rec


def _run_sub(name, timeout, extra_env=None):
    """One measurement in a FRESH process: each accel sub-bench gets the
    whole HBM (observed on-chip: the anchor's BERT-large params + Adam
    state stay resident in-process, and every follow-on model then dies
    with RESOURCE_EXHAUSTED).  The children share the repo's persistent
    compile cache (``compile_cache.ensure_compile_cache`` in
    ``_sub_main``), which keeps the per-process XLA recompiles cheap."""
    cached = _journal_lookup(name)
    if cached is not None:
        return cached
    env = {**os.environ, **(extra_env or {})}
    try:
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--sub", name],
            capture_output=True, text=True, timeout=timeout, env=env)
        if out.returncode == 0 and out.stdout.strip():
            rec = json.loads(out.stdout.strip().splitlines()[-1])
        else:
            tail = (out.stderr or out.stdout or "").strip().splitlines()
            rec = {"error": (tail[-1][:200] if tail
                             else f"rc={out.returncode}, no output")}
    except subprocess.TimeoutExpired:
        rec = {"error": f"sub-bench {name} hung >{timeout}s"}
    except Exception as e:
        rec = {"error": str(e)[:200]}
    _journal_append(name, rec)
    return rec


def _sub_main(name):
    """Entry for --sub NAME: run exactly one measurement on the device
    jax gives THIS process, print one JSON line naming that device."""
    from incubator_mxnet_tpu.compile_cache import ensure_compile_cache
    ensure_compile_cache()
    import jax
    dev = jax.devices()[0]
    kind = dev.device_kind
    on_accel = dev.platform != "cpu"
    if name == "anchor":
        s, B, T, mfu, remat = _bench_bert(on_accel, kind, dev)
        rec = {"samples_per_sec": round(s, 2), "batch_size": B,
               "seq_len": T,
               "mfu": round(mfu, 4) if mfu is not None else None,
               "remat": remat}
    elif name in ("phase2", "fusion512"):
        # ONE seq-512 config for both the XLA baseline and the fused
        # run, so the f512/phase2 ratio always compares like for like
        if name == "fusion512":
            os.environ["MXNET_USE_FUSION"] = "1"
        s, B, T, mfu, remat = _bench_bert(
            on_accel, kind, dev, seq_len=512,
            batch_ladder=[16, 8, 4], steps=10)
        rec = {"samples_per_sec": round(s, 2), "batch_size": B,
               "seq_len": T, "remat": remat,
               "mfu": round(mfu, 4) if mfu is not None else None}
    elif name == "fusion":
        os.environ["MXNET_USE_FUSION"] = "1"
        b_used = int(os.environ.get("BENCH_B_USED", "0"))
        s, B, _, mfu, remat = _bench_bert(
            on_accel, kind, dev,
            batch_ladder=[b_used] if b_used else None, steps=10)
        rec = {"samples_per_sec": round(s, 2), "batch_size": B,
               "remat": remat,
               "mfu": round(mfu, 4) if mfu is not None else None}
    elif name == "resnet50":
        rec = _bench_resnet50(on_accel, kind, dev)
    elif name == "int8":
        rec = _bench_int8(on_accel, kind, dev)
    elif name == "int8_conv":
        rec = _bench_int8_conv(on_accel, kind, dev)
    elif name == "optim":
        rec = _bench_optim(on_accel, kind, dev)
    elif name == "serve":
        rec = _bench_serve(on_accel, kind, dev)
    elif name == "generate":
        rec = _bench_generate(on_accel, kind, dev)
    elif name == "train_loop":
        rec = _bench_train_loop(on_accel, kind, dev)
    else:
        raise SystemExit(f"unknown sub-bench {name!r}")
    rec["device"] = {"platform": dev.platform, "kind": kind,
                     "count": len(jax.devices())}
    tel = _telemetry_snapshot()
    if tel is not None:
        rec["telemetry"] = tel
    print(json.dumps(rec))


def _main(preset_fusion):
    platform, kind = _device()
    on_accel = platform != "cpu"

    if on_accel:
        # accel path: NO jax client in this process — every measurement
        # runs in its own subprocess with a clean HBM (see _run_sub)
        anchor = _run_sub("anchor", timeout=3600)
        if "error" in anchor:
            sys.exit(f"bench.py: the anchor failed on {platform}:{kind}: "
                     f"{anchor['error']}")
        samples_per_sec = anchor["samples_per_sec"]
        B_used, T = anchor["batch_size"], anchor["seq_len"]
        mfu, remat = anchor["mfu"], anchor["remat"]

        phase2 = _run_sub("phase2", timeout=2700)
        fusion = _run_sub("fusion", timeout=2700,
                          extra_env={"BENCH_B_USED": str(B_used)})
        if "samples_per_sec" in fusion:
            fusion["speedup_vs_xla"] = round(
                fusion["samples_per_sec"] / samples_per_sec, 3)
        f512 = _run_sub("fusion512", timeout=2700)
        if "samples_per_sec" in f512 and isinstance(phase2, dict) \
                and phase2.get("samples_per_sec"):
            if f512.get("batch_size") == phase2.get("batch_size"):
                f512["speedup_vs_xla"] = round(
                    f512["samples_per_sec"] / phase2["samples_per_sec"],
                    3)
            else:
                # the OOM ladder settled differently (fused attention
                # has a smaller footprint): a throughput ratio would
                # conflate fusion with batch-size gains
                f512["speedup_note"] = (
                    f"batch sizes differ (fused {f512.get('batch_size')}"
                    f" vs xla {phase2.get('batch_size')}); no ratio")
        fusion["seq512"] = f512
        resnet = _run_sub("resnet50", timeout=2700)
        int8 = _run_sub("int8", timeout=1800)
        int8["conv"] = _run_sub("int8_conv", timeout=2700)
        optim = _run_sub("optim", timeout=1800)
        serve = _run_sub("serve", timeout=1800)
        serve["generate"] = _run_sub("generate", timeout=1800)
        train_loop = _run_sub("train_loop", timeout=1800)
        scaling = _scaling_dryrun()
    else:
        from incubator_mxnet_tpu.compile_cache import ensure_compile_cache
        ensure_compile_cache()
        import jax
        dev = jax.devices()[0]
        samples_per_sec, B_used, T, mfu, remat = _bench_bert(
            False, kind, dev)
        phase2 = fusion = None
        resnet = _cpu_bench("resnet50",
                            lambda: _bench_resnet50(False, kind, dev))
        int8 = _cpu_bench("int8", lambda: _bench_int8(False, kind, dev))
        int8["conv"] = _cpu_bench(
            "int8_conv", lambda: _bench_int8_conv(False, kind, dev))
        optim = _cpu_bench("optim",
                           lambda: _bench_optim(False, kind, dev))
        serve = _cpu_bench("serve",
                           lambda: _bench_serve(False, kind, dev))
        serve["generate"] = _cpu_bench(
            "generate", lambda: _bench_generate(False, kind, dev))
        train_loop = _cpu_bench(
            "train_loop", lambda: _bench_train_loop(False, kind, dev))
        scaling = _scaling_dryrun()

    out = {
        "metric": ("bert_large_pretrain_samples_per_sec_per_chip"
                   if on_accel else
                   "bert_tiny_cpu_smoke_samples_per_sec"),
        "value": round(samples_per_sec, 2),
        "unit": "samples/s",
        "vs_baseline": round(
            samples_per_sec / BASELINE_SAMPLES_PER_SEC_PER_CHIP, 3),
        "baseline_anchor": BASELINE_ANCHOR,
        "mfu": round(mfu, 4) if mfu is not None else None,
        "batch_size": B_used,
        "seq_len": T,
        "objective": "MLM+NSP",
        "device": f"{platform}:{kind}",
        "dtype": "bfloat16" if on_accel else "float32",
        "remat": remat,
        "resnet50": resnet,
        "int8_inference": int8,
        "optimizer_update": optim,
        "serving": serve,
        "train_loop": train_loop,
        "dp_scaling": scaling,
    }
    if out["mfu"] is None and isinstance(train_loop, dict) \
            and train_loop.get("mfu"):
        # the anchor's own mfu came back null (no peak-FLOPs estimate):
        # surface the CompiledLoop measurement instead of null
        out["mfu"] = train_loop["mfu"]
        out["mfu_source"] = ("train_loop: "
                             + train_loop.get("mfu_source", ""))
    if phase2 is not None:
        out["phase2_seq512"] = phase2
    if fusion is not None:
        out["fusion_on"] = fusion
    tel = _telemetry_snapshot()
    if tel is not None:
        out["telemetry"] = tel
    if preset_fusion is not None:
        out["note"] = (f"pre-set flags ignored ({preset_fusion}): the "
                       "anchor measures the default config; fusion_on "
                       "covers the fused path and the OOM ladder decides "
                       "remat itself (recorded per measurement)")
    print(json.dumps(out))
    failed = _errors(out)
    if failed:
        sys.exit("bench.py: failed measurements: " + ", ".join(failed))


def _errors(rec, path=""):
    """Dotted paths of every sub-record that carries an ``error``."""
    if not isinstance(rec, dict):
        return []
    found = [path or "record"] if "error" in rec else []
    for k, v in rec.items():
        if k != "telemetry":
            found += _errors(v, f"{path}.{k}" if path else k)
    return found


if __name__ == "__main__":
    if len(sys.argv) >= 3 and sys.argv[1] == "--sub":
        _sub_main(sys.argv[2])   # let failures propagate: the parent
        sys.exit(0)              # records stderr as the sub's error
    if "--jsonl" in sys.argv:
        i = sys.argv.index("--jsonl")
        try:
            _JOURNAL_PATH = os.path.abspath(sys.argv[i + 1])
        except IndexError:
            sys.exit("bench.py: --jsonl needs a PATH")
        del sys.argv[i:i + 2]
    if "--resume" in sys.argv:
        sys.argv.remove("--resume")
        _RESUME = True
        if not _JOURNAL_PATH:
            sys.exit("bench.py: --resume needs --jsonl PATH")
    if _JOURNAL_PATH and not _RESUME and os.path.exists(_JOURNAL_PATH):
        os.unlink(_JOURNAL_PATH)  # fresh run: a stale journal would lie
    main()
