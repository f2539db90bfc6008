"""The runner of a training configuration: one process, which holds the chip.

Set-up builds ONE trainer from the seed's weights, drives it through its first
steps with the window's own call and feed (reading what the check needs on the
way), and hands that same object to the window.  After the window the program's
state is freed and the plain reference follows the first steps from the seed.
"""
import gc
import importlib
import math
import os
import shutil
import time

import numpy as np

import checks
import common
import readers
from common import log


def make_corpus(cfg, seed):
    """The seeded synthetic corpus: token ids, segment ids (a sentence pair
    split at a random point), and the packed labels the job trains on: one
    MLM target a position and the NSP class, all rows different."""
    job = cfg["job"]
    n, T, V = job["corpus_sequences"], job["seq_len"], cfg["vocab_size"]
    rng = np.random.default_rng(int(seed))
    ids = rng.integers(0, V, (n, T), dtype=np.int32)
    split = rng.integers(T // 4, 3 * T // 4, (n, 1))
    types = (np.arange(T)[None, :] >= split).astype(np.int32)
    labels = np.concatenate(
        [rng.integers(0, V, (n, T)), rng.integers(0, 2, (n, 1))],
        axis=1).astype(np.float32)
    return ids, types, labels


def first_batches(corpus, batch_size, n):
    """The first n batches as the sequential loader yields them."""
    return [tuple(a[i * batch_size:(i + 1) * batch_size] for a in corpus)
            for i in range(n)]


def _cycle(loader):
    while True:
        yield from loader


def _norms_fn():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def norms(tree):
        return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
                for k, v in tree.items()}
    return norms


def _start_trace(directory):
    """Device and host spans only: the Python tracer would add an event for
    every call of the loop and slow the very steps that are traced."""
    import jax
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(directory, profiler_options=options)


def run(cell, seed, seconds, trace, platform="tpu", t_start=None,
        break_step=False, control=False):
    """One run of a training cell.  ``break_step`` (tests only) makes the
    timed path return its state unchanged."""
    import jax
    import jax.numpy as jnp
    t_start = time.monotonic() if t_start is None else t_start
    cfg = cell["config"]
    job = cfg["job"]
    B, T = job["batch_size"], job["seq_len"]
    counter = common.CompileCounter()
    device = common.device_record(platform)
    if device["count"] < cell["chips"]:
        raise SystemExit(f"bench: the cell asks for {cell['chips']} chip(s), "
                         f"jax sees {device['count']}")
    peaks = common.peaks_for(device["kind"]) if platform == "tpu" else {}
    ref = importlib.import_module("reference." + cfg["reference"])
    prog = importlib.import_module("programs." + cfg["program"])

    corpus = make_corpus(cfg, seed)
    net = prog.build_net(cfg)
    prog.load_weights(net, ref.init_params(cfg, seed))
    trainer = prog.build_trainer(cfg, net)
    feed = _cycle(prog.make_loader(corpus, B))
    norms = _norms_fn()
    series = {"step_s": [], "data_wait_s": []}

    def one_step(sync):
        """The window's call and feed: fetch, enqueue, optionally sync."""
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.data"):
            batch = next(feed)
        t1 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.enqueue"):
            loss = None if break_step else trainer.step(*batch)
        if break_step:
            loss = jnp.float32(0.0)
        if sync:
            with jax.profiler.TraceAnnotation("bench.sync"):
                jax.block_until_ready(loss)
            series["step_s"].append(time.perf_counter() - t1)
        series["data_wait_s"].append(t1 - t0)
        return loss

    # set-up: the first steps, read for the check
    n_ref = int(cfg["check"]["reference_steps"])
    program = {"losses": []}
    for i in range(max(n_ref, int(job.get("warm_steps", 5)))):
        loss = one_step(sync=True)
        if i < n_ref:
            program["losses"].append(float(loss))
        if i == 0:
            b1 = job["optimizer_params"].get("beta1", 0.9)
            m = norms(prog.leaf_values(net, trainer, "m"))
            program["grad_norms"] = {k: float(v) / (1.0 - b1)
                                     for k, v in m.items()}
        if i == n_ref - 1:
            cur = prog.leaf_values(net, trainer, "params")
            start = prog.leaf_values(net, trainer, "start")
            program["delta_norms"] = {k: float(v) for k, v in norms(
                {k: cur[k].astype(jnp.float32) - start[k].astype(jnp.float32)
                 for k in cur}).items()}
    series["step_s"].clear()
    series["data_wait_s"].clear()
    compiled_setup = counter.requests

    # the window
    profile_dir = os.path.join(common.WORK, "profile_train")
    shutil.rmtree(profile_dir, ignore_errors=True)
    tracing = {"on": False, "done": not trace, "t_on": None}
    prof_seconds = max(1.0, min(3.0, seconds / 3.0))
    losses, inflight = [], 2
    w0 = time.monotonic()
    steps = 0
    while time.monotonic() < w0 + seconds:
        now = time.monotonic()
        if trace and not tracing["on"] and not tracing["done"] \
                and now >= w0 + min(1.0, seconds / 4.0):
            _start_trace(profile_dir)
            tracing.update(on=True, t_on=now)
        losses.append(one_step(sync=trace))
        steps += 1
        if not trace and steps > inflight:
            jax.block_until_ready(losses[steps - 1 - inflight])
        if tracing["on"] and time.monotonic() >= tracing["t_on"] \
                + prof_seconds:
            jax.block_until_ready(losses[-1])
            jax.profiler.stop_trace()
            tracing.update(on=False, done=True)
    jax.block_until_ready(losses[-1])
    elapsed = time.monotonic() - w0
    if tracing["on"]:
        jax.profiler.stop_trace()
    compiled_in_window = counter.requests - compiled_setup
    setup_s = w0 - t_start
    window_losses = [float(x) for x in losses]
    finite = all(math.isfinite(x) for x in window_losses)
    log(f"window {elapsed:.3f} s: {steps} steps of {B} x {T}; losses "
        f"{window_losses[0]:.4f} .. {window_losses[-1]:.4f}, all finite: "
        f"{finite}; compile requests inside the window {compiled_in_window}; "
        f"process compile requests {counter.requests}, cache hits "
        f"{counter.hits}, cache at {counter.cache_dir}")
    series["setup_s"] = [setup_s]
    series["train_tokens"] = [steps * B * T]
    log(f"samples: steps {steps}, data waits {len(series['data_wait_s'])}, "
        f"synced step times {len(series['step_s'])}")

    # the check, with the program's state freed
    peak = common.memory_peak_bytes()
    del trainer, net, feed, losses, loss
    gc.collect()
    t_ref = time.time()
    hp = dict(job["optimizer_params"])
    batches = first_batches(make_corpus(cfg, seed), B, n_ref)
    reference = ref.follow(cfg, hp, seed, batches)
    numbers = checks.train_numbers(program, reference)
    log(f"check: program losses {program['losses']}, reference losses "
        f"{reference['losses']}, reference took {time.time() - t_ref:.1f} s")
    correct = checks.judge(numbers, cfg["check"]["limits"], "train") \
        and finite and compiled_in_window == 0
    extra = {"program": numbers,
             "raw": {"program": program, "reference": reference}}
    if control:
        low = ref.follow(cfg, hp, seed, batches,
                         cfg["check"]["control_precision"])
        extra["control"] = checks.train_numbers(low, reference)
        extra["raw"]["control"] = low
        log(f"control ({cfg['check']['control_precision']}): "
            f"{extra['control']}")
    device = dict(device, memory_peak_bytes=peak)

    ctx = {"series": series, "window_s": elapsed, "config": cfg,
           "peaks": peaks,
           "counters": {"programs_compiled": counter.compiled}}
    breakdown = None
    if trace:
        import trace_reduce
        reduction = trace_reduce.reduce_dir(profile_dir, cfg.get("trace", {}))
        for line in reduction.pop("summary", []):
            log("trace: " + line)
        if "missing" in reduction:
            log(f"trace: {reduction}")
            reduction = None
        ctx["trace"] = reduction
        if reduction:
            device["busy_s"] = reduction["busy_s"]
            device["window_s"] = reduction["window_s"]
            breakdown = reduction["breakdown"]
            for name, row in sorted(reduction["programs"].items()):
                log(f"trace program {name}: {row['count']:.0f} x, "
                    f"{row['seconds']:.4f} s")
    metrics = readers.read_all(cell["per_layer" if trace else "end_to_end"],
                               ctx)
    for note in ctx.get("notes", []):
        log(note)
    return correct, steps, 0, metrics, device, breakdown, extra
