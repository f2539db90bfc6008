"""The serving worker loop measured from inside (docs/observability.md
"Worker-loop phases"): the tracer's bridge into the profiler's trace,
``capture_profile`` with the program's spans in the capture, the phase
clock of the ``ContinuousBatcher`` worker (counters and spans cut at the
same boundaries), the steps that subdivide a host phase and the thread's
CPU time beside its wall time, queue wait, tokens by path, the burst
gate, and the token-gap histogram on bursts."""
import ast
import glob
import json
import os
import threading
import time
import urllib.request

import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import fault, telemetry, telemetry_device
from incubator_mxnet_tpu.models.gpt import GPTModel
from incubator_mxnet_tpu.serving import (ContinuousBatcher,
                                         GenerationEngine, ModelServer)
from incubator_mxnet_tpu.serving import metrics as _m
from incubator_mxnet_tpu.serving import slo as _slo
from incubator_mxnet_tpu.serving.batcher import _GenRequest


@pytest.fixture(autouse=True)
def _clean_state():
    def clean():
        fault.clear_plan()
        telemetry.stop()
        telemetry.reset()
        telemetry.tracer.annotate = None
        telemetry.tracer.clear()
        _slo.tracker.reset()
    clean()
    yield
    clean()


class FakeAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation``: logs enter/exit."""
    log = []

    def __init__(self, name, **ids):
        self.name, self.ids = name, ids

    def __enter__(self):
        FakeAnnotation.log.append(("enter", self.name,
                                   threading.get_ident(), self.ids))
        return self

    def __exit__(self, *exc):
        FakeAnnotation.log.append(("exit", self.name,
                                   threading.get_ident(), self.ids))
        return False


@pytest.fixture
def bridge():
    FakeAnnotation.log = []
    telemetry.tracer.enable()
    telemetry.tracer.annotate = FakeAnnotation
    yield FakeAnnotation
    telemetry.tracer.annotate = None
    telemetry.tracer.disable()


# ------------------------------------------------------------ the bridge
def test_bridge_enters_and_exits_lifo_per_thread(bridge):
    # both threads alive at once: a thread that ended before the other
    # began would hand it its id, and the log is read by thread id
    both = threading.Barrier(2)

    def work(tag):
        both.wait(10)
        with telemetry.trace_span(f"outer.{tag}"):
            with telemetry.trace_span(f"mid.{tag}"):
                with telemetry.trace_span(f"inner.{tag}"):
                    time.sleep(0.002)
            with telemetry.trace_span(f"second.{tag}"):
                pass
        both.wait(10)

    threads = [threading.Thread(target=work, args=(t,)) for t in "ab"]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    by_thread = {}
    for kind, name, tid, _ in bridge.log:
        by_thread.setdefault(tid, []).append((kind, name))
    assert len(by_thread) == 2
    for events in by_thread.values():
        assert len(events) == 8
        stack = []
        for kind, name in events:
            if kind == "enter":
                stack.append(name)
            else:       # every exit closes the innermost open annotation
                assert stack.pop() == name
        assert not stack


def test_bridge_forwards_only_the_ids_that_name_work(bridge):
    with telemetry.trace_span("serve.batch", model="m", step=7, slots=3,
                              links=["a", "b"]):
        with telemetry.trace_span("slot.join", request_id="r-1", slot=2,
                                  prompt_tokens=5):
            pass
    ids = {name: got for kind, name, _, got in bridge.log
           if kind == "enter"}
    assert ids["serve.batch"] == {"model": "m", "step": 7}
    assert ids["slot.join"] == {"request_id": "r-1", "slot": 2}


def test_bridge_off_enters_nothing():
    FakeAnnotation.log = []
    telemetry.tracer.enable()
    try:
        with telemetry.trace_span("quiet") as sp:
            assert sp is not None and sp.ann is None
    finally:
        telemetry.tracer.disable()
    assert FakeAnnotation.log == []


def test_span_across_the_switch_ends_cleanly(bridge):
    tr = telemetry.tracer
    tr.annotate = None
    before = telemetry.trace_span("began.before")
    before.__enter__()
    tr.annotate = bridge
    inside = telemetry.trace_span("began.inside")
    inside.__enter__()
    tr.annotate = None          # the capture stops with both still open
    inside.__exit__(None, None, None)
    before.__exit__(None, None, None)
    assert [(k, n) for k, n, _, _ in bridge.log] == [
        ("enter", "began.inside"), ("exit", "began.inside")]
    assert before.span.t1 is not None and inside.span.t1 is not None
    assert inside.span.ann is None


def test_annotation_fault_never_fails_the_span(bridge):
    def broken(name, **ids):
        raise RuntimeError("profiler is gone")
    telemetry.tracer.annotate = broken
    with telemetry.trace_span("still.fine") as sp:
        pass
    assert sp.seconds is not None and sp.ann is None


def test_inactive_trace_span_creates_no_span():
    seq = telemetry.Span("probe").sid
    assert not telemetry.tracer.active
    telemetry.tracer.annotate = FakeAnnotation
    FakeAnnotation.log = []
    with telemetry.trace_span("nothing", request_id="x") as sp:
        assert sp is None
    assert FakeAnnotation.log == []
    # no Span object was made in between: the ids are consecutive
    assert int(telemetry.Span("probe").sid, 16) == int(seq, 16) + 1
    assert telemetry.tracer.record("nothing", 0.0, 1.0) is None


def test_record_places_a_finished_span():
    telemetry.tracer.enable()
    try:
        with telemetry.trace_span("serve.request", request_id="q") as req:
            t1 = time.perf_counter()
            sp = telemetry.tracer.record("serve.queue", t1 - 0.25, t1,
                                         parent=req, request_id="q")
        assert sp in req.children and abs(sp.seconds - 0.25) < 1e-9
        root = telemetry.tracer.record("lonely", t1 - 1.0, t1)
        names = [d["name"] for d in telemetry.tracer.tree()["finished"]]
        assert "lonely" in names and root.parent is None
        found = telemetry.tracer.find_spans("request_id", "q")
        assert [c["name"] for c in found[0]["children"]] == ["serve.queue"]
    finally:
        telemetry.tracer.disable()


def test_telemetry_module_never_imports_jax_at_import():
    """The router and the supervisor import telemetry and hold no
    device: the bridge takes its factory from the capture, and no
    module-level statement of telemetry.py imports jax."""
    tree = ast.parse(open(telemetry.__file__).read())
    for node in tree.body:
        if isinstance(node, (ast.Import, ast.ImportFrom)):
            names = [a.name for a in node.names] \
                + [getattr(node, "module", None) or ""]
            assert not any(n.split(".")[0] == "jax" for n in names)
    src = ast.get_source_segment(
        open(telemetry.__file__).read(),
        next(n for n in tree.body if isinstance(n, ast.ClassDef)
             and n.name == "Tracer"))
    assert "import jax" not in src


# ----------------------------------------------- a tiny served decoder
def _gpt(max_length=64, seed=3):
    mx.random.seed(seed)
    net = GPTModel(vocab_size=50, units=32, hidden_size=64, num_layers=2,
                   num_heads=2, max_length=max_length, dropout=0.0)
    net.initialize(init=mx.init.Normal(0.6))
    net(mx.nd.array(np.zeros((1, 2), np.int32)))
    return net


@pytest.fixture(scope="module")
def engine():
    # blocks of four: an eight-token prompt has whole blocks to hash
    eng = GenerationEngine(_gpt(), name="ph", max_slots=2, max_len=64,
                           prefill_buckets=[8], scan_steps=4, block_size=4)
    eng.warmup()
    return eng


@pytest.fixture
def batcher(engine):
    engine.reset()
    b = ContinuousBatcher(engine)
    yield b
    b.close()


def _counter(name):
    """``{label string: value}`` of one counter, as /metrics.json has it."""
    return dict(telemetry.registry.export_state()["counters"]
                .get(name, {}).get("values", {}))


def _delta(after, before):
    return {k: v - before.get(k, 0.0) for k, v in after.items()}


# ---------------------------------------------------------- queue wait
def test_queue_wait_observed_once_per_admitted_request(batcher):
    c0 = _m.QUEUE_WAIT.count
    # two slots, three requests: the third waits for a slot to free
    reqs = [batcher.submit_async([3, 7, 11], max_new_tokens=24),
            batcher.submit_async([5, 2], max_new_tokens=24),
            batcher.submit_async([9, 4, 1], max_new_tokens=6)]
    for r in reqs:
        r.result(60)
    assert _m.QUEUE_WAIT.count == c0 + 3
    waits = _m.QUEUE_WAIT.state()["samples"][-3:]
    step = _m.DECODE_STEP.stats()["p50"]
    # behind a full slot set: at least one decode dispatch long
    assert max(waits) >= step > 0.0
    assert min(waits) < max(waits)


# ---------------------------------------------------- phase accounting
def test_phases_partition_the_worker_loop(batcher):
    batcher.submit([3, 7], max_new_tokens=4)        # worker up and warm
    s0, t0 = batcher.stats()["loop_seconds"], time.perf_counter()
    c0 = _counter("mxtpu_serve_loop_seconds")
    reqs = [batcher.submit_async([3, 7, 11], max_new_tokens=40),
            batcher.submit_async([5, 2], max_new_tokens=9)]
    for r in reqs:
        r.result(60)
    time.sleep(1.0)             # idle polls after the work
    s1, wall = batcher.stats()["loop_seconds"], time.perf_counter() - t0
    d = _delta(s1, s0)
    assert set(d) == set(_m.PHASES)
    assert all(v > 0.0 for v in d.values()), d
    # time is credited at boundaries: up to one idle poll (50 ms) is
    # still uncredited at either reading
    assert 0.9 * wall <= sum(d.values()) <= wall + 0.06
    # the registry's counter is fed at the same boundaries
    cd = _delta(_counter("mxtpu_serve_loop_seconds"), c0)
    for phase in _m.PHASES:
        slack = 0.06 if phase in ("wait", "admit") else 1e-9
        assert abs(cd[f"model=ph,phase={phase}"] - d[phase]) <= slack
    # idle: `wait` alone grows (and `admit`, by the polls' microseconds)
    s2 = batcher.stats()["loop_seconds"]
    time.sleep(0.3)
    idle = _delta(batcher.stats()["loop_seconds"], s2)
    assert idle["wait"] >= 0.2
    assert all(idle[p] == 0.0 for p in _m.PHASES
               if p not in ("wait", "admit"))
    assert idle["admit"] < 0.02


# ------------------------------------------------- steps under a phase
STEPS = {"lock": {"admit"}, "hash": {"admit", "prefill_host"},
         "alloc": {"admit", "prefill_host", "emit"},
         "sampling": {"prefill_host"}, "params": {"prefill_host", "operands"},
         "edit": {"prefill_host", "operands"},
         "enqueue": {"prefill_host", "operands"}, "carry": {"operands"},
         "fanout": {"emit"}, "finish": {"emit"}}


def _by_phase_step(counter):
    out = {}
    for key, v in counter.items():
        labels = dict(part.split("=", 1) for part in key.split(","))
        if labels["model"] == "ph":
            out[labels["phase"], labels["step"]] = v
    return out


def _serve_some(batcher):
    """One join, bursts, single steps behind a full slot set, leaves."""
    # prompts of two whole blocks: the chain hash has work to do
    reqs = [batcher.submit_async(list(range(1, 9)), max_new_tokens=20),
            batcher.submit_async(list(range(12, 19)), max_new_tokens=9),
            batcher.submit_async([9, 4, 1], max_new_tokens=5)]
    for r in reqs:
        r.result(60)


def test_steps_subdivide_their_phase(batcher):
    batcher.submit([3, 7], max_new_tokens=4)        # worker up and warm
    st0 = batcher.stats()
    c0 = _counter("mxtpu_serve_loop_step_seconds")
    p0 = _counter("mxtpu_serve_loop_seconds")
    _serve_some(batcher)
    time.sleep(0.2)
    st1 = batcher.stats()
    phases = _delta(st1["loop_seconds"], st0["loop_seconds"])
    # the phases are what they were: all of them, and nothing else
    assert set(phases) == set(_m.PHASES)
    for phase, steps in st1["loop_step_seconds"].items():
        d = _delta(steps, st0["loop_step_seconds"].get(phase, {}))
        assert all(v >= 0.0 for v in d.values())
        assert 0.0 < sum(d.values()) <= phases[phase] + 1e-9, (phase, d)
    # the registry's step counter is fed at the same boundaries, and a
    # phase's steps stay inside the phase's own counter
    steps = _by_phase_step(_delta(
        _counter("mxtpu_serve_loop_step_seconds"), c0))
    walls = _delta(_counter("mxtpu_serve_loop_seconds"), p0)
    for phase in {p for p, _ in steps}:
        inside = sum(v for (p, _), v in steps.items() if p == phase)
        assert inside <= walls[f"model=ph,phase={phase}"] + 1e-9
    for (phase, step), v in steps.items():
        mine = st1["loop_step_seconds"][phase][step] \
            - st0["loop_step_seconds"].get(phase, {}).get(step, 0.0)
        assert abs(v - mine) <= 1e-9
    # only the host phases have steps
    assert {p for p, _ in steps} <= {"admit", "prefill_host", "operands",
                                     "emit"}


@pytest.mark.parametrize("step", sorted(STEPS))
def test_every_step_appears_in_its_phases(batcher, step):
    c0 = _counter("mxtpu_serve_loop_step_seconds")
    _serve_some(batcher)
    got = _by_phase_step(_delta(
        _counter("mxtpu_serve_loop_step_seconds"), c0))
    phases = {p for (p, s), v in got.items() if s == step and v > 0.0}
    assert phases == STEPS[step], (step, phases)


def test_cpu_seconds_stay_under_wall_seconds(batcher):
    batcher.submit([3, 7], max_new_tokens=4)
    st0 = batcher.stats()
    c0 = _counter("mxtpu_serve_loop_cpu_seconds")
    _serve_some(batcher)
    time.sleep(0.5)                                 # idle: wall, no CPU
    st1 = batcher.stats()
    wall = _delta(st1["loop_seconds"], st0["loop_seconds"])
    cpu = _delta(st1["loop_cpu_seconds"], st0["loop_cpu_seconds"])
    assert set(cpu) == set(_m.PHASES)
    for phase in _m.PHASES:
        # the two clocks are read one after the other at each boundary
        assert 0.0 <= cpu[phase] <= wall[phase] + 1e-3, (phase, cpu, wall)
    assert cpu["wait"] < 0.2 * wall["wait"]         # asleep, not running
    assert sum(cpu[p] for p in ("admit", "prefill_host", "operands",
                                "emit")) > 0.0
    cd = _delta(_counter("mxtpu_serve_loop_cpu_seconds"), c0)
    for phase in _m.PHASES:
        slack = 0.01 if phase in ("wait", "admit") else 1e-9
        assert abs(cd[f"model=ph,phase={phase}"] - cpu[phase]) <= slack


def test_loop_step_does_nothing_off_the_worker_thread(engine):
    """An engine driven directly: no clock is bound to this thread, so
    the cuts inside the engine and the pool count nothing."""
    engine.reset()
    c0 = (_counter("mxtpu_serve_loop_step_seconds"),
          _counter("mxtpu_serve_loop_cpu_seconds"),
          _counter("mxtpu_serve_loop_seconds"))
    with _m.loop_step("hash", "serve.join.hash") as blk:
        assert blk.clock is None
    assert engine.can_admit(list(range(1, 9)), 16)
    first = engine.prefill(list(range(1, 9)), 0, reserve_tokens=16)
    S = engine.max_slots
    last, pos = np.zeros(S, np.int32), np.zeros(S, np.int32)
    last[0], pos[0] = first, 8
    engine.decode(last, pos)
    engine.release_slot(0)
    assert (_counter("mxtpu_serve_loop_step_seconds"),
            _counter("mxtpu_serve_loop_cpu_seconds"),
            _counter("mxtpu_serve_loop_seconds")) == c0


@pytest.fixture
def clock():
    """A clock bound to the test's own thread."""
    totals = (dict.fromkeys(_m.PHASES, 0.0), dict.fromkeys(_m.PHASES, 0.0),
              {})
    _m.LoopClock("unit", *totals).bind()
    yield totals
    del _m._loop_tl.clock


def test_steps_are_exclusive_and_reentrant(clock, bridge):
    wall, cpu, steps = clock
    took = {}

    def spend(what, seconds):           # the test's own clock beside it
        t0 = time.perf_counter()
        time.sleep(seconds)
        took[what] = took.get(what, 0.0) + time.perf_counter() - t0

    with _m.loop_phase("prefill_host"):
        with _m.loop_step("enqueue", "serve.enqueue"):
            spend("enqueue", 0.02)
            with _m.loop_step("params", "serve.params"):
                spend("params", 0.03)
            # the step it is already inside: no boundary, no second span
            with _m.loop_step("enqueue", "serve.enqueue") as again:
                assert again.clock is None
                spend("enqueue", 0.01)
            # a phase opened inside a step suspends the step
            with _m.loop_phase("prefill_wait", "serve.prefill.wait"):
                spend("waits", 0.02)
                _m.loop_phase_switch("decode_wait", "serve.decode.wait")
            # a switch inside an open step does nothing
            _m.loop_phase_switch("operands", "serve.operands")
        spend("remainder", 0.01)
    assert set(steps) == {("prefill_host", "enqueue"),
                          ("prefill_host", "params")}
    near = lambda got, want: want <= got <= want + 2e-3
    assert near(steps["prefill_host", "enqueue"], took["enqueue"])
    assert near(steps["prefill_host", "params"], took["params"])
    assert near(wall["prefill_host"] - sum(steps.values()),
                took["remainder"])
    assert near(wall["prefill_wait"] + wall["decode_wait"], took["waits"])
    assert wall["operands"] == 0.0
    assert all(cpu[p] <= wall[p] + 1e-3 for p in _m.PHASES)
    entered = [n for k, n, _, _ in bridge.log if k == "enter"]
    assert entered == ["serve.enqueue", "serve.params",
                       "serve.prefill.wait", "serve.decode.wait"]
    by = _by_phase_step({k.replace("model=unit", "model=ph"): v for k, v in
                         _counter("mxtpu_serve_loop_step_seconds").items()
                         if "model=unit" in k})
    assert by == steps


def test_bound_adder_feeds_the_series_inc_feeds():
    add = _m.LOOP_STEP_SECONDS.bound(model="b", phase="emit", step="fanout")
    add(0.25)
    _m.LOOP_STEP_SECONDS.inc(0.5, step="fanout", phase="emit", model="b")
    add(0.25)
    assert _counter("mxtpu_serve_loop_step_seconds") == {
        "model=b,phase=emit,step=fanout": 1.0}
    telemetry.reset()                   # in place: the adder stays good
    add(2.0)
    assert _m.LOOP_STEP_SECONDS.value == 2.0


def test_generation_dispatches_feed_no_batch_series(batcher):
    """``mxtpu_serve_batches`` / ``mxtpu_serve_batch_size`` are the
    one-shot ``DynamicBatcher``'s: a generation dispatch is counted by
    its tokens' path, the dispatch ledger and the slots in use."""
    b0, n0 = _m.BATCHES.value, _m.BATCH_SIZE.count
    t0 = _counter("mxtpu_generate_tokens")
    _serve_some(batcher)
    assert (_m.BATCHES.value, _m.BATCH_SIZE.count) == (b0, n0)
    by = _delta(_counter("mxtpu_generate_tokens"), t0)
    assert sum(by.values()) == 34 and by["model=ph,path=burst"] > 0
    assert batcher.stats()["decode_burst_dispatches"] > 0


def test_idle_worker_opens_no_span_outside_a_capture(batcher):
    batcher.submit([3, 7], max_new_tokens=4)
    time.sleep(0.2)                     # the worker is back in its wait
    telemetry.tracer.enable()
    try:
        telemetry.tracer.clear()
        time.sleep(0.3)                 # idle polls with the tracer active
        assert telemetry.tracer.tree()["finished"] == []
        telemetry.tracer.annotate = FakeAnnotation  # as a capture sets it
        time.sleep(0.3)
        telemetry.tracer.annotate = None
        names = [d["name"] for d in telemetry.tracer.tree()["finished"]]
    finally:
        telemetry.tracer.disable()
    assert names and set(names) == {"serve.wait"}


# ------------------------------------------------------ tokens by path
def test_tokens_by_path_sum_to_tokens_emitted(batcher):
    c0 = _counter("mxtpu_generate_tokens")
    g0 = _counter("mxtpu_serve_burst_gate")
    # a long stream alone takes bursts; a second request queued behind a
    # full slot set forces the per-step path while it waits
    first = batcher.submit_async([3, 7, 11], max_new_tokens=40)
    second = batcher.submit_async([5, 2], max_new_tokens=30)
    third = batcher.submit_async([9, 4, 1], max_new_tokens=5)
    outs = [r.result(60) for r in (first, second, third)]
    emitted = sum(len(o) for o in outs)
    by = _delta(_counter("mxtpu_generate_tokens"), c0)
    by = {k.split("path=")[1]: v for k, v in by.items()}
    assert sum(by.values()) == emitted == 75
    assert by["prefill"] == 3
    assert by["burst"] > 0 and by["step"] > 0
    assert batcher.stats()["tokens_by_path"] == {
        "prefill": 3, "step": by["step"], "burst": by["burst"], "spec": 0}
    gates = _delta(_counter("mxtpu_serve_burst_gate"), g0)
    assert gates.get("model=ph,reason=queue", 0) >= 1
    # a per-step dispatch emits one token per live slot
    assert by["step"] / 2 <= sum(gates.values()) <= by["step"]


def test_batch_span_names_its_path_and_gate(batcher):
    telemetry.tracer.enable()
    try:
        rs = [batcher.submit_async([3, 7, 11], max_new_tokens=30),
              batcher.submit_async([5, 2], max_new_tokens=30),
              batcher.submit_async([9, 4], max_new_tokens=4)]
        for r in rs:
            r.result(60)
        # the last answer is handed over inside the last serve.batch
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            tree = telemetry.tracer.tree(max_finished=None)
            if not tree["live"]:
                break
            time.sleep(0.01)
    finally:
        telemetry.tracer.disable()
    batches = [d for d in tree["finished"] if d["name"] == "serve.batch"]
    assert batches
    paths = {d["attrs"]["path"] for d in batches}
    assert paths == {"burst", "step"}
    for d in batches:
        assert ("gate" in d["attrs"]) == (d["attrs"]["path"] == "step")
    assert "queue" in {d["attrs"].get("gate") for d in batches}
    # one loop iteration: admit outside, then operands -> wait -> emit
    kids = [c["name"] for c in batches[-1]["children"]]
    assert kids[-3:] == ["serve.operands", "serve.decode.wait",
                         "serve.emit"]
    admits = [d for d in tree["finished"] if d["name"] == "serve.admit"]
    assert len(admits) >= len(batches) - 1


# ------------------------------------------- the capture, on the CPU
def test_capture_holds_the_program_spans(batcher, tmp_path):
    from jax.profiler import ProfileData
    stop = threading.Event()

    def load():
        while not stop.is_set():
            batcher.submit([3, 7, 11], max_new_tokens=40)

    batcher.submit([3, 7], max_new_tokens=4)
    t = threading.Thread(target=load, daemon=True)
    t.start()
    try:
        assert not telemetry.tracer.active
        path = telemetry_device.capture_profile(0.3,
                                                out_dir=str(tmp_path))
    finally:
        stop.set()
        t.join(60)
    # the capture's reference on the tracer is gone, and so is the bridge
    assert not telemetry.tracer.active
    assert telemetry.tracer.annotate is None
    xplane = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                       recursive=True)
    assert xplane
    names = {}
    for plane in ProfileData.from_file(xplane[-1]).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(("serve.", "slot.")):
                    names[ev.name] = names.get(ev.name, 0) + 1
    for want in ("serve.batch", "serve.admit", "serve.operands",
                 "serve.decode.wait", "serve.emit", "serve.prefill",
                 "serve.prefill.wait"):
        assert names.get(want, 0) >= 1, (want, names)
    with open(os.path.join(path, "spans.json")) as f:
        spans = json.load(f)
    seen = set()

    def walk(d):
        seen.add(d["name"])
        for c in d.get("children", []):
            walk(c)
    for d in spans["finished"] + spans["live"]:
        walk(d)
    assert {"serve.batch", "serve.operands", "serve.decode.wait",
            "serve.emit"} <= seen
    # outside a capture a plain server records nothing
    telemetry.tracer.clear()
    batcher.submit([3, 7], max_new_tokens=4)
    assert telemetry.tracer.tree()["finished"] == []


@pytest.fixture(scope="module")
def captured(engine, tmp_path_factory):
    """One capture over a worker that joins, bursts, leaves and idles:
    ``(spans.json, the serve.* event names of the profiler's trace)``."""
    from jax.profiler import ProfileData
    telemetry.stop()
    telemetry.tracer.annotate = None
    engine.reset()
    batcher = ContinuousBatcher(engine)
    stop = threading.Event()

    def load():
        seed = 0
        while not stop.is_set():
            seed += 1
            batcher.submit([1 + (seed + i) % 48 for i in range(8)],
                           max_new_tokens=12)
            time.sleep(0.12)            # the worker idles between requests

    try:
        batcher.submit([3, 7], max_new_tokens=4)
        t = threading.Thread(target=load, daemon=True)
        t.start()
        try:
            path = telemetry_device.capture_profile(
                0.6, out_dir=str(tmp_path_factory.mktemp("capture")))
        finally:
            stop.set()
            t.join(60)
    finally:
        batcher.close()
    events = set()
    xplane = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                       recursive=True)
    for plane in ProfileData.from_file(xplane[-1]).planes:
        for line in plane.lines:
            events.update(ev.name for ev in line.events
                          if ev.name.startswith("serve."))
    with open(os.path.join(path, "spans.json")) as f:
        return json.load(f), events


@pytest.mark.parametrize("span,under", [
    ("serve.join.hash", "serve.admit"), ("serve.join.hash", "serve.prefill"),
    ("serve.join.alloc", "serve.prefill"), ("serve.join.alloc", "serve.emit"),
    ("serve.join.sampling", "slot.join"),
    ("serve.params", "serve.operands"), ("serve.carry", "serve.operands"),
    ("serve.edit", "serve.operands"), ("serve.edit", "serve.prefill"),
    ("serve.enqueue", "serve.operands"), ("serve.enqueue", "serve.prefill"),
    ("serve.emit.fanout", "serve.emit"), ("serve.emit.finish", "serve.emit"),
    ("serve.wait", None), ("serve.admit.lock", None)])
def test_capture_nests_the_step_spans_under_their_phase(captured, span,
                                                        under):
    spans, events = captured
    found = []

    def walk(d, above):
        if d["name"] == span:
            found.append(above)
        for c in d.get("children", []):
            walk(c, above + [d["name"]])
    for d in spans["finished"] + spans["live"]:
        walk(d, [])
    assert found, span
    if under is None:                   # the idle worker's and the lock's
        assert all(above == [] for above in found)      # are roots
    else:
        assert any(under in above for above in found), (span, found)
    # and the step's annotation lies in the profiler's own trace
    assert span in events


# -------------------------------------- one request, end to end, /trace
def test_trace_endpoint_shows_a_request_through_the_loop(engine):
    telemetry.start()
    engine.reset()
    srv = ModelServer(port=0, host="127.0.0.1")
    srv.add_model("ph", engine)
    srv.start()
    try:
        url = f"http://127.0.0.1:{srv.port}"
        req = urllib.request.Request(
            url + "/v1/models/ph:generate",
            data=json.dumps({"tokens": [3, 7, 11],
                             "max_new_tokens": 40}).encode(),
            headers={"Content-Type": "application/json",
                     "x-request-id": "walk-1"})
        with urllib.request.urlopen(req, timeout=60) as r:
            assert len(json.loads(r.read())["tokens"]) == 40
        with urllib.request.urlopen(url + "/trace?request_id=walk-1",
                                    timeout=10) as r:
            body = json.loads(r.read())
        with urllib.request.urlopen(url + "/v1/models", timeout=10) as r:
            row = json.loads(r.read())["models"]["ph"]
    finally:
        srv.stop()
    # the clock's totals, next to each other
    assert set(row["loop_cpu_seconds"]) == set(row["loop_seconds"]) \
        == set(_m.PHASES)
    assert row["loop_step_seconds"]["prefill_host"]["enqueue"] > 0.0
    assert row["loop_step_seconds"]["emit"]["fanout"] > 0.0
    assert sum(row["loop_step_seconds"]["operands"].values()) \
        <= row["loop_seconds"]["operands"]
    root = body["spans"][0]
    assert root["name"] == "serve.request"
    kids = root["children"]
    assert kids[0]["name"] == "serve.queue"
    assert kids[0]["attrs"]["request_id"] == "walk-1"

    def find(d, name):
        if d["name"] == name:
            return d
        for c in d.get("children", []):
            hit = find(c, name)
            if hit:
                return hit
        return None
    join = find(root, "slot.join")
    prefill = find(join, "serve.prefill")
    wait = find(prefill, "serve.prefill.wait")
    assert join and prefill and wait
    for d in (join, prefill, find(root, "slot.leave")):
        assert d["attrs"]["request_id"] == "walk-1"
    assert wait["duration_s"] <= prefill["duration_s"]
    # from the request's start to the end of its last loop iteration the
    # children leave no more than a tenth uncovered (what follows is the
    # handler thread waking up and writing the answer)
    covered = sum(c["duration_s"] for c in kids)
    reach = max(c["start_s"] + c["duration_s"] for c in kids) \
        - root["start_s"]
    assert covered >= 0.9 * reach, (covered, reach, root)


# ------------------------------------------- the token gap on a burst
def test_emit_burst_observes_what_the_client_receives():
    req = _GenRequest(np.asarray([1, 2], np.int32), budget=16)
    c0 = _m.TOKEN_LATENCY.count
    time.sleep(0.05)
    gap = req._emit_burst([4, 5, 6, 7])
    samples = _m.TOKEN_LATENCY.state()["samples"]
    assert _m.TOKEN_LATENCY.count == c0 + 4
    assert samples[-4] == gap >= 0.05
    assert samples[-3:] == [0.0, 0.0, 0.0]
    assert req.tokens_out == [4, 5, 6, 7] and req._q.qsize() == 4
    # the per-step path: every token carries its own gap
    time.sleep(0.02)
    assert req._emit(8) >= 0.02


def test_slo_token_window_keeps_raw_burst_gaps(batcher):
    batcher.submit([3, 7, 11], max_new_tokens=13)
    snap = _slo.tracker.model("ph").snapshot()
    assert snap["token_window"] == 13
    gaps = list(_slo.tracker.model("ph")._token_window)
    # 1 prefill token, then bursts of 4: three gaps in four are zero
    assert sum(1 for g in gaps if g == 0.0) == 9
