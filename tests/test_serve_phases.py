"""The serving worker loop measured from inside (docs/observability.md
"Worker-loop phases"): the tracer's bridge into the profiler's trace,
``capture_profile`` with the program's spans in the capture, the phase
clock of the ``ContinuousBatcher`` worker (counters and spans cut at the
same boundaries), queue wait, tokens by path, the burst gate, and the
token-gap histogram on bursts."""
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
    eng = GenerationEngine(_gpt(), name="ph", max_slots=2, max_len=64,
                           prefill_buckets=[8], scan_steps=4)
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
    finally:
        srv.stop()
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
