"""Generation serving tests: GenerationEngine's
prefill/decode split against a full re-forward at every step, the
ContinuousBatcher's per-slot join/leave machinery (mid-flight join,
slot free on finish/cancel/deadline, watchdog restart mid-decode), the
``:generate`` HTTP route with SSE streaming, and the token-latency
SLI."""
import json
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from common import greedy_reference

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import fault, telemetry
from incubator_mxnet_tpu.base import MXNetError
from incubator_mxnet_tpu.models.gpt import GPTModel
from incubator_mxnet_tpu.serving import (Cancelled, ContinuousBatcher,
                                         DeadlineExceeded,
                                         GenerationEngine, ModelServer,
                                         RequestAborted,
                                         derive_prefill_buckets)
from incubator_mxnet_tpu.serving import metrics as smetrics
from incubator_mxnet_tpu.serving import slo as _slo


@pytest.fixture(autouse=True)
def _clean_state():
    fault.clear_plan()
    telemetry.stop()
    telemetry.reset()
    _slo.tracker.reset()
    yield
    fault.clear_plan()
    telemetry.stop()
    telemetry.reset()
    _slo.tracker.reset()


def _gpt(max_length=64, seed=3):
    mx.random.seed(seed)
    net = GPTModel(vocab_size=50, units=32, hidden_size=64,
                   num_layers=2, num_heads=2, max_length=max_length,
                   dropout=0.0)
    net.initialize(init=mx.init.Normal(0.6))
    net(mx.nd.array(np.zeros((1, 2), np.int32)))   # settle shapes
    return net


def _engine(max_slots=4, max_len=64, seed=3):
    net = _gpt(max_length=max_len, seed=seed)
    return net, GenerationEngine(net, name="g", max_slots=max_slots,
                                 max_len=max_len)


def test_derive_prefill_buckets():
    assert derive_prefill_buckets(128) == (8, 16, 32, 64, 128)
    assert derive_prefill_buckets(48) == (8, 16, 32, 48)
    assert derive_prefill_buckets(8) == (8,)
    with pytest.raises(MXNetError):
        derive_prefill_buckets(0)


# ------------------------------------------------------------- engine
def test_prefill_decode_matches_full_reforward_every_step():
    """The cached path must reproduce a full re-forward of the growing
    context at EVERY decode step — one wrong K/V write or position
    shows up as a divergence somewhere in the sequence."""
    net, eng = _engine()
    prompt = [3, 7, 11]
    out = eng.generate(prompt, max_new_tokens=20)
    assert len(out) == 20
    ctx = list(prompt)
    for i, tok in enumerate(out):
        logits = net(mx.nd.array(np.asarray([ctx], np.int32)))
        ref = int(np.argmax(np.asarray(logits.asnumpy())[0, -1]))
        assert tok == ref, f"step {i}: cached {tok} != re-forward {ref}"
        ctx.append(tok)


def test_engine_generate_matches_net_generate():
    net, eng = _engine()
    for prompt in ([5, 2], [9, 9, 4, 1], [1]):
        assert eng.generate(prompt, max_new_tokens=16) \
            == greedy_reference(net, prompt, 16)


def test_warmup_compiles_closed_program_set():
    _, eng = _engine()
    warmed = eng.warmup()
    assert warmed == eng.expected_programs
    n = eng.compiled_programs()
    eng.generate([4, 4, 4], max_new_tokens=8)
    eng.generate([2] * 17, max_new_tokens=8)     # different bucket
    eng.generate([2] * 17, max_new_tokens=8)     # prefix-cache hit path
    assert eng.compiled_programs() == n          # nothing new compiled


def test_prefill_validation():
    _, eng = _engine()
    with pytest.raises(MXNetError):
        eng.prefill(np.zeros(0, np.int32), 0)
    with pytest.raises(MXNetError):
        eng.prefill(np.zeros(eng.max_len, np.int32), 0)  # no room left
    with pytest.raises(MXNetError):
        eng.prefill(np.zeros(3, np.int32), eng.max_slots)


# ----------------------------------------------------- batcher: joins
def test_mid_flight_join_identical_to_solo():
    net, eng = _engine(max_slots=2, max_len=128)
    solo_long = eng.generate([9, 9, 4, 1], max_new_tokens=100)
    solo_short = eng.generate([3, 7, 11], max_new_tokens=5)
    eng.reset()

    batcher = ContinuousBatcher(eng, name="g")
    try:
        req_a = batcher.submit_async([9, 9, 4, 1], max_new_tokens=100)
        # let A prefill and start decoding, then join B mid-flight
        while not req_a.tokens_out:
            time.sleep(0.002)
        req_b = batcher.submit_async([3, 7, 11], max_new_tokens=5)
        got_b = req_b.result(timeout=30)
        got_a = req_a.result(timeout=30)
        assert got_a == solo_long       # rider unperturbed by the join
        assert got_b == solo_short      # joiner identical to solo
        assert len(req_a.tokens_out) > len(got_b)  # B left while A ran
        assert batcher.slots_in_use() == 0
        st = batcher.stats()
        assert st["kind"] == "generation"
        assert st["decode_steps"] > 0
        assert st["tokens_emitted"] == len(got_a) + len(got_b)
    finally:
        batcher.close()


def test_queued_request_admitted_when_slot_frees():
    _, eng = _engine(max_slots=1, max_len=64)
    refs = [eng.generate(p, max_new_tokens=10)
            for p in ([5, 2], [9, 9, 4, 1])]
    eng.reset()
    batcher = ContinuousBatcher(eng, name="g")
    try:
        reqs = [batcher.submit_async(p, max_new_tokens=10)
                for p in ([5, 2], [9, 9, 4, 1])]
        assert [r.result(timeout=30) for r in reqs] == refs
    finally:
        batcher.close()


# ------------------------------------------- slot free: cancel/deadline
def test_cancel_frees_slot_mid_decode():
    _, eng = _engine(max_slots=2, max_len=128)
    batcher = ContinuousBatcher(eng, name="g")
    cancelled0 = smetrics.CANCELLED.value
    try:
        req = batcher.submit_async([3, 7, 11], max_new_tokens=100)
        got = []
        for tok in req.stream(timeout=30):
            got.append(tok)
            if len(got) == 3:
                break               # closing the generator cancels
        deadline = time.monotonic() + 5
        while batcher.slots_in_use() and time.monotonic() < deadline:
            time.sleep(0.005)
        assert batcher.slots_in_use() == 0
        assert req.done and isinstance(req.error, Cancelled)
        assert smetrics.CANCELLED.value == cancelled0 + 1
    finally:
        batcher.close()


def test_deadline_mid_decode_frees_slot_with_decode_stage():
    _, eng = _engine(max_slots=2, max_len=128)
    eng.generate([3, 7, 11], max_new_tokens=1)  # compile OUTSIDE the
    eng.reset()                                 # 40ms deadline below
    batcher = ContinuousBatcher(eng, name="g")
    before = smetrics.DEADLINE_EXCEEDED.sample()
    before = before["by"].get("model=g,stage=decode", 0.0) \
        if isinstance(before, dict) else 0.0
    try:
        req = batcher.submit_async([3, 7, 11], max_new_tokens=120,
                                   timeout_ms=40)
        with pytest.raises(DeadlineExceeded):
            req.result(timeout=30)
        assert 0 < len(req.tokens_out) < 120   # died mid-decode
        deadline = time.monotonic() + 5
        while batcher.slots_in_use() and time.monotonic() < deadline:
            time.sleep(0.005)
        assert batcher.slots_in_use() == 0
        after = smetrics.DEADLINE_EXCEEDED.sample()
        assert isinstance(after, dict)
        assert after["by"].get("model=g,stage=decode", 0.0) == before + 1
    finally:
        batcher.close()


# --------------------------------------------------- watchdog restart
def test_watchdog_restart_mid_decode_fails_riders_with_ids():
    _, eng = _engine(max_slots=2, max_len=128)
    batcher = ContinuousBatcher(eng, name="g")
    try:
        # hang the 5th decode dispatch for 30s (well past any test
        # timeout) so the request wedges mid-flight
        fault.install_plan("serving.infer:hang:30@5")
        req = batcher.submit_async([3, 7, 11], max_new_tokens=100,
                                   request_id="rider-1")
        deadline = time.monotonic() + 10
        while not req.tokens_out and time.monotonic() < deadline:
            time.sleep(0.005)
        time.sleep(0.1)                 # let the hang engage
        reason = batcher.check_worker(hang_seconds=0.05)
        assert reason == "hung"
        with pytest.raises(RequestAborted) as ei:
            req.result(timeout=30)
        assert "rider-1" in str(ei.value)
        assert batcher.restarts == 1
        # the replacement worker clears stale slots at its first
        # boundary — poll briefly rather than racing it
        deadline = time.monotonic() + 5
        while batcher.slots_in_use() and time.monotonic() < deadline:
            time.sleep(0.005)
        assert batcher.slots_in_use() == 0
        assert batcher.active_request_ids() == {"queued": [],
                                                "inflight": []}
    finally:
        fault.clear_plan()
        batcher.close()


# ---------------------------------------------------------- HTTP route
def test_http_generate_route_stream_and_sync():
    _, eng = _engine(max_slots=2, max_len=64)
    solo = eng.generate([3, 7, 11], max_new_tokens=8)
    eng.reset()
    srv = ModelServer(port=0)
    srv.add_model("g", eng)
    srv.start()
    try:
        assert isinstance(srv.get_model("g"), ContinuousBatcher)
        base = f"http://127.0.0.1:{srv.port}"

        def post(body, headers=None):
            r = urllib.request.Request(
                base + "/v1/models/g:generate",
                data=json.dumps(body).encode(),
                headers={"Content-Type": "application/json",
                         **(headers or {})})
            return urllib.request.urlopen(r, timeout=30)

        # non-streaming
        r = post({"tokens": [3, 7, 11], "max_new_tokens": 8})
        out = json.loads(r.read())
        assert out["tokens"] == solo
        assert r.headers["X-Request-Id"] == out["request_id"]

        # streaming SSE with an explicit request id
        r = post({"tokens": [3, 7, 11], "max_new_tokens": 8,
                  "stream": True}, {"x-request-id": "sse-1"})
        assert r.headers["X-Request-Id"] == "sse-1"
        toks, events = [], []
        for line in r:
            line = line.strip()
            if line.startswith(b"event:"):
                events.append(line.split(b":", 1)[1].strip().decode())
            elif line.startswith(b"data:"):
                d = json.loads(line.split(b":", 1)[1])
                if "token" in d:
                    toks.append(d["token"])
                else:
                    assert d["request_id"] == "sse-1"
        assert toks == solo
        assert events and events[-1] == "done"

        # malformed body → 400 with request id
        try:
            post({"tokens": []})
            raise AssertionError("expected HTTP 400")
        except urllib.error.HTTPError as e:
            assert e.code == 400
            assert json.loads(e.read())["request_id"]
    finally:
        srv.stop()


# ------------------------------------------------------ token-gap SLI
def test_token_latency_sli(monkeypatch):
    monkeypatch.setenv("MXNET_SERVE_SLO_TOKEN_P99_MS", "5000")
    _, eng = _engine(max_slots=2)
    batcher = ContinuousBatcher(eng, name="g")
    try:
        batcher.submit_async([3, 7, 11],
                             max_new_tokens=10).result(timeout=30)
    finally:
        batcher.close()
    snap = _slo.tracker.model("g").snapshot()
    assert snap["token_window"] == 10
    assert snap["token_p99_seconds"] is not None
    assert snap["burn_rate"] == 0.0        # nothing near a 5s gap
    assert _slo.tracker.snapshot()["objectives"]["token_p99_ms"] == 5000
