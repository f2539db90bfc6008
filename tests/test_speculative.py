"""Speculative decoding tests: exact greedy draft-verify acceptance
(bit-identical to the cache-free oracle at accept-rate 1, accept-rate
0, and in between), the spec x prefix-cache x mid-flight-join matrix
through the ContinuousBatcher (a joining stream must not observe a
neighbor's rejected-token rollback), ``BlockPool.rewind``'s
refcount/COW safety, the closed compiled-program set (verify adds
exactly ONE program), the k-wide verify kernel interpreted under the
engine, per-request accepted/draft token accounting on the HTTP
surface, and ``ModelServer.preload``."""
import json
import urllib.request

import numpy as np
import pytest

from common import greedy_reference

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import fault, telemetry
from incubator_mxnet_tpu.base import MXNetError
from incubator_mxnet_tpu.models.gpt import GPTModel
from incubator_mxnet_tpu.serving import (BlockPool, ContinuousBatcher,
                                         GenerationEngine, ModelServer)
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


def _gpt(max_length=64, seed=3, units=32, hidden=64, layers=2, heads=2):
    mx.random.seed(seed)
    net = GPTModel(vocab_size=50, units=units, hidden_size=hidden,
                   num_layers=layers, num_heads=heads,
                   max_length=max_length, dropout=0.0)
    net.initialize(init=mx.init.Normal(0.6))
    net(mx.nd.array(np.zeros((1, 2), np.int32)))   # settle shapes
    return net


def _spec_pair(max_slots=2, max_len=64, spec_k=4, draft_seed=3, **kw):
    """Target + attached draft over the same slot geometry.  With
    ``draft_seed=3`` the draft IS the target (accept rate 1); any other
    seed gives an honest independent draft."""
    tnet = _gpt(max_length=max_len, seed=3)
    dnet = tnet if draft_seed == 3 else _gpt(max_length=max_len,
                                             seed=draft_seed)
    tgt = GenerationEngine(tnet, name="tgt", max_slots=max_slots,
                           max_len=max_len, **kw)
    drf = GenerationEngine(dnet, name="drf", max_slots=max_slots,
                           max_len=max_len, **kw)
    tgt.attach_draft(drf, spec_k=spec_k)
    return tgt


def _golden(prompts, max_new=12, max_len=64):
    """What the target must emit whatever the draft proposes: the
    cache-free greedy continuation of the target's own weights."""
    net = _gpt(max_length=max_len)
    return [greedy_reference(net, p, max_new) for p in prompts]


def _contrarian_draft(eng):
    """Make ``eng``'s draft always propose a token the target will NOT
    pick next (its own sample, perturbed) -> accept rate 0, every step
    emits exactly the target's bonus token and rolls the rest back.
    Returns the list the perturbed dispatches are counted in."""
    calls = []
    real_decode, real_burst = eng.draft.decode, eng.draft.decode_burst

    def decode(last, pos):
        calls.append("decode")
        return (np.asarray(real_decode(last, pos)) + 1) % 50

    def decode_burst(*a, **kw):
        calls.append("burst")
        toks, emitted = real_burst(*a, **kw)
        return (toks + 1) % 50, emitted

    eng.draft.decode, eng.draft.decode_burst = decode, decode_burst
    return calls


PROMPTS = [[3, 7, 11, 2], [5, 5, 9], [1, 2, 3, 4, 5, 6]]


# ===================================================== BlockPool.rewind
def test_rewind_private_blocks_is_identity():
    pool = BlockPool(8, 4, model="t")
    table, shared, _ = pool.allocate([1, 2, 3, 4, 5], 5, 12, share=False)
    assert shared == 0
    out = pool.rewind(table, keep_tokens=6)
    assert out == table                     # exclusive + unpublished
    assert pool.rewinds == 0                # nothing to COW


def test_rewind_cows_published_tail_block():
    pool = BlockPool(8, 4, model="t")
    # 8 prompt tokens = 2 full blocks, both published in the prefix
    # cache; the reservation extends into a third (private) block
    toks = [1, 2, 3, 4, 5, 6, 7, 8]
    table, shared, _ = pool.allocate(toks, 8, 12, share=True)
    assert shared == 0                      # cold: registered, not hit
    # a rewind that dirties the whole published second block (keep only
    # the first block's 4 tokens) must unpublish it so the overwrite
    # can't serve a later prefix-cache hit
    out = pool.rewind(table, keep_tokens=4)
    assert out[0] == table[0]               # clean block untouched
    assert pool.rewinds == 1
    # the dirty block is now private: a second identical prompt shares
    # at most the first block
    t2, shared2, _ = pool.allocate(toks, 8, 12, share=True)
    assert shared2 <= 4


def test_rewind_shared_block_gets_private_copy():
    pool = BlockPool(10, 4, model="t")
    toks = [1, 2, 3, 4, 5, 6, 7, 8, 9]
    t1, _, _ = pool.allocate(toks, 9, 12, share=True)
    t2, shared, _ = pool.allocate(toks, 9, 12, share=True)
    assert shared == 8                      # both full blocks reused
    # t2 rewinds into its shared second block: must get a fresh id,
    # t1's view stays intact
    out = pool.rewind(t2, keep_tokens=4)
    assert out[0] == t2[0]
    assert out[1] != t2[1]
    assert pool.cow_copies >= 1
    assert t1[1] == t2[1]                   # neighbor untouched


def test_rewind_refuses_cow_of_kept_positions():
    pool = BlockPool(10, 4, model="t")
    toks = [1, 2, 3, 4, 5, 6, 7, 8, 9]
    t1, _, _ = pool.allocate(toks, 9, 12, share=True)
    t2, shared, _ = pool.allocate(toks, 9, 12, share=True)
    assert shared == 8
    # keeping 6 tokens means block 1 (positions 4..7) holds kept
    # positions AND is shared — rolling it back on the host would lose
    # the kept K/V, so the pool must refuse
    with pytest.raises(MXNetError):
        pool.rewind(t2, keep_tokens=6)


# ============================================ exact acceptance, engine
def test_accept_rate_one_bitwise_identical():
    golden = _golden(PROMPTS)
    eng = _spec_pair(max_slots=2)           # draft == target weights
    for p, g in zip(PROMPTS, golden):
        assert eng.generate(p, max_new_tokens=12, speculative=True) == g


def test_adversarial_draft_still_bitwise_identical():
    golden = _golden(PROMPTS)
    eng = _spec_pair(max_slots=2)
    calls = _contrarian_draft(eng)
    for p, g in zip(PROMPTS, golden):
        assert eng.generate(p, max_new_tokens=12, speculative=True) == g
    # one draft dispatch per emitted token after the first: nothing the
    # draft proposed was ever accepted
    assert len(calls) == len(PROMPTS) * 11


def test_independent_draft_bitwise_identical():
    golden = _golden(PROMPTS)
    eng = _spec_pair(max_slots=2, draft_seed=7)
    for p, g in zip(PROMPTS, golden):
        assert eng.generate(p, max_new_tokens=12, speculative=True) == g


def test_attach_draft_validations():
    tgt = GenerationEngine(_gpt(), name="t", max_slots=2, max_len=64)
    with pytest.raises(MXNetError):
        tgt.attach_draft(tgt)               # cannot draft itself
    small = GenerationEngine(_gpt(seed=5), name="d", max_slots=1,
                             max_len=64)
    with pytest.raises(MXNetError):
        tgt.attach_draft(small)             # slot mismatch
    short = GenerationEngine(_gpt(max_length=32, seed=5), name="d2",
                             max_slots=2, max_len=32)
    with pytest.raises(MXNetError):
        tgt.attach_draft(short)             # draft max_len too small
    ok = GenerationEngine(_gpt(seed=5), name="d3", max_slots=2,
                          max_len=64)
    with pytest.raises(MXNetError):
        tgt.attach_draft(ok, spec_k=0)      # k must be >= 1


def test_spec_k_env_default(monkeypatch):
    monkeypatch.setenv("MXNET_SPEC_K", "2")
    tgt = GenerationEngine(_gpt(), name="t", max_slots=2, max_len=64)
    drf = GenerationEngine(_gpt(seed=5), name="d", max_slots=2,
                           max_len=64)
    tgt.attach_draft(drf)
    assert tgt.spec_k == 2


# ====================================== closed compiled-program set
def test_verify_adds_exactly_one_program():
    eng = _spec_pair(max_slots=2)
    eng.warmup()
    assert eng.compiled_programs() == eng.expected_programs
    before = eng.compiled_programs()
    for p in PROMPTS:
        eng.generate(p, max_new_tokens=10, speculative=True)
        eng.generate(p, max_new_tokens=10, speculative=False)
    assert eng.compiled_programs() == before    # no per-accept recompile
    # detaching nothing: a plain engine's expectation is one fewer
    plain = GenerationEngine(_gpt(), name="plain", max_slots=2,
                             max_len=64)
    assert eng.expected_programs == plain.expected_programs + 1


# ===================================== batcher matrix: spec x prefix x join
def test_spec_batcher_matrix_mid_flight_joins():
    import threading
    import time as _time
    system = list(range(1, 33))             # 32-token shared prefix
    prompts = [system + [40 + i] for i in range(4)]
    golden = _golden(prompts, max_new=10)
    eng = _spec_pair(max_slots=2)           # 2 slots, 4 requests: the
    bat = ContinuousBatcher(eng, name="t")  # later two join mid-flight
    outs = [None] * 4
    errs = []

    def client(i):
        try:
            req = bat.submit_async(prompts[i], max_new_tokens=10)
            outs[i] = [t for t in req.stream(timeout=120)]
            outs[i] = (outs[i], req.accepted_tokens, req.draft_tokens)
        except Exception as e:              # pragma: no cover
            errs.append(f"{i}: {e!r}")

    try:
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
            _time.sleep(0.05)               # staggered joins
        for t in threads:
            t.join()
    finally:
        bat.close()
    assert not errs, errs
    for i in range(4):
        toks, acc, drafted = outs[i]
        assert toks == golden[i], (i, toks, golden[i])
        assert drafted >= acc >= 0
    # the matrix includes prefix hits
    assert eng.pool.stats()["prefix_cache_hits"] > 0


def test_joining_stream_unaffected_by_neighbor_rollback():
    """Slot A runs an adversarial draft (rollback EVERY step) while B
    joins mid-flight; B's stream must equal the plain golden."""
    import threading
    import time as _time
    golden = _golden(PROMPTS, max_new=12)
    eng = _spec_pair(max_slots=2)
    calls = _contrarian_draft(eng)          # accept rate 0 everywhere
    bat = ContinuousBatcher(eng, name="t")
    outs = [None, None]
    errs = []

    def client(i, delay):
        try:
            _time.sleep(delay)
            req = bat.submit_async(PROMPTS[i], max_new_tokens=12)
            outs[i] = list(req.stream(timeout=120))
        except Exception as e:              # pragma: no cover
            errs.append(f"{i}: {e!r}")

    try:
        a = threading.Thread(target=client, args=(0, 0.0))
        b = threading.Thread(target=client, args=(1, 0.3))
        a.start()
        b.start()
        a.join()
        b.join()
    finally:
        bat.close()
    assert not errs, errs
    assert outs[0] == golden[0]
    assert outs[1] == golden[1]
    assert calls                            # rollback path exercised


def test_batcher_spec_stats_and_gauge():
    from incubator_mxnet_tpu.serving import metrics as _m
    eng = _spec_pair(max_slots=2)
    bat = ContinuousBatcher(eng, name="t")
    try:
        req = bat.submit_async(PROMPTS[0], max_new_tokens=12)
        req.result(120)
        st = bat.stats()
        assert st["spec_k"] == 4
        assert st["spec_dispatches"] > 0
        assert st["accepted_tokens_per_dispatch"] > 1.0
        assert 0.0 <= st["spec_accept_rate"] <= 1.0
        vals = _m.SPEC_TOKENS_PER_DISPATCH._values
        assert any(v > 1.0 for v in vals.values()), vals
    finally:
        bat.close()


# ==================================== k-wide verify kernel, forced Pallas
def test_paged_engine_parity_with_forced_pallas_verify(monkeypatch):
    golden = _golden(PROMPTS)
    monkeypatch.setenv("MXNET_FA_DECODE_FORCE_PALLAS", "1")
    # block_size 8 (divisible by 8) keeps the paged kernel's alignment
    # gate open so the interpreted Pallas path actually runs
    eng = _spec_pair(max_slots=2, block_size=8)
    for p, g in zip(PROMPTS, golden):
        assert eng.generate(p, max_new_tokens=12, speculative=True) == g
    assert eng.program_inventory()["paged_attention"] == "pallas"


# =========================================== HTTP surface + preload
@pytest.mark.slow  # 64 s alone: preload compiles both whole program sets
def test_http_spec_fields_and_preload():
    eng = _spec_pair(max_slots=2)
    srv = ModelServer(port=0)
    srv.add_model("g", eng)
    srv.preload()                           # warm BEFORE binding
    assert eng.warm and eng.draft.warm
    progs_before = eng.compiled_programs()
    srv.start()
    base = f"http://127.0.0.1:{srv.port}"
    try:
        ready = urllib.request.urlopen(f"{base}/readyz", timeout=10)
        assert ready.status == 200          # never serves cold
        body = json.dumps({"tokens": PROMPTS[0],
                           "max_new_tokens": 10}).encode()
        req = urllib.request.Request(f"{base}/v1/models/g:generate",
                                     data=body)
        resp = urllib.request.urlopen(req, timeout=60)
        rid = resp.headers.get("X-Request-Id")
        out = json.load(resp)
        assert out["count"] == 10
        assert out["draft_tokens"] > 0
        assert 0 <= out["accepted_tokens"] <= out["draft_tokens"]
        assert rid and out["request_id"]    # id parity on new fields
        # streaming done event carries the same accounting
        body = json.dumps({"tokens": PROMPTS[1], "max_new_tokens": 10,
                           "stream": True}).encode()
        req = urllib.request.Request(f"{base}/v1/models/g:generate",
                                     data=body)
        text = urllib.request.urlopen(req, timeout=60).read().decode()
        done = [json.loads(line[len("data: "):])
                for line in text.splitlines()
                if line.startswith("data: ")][-1]
        assert done["draft_tokens"] > 0
        assert "accepted_tokens" in done and "request_id" in done
        # the spec gauge is on /metrics under its exact exported name
        prom = urllib.request.urlopen(f"{base}/metrics",
                                      timeout=10).read().decode()
        assert "mxtpu_spec_accepted_tokens_per_dispatch" in prom
        # preload really did compile everything: serving added nothing
        assert eng.compiled_programs() == progs_before
    finally:
        srv.stop()
