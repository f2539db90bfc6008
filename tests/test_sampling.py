"""Sampling-plane tests (docs/serving.md "Sampling"): seeded
bit-identity across temperature/top-k/top-p x per-step/burst x
spec-on/off, temperature->0 greedy parity with the cache-free oracle,
Gumbel-coupled speculative sampling preserving the no-draft sampled
stream bit-for-bit, per-token logprobs, multi-token stop sequences,
JSON-mode constrained output, n>1 candidate fan-out, and the seed
replay contract over HTTP."""
import json
import urllib.error
import urllib.request

import numpy as np
import pytest

from common import greedy_reference

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import fault, telemetry
from incubator_mxnet_tpu.models.gpt import GPTModel
from incubator_mxnet_tpu.serving import (ContinuousBatcher,
                                         GenerationEngine, ModelServer,
                                         SamplingParams)
from incubator_mxnet_tpu.serving import slo as _slo
from incubator_mxnet_tpu.serving.sampling import (JsonMaskMachine,
                                                  derive_candidate_seed,
                                                  root_key, stop_trim)


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


def _gpt(vocab=50, seed=3):
    mx.random.seed(seed)
    net = GPTModel(vocab_size=vocab, units=32, hidden_size=64,
                   num_layers=2, num_heads=2, max_length=64,
                   dropout=0.0)
    net.initialize(init=mx.init.Normal(0.6))
    net(mx.nd.array(np.zeros((1, 2), np.int32)))
    return net


PROMPT = [3, 1, 4, 1, 5]


@pytest.fixture(scope="module")
def _net():
    return _gpt()


@pytest.fixture(scope="module")
def paged_eng(_net):
    return GenerationEngine(_net, name="smp-p", max_slots=2, max_len=64,
                            block_size=8, prefix_cache=False,
                            scan_steps=4, logprobs_topn=3)


# ------------------------------------------------------------ unit layer
def test_validate_rejects_bad_params():
    for bad in (SamplingParams(temperature=-0.1),
                SamplingParams(top_p=0.0),
                SamplingParams(top_k=-1),
                SamplingParams(logprobs=-1),
                SamplingParams(seed=2 ** 63),
                SamplingParams(n=0),
                SamplingParams(stop=((),)),
                SamplingParams(stop=(tuple(range(99)),)),
                SamplingParams(stop=((1,),) * 9)):
        with pytest.raises(ValueError):
            bad.validate()
    ok = SamplingParams(temperature=0.5, stop=([4, 2], 7)).validate()
    assert ok.stop == ((4, 2), (7,))
    with pytest.raises(ValueError):
        SamplingParams(n=3).validate(max_n=2)


def test_root_key_matches_prngkey():
    import jax
    for seed in (0, 1, 42, 2 ** 62 + 17):
        assert np.array_equal(root_key(seed),
                              np.asarray(jax.random.PRNGKey(seed)))


def test_derive_candidate_seed():
    assert derive_candidate_seed(99, 0) == 99
    seeds = {derive_candidate_seed(99, i) for i in range(8)}
    assert len(seeds) == 8
    assert all(0 <= s < 2 ** 63 for s in seeds)


def test_stop_trim():
    # stop completes mid-burst: keep through the stop, drop the tail
    assert stop_trim([1, 2], [3, 4, 5, 6], ((3, 4),)) == (2, True)
    # stop spans the previous emit boundary
    assert stop_trim([1, 7], [8, 5], ((7, 8),)) == (1, True)
    # no stop anywhere
    assert stop_trim([1, 2], [3, 4], ((9,),)) == (2, False)
    # earliest of several stops wins
    assert stop_trim([], [1, 2, 3], ((2,), (1, 2))) == (2, True)


def test_json_machine_accepts_and_closes():
    toks = [chr(i) for i in range(128)]
    m = JsonMaskMachine(toks)
    for ch in '{"a": [1, true, "x"]}':
        assert m.advance(ord(ch)), ch
    assert m.done
    # every char of a legal doc was inside the pre-advance mask
    m2 = JsonMaskMachine(toks)
    for ch in '[{"k": null}]':
        assert m2.mask()[ord(ch)] == 0.0
        m2.advance(ord(ch))
    assert m2.done
    # illegal top-level scalar and illegal transition
    m3 = JsonMaskMachine(toks)
    assert not m3.advance(ord("7"))
    assert m3.mask()[ord("}")] != 0.0


def test_json_machine_budget_forces_closure():
    toks = [chr(i) for i in range(128)]
    rng = np.random.RandomState(0)
    for budget in (2, 5, 9, 17):
        m = JsonMaskMachine(toks)
        remaining = budget
        while not m.done:
            legal = np.where(m.mask(budget=remaining) == 0.0)[0]
            assert legal.size, (budget, remaining, m._state)
            m.advance(int(rng.choice(legal)))
            remaining -= 1
        assert remaining >= 0


# ---------------------------------------------------------- engine layer
MATRIX = [SamplingParams(temperature=0.7, seed=11),
          SamplingParams(temperature=0.9, top_k=5, seed=11),
          SamplingParams(temperature=0.9, top_p=0.7, seed=11),
          SamplingParams(temperature=1.1, top_k=8, top_p=0.9, seed=11)]


def _burst_run(eng, prompt, budget, sp):
    """Drive ``decode_burst`` directly: the scanned path's sampled
    continuation for slot 0."""
    eng.set_slot_sampling(0, sp)
    out = [eng.prefill(np.asarray(prompt, np.int32), 0,
                       reserve_tokens=len(prompt) + budget)]
    S = eng.max_slots
    while len(out) < budget:
        last = np.zeros(S, np.int32)
        pos = np.zeros(S, np.int32)
        bud = np.ones(S, np.int32)
        eos = np.full(S, -1, np.int32)
        act = np.zeros(S, bool)
        last[0] = out[-1]
        pos[0] = len(prompt) + len(out) - 1
        bud[0] = budget - len(out)
        act[0] = True
        toks, emitted = eng.decode_burst(last, pos, bud, eos, act)
        n = int(emitted[0])
        assert n >= 1
        out += [int(t) for t in toks[:n, 0]]
    eng.release_slot(0)
    return out


def test_seeded_bit_identity_and_burst_parity(paged_eng, _net):
    eng = paged_eng
    greedy = greedy_reference(_net, PROMPT, 12)
    assert eng.generate(PROMPT, 12) == greedy
    for sp in MATRIX:
        s1 = eng.generate(PROMPT, 12, sampling=sp)
        # bit-identical across repeats at the same seed
        assert eng.generate(PROMPT, 12, sampling=sp) == s1
        # per-step and k-step burst walk the same keyed stream
        assert _burst_run(eng, PROMPT, 12, sp) == s1
        assert all(0 <= t < eng.vocab_size for t in s1)
    # a different seed diverges somewhere in the matrix
    alt = [eng.generate(PROMPT, 12, sampling=SamplingParams(
        temperature=sp.temperature, top_k=sp.top_k, top_p=sp.top_p,
        seed=12)) for sp in MATRIX]
    assert any(a != eng.generate(PROMPT, 12, sampling=sp)
               for a, sp in zip(alt, MATRIX))
    # temperature -> 0 is bit-for-bit the greedy contract, seed or not
    assert eng.generate(PROMPT, 12, sampling=SamplingParams(
        temperature=0.0, seed=7)) == greedy
    # the sampling operands are data, not programs: the closed set held
    assert eng.compiled_programs() <= eng.expected_programs


def test_spec_bit_identical_to_solo_sampled(_net):
    """Distribution preservation, in its strongest form: with the
    draft sampling the SAME keyed stream, every spec-emitted token
    equals the no-draft sampled run's token at any accept rate."""
    tgt = GenerationEngine(_net, name="smp-st", max_slots=2, max_len=64,
                           block_size=8, prefix_cache=False, scan_steps=0)
    dr = GenerationEngine(_gpt(seed=5), name="smp-sd", max_slots=2,
                          max_len=64, block_size=8,
                          prefix_cache=False, scan_steps=0)
    tgt.attach_draft(dr, spec_k=3)
    solo = GenerationEngine(_net, name="smp-ss", max_slots=2,
                            max_len=64, block_size=8,
                            prefix_cache=False, scan_steps=0)
    for sp in (SamplingParams(temperature=0.9, top_p=0.95, seed=1234),
               SamplingParams(temperature=0.7, seed=7),
               SamplingParams(temperature=0.0, seed=1)):
        assert tgt.generate(PROMPT, 12, sampling=sp) \
            == solo.generate(PROMPT, 12, sampling=sp)
    # greedy (no params) through spec is the temperature-0 special case
    assert tgt.generate(PROMPT, 12) == solo.generate(PROMPT, 12)


def test_first_token_frequency_matches_model(paged_eng, _net):
    """Seed-averaged frequency test: the sampled first token's
    empirical distribution tracks the model's temperature-1 softmax."""
    logits = _net(mx.nd.array(np.asarray([PROMPT], np.int32)))
    logits = np.asarray(logits.asnumpy())[0, len(PROMPT) - 1]
    p = np.exp(logits - logits.max())
    p /= p.sum()
    n = 48
    counts = np.zeros(p.size)
    for seed in range(n):
        tok = paged_eng.generate(PROMPT, 1, sampling=SamplingParams(
            temperature=1.0, seed=seed))[0]
        counts[tok] += 1
    emp = counts / n
    assert abs(emp - p).max() < 0.2         # ~3 sigma at n=48 for any p
    assert 0.5 * abs(emp - p).sum() < 0.35  # total variation


# --------------------------------------------------------- batcher layer
def test_batcher_seeded_replay_and_seed_echo(paged_eng):
    b = ContinuousBatcher(paged_eng, name="smp-p")
    try:
        sp = SamplingParams(temperature=0.8, top_k=10, seed=42)
        s1 = b.submit(PROMPT, 10, sampling=sp)
        assert b.submit(PROMPT, 10, sampling=sp) == s1
        # seedless sampled request: server picks + echoes a seed, and
        # replaying the echoed seed reproduces the tokens
        r = b.submit_async(PROMPT, 10,
                           sampling=SamplingParams(temperature=0.8,
                                                   top_k=10))
        toks = r.result(30)
        assert r.seed is not None
        assert b.submit(PROMPT, 10, sampling=SamplingParams(
            temperature=0.8, top_k=10, seed=r.seed)) == toks
    finally:
        b.close()


def test_batcher_logprobs_ride_along(paged_eng):
    b = ContinuousBatcher(paged_eng, name="smp-p")
    try:
        r = b.submit_async(PROMPT, 6, sampling=SamplingParams(
            temperature=0.8, seed=7, logprobs=9))
        toks = r.result(30)
        # one entry per emitted token (prefill's first token included),
        # clamped to the engine's baked top-N of 3
        assert len(r.logprobs_out) == len(toks)
        for e in r.logprobs_out:
            assert len(e["token_ids"]) == 3
            assert len(e["logprobs"]) == 3
            assert all(v <= 0.0 for v in e["logprobs"])
        # greedy requests can ask for logprobs too; the argmax token is
        # by construction the top-1 entry
        r2 = b.submit_async(PROMPT, 6, sampling=SamplingParams(
            logprobs=1))
        toks2 = r2.result(30)
        assert [e["token_ids"][0] for e in r2.logprobs_out] == toks2
    finally:
        b.close()


def test_batcher_stop_sequence_trims_burst(paged_eng):
    b = ContinuousBatcher(paged_eng, name="smp-p")
    try:
        sp = SamplingParams(temperature=0.8, seed=11)
        base = b.submit(PROMPT, 16, sampling=sp)
        stop = tuple(base[2:4])
        # where the stop is first complete in the sampled stream (the
        # pair may already occur before index 2)
        end = next(i for i in range(2, len(base) + 1)
                   if tuple(base[i - 2:i]) == stop)
        assert end <= 4
        got = b.submit(PROMPT, 16, sampling=SamplingParams(
            temperature=0.8, seed=11, stop=(stop,)))
        # stop sequence itself stays; the over-generated tail (the
        # burst ran past it) is discarded host-side
        assert got == base[:end]
        st = b.stats()
        assert st["stop_hits"] >= 1
        assert st["slots_in_use"] == 0
    finally:
        b.close()


def test_batcher_n_fanout_slot_accounting(paged_eng):
    b = ContinuousBatcher(paged_eng, name="smp-p")
    try:
        r = b.submit_async(PROMPT, 8, sampling=SamplingParams(
            temperature=0.9, seed=99, n=2))
        outs = r.results(60)
        assert len(outs) == 2
        # candidate 0 replays as a plain n=1 request at the echoed seed
        assert outs[0] == b.submit(PROMPT, 8, sampling=SamplingParams(
            temperature=0.9, seed=99))
        assert r.result(1) == outs[0]
        assert b.stats()["slots_in_use"] == 0
        with pytest.raises(ValueError):
            b.submit_async(PROMPT, 8, sampling=SamplingParams(
                temperature=0.9, n=99))
    finally:
        b.close()


def test_json_mode_output_parses():
    eng = GenerationEngine(_gpt(vocab=128, seed=7), name="smp-j",
                           max_slots=2, max_len=64,
                           prefix_cache=False, scan_steps=4)
    b = ContinuousBatcher(eng, name="smp-j")
    try:
        for seed in (5, 6):
            out = b.submit([1], 40, sampling=SamplingParams(
                temperature=0.9, seed=seed, json_mode=True))
            doc = json.loads("".join(chr(t) for t in out))
            assert isinstance(doc, (dict, list))
    finally:
        b.close()


# ------------------------------------------------------------ HTTP layer
def test_http_generate_sampling_fields(paged_eng):
    srv = ModelServer(port=0)
    srv.add_model("g", paged_eng)
    srv.start()
    try:
        base = f"http://127.0.0.1:{srv.port}"

        def post(body):
            r = urllib.request.Request(
                base + "/v1/models/g:generate",
                data=json.dumps(body).encode(),
                headers={"Content-Type": "application/json"})
            return urllib.request.urlopen(r, timeout=30)

        body = {"tokens": PROMPT, "max_new_tokens": 8,
                "temperature": 0.8, "top_k": 10, "seed": 42,
                "logprobs": 2}
        out = json.loads(post(body).read())
        assert out["seed"] == 42
        assert len(out["logprobs"]) == len(out["tokens"])
        assert all(len(e["token_ids"]) == 2 for e in out["logprobs"])
        # same seed, same bytes
        assert json.loads(post(body).read())["tokens"] == out["tokens"]
        # seedless sampled: the server picks a seed and echoes it
        out2 = json.loads(post({"tokens": PROMPT, "max_new_tokens": 8,
                                "temperature": 0.8}).read())
        assert isinstance(out2["seed"], int)
        # SSE: logprobs on token events, seed on the done event
        r = post(dict(body, stream=True))
        toks, seed_done, lp = [], None, []
        for line in r:
            line = line.strip()
            if line.startswith(b"data:"):
                d = json.loads(line.split(b":", 1)[1])
                if "token" in d:
                    toks.append(d["token"])
                    lp.append(d.get("logprobs"))
                elif "tokens" in d:
                    seed_done = d.get("seed")
        assert toks == out["tokens"]
        assert seed_done == 42
        assert all(e and len(e["token_ids"]) == 2 for e in lp)
        # n>1: candidates in the sync body; rejected when streaming
        out3 = json.loads(post({"tokens": PROMPT, "max_new_tokens": 6,
                                "temperature": 0.9, "seed": 5,
                                "n": 2}).read())
        assert len(out3["candidates"]) == 2
        assert out3["candidates"][0]["tokens"] == out3["tokens"]
        try:
            post({"tokens": PROMPT, "temperature": 0.9, "n": 2,
                  "stream": True})
            raise AssertionError("expected HTTP 400")
        except urllib.error.HTTPError as e:
            assert e.code == 400
        # out-of-range sampling params -> 400
        try:
            post({"tokens": PROMPT, "temperature": -1.0})
            raise AssertionError("expected HTTP 400")
        except urllib.error.HTTPError as e:
            assert e.code == 400
    finally:
        srv.stop()
