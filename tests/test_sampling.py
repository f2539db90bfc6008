"""Sampling-plane tests (docs/serving.md "Sampling"): seeded
bit-identity across temperature/top-k/top-p x per-step/burst x
spec-on/off, temperature->0 greedy parity with the cache-free oracle,
Gumbel-coupled speculative sampling preserving the no-draft sampled
stream bit-for-bit, per-token logprobs, multi-token stop sequences,
JSON-mode constrained output, n>1 candidate fan-out, and the seed
replay contract over HTTP; and the batch's one branch — a step in
which no live slot samples takes the argmax alone, bit-identically.
"""
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
from incubator_mxnet_tpu.serving import sampling as _sampling
from incubator_mxnet_tpu.serving.sampling import (MASK_OFF,
                                                  JsonMaskMachine,
                                                  derive_candidate_seed,
                                                  root_key, sample_tokens,
                                                  step_keys, stop_trim)


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


# ------------------------------------------------- the batch's one branch
def _reference_row(lg, temperature, top_k, top_p, bias, key):
    """The sampler as it stood before the batch-level branch, kept
    here verbatim: every slot pays sort, softmax, cumsum and noise, and
    ``where`` picks the argmax for a greedy one."""
    import jax
    import jax.numpy as jnp
    V = lg.shape[-1]
    lgb = (lg + bias).astype(jnp.float32)
    greedy = jnp.argmax(lgb, axis=-1).astype(jnp.int32)
    z = lgb / jnp.maximum(temperature.astype(jnp.float32), 1e-6)
    srt = jnp.sort(z)[::-1]
    kk = jnp.where(top_k <= 0, V, jnp.minimum(top_k, V))
    keep = z >= srt[kk - 1]
    probs = jax.nn.softmax(srt)
    before = jnp.cumsum(probs) - probs
    cutoff = jnp.min(jnp.where(before < top_p, srt, jnp.inf))
    keep &= z >= cutoff
    sampled = jnp.argmax(jnp.where(keep, z, MASK_OFF)
                         + _sampling._gumbel_row(key, V),
                         axis=-1).astype(jnp.int32)
    return jnp.where(temperature > 0.0, sampled, greedy)


def _reference_tokens(logits, temps, topks, topps, biases, keys):
    """The three callers' old expressions: a prefill's row, the step's
    ``vmap``, the verify grid's nested one."""
    import jax
    row = _reference_row
    if logits.ndim == 3:
        row = jax.vmap(row, in_axes=(0, None, None, None, None, 0))
    if logits.ndim > 1:
        row = jax.vmap(row)
    return row(logits, temps, topks, topps, biases, keys)


def _operands(shape, temps, seed=0):
    """Random sampler operands for ``shape`` = (V,), (S, V) or
    (S, Q, V): logits quantised so that every row holds ties (also at
    its maximum), slot 0 (or the row) under a ``MASK_OFF`` bias that
    leaves three tokens, top-k / top-p set on every slot."""
    rng = np.random.RandomState(seed)
    V = shape[-1]
    lead = shape[:1] if len(shape) > 1 else ()
    logits = np.round(rng.randn(*shape) * 2).astype(np.float32)
    best = logits.max(axis=-1, keepdims=True)
    logits[..., V // 2:] = np.where(rng.rand(*shape)[..., V // 2:] < 0.1,
                                    best, logits[..., V // 2:])
    biases = np.zeros(lead + (V,), np.float32)
    masked = biases[0] if lead else biases
    masked[:] = MASK_OFF
    masked[[5, 17, V - 1]] = 0.0
    temps = np.broadcast_to(np.asarray(temps, np.float32), lead).copy()
    topks = np.full(lead, 7, np.int32)
    topps = np.full(lead, 0.9, np.float32)
    roots = rng.randint(0, 2 ** 31, lead + (2,)).astype(np.uint32)
    pos = rng.randint(1, 60, shape[:-1]).astype(np.int32)
    keys = step_keys(roots[:, None, :] if len(shape) == 3 else roots, pos)
    return logits, temps, topks, topps, biases, keys


SHAPES = {"prefill": (97,), "step": (6, 97), "verify": (6, 3, 97)}


@pytest.mark.parametrize("kind", sorted(SHAPES))
def test_greedy_batch_takes_argmax_bit_identically(kind):
    """No slot samples: the branch's argmax is what the old sampler's
    ``where`` selected, ties and a masked row included."""
    import jax
    args = _operands(SHAPES[kind], 0.0)
    got = np.asarray(jax.jit(sample_tokens)(*args))
    assert np.array_equal(got, np.asarray(
        jax.jit(_reference_tokens)(*args)))
    want = np.argmax(args[0] + (args[4][:, None] if kind == "verify"
                                else args[4]), axis=-1)
    assert np.array_equal(got, want)
    assert got.dtype == np.int32


@pytest.mark.parametrize("kind", sorted(SHAPES))
def test_mixed_batch_bit_identical_to_old_sampler(kind):
    """One sampled slot among greedy ones (the row itself in a prefill)
    takes the full branch: every slot, greedy or not, as before."""
    import jax
    temps = 0.9 if kind == "prefill" else [0, 0, 0.9, 0, 0, 0]
    for seed in range(4):
        args = _operands(SHAPES[kind], temps, seed)
        live = None if kind == "prefill" else np.ones(6, bool)
        got = np.asarray(jax.jit(sample_tokens)(*args, live))
        assert np.array_equal(got, np.asarray(
            jax.jit(_reference_tokens)(*args)))


@pytest.mark.parametrize("kind", ["step", "verify"])
def test_slot_that_is_not_live_does_not_select_full(kind):
    """The predicate is masked by ``live``: a slot nobody reads, hot
    from the request that last held it, leaves the batch on the argmax
    (seen in its own token: the argmax, not the noise's choice)."""
    import jax
    temps = [0, 0, 5.0, 0, 0, 0]
    args = _operands(SHAPES[kind], temps)
    full = np.asarray(jax.jit(sample_tokens)(*args, np.ones(6, bool)))
    cold = np.asarray(jax.jit(sample_tokens)(*_operands(SHAPES[kind],
                                                        0.0)))
    assert not np.array_equal(full[2], cold[2])     # the noise shows
    live = np.ones(6, bool)
    live[2] = False
    assert np.array_equal(
        np.asarray(jax.jit(sample_tokens)(*args, live)), cold)


def _drive(eng, reqs, burst):
    """Run ``reqs`` — {slot: (prompt, budget, SamplingParams or None)}
    — side by side through per-step decode or ``decode_burst`` until
    each has its budget; {slot: tokens}."""
    S = eng.max_slots
    out = {}
    for s, (prompt, budget, sp) in reqs.items():
        eng.set_slot_sampling(s, sp)
        out[s] = [eng.prefill(np.asarray(prompt, np.int32), s,
                              reserve_tokens=len(prompt) + budget)]
    live = set(reqs)
    while live:
        for s in [s for s in live if len(out[s]) >= reqs[s][1]]:
            eng.release_slot(s)
            live.discard(s)
        if not live:
            break
        last, pos = np.zeros(S, np.int32), np.zeros(S, np.int32)
        bud, act = np.ones(S, np.int32), np.zeros(S, bool)
        for s in live:
            last[s] = out[s][-1]
            pos[s] = len(reqs[s][0]) + len(out[s]) - 1
            bud[s] = reqs[s][1] - len(out[s])
            act[s] = True
        if burst:
            toks, emitted = eng.decode_burst(
                last, pos, bud, np.full(S, -1, np.int32), act)
            for s in live:
                out[s] += [int(t) for t in toks[:int(emitted[s]), s]]
        else:
            nxt = eng.decode(last, pos)
            for s in live:
                out[s].append(int(nxt[s]))
    return out


def _delta(eng, before):
    return {k: v - before[k] for k, v in eng.sample_dispatches().items()}


@pytest.mark.parametrize("burst", [False, True], ids=["step", "burst"])
def test_mixed_engine_batch_matches_solo_runs(paged_eng, _net, burst):
    """A seeded sampled slot beside a greedy one: each emits what it
    emits alone, and the sampled one, budget 6 of the greedy one's 14,
    ends inside a burst whose later steps (and the dispatches after it,
    its slot released with its temperature still in the row) are greedy
    again."""
    eng = paged_eng
    sp = SamplingParams(temperature=0.9, top_k=8, top_p=0.9, seed=11)
    other = [2, 7, 1, 8]
    solo = eng.generate(PROMPT, 6, sampling=sp)
    greedy = greedy_reference(_net, other, 14)
    before = eng.sample_dispatches()
    out = _drive(eng, {0: (PROMPT, 6, sp), 1: (other, 14, None)}, burst)
    assert out[0] == solo
    assert out[1] == greedy
    assert eng._samp_temp[0] > 0            # released, not cleared
    got = _delta(eng, before)
    # sampled tokens 2..6 need 5 steps or 2 bursts of 4; the greedy
    # slot's other 8 steps or 2 bursts run with slot 0 freed
    assert got == ({"full": 2, "greedy": 2} if burst
                   else {"full": 5, "greedy": 8})


def test_sample_dispatches_counter_and_models_line(paged_eng):
    """A greedy run counts ``greedy`` alone; one sampled join flips the
    next dispatch to ``full``; the slot it leaves behind flips nothing.
    ``GET /v1/models`` and the registry carry the same counts."""
    eng = paged_eng
    b = ContinuousBatcher(eng, name="smp-p")
    try:
        before = eng.sample_dispatches()
        b.submit(PROMPT, 10)
        got = _delta(eng, before)
        assert got["full"] == 0 and got["greedy"] >= 2
        before = eng.sample_dispatches()
        b.submit(PROMPT, 10, sampling=SamplingParams(temperature=0.8,
                                                     seed=3))
        got = _delta(eng, before)
        assert got["greedy"] == 0 and got["full"] >= 2
        before = eng.sample_dispatches()
        b.submit(PROMPT, 10)
        got = _delta(eng, before)
        assert got["full"] == 0 and got["greedy"] >= 2
        assert b.stats()["sample_dispatches"] == eng.sample_dispatches()
        series = telemetry.registry.get(
            "mxtpu_sample_dispatches").sample()["by"]
    finally:
        b.close()
    for branch in ("greedy", "full"):
        assert series[f"branch={branch},model=smp-p"] > 0


def test_spec_greedy_counts_greedy_on_both_engines(_net):
    """Verify dispatches count too, and a greedy speculative run keeps
    target and draft on the argmax — also the draft's burst, which runs
    EVERY slot (``spec_step``), beside a slot that holds no table and
    still carries a temperature."""
    tgt = GenerationEngine(_net, name="smp-ct", max_slots=2, max_len=64,
                           block_size=8, prefix_cache=False, scan_steps=0)
    dr = GenerationEngine(_gpt(seed=5), name="smp-cd", max_slots=2,
                          max_len=64, block_size=8, prefix_cache=False)
    tgt.attach_draft(dr, spec_k=3)
    tgt.set_slot_sampling(1, SamplingParams(temperature=0.9, seed=3))
    assert dr._samp_temp[1] > 0
    assert tgt.generate(PROMPT, 12) == greedy_reference(_net, PROMPT, 12)
    for eng in (tgt, dr):
        got = eng.sample_dispatches()
        assert got["full"] == 0 and got["greedy"] >= 2
    tgt.generate(PROMPT, 12, sampling=SamplingParams(temperature=0.7,
                                                     seed=7))
    assert tgt.sample_dispatches()["full"] >= 2
    assert dr.sample_dispatches()["full"] >= 2


def _sorts_guarded(text):
    """Of StableHLO ``text``: (its ``sort`` operations, whether every
    path from ``main`` to each passes through a ``stablehlo.case``
    branch).  jax outlines ``jnp.sort`` into a private function, so a
    sort's guard may be the ``case`` around a call of its function."""
    import re
    lines = text.splitlines()
    where, fn, depth, cases = [], None, 0, []
    for line in lines:
        if "func.func" in line:
            fn, depth, cases = line.split("@")[1].split("(")[0], 0, []
        where.append((fn, bool(cases)))     # the lines a case encloses
        if '"stablehlo.case"' in line:
            cases.append(depth)
        depth += line.count("({") - line.count("})")
        while cases and depth <= cases[-1]:
            cases.pop()

    def guarded(i, seen=()):
        fn, in_case = where[i]
        if in_case:
            return True
        if fn == "main" or fn in seen:
            return False
        calls = [j for j, l in enumerate(lines)
                 if re.search(rf"call @{re.escape(fn)}\(", l)]
        return bool(calls) and all(guarded(j, seen + (fn,))
                                   for j in calls)

    sorts = [i for i, l in enumerate(lines) if "stablehlo.sort" in l]
    return len(sorts), all(guarded(i) for i in sorts)


@pytest.mark.parametrize("program", ["decode_burst", "decode", "verify"])
def test_lowered_program_sorts_only_inside_the_branch(paged_eng, program):
    """The lowered text holds exactly one ``sort``, reached only
    through a ``case`` branch: a refactor that moves the ``cond`` under
    the ``vmap`` fails here instead of bringing the sort back in
    silence."""
    import jax
    eng = paged_eng
    sds = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
    S = eng.max_slots
    extra = (np.zeros((S, 4), np.int32), np.zeros(S, np.int32)) \
        if program == "verify" else ()
    args = jax.tree.map(sds, (eng._cache, eng._slot_state(), *extra,
                              *eng._param_fn()))
    text = getattr(eng, f"_{program}_jit").lower(*args).as_text()
    assert _sorts_guarded(text) == (1, True)


def test_cond_under_vmap_is_what_the_lowering_check_catches():
    """The refactor the check above is for: a per-slot predicate under
    ``vmap`` lowers ``cond`` to a ``select`` that runs both sides."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def per_slot(lg, t, k, p, b, key):
        return lax.cond(t > 0.0,
                        lambda: _reference_row(lg, t, k, p, b, key),
                        lambda: jnp.argmax(lg + b).astype(jnp.int32))

    args = _operands(SHAPES["step"], 0.0)
    text = jax.jit(jax.vmap(per_slot)).lower(*args).as_text()
    assert _sorts_guarded(text) == (1, False)
    assert _sorts_guarded(jax.jit(sample_tokens).lower(*args).as_text()) \
        == (1, True)


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
