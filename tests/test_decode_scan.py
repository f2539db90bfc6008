"""Scanned decode-burst tests (docs/serving.md "Multi-token decode
bursts"): greedy bit-parity of the k-step ``lax.scan`` burst against
the cache-free oracle across k x in-program termination
(EOS-mid-burst, budget-cut-mid-burst), mid-flight join through the
``ContinuousBatcher``, the spec draft-scan, the closed-program-set
contract, and a forced-Pallas parity run."""
import time

import numpy as np
import pytest

from common import greedy_reference

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import fault, telemetry
from incubator_mxnet_tpu.base import MXNetError
from incubator_mxnet_tpu.models.gpt import GPTModel
from incubator_mxnet_tpu.serving import ContinuousBatcher, GenerationEngine
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
    net(mx.nd.array(np.zeros((1, 2), np.int32)))
    return net


PROMPTS = ([9, 9, 4, 1], [3, 7, 11], [5, 2])

# the continuations are deterministic per seed — computed once, shared
# by every k of the parity matrix to keep tier-1 cheap
_REF_CACHE = []


def _reference(net, budget=24):
    """Ground truth: each prompt's full greedy continuation, no eos, by
    the cache-free re-forward."""
    if not _REF_CACHE:
        _REF_CACHE.extend(greedy_reference(net, p, budget)
                          for p in PROMPTS)
    return _REF_CACHE


def _truncate(ref, budget, eos_id):
    """What the serving contract emits from a full greedy continuation
    under a budget and an eos id (eos token itself is emitted)."""
    out = []
    for tok in ref[:budget]:
        out.append(tok)
        if eos_id is not None and tok == eos_id:
            break
    return out


def _run_burst(eng, budgets, eos_ids):
    """Drive decode_burst the way the batcher does: prefill each slot,
    then burst until every slot is done, concatenating each slot's
    emitted prefix."""
    outs = [[] for _ in PROMPTS]
    S = eng.max_slots
    for s, p in enumerate(PROMPTS):
        outs[s].append(eng.prefill(np.asarray(p, np.int32), s,
                                   reserve_tokens=len(p) + budgets[s]))

    def finished(s):
        return len(outs[s]) >= budgets[s] or \
            (eos_ids[s] is not None and outs[s][-1] == eos_ids[s])

    while not all(finished(s) for s in range(S)):
        last = np.zeros(S, np.int32)
        pos = np.zeros(S, np.int32)
        bud = np.ones(S, np.int32)
        eos = np.full(S, -1, np.int32)
        act = np.zeros(S, bool)
        for s, p in enumerate(PROMPTS):
            if finished(s):
                continue
            last[s] = outs[s][-1]
            pos[s] = len(p) + len(outs[s]) - 1
            bud[s] = budgets[s] - len(outs[s])
            if eos_ids[s] is not None:
                eos[s] = eos_ids[s]
            act[s] = True
        toks, emitted = eng.decode_burst(last, pos, bud, eos, act)
        assert toks.shape[0] == eng.scan_steps
        for s in range(S):
            if act[s]:
                assert emitted[s] >= 1   # a live slot always emits
                outs[s].extend(int(t) for t in toks[:emitted[s], s])
            else:
                assert emitted[s] == 0   # free slots emit nothing
    return outs


def _eos_mid_burst(ref, k):
    """Pick an eos id that first occurs strictly mid-burst (index not
    on a k boundary) so the done mask must flip inside the scan."""
    for j, tok in enumerate(ref):
        if j % max(1, k) != max(1, k) - 1 and j > 0 \
                and tok not in ref[:j]:
            return tok
    return ref[1]


@pytest.mark.parametrize("k", [1, 4, 8])
def test_burst_parity_matrix(k):
    """k bursts x {budget-cut, EOS} mid-burst: every emitted token
    identical to the cache-free continuation, cut where the serving
    contract cuts it."""
    net = _gpt()
    ref = _reference(net)
    # slot 0: budget cut NOT on a burst boundary; slot 1: eos that
    # fires mid-burst; slot 2: plain short budget
    budgets = [k + 3 if k > 1 else 3, 24, 10]
    eos_ids = [None, _eos_mid_burst(ref[1], k), None]
    expected = [_truncate(ref[s], budgets[s], eos_ids[s])
                for s in range(len(PROMPTS))]
    assert len(expected[1]) < 24          # the eos really cut slot 1
    eng = GenerationEngine(net, name=f"scan{k}", scan_steps=k,
                           max_slots=len(PROMPTS), max_len=64,
                           block_size=8)
    got = _run_burst(eng, budgets, eos_ids)
    assert got == expected
    # lazy compilation stayed inside the closed AOT prediction
    # (warmup-compiles-everything is test_burst_program_joins_closed_set)
    assert eng.compiled_programs() <= eng.expected_programs


def test_burst_program_joins_closed_set():
    # max_len=16 keeps the prefill bucket ladder (and so the warmup
    # compile bill) minimal — this test only counts programs
    net = _gpt(max_length=16)
    off = GenerationEngine(net, name="off", scan_steps=0, max_slots=1,
                           max_len=16)
    on = GenerationEngine(net, name="on", scan_steps=8, max_slots=1,
                          max_len=16)
    # exactly ONE new program, warmup-compiled, inventoried
    assert on.expected_programs == off.expected_programs + 1
    assert off.warmup() == off.expected_programs
    assert on.warmup() == on.expected_programs
    assert on.program_inventory()["scan_steps"] == 8
    assert off.program_inventory()["scan_steps"] == 0
    with pytest.raises(MXNetError):
        on.scan_steps = 0                 # latched at warmup: a drifted
        on.warmup()                       # prediction must be LOUD
    with pytest.raises(MXNetError):
        GenerationEngine(net, name="bad", scan_steps=-1,
                         max_slots=1, max_len=16)


def test_burst_disabled_rejects_decode_burst():
    eng = GenerationEngine(_gpt(), name="noburst", scan_steps=0,
                           max_slots=2, max_len=64)
    eng.prefill(np.asarray([3, 7, 11], np.int32), 0, reserve_tokens=10)
    with pytest.raises(MXNetError):
        eng.decode_burst(np.zeros(2, np.int32), np.zeros(2, np.int32),
                         np.ones(2, np.int32),
                         np.full(2, -1, np.int32), np.ones(2, bool))


def test_mid_flight_join_burst_identical_to_solo():
    """The batcher's burst gate must not perturb join/leave parity: a
    rider decoding in bursts when a joiner arrives emits exactly its
    solo tokens, and so does the joiner."""
    net = _gpt(max_length=128)
    eng = GenerationEngine(net, name="bj", max_slots=2, max_len=128,
                           scan_steps=8)
    solo_long = eng.generate([9, 9, 4, 1], max_new_tokens=60)
    solo_short = eng.generate([3, 7, 11], max_new_tokens=5)
    eng.reset()
    batcher = ContinuousBatcher(eng, name="bj")
    try:
        req_a = batcher.submit_async([9, 9, 4, 1], max_new_tokens=60)
        while not req_a.tokens_out:
            time.sleep(0.002)
        req_b = batcher.submit_async([3, 7, 11], max_new_tokens=5)
        got_b = req_b.result(timeout=60)
        got_a = req_a.result(timeout=60)
        assert got_a == solo_long
        assert got_b == solo_short
        st = batcher.stats()
        assert st["decode_burst_dispatches"] > 0   # bursts were taken
        assert st["tokens_emitted"] == len(got_a) + len(got_b)
    finally:
        batcher.close()


def test_spec_draft_scan_parity_and_program_set():
    """attach_draft folds the draft's k proposal decodes into one
    scanned dispatch; outputs stay bit-identical to the host-loop
    draft (scan_steps=0 kill switch — spec-vs-plain parity itself is
    test_speculative's), and repeat generates compile nothing new:
    the draft burst is inside the closed program set (the full
    warmup-counts drill is test_burst_program_joins_closed_set)."""
    net = _gpt()
    tgt0 = GenerationEngine(net, name="t0", max_slots=2, max_len=64)
    dr0 = GenerationEngine(net, name="d0", max_slots=2, max_len=64,
                           scan_steps=0)
    tgt0.attach_draft(dr0, spec_k=3)
    assert dr0.scan_steps == 0            # kill switch respected
    host_loop = tgt0.generate([3, 7, 11], max_new_tokens=20,
                              speculative=True)

    tgt1 = GenerationEngine(net, name="t1", max_slots=2, max_len=64)
    dr1 = GenerationEngine(net, name="d1", max_slots=2, max_len=64)
    tgt1.attach_draft(dr1, spec_k=3)
    assert dr1.scan_steps == 3            # draft burst sized to spec_k
    scanned = tgt1.generate([3, 7, 11], max_new_tokens=20,
                            speculative=True)
    assert scanned == host_loop
    # the second run is the first to edit a row of a slot state that is
    # there (the first built it whole): from then on nothing compiles
    assert tgt1.generate([3, 7, 11], max_new_tokens=20,
                         speculative=True) == scanned
    n_t, n_d = tgt1.compiled_programs(), dr1.compiled_programs()
    assert n_t <= tgt1.expected_programs
    assert n_d <= dr1.expected_programs
    assert tgt1.generate([3, 7, 11], max_new_tokens=20,
                         speculative=True) == scanned
    assert tgt1.compiled_programs() == n_t
    assert dr1.compiled_programs() == n_d


def test_burst_parity_forced_pallas(monkeypatch):
    """Forced-Pallas run (interpret mode on CPU): the kernel's
    comparison-based position mask honors carry-traced positions."""
    monkeypatch.setenv("MXNET_FA_DECODE_FORCE_PALLAS", "1")
    net = _gpt(max_length=128)           # T=128: tile-aligned
    eng0 = GenerationEngine(net, name="fp0", max_slots=2, max_len=128,
                            scan_steps=0)
    ref = eng0.generate([9, 9, 4, 1], max_new_tokens=12)
    eng = GenerationEngine(net, name="fp", max_slots=2, max_len=128,
                           scan_steps=4)
    out = eng.generate([9, 9, 4, 1], max_new_tokens=12)
    assert out == ref                     # per-step pallas parity
    eng.reset()
    b = ContinuousBatcher(eng, name="fp")
    try:
        assert b.submit([9, 9, 4, 1], max_new_tokens=12) == ref
        assert b.stats()["decode_burst_dispatches"] > 0
    finally:
        b.close()
