"""The slot state of ``GenerationEngine`` (docs/serving.md "The slot
state"): per-slot decode operands live on the device, the programs
return them advanced, and the host edits them by row.

* one scripted session through ``ContinuousBatcher`` — joins into free
  and just-released slots, leaves by EOS, budget, a host-side stop
  sequence and cancel, a constrained request, step and burst dispatches
  interleaved, a seeded sampled request beside greedy ones — gives the
  same tokens as the same script with the device's state rebuilt from
  the host's rows before every dispatch, and at every dispatch the rows
  the host believes the device holds ARE what it holds;
* ``mxtpu_serve_operands``: ``carried`` uploads nothing, a join is
  ``patched`` by row, ``reset()`` and a failed dispatch are ``rebuilt``;
* the row edit is one program of the closed set.
"""
import json
import os
import sys
import time

import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu.models.gpt import GPTModel
from incubator_mxnet_tpu.serving import (ContinuousBatcher,
                                         GenerationEngine, lifecycle)
from incubator_mxnet_tpu.serving import engine as engine_mod
from incubator_mxnet_tpu.serving.sampling import SamplingParams

CHIP = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "benchmark", "chip")


def _gpt(seed=3, units=32, layers=2):
    mx.random.seed(seed)
    net = GPTModel(vocab_size=160, units=units, hidden_size=2 * units,
                   num_layers=layers, num_heads=2, max_length=128,
                   dropout=0.0)
    net.initialize(init=mx.init.Normal(0.6))
    net(mx.nd.array(np.zeros((1, 2), np.int32)))
    return net


def _gpt_engine(name="st", **kw):
    args = dict(name=name, max_slots=3, max_len=128,
                prefill_buckets=[16, 64], scan_steps=4)
    args.update(kw)
    return GenerationEngine(_gpt(), **args)


def _afmoe_engine():
    if CHIP not in sys.path:
        sys.path.insert(0, CHIP)
    from programs import afmoe_serve as prog
    from reference import afmoe as ref
    with open(os.path.join(CHIP, "tests", "tiny_afmoe.json")) as f:
        cfg = json.load(f)
    net = prog.build_net(cfg)
    prog.load_weights(net, ref.init_params(cfg, 7))
    return GenerationEngine(net, name="st-afmoe", max_slots=3, max_len=128,
                            prefill_buckets=[16, 64], block_size=16,
                            scan_steps=4, logprobs_topn=0)


def _spec_engine():
    eng = _gpt_engine(name="st-spec")
    draft = GenerationEngine(_gpt(seed=5, units=16, layers=1),
                             name="st-draft", max_slots=3, max_len=128,
                             prefill_buckets=[16, 64])
    eng.attach_draft(draft, spec_k=3)
    return eng


ENGINES = {"gpt": _gpt_engine, "afmoe": _afmoe_engine, "gpt_spec": _spec_engine}


def _held_rows_are_the_devices(eng):
    """Wrap ``eng._slot_state`` so that every dispatch first holds the
    host's rows against the device's, outside the slots marked for an
    edit: the arithmetic by which the host follows the programs."""
    inner = eng._slot_state
    slots = np.arange(eng.max_slots)

    def checked():
        if eng._state is not None:
            clean = np.setdiff1d(slots, sorted(eng._dirty))
            np.testing.assert_array_equal(
                np.asarray(eng._state["rows"])[clean], eng._rows[clean])
            clean = np.setdiff1d(slots, sorted(eng._dirty_bias))
            np.testing.assert_array_equal(
                np.asarray(eng._state["bias"])[clean], eng._samp_bias[clean])
        return inner()
    eng._slot_state = checked


def _rebuilt_before_every_dispatch(eng):
    inner = eng._slot_state

    def rebuilt():
        eng.rebuild_slot_state()
        return inner()
    eng._slot_state = rebuilt


def _wait_tokens(req, n, seconds=30):
    deadline = time.monotonic() + seconds
    while len(req.tokens_out) < n and time.monotonic() < deadline:
        time.sleep(0.002)
    assert len(req.tokens_out) >= n


def _session(bat):
    """The script.  Returns ``{name: tokens}``; ``cancelled`` is however
    far that stream got."""
    rng = np.random.RandomState(11)
    prompt = lambda n: [int(t) for t in rng.randint(1, 150, n)]  # noqa: E731
    P = {k: prompt(n) for k, n in (("a", 5), ("b", 3), ("c", 20), ("d", 4),
                                   ("e", 7), ("g", 6), ("h", 2))}
    out = {}
    # a probe alone: its stream gives the stop id and the stop sequence
    probe = bat.submit(P["e"], max_new_tokens=24)
    out["probe"] = probe
    # three joins into free slots: greedy, seeded sampled, a short budget
    a = bat.submit_async(P["a"], max_new_tokens=41)
    b = bat.submit_async(P["b"], max_new_tokens=23, sampling=SamplingParams(
        temperature=0.8, top_p=0.9, top_k=40, seed=11,
        logit_bias={7: -3.0, 19: 2.5}))
    c = bat.submit_async(P["c"], max_new_tokens=6)
    # every slot is held: these wait (per-step dispatches meanwhile) and
    # join the slots that c, then the others, release
    eos = bat.submit_async(P["e"], max_new_tokens=24, eos_id=probe[9])
    stop = bat.submit_async(P["e"], max_new_tokens=24,
                            sampling=SamplingParams(
                                stop=((probe[12], probe[13]),)))
    g = bat.submit_async(P["g"], max_new_tokens=90)
    _wait_tokens(g, 5)
    g.cancel()
    h = bat.submit_async(P["h"], max_new_tokens=12, sampling=SamplingParams(
        temperature=0.9, seed=5, json_mode=True))
    d = bat.submit_async(P["d"], max_new_tokens=30)
    for name, req in (("a", a), ("b", b), ("c", c), ("eos", eos),
                      ("stop", stop), ("h", h), ("d", d)):
        out[name] = req.result(120)
    with pytest.raises(lifecycle.Cancelled):
        g.result(120)
    out["cancelled"] = list(g.tokens_out)
    # the leaves were the ones the script names
    assert len(out["c"]) == 6
    assert out["eos"] == probe[:probe.index(probe[9]) + 1]
    assert out["stop"][-2:] == [probe[12], probe[13]] \
        and out["stop"] == probe[:len(out["stop"])]
    assert 5 <= len(out["cancelled"]) < 90
    return out


@pytest.mark.parametrize("kind", sorted(ENGINES))
def test_session_matches_state_rebuilt_before_every_dispatch(kind):
    eng = ENGINES[kind]()
    eng.warmup()
    compiled = eng.compiled_programs()
    engines = [eng] + ([eng.draft] if eng.draft is not None else [])
    for e in engines:
        _held_rows_are_the_devices(e)
    bat = ContinuousBatcher(eng, name=eng.name)
    try:
        carried = _session(bat)
        stats = bat.stats()
    finally:
        bat.close()
    ops = stats["operands"]
    assert ops["carried"] > 0 and ops["patched"] > 0 and ops["rows"] > 0
    assert ops["rebuilt"] == 1                      # the first, after warm-up
    by_path = stats["tokens_by_path"]
    assert by_path["step"] > 0                      # the constrained request
    assert by_path["spec" if kind == "gpt_spec" else "burst"] > 0
    assert eng.compiled_programs() == compiled      # the set stayed closed

    eng.reset()
    for e in engines:
        del e._slot_state                           # back to the method
        _rebuilt_before_every_dispatch(e)
    bat = ContinuousBatcher(eng, name=eng.name)
    try:
        rebuilt = _session(bat)
        ops2 = bat.stats()["operands"]
    finally:
        bat.close()
    assert ops2["carried"] == ops["carried"] \
        and ops2["patched"] == ops["patched"]       # all of them rebuilt
    n = min(len(carried["cancelled"]), len(rebuilt["cancelled"]))
    assert carried.pop("cancelled")[:n] == rebuilt.pop("cancelled")[:n]
    assert carried == rebuilt


# ------------------------------------------------------- the counter
@pytest.fixture(scope="module")
def warm():
    eng = _gpt_engine(name="st-count", max_slots=4)
    eng.warmup()
    return eng


class _Uploads:
    """Every array the engine sends to the device, by shape: what it
    puts there to stay, and the numpy operands it hands a program."""

    def __init__(self, eng, monkeypatch):
        self.shapes = []
        for name in ("_put", "_prefill", "_prefill_ext", "_decode",
                     "_decode_burst", "_verify", "_slot_edit"):
            monkeypatch.setattr(eng, name, self._recording(
                getattr(eng, name)))

    def _recording(self, program):
        def call(*args):
            self.shapes += [a.shape for a in args
                            if isinstance(a, np.ndarray)]
            return program(*args)
        return call

    def take(self):
        out, self.shapes = self.shapes, []
        return out


def _delta(eng, before):
    return {k: v - before[k] for k, v in eng.operand_sources().items()}


def _burst_operands(eng, heads, budget=30):
    """What the batcher would hand ``decode_burst`` for the live slots
    ``heads``: ``{slot: (last token, position)}``."""
    S = eng.max_slots
    last, pos = np.zeros(S, np.int32), np.zeros(S, np.int32)
    bud, eos = np.ones(S, np.int32), np.full(S, -1, np.int32)
    act = np.zeros(S, bool)
    for s, (t, p) in heads.items():
        last[s], pos[s], bud[s], act[s] = t, p, budget - p, True
    return last, pos, bud, eos, act


def _advance(heads, toks, emitted):
    for s in heads:
        n = int(emitted[s])
        heads[s] = (int(toks[n - 1, s]), heads[s][1] + n)


def test_carried_uploads_nothing_and_a_join_is_one_row(warm, monkeypatch):
    eng = warm
    eng.reset()
    ups = _Uploads(eng, monkeypatch)
    S, V = eng.max_slots, eng.vocab_size
    edit = (engine_mod._EDIT_ROWS, 2 + eng._rows.shape[1])
    heads = {}
    for s, prompt in ((0, [5, 2, 9]), (1, [9, 9, 4, 1])):
        eng.set_slot_sampling(s)
        heads[s] = (eng.prefill(prompt, s, reserve_tokens=40), len(prompt))
    before = eng.operand_sources()
    _advance(heads, *eng.decode_burst(*_burst_operands(eng, heads)))
    assert _delta(eng, before)["rebuilt"] == 1      # first since reset()
    ups.take()

    # no join, no leave: what the batcher hands over is what the device
    # carried — nothing is uploaded
    before = eng.operand_sources()
    _advance(heads, *eng.decode_burst(*_burst_operands(eng, heads)))
    assert _delta(eng, before) == {"carried": 1, "patched": 0,
                                   "rebuilt": 0, "rows": 0}
    assert ups.take() == []

    # a join with a bias of its own: its row before its prefill, its
    # dispatch columns before the burst; the largest upload is ONE bias
    # row, never the (slots, vocabulary) matrix
    before = eng.operand_sources()
    eng.set_slot_sampling(2, SamplingParams(temperature=0.7, seed=3,
                                            logit_bias={4: 1.5}))
    heads[2] = (eng.prefill([8, 1], 2, reserve_tokens=40), 2)
    _advance(heads, *eng.decode_burst(*_burst_operands(eng, heads)))
    assert _delta(eng, before) == {"carried": 0, "patched": 1,
                                   "rebuilt": 0, "rows": 2}
    shapes = ups.take()
    assert sorted(shapes) == sorted([edit, (V,), (1, 16), (2,), edit])
    assert max(int(np.prod(s)) for s in shapes) == V < S * V

    # a leave is a row as well, and the next burst is carried again
    before = eng.operand_sources()
    eng.release_slot(0)
    del heads[0]
    _advance(heads, *eng.decode_burst(*_burst_operands(eng, heads)))
    _advance(heads, *eng.decode_burst(*_burst_operands(eng, heads)))
    assert _delta(eng, before) == {"carried": 1, "patched": 1,
                                   "rebuilt": 0, "rows": 1}
    assert ups.take() == [edit]


def test_reset_and_a_failed_dispatch_rebuild_and_tokens_stay_right(
        warm, monkeypatch):
    eng = warm

    def run(fail_at=None):
        eng.reset()
        eng.set_slot_sampling(0, SamplingParams(temperature=0.9, seed=21))
        heads = {0: (eng.prefill([3, 1, 4, 1, 5], 0, reserve_tokens=60), 5)}
        seen, sources = [], []
        for i in range(5):
            before = eng.operand_sources()
            if i == fail_at:
                # the dispatch raises; the state it was given is gone
                monkeypatch.setattr(eng, "_decode_burst",
                                    lambda *a: 1 / 0)
                with pytest.raises(ZeroDivisionError):
                    eng.decode_burst(*_burst_operands(eng, heads, 60))
                monkeypatch.undo()
                assert eng._state is None
            toks, emitted = eng.decode_burst(*_burst_operands(eng, heads,
                                                              60))
            seen += [int(t) for t in toks[:emitted[0], 0]]
            _advance(heads, toks, emitted)
            sources.append([k for k, v in _delta(eng, before).items()
                            if v and k != "rows"])
        return seen, sources

    golden, sources = run()
    assert sources == [["rebuilt"]] + [["carried"]] * 4
    again, sources = run(fail_at=2)
    assert again == golden
    assert sources == [["rebuilt"], ["carried"], ["rebuilt"], ["carried"],
                       ["carried"]]


BURSTS = {
    # budgets, stop ids (as offsets into the slot's own greedy stream; None:
    # no stop id), active
    "runs_on": ([30, 30, 30], [None, None, None], [True, True, True]),
    "budget_ends_it": ([2, 1, 4], [None, None, None], [True, True, True]),
    "stop_id_ends_it": ([30, 30, 30], [0, 2, 3], [True, True, True]),
    "a_free_slot_rides": ([30, 1, 30], [None, None, 1], [True, False, True]),
}


@pytest.fixture(scope="module")
def follower():
    return _gpt_engine(name="st-follow")


@pytest.mark.parametrize("case", sorted(BURSTS))
def test_rows_follow_the_burst_program(case, follower):
    """After a burst the host's rows, moved on from the tokens it
    returned, are the device's — last token, position, budget, ``done``
    — through every way a slot's burst can end."""
    budgets, stops, active = BURSTS[case]
    eng = follower
    eng.reset()
    S = eng.max_slots
    prompts = [[5, 2, 9], [9, 9, 4, 1], [7]]
    streams = [eng.generate(p, max_new_tokens=6) for p in prompts]
    eng.reset()
    last, pos = np.zeros(S, np.int32), np.zeros(S, np.int32)
    eos = np.full(S, -1, np.int32)
    for s, p in enumerate(prompts):
        if not active[s]:
            continue
        eng.set_slot_sampling(s)
        last[s], pos[s] = eng.prefill(p, s, reserve_tokens=40), len(p)
        if stops[s] is not None:
            eos[s] = streams[s][1 + stops[s]]
    toks, emitted = eng.decode_burst(last, pos, np.asarray(budgets, np.int32),
                                     eos, np.asarray(active))
    for s in range(S):
        if active[s]:
            assert [int(t) for t in toks[:emitted[s], s]] \
                == streams[s][1:1 + emitted[s]]
    assert not eng._dirty
    np.testing.assert_array_equal(np.asarray(eng._state["rows"]), eng._rows)
    # and so does a step: a slot that holds a table moved on by one
    eng.decode(eng._rows[:, engine_mod._LAST].copy(),
               eng._rows[:, engine_mod._POS].copy())
    np.testing.assert_array_equal(np.asarray(eng._state["rows"]), eng._rows)


# ------------------------------------------------- the closed program set
def test_row_edit_is_one_program_of_the_closed_set():
    eng = _gpt_engine(name="st-closed", max_slots=8)
    assert eng.expected_programs == 2 * len(eng.prefill_buckets) + 3
    assert eng._slot_edit_jit._cache_size() == 0
    assert eng.warmup() == eng.expected_programs == eng.compiled_programs()
    assert eng._slot_edit_jit._cache_size() == 1    # warm-up compiled it
    inv = eng.program_inventory()
    assert inv["compiled_programs"] == inv["expected_programs"]
    before = eng.operand_sources()
    bat = ContinuousBatcher(eng, name=eng.name)
    try:
        # a run of joins: by row, with and without a bias row, and six at
        # once (more than a quarter of the slots: the rows matrix whole)
        reqs = [bat.submit_async([1 + i, 2, 3], max_new_tokens=9 + i,
                                 sampling=SamplingParams(
                                     logit_bias={i: 2.0}) if i % 2 else None)
                for i in range(6)]
        for r in reqs:
            r.result(60)
        for i in range(4):
            bat.submit([4, i + 1], max_new_tokens=7)
        stats = bat.stats()
    finally:
        bat.close()
    assert stats["operands"] == eng.operand_sources()
    assert _delta(eng, before)["patched"] >= 5
    assert eng.compiled_programs() == eng.expected_programs
    assert eng._slot_edit_jit._cache_size() == 1
    ledger = eng.program_inventory()["programs"]
    assert ledger["serving:st-closed:slot_edit"]["dispatches"] >= 5
