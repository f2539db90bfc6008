"""The KV pool is stored so that it rests in the layout the serving programs
keep it in (``KVLayout.pool_shape``): on the CPU that is the stated shape,
so the programs and the tokens are the parent's; stored position-major with
the features on whole lanes — forced here, as the rule has it on a TPU for
GPT-2's pool — the same values lie elsewhere and nothing else changes.
"""
import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu.models.gpt import GPTModel
from incubator_mxnet_tpu.serving import GenerationEngine
from incubator_mxnet_tpu.serving.kvcache import KVLayout

A = [3, 7, 11, 2, 9, 14, 5, 8, 21, 30]
B = A[:8] + [40, 41, 42]            # shares A's first two whole blocks

#: what the parent commit (PR 26's tree) serves for `_drive`, recorded from
#: a checkout of it: first tokens of a miss and of a prefix hit, three single
#: steps, two bursts of 4 (slot 1's budget 3 ends inside them), and three
#: speculative steps' verify columns with what was accepted
PARENT = {
    "prefill": [33, 0], "prefix_hits": 2,
    "steps": [[7, 32], [16, 20], [38, 33]],
    "bursts": [[[[38, 33], [7, 38], [6, 23], [32, 23]], [4, 3]],
               [[[16, 15], [33, 23], [16, 10], [26, 10]], [4, 3]]],
    "spec": [[[[7, 33, 16, 33], [32, 47, 23, 16]], [0, 0]],
             [[[38, 7, 32, 20], [16, 26, 42, 42]], [0, 0]],
             [[[26, 7, 33, 20], [42, 33, 42, 33]], [0, 0]]],
}


def _gpt(seed, layers=2):
    mx.random.seed(seed)
    net = GPTModel(vocab_size=50, units=32, hidden_size=64,
                   num_layers=layers, num_heads=2, max_length=64,
                   dropout=0.0)
    net.initialize(init=mx.init.Normal(0.6))
    net(mx.nd.array(np.zeros((1, 2), np.int32)))
    return net


def _engines(name, **kw):
    """A target with a one-layer draft attached, as `PARENT` was recorded."""
    args = dict(max_slots=3, max_len=64, block_size=4,
                prefill_buckets=[8, 32])
    eng = GenerationEngine(_gpt(3), name=name, scan_steps=4, **args, **kw)
    draft = GenerationEngine(_gpt(5, layers=1), name=name + "-draft",
                             **args, **kw)
    eng.attach_draft(draft, spec_k=3)
    for prompt, slot in ((A, 0), (B, 1)):
        draft.prefill(prompt, slot, reserve_tokens=40)
    return eng


def _drive(eng):
    """A miss, a prefix hit, single steps, bursts, speculative steps (the
    draft's burst and the target's verify): every paged program."""
    S = eng.max_slots
    t0 = eng.prefill(A, 0, reserve_tokens=40)
    hits = eng.pool.hits
    t1 = eng.prefill(B, 1, reserve_tokens=40)
    out = {"prefill": [int(t0), int(t1)],
           "prefix_hits": int(eng.pool.hits - hits),
           "steps": [], "bursts": [], "spec": []}
    lt, pos = np.zeros(S, np.int32), np.zeros(S, np.int32)
    lt[:2], pos[:2] = (t0, t1), (len(A), len(B))
    for _ in range(3):
        nxt = np.asarray(eng.decode(lt, pos)).reshape(S)
        out["steps"].append(nxt[:2].tolist())
        lt[:2] = nxt[:2]
        pos[:2] += 1
    active = np.arange(S) < 2
    for _ in range(2):
        toks, emitted = eng.decode_burst(
            lt, pos, np.array([4, 3] + [0] * (S - 2), np.int32),
            np.full(S, -1, np.int32), active)
        out["bursts"].append([toks[:, :2].tolist(), emitted[:2].tolist()])
        for s in range(2):
            lt[s] = toks[int(emitted[s]) - 1, s]
            pos[s] += int(emitted[s])
    for _ in range(3):
        o, acc = eng.spec_step(lt, pos)
        out["spec"].append([o[:2].tolist(), acc[:2].tolist()])
        for s in range(2):
            lt[s] = o[s, int(acc[s])]
            pos[s] += int(acc[s]) + 1
    return out


@pytest.fixture()
def position_major(monkeypatch):
    """The rule answers as it does for GPT-2's pool on the chip: every
    engine built under it stores ``[N, bs, H, 128]``."""
    def rule(self, num_blocks, block_size, device):
        return (int(num_blocks), int(block_size), self.kv_heads,
                -(-self.head_dim // self.LANES) * self.LANES), True
    monkeypatch.setattr(KVLayout, "pool_shape", rule)


def _shapes(eng):
    return {c.shape for c in eng._cache}


def test_tokens_are_the_parents_on_the_cpu():
    eng = _engines("asfound")
    assert not eng._position_major and not eng.draft._position_major
    assert _shapes(eng) == {(49, 2, 4, 16)}
    assert _drive(eng) == PARENT


@pytest.mark.parametrize("health", ["0", "1"])
def test_tokens_are_the_parents_with_the_pools_position_major(
        health, position_major, monkeypatch):
    """Another place for the same values is no arithmetic: the same tokens
    through every paged program, whatever else the programs return (the
    health plane's rows, log-probabilities)."""
    monkeypatch.setenv("MXNET_HEALTH_PLANE", health)
    eng = _engines("bypos" + health)
    assert _shapes(eng) == _shapes(eng.draft) == {(49, 4, 2, 128)}
    assert _drive(eng) == PARENT
    assert _shapes(eng) == {(49, 4, 2, 128)}
    # the lanes past the 16 features were never written
    assert not any(np.asarray(c[..., 16:]).any() for c in eng._cache)
    assert eng.cache_bytes == 4 * 49 * 4 * 2 * 128 * 4


def test_the_kernel_reads_a_position_major_pool(position_major,
                                                monkeypatch):
    """Block size 8, so the (interpreted) kernel takes the decode, burst
    and verify programs: its pages are the stored pool's blocks as they
    lie, and the stream is the gather's."""
    def stream(name):
        eng = GenerationEngine(_gpt(3), name=name, max_slots=2, max_len=64,
                               block_size=8, prefill_buckets=[16],
                               scan_steps=4)
        out = eng.generate(A, max_new_tokens=20)
        return out, eng.program_inventory()["paged_attention"]

    monkeypatch.delenv("MXNET_FA_DECODE_FORCE_PALLAS", raising=False)
    want, impl = stream("gather")
    assert impl == "lax_gather"
    monkeypatch.setenv("MXNET_FA_DECODE_FORCE_PALLAS", "1")
    got, impl = stream("kernel")
    assert impl == "pallas" and got == want


@pytest.mark.parametrize("by_position", [False, True])
def test_reset_after_a_restart_reallocates_as_reported(by_position,
                                                       request):
    """What the batcher does when the watchdog replaced its worker: the
    donated pools a dying dispatch consumed are gone, `reset()` makes new
    ones in the shape the inventory reports, and the next dispatch runs
    in the programs already compiled."""
    if by_position:
        request.getfixturevalue("position_major")
    eng = _engines("restart%d" % by_position)
    want = _drive(eng)
    compiled = eng.compiled_programs()
    for c in eng._cache + eng.draft._cache:
        c.delete()
    eng.reset()
    layout = eng.program_inventory()["pool_layout"]
    if by_position:
        assert layout == {"stored": "position_major",
                          "shape": [49, 4, 2, 128],
                          "stated": [49, 2, 4, 16]}
        assert _shapes(eng) == _shapes(eng.draft) == {(49, 4, 2, 128)}
    else:
        assert layout == "default"
        assert _shapes(eng) == {(49, 2, 4, 16)}
    assert not any(np.asarray(c).any() for c in eng._cache)
    for prompt, slot in ((A, 0), (B, 1)):
        eng.draft.prefill(prompt, slot, reserve_tokens=40)
    assert _drive(eng) == want == PARENT
    assert eng.compiled_programs() == compiled


def test_inventory_reports_the_default_on_the_cpu():
    eng = _engines("inv")
    inv = eng.program_inventory()
    assert inv["pool_layout"] == "default"
    assert inv["draft"]["pool_layout"] == "default"
