"""The grouped expert product's Pallas kernel (``kernels/grouped_experts.py``)
interpreted on the CPU through ``MXNET_FA_DECODE_FORCE_PALLAS``, against the
lax loop it replaces for decode-shaped calls and against a dense per-token
sum: the three served models' routing shapes at small widths, the work list,
the shape rule, and what the engines trace, count and serve.
"""
import importlib
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

CHIP = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "benchmark", "chip")
if CHIP not in sys.path:
    sys.path.insert(0, CHIP)

from incubator_mxnet_tpu.models import moe              # noqa: E402
from incubator_mxnet_tpu.serving import (               # noqa: E402
    ContinuousBatcher, GenerationEngine)

fa = importlib.import_module("incubator_mxnet_tpu.kernels.flash_attention")
ge = importlib.import_module("incubator_mxnet_tpu.kernels.grouped_experts")

FORCE = "MXNET_FA_DECODE_FORCE_PALLAS"

#: model -> (tokens, experts a token, published, first held, held, scoring,
#: activation): the three cells' decode calls, a tenth as many experts
MODELS = {
    "smallthinker": (32, 6, 16, 0, 16, "softmax", "relu"),
    "qwen3next": (64, 10, 48, 0, 24, "softmax", "silu"),
    "afmoe": (64, 4, 32, 8, 8, "sigmoid", "silu"),
}


def _case(model, dtype, seed=0, d=64, f=48):
    T, k, E, first, count, score, act = MODELS[model]
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((T, d)), dtype)
    gate, up = (jnp.asarray(rng.standard_normal((count, d, f)) * 0.2, dtype)
                for _ in range(2))
    down = jnp.asarray(rng.standard_normal((count, f, d)) * 0.2, dtype)
    logits = rng.standard_normal((T, E)).astype(np.float32)
    # nobody chooses the held range's second expert; where there are 64
    # tokens more than a tile's 32 rows choose its first (the loop visits
    # it twice)
    logits[:, first + 1] = -50.0
    logits[:40, first] = 50.0
    bias = None if score == "softmax" else jnp.zeros(E, jnp.float32)
    idx, w = moe.route_token_choice(jnp.asarray(logits), bias, k,
                                    score=score)
    live = jnp.asarray(rng.random(T) < 0.8)         # free slots ride along
    return (x, idx, w, (first, count), gate, up, down, live), act


def _dense(x, idx, w, held, gate, up, down, live, act):
    """Every token's own sum over its experts held here, one at a time."""
    first, count = held
    y = np.zeros(x.shape, np.float32)
    for t in range(x.shape[0]):
        for e, we in zip(np.asarray(idx[t]), np.asarray(w[t])):
            if live[t] and first <= e < first + count:
                y[t] += we * np.asarray(moe._glu(
                    x[t:t + 1], gate[e - first], up[e - first],
                    down[e - first], act))[0]
    return y


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("model", sorted(MODELS))
def test_kernel_is_the_loop_is_the_dense_sum(model, dtype, monkeypatch):
    """A held range that is part of the published experts, a ``live`` mask
    with free slots, an expert no token chose, an expert with more rows
    than a tile: the same output (float32 sums in another order) and the
    same three counts."""
    args, act = _case(model, jnp.dtype(dtype))
    monkeypatch.setenv(FORCE, "1")
    assert moe.held_experts_impl(args[0], args[4], args[1].size) == "pallas"
    y, counts = moe.held_experts_ffn(*args, act=act)
    monkeypatch.delenv(FORCE)
    assert moe.held_experts_impl(args[0], args[4], args[1].size) \
        == "lax_loop"
    y_loop, counts_loop = moe.held_experts_ffn(*args, act=act)
    assert [int(c) for c in counts] == [int(c) for c in counts_loop]
    pairs, held, touched = (int(c) for c in counts)
    T, k, E, _, count, _, _ = MODELS[model]
    assert pairs == int(jnp.sum(args[-1])) * k
    assert 0 < held < pairs or (count == E and held == pairs)
    assert 1 < touched < count                  # one expert held is idle
    tol = dict(atol=2e-5, rtol=2e-5) if dtype == "float32" \
        else dict(atol=2e-2, rtol=2e-2)
    assert y.dtype == y_loop.dtype == jnp.float32 and y.shape == (T, 64)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_loop), **tol)
    np.testing.assert_allclose(np.asarray(y), _dense(*args, act), **tol)
    dead = ~np.asarray(args[-1])
    assert not np.asarray(y)[dead].any()        # a free slot routes nowhere


@pytest.mark.parametrize("model", sorted(MODELS))
def test_every_pair_held_elsewhere_is_exactly_zero(model, monkeypatch):
    """Zero visits: the output is written all the same, as zeros."""
    args, act = _case(model, jnp.float32)
    E = MODELS[model][2]
    monkeypatch.setenv(FORCE, "1")
    y, (pairs, held, touched) = moe.held_experts_ffn(
        args[0], args[1], args[2], (E + 3, args[4].shape[0]), *args[4:],
        act=act)
    assert int(held) == int(touched) == 0 and int(pairs) > 0
    assert not np.asarray(y).any()


@pytest.mark.parametrize("model", sorted(MODELS))
def test_work_list_names_the_touched_experts_and_no_other(model):
    """The block index of an expert no token chose appears in no visit;
    the list's padding repeats its last entry (no fetch)."""
    (x, idx, w, (first, count), *_), _ = _case(model, jnp.float32)
    on = (idx >= first) & (idx < first + count)
    local = jnp.where(on, idx - first, -1)
    n_steps = min(count, idx.size)
    n, expert, held = ge._visits(local, count, n_steps)
    n, expert = int(n[0]), np.asarray(expert)
    chosen = sorted(set(np.asarray(local).ravel().tolist()) - {-1})
    assert expert[:n].tolist() == chosen and 1 not in expert
    assert (expert[n:] == chosen[-1]).all() and expert.shape == (n_steps,)
    assert int(held) == int(np.sum(np.asarray(on)))
    none = ge._visits(jnp.full_like(local, -1), count, n_steps)
    assert int(none[0][0]) == 0 and not np.asarray(none[1]).any()


def test_blocks_of_the_hidden_width_sum_in_float32(monkeypatch):
    """``f`` is a grid axis: two and four blocks give what one gives."""
    monkeypatch.setenv(FORCE, "1")
    (x, idx, w, (first, count), gate, up, down, live), act = _case(
        "afmoe", jnp.float32, d=128, f=512)
    local = jnp.where((idx >= first) & (idx < first + count) & live[:, None],
                      idx - first, -1)
    one = ge.held_experts_pallas(x, local, w, gate, up, down, act, 512)
    for fb in (256, 128):
        got = ge.held_experts_pallas(x, local, w, gate, up, down, act, fb)
        np.testing.assert_allclose(np.asarray(got[0]), np.asarray(one[0]),
                                   atol=2e-5, rtol=2e-5)
        assert int(got[1]) == int(one[1]) and int(got[2]) == int(one[2])
    # the block is the widest whose two buffers fit, in whole lane tiles
    assert ge._f_block(2560, 768, 2) == 768 and ge._f_block(2048, 512, 2) == 512
    assert ge._f_block(3072, 3072, 2) == 512 and ge._f_block(64, 48, 4) == 48


def test_the_rule_reads_platform_and_shape_never_a_flag(monkeypatch):
    monkeypatch.delenv(FORCE, raising=False)
    x = jnp.zeros((32, 256), jnp.bfloat16)
    wg = jnp.zeros((4, 256, 128), jnp.bfloat16)
    impl = moe.held_experts_impl
    assert impl(x, wg, 192) == "lax_loop"                   # the CPU
    monkeypatch.setattr(fa, "_platform_of", lambda x: "tpu")
    assert impl(x, wg, 192) == "pallas"
    assert impl(x, wg, 1024) == "lax_loop"                  # a prompt
    assert impl(jnp.zeros((129, 256)), wg, 129) == "lax_loop"
    assert impl(jnp.zeros((128, 256)), wg, 512) == "pallas"
    assert impl(x, jnp.zeros((4, 256, 96)), 192) == "lax_loop"   # odd f
    assert impl(jnp.zeros((32, 200)), jnp.zeros((4, 200, 128)), 192) \
        == "lax_loop"                                       # odd d
    monkeypatch.setattr(fa, "_platform_of", lambda x: "cpu")
    monkeypatch.setenv(FORCE, "1")
    assert impl(jnp.zeros((32, 200)), jnp.zeros((4, 200, 96)), 192) \
        == "pallas"                         # interpreted at any width
    assert impl(x, wg, 1024) == "lax_loop"
    # a tile asks for the loop's tiles
    with moe.traced_expert_impls() as seen:
        moe.held_experts_ffn(x, jnp.zeros((32, 2), jnp.int32),
                             jnp.ones((32, 2)), (0, 4), wg, wg,
                             jnp.zeros((4, 128, 256), jnp.bfloat16), tile=32)
    assert seen == {"lax_loop"}


# ------------------------------------------------------------ the engines
_TINY = {
    "smallthinker": ("tiny_smallthinker", {"moe_ffn_hidden_size": 128}, {}),
    "afmoe": ("tiny_afmoe", {"moe_intermediate_size": 128}, {}),
    "qwen3next": ("tiny_qwen3next", {"moe_intermediate_size": 128},
                  dict(state_snapshot_tokens=64, state_snapshot_rows=8)),
}


def _engine(model, name, **over):
    """The tiny configuration of ``model`` with ``over`` laid over it."""
    cfgname, _, kw = _TINY[model]
    ref = importlib.import_module("reference." + model)
    prog = importlib.import_module("programs." + model + "_serve")
    with open(os.path.join(CHIP, "tests", cfgname + ".json")) as f:
        cfg = dict(json.load(f), **over)
    net = prog.build_net(cfg)
    prog.load_weights(net, ref.init_params(cfg, 7))
    return GenerationEngine(net, name=name, max_slots=3, max_len=128,
                            prefill_buckets=[64], block_size=16,
                            scan_steps=4, **kw), cfg


def _lowered_burst(eng, platform):
    sds = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)     # noqa: E731
    args = jax.tree.map(sds, (eng._cache + eng._recur, eng._slot_state(),
                              *eng._param_fn()))
    return eng._decode_burst_jit.trace(*args).lower(
        lowering_platforms=(platform,)).as_text(debug_info=True)


@pytest.mark.parametrize("model", sorted(_TINY))
def test_the_burst_program_holds_the_kernel_where_a_tpu_would_run_it(
        model, monkeypatch):
    """Lowered for a TPU at widths of whole lane tiles the burst program
    holds one kernel call an expert layer and no ``while`` under
    ``moe.experts``; lowered for the CPU it holds the loop's ``while``,
    the program the parent lowered (PERF.md section 6 keeps the digests)."""
    monkeypatch.delenv(FORCE, raising=False)
    wide = dict(_TINY[model][1], hidden_size=128)
    eng, cfg = _engine(model, model + "-lo", **wide)
    layers = sum(1 for l in eng._layers if not getattr(l, "_dense", False))
    text = _lowered_burst(eng, "cpu")
    assert eng.program_inventory()["experts_impl"] == {
        "decode_burst": "lax_loop"}
    assert '"moe.experts/while"' in text and "tpu_custom_call" not in text
    monkeypatch.setattr(fa, "_platform_of", lambda x: "tpu")
    eng, _ = _engine(model, model + "-ke", **wide)
    text = _lowered_burst(eng, "tpu")
    assert eng.program_inventory()["experts_impl"] == {
        "decode_burst": "pallas"}
    assert '"moe.experts/while"' not in text
    assert text.count("tpu_custom_call") >= layers >= 1
    assert text.count("held_experts") >= layers


@pytest.mark.parametrize("model", sorted(_TINY))
def test_the_engine_serves_the_same_tokens_through_the_kernel(
        model, monkeypatch):
    """Single steps and bursts through the interpreted kernel give the
    loop's tokens; every dispatch is counted by the path its program
    traced, warm-up left out, and ``/v1/models`` shows it."""
    out, paths = {}, {}
    for force in (False, True):
        if force:
            monkeypatch.setenv(FORCE, "1")
        else:
            monkeypatch.delenv(FORCE, raising=False)
        eng, cfg = _engine(model, f"{model}-f{int(force)}")
        prompt = [int(t) for t in np.random.RandomState(5).randint(
            0, cfg["vocab_size"], 40)]
        eng.warmup()
        assert eng.expert_dispatches() == {}
        bat = ContinuousBatcher(eng, name=eng.name)
        try:
            out[force] = bat.submit(prompt, 14)
            paths[force] = bat.stats()["expert_dispatches"]
        finally:
            bat.close()
        impls = eng.program_inventory()["experts_impl"]
        assert impls["decode_burst"] == impls["decode"] == (
            "pallas" if force else "lax_loop")
    assert out[True] == out[False] and len(out[True]) == 14
    assert set(paths[False]) == {"loop"}
    ledger = eng.program_inventory()["programs"]
    calls = sum(row["dispatches"] for name, row in ledger.items()
                if not name.endswith("slot_edit"))
    assert sum(paths[True].values()) <= calls
    assert paths[True].get("kernel", 0) >= 2


def test_a_model_without_experts_counts_nothing():
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.models.gpt import GPTModel
    mx.random.seed(3)
    net = GPTModel(vocab_size=50, units=32, hidden_size=64, num_layers=2,
                   num_heads=2, max_length=64, dropout=0.0)
    net.initialize(init=mx.init.Normal(0.6))
    net(mx.nd.array(np.zeros((1, 2), np.int32)))
    eng = GenerationEngine(net, name="noexp", max_slots=2, max_len=64,
                           scan_steps=2)
    eng.generate([3, 7, 11], max_new_tokens=6)
    assert eng.expert_dispatches() == {}
    assert eng.program_inventory()["experts_impl"] is None
