"""The grouped expert product's Pallas kernels (``kernels/grouped_experts.py``)
interpreted on the CPU through ``MXNET_FA_DECODE_FORCE_PALLAS``, against the
lax loop they replace on a TPU and against a dense per-token sum: the three
served models' decode shapes at small widths, a prompt's sorted form under
every kind of routing, the work lists, the shape rule, what a program's
layers share, and what the engines trace, count and serve.
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
    one = ge.held_experts_pallas(x, local, w, gate, up, down, act, 512, True)
    for fb in (256, 128):
        got = ge.held_experts_pallas(x, local, w, gate, up, down, act, fb,
                                      True)
        np.testing.assert_allclose(np.asarray(got[0]), np.asarray(one[0]),
                                   atol=2e-5, rtol=2e-5)
        assert int(got[1]) == int(one[1]) and int(got[2]) == int(one[2])
    # the block is the widest whose two buffers fit, in whole lane tiles
    assert ge._f_block(2560, 768, 2) == 768 and ge._f_block(2048, 512, 2) == 512
    assert ge._f_block(3072, 3072, 2) == 512 and ge._f_block(64, 48, 4) == 48


#: (tokens, width, hidden width, held experts, array type, P) -> the answer
#: on a TPU, and on the CPU under the test hook
RULE = {
    "decode":        (32, 256, 128, 4, "bfloat16", 192, "pallas", "pallas"),
    "decode-128":    (128, 256, 128, 4, "bfloat16", 512, "pallas", "pallas"),
    "prompt":        (256, 256, 128, 128, "bfloat16", 1024, "pallas_sorted",
                      "pallas_sorted"),
    "prompt-f32":    (256, 256, 128, 256, "float32", 1024, "pallas_sorted",
                      "pallas_sorted"),
    "prompt-f16":    (256, 256, 128, 128, "float16", 2048, "pallas_sorted",
                      "pallas_sorted"),
    "verify-129":    (129, 256, 128, 128, "bfloat16", 129, "pallas_sorted",
                      "pallas_sorted"),
    "prompt-few-experts": (256, 256, 128, 4, "bfloat16", 1024,
                           "pallas_sorted", "pallas_sorted"),
    "prompt-float8": (256, 256, 128, 128, "float8_e4m3fn", 1024, "lax_loop",
                      "lax_loop"),
    "odd-f":         (32, 256, 96, 4, "bfloat16", 192, "lax_loop", "pallas"),
    "odd-d":         (32, 200, 128, 4, "bfloat16", 192, "lax_loop",
                      "pallas"),
    "prompt-odd-f":  (256, 256, 96, 128, "bfloat16", 1024, "lax_loop",
                      "pallas_sorted"),
    "prompt-wide-d": (256, 32768, 128, 128, "bfloat16", 1024, "lax_loop",
                      "lax_loop"),
}


@pytest.mark.parametrize("case", sorted(RULE))
def test_the_rule_reads_platform_shape_and_type_never_a_flag(
        case, monkeypatch):
    """``held_experts_impl``'s three answers: the CPU takes the loop; a
    TPU the kernel over the touched experts for a decode-shaped call and
    the kernel over the sorted rows for any other, at whole lane tiles
    and (the sorted form) 16-bit or float32 arrays and a row tile that
    fits, whatever the count of held experts; the test hook interprets
    either on the CPU at any width.  The kernel it names is interpreted on
    the CPU alone."""
    T, d, f, count, dtype, P, on_tpu, forced = RULE[case]
    x = jax.ShapeDtypeStruct((T, d), jnp.dtype(dtype))
    wg = jax.ShapeDtypeStruct((count, d, f), jnp.dtype(dtype))
    impl = moe.held_experts_impl
    monkeypatch.setattr(fa, "_platform_of", lambda x: "cpu")
    monkeypatch.delenv(FORCE, raising=False)
    assert impl(x, wg, P) == "lax_loop"                     # the CPU
    monkeypatch.setenv(FORCE, "1")
    assert impl(x, wg, P) == forced
    assert ge.held_experts_route(x, wg, P) == (forced, forced != "lax_loop")
    monkeypatch.delenv(FORCE)
    monkeypatch.setattr(fa, "_platform_of", lambda x: "tpu")
    assert impl(x, wg, P) == on_tpu
    assert ge.held_experts_route(x, wg, P) == (on_tpu, False)


def test_the_platform_is_read_without_asking_a_tracer(monkeypatch):
    """``_platform_of`` asks a concrete array where it lives and answers
    the context's device for a host array and for a TRACER, whose
    ``devices()`` it never calls: jax raises there, and the error's message
    walks the tracer's whole ancestry (seconds a hit-prefill program)."""
    def asked(self):
        raise AssertionError("a tracer was asked for its devices")
    monkeypatch.setattr(jax.core.Tracer, "devices", asked)
    seen = []

    def program(x):
        seen.append(fa._platform_of(x))
        seen.append(moe.held_experts_impl(x, x[None], 4096))
        return x

    jax.jit(program)(jnp.zeros((256, 128), jnp.bfloat16))
    assert seen == ["cpu", "lax_loop"]
    assert fa._platform_of(jnp.zeros(3)) == "cpu"
    assert fa._platform_of(np.zeros(3)) == "cpu"


def test_a_tile_asks_for_the_loop(monkeypatch):
    """``tile=`` takes the loop's tiles whatever the rule would answer."""
    monkeypatch.setattr(fa, "_platform_of", lambda x: "tpu")
    x = jnp.zeros((32, 256), jnp.bfloat16)
    wg = jnp.zeros((4, 256, 128), jnp.bfloat16)
    assert moe.held_experts_impl(x, wg, 64) == "pallas"
    with moe.traced_expert_impls() as seen:
        moe.held_experts_ffn(x, jnp.zeros((32, 2), jnp.int32),
                             jnp.ones((32, 2)), (0, 4), wg, wg,
                             jnp.zeros((4, 128, 256), jnp.bfloat16), tile=32)
    assert seen == {"lax_loop"}


# ------------------------------------------------- a prompt: the sorted form
#: routing -> what the (300, 4) choices over 16 published experts are
ROUTINGS = ("random", "alike", "elsewhere")
#: shape -> (held, hidden width, block of it, row tile; None: the call's own)
SORTED_SHAPES = {
    "tile128": ((2, 10), 48, None, None),
    "tile256-two-f-blocks": ((3, 4), 256, 128, 256),
}


def _prompt_case(routing, held, f, dtype, seed=0, T=300, k=4, E=16, d=64):
    """A prompt of ``T`` tokens whose ``T * k`` = 1,200 pairs are no whole
    number of row tiles: a held range that is part of the published
    experts, a ``live`` mask with dead tokens, an expert nobody chose."""
    first, count = held
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((T, d)), dtype)
    gate, up = (jnp.asarray(rng.standard_normal((count, d, f)) * 0.2, dtype)
                for _ in range(2))
    down = jnp.asarray(rng.standard_normal((count, f, d)) * 0.2, dtype)
    logits = rng.standard_normal((T, E)).astype(np.float32)
    logits[:, first + 1] = -50.0                # an expert nobody chose
    if routing == "alike":                      # the warm-up's zero tokens
        logits[:] = logits[0]
    elif routing == "elsewhere":                # no pair falls here
        logits[:, first:first + count] = -50.0
    idx, w = moe.route_token_choice(jnp.asarray(logits), None, k,
                                    score="softmax")
    live = jnp.asarray(rng.random(T) < 0.8)
    return x, idx, w, held, gate, up, down, live


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("act", ["silu", "relu"])
@pytest.mark.parametrize("shape", sorted(SORTED_SHAPES))
@pytest.mark.parametrize("routing", ROUTINGS)
def test_sorted_kernel_is_the_loop_is_the_dense_sum(routing, shape, act,
                                                    dtype, monkeypatch):
    """The prompt's kernel against the loop and the dense sum: random
    routing, every token choosing alike (all pairs on three experts),
    every pair held elsewhere (no visit: exactly zero); 128- and 256-row
    tiles, one and two blocks of the hidden width — the same output
    (the products' float32 sums in another order) and the same counts."""
    held, f, fb, tm = SORTED_SHAPES[shape]
    args = _prompt_case(routing, held, f, jnp.dtype(dtype))
    x, idx = args[0], args[1]
    monkeypatch.setenv(FORCE, "1")
    assert moe.held_experts_impl(x, args[4], idx.size) == "pallas_sorted"
    if fb is None:
        y, counts = moe.held_experts_ffn(*args, act=act)
    else:
        y, counts = ge.held_experts_sorted(*args, act=act, row_tile=tm,
                                           f_block=fb, interpret=True)
    monkeypatch.delenv(FORCE)
    y_loop, counts_loop = moe.held_experts_ffn(*args, act=act)
    assert [int(c) for c in counts] == [int(c) for c in counts_loop]
    pairs, pairs_held, touched = (int(c) for c in counts)
    assert pairs == int(jnp.sum(args[-1])) * idx.shape[1]
    if routing == "elsewhere":
        assert pairs_held == touched == 0 and not np.asarray(y).any()
    else:
        assert 0 < pairs_held < pairs
        assert 0 < touched < held[1]            # one expert held is idle
        assert routing != "alike" or touched <= idx.shape[1]
    tol = dict(atol=2e-5, rtol=2e-5) if dtype == "float32" \
        else dict(atol=2e-2, rtol=2e-2)
    assert y.dtype == jnp.float32 and y.shape == x.shape
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_loop), **tol)
    np.testing.assert_allclose(np.asarray(y), _dense(*args, act), **tol)
    assert not np.asarray(y)[~np.asarray(args[-1])].any()   # dead tokens


@pytest.mark.parametrize("routing", ROUTINGS)
def test_tile_visits_follow_the_pairs_not_their_spread(routing):
    """The sorted form's work list: a visit a (run, tile it reaches into)
    in expert order, an idle expert in none, a tile past the held rows in
    none; every held row is written by exactly one visit; the padding
    repeats the last entry."""
    held, tm = (2, 10), 128
    _, idx, _, _, _, _, _, live = _prompt_case(routing, held, 48,
                                               jnp.float32)
    _, _, n, starts, _, _ = ge.group_pairs(idx, held, live)
    n_e, starts = n[:held[1]], starts[:held[1]]
    n_steps = -(-idx.size // tm) + held[1] - 1
    nv, tile, expert, lo, hi = (np.asarray(a) for a in ge._tile_visits(
        n_e, starts, tm, n_steps))
    nv = int(nv[0])
    n_e, starts = np.asarray(n_e), np.asarray(starts)
    want = [(e, t) for e in range(held[1]) if n_e[e]
            for t in range(starts[e] // tm,
                           (starts[e] + n_e[e] - 1) // tm + 1)]
    assert list(zip(expert[:nv], tile[:nv])) == want
    assert nv <= -(-int(n_e.sum()) // tm) + int((n_e > 0).sum())
    written = np.zeros(n_steps * tm, int)
    for i in range(nv):
        rows = np.arange(tile[i] * tm, (tile[i] + 1) * tm)
        written[rows[(rows >= lo[i]) & (rows < hi[i])]] += 1
    assert (written[:n_e.sum()] == 1).all() and not written[n_e.sum():].any()
    if nv:
        assert (expert[nv:] == expert[nv - 1]).all() \
            and (tile[nv:] == tile[nv - 1]).all()
    assert tile.max() < -(-idx.size // tm) and expert.max() < held[1]


def test_a_programs_layers_share_one_sorted_body(monkeypatch):
    """Eight expert layers of one program on a prompt's shapes, lowered for
    a TPU: ONE ``tpu_custom_call`` (the jitted prompt path is traced and
    lowered once and called eight times), and what the rule answered is
    recorded for EVERY program traced, not the first alone."""
    monkeypatch.delenv(FORCE, raising=False)
    monkeypatch.setattr(fa, "_platform_of", lambda x: "tpu")
    T, k, count, d, f, layers = 256, 4, 128, 128, 128, 8
    sds = jax.ShapeDtypeStruct

    def a_program():                    # a program, a function object
        def program(x, idx, w, live, weights):
            for gate, up, down in weights:
                y, _ = moe.held_experts_ffn(x, idx, w, (0, count), gate, up,
                                            down, live, act="relu")
                x = x + y.astype(x.dtype)
            return x
        return program

    args = (sds((T, d), jnp.bfloat16), sds((T, k), jnp.int32),
            sds((T, k), jnp.float32), sds((T,), jnp.bool_),
            [(sds((count, d, f), jnp.bfloat16),) * 2
             + (sds((count, f, d), jnp.bfloat16),)] * layers)
    for _ in range(3):                  # a program, then two more
        with moe.traced_expert_impls() as seen:
            text = jax.jit(a_program()).trace(*args).lower(
                lowering_platforms=("tpu",)).as_text()
        assert seen == {"pallas_sorted"}
    assert text.count("tpu_custom_call") == 1
    assert text.count("call @") >= layers and "stablehlo.while" not in text


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
