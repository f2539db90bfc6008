"""SmallThinker through the serving path against the plain reference
(``benchmark/chip/reference/smallthinker.py``), at a tiny size on the CPU:
log-probabilities of prefill-then-decode through the paged cache (a miss
prefill, a prefix hit, contexts that cross the tiny window, single steps and
bursts), the same in bfloat16 against the float8 control, four shares of the
experts adding up to the uncut layer, the router reading the layer's input,
the softmax of the chosen logits, no token dropped at a skewed router, and
the window counter.
"""
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

from reference import smallthinker as ref               # noqa: E402
from programs import smallthinker_serve as prog         # noqa: E402

from incubator_mxnet_tpu.base import MXNetError         # noqa: E402
from incubator_mxnet_tpu.models import moe              # noqa: E402
from incubator_mxnet_tpu.serving import (               # noqa: E402
    ContinuousBatcher, GenerationEngine)


def _full(lp):
    """top-N (values, ids) with N = vocab -> the whole log-softmax row."""
    vals, ids = (np.asarray(a) for a in lp)
    out = np.zeros(vals.shape, np.float32)
    np.put_along_axis(out, ids, vals, -1)
    return out


def _cfg(dtype="float32", **over):
    with open(os.path.join(CHIP, "tests", "tiny_smallthinker.json")) as f:
        cfg = json.load(f)
    cfg["deployment"]["param_dtype"] = dtype
    cfg.update(over)
    return cfg


def _engine(cfg, seed=7, **kw):
    params = ref.init_params(cfg, seed)
    net = prog.build_net(cfg)
    prog.load_weights(net, params)
    args = dict(name="tiny", max_slots=3, max_len=128,
                prefill_buckets=[16, 64], block_size=16, scan_steps=8,
                logprobs_topn=cfg["vocab_size"])
    args.update(kw)
    return GenerationEngine(net, **args), params


def _serve(eng, V):
    """Two streams through the paged programs: A (20 tokens: inside the
    window of 24, which its decoding then crosses) prefills on a miss, B
    (40 tokens, already past the window) shares nothing with it but its
    own first 32 tokens with a third stream that prefilled them first (a
    prefix hit, the suffix program); then three single steps and two
    8-step bursts (B's budget ends it inside the second).  Returns
    ``{slot: (prompt length, tokens, log-softmax rows)}``."""
    rng = np.random.RandomState(3)
    A = [int(t) for t in rng.randint(0, V, 20)]
    B = [int(t) for t in rng.randint(0, V, 40)]
    eng.prefill(B[:32] + [1, 2, 3], 2, reserve_tokens=40)
    eng.release_slot(2)
    seqs, rows = {0: list(A), 1: list(B)}, {0: [], 1: []}
    for s in (0, 1):
        seqs[s].append(eng.prefill(seqs[s], s,
                                   reserve_tokens=len(seqs[s]) + 30))
        rows[s].append(_full(eng.last_prefill_logprobs()))
    assert eng.pool.hits == 2                   # B's two shared blocks
    lt, pv = np.zeros(3, np.int32), np.zeros(3, np.int32)

    def heads():
        for s in (0, 1):
            lt[s], pv[s] = seqs[s][-1], len(seqs[s]) - 1

    for _ in range(3):
        heads()
        nxt = eng.decode(lt, pv)
        lp = _full(eng.last_logprobs())
        for s in (0, 1):
            seqs[s].append(int(nxt[s]))
            rows[s].append(lp[s])
    for budget in ([8, 8, 0], [8, 5, 0]):
        heads()
        toks, emitted = eng.decode_burst(
            lt, pv, np.array(budget, np.int32), np.full(3, -1, np.int32),
            np.array([True, True, False]))
        lp = _full(eng.last_logprobs())
        assert emitted.tolist() == budget
        for s in (0, 1):
            for j in range(emitted[s]):
                seqs[s].append(int(toks[j, s]))
                rows[s].append(lp[j, s])
    return {s: (len(A) if s == 0 else len(B), seqs[s], np.stack(rows[s]))
            for s in (0, 1)}


def _reference_rows(cfg, params, n_prompt, seq, precision="float32"):
    fwd = ref.make_forward(cfg, precision)
    lg = fwd(params, jnp.asarray(np.asarray(seq, np.int32)[None]))[0]
    return np.asarray(jax.nn.log_softmax(lg, -1))[n_prompt - 1:len(seq) - 1]


@pytest.mark.parametrize("slot", [0, 1],
                         ids=["miss_crossing_the_window", "prefix_hit"])
def test_paged_float32_matches_reference(slot):
    """Every log-probability the served path computed — at the prefill's
    last position, three single steps, two 8-step bursts — against the
    reference's full forward over the same tokens.  Both sides are
    float32 with exact float32 products on the CPU; they differ in the
    order of sums (cache strips, grouped experts), which leaves a few
    float32 ulps on log-probabilities of size ~5: 2e-5.  Slot 0's context
    grows from 20 to 39 written positions, across the window of 24."""
    cfg = _cfg()
    eng, params = _engine(cfg)
    n_prompt, seq, rows = _serve(eng, cfg["vocab_size"])[slot]
    want = _reference_rows(cfg, params, n_prompt, seq)
    assert rows.shape == want.shape and len(rows) == (20 if slot == 0
                                                       else 17)
    np.testing.assert_allclose(rows, want, atol=2e-5, rtol=0)


def test_paged_bfloat16_is_the_stated_precision_and_float8_is_not():
    """Served in bfloat16 (parameters, activations, pool; float32 norms,
    router, softmax) the path's mean error against the float32 reference
    is bfloat16's: within 1.5 x the bfloat16 reference's own (one routing
    flip moves single rows by 0.1, so the mean is compared, not the
    maximum); the float8 reference fails that by a wide margin."""
    cfg = _cfg("bfloat16")
    eng, params = _engine(cfg)
    assert {str(c.dtype) for c in eng._cache} == {"bfloat16"}
    err = {"served": [], "bfloat16": [], "float8": []}
    for n_prompt, seq, rows in _serve(eng, cfg["vocab_size"]).values():
        want = _reference_rows(cfg, params, n_prompt, seq)
        err["served"].append(np.abs(rows - want))
        for p in ("bfloat16", "float8"):
            err[p].append(np.abs(
                _reference_rows(cfg, params, n_prompt, seq, p) - want))
    mean = {k: float(np.concatenate(v).mean()) for k, v in err.items()}
    tol = 1.5 * mean["bfloat16"]
    assert mean["served"] <= tol, mean
    assert mean["float8"] > 3 * tol, mean


def test_the_model_alone_is_the_reference():
    """``SmallThinkerModel``'s own forward (no engine, no cache)."""
    import incubator_mxnet_tpu as mx
    cfg = _cfg()
    params = ref.init_params(cfg, 11)
    net = prog.build_net(cfg)
    prog.load_weights(net, params)
    ids = np.random.RandomState(0).randint(0, cfg["vocab_size"], (2, 50))
    got = net(mx.nd.array(ids.astype(np.int32))).asnumpy()
    want = np.asarray(ref.make_forward(cfg)(params, jnp.asarray(ids)))
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_four_shares_of_the_experts_add_up_to_the_uncut_model():
    """Guide section 4's tying test, though the cell holds every expert:
    a ONE-layer model of 64 experts, top 6, cut into four chips' shares of
    16 — the router keeps its 64 outputs and its 6 experts a token — where each
    share computes attention alike and its own experts' part of the sum.
    The four shares' expert parts, with the residual and attention (what
    every chip computes alike) counted once, give the uncut layer, for
    the model and for the reference given the same shares."""
    import incubator_mxnet_tpu as mx
    one = dict(num_hidden_layers=1, rope_layout=[1],
               sliding_window_layout=[1], moe_num_active_primary_experts=6)
    E, n = 64, 16
    cfg = _cfg(moe_num_primary_experts=E, **one)
    params = ref.init_params(cfg, 5)
    V, d = cfg["vocab_size"], cfg["hidden_size"]
    ids = np.random.RandomState(1).randint(0, V, (1, 40)).astype(np.int32)

    def hidden(c, p):
        """The layer's output (before the final norm and head)."""
        net = prog.build_net(c)
        prog.load_weights(net, p)
        return net.layers[0](mx.nd.array(
            np.asarray(p["embed_tokens"])[ids])).asnumpy()

    whole = hidden(cfg, params)
    parts = []
    for first in range(0, E, n):
        share = _cfg(moe_num_primary_experts=n, first_expert=first,
                     moe_num_primary_experts_published=E, **one)
        p = dict(params, layers=[dict(
            params["layers"][0],
            **{m: params["layers"][0][m][first:first + n]
               for m in ("experts_gate", "experts_up", "experts_down")})])
        parts.append(hidden(share, p))
        # and the reference, given the same share, is that share
        want = ref.make_forward(share)(p, jnp.asarray(ids))
        got = prog.build_net(share)
        prog.load_weights(got, p)
        np.testing.assert_allclose(
            got(mx.nd.array(ids)).asnumpy(), np.asarray(want), atol=2e-5,
            rtol=2e-5)
    # a share = (residual + attention) + its experts' part; a share of no
    # expert is that common part alone
    none = _cfg(moe_num_primary_experts=n, first_expert=0,
                moe_num_primary_experts_published=E, **one)
    zero = dict(params, layers=[dict(
        params["layers"][0],
        **{m: jnp.zeros_like(params["layers"][0][m][:n])
           for m in ("experts_gate", "experts_up", "experts_down")})])
    common = hidden(none, zero)
    total = common + sum(p - common for p in parts)
    assert np.abs(whole - common).max() > 1e-4           # the experts count
    np.testing.assert_allclose(total, whole, atol=2e-5, rtol=2e-5)


def test_the_router_reads_the_layers_input():
    """The layer's expert part is the sum over the experts chosen from
    ``h``, the layer's input — not from ``RMSNorm(h)`` (a gain on the
    attention norm reorders those logits) and not from the post-attention
    state (a large attention output reorders those): fed either, the
    layer's output is far from what it gives."""
    cfg = _cfg(num_hidden_layers=1, rope_layout=[1],
               sliding_window_layout=[1])
    params = ref.init_params(cfg, 9)
    rng = np.random.default_rng(0)
    layer = dict(params["layers"][0])
    layer["input_layernorm"] = jnp.asarray(
        rng.uniform(0.2, 3.0, cfg["hidden_size"]), jnp.float32)
    layer["o_proj"] = layer["o_proj"] * 40.0
    T, k = 30, cfg["moe_num_active_primary_experts"]
    h = 0.5 * rng.standard_normal((T, cfg["hidden_size"])).astype(np.float32)

    def run(lay):
        net = prog.build_net(cfg)
        prog.load_weights(net, dict(params, layers=[lay]))
        return np.asarray(net.layers[0].serve_prefill(
            jnp.asarray(h)[None], jnp.arange(T, dtype=jnp.int32)[None])[0])[0]

    out = run(layer)
    h1 = run(dict(layer, experts_down=jnp.zeros_like(layer["experts_down"])))
    rms = lambda a: a / np.sqrt((a * a).mean(-1, keepdims=True) + 1e-6)  # noqa: E731
    router = np.asarray(layer["router"])

    def routed_from(state):
        logits = state @ router
        idx = np.argsort(-logits, -1)[:, :k]
        top = np.take_along_axis(logits, idx, -1)
        w = np.exp(top - top.max(-1, keepdims=True))
        w = w / w.sum(-1, keepdims=True)
        y, m = jnp.asarray(rms(h1)), np.zeros_like(h1)
        for e in range(cfg["moe_num_primary_experts"]):
            m += np.asarray(moe._glu(
                y, layer["experts_gate"][e], layer["experts_up"][e],
                layer["experts_down"][e], "relu")) \
                * np.where(idx == e, w, 0.0).sum(-1)[:, None]
        return h1 + m

    scale = np.abs(out - h1).max()
    assert scale > 1e-3                                   # the experts count
    np.testing.assert_allclose(out, routed_from(h), atol=1e-3 * scale)
    normed = rms(h) * np.asarray(layer["input_layernorm"])
    assert np.abs(out - routed_from(normed)).max() > 0.1 * scale
    assert np.abs(out - routed_from(h1)).max() > 0.1 * scale


def test_softmax_of_the_chosen_is_the_softmax_top_k_renormalised():
    rng = np.random.default_rng(4)
    logits = jnp.asarray(3.0 * rng.standard_normal((50, 64)), jnp.float32)
    idx, w = moe.route_token_choice(logits, None, 6, score="softmax")
    full = np.asarray(jax.nn.softmax(logits, -1))
    order = np.argsort(-full, -1)[:, :6]
    np.testing.assert_array_equal(np.asarray(idx), order)
    top = np.take_along_axis(full, order, -1)
    np.testing.assert_allclose(np.asarray(w),
                               top / top.sum(-1, keepdims=True), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 1.0, rtol=1e-6)
    with pytest.raises(MXNetError, match="no choice bias"):
        moe.route_token_choice(logits, jnp.zeros(64), 6, score="softmax")
    with pytest.raises(MXNetError, match="no such routing score"):
        moe.route_token_choice(logits, None, 6, score="tanh")


@pytest.mark.parametrize("tokens", [7, 200])
def test_no_token_is_dropped_at_a_skewed_router(tokens):
    """A router that sends EVERY token to expert 3 (a capacity layer would
    drop most of them): all of them are computed by the ReGLU product,
    and dead tokens route nowhere."""
    rng = np.random.default_rng(1)
    d, f, E, k = 32, 24, 16, 3
    mk = lambda *s: jnp.asarray(0.2 * rng.standard_normal(s), jnp.float32)  # noqa: E731
    gate, up, down = mk(E, d, f), mk(E, d, f), mk(E, f, d)
    x = mk(tokens, d)
    logits = (x @ mk(d, E)).at[:, 3].add(50.0)
    idx, wt = moe.route_token_choice(logits, None, k, score="softmax")
    assert bool(jnp.all(idx[:, 0] == 3))
    live = jnp.arange(tokens) % 2 == 0
    y, (pairs, held, touched) = moe.held_experts_ffn(
        x, idx, wt, (0, E), gate, up, down, live, act="relu")
    assert int(pairs) == int(held) == int(jnp.sum(live)) * k
    assert int(touched) <= E
    want = sum(
        moe._glu(x, gate[e], up[e], down[e], "relu")
        * jnp.sum(jnp.where(idx == e, wt, 0.0), -1)[:, None]
        for e in range(E))
    np.testing.assert_allclose(np.asarray(y)[::2], np.asarray(want)[::2],
                               atol=2e-5, rtol=2e-5)
    assert not np.asarray(y)[1::2].any()
    # and ReLU is not SiLU
    y_silu, _ = moe.held_experts_ffn(x, idx, wt, (0, E), gate, up, down,
                                     live)
    assert np.abs(np.asarray(y_silu) - np.asarray(y)).max() > 1e-3


def test_counters_reach_the_batcher_stats():
    """What the expert layers count in the decode programs and the two
    host-arithmetic context counters show in ``stats()`` (``GET
    /v1/models``): 4 layers x 3 experts a token a live slot-step; written
    positions capped at the window of 24 for the window counter."""
    cfg = _cfg()
    eng, _ = _engine(cfg, logprobs_topn=0)
    assert eng.warmup() == eng.expected_programs == 7
    assert eng.decode_counters()["moe_pairs_total"] == 0    # not warm-up's
    assert eng.decode_counters()["decode_window_tokens"] == 0
    bat = ContinuousBatcher(eng, name="tiny")
    try:
        out = bat.submit_async([5, 9, 2, 40, 17], max_new_tokens=32)
        assert len(out.result(60)) == 32
        st = bat.stats()
    finally:
        bat.close()
    steps = 31                                  # the prefill gave token 1
    assert st["moe_pairs_total"] == st["moe_pairs_held"] == steps * 4 * 3
    assert 0 < st["moe_experts_touched"] <= st["moe_pairs_held"]
    # write heads 5..35 -> written positions 6..36, the window holds 24
    assert st["decode_context_tokens"] == sum(range(6, 37))
    assert st["decode_window_tokens"] == sum(min(n, 24)
                                             for n in range(6, 37))
    assert eng.program_inventory()["paged_attention"] == "lax_gather"


def test_a_model_without_a_window_has_no_window_counter():
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.models.gpt import GPTModel
    mx.random.seed(3)
    net = GPTModel(vocab_size=50, units=32, hidden_size=64, num_layers=2,
                   num_heads=2, max_length=64, dropout=0.0)
    net.initialize(init=mx.init.Normal(0.6))
    net(mx.nd.array(np.zeros((1, 2), np.int32)))
    eng = GenerationEngine(net, name="nowin", max_slots=2, max_len=64)
    assert "decode_window_tokens" not in eng.decode_counters()


def test_the_engine_serves_it_through_the_kernel_when_forced(monkeypatch):
    """With the test hook that takes the Pallas kernels wherever shapes
    allow (interpreted on the CPU), heads of 128 features on two KV heads
    take the grouped kernel in every layer — windowed and full — and the
    tokens are those of the gather; such a pool's rows are written through
    the ``[N, H * bs, D]`` view the kernel reads."""
    cfg = _cfg(head_dim=128, num_hidden_layers=2, rope_layout=[0, 1],
               sliding_window_layout=[0, 1], hidden_size=32,
               num_attention_heads=4, moe_ffn_hidden_size=16)
    prompt = [int(t) for t in np.random.RandomState(5).randint(
        0, cfg["vocab_size"], 30)]
    out = {}
    for force in (False, True):
        if force:
            monkeypatch.setenv("MXNET_FA_DECODE_FORCE_PALLAS", "1")
        else:
            monkeypatch.delenv("MXNET_FA_DECODE_FORCE_PALLAS",
                               raising=False)
        eng, _ = _engine(cfg, name=f"k{int(force)}", max_slots=2,
                         max_len=64, prefill_buckets=[32], scan_steps=4,
                         logprobs_topn=0)
        out[force] = eng.generate(prompt, max_new_tokens=12)
        assert eng.program_inventory()["paged_attention"] == (
            "pallas" if force else "lax_gather")
    assert out[True] == out[False]


def test_the_constructor_refuses_what_it_cannot_write_down():
    cfg = _cfg()
    with pytest.raises(MXNetError, match="published one"):
        prog.build_net(dict(cfg, norm_topk_prob=False))
    with pytest.raises(MXNetError, match="num_hidden_layers"):
        prog.build_net(dict(cfg, rope_layout=[0, 1]))
    with pytest.raises(MXNetError, match="not among"):
        prog.build_net(dict(cfg, first_expert=60))


def test_paged_groups_counter_is_the_kernels_work_list(monkeypatch):
    """``mxtpu_paged_groups_total{fetch}`` — host arithmetic over the
    engine's tables — is the grouped kernel's work list: with the kernels
    forced and heads of 128 features, through a miss, a prefix hit,
    contexts that cross the window, single steps and bursts, over a pool
    whose FIFO has been turned (so a table spans its seam and has a joint),
    both totals equal a count by loops and show in ``decode_counters()``
    (``GET /v1/models``)."""
    from paged_groups import check_paged_groups, turn_pool
    monkeypatch.setenv("MXNET_FA_DECODE_FORCE_PALLAS", "1")
    cfg = _cfg()
    cfg["head_dim"] = 128
    eng, _ = _engine(cfg, name="groups")
    assert "paged_groups_run" not in eng.decode_counters()    # not traced
    turn_pool(eng, 16)
    got = check_paged_groups(
        eng, lambda: _serve(eng, cfg["vocab_size"]), monkeypatch)
    assert got["run"] > 0 and got["blocks"] > 0
