"""dots3-note through the serving path against the plain reference
(``benchmark/chip/reference/dots3.py``), at a tiny size on the CPU: logits
of a miss prefill, a prefix hit's suffix, a miss in chunks, single steps
and bursts through the paged latent cache, with contexts past the tiny
window (21) and past the tiny ``index_topk`` (12) in one batch; the
absorbed form against the unabsorbed; the program's chosen keys against
the reference's; the shares adding up to the uncut layer; what the layout
states and what the engine refuses.
"""
import json
import os
import sys
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

CHIP = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "benchmark", "chip")
if CHIP not in sys.path:
    sys.path.insert(0, CHIP)

from reference import dots3 as ref                      # noqa: E402
from programs import dots3_serve as prog                # noqa: E402

from incubator_mxnet_tpu.base import MXNetError         # noqa: E402
from incubator_mxnet_tpu.kernels import latent_attention as la  # noqa: E402
from incubator_mxnet_tpu.serving import (               # noqa: E402
    ContinuousBatcher, GenerationEngine)
from incubator_mxnet_tpu.serving.kvcache import KVLayout  # noqa: E402


def _cfg(dtype="float32", **over):
    with open(os.path.join(CHIP, "tests", "tiny_dots3.json")) as f:
        cfg = json.load(f)
    cfg["deployment"]["param_dtype"] = dtype
    cfg.update(over)
    return cfg


def _params(cfg, seed):
    """The seed's weights with the two biases (zero as published weights
    start) made random: a choice-only bias and a LayerNorm bias that are
    zero test nothing."""
    params = ref.init_params(cfg, seed)
    key = jax.random.PRNGKey(seed)
    for i, layer in enumerate(params["layers"]):
        for j, name in enumerate(ref.ZEROS):
            if name in layer:
                layer[name] = (0.05 * jax.random.normal(
                    jax.random.fold_in(key, 2 * i + j), layer[name].shape)
                ).astype(layer[name].dtype)
    return params


def _engine(cfg, seed=7, **kw):
    params = _params(cfg, seed)
    net = prog.build_net(cfg)
    prog.load_weights(net, params)
    args = dict(name="tiny", max_slots=3, max_len=128,
                prefill_buckets=[16, 32], block_size=16, scan_steps=4,
                logprobs_topn=cfg["vocab_size"])
    args.update(kw)
    return GenerationEngine(net, **args), params


def _full(lp):
    """top-N (values, ids) with N = vocab -> the whole log-softmax row."""
    vals, ids = (np.asarray(a) for a in lp)
    out = np.zeros(vals.shape, np.float32)
    np.put_along_axis(out, ids, vals, -1)
    return out


def _serve(eng, V):
    """Three streams through the paged programs.  A (28 tokens: past the
    window of 21 and the index's 12) prefills on a miss in ONE bucket; B
    shares A's first 16 tokens (a block: a prefix hit, the suffix
    program); C (75 tokens, more than the largest bucket of 32) is a miss
    in three chunks.  Then three single steps and three bursts of 4 (B's
    budget of 3 ends it inside each).  Returns ``{slot: (prompt length,
    tokens, log-softmax rows)}``."""
    rng = np.random.RandomState(3)
    A = [int(t) for t in rng.randint(0, V, 28)]
    B = A[:16] + [int(t) for t in rng.randint(0, V, 9)]
    C = [int(t) for t in rng.randint(0, V, 75)]
    prompts = {0: A, 1: B, 2: C}
    seqs = {s: list(p) for s, p in prompts.items()}
    rows = {s: [] for s in seqs}
    for s in seqs:
        seqs[s].append(eng.prefill(seqs[s], s,
                                   reserve_tokens=len(seqs[s]) + 30))
        rows[s].append(_full(eng.last_prefill_logprobs()))
    assert eng.pool.hits == 1                   # B's shared block
    assert eng.kv_stats()["prefill_tokens"] == {
        "miss": 28 + 75, "hit": 9, "prefix_hit": 16}
    lt, pv = np.zeros(3, np.int32), np.zeros(3, np.int32)

    def heads():
        for s in seqs:
            lt[s], pv[s] = seqs[s][-1], len(seqs[s]) - 1

    for _ in range(3):
        heads()
        nxt = eng.decode(lt, pv)
        lp = _full(eng.last_logprobs())
        for s in seqs:
            seqs[s].append(int(nxt[s]))
            rows[s].append(lp[s])
    for _ in range(3):
        heads()
        toks, emitted = eng.decode_burst(
            lt, pv, np.array([4, 3, 4], np.int32), np.full(3, -1, np.int32),
            np.ones(3, bool))
        lp = _full(eng.last_logprobs())
        assert emitted.tolist() == [4, 3, 4]
        for s in seqs:
            for j in range(emitted[s]):
                seqs[s].append(int(toks[j, s]))
                rows[s].append(lp[j, s])
    return {s: (len(prompts[s]), seqs[s], np.stack(rows[s])) for s in seqs}


def _reference_rows(cfg, params, n_prompt, seq, precision="float32"):
    fwd = ref.make_forward(cfg, precision)
    lg = fwd(params, jnp.asarray(np.asarray(seq, np.int32)[None]))[0]
    return np.asarray(jax.nn.log_softmax(lg, -1))[n_prompt - 1:len(seq) - 1]


@pytest.fixture(scope="module")
def served():
    cfg = _cfg()
    eng, params = _engine(cfg)
    return cfg, params, _serve(eng, cfg["vocab_size"]), eng


@pytest.mark.parametrize("slot", [0, 1, 2],
                         ids=["miss", "prefix_hit", "miss_in_chunks"])
def test_paged_float32_matches_reference(served, slot):
    """Every log-probability the served path computed — at the prefill's
    last position (of a one-bucket miss, of a hit's suffix, of the last
    of three chunks), three single steps, three bursts — against the
    reference's full forward over the same tokens.  Both sides float32
    with exact products on the CPU, where no index score ties: they differ
    in the order of sums and in the form (the cache's rows absorbed, the
    reference's expanded), a few float32 ulps on log-probabilities of
    size ~5."""
    cfg, params, out, _ = served
    n_prompt, seq, rows = out[slot]
    want = _reference_rows(cfg, params, n_prompt, seq)
    assert rows.shape == want.shape and len(rows) == (13 if slot == 1
                                                       else 16)
    np.testing.assert_allclose(rows, want, atol=3e-5, rtol=0)


def test_the_counters_of_an_indexed_model(served):
    """What the two steps' worth of host arithmetic says of the serving
    above: every written position scored in both full layers, min(written,
    12) chosen; the window's 21; and the bytes a position keeps."""
    _, _, out, eng = served
    got = eng.decode_counters()
    written = [n + 1 + j for n, seq, _ in out.values()
               for j in range(len(seq) - n - 1)]
    assert got["decode_context_tokens"] == sum(written)
    assert got["index_keys_scored"] == 2 * sum(written)
    assert got["index_keys_selected"] == 2 * sum(min(w, 12) for w in written)
    assert got["decode_window_tokens"] == sum(min(w, 21) for w in written)
    # two full layers keep 32 + 8 and an index key of 16, three sliding
    # ones 40 + 8, in float32
    assert eng.layout.block_bytes(1) == 4 * (2 * (40 + 16) + 3 * 48)
    assert [c.shape for c in eng._cache] == [
        (25, 16, 40)] * 2 + [(25, 16, 48)] * 3 + [(25, 16, 16)] * 2
    assert eng.cache_bytes == 25 * eng.layout.block_bytes(16)
    # the sliding layers' read and the indexed layers' choice, by name
    assert eng.program_inventory()["paged_attention"] \
        == "lax_gather+select:lax"


def test_the_model_alone_is_the_reference():
    """``Dots3Model``'s own forward (no engine, no cache): the unabsorbed
    form over a prompt's own rows, two sequences at once."""
    import incubator_mxnet_tpu as mx
    cfg = _cfg()
    params = _params(cfg, 11)
    net = prog.build_net(cfg)
    prog.load_weights(net, params)
    ids = np.random.RandomState(0).randint(0, cfg["vocab_size"], (2, 50))
    got = net(mx.nd.array(ids.astype(np.int32))).asnumpy()
    want = np.asarray(ref.make_forward(cfg)(params, jnp.asarray(ids)))
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_paged_bfloat16_is_the_stated_precision_and_float8_is_not():
    """Served in bfloat16 the path's mean error against the float32
    reference is bfloat16's own (1.5 x the bfloat16 reference's: a
    routing flip or one key of twelve chosen otherwise moves single rows,
    so the mean is compared); the float8 reference fails that by a wide
    margin."""
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
    assert mean["float8"] > 2 * tol, mean


# -- the two forms, and the choice -------------------------------------------

def _latent_case(seed, T=40, H=3, r=16, d_n=8, d_r=4, d_v=6):
    rng = np.random.default_rng(seed)
    mk = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731
    return dict(q_n=mk(T, H, d_n), q_r=mk(T, H, d_r), rows=mk(T, r + d_r),
                w_uk=mk(r, H, d_n), w_uv=mk(r, H, d_v),
                q_i=mk(T, 2, 5), w_i=mk(T, 2), k_i=mk(T, 5))


@pytest.mark.parametrize("window,k", [(None, None), (9, None), (None, 7)],
                         ids=["all", "window", "chosen"])
def test_absorbed_is_unabsorbed(window, k):
    """One layer, two forms: every query of a prompt in the unabsorbed
    form against the same query alone in the absorbed form over the same
    rows — all of them, a window's, the index's choice."""
    c = _latent_case(5)
    T = c["q_n"].shape[0]
    pos = jnp.arange(T, dtype=jnp.int32)
    scale = 0.3
    select = None if k is None else (c["q_i"], c["w_i"], c["k_i"], k)
    want = la.latent_prompt_attention(
        c["q_n"], c["q_r"], c["rows"], pos, pos, c["w_uk"], c["w_uv"],
        scale, window, select)
    live = pos[None, :] <= pos[:, None]
    if window is not None:
        live = live & (pos[None, :] > pos[:, None] - window)
    if k is not None:
        scores = jnp.where(live, la.index_scores(c["q_i"], c["w_i"],
                                                 c["k_i"]), -jnp.inf)
        idx, valid = la.choose_topk(scores, k)
        live = jnp.zeros_like(live).at[pos[:, None], idx].set(valid)
        assert live.sum(-1).tolist() == [min(k, t + 1) for t in range(T)]
        assert bool(jnp.all(live == la.chosen_mask(scores, k)))
    rows = jnp.broadcast_to(c["rows"][None], (T,) + c["rows"].shape)
    got = la.absorbed_attention(
        c["q_n"], c["q_r"], c["w_uk"], c["w_uv"],
        lambda q: la._rows_attention(q, rows, live, 16, scale))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_the_choice_is_exact_at_ties():
    """Equal scores at the threshold: the lowest positions are taken, as
    many as there is room for, by the mask and by the list alike."""
    s = jnp.asarray([[3., 1., 2., 2., 2., -jnp.inf, 0.],
                     [1., 1., 1., 1., -jnp.inf, -jnp.inf, -jnp.inf]])
    assert la.chosen_mask(s, 3).tolist() == [
        [True, False, True, True, False, False, False],
        [True, True, True, False, False, False, False]]
    idx, valid = la.choose_topk(s, 5)
    assert sorted(idx[0].tolist()) == [0, 1, 2, 3, 4] and bool(valid[0].all())
    assert valid[1].tolist() == [True] * 4 + [False]
    assert la.chosen_mask(s, 9).tolist() == (s > -jnp.inf).tolist()


def test_the_programs_choice_is_the_references(served):
    """The keys the reference's two full layers choose for sequence A's
    last position (float32, no tie) are those the decode program's index
    picks there: the same 12 of 43 in either layer."""
    cfg, params, out, eng = served
    _, seq, _ = out[0]
    want = ref.chosen_sets(cfg, params, seq[:-1])
    assert len(want) == 2
    net = eng.block
    pos = jnp.arange(len(seq) - 1, dtype=jnp.int32)[None]
    h = net.serve_embed(jnp.asarray([seq[:-1]], jnp.int32), pos)
    for l, layer in enumerate(net.serve_layers()):
        if layer.select is not None:
            x = ref._rms(h, layer._w("input_layernorm"), 1e-5)
            _, _, _, _, _, (q_i, w_i, k_i) = layer._attention_inputs(x, pos)
            scores = la.index_scores(q_i[0, -1:], w_i[0, -1:], k_i[0])
            idx, valid = la.choose_topk(scores, layer.select)
            assert bool(valid.all())
            assert sorted(idx[0].tolist()) == np.flatnonzero(
                np.asarray(want[l][-1])).tolist()
        h = layer.serve_prefill(h, pos)[0]


# -- the shares -----------------------------------------------------------------

def test_the_head_shares_add_up():
    """4 chips each holding 1 of a full layer's 4 heads and half of a
    sliding layer's 2... the whole attention's output is the sum of the
    shares' ``W_o`` parts: a share's forward over ITS heads' columns of
    ``q_b``, ``kv_b``, ``gate`` and rows of ``o`` (the low-rank ``_a``
    projections, the norms and the indexer whole on every chip)."""
    whole = _cfg(num_hidden_layers=1, layer_types=["full_attention"])
    params = _params(whole, 3)
    p = params["layers"][0]
    _, H, r_q, r, d_n, d_r, d_v, _ = ref.kind(whole, 0)
    ids = np.random.RandomState(1).randint(0, whole["vocab_size"], (1, 30))

    def attention_part(cfg, lp):
        """h1 - h of the one layer: the attention's contribution."""
        net = prog.build_net(cfg)
        prog.load_weights(net, dict(params, layers=[lp]))
        pos = jnp.arange(30, dtype=jnp.int32)[None]
        h = net.serve_embed(jnp.asarray(ids, jnp.int32), pos)
        layer = net.serve_layers()[0]
        kept = []
        x = ref._rms(h, lp["input_layernorm"], 1e-5)
        q_n, q_r, row, w_uk, w_uv, index = layer._attention_inputs(x, pos)
        o = layer._latent_prompt(pos, kept)(
            q_n, q_r, row, w_uk, w_uv, 1 / np.sqrt(d_n + d_r), index)
        g = jax.nn.sigmoid(x @ lp["gate_proj"])
        return (o * g[..., None]).reshape(1, 30, -1) @ lp["o_proj"]

    want = attention_part(whole, p)
    total = 0
    for s in range(H):
        def cols(w, width):
            return w.reshape(w.shape[0], H, width)[:, s].reshape(
                w.shape[0], width)
        share = dict(
            p, q_b_proj=cols(p["q_b_proj"], d_n + d_r),
            kv_b_proj=cols(p["kv_b_proj"], d_n + d_v),
            gate_proj=p["gate_proj"][:, s:s + 1],
            o_proj=p["o_proj"].reshape(H, d_v, -1)[s])
        total = total + attention_part(
            dict(whole, num_attention_heads=1), share)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_the_expert_shares_add_up():
    """4 chips each holding 4 of the 16 published experts: their routed
    parts, with the shared expert counted ONCE, are the uncut reference's
    whole FFN (``reference/dots3.py`` given all 16)."""
    from incubator_mxnet_tpu.models.afmoe import swiglu_ffn
    from incubator_mxnet_tpu.models import moe
    whole = _cfg(n_routed_experts=16, first_expert=0, num_hidden_layers=2,
                 layer_types=["full_attention", "sliding_attention"])
    p = _params(whole, 5)["layers"][1]
    x = jnp.asarray(np.random.default_rng(0).standard_normal((1, 37, 64)),
                    jnp.float32)

    class Share:
        _dense = False

        def __init__(self, first):
            self._c = dict(num_experts_per_tok=4, route_norm=True,
                           route_scale=1.0, first_expert=first,
                           num_experts=4)
            self._p = dict(p, **{n: p[n][first:first + 4] for n in (
                "experts_gate", "experts_up", "experts_down")})

        def _w(self, name):
            return self._p[name]

    shared = moe._glu(x[0], p["shared_gate"], p["shared_up"],
                      p["shared_down"])
    total, held = shared, 0
    for first in range(0, 16, 4):
        y, counts = swiglu_ffn(Share(first), x, None)
        total = total + (y[0] - shared)
        held += int(counts[1])
    assert held == 37 * 4                       # every pair on some chip

    # the uncut layer: every one of the 16 experts over every token
    s = jax.nn.sigmoid(x[0] @ p["router"])
    _, idx = jax.lax.top_k(s + p["expert_bias"][None], 4)
    w = jnp.take_along_axis(s, idx, -1)
    w = w / jnp.sum(w, -1, keepdims=True)
    want = shared
    for e in range(16):
        share = jnp.sum(jnp.where(idx == e, w, 0.0), -1)
        want = want + moe._glu(x[0], p["experts_gate"][e],
                               p["experts_up"][e], p["experts_down"][e]) \
            * share[:, None]
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


# -- the layout -------------------------------------------------------------------

def _stated(kind):
    """``kv_layout()`` as each model in the benchmark states it today (a
    tiny instance of each), with the pools and bytes it got before
    ``KVLayout`` had rows."""
    base = dict(num_layers=3, kv_heads=2, head_dim=8, dtype="float32",
                windows=(None, 4, None), max_length=64)
    if kind == "gpt":
        return dict(base, windows=(None,) * 3), 6, 2 * 3 * 2 * 8 * 4
    if kind == "afmoe_smallthinker":
        return dict(base, states=(None,) * 3), 6, 2 * 3 * 2 * 8 * 4
    if kind == "qwen3next":
        return dict(base, windows=(None,) * 3, states=(
            (((4, 3), "float32"), ((2,), "float32")), None,
            (((4, 3), "float32"), ((2,), "float32")))), 2, 2 * 2 * 8 * 4
    return dict(base, rows=(None, None, None),
                selects=(None, None, None)), 6, 2 * 3 * 2 * 8 * 4


@pytest.mark.parametrize("kind", ["gpt", "afmoe_smallthinker", "qwen3next",
                                  "rows_stated_as_none"])
def test_existing_layouts_keep_their_pools_and_bytes(kind):
    stated, n_pools, token_bytes = _stated(kind)
    lay = KVLayout.of(stated)
    assert lay.block_bytes(16) == 16 * token_bytes
    assert sum(len(lay.layer_rows(l)) for l in range(3)) == n_pools
    for l in lay.kv_layers:
        assert lay.layer_rows(l) == ((16, "float32"),) * 2
    dev = jax.devices("cpu")[0]
    assert lay.pool_shape(9, 16, dev) == ((9, 2, 16, 8), False)


def test_a_latent_layouts_bytes_are_the_rows_it_states():
    lay = KVLayout.of(dict(
        num_layers=3, kv_heads=1, head_dim=1, dtype="bfloat16",
        windows=(None, 513, 513), max_length=64,
        rows=(((576, "bfloat16"), (128, "bfloat16")),
              ((1088, "bfloat16"),), ((1088, "bfloat16"),)),
        selects=(2048, None, None)))
    assert lay.block_bytes(1) == 2 * (576 + 128 + 2 * 1088)
    assert lay.row_pool_shape(7, 16, 576, jax.devices("cpu")[0]) \
        == (7, 16, 576)

    class Tpu:
        platform = "tpu"
    assert lay.row_pool_shape(7, 16, 576, Tpu) == (7, 16, 640)
    assert lay.row_pool_shape(7, 16, 1088, Tpu) == (7, 16, 1152)
    with pytest.raises(MXNetError, match="rows"):
        KVLayout.of(dict(num_layers=2, kv_heads=1, head_dim=1,
                         dtype="float32", windows=(None, None),
                         max_length=8, rows=(None,)))


# -- what the engine refuses, and what it serves --------------------------------

def test_no_speculation_over_a_latent_cache():
    cfg = _cfg()
    eng, _ = _engine(cfg, logprobs_topn=0)
    draft, _ = _engine(cfg, logprobs_topn=0, name="draft")
    with pytest.raises(MXNetError, match="no speculation over a latent"):
        eng.attach_draft(draft, spec_k=2)


def test_a_batcher_serves_it_and_counts():
    """Through ``ContinuousBatcher``: the closed set of programs warm, a
    prompt longer than the largest bucket admitted and served in chunks,
    the counters in ``stats()`` (``GET /v1/models``)."""
    cfg = _cfg()
    eng, params = _engine(cfg, logprobs_topn=0)
    assert eng.warmup() == eng.expected_programs == 7
    assert eng.decode_counters()["index_keys_scored"] == 0  # not warm-up's
    bat = ContinuousBatcher(eng, name="tiny")
    prompt = [int(t) for t in
              np.random.RandomState(9).randint(0, 211, 50)]
    try:
        out = bat.submit_async(prompt, max_new_tokens=14)
        toks = out.result(120)
        st = bat.stats()
    finally:
        bat.close()
    assert len(toks) == 14
    lg = ref.make_forward(cfg)(params, jnp.asarray(
        [prompt + toks[:-1]], jnp.int32))[0]
    assert np.asarray(jnp.argmax(lg, -1))[49:].tolist() == toks
    written = range(51, 64)                     # 13 steps after the prefill
    assert st["index_keys_scored"] == 2 * sum(written)
    assert st["index_keys_selected"] == 2 * 12 * 13
    assert st["decode_window_tokens"] == 21 * 13
    assert st["moe_pairs_total"] == 13 * 4 * 4
    assert st["prefill_tokens"]["miss"] == 50


@pytest.mark.parametrize("window", [None, 37, 200])
def test_the_latent_kernel_is_the_gather(monkeypatch, window):
    """The Pallas kernel (interpreted) against the lax gather: three slots
    whose write heads lie in their first block, past a 128-key group and
    past a 512-key step, over tables that name blocks in a row and out of
    order; a page of two lane tiles read once, key and value both."""
    monkeypatch.setenv("MXNET_FA_DECODE_FORCE_PALLAS", "1")
    rng = np.random.default_rng(0)
    N, bs, F, r, H, S, cols = 80, 16, 256, 128, 3, 3, 40
    pool = jnp.asarray(rng.standard_normal((N, bs, F)), jnp.bfloat16)
    tables = np.zeros((S, cols), np.int32)
    tables[0, :3] = [5, 6, 7]
    tables[1, :12] = rng.permutation(np.arange(20, 32))
    tables[2, :38] = np.arange(40, 78)
    positions = jnp.asarray([9, 150, 600], jnp.int32)
    q = jnp.asarray(rng.standard_normal((S, H, F)), jnp.float32)
    assert la.latent_decode_impl(q, pool) == "pallas"
    got = la.paged_latent_decode(q, pool, jnp.asarray(tables), positions, r,
                                 0.07, window)
    want = la._xla_paged_latent_decode(q, pool, jnp.asarray(tables),
                                       positions, r, 0.07, window)
    assert got.shape == (S, H, r)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-2, rtol=2e-2)


def test_the_index_kernel_is_the_gather(monkeypatch):
    """The scoring kernel (interpreted) against the lax gather: the same
    three slots; every written position's score to rounding, the same
    keys chosen where no two scores are that close."""
    monkeypatch.setenv("MXNET_FA_DECODE_FORCE_PALLAS", "1")
    rng = np.random.default_rng(1)
    N, bs, dI, HI, S, cols = 80, 16, 128, 4, 3, 40
    pool = jnp.asarray(rng.standard_normal((N, bs, dI)), jnp.bfloat16)
    tables = np.zeros((S, cols), np.int32)
    tables[0, :3] = [5, 6, 7]
    tables[1, :12] = rng.permutation(np.arange(20, 32))
    tables[2, :38] = np.arange(40, 78)
    tables = jnp.asarray(tables)
    positions = jnp.asarray([9, 150, 600], jnp.int32)
    q = jnp.asarray(rng.standard_normal((S, HI, dI)), jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((S, HI)), jnp.float32)
    assert la.index_select_impl(q, pool) == "select:kernel"
    got = la._paged_index_pallas(q, w, pool, tables, positions, True)
    want = la._xla_paged_index_scores(q, w, pool, tables)
    live = np.arange(cols * bs)[None, :] <= np.asarray(positions)[:, None]
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               atol=1e-3, rtol=1e-3)
    rows, valid = la.paged_index_select(q, w, pool, tables, positions, 32)
    monkeypatch.delenv("MXNET_FA_DECODE_FORCE_PALLAS")
    rows2, valid2 = la.paged_index_select(q, w, pool, tables, positions, 32)
    assert valid.tolist() == valid2.tolist()
    assert valid.sum(-1).tolist() == [10, 32, 32]
    for s in range(S):
        a = set(np.asarray(rows[s])[np.asarray(valid[s])].tolist())
        b = set(np.asarray(rows2[s])[np.asarray(valid2[s])].tolist())
        assert len(a ^ b) <= 2, (s, a ^ b)
    # the rows are the chosen positions' own: the lax scores' exact top
    # 32 (lowest position first among equals) through the slot's table
    want = la.choose_topk(jnp.where(jnp.asarray(live), want, -jnp.inf), 32)
    tb = np.asarray(tables)
    for s in range(S):
        pos = np.asarray(want[0][s])[np.asarray(want[1][s])]
        assert sorted(np.asarray(rows2[s])[np.asarray(valid2[s])].tolist()) \
            == sorted((tb[s, pos // bs] * bs + pos % bs).tolist())


def test_the_prompts_index_kernel_is_the_einsum(monkeypatch):
    """A prompt's dense index scores through the kernel (interpreted):
    24 queries of 8 heads against 300 keys, and the unabsorbed attention
    that chooses by them equal to the lax path's."""
    rng = np.random.default_rng(2)
    T, HI, dI, K = 24, 8, 128, 300
    mk = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731
    q_i, w_i, k_i = mk(T, HI, dI), mk(T, HI), mk(K, dI)
    assert la.prompt_index_impl(q_i, k_i) == "lax"
    want = la.index_scores(q_i, w_i, k_i)
    monkeypatch.setenv("MXNET_FA_DECODE_FORCE_PALLAS", "1")
    assert la.prompt_index_impl(q_i, k_i) == "pallas"
    got = la._index_scores_pallas(q_i, w_i, k_i, True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-3, rtol=1e-4)
    H, r, d_n, d_r, d_v = 2, 16, 8, 4, 6
    args = (mk(T, H, d_n), mk(T, H, d_r), mk(K, r + d_r),
            jnp.arange(K - T, K, dtype=jnp.int32),
            jnp.arange(K, dtype=jnp.int32), mk(r, H, d_n), mk(r, H, d_v),
            0.3, None, (q_i, w_i, k_i, 40))
    with_kernel = la.latent_prompt_attention(*args)
    monkeypatch.delenv("MXNET_FA_DECODE_FORCE_PALLAS")
    np.testing.assert_allclose(
        np.asarray(with_kernel),
        np.asarray(la.latent_prompt_attention(*args)), atol=1e-4, rtol=1e-4)


def test_the_queue_holds_a_request_a_slot_whatever_the_footprint():
    """The capacity-aware queue bound counts a request's footprint as if
    nothing were shared — here ONE stream's worth of the pool, so 4
    waiters by that count — but never admits fewer than one request a
    slot: six streams that share most of a long prompt all fit."""
    from incubator_mxnet_tpu.serving import QueueFullError
    cfg = _cfg()
    eng, _ = _engine(cfg, logprobs_topn=0, max_slots=6, num_blocks=13)
    assert eng.kv_capacity_tokens() // 128 == 1
    bat = ContinuousBatcher(eng, name="tiny")
    rng = np.random.RandomState(4)
    shared = [int(t) for t in rng.randint(0, 211, 96)]
    try:
        first = bat.submit_async(shared + [1, 2, 3, 4], max_new_tokens=28)
        deadline = time.time() + 30     # the worker takes it and compiles
        while bat.stats()["queue_depth"] and time.time() < deadline:
            time.sleep(0.01)
        waiting = [bat.submit_async(shared + [5 + i] * 4, max_new_tokens=28)
                   for i in range(6)]
        with pytest.raises(QueueFullError, match="backpressure"):
            bat.submit_async(shared + [9] * 4, max_new_tokens=28)
        assert len(first.result(120)) == 28
        assert all(len(w.result(120)) == 28 for w in waiting)
    finally:
        bat.close()


@pytest.mark.parametrize("k", [1, 7, 40, 299])
def test_the_mask_is_the_top_k_on_random_scores(k):
    """The threshold found bit by bit against ``lax.top_k``: negative,
    zero and repeated scores, rows with fewer finite scores than k."""
    rng = np.random.default_rng(k)
    s = rng.standard_normal((9, 300)).astype(np.float32)
    s[0, :50] = 0.0
    s[1] = np.round(s[1], 1)                    # many equal scores
    s[2, 20:] = -np.inf
    s[3] = -np.abs(s[3])
    s = jnp.asarray(s)
    idx, valid = la.choose_topk(s, k)
    want = np.zeros(s.shape, bool)
    np.put_along_axis(want, np.asarray(idx), np.asarray(valid), -1)
    assert np.asarray(la.chosen_mask(s, k)).tolist() == want.tolist()


# -- the decode step's choice: a threshold and a compaction, no sort -----------

def _choice_case(name):
    """``(scores (S, K) of bfloat16-exact values, positions (S,), bs, k)``
    of one case of :func:`test_the_choice_is_choose_topks_set`."""
    rng = np.random.default_rng(sum(map(ord, name)))
    S, K, bs, k = 2, 640, 16, 200
    scores = np.round(rng.standard_normal((S, K)) * 16) / 8
    positions = np.array([K - 1, K - 37])
    if name == "ties":
        # 300 equal scores from position 100 on — across the 128-lane
        # group edges at 128, 256 and 384 — under ~110 larger ones, so
        # the threshold falls among the equal: ~90 of them are taken
        scores = -np.abs(scores) - 1
        scores[:, 100:400] = 0.5
        scores[:, rng.choice(np.r_[0:100, 400:600], 110, False)] = 3.0
    elif name == "all_equal":
        scores[:] = 1.25
    elif name == "few_written":
        positions = np.array([k - 2, 17])
    elif name == "free_slot":
        positions = np.array([0, K - 1])
    elif name == "short_table":              # K <= k: nothing is counted
        k = K + 60
    elif name == "cell_shape":               # 2,048 of 27,136, two slots
        K, k = 27136, 2048
        scores = np.round(rng.standard_normal((S, K)) * 16) / 8
        positions = np.array([K - 1, 25731])
    # + 0.0: a rounded -0.0 would come back from the index as +0.0, and
    # the exact choice tells the two apart
    return (scores + 0.0).astype(np.float32), positions.astype(np.int32), \
        bs, k


def _choice_operands(scores, bs, seed=0):
    """A pool and PERMUTED tables whose index keys score exactly
    ``scores`` against the returned queries: feature 0 of a position's key
    is its score, two heads ``relu(k_0) - relu(-k_0)``."""
    rng = np.random.default_rng(seed)
    S, K = scores.shape
    n_cols = K // bs
    tables = 1 + rng.permutation(S * n_cols).reshape(S, n_cols)
    pool = np.zeros((S * n_cols + 1, bs, 128), np.float32)
    pool[tables, :, 0] = scores.reshape(S, n_cols, bs)
    q = np.zeros((S, 4, 128), np.float32)
    q[:, 0, 0], q[:, 1, 0] = 1.0, -1.0
    w = np.tile(np.asarray([1.0, -1.0, 0.0, 0.0], np.float32), (S, 1))
    return (jnp.asarray(q, jnp.bfloat16), jnp.asarray(w),
            jnp.asarray(pool, jnp.bfloat16),
            jnp.asarray(tables.astype(np.int32)))


def _assert_choose_topks_set(rows, valid, scores, tables, positions, bs, k):
    """``rows`` where ``valid`` are, as a SET, the pool rows of the
    positions ``choose_topk`` takes of the written scores."""
    K = scores.shape[1]
    live = np.arange(K)[None, :] <= np.asarray(positions)[:, None]
    idx, ok = la.choose_topk(
        jnp.where(jnp.asarray(live), jnp.asarray(scores), -jnp.inf), k)
    assert rows.shape == valid.shape == idx.shape
    assert np.asarray(valid).sum(-1).tolist() \
        == np.asarray(ok).sum(-1).tolist()
    tables = np.asarray(tables)
    for s in range(len(scores)):
        pos = np.asarray(idx[s])[np.asarray(ok[s])]
        got = np.asarray(rows[s])[np.asarray(valid[s])]
        assert len(set(got.tolist())) == len(got)
        assert sorted(got.tolist()) == sorted(
            (tables[s, pos // bs] * bs + pos % bs).tolist()), s


@pytest.mark.parametrize("name,impl", [
    (name, impl) for name in ("random", "ties", "all_equal", "few_written",
                              "free_slot", "cell_shape", "scan_carry")
    for impl in ("select:kernel", "select:lax")] + [
        ("short_table", "select:lax")])
def test_the_choice_is_choose_topks_set(name, impl, monkeypatch):
    """``paged_index_select`` — the threshold by counting passes and the
    compaction, interpreted kernel and lax form — against ``choose_topk``
    through a permuted block table: the same set, ties taken lowest
    position first, in every case of :func:`_choice_case`; under ``jit``
    with ``positions`` riding a scan's carry in ``scan_carry``."""
    if impl == "select:kernel":
        monkeypatch.setenv("MXNET_FA_DECODE_FORCE_PALLAS", "1")
    scores, positions, bs, k = _choice_case(name)
    q, w, pool, tables = _choice_operands(scores, bs)
    assert la.index_select_impl(q, pool) == impl
    if name != "scan_carry":
        rows, valid = la.paged_index_select(q, w, pool, tables,
                                            jnp.asarray(positions), k)
        _assert_choose_topks_set(rows, valid, scores, tables, positions,
                                 bs, k)
        return
    start = jnp.asarray([150, 397], jnp.int32)

    @jax.jit
    def steps(q, w, pool, tables, start):
        def step(pos, _):
            return pos + 1, la.paged_index_select(q, w, pool, tables, pos, k)
        return jax.lax.scan(step, start, None, length=3)[1]

    rows, valid = steps(q, w, pool, tables, start)
    for i in range(3):
        _assert_choose_topks_set(rows[i], valid[i], scores, tables,
                                 np.asarray(start) + i, bs, k)
