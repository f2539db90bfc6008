"""AFMoE through the serving path against the plain reference
(``benchmark/chip/reference/afmoe.py``), at a tiny size on the CPU: logits
of prefill-then-decode through the paged cache (single steps and bursts, a
prefix hit among them, contexts that cross the tiny window), the same in
bfloat16, the expert layer's shares adding up to the uncut layer, no token
dropped at a skewed router, and what the engine refuses.
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

from reference import afmoe as ref                      # noqa: E402
from programs import afmoe_serve as prog                # noqa: E402

from incubator_mxnet_tpu.base import MXNetError         # noqa: E402
from incubator_mxnet_tpu.models import moe              # noqa: E402
from incubator_mxnet_tpu.serving import (               # noqa: E402
    ContinuousBatcher, GenerationEngine)


def _cfg(dtype="float32"):
    with open(os.path.join(CHIP, "tests", "tiny_afmoe.json")) as f:
        cfg = json.load(f)
    cfg["deployment"]["param_dtype"] = dtype
    return cfg


def _engine(cfg, seed=7, **kw):
    params = ref.init_params(cfg, seed)
    net = prog.build_net(cfg)
    prog.load_weights(net, params)
    args = dict(name="tiny", max_slots=3, max_len=128,
                prefill_buckets=[16, 64], block_size=16, scan_steps=4,
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
    """Two streams through the paged programs: A (40 tokens: past the
    window of 24) prefills on a miss, B shares A's first 32 tokens (two
    whole blocks: a prefix hit, the suffix program), then three single
    steps and three bursts of 4 (B's budgets end it inside a burst).
    Returns ``{slot: (prompt length, tokens, log-softmax rows)}``."""
    rng = np.random.RandomState(3)
    A = [int(t) for t in rng.randint(0, V, 40)]
    B = A[:32] + [int(t) for t in rng.randint(0, V, 9)]
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
    for _ in range(3):
        heads()
        toks, emitted = eng.decode_burst(
            lt, pv, np.array([4, 3, 0], np.int32), np.full(3, -1, np.int32),
            np.array([True, True, False]))
        lp = _full(eng.last_logprobs())
        assert emitted.tolist() == [4, 3, 0]
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


@pytest.mark.parametrize("slot", [0, 1], ids=["miss", "prefix_hit"])
def test_paged_float32_matches_reference(slot):
    """Every log-probability the served path computed — at the prefill's
    last position, three single steps, three bursts — against the
    reference's full forward over the same tokens.  Both sides are
    float32 with exact float32 products on the CPU; they differ in the
    order of sums (cache strips, grouped experts, online softmax), which
    leaves a few float32 ulps on log-probabilities of size ~5: 2e-5."""
    cfg = _cfg()
    eng, params = _engine(cfg)
    n_prompt, seq, rows = _serve(eng, cfg["vocab_size"])[slot]
    want = _reference_rows(cfg, params, n_prompt, seq)
    assert rows.shape == want.shape and len(rows) == (16 if slot == 0
                                                       else 13)
    np.testing.assert_allclose(rows, want, atol=2e-5, rtol=0)


def test_paged_bfloat16_is_the_stated_precision_and_float8_is_not():
    """Served in bfloat16 (parameters, activations, pool; float32 norms,
    router, softmax) the path reads the same numbers as the reference
    computed in bfloat16, and its mean error against the float32
    reference is bfloat16's.  The tolerance is 1.5 x the bfloat16
    reference's own mean error (one routing flip moves single rows by
    0.1, so the mean is compared, not the maximum); the float8 reference
    fails it by a wide margin."""
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


def _layer_weights(rng, d, f, E):
    mk = lambda *s: jnp.asarray(0.2 * rng.standard_normal(s), jnp.float32)  # noqa: E731
    return dict(router=mk(d, E), bias=mk(E), gate=mk(E, d, f), up=mk(E, d, f),
                down=mk(E, f, d), sg=mk(d, f), su=mk(d, f), sd=mk(f, d))


def _dense_layer(x, w, idx, wt):
    """The uncut layer, every expert over every token, masked."""
    y = moe._glu(x, w["sg"], w["su"], w["sd"])
    for e in range(w["gate"].shape[0]):
        share = jnp.sum(jnp.where(idx == e, wt, 0.0), -1)
        y = y + moe._glu(x, w["gate"][e], w["up"][e], w["down"][e]) \
            * share[:, None]
    return y


@pytest.mark.parametrize("tokens", [5, 64, 300])
def test_the_shares_add_up(tokens):
    """8 chips each holding 2 of 16 experts: their routed parts, plus the
    shared expert ONCE, equal the uncut layer — for a decode step's few
    tokens and for a prompt's many (tiles of 32 and of 128 rows)."""
    rng = np.random.default_rng(tokens)
    d, f, E, k = 32, 24, 16, 4
    w = _layer_weights(rng, d, f, E)
    x = jnp.asarray(rng.standard_normal((tokens, d)), jnp.float32)
    idx, wt = moe.route_token_choice(x @ w["router"], w["bias"], k, True,
                                     2.448)
    total = moe._glu(x, w["sg"], w["su"], w["sd"])
    held_pairs = 0
    for first in range(0, E, 2):
        y, (pairs, held, touched) = moe.held_experts_ffn(
            x, idx, wt, (first, 2), w["gate"][first:first + 2],
            w["up"][first:first + 2], w["down"][first:first + 2])
        assert int(pairs) == tokens * k and int(touched) <= 2
        held_pairs += int(held)
        total = total + y
    assert held_pairs == tokens * k             # every pair on some chip
    np.testing.assert_allclose(np.asarray(total),
                               np.asarray(_dense_layer(x, w, idx, wt)),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("tokens", [7, 200])
def test_no_token_is_dropped_at_a_skewed_router(tokens):
    """A bias that sends EVERY token to expert 3 (a capacity layer would
    drop most of them): all of them are computed, and dead tokens route
    nowhere."""
    rng = np.random.default_rng(1)
    d, f, E, k = 32, 24, 16, 4
    w = _layer_weights(rng, d, f, E)
    w["bias"] = w["bias"].at[3].set(50.0)
    x = jnp.asarray(rng.standard_normal((tokens, d)), jnp.float32)
    idx, wt = moe.route_token_choice(x @ w["router"], w["bias"], k)
    assert bool(jnp.all(jnp.any(idx == 3, -1)))
    y, (pairs, held, touched) = moe.held_experts_ffn(
        x, idx, wt, (0, E), w["gate"], w["up"], w["down"])
    assert int(held) == int(pairs) == tokens * k
    want = _dense_layer(x, w, idx, wt) - moe._glu(x, w["sg"], w["su"],
                                                     w["sd"])
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=2e-5,
                               rtol=2e-5)
    live = jnp.arange(tokens) % 2 == 0
    y2, (pairs2, held2, _) = moe.held_experts_ffn(
        x, idx, wt, (0, E), w["gate"], w["up"], w["down"], live)
    n_live = int(jnp.sum(live))
    assert int(pairs2) == int(held2) == n_live * k
    np.testing.assert_allclose(np.asarray(y2)[::2], np.asarray(want)[::2],
                               atol=2e-5, rtol=2e-5)
    assert not np.asarray(y2)[1::2].any()


def test_the_model_alone_is_the_reference():
    """``AFMoEModel``'s own forward (no engine, no cache)."""
    import incubator_mxnet_tpu as mx
    cfg = _cfg()
    params = ref.init_params(cfg, 11)
    net = prog.build_net(cfg)
    prog.load_weights(net, params)
    ids = np.random.RandomState(0).randint(0, cfg["vocab_size"], (2, 50))
    got = net(mx.nd.array(ids.astype(np.int32))).asnumpy()
    want = np.asarray(ref.make_forward(cfg)(params, jnp.asarray(ids)))
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_counters_reach_the_batcher_stats():
    """What the expert layers count in the decode programs comes back
    with the dispatch and shows in ``stats()`` (``GET /v1/models``):
    4 expert layers x 4 experts a token a live slot-step."""
    cfg = _cfg()
    eng, _ = _engine(cfg, logprobs_topn=0)
    assert eng.warmup() == eng.expected_programs == 7
    assert eng.decode_counters()["moe_pairs_total"] == 0    # not warm-up's
    bat = ContinuousBatcher(eng, name="tiny")
    try:
        out = bat.submit_async([5, 9, 2, 40, 17], max_new_tokens=22)
        assert len(out.result(60)) == 22
        st = bat.stats()
    finally:
        bat.close()
    steps = 21                                  # the prefill gave token 1
    assert st["moe_pairs_total"] == steps * 4 * 4
    assert 0 < st["moe_pairs_held"] <= st["moe_pairs_total"]
    assert 0 < st["moe_experts_touched"] <= st["moe_pairs_held"]
    # write heads 5..25 -> written positions 6..26
    assert st["decode_context_tokens"] == sum(range(6, 27))
    assert eng.program_inventory()["paged_attention"] == "lax_gather"


def test_the_dense_mode_is_gone_for_this_block_too():
    cfg = _cfg()
    net = prog.build_net(cfg)
    prog.load_weights(net, ref.init_params(cfg, 1))
    with pytest.raises(MXNetError, match="dense KV mode is gone"):
        GenerationEngine(net, name="t", max_slots=2, max_len=64, paged=False)


def test_an_engine_needs_the_layer_interface():
    from incubator_mxnet_tpu.gluon import nn
    with pytest.raises(MXNetError, match="serving layer interface"):
        GenerationEngine(nn.Dense(4, in_units=4), name="t")


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
    turn_pool(eng, 18)
    got = check_paged_groups(
        eng, lambda: _serve(eng, cfg["vocab_size"]), monkeypatch)
    assert got["run"] > 0 and got["blocks"] > 0
