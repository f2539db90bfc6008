"""Qwen3-Next through the serving path against the plain reference
(``benchmark/chip/reference/qwen3next.py``), at a tiny size on the CPU:
log-probabilities through a miss prefill, a snapshot hit, per-step decode and
8-step bursts with a slot ending mid-burst; the same in bfloat16 against the
float8 control; the two chips' shares of a layer adding up to the uncut
layer, for a DeltaNet and for a full layer; the partial rotary embedding and
the gate on the shared expert.
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

from reference import qwen3next as ref                  # noqa: E402
from programs import qwen3next_serve as prog            # noqa: E402

from incubator_mxnet_tpu.base import MXNetError         # noqa: E402
from incubator_mxnet_tpu.models.decoder import rotary   # noqa: E402
from incubator_mxnet_tpu.serving import GenerationEngine    # noqa: E402


def _full(lp):
    """top-N (values, ids) with N = vocab -> the whole log-softmax row."""
    vals, ids = (np.asarray(a) for a in lp)
    out = np.zeros(vals.shape, np.float32)
    np.put_along_axis(out, ids, vals, -1)
    return out


def _cfg(dtype="float32", **over):
    with open(os.path.join(CHIP, "tests", "tiny_qwen3next.json")) as f:
        cfg = json.load(f)
    cfg["deployment"]["param_dtype"] = dtype
    cfg.update(over)
    return cfg


def _engine(cfg, seed=7, **kw):
    params = ref.init_params(cfg, seed)
    net = prog.build_net(cfg)
    prog.load_weights(net, params)
    args = dict(name="tiny", max_slots=3, max_len=256,
                prefill_buckets=[64, 192], block_size=16, scan_steps=8,
                logprobs_topn=cfg["vocab_size"], state_snapshot_tokens=64,
                state_snapshot_rows=8)
    args.update(kw)
    return GenerationEngine(net, **args), params


def _serve(eng, V):
    """Two streams through the paged programs, after a request that sent
    their first 140 tokens before them and left: A (150 tokens) finds its
    blocks, prefills on a miss all the same (no state was kept for a prefix
    seen once) and leaves snapshots at 64 and 128; B shares the 140 and
    goes on with 30 of its own — 8 cached blocks match, and the hit is the
    128 positions that end at a snapshot; then three single steps and
    two 8-step bursts (B's budget ends it inside the second).  Returns
    ``{slot: (prompt length, tokens, log-softmax rows)}``."""
    rng = np.random.RandomState(3)
    A = [int(t) for t in rng.randint(0, V, 150)]
    B = A[:140] + [int(t) for t in rng.randint(0, V, 30)]
    eng.prefill(A[:140] + [int(t) for t in rng.randint(0, V, 10)], 2,
                reserve_tokens=180)
    eng.release_slot(2)
    assert eng.pool.snapshots_in_use == 0
    seqs, rows = {0: list(A), 1: list(B)}, {0: [], 1: []}
    for s in (0, 1):
        seqs[s].append(eng.prefill(seqs[s], s,
                                   reserve_tokens=len(seqs[s]) + 30))
        rows[s].append(_full(eng.last_prefill_logprobs()))
    assert eng.pool.hits == 8 and eng.pool.snapshots_restored == 1
    assert eng.kv_stats()["prefill_tokens"] == {
        "miss": 300, "hit": 42, "prefix_hit": 128}
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


@pytest.fixture(scope="module")
def served_f32():
    cfg = _cfg()
    eng, params = _engine(cfg)
    return cfg, params, _serve(eng, cfg["vocab_size"])


@pytest.mark.parametrize("slot", [0, 1], ids=["miss", "snapshot_hit"])
def test_paged_float32_matches_reference(served_f32, slot):
    """Every log-probability the served path computed — at the prefill's
    last position, three single steps, two 8-step bursts — against the
    reference's full forward over the same tokens: the chunked delta rule
    (from zeros, and from a snapshot restored by row) and the one-token
    step against the recurrence token by token.  Float32 on both sides,
    sums in another order: 2e-5 on log-probabilities of size ~5."""
    cfg, params, served = served_f32
    n_prompt, seq, rows = served[slot]
    want = _reference_rows(cfg, params, n_prompt, seq)
    assert rows.shape == want.shape and len(rows) == (20 if slot == 0
                                                       else 17)
    np.testing.assert_allclose(rows, want, atol=2e-5, rtol=0)


def test_a_hit_changes_no_logit(served_f32):
    """B's rows through the snapshot hit against B served alone on a
    miss."""
    cfg, params, served = served_f32
    n_prompt, seq, rows = served[1]
    eng, _ = _engine(cfg, prefix_cache=False)
    assert eng.state_snapshot_rows == 0
    eng.prefill(seq[:n_prompt], 0, reserve_tokens=n_prompt + 30)
    np.testing.assert_allclose(_full(eng.last_prefill_logprobs()), rows[0],
                               atol=2e-5, rtol=0)


def test_paged_bfloat16_is_the_stated_precision_and_float8_is_not():
    """Served in bfloat16 (parameters, activations, pool; float32 norms,
    router, softmax, gates and state) the path's mean error against the
    float32 reference is bfloat16's: within 1.5 x the bfloat16 reference's
    own; the float8 reference fails that by a wide margin."""
    cfg = _cfg("bfloat16")
    eng, params = _engine(cfg)
    assert {str(c.dtype) for c in eng._cache} == {"bfloat16"}
    assert {str(c.dtype) for c in eng._recur} == {"float32"}
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


@pytest.mark.parametrize("rows", [8192, 32])
def test_the_model_alone_is_the_reference(rows, monkeypatch):
    """``Qwen3NextModel``'s own forward (no engine, no cache; lengths off a
    chunk's edge, two sequences at once) — with the expert layer taking
    all 192 tokens at once, and 32 at a time as a long prompt's would."""
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.models import qwen3_next
    monkeypatch.setattr(qwen3_next, "_EXPERT_ROWS", rows)
    cfg = _cfg()
    params = ref.init_params(cfg, 11)
    net = prog.build_net(cfg)
    prog.load_weights(net, params)
    ids = np.random.RandomState(0).randint(0, cfg["vocab_size"], (2, 96))
    got = net(mx.nd.array(ids.astype(np.int32))).asnumpy()
    want = np.asarray(ref.make_forward(cfg)(params, jnp.asarray(ids)))
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


# what a chip's share takes of a layer's leaves: (axis, pieces along it in
# this file's column order, each cut in halves)
def _half(x, axis, pieces, which):
    parts = jnp.split(x, np.cumsum(pieces)[:-1], axis=axis)
    return jnp.concatenate(
        [jnp.split(p, 2, axis=axis)[which] for p in parts], axis=axis)


def _share(cfg, layer, p, which):
    """Chip ``which`` of two: its half of the experts, and of the query,
    KV, key and value heads; the shared expert, the norms and the router
    whole."""
    out = dict(p)
    E = cfg["num_experts"] // 2
    for m in ("experts_gate", "experts_up", "experts_down"):
        out[m] = p[m][which * E:(which + 1) * E]
    if ref.is_linear(cfg, layer):
        hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
        dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
        cols = [hk * dk, hk * dk, hv * dv]
        out["in_proj_qkvz"] = _half(p["in_proj_qkvz"], 1, cols + [hv * dv],
                                    which)
        out["in_proj_ba"] = _half(p["in_proj_ba"], 1, [hv, hv], which)
        out["conv1d"] = _half(p["conv1d"], 1, cols, which)
        out["dt_bias"] = _half(p["dt_bias"], 0, [hv], which)
        out["A_log"] = _half(p["A_log"], 0, [hv], which)
        out["out_proj"] = _half(p["out_proj"], 0, [hv * dv], which)
    else:
        for m in ("q_proj", "k_proj", "v_proj"):
            out[m] = _half(p[m], 1, [p[m].shape[1]], which)
        out["o_proj"] = _half(p["o_proj"], 0, [p["o_proj"].shape[0]], which)
    return out


@pytest.mark.parametrize("interval,kind", [(2, "delta_net"), (1, "full")])
def test_two_shares_add_up_to_the_uncut_layer(interval, kind):
    """Guide section 4's tying test at the cell's own cut: a ONE-layer
    model, uncut (8 experts, 4 query on 2 KV heads, 2 key and 4 value
    heads), against two chips' shares of it — half the experts from
    ``first_expert`` on, half of every kind of head; the router, the norms
    and the shared expert whole on both.  The mixer is a sum over heads and
    the expert layer a sum over experts, and between the two a deployment
    sums the chips' results, so each is tied for itself: the two shares'
    mixer outputs add up to the uncut mixer's; and, given the same input,
    the two shares' expert layers — with what both chips compute alike, the
    residual and the shared expert, counted once — add up to the uncut
    one.  Each share is also the reference given the same share."""
    import incubator_mxnet_tpu as mx
    whole = _cfg(num_hidden_layers=1, full_attention_interval=interval,
                 num_key_value_heads=2)
    assert ref.is_linear(whole, 0) == (kind == "delta_net")
    params = ref.init_params(whole, 5)
    lp = params["layers"][0]
    ids = np.random.RandomState(1).randint(
        0, whole["vocab_size"], (1, 70)).astype(np.int32)
    emb = np.asarray(params["embed_tokens"])[ids]
    cut = dict(num_experts=4, num_experts_published=8,
               num_attention_heads=2, num_key_value_heads=1,
               linear_num_key_heads=1, linear_num_value_heads=2)
    shares = [dict(whole, first_expert=4 * w, **cut) for w in (0, 1)]

    def hidden(c, layer_params):
        """The layer's output; the whole model is the reference's."""
        p = dict(params, layers=[layer_params])
        net = prog.build_net(c)
        prog.load_weights(net, p)
        want = np.asarray(ref.make_forward(c)(p, jnp.asarray(ids)))
        np.testing.assert_allclose(net(mx.nd.array(ids)).asnumpy(), want,
                                   atol=2e-5, rtol=2e-5)
        return net.layers[0](mx.nd.array(emb)).asnumpy()

    def without(layer_params, *names):
        return dict(layer_params, **{n: jnp.zeros_like(layer_params[n])
                                     for n in names})

    def run(*off):
        return (hidden(whole, without(lp, *off)),
                [hidden(c, without(_share(whole, 0, lp, w), *off))
                 for w, c in enumerate(shares)])

    # the mixer: no expert of either kind
    uncut, parts = run("experts_down", "shared_down")
    np.testing.assert_allclose(parts[0] + parts[1] - emb, uncut, atol=2e-5,
                               rtol=2e-5)
    assert np.abs(uncut - emb).max() > 1e-3              # the mixer counts
    assert np.abs(parts[0] - parts[1]).max() > 1e-3      # and is divided
    # the experts, every chip given the same input (the mixer off)
    mixer = "out_proj" if kind == "delta_net" else "o_proj"
    uncut, parts = run(mixer)
    alike = hidden(shares[0], without(_share(whole, 0, lp, 0), mixer,
                                      "experts_down"))
    np.testing.assert_allclose(parts[0] + parts[1] - alike, uncut,
                               atol=2e-5, rtol=2e-5)
    assert np.abs(uncut - alike).max() > 1e-4            # the experts count
    assert np.abs(alike - emb).max() > 1e-4              # so does the shared


def test_partial_rotary_touches_the_first_features_only():
    x = jnp.asarray(np.random.RandomState(0).randn(1, 5, 2, 32), jnp.float32)
    pos = jnp.arange(5, dtype=jnp.int32)[None] + 3
    got = np.asarray(rotary(x, pos, 1e7, 8))
    np.testing.assert_array_equal(got[..., 8:], np.asarray(x)[..., 8:])
    np.testing.assert_allclose(got[..., :8],
                               np.asarray(rotary(x[..., :8], pos, 1e7)),
                               atol=1e-6)
    assert np.abs(got[..., :8] - np.asarray(x)[..., :8]).max() > 0.1
    np.testing.assert_array_equal(np.asarray(rotary(x, pos, 1e7, 32)),
                                  np.asarray(rotary(x, pos, 1e7)))


def test_the_shared_expert_is_gated():
    """``sigmoid(x . w_s)`` scales the shared expert: with the gate's
    weights at zero the shared expert counts half."""
    import incubator_mxnet_tpu as mx
    cfg = _cfg(num_hidden_layers=1, full_attention_interval=1)
    params = ref.init_params(cfg, 3)
    ids = np.random.RandomState(2).randint(0, cfg["vocab_size"], (1, 20))
    emb = mx.nd.array(np.asarray(params["embed_tokens"])[ids])

    def hidden(**over):
        net = prog.build_net(cfg)
        prog.load_weights(net, dict(params, layers=[dict(
            params["layers"][0], **over)]))
        return net.layers[0](emb).asnumpy()

    lp = params["layers"][0]
    none = hidden(shared_down=jnp.zeros_like(lp["shared_down"]))
    open_half = hidden(
        shared_expert_gate=jnp.zeros_like(lp["shared_expert_gate"]))
    double = hidden(shared_expert_gate=jnp.zeros_like(
        lp["shared_expert_gate"]), shared_down=2 * lp["shared_down"])
    np.testing.assert_allclose(double - none, 2 * (open_half - none),
                               atol=2e-5, rtol=2e-5)
    assert np.abs(hidden() - open_half).max() > 1e-5     # the gate counts
    assert np.abs(open_half - none).max() > 1e-4


def test_the_constructor_refuses_what_it_cannot_write_down():
    for over in (dict(norm_topk_prob=False), dict(decoder_sparse_step=2),
                 dict(mlp_only_layers=[0]), dict(num_attention_heads=3,
                                                 num_key_value_heads=2),
                 dict(num_experts_published=4)):
        with pytest.raises(MXNetError):
            prog.build_net(_cfg(**over))


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
    turn_pool(eng, 31)
    got = check_paged_groups(
        eng, lambda: _serve(eng, cfg["vocab_size"]), monkeypatch)
    assert got["run"] > 0 and got["blocks"] > 0
