"""Granite 4.0-H through the serving path against the plain reference
(``benchmark/chip/reference/granite_hybrid.py``), at a tiny size on the CPU:
the whole forward; log-probabilities through a miss prefill, a snapshot hit,
per-step decode and 8-step bursts with a slot ending mid-burst; the same with
the kernels forced (the one-token state step and paged attention on heads of
64, interpreted) and in bfloat16 against the float8 control; a stacked run
against its layers one by one; the four multipliers and the convolution's
bias; the tied head; spans and counters.
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

from reference import granite_hybrid as ref             # noqa: E402
from programs import granite_hybrid_serve as prog       # noqa: E402

import incubator_mxnet_tpu as mx                        # noqa: E402
from incubator_mxnet_tpu import telemetry               # noqa: E402
from incubator_mxnet_tpu.base import MXNetError         # noqa: E402
from incubator_mxnet_tpu.models.granite_hybrid import (  # noqa: E402
    GraniteMambaRun, layer_runs)
from incubator_mxnet_tpu.serving import GenerationEngine    # noqa: E402


def _full(lp):
    """top-N (values, ids) with N = vocab -> the whole log-softmax row."""
    vals, ids = (np.asarray(a) for a in lp)
    out = np.zeros(vals.shape, np.float32)
    np.put_along_axis(out, ids, vals, -1)
    return out


def _cfg(dtype="float32", **over):
    with open(os.path.join(CHIP, "tests", "tiny_granite4.json")) as f:
        cfg = json.load(f)
    cfg["deployment"]["param_dtype"] = dtype
    cfg.update(over)
    return cfg


def _net(cfg, seed=7, edit=None):
    params = ref.init_params(cfg, seed)
    if edit is not None:
        edit(params)
    net = prog.build_net(cfg)
    prog.load_weights(net, params)
    return net


def _engine(cfg, seed=7, **kw):
    args = dict(name="tiny", max_slots=3, max_len=768,
                prefill_buckets=[256, 512], block_size=16, scan_steps=8,
                logprobs_topn=cfg["vocab_size"], state_snapshot_tokens=256,
                state_snapshot_rows=4)
    args.update(kw)
    return GenerationEngine(_net(cfg, seed), **args), \
        ref.init_params(cfg, seed)


def _serve(eng, V, steps=3, bursts=([8, 8, 0], [8, 5, 0])):
    """Two streams through the paged programs, after a request that sent
    their first 280 tokens before them and left: A (300 tokens) finds its
    blocks, prefills on a miss all the same (no state was kept for a prefix
    seen once) and leaves a snapshot at 256; B shares the 280 and goes on
    with 50 of its own — 17 cached blocks match, and the hit is the 256
    positions that end at the snapshot; then single steps and bursts (B's
    budget ends it inside the second).  Returns ``{slot: (prompt length,
    tokens, log-softmax rows)}``."""
    rng = np.random.RandomState(3)
    A = [int(t) for t in rng.randint(0, V, 300)]
    B = A[:280] + [int(t) for t in rng.randint(0, V, 50)]
    eng.prefill(A[:280] + [int(t) for t in rng.randint(0, V, 10)], 2,
                reserve_tokens=330)
    eng.release_slot(2)
    assert eng.pool.snapshots_in_use == 0
    seqs, rows = {0: list(A), 1: list(B)}, {0: [], 1: []}
    for s in (0, 1):
        seqs[s].append(eng.prefill(seqs[s], s,
                                   reserve_tokens=len(seqs[s]) + 30))
        rows[s].append(_full(eng.last_prefill_logprobs()))
    assert eng.pool.hits == 16 and eng.pool.snapshots_restored == 1
    assert eng.kv_stats()["prefill_tokens"] == {
        "miss": 590, "hit": 74, "prefix_hit": 256}
    lt, pv = np.zeros(3, np.int32), np.zeros(3, np.int32)

    def heads():
        for s in (0, 1):
            lt[s], pv[s] = seqs[s][-1], len(seqs[s]) - 1

    for _ in range(steps):
        heads()
        nxt = eng.decode(lt, pv)
        lp = _full(eng.last_logprobs())
        for s in (0, 1):
            seqs[s].append(int(nxt[s]))
            rows[s].append(lp[s])
    for budget in bursts:
        heads()
        toks, emitted = eng.decode_burst(
            lt, pv, np.array(budget, np.int32), np.full(3, -1, np.int32),
            np.array([True, True, False]))
        lp = _full(eng.last_logprobs())
        assert emitted.tolist() == list(budget)
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


@pytest.mark.parametrize("dtype,tol", [("float32", 6e-5), ("bfloat16", 0.3)])
def test_the_whole_forward_is_the_reference(dtype, tol):
    """The cacheless forward of the model (its runs scanned, two prompts at
    once, 300 positions: two chunks) against the reference, layer by layer
    and token by token.  Float32: sums in another order; bfloat16: the
    precision the configuration states, on logits of size ~2.7."""
    cfg = _cfg(dtype)
    ids = np.random.RandomState(0).randint(0, cfg["vocab_size"], (2, 300))
    want = np.asarray(ref.make_forward(cfg, "float32")(
        ref.init_params(cfg, 3), jnp.asarray(ids, jnp.int32)))
    with mx.autograd.pause():
        got = _net(cfg, 3)(mx.nd.array(ids.astype(np.int32))).asnumpy()
    assert want.std() > 0.5
    np.testing.assert_allclose(got, want, atol=tol, rtol=0)


@pytest.fixture(scope="module")
def served_f32():
    cfg = _cfg()
    eng, params = _engine(cfg)
    return cfg, params, _serve(eng, cfg["vocab_size"]), eng


@pytest.mark.parametrize("slot", [0, 1], ids=["miss", "snapshot_hit"])
def test_paged_float32_matches_reference(served_f32, slot):
    """Every log-probability the served path computed — at the prefill's
    last position, three single steps, two 8-step bursts with a slot
    leaving inside the second — against the reference's full forward over
    the same tokens: the chunked scan (from zeros, and from a snapshot
    restored by row), the one-token step on the engine's leaves, and paged
    attention over two KV heads of 64 stored as one of 128, against the
    recurrence token by token and dense attention on heads of 64."""
    cfg, params, served, eng = served_f32
    assert (eng.layout.kv_heads, eng.layout.head_dim) == (1, 128)
    n_prompt, seq, rows = served[slot]
    want = _reference_rows(cfg, params, n_prompt, seq)
    assert rows.shape == want.shape and len(rows) == (20 if slot == 0
                                                       else 17)
    np.testing.assert_allclose(rows, want, atol=6e-5, rtol=0)


def test_a_snapshot_hit_equals_the_miss(served_f32):
    """B's first row after a hit that started from the snapshot's row
    against the same prompt prefilled whole on an engine that never saw
    it."""
    cfg, _, served, _ = served_f32
    n_prompt, seq, rows = served[1]
    eng, _ = _engine(cfg, name="fresh")
    eng.prefill(seq[:n_prompt], 0, reserve_tokens=n_prompt + 8)
    assert eng.pool.hits == 0
    np.testing.assert_allclose(_full(eng.last_prefill_logprobs()), rows[0],
                               atol=6e-5, rtol=0)


def test_counters_and_the_state_gauge(served_f32):
    """``mxtpu_ssm_step_rows_total`` counts a live slot a decode step (2
    slots x 3 steps + 16 + 13 in the bursts), ``mxtpu_ssm_step_rows_
    skipped_total`` the slots the step's work list left out (a step of a
    dispatch has ``max_slots`` rows: updated or skipped),
    ``mxtpu_ssm_prefill_tokens_total`` every computed prompt position,
    ``mxtpu_ssm_state_bytes`` one sequence's state — and the stock
    state-row series count these rows."""
    cfg, _, _, eng = served_f32
    got = eng.decode_counters()
    assert got["ssm_step_rows"] == 35 and got["ssm_prefill_tokens"] == 664
    # 3 single steps and 2 bursts of 8 over 3 slots: 57 rows in all
    assert got["ssm_step_rows_skipped"] == 3 * (3 + 2 * 8) - 35
    H, P, N = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    row = 6 * 4 * (H * P * N + 3 * (H * P + 2 * N))     # six Mamba layers
    assert eng._ssm_bytes == row
    stats = eng.kv_stats()
    assert stats["state_rows_total"] == 3 + 4
    assert stats["state_bytes"] == (3 + 4 + 1) * row
    text = telemetry.registry.render_prometheus()
    for name in ("mxtpu_ssm_step_rows_total", "mxtpu_ssm_state_bytes",
                 "mxtpu_ssm_step_rows_skipped_total",
                 "mxtpu_ssm_prefill_tokens_total"):
        assert f'{name}{{model="tiny"}}' in text
    assert f'mxtpu_ssm_state_bytes{{model="tiny"}} {row}' in text


def test_spans_name_the_mixer_and_the_mlp(served_f32):
    """The scopes a device trace reads: the mixer, its convolution, the
    chunked scan in a prefill program and the one-token step in a decode
    program, and the MLP."""
    eng = served_f32[3]
    sds = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)      # noqa: E731
    params, aux = eng._param_fn()
    tail = (tuple(map(sds, params)), tuple(map(sds, aux)))
    cache = tuple(map(sds, eng._cache + eng._recur))
    state = jax.tree.map(sds, eng._slot_state())
    step = eng._decode_jit.trace(cache, state, *tail).lower().as_text(
        debug_info=True)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)         # noqa: E731
    prompt = eng._prefill_jit.trace(cache, state, i32(1, 256), i32(3),
                                    *tail).lower().as_text(debug_info=True)
    for text, scopes in ((step, ("attn.ssm", "ssm.conv", "ssm.step",
                                 "mlp.shared", "attn.full")),
                         (prompt, ("attn.ssm", "ssm.conv", "ssm.scan",
                                   "mlp.shared"))):
        for scope in scopes:
            assert scope in text, scope
    assert "ssm.scan" not in step and "ssm.step" not in prompt


def test_kernels_forced_serve_the_reference(monkeypatch):
    """With the kernels interpreted — the state step in place on the
    engine's leaves, the grouped paged kernel over pairs of KV heads of 64
    — the served rows are still the reference's."""
    monkeypatch.setenv("MXNET_FA_DECODE_FORCE_PALLAS", "1")
    cfg = _cfg()
    eng, params = _engine(cfg, name="forced", scan_steps=4)
    served = _serve(eng, cfg["vocab_size"], steps=1, bursts=([4, 3, 0],))
    assert eng.program_inventory()["paged_attention"] == "pallas"
    for slot in (0, 1):
        n_prompt, seq, rows = served[slot]
        np.testing.assert_allclose(
            rows, _reference_rows(cfg, params, n_prompt, seq), atol=6e-5,
            rtol=0)


def test_paged_bfloat16_is_the_stated_precision_and_float8_is_not():
    """In bfloat16 the served rows lie as near the float32 reference as the
    reference computed in bfloat16 does, and the float8 control lies well
    beyond both."""
    cfg = _cfg("bfloat16")
    eng, params = _engine(cfg, name="bf16")
    n_prompt, seq, rows = _serve(eng, cfg["vocab_size"])[0]
    want = _reference_rows(cfg, params, n_prompt, seq)
    err = {p: np.abs(_reference_rows(cfg, params, n_prompt, seq, p)
                     - want).mean() for p in ("bfloat16", "float8")}
    served = np.abs(rows - want).mean()
    assert served < 2 * err["bfloat16"] and 3 * served < err["float8"]


def test_a_stacked_run_is_its_layers_one_by_one():
    """The run of three Mamba layers (parameters stacked, blocks scanned)
    over a prompt of two chunks, against three runs of one layer that hold
    its slices."""
    cfg = _cfg()
    net = _net(cfg)
    kind, n = layer_runs(cfg["layer_types"])[2]
    run = net.layers[2]
    assert (kind, n) == ("mamba", 3) and run.state_shapes[0][0][0] == 3
    h = mx.nd.array(np.random.RandomState(1).normal(
        size=(2, 300, cfg["hidden_size"])).astype(np.float32))
    with mx.autograd.pause():
        want, got = run(h).asnumpy(), h
        for i in range(n):
            one = GraniteMambaRun(net._cfg, 1, prefix=f"one{i}_")
            for name in one._names:
                arr = getattr(run, name).data()._data[i:i + 1]
                p = getattr(one, name)
                p._data, p._deferred_init = mx.nd.NDArray(arr), None
            got = one(got)
    np.testing.assert_allclose(got.asnumpy(), want, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("forced", [False, True], ids=["lax", "pallas"])
def test_one_compiled_step_of_a_run_serves_any_live_rows(forced,
                                                         monkeypatch):
    """One token a row through the run of three layers, the engine's whole
    leaves handed in (``T == 1``): which rows are live is DATA — two calls
    of one compiled program with other rows live trace once — and each call
    gives its live rows what a call with every row live gives them, and
    leaves the others' state, both leaves, bit for bit."""
    from incubator_mxnet_tpu.kernels import mamba2
    monkeypatch.setenv("MXNET_FA_DECODE_FORCE_PALLAS", "1" if forced else "0")
    mamba2._step_pallas.clear_cache()
    cfg = _cfg()
    run = _net(cfg).layers[2]
    rng = np.random.RandomState(5)
    B, R = 4, 6                         # sequences, rows of the leaves
    leaves = tuple(jnp.asarray(rng.normal(size=(R,) + shape), dtype)
                   for shape, dtype in run.state_shapes)
    h = jnp.asarray(rng.normal(size=(B, 1, cfg["hidden_size"])), jnp.float32)
    traced = []

    @jax.jit
    def step(h, leaves, live):
        traced.append(1)
        out, new, _, _ = run.serve_recurrent(
            h, jnp.zeros((B, 1), jnp.int32), leaves, live)
        return out, new

    want_h, want = step(h, leaves, jnp.ones((B, 1), bool))
    assert any((np.asarray(a) != np.asarray(b)).any()
               for a, b in zip(want, leaves))
    for live in ([True, False, True, True], [False, True, False, False],
                 [False, False, False, False]):
        on = np.asarray(live)
        got_h, got = step(h, leaves, jnp.asarray(on)[:, None])
        assert np.isfinite(np.asarray(got_h)).all()
        np.testing.assert_allclose(np.asarray(got_h)[on],
                                   np.asarray(want_h)[on], atol=1e-6, rtol=0)
        for new, full, old in zip(got, want, leaves):
            new, full, old = (np.asarray(a) for a in (new, full, old))
            np.testing.assert_allclose(new[:B][on], full[:B][on], atol=1e-6,
                                       rtol=0)
            assert (new[:B][~on] == old[:B][~on]).all()
            assert (new[B:] == old[B:]).all()
    assert len(traced) == 1
    mamba2._step_pallas.clear_cache()


def _zero_conv_bias(params):
    for layer in params["layers"]:
        if "conv_bias" in layer:
            layer["conv_bias"] = jnp.zeros_like(layer["conv_bias"])


@pytest.mark.parametrize("what", [
    "embedding_multiplier", "attention_multiplier", "residual_multiplier",
    "logits_scaling", "conv_bias"])
def test_each_scalar_and_the_bias_count(what):
    """None of the four multipliers is silently 1 and the convolution's
    bias is not silently 0: a model built with one of them changed gives
    other logits than the reference."""
    cfg = _cfg()
    ids = np.random.RandomState(0).randint(0, cfg["vocab_size"], (1, 40))
    want = np.asarray(ref.make_forward(cfg, "float32")(
        ref.init_params(cfg, 3), jnp.asarray(ids, jnp.int32)))
    if what == "conv_bias":
        net = _net(cfg, 3, _zero_conv_bias)
    else:
        net = _net(dict(cfg, **{what: 1.0}), 3)
    with mx.autograd.pause():
        got = net(mx.nd.array(ids.astype(np.int32))).asnumpy()
    assert np.abs(got - want).max() > 1e-2


def test_the_tied_head_is_one_array():
    """No ``lm_head`` parameter exists; the head's product reads the
    embedding's own array, adopted once, and ``served_state`` sees one
    type over the parameters there are."""
    cfg = _cfg("bfloat16")
    params = ref.init_params(cfg, 3)
    net = prog.build_net(cfg)
    embedding = params["embed_tokens"]
    prog.load_weights(net, params)
    assert net.lm_head is None
    assert not [n for n in net.collect_params() if "lm_head" in n]
    assert net.embed_tokens.data()._data is embedding
    eng = GenerationEngine(net, name="tied", max_slots=2, max_len=64,
                           prefill_buckets=[32], state_snapshot_rows=0)
    held = sum(int(p.data()._data.size) for p in
               net.collect_params().values())
    d, V = cfg["hidden_size"], cfg["vocab_size"]
    assert held < sum(int(np.prod(s)) for i in range(8) for s in
                      ref.layer_shapes(cfg, i).values()) + V * d + d + 1
    assert prog.served_state(net, eng) == {
        "param_dtype": "bfloat16", "param_bytes": 2, "kv_dtype": "bfloat16",
        "kv_bytes": 2, "matmul_precision": "default",
        "state_dtype": "float32", "state_bytes": 4}
    with pytest.raises(MXNetError):
        net.adopt_arrays({"embed_tokens": embedding, "norm": params["norm"],
                          "lm_head": embedding.T, "layers": []})


def test_the_constructor_refuses_what_it_cannot_write_down():
    for over in (dict(num_local_experts=4), dict(mamba_n_groups=2),
                 dict(position_embedding_type="rope"),
                 dict(tie_word_embeddings=False), dict(mamba_conv_bias=False),
                 dict(num_attention_heads=3), dict(mamba_chunk_size=128),
                 dict(layer_types=["mamba"] * 7),
                 dict(layer_types=["mamba"] * 7 + ["window"])):
        with pytest.raises(MXNetError):
            prog.build_net(_cfg(**over))
