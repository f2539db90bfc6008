"""Every Pallas kernel compiled for the chip (``interpret=False``) at the
shapes the served models use, against its lax reference.

gpt2_124m decode: H 12, D 64, block 16, T 1024 (8 slots; verify width
spec_k + 1 = 5).  The benchmark's serve cell (GPT-2-medium): 36 slots, H 16,
D 64, block 16, 64 table entries, 1,217 blocks, ragged positions — with one
timing line a call (``-s`` shows them; PERF.md section 6 keeps them).
BERT-large attention: H 16, D 64, T 128 and 512.

Precision: the kernels run as served — Mosaic's default f32 matmul, like
XLA's on the chip, is ONE bf16 pass (operands rounded to 8 mantissa bits;
measured on the v5e: a single-key decode returns exactly bf16(v)).  The
lax references run at ``highest``, so the comparison is against the math
and the tolerance is that rounding: 2e-2 on O(1) values.  The paged kernel
has no matmul — float32 products and sums on the VPU — and is held to
1e-5."""
import importlib
import math
import re
import time

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

fa = importlib.import_module("incubator_mxnet_tpu.kernels.flash_attention")

S, H, D, BS, T = 8, 12, 64, 16, 1024
SCALE = 1.0 / 8.0
TOL = dict(rtol=2e-2, atol=2e-2)


def _rand(rng, shape, dtype=jnp.float32):
    return jnp.asarray(rng.standard_normal(shape), dtype)


def _ref(fn, *args, **kw):
    with jax.default_matmul_precision("highest"):
        return onp.asarray(fn(*args, **kw), onp.float32)


def _positions():
    # first block, mid-block, block boundary, last position
    return jnp.asarray([0, 5, 15, 16, 100, 511, 1000, T - 1], jnp.int32)


def _paged(rng):
    nb = T // BS
    pages = 1 + S * nb
    kp = _rand(rng, (pages, H, BS, D))
    vp = _rand(rng, (pages, H, BS, D))
    tables = jnp.asarray(
        1 + rng.permutation(S * nb).reshape(S, nb), jnp.int32)
    return kp, vp, tables


def test_decode_paged_matches_lax():
    rng = onp.random.default_rng(1)
    q = _rand(rng, (S, H, D))
    kp, vp, tables = _paged(rng)
    pos = _positions()
    ref = _ref(fa._xla_paged_decode_attention, q, kp, vp, tables, pos, SCALE)
    # on the chip the public paged entry point takes the kernel by default
    assert fa.paged_attention_impl(q, kp) == "pallas"
    pub = jax.jit(lambda *a: fa.paged_decode_attention(*a, scale=SCALE))(
        q, kp, vp, tables, pos)
    onp.testing.assert_allclose(onp.asarray(pub), ref, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("n_q", [5])
def test_verify_paged_matches_lax(n_q):
    rng = onp.random.default_rng(3)
    q = _rand(rng, (S, H, n_q, D))
    kp, vp, tables = _paged(rng)
    pos = jnp.minimum(_positions(), T - n_q)
    got = jax.jit(lambda *a: fa.paged_verify_decode_attention(
        *a, scale=SCALE))(q, kp, vp, tables, pos)
    ref = _ref(fa._xla_paged_verify_decode_attention, q, kp, vp, tables,
               pos, SCALE)
    onp.testing.assert_allclose(onp.asarray(got), ref, rtol=1e-5, atol=1e-5)


# --- the benchmark's serve cell: GPT-2-medium, 36 slots, 1,217 blocks
CELL = dict(S=36, H=16, D=64, bs=16, n_cols=64, N=1217)


def _cell_case(fill):
    """Tables and positions as a serve cell holds them: every stream
    reserves past its write head; ``chat`` leaves half the slots free."""
    c = CELL
    r = onp.random.default_rng(11)
    pos = r.integers(32, 640, c["S"])
    live = onp.ones(c["S"], bool) if fill == "closed" \
        else r.random(c["S"]) < 0.5
    pos = onp.where(live, pos, 0)
    perm = 1 + r.permutation(c["N"] - 1)
    tables = onp.zeros((c["S"], c["n_cols"]), onp.int32)
    used = 0
    for s in range(c["S"]):
        if live[s]:
            n = min(c["n_cols"], (pos[s] + 96) // c["bs"] + 1)
            tables[s, :n] = perm[used:used + n]
            used += n
    assert used < c["N"], used
    return jnp.asarray(tables), jnp.asarray(pos, jnp.int32), \
        int((pos[live] + 1).sum())


def _ms_per_call(step, q, k, v, t, p):
    """Device time of one call inside a program: the slope of a
    ``fori_loop`` over the call between 10 and 60 trips (what the
    program pays once — dispatch, the pools' relayout — drops out)."""
    def loop(n):
        def run(q, k, v, t, p):
            def body(_, c):
                qq, tt = c
                o = step(qq, k, v, tt, p)
                z = (o.reshape(-1)[0] * 0).astype(jnp.int32)
                return qq + o * 0, tt + z      # each trip needs the last
            return jax.lax.fori_loop(0, n, body, (q, t))[0]
        return jax.jit(run)
    took = {}
    for n in (10, 60):
        f = loop(n)
        f(q, k, v, t, p).block_until_ready()
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            f(q, k, v, t, p).block_until_ready()
            best = min(best, time.perf_counter() - t0)
        took[n] = best
    return (took[60] - took[10]) / 50 * 1e3


def _cell_tables(rng, S, n_cols, used, shared=0):
    """A cell's block tables, ``used`` blocks a slot, two ways: ``row`` — as
    the pool hands them out, each slot's blocks in a row after ``shared``
    blocks that every slot reads (the grouped kernel fetches a step in one
    copy) — and ``shuffled`` block by block (a copy a block: the traffic
    of the kernel before it fetched by runs)."""
    own = used - shared
    row = onp.zeros((S, n_cols), onp.int32)
    row[:, :shared] = 1 + onp.arange(shared)
    row[:, shared:used] = 1 + shared + onp.arange(S * own).reshape(S, own)
    shuffled = onp.zeros((S, n_cols), onp.int32)
    shuffled[:, :used] = 1 + rng.permutation(S * used).reshape(S, used)
    return {"row": jnp.asarray(row), "shuffled": jnp.asarray(shuffled)}


def _gqa_against_the_gather(kernel, gather, q, kp, vp, tables, pos):
    """Parity of ``kernel`` with ``gather`` at ``highest`` over both ways a
    table can lie, and ``{tables: {implementation: ms a call}}``."""
    line = {}
    for name, t in tables.items():
        got = onp.asarray(jax.jit(kernel)(q, kp, vp, t, pos), onp.float32)
        onp.testing.assert_allclose(got, _ref(gather, q, kp, vp, t, pos),
                                    **TOL)
        line[name] = {"pallas": round(
            _ms_per_call(kernel, q, kp, vp, t, pos), 4)}
    line["row"]["lax_gather"] = round(
        _ms_per_call(gather, q, kp, vp, tables["row"], pos), 4)
    return line


@pytest.mark.parametrize("n_q", [1, 5])
@pytest.mark.parametrize("fill", ["closed", "chat"])
def test_paged_at_the_serve_cell_shapes(fill, n_q):
    c = CELL
    rng = onp.random.default_rng(5)
    q = _rand(rng, (c["S"], c["H"], n_q, D))
    kp = _rand(rng, (c["N"], c["H"], c["bs"], c["D"]))
    vp = _rand(rng, (c["N"], c["H"], c["bs"], c["D"]))
    tables, pos, live_keys = _cell_case(fill)
    kernel = lambda *a: fa._paged_verify_pallas(*a, SCALE, False)  # noqa: E731
    gather = lambda *a: fa._xla_paged_verify_decode_attention(     # noqa: E731
        *a, SCALE)
    got = jax.jit(kernel)(q, kp, vp, tables, pos)
    ref = _ref(gather, q, kp, vp, tables, pos)
    onp.testing.assert_allclose(onp.asarray(got), ref, rtol=1e-5, atol=1e-5)
    line = {name: round(_ms_per_call(step, q, kp, vp, tables, pos), 4)
            for name, step in (("pallas", kernel), ("lax_gather", gather))}
    print(f"\npaged attention, serve cell {fill}, n_q {n_q}, "
          f"{live_keys} live keys: ms a call {line}", flush=True)
    assert line["pallas"] < line["lax_gather"]


@pytest.mark.parametrize("window", [None, 4096])
def test_gqa_paged_at_the_agent_cell_shapes(window):
    """The grouped-query kernel compiled for the chip at the
    ``trinity-ep8-agent-closed`` cell's shapes — 64 slots, 6 query heads
    on 1 KV head of 128, block 16, 512 table entries, a bfloat16 pool,
    contexts 4,300-5,300 — against the lax gather at ``highest``, with
    and without the window's lower bound.  Both round K, V and the
    softmax weights to bfloat16, so they agree to 2e-2.  The timing line
    is a record, not a claim: at these shapes (59% of every table live)
    the gather, whose cost does not depend on what is live, was the
    faster on the v5e — 1.44 / 1.69 ms a call against the kernel's 2.74 /
    2.47 (my chip run 1, PR 26).  Since PR 38 the kernel fetches by runs,
    512 keys a step: tables as the pool hands them out (256 shared blocks,
    then each slot's own in a row) and shuffled block by block, one timing
    line each (PERF.md section 6, PR 38, has what was read)."""
    rng = onp.random.default_rng(11)
    S, Hq, Dh, bs, n_cols, used = 64, 6, 128, 16, 512, 336
    N = 1 + S * used
    kp = _rand(rng, (N, 1, bs, Dh), jnp.bfloat16)
    vp = _rand(rng, (N, 1, bs, Dh), jnp.bfloat16)
    tables = _cell_tables(rng, S, n_cols, used, shared=256)
    pos = jnp.asarray(rng.integers(4300, 5300, S), jnp.int32)
    q = _rand(rng, (S, Hq, Dh), jnp.bfloat16)
    scale = 1.0 / math.sqrt(Dh)

    def kernel(q, k, v, t, p):
        return fa._paged_gqa_pallas(q[:, :, None, :], k, v, t, p, scale,
                                    window, False)[:, :, 0, :]

    def gather(q, k, v, t, p):
        return fa._xla_paged_decode_attention(q, k, v, t, p, scale, window)

    line = _gqa_against_the_gather(kernel, gather, q, kp, vp, tables, pos)
    keys = int(onp.sum(onp.minimum(onp.asarray(pos) + 1, window or 10**9)))
    print(f"\ngqa paged attention, agent cell, window {window}, {keys} "
          f"keys read: ms a call {line}", flush=True)


@pytest.mark.parametrize("window", [None, 4096])
def test_gqa_paged_at_the_docqa_cell_shapes(window):
    """The grouped-query kernel over FOUR KV heads compiled for the chip at
    the ``smallthinker-l8-docqa-closed`` cell's shapes — 32 slots, 28 query
    heads on 4 KV heads of 128, block 16, 512 table entries, a bfloat16
    pool, contexts 1,500-7,700 on both sides of the window — against the
    lax gather at ``highest``, with and without the window's lower bound,
    tolerances as at the agent cell's shapes.  A page is a block with all
    its four heads (16 KB).  Tables in a row and shuffled block by block,
    one timing line each: a record, not a claim (PERF.md, PR 31 and PR 38,
    has what was read)."""
    rng = onp.random.default_rng(13)
    S, H, Hq, Dh, bs, n_cols = 32, 4, 28, 128, 16, 512
    N = 1 + S * n_cols
    kp = _rand(rng, (N, H, bs, Dh), jnp.bfloat16)
    vp = _rand(rng, (N, H, bs, Dh), jnp.bfloat16)
    tables = _cell_tables(rng, S, n_cols, n_cols)
    pos = jnp.asarray(rng.integers(1500, 7700, S), jnp.int32)
    q = _rand(rng, (S, Hq, Dh), jnp.bfloat16)
    scale = 1.0 / math.sqrt(Dh)

    def kernel(q, k, v, t, p):
        return fa._paged_gqa_pallas(q[:, :, None, :], k, v, t, p, scale,
                                    window, False)[:, :, 0, :]

    def gather(q, k, v, t, p):
        return fa._xla_paged_decode_attention(q, k, v, t, p, scale, window)

    line = _gqa_against_the_gather(kernel, gather, q, kp, vp, tables, pos)
    keys = int(onp.sum(onp.minimum(onp.asarray(pos) + 1, window or 10**9)))
    nbytes = 2 * H * Dh * 2 * keys
    print(f"\ngqa paged attention, docqa cell, window {window}, {keys} "
          f"keys read = {nbytes / 1e6:.1f} MB = {nbytes / 819e9 * 1e3:.3f} "
          f"ms of bandwidth: ms a call {line}", flush=True)


def test_engine_traces_the_kernel_on_the_chip():
    """A paged engine on the chip decodes (per step and in bursts) through
    the kernel and says so in its inventory; the per-step and the scanned
    program give the same greedy stream."""
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.models.gpt import GPTModel
    from incubator_mxnet_tpu.serving import GenerationEngine
    mx.random.seed(3)
    net = GPTModel(vocab_size=64, units=768, hidden_size=1024, num_layers=2,
                   num_heads=12, max_length=256, dropout=0.0)
    net.initialize(init=mx.init.Normal(0.05))
    net(mx.nd.array(onp.zeros((1, 2), onp.int32)))
    prompt = [3, 7, 11, 5, 9]
    got = {}
    for steps in (0, 4):
        eng = GenerationEngine(net, name=f"chip{steps}", max_slots=4,
                               max_len=256, prefill_buckets=[8],
                               scan_steps=steps)
        got[steps] = eng.generate(prompt, max_new_tokens=40)
        assert eng.program_inventory()["paged_attention"] == "pallas"
    assert len(got[0]) == 40 and got[0] == got[4]


def test_pool_rests_as_the_burst_program_takes_it():
    """On the chip the pools of a GPT of 16 heads of 64 features (stated
    ``[N, 16, 16, 64]``: N would lie on the lanes by default) are stored
    position-major, ``[N, 16, 16, 128]``, which rests row-major: that is
    the layout the burst program takes and returns them in — the engine's
    own jit says so — and they stay in it through prefill, single steps,
    bursts and `reset()`, in a second engine too, whose programs come out
    of the persistent compile cache (an executable read back from it
    gives its results in default layouts, whatever it was compiled for).
    One KV head of 128 features rests row-major as stated."""
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.models.gpt import GPTModel
    from incubator_mxnet_tpu.serving import GenerationEngine
    from incubator_mxnet_tpu.serving.kvcache import KVLayout
    mx.random.seed(3)
    net = GPTModel(vocab_size=64, units=1024, hidden_size=1024,
                   num_layers=2, num_heads=16, max_length=256, dropout=0.0)
    net.initialize(init=mx.init.Normal(0.05))
    net(mx.nd.array(onp.zeros((1, 2), onp.int32)))
    got = []
    for name in ("rest-a", "rest-b"):
        eng = GenerationEngine(net, name=name, max_slots=4, max_len=256,
                               prefill_buckets=[8], scan_steps=4)
        assert eng.program_inventory()["pool_layout"] == {
            "stored": "position_major", "shape": [65, 16, 16, 128],
            "stated": [65, 16, 16, 64]}

        def resting():
            return {(c.shape, c.format.layout) for c in eng._cache}

        allocated = resting()
        assert {(s, l.major_to_minor) for s, l in allocated} \
            == {((65, 16, 16, 128), (0, 1, 2, 3))}
        got.append(eng.generate([3, 7, 11, 5, 9], max_new_tokens=24))
        assert resting() == allocated
        assert eng.program_inventory()["paged_attention"] == "pallas"
        params, aux = eng._param_fn()
        compiled = eng._decode_burst_jit.lower(
            eng._cache, eng._slot_state(), params, aux).compile()
        taken = {f.layout for f in compiled.input_formats[0][0]}
        given = {f.layout for f in compiled.output_formats[0]}
        assert taken == given == {l for _, l in allocated}
        assert not re.search(r"= f32\[65,16,16,128\]\{[^}]*\} copy\(",
                             compiled.as_text())
        eng.reset()
        assert resting() == allocated
        assert eng.generate([3, 7, 11, 5, 9], max_new_tokens=24) == got[-1]
    assert got[0] == got[1]
    agent = KVLayout(5, 1, 128, "bfloat16", (None,) * 5, 8192)
    assert agent.pool_shape(32769, 16, jax.devices()[0]) \
        == ((32769, 1, 16, 128), False)


# --- flash forward + both backward kernels, BERT-large attention shapes
B_F, H_F = 4, 16


def _flash_case(rng, t, dtype, masked):
    q, k, v = (_rand(rng, (B_F, H_F, t, D), dtype) for _ in range(3))
    mask = None
    if masked:
        # not a 1-key row: there dK's true value is 0 by cancellation
        # (dP == D exactly), and one-bf16-pass dP against an f32 D leaves
        # ~0.06 * sqrt(T/128) of rounding (measured on the v5e) — the
        # interpret-mode tier-1 tests own that boundary in exact arithmetic
        valid = onp.asarray([t, t // 2, 9, t - 3])
        mask = jnp.asarray(onp.arange(t)[None, :] < valid[:, None],
                           jnp.float32)
    return q, k, v, mask


def _flash_ref(q, k, v, mask, causal):
    """The lax reference on f32 copies of the same (possibly bf16) values."""
    b, h, t, d = q.shape
    bias = None
    if mask is not None:
        bias = jnp.where(mask > 0, 0.0, -1e30).astype(jnp.float32)
        bias = jnp.broadcast_to(bias[:, None, None, :],
                                (b, h, 1, t)).reshape(b * h, 1, t)
    f = lambda x: x.astype(jnp.float32).reshape(b * h, t, d)
    return fa._xla_attention(f(q), f(k), f(v), SCALE, causal,
                             bias=bias).reshape(b, h, t, d)


@pytest.mark.parametrize("t", [128, 512])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("masked,causal", [(True, False), (False, True)],
                         ids=["keymask", "causal"])
def test_flash_forward_and_backward_match_lax(t, dtype, masked, causal):
    rng = onp.random.default_rng(t + masked)
    dt = jnp.dtype(dtype)
    q, k, v, mask = _flash_case(rng, t, dt, masked)
    g = _rand(rng, q.shape, dt)

    def loss_pallas(q_, k_, v_):
        out = fa.flash_attention(q_, k_, v_, scale=SCALE, causal=causal,
                                 mask=mask)
        return jnp.sum(out.astype(jnp.float32) * g.astype(jnp.float32)), out

    def loss_ref(q_, k_, v_):
        out = _flash_ref(q_, k_, v_, mask, causal)
        return jnp.sum(out * g.astype(jnp.float32)), out

    (_, out), grads = jax.jit(jax.value_and_grad(
        loss_pallas, argnums=(0, 1, 2), has_aux=True))(q, k, v)
    with jax.default_matmul_precision("highest"):
        (_, out_r), grads_r = jax.jit(jax.value_and_grad(
            loss_ref, argnums=(0, 1, 2), has_aux=True))(q, k, v)
    onp.testing.assert_allclose(onp.asarray(out, onp.float32),
                                onp.asarray(out_r, onp.float32), **TOL)
    # gradients chain three rounded matmuls, and sum up to T terms
    gtol = dict(rtol=5e-2, atol=5e-2 * (t / 128) ** 0.5)
    for name, got, ref in zip("qkv", grads, grads_r):
        onp.testing.assert_allclose(
            onp.asarray(got, onp.float32), onp.asarray(ref, onp.float32),
            err_msg=f"d{name}", **gtol)


def test_flash_lse_output_matches_lax():
    rng = onp.random.default_rng(9)
    q, k, v, _ = _flash_case(rng, 128, jnp.float32, False)
    out, lse = fa.flash_attention_lse(q, k, v, scale=SCALE, causal=True)
    b, h, t, d = q.shape
    f = lambda x: x.reshape(b * h, t, d)
    with jax.default_matmul_precision("highest"):
        out_r, lse_r = fa._xla_attention_lse(f(q), f(k), f(v), SCALE, True)
    onp.testing.assert_allclose(onp.asarray(out), onp.asarray(
        out_r).reshape(b, h, t, d), **TOL)
    onp.testing.assert_allclose(onp.asarray(lse), onp.asarray(
        lse_r).reshape(b, h, t), **TOL)


def test_gqa_paged_at_the_corpusqa_cell_shapes():
    """The grouped-query kernel on heads of 256 at the
    ``qwen3next-tp2-corpusqa-closed`` cell's shapes — 64 slots, 8 query
    heads on 1 KV head of 256, block 16, 1,088 table entries, a bfloat16
    pool, contexts 10,500-17,300 — against the lax gather at ``highest``
    (both round K, V and the softmax weights to bfloat16: 2e-2), tables as
    the pool hands them out (512 shared blocks, then each slot's own in a
    row) and shuffled block by block, a timing line each (a record, not a
    claim)."""
    rng = onp.random.default_rng(35)
    S, Hq, Dh, bs, n_cols = 64, 8, 256, 16, 1088
    N = 1 + S * n_cols
    kp = _rand(rng, (N, 1, bs, Dh), jnp.bfloat16)
    vp = _rand(rng, (N, 1, bs, Dh), jnp.bfloat16)
    tables = _cell_tables(rng, S, n_cols, n_cols, shared=512)
    pos = jnp.asarray(rng.integers(10500, 17300, S), jnp.int32)
    q = _rand(rng, (S, Hq, Dh), jnp.bfloat16)
    scale = 1.0 / math.sqrt(Dh)

    def kernel(q, k, v, t, p):
        return fa._paged_gqa_pallas(q[:, :, None, :], k, v, t, p, scale,
                                    None, False)[:, :, 0, :]

    def gather(q, k, v, t, p):
        return fa._xla_paged_decode_attention(q, k, v, t, p, scale, None)

    assert fa._paged_kernel_kind(q, kp, Hq, None) == "gqa"
    line = _gqa_against_the_gather(kernel, gather, q, kp, vp, tables, pos)
    keys = int(onp.sum(onp.asarray(pos) + 1))
    print(f"\ngqa paged attention, corpusqa cell (D 256), {keys} keys "
          f"read: ms a call {line}", flush=True)


@pytest.mark.parametrize("T,from_state", [(5120, True), (8192, False)])
def test_gated_delta_prefill_at_the_corpusqa_cell_shapes(T, from_state):
    """The chunked delta rule — the WY operands by XLA, the chunk-to-chunk
    recurrence by the Pallas kernel — at the cell's shapes (16 value heads
    of 128 x 128, a hit's suffix from a restored state, snapshots every
    2,048) against the recurrence token by token and against its own
    ``lax.scan`` twin; everything float32 at ``highest``: 1e-4.  And the
    one-token step over 64 rows.  Timing lines are records."""
    gd = importlib.import_module("incubator_mxnet_tpu.kernels.gated_delta")
    rng = onp.random.default_rng(T)
    Hv, Dk = 16, 128
    q, k = (_rand(rng, (T, Hv, Dk)) for _ in range(2))
    q, k = (x / jnp.linalg.norm(x, axis=-1, keepdims=True) for x in (q, k))
    v = _rand(rng, (T, Hv, Dk))
    g = -jnp.exp(_rand(rng, (T, Hv)) - 3.0)
    beta = jax.nn.sigmoid(_rand(rng, (T, Hv)))
    s0 = _rand(rng, (Hv, Dk, Dk)) if from_state \
        else jnp.zeros((Hv, Dk, Dk), jnp.float32)
    live = jnp.arange(T) < T - 100
    assert gd.gated_delta_impl(q) == "pallas"
    run = jax.jit(lambda *a: gd.gated_delta_prefill(*a, snapshot_every=2048))
    o, snaps, last = run(q, k, v, g, beta, s0, live)
    with jax.default_matmul_precision("highest"):
        o_ref, last_ref = jax.jit(gd.gated_delta_scan)(q, k, v, g, beta, s0,
                                                       live)
        twin = jax.jit(lambda *a: gd._recurrence_scan(
            *gd._chunk_operands(*(x.reshape(T // 64, 64, *x.shape[1:])
                                  for x in a[:5])), a[5], 32))(
            q, k, v, jnp.where(live[:, None], g, 0.0),
            jnp.where(live[:, None], beta, 0.0), s0)
    tol = dict(rtol=1e-4, atol=1e-4)
    onp.testing.assert_allclose(onp.asarray(o)[:T - 100],
                                onp.asarray(o_ref)[:T - 100], **tol)
    onp.testing.assert_allclose(onp.asarray(last), onp.asarray(last_ref),
                                **tol)
    assert snaps.shape == (T // 2048, Hv, Dk, Dk)
    onp.testing.assert_allclose(
        onp.asarray(snaps), onp.asarray(jnp.moveaxis(twin[1], 0, 1)), **tol)
    t0 = time.perf_counter()
    for _ in range(5):
        jax.block_until_ready(run(q, k, v, g, beta, s0, live))
    ms = (time.perf_counter() - t0) / 5 * 1e3
    # the step: 64 rows
    S = 64
    rows = _rand(rng, (S, Hv, Dk, Dk))
    step = jax.jit(gd.gated_delta_step)
    args = (q[:S], k[:S], v[:S], g[:S], beta[:S], rows,
            (jnp.arange(S) % 7 != 0)[:, None])
    o1, rows2 = step(*args)
    with jax.default_matmul_precision("highest"):
        o1_ref, rows_ref = jax.jit(gd.gated_delta_step)(*args)
    onp.testing.assert_allclose(onp.asarray(rows2), onp.asarray(rows_ref),
                                **tol)
    onp.testing.assert_array_equal(onp.asarray(rows2)[0],
                                   onp.asarray(rows)[0])
    t0 = time.perf_counter()
    for _ in range(20):
        jax.block_until_ready(step(*args))
    print(f"\ngated delta prefill, {T} positions x 16 heads, from_state "
          f"{from_state}: {ms:.2f} ms a call (dispatch included); step over "
          f"64 rows {(time.perf_counter() - t0) / 20 * 1e3:.3f} ms a call",
          flush=True)


# ---------------------------------------------------------------------------
# the grouped expert product of a decode step (kernels/grouped_experts.py):
# one kernel over the touched experts against the lax loop it replaces
# ---------------------------------------------------------------------------

def _experts_case(T, k, published, count, d, f, seed):
    """A decode step's operands at a cell's widths: bfloat16 tokens and
    stacked matrices, the first ``count`` of ``published`` experts held, a
    softmax router over random logits (an even router: 61 of 64, ~190 of
    256 and ~20 of 32 experts touched)."""
    from incubator_mxnet_tpu.models import moe
    rng = onp.random.default_rng(seed)
    x = _rand(rng, (T, d), jnp.bfloat16)
    gate, up = (jnp.asarray(rng.standard_normal((count, d, f)) * 0.02,
                            jnp.bfloat16) for _ in range(2))
    down = jnp.asarray(rng.standard_normal((count, f, d)) * 0.02,
                       jnp.bfloat16)
    idx, w = moe.route_token_choice(_rand(rng, (T, published)), None, k,
                                    score="softmax")
    return x, (idx, w, gate, up, down)


def _us_per_call(step, x, rest):
    """:func:`_ms_per_call` for a step over ``(x, *rest)``, in µs."""
    def loop(n):
        def run(x, *rest):
            def body(_, xx):            # each trip needs the last
                return xx + (step(xx, *rest) * 0).astype(xx.dtype)
            return jax.lax.fori_loop(0, n, body, x)
        return jax.jit(run)
    took = {}
    for n in (10, 60):
        f = loop(n)
        f(x, *rest).block_until_ready()
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            f(x, *rest).block_until_ready()
            best = min(best, time.perf_counter() - t0)
        took[n] = best
    return (took[60] - took[10]) / 50 * 1e6


def _experts_kernel_against_the_loop(cell, T, k, published, count, d, f, act):
    from incubator_mxnet_tpu.models import moe
    x, rest = _experts_case(T, k, published, count, d, f, seed=36)
    assert moe.held_experts_impl(x, rest[2], T * k) == "pallas"

    def kernel(x, idx, w, gate, up, down):
        return moe.held_experts_ffn(x, idx, w, (0, count), gate, up, down,
                                    act=act)

    def loop(x, idx, w, gate, up, down):        # a tile asks for the loop
        return moe.held_experts_ffn(x, idx, w, (0, count), gate, up, down,
                                    act=act, tile=32)

    got, counts = jax.jit(kernel)(x, *rest)
    ref, counts_loop = jax.jit(loop)(x, *rest)
    assert [int(c) for c in counts] == [int(c) for c in counts_loop]
    onp.testing.assert_allclose(onp.asarray(got), onp.asarray(ref), **TOL)
    visits = int(counts[2])
    us = {name: _us_per_call(lambda *a, s=step: s(*a)[0], x, rest) / visits
          for name, step in (("pallas", kernel), ("lax_loop", loop))}
    least = 3 * d * f * 2 / 819e9 * 1e6
    print(f"\nexpert product, {cell} cell ({T} tokens, {visits} of {count} "
          f"experts touched, {3 * d * f * 2 / 1e6:.2f} MB a visit = "
          f"{least:.2f} us at 819 GB/s): us a visit "
          f"{ {n: round(v, 2) for n, v in us.items()} }", flush=True)
    assert us["pallas"] < us["lax_loop"]


def test_experts_kernel_at_the_docqa_cell_shapes():
    """SmallThinker's decode step: 32 tokens, top-6 of 64 ReGLU experts of
    2560 x 768, all held — parity with the loop (bfloat16 operands,
    float32 sums in another order: 2e-2) and the µs a visit of both."""
    _experts_kernel_against_the_loop("docqa", 32, 6, 64, 64, 2560, 768,
                                     "relu")


def test_experts_kernel_at_the_corpusqa_cell_shapes():
    """Qwen3-Next's: 64 tokens, top-10 of 512 published, 256 of 2048 x 512
    held."""
    _experts_kernel_against_the_loop("corpusqa", 64, 10, 512, 256, 2048,
                                     512, "silu")


def test_experts_kernel_at_the_agent_cell_shapes():
    """AFMoE's: 64 tokens, top-4 of 256 published, 32 of 3072 x 3072 held
    (the hidden width in six blocks of 512)."""
    _experts_kernel_against_the_loop("agent", 64, 4, 256, 32, 3072, 3072,
                                     "silu")


# -- a prompt's expert product: the sorted form (kernels/grouped_experts.py) --

def _prompt_experts_against_the_loop(cell, T, k, published, count, d, f, act):
    """A prefill's call at a cell's shapes through the sorted-form kernel
    (``held_experts_impl``'s ``"pallas_sorted"``) and through the loop (``tile=128``, the parent's
    prompt path), both in this process: the difference, the three counts,
    and the ms a call of each — on the router's routing and with every
    token choosing as the first does (a warm-up's zero tokens: all the
    pairs on ``k`` experts), where the kernel's time has to follow the
    pairs as the loop's does.  The grouping reads ``idx`` alone, so XLA
    hoists it out of the timing loop on both sides: a call here is the
    rows' gather, the product, the un-sort and the weighted sum."""
    from incubator_mxnet_tpu.models import moe
    ge = importlib.import_module(
        "incubator_mxnet_tpu.kernels.grouped_experts")
    x, (idx, w, gate, up, down) = _experts_case(T, k, published, count, d, f,
                                                seed=44)
    assert moe.held_experts_impl(x, gate, T * k) == "pallas_sorted"

    def kernel(x, idx, w, gate, up, down):      # whatever the rule says
        return ge.held_experts_sorted(x, idx, w, (0, count), gate, up, down,
                                      act=act)

    def loop(x, idx, w, gate, up, down):        # a tile asks for the loop
        return moe.held_experts_ffn(x, idx, w, (0, count), gate, up, down,
                                    act=act, tile=128)

    alike = jnp.broadcast_to(idx[:1], idx.shape)
    for routing, ids in (("routed", idx), ("alike", alike)):
        rest = (ids, w, gate, up, down)
        got, counts = jax.jit(kernel)(x, *rest)
        ref, counts_loop = jax.jit(loop)(x, *rest)
        assert [int(c) for c in counts] == [int(c) for c in counts_loop]
        onp.testing.assert_allclose(onp.asarray(got), onp.asarray(ref),
                                    **TOL)
        ms = {name: _us_per_call(lambda *a, s=step: s(*a)[0], x, rest) / 1e3
              for name, step in (("pallas_sorted", kernel),
                                 ("lax_loop", loop))}
        held, touched = int(counts[1]), int(counts[2])
        flops = 6 * d * f * held
        moved = touched * 3 * d * f * 2 + held * d * (2 + 4)
        least = max(flops / 197e12, moved / 819e9) * 1e3
        print(f"\nprompt's expert product, {cell} cell, {routing}: {T} tokens"
              f", {held} pairs held on {touched} of {count} experts, least "
              f"{least:.2f} ms ({flops / 1e9:.0f} GFLOP, {moved / 1e6:.0f} MB"
              f"): ms a call { {n: round(v, 2) for n, v in ms.items()} }, "
              f"widest difference "
              f"{float(jnp.max(jnp.abs(got - ref))):.2e}", flush=True)
        assert ms["pallas_sorted"] < ms["lax_loop"]


def test_prompt_experts_at_the_docqa_cell_shapes():
    """SmallThinker's miss prefill of the 6,144 bucket: 36,864 pairs on 64
    ReGLU experts of 2560 x 768, all held."""
    _prompt_experts_against_the_loop("docqa", 6144, 6, 64, 64, 2560, 768,
                                     "relu")


def test_prompt_experts_at_the_corpusqa_cell_shapes():
    """Qwen3-Next's hit prefill of the 8,192 bucket: 81,920 pairs, top-10 of
    512 published, the 256 of 2048 x 512 held here."""
    _prompt_experts_against_the_loop("corpusqa", 8192, 10, 512, 256, 2048,
                                     512, "silu")


# -- the latent cache's three decode reads (kernels/latent_attention.py) ------

def _slope_ms(fn, first, *rest):
    """Device time of one ``fn(first, *rest)`` inside a program: the slope
    of a ``fori_loop`` between 5 and 25 trips, each trip needing the last.
    The big operands ride in ``rest`` (a closed-over pool would be a
    constant of the executable)."""
    def loop(n):
        def run(first, *rest):
            def body(_, a):
                o = fn(a, *rest)
                return a + (jnp.sum(o) * 0).astype(a.dtype)
            return jax.lax.fori_loop(0, n, body, first)
        return jax.jit(run)
    took = {}
    for n in (5, 25):
        f = loop(n)
        f(first, *rest).block_until_ready()
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            f(first, *rest).block_until_ready()
            best = min(best, time.perf_counter() - t0)
        took[n] = best
    return (took[25] - took[5]) / 20 * 1e3


def _repoagent_case():
    """`dots3-tp8-repoagent-closed`'s decode shapes: ``(the latent module,
    (slots, table columns, block size, pool blocks), tables, positions,
    mk)`` — 64 slots whose tables share 1,536 blocks and go on with 100 of
    their own, write heads (numpy) at 24.9–26.2 k, ``mk(i, shape)`` seeded
    bfloat16 normals."""
    la = importlib.import_module(
        "incubator_mxnet_tpu.kernels.latent_attention")
    rng = onp.random.default_rng(0)
    S, n_cols, bs, N = 64, 1696, 16, 24577
    shared, own = 1536, 100
    tables = onp.zeros((S, n_cols), onp.int32)
    tables[:, :shared] = 1 + onp.arange(shared)
    for s in range(S):
        tables[s, shared:shared + own] = 1 + shared + s * 160 + onp.arange(own)
    positions = rng.integers(shared * bs + 300, (shared + own) * bs - 1,
                             S).astype(onp.int32)
    key = jax.random.PRNGKey(0)
    mk = lambda i, shape: jax.random.normal(               # noqa: E731
        jax.random.fold_in(key, i), shape, jnp.float32).astype(jnp.bfloat16)
    return la, (S, n_cols, bs, N), jnp.asarray(tables), positions, mk


def test_latent_reads_at_the_repoagent_cell_shapes():
    """`dots3-tp8-repoagent-closed`: 64 slots whose contexts of ~26 k share
    1,536 blocks.  (a) the sliding layers' window read, kernel against
    gather; (b) the full layers' index scoring, kernel against gather, and
    the choice of 2,048; (c) the read of the chosen rows; and a hit
    prefill's dense index scores, kernel against einsum.  Prints the ms a
    call of each."""
    la, (S, n_cols, bs, N), tables, positions, mk = _repoagent_case()
    positions = jnp.asarray(positions)
    ms = {}
    # (a) a sliding layer: 8 heads over rows of 1,088 (stored 1,152)
    pool = jnp.pad(mk(1, (N, bs, 1088)), ((0, 0), (0, 0), (0, 64)))
    q = mk(2, (S, 8, 1088))
    assert la.latent_decode_impl(q, pool) == "pallas"
    for name, fn in (("latent_window_pallas", la.paged_latent_decode),
                     ("latent_window_gather", la._xla_paged_latent_decode)):
        read = lambda q, pool, t, p, fn=fn: fn(      # noqa: E731
            q, pool, t, p, 1024, 0.0625, 513)
        ms[name] = _slope_ms(read, q, pool, tables, positions)
    got = la.paged_latent_decode(q, pool, tables, positions, 1024, 0.0625,
                                 513)
    want = la._xla_paged_latent_decode(q, pool, tables, positions, 1024,
                                       0.0625, 513)
    onp.testing.assert_allclose(onp.asarray(got), onp.asarray(want),
                                atol=3e-2, rtol=3e-2)
    del pool
    # (b) a full layer's index: 64 heads of 128 over every written key
    keys = mk(3, (N, bs, 128))
    q_i, w_i = mk(4, (S, 64, 128)), mk(5, (S, 64)).astype(jnp.float32)
    live = onp.arange(n_cols * bs)[None, :] <= onp.asarray(positions)[:, None]
    got = onp.asarray(la._paged_index_pallas(q_i, w_i, keys, tables,
                                             positions, False))
    want = onp.asarray(la._xla_paged_index_scores(q_i, w_i, keys, tables))
    onp.testing.assert_allclose(got[live], want[live], atol=0.05, rtol=0.02)
    ms["index_scores_pallas"] = _slope_ms(
        lambda q_i, w_i, keys, t, p: la._paged_index_pallas(
            q_i, w_i, keys, t, p, False), q_i, w_i, keys, tables, positions)
    ms["index_scores_gather"] = _slope_ms(
        lambda q_i, w_i, keys, t: la._xla_paged_index_scores(
            q_i, w_i, keys, t), q_i, w_i, keys, tables)
    rows, valid = la.paged_index_select(q_i, w_i, keys, tables, positions,
                                        2048)
    assert bool(valid.all()) and rows.shape == (S, 2048)
    ms["index_select"] = _slope_ms(
        lambda q_i, w_i, keys, t, p: la.paged_index_select(
            q_i, w_i, keys, t, p, 2048)[0].astype(jnp.float32),
        q_i, w_i, keys, tables, positions)
    # (c) 16 heads over the chosen rows of 576 (stored 640)
    lat = jnp.pad(mk(6, (N, bs, 576)), ((0, 0), (0, 0), (0, 64)))
    q16 = mk(7, (S, 16, 576))
    ms["sparse_rows"] = _slope_ms(
        lambda q, lat, rows, valid: la.paged_sparse_latent(
            q, lat, rows, valid, 512, 0.072), q16, lat, rows, valid)
    # a hit prefill's dense scores: 1,024 queries against 27,136 keys
    qp, wp = mk(8, (1024, 64, 128)), mk(9, (1024, 64)).astype(jnp.float32)
    kp = mk(10, (27136, 128))
    got = onp.asarray(la._index_scores_pallas(qp, wp, kp, False))
    want = onp.asarray(la.index_scores(qp[:64], wp[:64], kp))
    onp.testing.assert_allclose(got[:64], want, atol=0.05, rtol=0.02)
    ms["prompt_index_pallas"] = _slope_ms(
        lambda q, w, k: la._index_scores_pallas(q, w, k, False), qp, wp, kp)
    print("\nms a call at the repoagent cell's shapes: " + ", ".join(
        f"{name} {v:.3f}" for name, v in ms.items()))


def test_the_choice_at_the_repoagent_cell_shapes():
    """`dots3-tp8-repoagent-closed`'s choice on the chip: 64 slots, 27,136
    keys behind tables that share a 1,536-block prefix, 2,048 chosen —
    `paged_index_select` (the kernels: counting passes, compaction by
    rank) against the PARENT's form kept here as the oracle, one stable
    sort of the negated scores that carries the rows: the same sets and
    the same counts, on the index's own scores and on scores rounded until
    ~200 are equal at the threshold.  Prints the ms a call of each."""
    la, (S, _, bs, N), tables, positions, mk = _repoagent_case()
    k = 2048
    positions[:3] = [0, 1500, 2047]         # a free slot, fewer than k
    positions = jnp.asarray(positions)
    keys = mk(3, (N, bs, 128))
    q_i, w_i = mk(4, (S, 64, 128)), mk(5, (S, 64)).astype(jnp.float32)
    assert la.index_select_impl(q_i, keys) == "select:kernel"

    @jax.jit
    def sort_form(scores, tables, positions):
        K = scores.shape[1]
        live = jnp.arange(K, dtype=jnp.int32)[None, :] <= positions[:, None]
        worst, rows = jax.lax.sort(
            (jnp.where(live, -scores, jnp.inf), la._pool_rows(tables, bs)),
            dimension=1, is_stable=True, num_keys=1)
        return rows[:, :k], worst[:, :k] < jnp.inf

    def sets(rows, valid):
        rows, valid = onp.asarray(rows), onp.asarray(valid)
        return [sorted(rows[s][valid[s]].tolist()) for s in range(S)]

    scores = la._paged_index_pallas(q_i, w_i, keys, tables, positions, False)
    rows, valid = la.paged_index_select(q_i, w_i, keys, tables, positions, k)
    assert rows.shape == valid.shape == (S, k)
    want = sort_form(scores, tables, positions)
    assert onp.asarray(valid).sum(-1).tolist() \
        == onp.asarray(want[1]).sum(-1).tolist()
    assert onp.asarray(valid).sum(-1)[:4].tolist() == [1, 1501, 2048, 2048]
    assert sets(rows, valid) == sets(*want)
    # + 0.0: the oracle's sort takes -0.0 and +0.0 as equal, the exact
    # choice (``lax.top_k``'s order) does not
    tied = jnp.round(scores / 4) + 0.0
    got = la._index_choose_pallas(tied, tables, positions, bs, k, False)
    assert sets(*got) == sets(*sort_form(tied, tables, positions))
    ms = {
        "select_kernels": _slope_ms(
            lambda p, q_i, w_i, keys, t: la.paged_index_select(
                q_i, w_i, keys, t, p, k)[0].astype(jnp.float32),
            positions, q_i, w_i, keys, tables),
        "choice_kernel": _slope_ms(
            lambda p, sc, t: la._index_choose_pallas(
                sc, t, p, bs, k, False)[0].astype(jnp.float32),
            positions, scores, tables),
        "choice_sort_form": _slope_ms(
            lambda p, sc, t: sort_form(sc, t, p)[0].astype(jnp.float32),
            positions, scores, tables),
    }
    print("\nms a call at the repoagent cell's shapes: " + ", ".join(
        f"{name} {v:.3f}" for name, v in ms.items()))



def test_state_step_at_the_chat_cell_shapes():
    """``granite4hm-chat-open``: the one-token Mamba-2 step over 48 rows of
    one layer of a leaf (64 heads x 64 x 128 floats a row), in place: the
    Pallas kernel against the lax step at ``highest`` (no matmul in the
    kernel: float32 products and sums on the VPU), every number it was not
    asked for bit for bit as it came, the outputs of rows that are not live
    zeros (the kernel never visits them: what it left there is whatever the
    buffer held), and the time a call takes with 48, 32 and 0 rows live
    against the bytes the live ones have to move (``-s`` shows it)."""
    from incubator_mxnet_tpu.kernels import mamba2
    rng = onp.random.default_rng(42)
    R, n, rows, Hm, P, N = 52, 3, 48, 64, 64, 128
    leaf = _rand(rng, (R, n, N, Hm * P))
    x, B, C, Dm = (_rand(rng, s) for s in ((rows, Hm, P), (rows, N),
                                           (rows, N), (Hm,)))
    dt = jnp.asarray(rng.uniform(0.001, 0.1, (rows, Hm)), jnp.float32)
    g = -jnp.asarray(rng.uniform(1, 16, (Hm,)), jnp.float32) * dt
    assert mamba2.ssd_impl(x) == "pallas"
    step = jax.jit(mamba2.ssd_step_rows)

    def some(count):                # `count` rows live, scattered
        on = onp.zeros(rows, bool)
        on[rng.permutation(rows)[:count]] = True
        return on

    for on in (some(38), some(0), some(48), some(1)):
        y, out = step(leaf, jnp.int32(1), x, dt, g, B, C, Dm,
                      jnp.asarray(on))
        with jax.default_matmul_precision("highest"):
            want_y, want_S = (onp.asarray(a) for a in jax.jit(
                mamba2.ssd_step)(x, dt, g, B, C, Dm,
                                 leaf[:rows, 1].reshape(rows, N, Hm, P),
                                 jnp.asarray(on)))
        y = onp.asarray(y)
        onp.testing.assert_allclose(y[on], want_y[on], rtol=1e-4, atol=1e-4)
        assert onp.isfinite(y).all() and (y[~on] == 0).all()
        onp.testing.assert_allclose(onp.asarray(out[:rows, 1]),
                                    want_S.reshape(rows, N, Hm * P),
                                    rtol=1e-5, atol=1e-5)
        untouched = onp.array(out)
        untouched[:rows, 1][on] = onp.asarray(leaf)[:rows, 1][on]
        assert (untouched == onp.asarray(leaf)).all()

    def loop(trips, live):
        def run(leaf, x):
            def body(i, c):
                leaf, acc = c
                y, leaf = mamba2.ssd_step_rows(leaf, i % n, x + acc * 0, dt,
                                               g, B, C, Dm, live)
                return leaf, y
            return jax.lax.fori_loop(0, trips, body,
                                     (leaf, jnp.zeros_like(x)))
        return jax.jit(run)
    us = {}
    for count in (48, 32, 0):
        live, took = jnp.asarray(some(count)), {}
        for trips in (10, 60):
            f = loop(trips, live)
            jax.block_until_ready(f(leaf, x))
            best = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                jax.block_until_ready(f(leaf, x))
                best = min(best, time.perf_counter() - t0)
            took[trips] = best
        us[count] = (took[60] - took[10]) / 50 * 1e6
    least = 2 * rows * N * Hm * P * 4 / 819e9 * 1e6
    print(f"\nssd_step, one layer of 48 rows: 48 live {us[48]:.1f} us a "
          f"call ({least:.1f} us at 819 GB/s), 32 live {us[32]:.1f} us, "
          f"0 live {us[0]:.1f} us", flush=True)
    assert us[48] < 1.05 * 4 * least
    assert us[32] <= 0.80 * us[48]
    assert us[0] <= 0.25 * us[48]

