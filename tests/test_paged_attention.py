"""Paged decode attention: the Pallas kernel that reads the pool in place,
live blocks only (``kernels/flash_attention.py``), against the lax gather.

Interpret-mode parity over the cases a dense grid never told apart, the
trace-time selection, the engine's inventory field, and — in the fixtures
at the bottom — compiles for a v5e that is not attached (the chip's own
numerics and speed are ``tests_tpu/test_kernels_tpu.py``'s).
"""
import importlib
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from common import greedy_reference

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu.models.gpt import GPTModel
from incubator_mxnet_tpu.serving import GenerationEngine

fa = importlib.import_module("incubator_mxnet_tpu.kernels.flash_attention")

H, D, BS = 2, 8, 16
SCALE = 0.3


def _pool(rng, n_blocks):
    kp = rng.standard_normal((n_blocks, H, BS, D)).astype(np.float32)
    vp = rng.standard_normal((n_blocks, H, BS, D)).astype(np.float32)
    return kp, vp


def _case(name, n_q):
    """``(k_pages, v_pages, tables, positions)`` of one scenario.  Tables
    reserve past the write head, as the engine's do (prompt + budget)."""
    rng = np.random.default_rng(sum(map(ord, name)))
    n_cols = 64                                   # 1,024 keys a slot
    kp, vp = _pool(rng, 200)

    def table(*rows):
        t = np.zeros((len(rows), n_cols), np.int32)
        for i, r in enumerate(rows):
            t[i, :len(r)] = r
        return t

    fresh = iter(range(1, 200))
    take = lambda n: [next(fresh) for _ in range(n)]       # noqa: E731
    if name == "position_0":
        tables, pos = table(take(3), take(1), take(2)), [0, 0, 0]
    elif name == "block_edge":                    # 15 / 16 / 17
        tables, pos = table(take(4), take(4), take(4)), [15, 16, 17]
    elif name == "last_key":                      # 1,023 less the rows
        tables = table(take(64), take(2), take(64))
        pos = [1024 - n_q, 3, 1024 - n_q]
    elif name == "null_table":                    # a free slot between two
        tables, pos = table(take(9), [], take(20)), [130, 0, 300]
    elif name == "shared_prefix":                 # prefix-cache hit
        lead = take(4)
        tables = table(lead + take(3), lead + take(5))
        pos = [70, 100]
    elif name == "frozen_slot":
        # a slot frozen mid-burst wrote its replayed steps to block 0:
        # the null block holds junk, and nobody may see it
        kp[0], vp[0] = 1e3, -1e3
        tables, pos = table(take(6), take(3), []), [40, 33, 0]
    elif name == "odd_slots":                     # S = 5, ragged
        tables = table(take(2), take(30), take(11), take(1), take(17))
        pos = [20, 470, 128, 7, 255]
    elif name == "short_table":                   # 12 columns: 1.5 groups
        n_cols = 12
        tables = table(take(12), take(5), take(12))
        pos = [192 - n_q, 64, 127]
    else:
        raise KeyError(name)
    return kp, vp, tables, np.asarray(pos, np.int32)


CASES = ["position_0", "block_edge", "last_key", "null_table",
         "shared_prefix", "frozen_slot", "odd_slots", "short_table"]


@pytest.mark.parametrize("n_q", [1, 5])
@pytest.mark.parametrize("case", CASES)
def test_kernel_matches_lax_gather(case, n_q):
    kp, vp, tables, pos = _case(case, n_q)
    rng = np.random.default_rng(7)
    q = rng.standard_normal((len(pos), H, n_q, D)).astype(np.float32)
    got = fa._paged_verify_pallas(q, kp, vp, tables, pos, SCALE,
                                  interpret=True)
    ref = fa._xla_paged_verify_decode_attention(q, kp, vp, tables, pos,
                                                SCALE)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-6, rtol=2e-6)
    if n_q == 1:
        ref1 = fa._xla_paged_decode_attention(q[:, :, 0], kp, vp, tables,
                                              pos, SCALE)
        np.testing.assert_allclose(np.asarray(got)[:, :, 0],
                                   np.asarray(ref1), atol=2e-6, rtol=2e-6)


@pytest.mark.parametrize("group_keys", [16, 64, 256])
def test_kernel_group_size_is_free(group_keys, monkeypatch):
    """Any group width gives the same answer (the width is a tuning
    constant, 128 keys as timed on the chip)."""
    monkeypatch.setattr(fa, "_PAGED_GROUP_KEYS", group_keys)
    kp, vp, tables, pos = _case("odd_slots", 1)
    q = np.random.default_rng(1).standard_normal(
        (len(pos), H, 2, D)).astype(np.float32)
    got = fa._paged_verify_pallas(q, kp, vp, tables, pos, SCALE,
                                  interpret=True)
    ref = fa._xla_paged_verify_decode_attention(q, kp, vp, tables, pos,
                                                SCALE)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-6, rtol=2e-6)


def test_work_list_visits_live_groups_only():
    """One step per (slot, group) up to the write head, slot-major; a
    dead column repeats the page its operand read a step earlier."""
    tables = np.zeros((4, 16), np.int32)
    tables[0, :16] = np.arange(1, 17)
    tables[2, :5] = np.arange(20, 25)
    tables[3, :9] = np.arange(30, 39)
    pos = np.asarray([255, 0, 40, 130], np.int32)     # pages 16, 1, 3, 9
    n_steps, slot, group, page = fa._paged_work_list(
        jnp.asarray(tables), jnp.asarray(pos), 1, BS, 4)
    n = int(n_steps)
    assert n == 4 + 1 + 1 + 3
    assert np.asarray(slot)[:n].tolist() == [0] * 4 + [1, 2] + [3] * 3
    assert np.asarray(group)[:n].tolist() == [0, 1, 2, 3, 0, 0, 0, 1, 2]
    page = np.asarray(page).reshape(-1, 4)
    assert page[:4].reshape(-1).tolist() == list(range(1, 17))
    # the free slot reads the null block once, then keeps what was there
    assert page[4].tolist() == [0, 14, 15, 16]
    assert page[5].tolist() == [20, 21, 22, 16]
    assert page[6].tolist() == [30, 31, 32, 33]
    assert page[8].tolist() == [38, 35, 36, 37]
    # the padding repeats the last live step: nothing moves, nothing runs
    assert (np.asarray(slot)[n:] == 3).all()
    assert (page[n:] == page[n - 1]).all()


# -- a chip's share of a grouped-query layer: query heads on ONE KV head,
# -- a window's lower bound, a float32 or bfloat16 pool ----------------------
GQ, GD = 6, 128


def _gqa_case(dtype, n_q, seed=0):
    """4 slots over a pool of one KV head: a free slot, a head inside its
    first window, one far past it, one at the table's end."""
    rng = np.random.default_rng(seed)
    n_cols, n_blocks = 32, 100                     # 512 keys a slot
    kp = jnp.asarray(rng.standard_normal((n_blocks, 1, BS, GD)), dtype)
    vp = jnp.asarray(rng.standard_normal((n_blocks, 1, BS, GD)), dtype)
    tables = np.zeros((4, n_cols), np.int32)
    tables[1, :3] = [7, 8, 9]
    tables[2, :] = np.arange(20, 52)
    tables[3, :] = np.arange(60, 92)
    pos = np.asarray([0, 37, 300, 512 - n_q], np.int32)
    q = jnp.asarray(rng.standard_normal((4, GQ, n_q, GD)), jnp.float32)
    return q, kp, vp, jnp.asarray(tables), jnp.asarray(pos)


@pytest.mark.parametrize("window", [None, 40, 200])
@pytest.mark.parametrize("n_q", [1, 3])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_gqa_kernel_matches_lax_gather(dtype, n_q, window):
    """The MXU kernel against the lax twin: float32 pools agree to
    float32 rounding; on a bfloat16 pool both round the softmax weights
    to bfloat16 before the product with V (the twin by design, the same
    way), so they agree to bfloat16's 2**-8 on O(1) values."""
    q, kp, vp, tables, pos = _gqa_case(jnp.dtype(dtype), n_q)
    got = fa._paged_gqa_pallas(q, kp, vp, tables, pos, SCALE, window,
                               interpret=True)
    ref = fa._xla_paged_verify_decode_attention(q, kp, vp, tables, pos,
                                                SCALE, window)
    tol = 2e-5 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(np.asarray(got)[1:], np.asarray(ref)[1:],
                               atol=tol, rtol=tol)
    # and the twin against plain numpy over the keys the window leaves
    k = np.asarray(kp, np.float32)[np.asarray(tables)].reshape(4, -1, GD)
    v = np.asarray(vp, np.float32)[np.asarray(tables)].reshape(4, -1, GD)
    for s in (1, 2, 3):
        for j in range(n_q):
            head = int(pos[s]) + j
            lo = 0 if window is None else max(0, head - window + 1)
            sc = np.asarray(q)[s, :, j] @ k[s, lo:head + 1].T * SCALE
            w = np.exp(sc - sc.max(-1, keepdims=True))
            want = (w / w.sum(-1, keepdims=True)) @ v[s, lo:head + 1]
            np.testing.assert_allclose(np.asarray(ref)[s, :, j], want,
                                       atol=3e-2 if dtype == "bfloat16"
                                       else 1e-4)


def _gqa_heads_case(dtype, kv_heads, group, n_q, seed=0):
    """:func:`_gqa_case` over ``kv_heads`` KV heads with ``group`` query
    heads each."""
    rng = np.random.default_rng(seed)
    n_cols, n_blocks = 32, 100                     # 512 keys a slot
    kp = jnp.asarray(rng.standard_normal((n_blocks, kv_heads, BS, GD)), dtype)
    vp = jnp.asarray(rng.standard_normal((n_blocks, kv_heads, BS, GD)), dtype)
    tables = np.zeros((4, n_cols), np.int32)
    tables[1, :3] = [7, 8, 9]
    tables[2, :] = np.arange(20, 52)
    tables[3, :] = np.arange(60, 92)
    pos = np.asarray([0, 37, 300, 512 - n_q], np.int32)
    q = jnp.asarray(rng.standard_normal((4, kv_heads * group, n_q, GD)),
                    jnp.float32)
    return q, kp, vp, jnp.asarray(tables), jnp.asarray(pos)


@pytest.mark.parametrize("window", [None, 200])
@pytest.mark.parametrize("group", [1, 7])
@pytest.mark.parametrize("kv_heads", [1, 2, 4])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_gqa_kernel_over_kv_heads_matches_lax_gather(dtype, kv_heads, group,
                                                     window):
    """The grouped kernel's KV-head axis: ``group`` query heads on each of
    ``kv_heads`` KV heads, a page all the heads of a block, against the
    gather (single-query decode and a 3-wide verify block), tolerances as
    in :func:`test_gqa_kernel_matches_lax_gather`; query head n must read
    KV head n // group (checked against plain numpy on one slot)."""
    for n_q in (1, 3):
        q, kp, vp, tables, pos = _gqa_heads_case(jnp.dtype(dtype), kv_heads,
                                                 group, n_q)
        got = fa._paged_gqa_pallas(q, kp, vp, tables, pos, SCALE, window,
                                   interpret=True)
        ref = fa._xla_paged_verify_decode_attention(q, kp, vp, tables, pos,
                                                    SCALE, window)
        tol = 2e-5 if dtype == "float32" else 2e-2
        np.testing.assert_allclose(np.asarray(got)[1:], np.asarray(ref)[1:],
                                   atol=tol, rtol=tol)
    s, j = 2, 0                                    # slot 2, its first row
    k = np.asarray(kp, np.float32)[np.asarray(tables)[s]]   # (cols, H, bs, D)
    v = np.asarray(vp, np.float32)[np.asarray(tables)[s]]
    head = int(pos[s]) + j
    lo = 0 if window is None else max(0, head - window + 1)
    for n in range(kv_heads * group):
        kh = k[:, n // group].reshape(-1, GD)[lo:head + 1]
        vh = v[:, n // group].reshape(-1, GD)[lo:head + 1]
        sc = np.asarray(q)[s, n, j] @ kh.T * SCALE
        w = np.exp(sc - sc.max())
        np.testing.assert_allclose(
            np.asarray(got)[s, n, j], (w / w.sum()) @ vh,
            atol=3e-2 if dtype == "bfloat16" else 1e-4)


def test_work_list_starts_at_the_window(monkeypatch):
    """With a window a slot's first step is the group that holds the
    first key its first row reads; groups before it are not visited."""
    tables = np.zeros((3, 16), np.int32)
    tables[0, :16] = np.arange(1, 17)
    tables[2, :16] = np.arange(20, 36)
    pos = np.asarray([255, 0, 130], np.int32)
    n_steps, slot, group, _ = fa._paged_work_list(
        jnp.asarray(tables), jnp.asarray(pos), 1, BS, 4, window=100)
    n = int(n_steps)
    # slot 0: keys 156..255 -> groups 2, 3; slot 2: keys 31..130 -> 0..2
    assert np.asarray(slot)[:n].tolist() == [0, 0, 1, 2, 2, 2]
    assert np.asarray(group)[:n].tolist() == [2, 3, 0, 0, 1, 2]
    full = fa._paged_work_list(jnp.asarray(tables), jnp.asarray(pos), 1,
                               BS, 4)
    assert int(full[0]) == 4 + 1 + 3


def test_gqa_selection(monkeypatch):
    """Grouped heads take a kernel where a page is whole tiles (128
    features, 8 positions of float32 or 16 of bfloat16) and the query
    heads divide over the KV heads; everything else takes the gather."""
    monkeypatch.setattr(fa, "_platform_of", lambda x: "tpu")
    q = jnp.zeros((2, 6, 128))
    pool = lambda h, bs, d, dt: jnp.zeros((4, h, bs, d), dt)   # noqa: E731
    impl = fa.paged_attention_impl
    assert impl(q, pool(1, 16, 128, jnp.bfloat16), 6, 4096) == "pallas"
    assert impl(q, pool(1, 16, 128, jnp.bfloat16), 6) == "pallas"
    assert impl(q, pool(1, 8, 128, jnp.float32), 6) == "pallas"
    assert impl(q, pool(1, 8, 128, jnp.bfloat16), 6) == "lax_gather"
    # more than one KV head: the same kernel, a page all heads of a block
    assert impl(q, pool(2, 16, 128, jnp.bfloat16), 6) == "pallas"
    assert impl(q, pool(4, 16, 128, jnp.bfloat16), 28, 4096) == "pallas"
    assert impl(q, pool(4, 16, 128, jnp.bfloat16), 28) == "pallas"
    assert impl(q, pool(4, 16, 128, jnp.bfloat16), 4, 4096) == "pallas"
    assert impl(q, pool(4, 16, 128, jnp.bfloat16), 6) == "lax_gather"
    assert impl(q, pool(4, 16, 64, jnp.bfloat16), 28) == "lax_gather"
    assert impl(q, jnp.zeros((4, 16, 4, 128), jnp.bfloat16), 28,
                position_major=True) == "lax_gather"
    # and ONE KV head selects what it selected
    assert fa._paged_kernel_kind(q, pool(1, 16, 128, jnp.bfloat16), 6,
                                 4096) == "gqa"
    assert fa._paged_kernel_kind(q, pool(16, 16, 64, jnp.float32), 16,
                                 None) == "mha"
    assert impl(q, pool(1, 16, 64, jnp.float32), 6) == "lax_gather"
    assert impl(q, pool(16, 16, 64, jnp.float32)) == "pallas"
    assert impl(q, pool(16, 16, 64, jnp.float32), 16, 128) == "lax_gather"


def test_selection_is_by_platform_and_shape_never_by_flag(monkeypatch):
    monkeypatch.delenv("MXNET_FA_DECODE_FORCE_PALLAS", raising=False)
    q = jnp.zeros((2, H, D), jnp.float32)
    pool = jnp.zeros((4, H, BS, D), jnp.float32)
    monkeypatch.setenv("MXNET_USE_FUSION", "1")     # no longer read here
    assert fa.paged_attention_impl(q, pool) == "lax_gather"
    monkeypatch.setattr(fa, "_platform_of", lambda x: "tpu")
    monkeypatch.delenv("MXNET_USE_FUSION")
    assert fa.paged_attention_impl(q, pool) == "pallas"
    assert fa.paged_attention_impl(
        q, jnp.zeros((4, H, 12, D), jnp.float32)) == "lax_gather"
    assert fa.paged_attention_impl(
        q, pool.astype(jnp.bfloat16)) == "lax_gather"
    src = open(fa.__file__).read()
    paged = src[src.index("def paged_attention_impl"):]
    assert "MXNET_USE_FUSION" not in paged


def _gpt():
    mx.random.seed(3)
    net = GPTModel(vocab_size=50, units=32, hidden_size=64, num_layers=2,
                   num_heads=2, max_length=64, dropout=0.0)
    net.initialize(init=mx.init.Normal(0.6))
    net(mx.nd.array(np.zeros((1, 2), np.int32)))
    return net


@pytest.mark.parametrize("force,want", [(False, "lax_gather"),
                                        (True, "pallas")])
def test_inventory_reports_what_the_trace_picked(force, want, monkeypatch):
    if force:
        monkeypatch.setenv("MXNET_FA_DECODE_FORCE_PALLAS", "1")
    else:
        monkeypatch.delenv("MXNET_FA_DECODE_FORCE_PALLAS", raising=False)
    eng = GenerationEngine(_gpt(), name="inv%d" % force, max_slots=2,
                           max_len=64, scan_steps=2)
    assert eng.program_inventory()["paged_attention"] is None
    out = eng.generate([3, 7, 11], max_new_tokens=6)
    assert eng.program_inventory()["paged_attention"] == want
    assert out == greedy_reference(eng.block, [3, 7, 11], 6)
    # one query head a KV head: not the kernel that fetches by runs, so
    # no mxtpu_paged_groups_total
    assert not [k for k in eng.decode_counters() if k.startswith("paged_g")]


def test_the_gpt2_burst_program_traces_what_it_traced(monkeypatch):
    """The run flags are the grouped kernel's alone: the burst program of
    a model with one query head a KV head, traced where a TPU would run it
    (the GPT-2 kernel in every layer, its work list of four arrays), is
    the program it was before the grouped kernel fetched by runs — its
    jaxpr, the kernel's body included, letter for letter (the digest of
    commit 7b357d3's; the lowered StableHLO agrees too, but carries the
    kernel as bytes that hold ``flash_attention.py``'s line numbers).  A
    change that means to alter that program restates the digest."""
    import hashlib
    monkeypatch.setattr(fa, "_platform_of", lambda x: "tpu")
    eng = GenerationEngine(_gpt(), name="digest", max_slots=2, max_len=64,
                           scan_steps=2)
    sds = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)     # noqa: E731
    args = jax.tree.map(sds, (eng._cache + eng._recur, eng._slot_state(),
                              *eng._param_fn()))
    text = str(eng._decode_burst_jit.trace(*args).jaxpr)
    assert text.count("pallas_call") == 1           # the layers share a jit
    assert hashlib.sha256(text.encode()).hexdigest() == (
        "f4d8478cd2013ac5f375ba7748a8ac68d3ff705206fd5bd3812187efca9bfb4c")


# --- compiled for a v5e that is not attached --------------------------------
# (on-chip-measurement guide, section 2: the topology is described inside a
# fixture, in this one file, so only the worker that runs it loads libtpu)

@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                        # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_compile_cache():
    """A described-device compile can be written to the persistent cache
    but not read back: keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    cc.reset_cache()


def _compile(fn, *args):
    return jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).compile()


@pytest.mark.parametrize("n_q", [1, 5])
def test_kernel_compiles_for_v5e_at_the_serve_cell_shapes(
        n_q, one_chip, no_compile_cache):
    """S 36, H 16, bs 16, D 64, 64 table entries, 1,217 blocks, f32: with
    the pool as the decode programs keep it (H and D minor-most) the call
    needs no copy of it."""
    from jax.experimental.layout import Format, Layout
    S, Hc, Dc, bs, n_cols, N = 36, 16, 64, 16, 64, 1217

    def sds(shape, dtype=jnp.float32, order=None):
        order = order or tuple(range(len(shape)))
        return jax.ShapeDtypeStruct(shape, dtype, sharding=Format(
            Layout(major_to_minor=order), one_chip))

    pool = sds((N, Hc, bs, Dc), order=(0, 2, 1, 3))
    compiled = _compile(
        lambda q, k, v, t, p: fa._paged_verify_pallas(q, k, v, t, p,
                                                      0.125, False),
        sds((S, Hc, n_q, Dc)), pool, pool, sds((S, n_cols), jnp.int32),
        sds((S,), jnp.int32))
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert not re.search(r"= f32\[1217,16,16,64\]\{[^}]*\} copy\(", text)


@pytest.mark.parametrize("window", [None, 4096])
def test_gqa_kernel_compiles_for_v5e_at_the_agent_cell_shapes(
        window, one_chip, no_compile_cache):
    """S 64, 6 query heads on 1 KV head of 128, bs 16, 512 table entries,
    a bfloat16 pool of 32,769 blocks: the kernel takes the pool as it
    rests, without a copy."""
    S, n_cols, N = 64, 512, 32769

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = sds((N, 1, 16, 128), jnp.bfloat16)
    compiled = _compile(
        lambda q, k, v, t, p: fa._paged_gqa_pallas(
            q, k, v, t, p, 0.088, window, False),
        sds((S, 6, 1, 128), jnp.bfloat16), pool, pool,
        sds((S, n_cols), jnp.int32), sds((S,), jnp.int32))
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert not re.search(r"= bf16\[32769,[^\]]*\]\{[^}]*\} copy\(", text)


@pytest.mark.parametrize("cell,T,k,count,d,f,act", [
    ("docqa", 32, 6, 64, 2560, 768, "relu"),
    ("corpusqa", 64, 10, 256, 2048, 512, "silu"),
    ("agent", 64, 4, 32, 3072, 3072, "silu")])
def test_experts_kernel_compiles_for_v5e_at_the_cell_shapes(
        cell, T, k, count, d, f, act, one_chip, no_compile_cache,
        monkeypatch):
    """The grouped expert product of a decode step at the three expert
    cells' widths (bfloat16): ``held_experts_ffn`` takes the kernel where a
    TPU would run it, ONE call and no loop, the stacked matrices read where
    they rest (no copy of one), two buffers of its blocks inside the
    kernel's VMEM limit."""
    from incubator_mxnet_tpu.models import moe
    monkeypatch.setattr(fa, "_platform_of", lambda x: "tpu")

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = _compile(
        lambda x, idx, w, g, u, dn: moe.held_experts_ffn(
            x, idx, w, (0, count), g, u, dn, act=act),
        sds((T, d)), sds((T, k), jnp.int32), sds((T, k), jnp.float32),
        sds((count, d, f)), sds((count, d, f)), sds((count, f, d)))
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1 and " while(" not in text
    assert not re.search(rf"= bf16\[{count},[^\]]*\]\{{[^}}]*\}} copy\(",
                         text)


@pytest.mark.parametrize("cell,T,k,count,d,f,act", [
    ("docqa", 6144, 6, 64, 2560, 768, "relu"),
    ("corpusqa", 8192, 10, 256, 2048, 512, "silu"),
    ("agent", 512, 4, 32, 3072, 3072, "silu"),
    ("repoagent", 1024, 8, 32, 5120, 1536, "silu")])
def test_sorted_experts_kernel_compiles_for_v5e_at_the_prefill_shapes(
        cell, T, k, count, d, f, act, one_chip, no_compile_cache,
        monkeypatch):
    """A prefill's grouped expert product at the four expert cells' widths
    (bfloat16, a bucket each) through the sorted-form kernel, which the
    rule takes for all four: ONE call and no loop under it, no copy of a
    stacked matrix, its row tile and blocks inside the kernel's VMEM limit
    (one block of the hidden width, six and four)."""
    from incubator_mxnet_tpu.models import moe
    ge = importlib.import_module(
        "incubator_mxnet_tpu.kernels.grouped_experts")
    monkeypatch.setattr(fa, "_platform_of", lambda x: "tpu")

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    x = sds((T, d))
    assert moe.held_experts_impl(x, sds((count, d, f)), T * k) \
        == "pallas_sorted"
    compiled = _compile(
        lambda x, idx, w, g, u, dn, live: ge.held_experts_sorted(
            x, idx, w, (0, count), g, u, dn, live, act),
        x, sds((T, k), jnp.int32), sds((T, k), jnp.float32),
        sds((count, d, f)), sds((count, d, f)), sds((count, f, d)),
        sds((T,), jnp.bool_))
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1 and " while(" not in text
    assert "held_experts_sorted" in text
    assert not re.search(rf"= bf16\[{count},[^\]]*\]\{{[^}}]*\}} copy\(",
                         text)


@pytest.mark.parametrize("window", [None, 4096])
def test_gqa_kernel_compiles_for_v5e_at_the_docqa_cell_shapes(
        window, one_chip, no_compile_cache):
    """S 32, 28 query heads on 4 KV heads of 128, bs 16, 512 table
    entries, a bfloat16 pool of 16,385 blocks: the kernel takes the pool
    as it rests (``[N, H * bs, D]`` is the same bytes), without a copy."""
    S, n_cols, N = 32, 512, 16385

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = sds((N, 4, 16, 128), jnp.bfloat16)
    compiled = _compile(
        lambda q, k, v, t, p: fa._paged_gqa_pallas(
            q, k, v, t, p, 0.088, window, False),
        sds((S, 28, 1, 128), jnp.bfloat16), pool, pool,
        sds((S, n_cols), jnp.int32), sds((S,), jnp.int32))
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert not re.search(r"= bf16\[16385,[^\]]*\]\{[^}]*\} copy\(", text)


@pytest.mark.parametrize("kind,heads,features,r_kv,window", [
    ("sliding", 8, 1088, 1024, 513), ("full", 16, 576, 512, None)])
def test_latent_kernel_compiles_for_v5e_at_the_repoagent_cell_shapes(
        kind, heads, features, r_kv, window, one_chip, no_compile_cache):
    """S 64, the chip's share of a latent layer's heads over ONE row pool
    of 24,577 blocks stored on whole lanes (1,088 -> 1,152, 576 -> 640),
    1,696 table entries: the kernel takes the pool as it rests (``[N * bs,
    Fp]`` is the same bytes), without a copy, its two page buffers inside
    the VMEM limit.  (The full layers' chosen rows do not come through it
    in the cell; its shape must compile all the same.)"""
    import importlib
    la = importlib.import_module(
        "incubator_mxnet_tpu.kernels.latent_attention")
    S, n_cols, N = 64, 1696, 24577
    Fp = -(-features // 128) * 128

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = _compile(
        lambda q, pool, t, p: la._paged_latent_pallas(
            q, pool, t, p, r_kv, 0.0625, window, False),
        sds((S, heads, features), jnp.bfloat16),
        sds((N, 16, Fp), jnp.bfloat16), sds((S, n_cols), jnp.int32),
        sds((S,), jnp.int32))
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert not re.search(r"= bf16\[24577,[^\]]*\]\{[^}]*\} copy\(", text)


def test_index_kernel_compiles_for_v5e_at_the_repoagent_cell_shapes(
        one_chip, no_compile_cache):
    """S 64, 64 index heads of 128 over the index-key pool of 24,577
    blocks, 1,696 table entries, 4,096 keys a step: the pool read where it
    rests, the (slots, heads, keys) scores nowhere in the program."""
    import importlib
    la = importlib.import_module(
        "incubator_mxnet_tpu.kernels.latent_attention")
    S, n_cols, N = 64, 1696, 24577

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = _compile(
        lambda q, w, pool, t, p: la._paged_index_pallas(q, w, pool, t, p,
                                                        False),
        sds((S, 64, 128), jnp.bfloat16), sds((S, 64), jnp.float32),
        sds((N, 16, 128), jnp.bfloat16), sds((S, n_cols), jnp.int32),
        sds((S,), jnp.int32))
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert not re.search(r"= bf16\[24577,[^\]]*\]\{[^}]*\} copy\(", text)
    assert "f32[64,64,27136]" not in text


def test_prompt_index_kernel_compiles_for_v5e_at_the_repoagent_cell_shapes(
        one_chip, no_compile_cache):
    """A hit's suffix of 1,024 queries, 64 index heads of 128, against a
    slot's 27,136 index keys: one kernel, eight queries x 2,048 keys a
    step, its (512, 2048) float32 tile inside the VMEM limit."""
    import importlib
    la = importlib.import_module(
        "incubator_mxnet_tpu.kernels.latent_attention")

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = _compile(
        lambda q, w, k: la._index_scores_pallas(q, w, k, False),
        sds((1024, 64, 128), jnp.bfloat16), sds((1024, 64), jnp.float32),
        sds((27136, 128), jnp.bfloat16))
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert "f32[1024,64,27136]" not in text


def _position_major(pages, lanes=128):
    """A stated pool (N, H, bs, D) as ``KVLayout.pool_shape`` stores it
    position-major: (N, bs, H, Dp), zeros on the lanes past D."""
    pad = -pages.shape[-1] % lanes
    return jnp.pad(jnp.swapaxes(pages, 1, 2), ((0, 0),) * 3 + ((0, pad),))


@pytest.mark.parametrize("force", [False, True], ids=["lax", "kernel"])
@pytest.mark.parametrize("entry", ["decode", "verify", "prefix"])
def test_position_major_pool_reads_as_the_stated_one(entry, force,
                                                     monkeypatch):
    """The three paged entry points over a pool stored position-major
    give what they give over the stated pool: to the bit through the
    gather, which only moves the same values, and to rounding through the
    kernel (interpreted), whose padded features add zeros in another
    order of summation."""
    if force:
        monkeypatch.setenv("MXNET_FA_DECODE_FORCE_PALLAS", "1")
    else:
        monkeypatch.delenv("MXNET_FA_DECODE_FORCE_PALLAS", raising=False)
    rng = np.random.default_rng(11)
    S, nb = 3, 4
    kp = jnp.asarray(rng.standard_normal((1 + S * nb, H, BS, D)),
                     jnp.float32)
    vp = jnp.asarray(rng.standard_normal(kp.shape), jnp.float32)
    tables = jnp.asarray(1 + rng.permutation(S * nb).reshape(S, nb),
                         jnp.int32)
    pos = jnp.asarray([0, BS + 3, nb * BS - 6], jnp.int32)
    pools = {False: (kp, vp), True: (_position_major(kp),
                                     _position_major(vp))}
    q = jnp.asarray(rng.standard_normal(
        {"decode": (S, H, D), "verify": (S, H, 5, D),
         "prefix": (1, H, 6, D)}[entry]), jnp.float32)
    got = {}
    for pm, (k, v) in pools.items():
        if entry == "decode":
            got[pm] = fa.paged_decode_attention(
                q, k, v, tables, pos, position_major=pm)
        elif entry == "verify":
            got[pm] = fa.paged_verify_decode_attention(
                q, k, v, tables, pos, position_major=pm)
        else:
            got[pm] = fa.paged_prefix_attention(
                q, k, v, tables[1], jnp.int32(BS), position_major=pm)
    assert fa.paged_attention_impl(
        q, pools[True][0], position_major=True) \
        == ("pallas" if force else "lax_gather")
    if force and entry != "prefix":     # a sum over 128 lanes, not over 8
        np.testing.assert_allclose(np.asarray(got[True]),
                                   np.asarray(got[False]), rtol=0, atol=2e-6)
    else:
        np.testing.assert_array_equal(np.asarray(got[True]),
                                      np.asarray(got[False]))


@pytest.mark.parametrize("heads,head_dim,dtype,want", [
    (16, 64, "float32", ((1217, 16, 16, 128), True)),  # gpt2-medium-serve
    (16, 64, "bfloat16", ((1217, 16, 16, 128), True)),
    (12, 64, "float32", ((1217, 12, 16, 64), False)),  # chip_smoke's gpt2
    (1, 128, "bfloat16", ((1217, 1, 16, 128), False)),  # the agent cell's
    (8, 128, "float32", ((1217, 8, 16, 128), False)),
    (4, 128, "bfloat16", ((1217, 4, 16, 128), False)),  # the docqa cell's
])
def test_pool_is_stored_so_that_it_rests_as_the_programs_keep_it(
        heads, head_dim, dtype, want, topo):
    """The rule, read off platform, shape and type: on a v5e position-major
    with the features on whole lanes where the stated shape would rest in
    another order than row-major AND the position-major one does rest
    row-major; the stated shape where it rests row-major already (a head
    of 128 features), where the device would not rest the other one
    row-major either (12 heads: it puts the 16 positions on the sublanes),
    and on the CPU always."""
    from incubator_mxnet_tpu.serving.kvcache import KVLayout
    lay = KVLayout(2, heads, head_dim, dtype, (None, None), 1024)
    assert lay.pool_shape(1217, 16, topo.devices[0]) == want
    assert lay.pool_shape(1217, 16, jax.devices("cpu")[0]) \
        == ((1217, heads, 16, head_dim), False)


@pytest.fixture(scope="module")
def serve_cell_programs(topo, one_chip):
    """The five paged programs of a GPT at GPT-2-medium's widths (2
    layers), each with its operands as the serve cell dispatches them (S
    36, N 1,217) on the described chip, the pools in the shape the rule
    gives for that chip: ``(engine, {name: (the engine's jit, operands)})``."""
    mx.random.seed(0)
    net = GPTModel(vocab_size=512, units=1024, hidden_size=4096,
                   num_layers=2, num_heads=16, max_length=1024, dropout=0.0)
    net.initialize(mx.init.Zero())
    with mx.autograd.pause():
        net(mx.nd.array(np.zeros((1, 2), np.int32)))
    S, N = 36, 1217
    eng = GenerationEngine(net, name="aot", max_slots=S, max_len=1024,
                           prefill_buckets=[256], paged=True, block_size=16,
                           num_blocks=65, scan_steps=8)
    # the engine lives on the CPU; its programs are traced for the chip
    eng._pool_shape, eng._position_major = eng.layout.pool_shape(
        N, eng.block_size, topo.devices[0])
    assert eng._position_major

    def sds(x, shape=None):
        x = jnp.asarray(x) if not hasattr(x, "dtype") else x
        return jax.ShapeDtypeStruct(shape or x.shape, x.dtype,
                                    sharding=one_chip)

    i32 = lambda *shape: jax.ShapeDtypeStruct(                # noqa: E731
        shape, jnp.int32, sharding=one_chip)
    cache = tuple(sds(c, eng._pool_shape) for c in eng._cache)
    params, aux = eng._param_fn()
    tail = (tuple(sds(p) for p in params), tuple(sds(a) for a in aux))
    # the per-slot operands are the engine's slot state, as it builds it
    state = jax.tree.map(sds, eng._slot_state())
    programs = {
        "decode_burst": (eng._decode_burst_jit, ()),
        "decode": (eng._decode_jit, ()),
        "verify": (eng._verify_jit, (i32(S, 5), i32(S))),
        "prefill": (eng._prefill_jit, (i32(1, 256), i32(2))),
        "prefill_ext": (eng._prefill_ext_jit, (i32(1, 256), i32(3))),
    }
    return eng, {name: (jitted, (cache, state) + operands + tail)
                 for name, (jitted, operands) in programs.items()}


@pytest.mark.parametrize("program", ["decode_burst", "decode", "verify",
                                     "prefill", "prefill_ext"])
def test_paged_programs_keep_the_pool_in_place_on_v5e(
        program, serve_cell_programs, no_compile_cache, monkeypatch):
    """Each paged program at the serve cell's shapes, the engine's own
    jit compiled for the v5e over pools stored as the rule has them there
    (``f32[1217, 16, 16, 128]``, position-major): no pool is copied
    anywhere in it, the ENTRY computation included — the pools come in and
    go out in the device's default layout, row-major, and that is the
    layout the program keeps them in.  The decode programs hold the
    kernel and no dense gather."""
    monkeypatch.setattr(fa, "_platform_of", lambda x: "tpu")
    eng, programs = serve_cell_programs
    jitted, args = programs[program]
    compiled = jitted.trace(*args).lower(
        lowering_platforms=("tpu",)).compile()
    text = compiled.as_text()
    assert not re.search(r"= f32\[1217,16,16,128\]\{[^}]*\} copy\(", text)
    assert "f32[1217,16,16,64]" not in text             # the stated shape
    pools_in, pools_out = compiled.input_formats[0][0], \
        compiled.output_formats[0]
    assert {f.layout.major_to_minor for f in (*pools_in, *pools_out)} \
        == {(0, 1, 2, 3)}
    if program == "decode_burst":
        assert eng.program_inventory()["paged_attention"] == "pallas"
        assert text.count('custom_call_target="tpu_custom_call"') == 2
    if program in ("decode_burst", "decode", "verify"):
        assert "f32[2304,16,16," not in text            # the dense gather


def test_agent_cell_pool_stays_as_stated_and_is_not_copied_on_v5e(
        topo, one_chip, no_compile_cache, monkeypatch):
    """The AFMoE cell's pool, ``bf16[32769, 1, 16, 128]``, rests row-major
    as stated: the rule leaves it so, its programs are the ones they
    were, and the burst program (2 of the configuration's layers, a
    sliding and a full one, 4 experts held) takes the pools as they rest:
    no copy of one in it."""
    import json
    import os
    import sys
    chip = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "chip")
    if chip not in sys.path:
        sys.path.insert(0, chip)
    from programs import afmoe_serve
    from reference import afmoe as ref
    monkeypatch.setattr(fa, "_platform_of", lambda x: "tpu")
    with open(os.path.join(chip, "configs",
                           "trinity-large-serve-ep8.json")) as f:
        cfg = json.load(f)
    dep = cfg["deployment"]
    cfg.update(num_hidden_layers=2, num_experts=4, vocab_size=512,
               layer_types=["sliding_attention", "full_attention"])
    dt, d, V = ref.param_dtype(cfg), cfg["hidden_size"], cfg["vocab_size"]
    shape = lambda s: jax.ShapeDtypeStruct(s, dt)             # noqa: E731
    net = afmoe_serve.build_net(cfg)
    net.adopt_arrays({
        "embed_tokens": shape((V, d)), "norm": shape((d,)),
        "lm_head": shape((d, V)),
        "layers": [{n: shape(s) for n, s in ref.layer_shapes(cfg, i).items()}
                   for i in range(cfg["num_hidden_layers"])]})
    S, N = dep["max_slots"], dep["num_blocks"]
    eng = GenerationEngine(net, name="aot-agent", max_slots=S,
                           max_len=dep["max_len"], prefill_buckets=[512],
                           paged=True, block_size=dep["block_size"],
                           num_blocks=1 + dep["max_len"] // dep["block_size"],
                           scan_steps=dep["scan_steps"])
    pool, position_major = eng.layout.pool_shape(N, eng.block_size,
                                                 topo.devices[0])
    assert (pool, position_major, eng.layout.dtype) \
        == ((32769, 1, 16, 128), False, "bfloat16")
    assert not eng._position_major

    def sds(x, shape=None):
        x = jnp.asarray(x) if not hasattr(x, "dtype") else x
        return jax.ShapeDtypeStruct(shape or x.shape, x.dtype,
                                    sharding=one_chip)

    i32 = lambda *shape: jax.ShapeDtypeStruct(                # noqa: E731
        shape, jnp.int32, sharding=one_chip)
    params, aux = eng._param_fn()
    args = (tuple(sds(c, pool) for c in eng._cache),
            jax.tree.map(sds, eng._slot_state()),
            tuple(sds(p) for p in params), tuple(sds(a) for a in aux))
    text = eng._decode_burst_jit.trace(*args).lower(
        lowering_platforms=("tpu",)).compile().as_text()
    assert eng.program_inventory()["paged_attention"] == "pallas"
    assert not re.search(r"= bf16\[32769,[^\]]*\]\{[^}]*\} copy\(", text)


@pytest.mark.parametrize("program", ["decode_burst", "decode"])
def test_docqa_cell_pool_stays_as_stated_and_is_not_copied_on_v5e(
        program, topo, one_chip, no_compile_cache, monkeypatch):
    """The SmallThinker cell's pool, ``bf16[16385, 4, 16, 128]``, rests
    row-major as stated and the rule leaves it so.  Its decode and burst
    programs (2 of the configuration's layers, a full and a windowed one,
    8 experts held) must keep it in that order: the rows are written
    through the ``[N, H * bs, D]`` view the grouped kernel reads, so no
    pool is copied on the way in, for the kernel or on the way out (written
    as ``[N, H, bs, D]`` the compiler kept positions before heads inside
    the program: 48 whole-pool copies a burst, PERF.md section 6, PR 31),
    and both layers take the kernel."""
    import json
    import os
    import sys
    chip = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "chip")
    if chip not in sys.path:
        sys.path.insert(0, chip)
    from programs import smallthinker_serve
    from reference import smallthinker as ref
    monkeypatch.setattr(fa, "_platform_of", lambda x: "tpu")
    with open(os.path.join(chip, "configs",
                           "smallthinker-21b-serve-l8.json")) as f:
        cfg = json.load(f)
    dep = cfg["deployment"]
    cfg.update(num_hidden_layers=2, moe_num_primary_experts=8,
               moe_num_primary_experts_published=8, vocab_size=512,
               rope_layout=[0, 1], sliding_window_layout=[0, 1])
    dt, d, V = jnp.dtype(dep["param_dtype"]), cfg["hidden_size"], 512
    shape = lambda s: jax.ShapeDtypeStruct(s, dt)             # noqa: E731
    net = smallthinker_serve.build_net(cfg)
    net.adopt_arrays({
        "embed_tokens": shape((V, d)), "norm": shape((d,)),
        "lm_head": shape((d, V)),
        "layers": [{n: shape(s) for n, s in ref.layer_shapes(cfg).items()}
                   for _ in range(2)]})
    S, N = dep["max_slots"], dep["num_blocks"]
    eng = GenerationEngine(net, name="aot-docqa", max_slots=S,
                           max_len=dep["max_len"], prefill_buckets=[512],
                           paged=True, block_size=dep["block_size"],
                           num_blocks=1 + dep["max_len"] // dep["block_size"],
                           scan_steps=dep["scan_steps"])
    pool, position_major = eng.layout.pool_shape(N, eng.block_size,
                                                 topo.devices[0])
    assert (pool, position_major, eng.layout.dtype) \
        == ((16385, 4, 16, 128), False, "bfloat16")
    assert eng.layout.windows == (None, 4096)

    def sds(x, shape=None):
        x = jnp.asarray(x) if not hasattr(x, "dtype") else x
        return jax.ShapeDtypeStruct(shape or x.shape, x.dtype,
                                    sharding=one_chip)

    params, aux = eng._param_fn()
    args = (tuple(sds(c, pool) for c in eng._cache),
            jax.tree.map(sds, eng._slot_state()),
            tuple(sds(p) for p in params), tuple(sds(a) for a in aux))
    jitted = {"decode_burst": eng._decode_burst_jit,
              "decode": eng._decode_jit}[program]
    compiled = jitted.trace(*args).lower(
        lowering_platforms=("tpu",)).compile()
    text = compiled.as_text()
    assert eng.program_inventory()["paged_attention"] == "pallas"
    # two layers: an attention kernel and (since PR 36) an expert kernel each
    assert text.count('custom_call_target="tpu_custom_call"') == 4
    assert len(re.findall(r"%_paged_gqa_pallas[.\d]* = ", text)) == 2
    assert len(re.findall(r"%held_experts[.\d]* = ", text)) == 2
    assert not re.search(r"= bf16\[16385,[^\]]*\]\{[^}]*\} copy\(", text)
    pools_in, pools_out = compiled.input_formats[0][0], \
        compiled.output_formats[0]
    assert {f.layout.major_to_minor for f in (*pools_in, *pools_out)} \
        == {(0, 1, 2, 3)}


# -- heads of 256 features (a gated attention layer beside recurrent ones): two
# -- lane tiles a key, 8 query heads on the chip's one KV head ----------------
def _wide_case(dtype, kv_heads, n_q, D=256, group=8, seed=0):
    rng = np.random.default_rng(seed)
    n_cols, n_blocks = 32, 100                     # 512 keys a slot
    kp = jnp.asarray(rng.standard_normal((n_blocks, kv_heads, BS, D)), dtype)
    vp = jnp.asarray(rng.standard_normal((n_blocks, kv_heads, BS, D)), dtype)
    tables = np.zeros((4, n_cols), np.int32)
    tables[1, :3] = [7, 8, 9]
    tables[2, :] = np.arange(20, 52)
    tables[3, :] = np.arange(60, 92)
    pos = np.asarray([0, 37, 300, 512 - n_q], np.int32)
    q = jnp.asarray(rng.standard_normal((4, kv_heads * group, n_q, D)),
                    jnp.float32)
    return q, kp, vp, jnp.asarray(tables), jnp.asarray(pos)


@pytest.mark.parametrize("n_q", [1, 3])
@pytest.mark.parametrize("kv_heads", [1, 2])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_gqa_kernel_on_heads_of_256_matches_lax_gather(dtype, kv_heads, n_q):
    """D = 256 at scale 256^-1/2: the kernel against the lax twin, and the
    entry point takes the kernel when forced."""
    q, kp, vp, tables, pos = _wide_case(jnp.dtype(dtype), kv_heads, n_q)
    scale = 256 ** -0.5
    got = fa._paged_gqa_pallas(q, kp, vp, tables, pos, scale, None,
                               interpret=True)
    ref = fa._xla_paged_verify_decode_attention(q, kp, vp, tables, pos,
                                                scale, None)
    tol = 2e-5 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(np.asarray(got)[1:], np.asarray(ref)[1:],
                               atol=tol, rtol=tol)
    assert fa._paged_kernel_kind(q, kp, q.shape[1], None) is None


def test_heads_of_256_take_the_grouped_kernel_when_forced(monkeypatch):
    q, kp, vp, tables, pos = _wide_case(jnp.bfloat16, 1, 1)
    monkeypatch.setenv("MXNET_FA_DECODE_FORCE_PALLAS", "1")
    assert fa._paged_kernel_kind(q, kp, 8, None) == "gqa"
    assert fa.paged_attention_impl(tables, kp, 8, None) == "pallas"
    got = fa.paged_decode_attention(q[:, :, 0], kp, vp, tables, pos)
    monkeypatch.delenv("MXNET_FA_DECODE_FORCE_PALLAS")
    ref = fa.paged_decode_attention(q[:, :, 0], kp, vp, tables, pos)
    np.testing.assert_allclose(np.asarray(got)[1:], np.asarray(ref)[1:],
                               atol=2e-2, rtol=2e-2)


def test_gqa_kernel_compiles_for_v5e_at_the_corpusqa_cell_shapes(
        one_chip, no_compile_cache):
    """S 64, 8 query heads on 1 KV head of 256, bs 16, 1,088 table entries,
    a bfloat16 pool of 69,633 blocks: the kernel takes the pool as it
    rests, without a copy."""
    S, n_cols, N = 64, 1088, 69633

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = sds((N, 1, 16, 256), jnp.bfloat16)
    compiled = _compile(
        lambda q, k, v, t, p: fa._paged_gqa_pallas(
            q, k, v, t, p, 0.0625, None, False),
        sds((S, 8, 1, 256), jnp.bfloat16), pool, pool,
        sds((S, n_cols), jnp.int32), sds((S,), jnp.int32))
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert not re.search(r"= bf16\[69633,[^\]]*\]\{[^}]*\} copy\(", text)


def test_state_step_kernel_compiles_for_v5e_in_place_at_the_chat_cell_shapes(
        one_chip, no_compile_cache, monkeypatch):
    """``granite4hm-chat-open``: 48 slots + 12 snapshots + 1 null = 61 rows
    of a run of 9 Mamba-2 layers, 128 x 4,096 floats a row and layer (1.15
    GB a leaf).  The one-token step in a ``lax.scan`` over the run's layers,
    the leaf donated: ONE kernel (the scan's body), the leaf aliased through
    it and never copied, under a megabyte of temporaries."""
    from jax import lax
    from incubator_mxnet_tpu.kernels import mamba2
    R, n, S, H, P, N = 61, 9, 48, 64, 64, 128

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def steps(leaf, x, dt, g, B, C, D, live):
        def body(carry, i):
            leaf, acc = carry
            y, leaf = mamba2.ssd_step_rows(leaf, i, x + acc, dt, g, B, C, D,
                                           live)
            return (leaf, acc + y), None
        return lax.scan(body, (leaf, jnp.zeros_like(x)),
                        jnp.arange(n, dtype=jnp.int32))[0]

    monkeypatch.setattr(fa, "_platform_of", lambda x: "tpu")
    compiled = jax.jit(steps, donate_argnums=(0,)).trace(
        sds((R, n, N, H * P)), sds((S, H, P)), sds((S, H)), sds((S, H)),
        sds((S, N)), sds((S, N)), sds((H,)), sds((S,), jnp.bool_)
    ).lower(lowering_platforms=("tpu",)).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert text.count("tpu_custom_call") == 1
    assert not re.search(r"= f32\[61,9,128,4096\]\{[^}]*\} copy\(", text)
    assert mem.alias_size_in_bytes >= R * n * N * H * P * 4
    assert mem.temp_size_in_bytes < 1 << 20


# -- the grouped kernel's fetch: a step whose live blocks lie in a row in the
# -- pool takes them in ONE copy, any other a copy a block -------------------
RUN_COLS, RUN_BLOCKS, RUN_PAGES = 24, 64, 8       # 3 groups of 8 blocks


def _run_tables(name):
    """``(tables (4, 24), positions (4,), junk)`` of one way a table can
    lie in a pool of 64 blocks; ``junk``: the null block holds rubbish."""
    rng = np.random.default_rng(sum(map(ord, name)))
    t = np.zeros((4, RUN_COLS), np.int32)
    pos, junk = [383, 200, 130, 17], False
    if name == "in_a_row":
        for s, n in enumerate((24, 14, 9, 2)):
            t[s, :n] = 1 + 12 * s + np.arange(n)   # reserved past the head
    elif name == "shuffled":
        for s, ids in enumerate(np.split(1 + rng.permutation(60), 4)):
            t[s, :15] = ids
        pos = [239, 200, 130, 17]
    elif name == "joints":
        t[0] = 1 + np.arange(24)
        t[0, 3:] += 7                              # inside group 0
        t[1, :8], t[1, 8:16] = 1 + np.arange(8), 40 + np.arange(8)   # edge
        t[2, :9] = [5, 6, 7, 8, 9, 10, 11, 13, 14]   # the group's last column
        t[3, :2] = [30, 20]
    elif name == "pool_end":                       # first block + 8 == N
        t[0] = RUN_BLOCKS - 24 + np.arange(24)
        t[1, :16] = np.r_[1 + np.arange(8), RUN_BLOCKS - 8 + np.arange(8)]
        t[2, :9], t[3, :2] = 10 + np.arange(9), [RUN_BLOCKS - 8,
                                                 RUN_BLOCKS - 7]
    elif name == "past_pool_end":                  # first block + 8 == N + 1
        t[0, :23] = RUN_BLOCKS - 23 + np.arange(23)
        t[1, :15] = np.r_[1 + np.arange(8), RUN_BLOCKS - 7 + np.arange(7)]
        t[2, :9], t[3, :2] = 10 + np.arange(9), [RUN_BLOCKS - 2,
                                                 RUN_BLOCKS - 1]
        pos = [23 * BS - 3, 200, 130, 17]
    elif name == "short_table":                    # null block past the end
        t[0, :3], t[1, :13], t[3, :1] = [4, 5, 6], 20 + np.arange(13), [9]
        pos = [40, 200, 0, 3]
    elif name == "shared_prefix":                  # 10 blocks every slot reads
        for s, n in enumerate((24, 14, 12, 11)):
            t[s, :10] = 1 + np.arange(10)
            t[s, 10:n] = 11 + 13 * s + np.arange(n - 10)
        pos = [383, 200, 170, 165]
    elif name == "frozen_slot":
        # a slot frozen mid-burst wrote its replayed steps to block 0, and
        # a run from block 0 brings that block along
        junk = True
        t[0, :6], t[1, :14] = 1 + np.arange(6), 20 + np.arange(14)
        pos = [40, 200, 0, 0]
    else:
        raise KeyError(name)
    return t, np.asarray(pos, np.int32), junk


RUN_TABLES = ["in_a_row", "shuffled", "joints", "pool_end", "past_pool_end",
              "short_table", "shared_prefix", "frozen_slot"]


def _run_flags_oracle(tables, pos, n_q, window, n_runs):
    """``[(slot, step's group, [run flag of each of its live groups of 8
    columns])]`` of the work list whose step is ``n_runs`` such groups, by
    loops."""
    out = []
    step = n_runs * RUN_PAGES
    for s in range(len(tables)):
        last = min((int(pos[s]) + n_q - 1) // BS, RUN_COLS - 1)
        first = 0 if window is None else \
            max(int(pos[s]) - window + 1, 0) // (BS * step)
        for g in range(first, last // step + 1):
            flags = []
            for c in range(g * step, min((g + 1) * step, last + 1),
                           RUN_PAGES):
                run = int(tables[s, c]) + RUN_PAGES <= RUN_BLOCKS
                for j in range(RUN_PAGES):
                    if c + j <= last \
                            and tables[s, c + j] != tables[s, c] + j:
                        run = False
                flags.append(int(run))
            out.append((s, g, flags))
    return out


@pytest.mark.parametrize("n_q", [1, 3])
@pytest.mark.parametrize("n_runs", [1, 3])
@pytest.mark.parametrize("window", [None, 200])
@pytest.mark.parametrize("name", RUN_TABLES)
def test_run_flags_match_a_numpy_oracle(name, window, n_runs, n_q):
    """The work list's run flags — live columns in a row, the copy inside
    the pool, dead columns and the window's first step as they fall, a
    step one group or several — and the host's half
    (``paged_run_lengths``) against plain loops."""
    tables, pos, _ = _run_tables(name)
    n_pages = n_runs * RUN_PAGES
    n_steps, slot, group, page, run = fa._paged_work_list(
        jnp.asarray(tables), jnp.asarray(pos), n_q, BS, n_pages, window,
        runs=(RUN_PAGES, RUN_BLOCKS))
    n = int(n_steps)
    want = _run_flags_oracle(tables, pos, n_q, window, n_runs)
    assert np.asarray(slot)[:n].tolist() == [s for s, _, _ in want]
    assert np.asarray(group)[:n].tolist() == [g for _, g, _ in want]
    run = np.asarray(run).reshape(-1, n_runs)
    page = np.asarray(page).reshape(-1, n_pages)
    lengths = [fa.paged_run_lengths(t, RUN_PAGES, RUN_BLOCKS)
               for t in tables]
    for i, (s, g, flags) in enumerate(want):
        last = min((int(pos[s]) + n_q - 1) // BS, RUN_COLS - 1)
        # a group past the write head is not fetched, whatever its flag
        assert (run[i, :len(flags)] >= 1).tolist() == flags, (s, g)
        # ... and 2 on all its groups where the step's blocks lie in a row
        ids, n_live = tables[s, g * n_pages:], last - g * n_pages + 1
        whole = int(ids[0]) + n_pages <= RUN_BLOCKS and all(
            ids[j] == ids[0] + j for j in range(min(n_live, n_pages)))
        assert (run[i] == 2).all() if whole else (run[i] < 2).all(), (s, g)
        for u, flag in enumerate(flags):
            c = g * n_pages + u * RUN_PAGES
            # a run's copy starts at the group's first page
            assert page[i, u * RUN_PAGES] == tables[s, c]
            live = min(last - c + 1, RUN_PAGES)
            assert int(lengths[s][c // RUN_PAGES] >= live) == flag, (s, c)
    # without the pool's size the list is the one the GPT-2 kernel takes
    four = fa._paged_work_list(jnp.asarray(tables), jnp.asarray(pos), n_q,
                               BS, n_pages, window)
    assert len(four) == 4
    filled = np.asarray(four[3]).reshape(page.shape)[:n]
    col = np.asarray(group)[:n, None] * n_pages + np.arange(n_pages)
    live = col <= np.minimum((pos + n_q - 1) // BS,
                             RUN_COLS - 1)[np.asarray(slot)[:n], None]
    np.testing.assert_array_equal(filled[live], page[:n][live])
    flags = [f for _, _, fs in want for f in fs]
    if name in ("in_a_row", "pool_end", "frozen_slot"):
        assert all(flags)
    if name == "shuffled":
        assert sum(flags) <= 4          # a slot's last group of one column
    if name == "past_pool_end":
        assert not all(flags)


_INTERPRETED = []          # the shapes the grouped kernel is compiled for


@pytest.mark.parametrize("name", RUN_TABLES)        # innermost: one compile
@pytest.mark.parametrize("n_q", [1, 3])
@pytest.mark.parametrize("window", [None, 200])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("D", [128, 256])
@pytest.mark.parametrize("kv_heads", [1, 4])
def test_gqa_kernel_fetches_runs_and_blocks(name, kv_heads, D, dtype, window,
                                            n_q):
    """The grouped kernel, interpreted, against the lax gather over every
    way a table can lie in the pool: one copy a step where it lies in a
    row, a copy a block where it does not, both in one call where a table
    has joints.  Tolerances as in
    :func:`test_gqa_kernel_matches_lax_gather`.

    An interpreted kernel is a CPU executable of ~500 memory mappings
    that lives as long as the jit's cache, and a process may hold 65,530
    (``vm.max_map_count``; past it XLA's compiler dies of a segmentation
    fault, and this file's process is near 50,000 by here): the eight
    tables of a shape share one compile, and the last shape's goes when
    the next one's comes."""
    if _INTERPRETED != [(kv_heads, D, dtype, window, n_q)]:
        fa._paged_gqa_pallas.clear_cache()
        _INTERPRETED[:] = [(kv_heads, D, dtype, window, n_q)]
    tables, pos, junk = _run_tables(name)
    rng = np.random.default_rng(5)
    shape = (RUN_BLOCKS, kv_heads, BS, D)
    kp, vp = (rng.standard_normal(shape).astype(np.float32)
              for _ in range(2))
    if junk:
        kp[0], vp[0] = 1e3, -1e3
    kp, vp = jnp.asarray(kp, dtype), jnp.asarray(vp, dtype)
    q = jnp.asarray(rng.standard_normal((4, 2 * kv_heads, n_q, D)),
                    jnp.float32)
    scale = D ** -0.5
    got = fa._paged_gqa_pallas(q, kp, vp, jnp.asarray(tables),
                               jnp.asarray(pos), scale, window,
                               interpret=True)
    ref = fa._xla_paged_verify_decode_attention(
        q, kp, vp, jnp.asarray(tables), jnp.asarray(pos), scale, window)
    tol = 2e-5 if dtype == "float32" else 2e-2
    live = tables[:, 0] != 0            # a free slot's row is nobody's
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(ref)[live],
                               atol=tol, rtol=tol)
    assert np.isfinite(np.asarray(got, np.float32)).all()
