"""Pallas flash-attention kernel vs the XLA reference (interpret mode on
CPU keeps the kernel testable without a chip)."""
import numpy as np
import pytest

import incubator_mxnet_tpu as mx  # noqa: F401  (jax config via conftest)


def _ref(q, k, v, causal=False, mask=None):
    import jax.numpy as jnp
    scale = 1.0 / np.sqrt(q.shape[-1])
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if mask is not None:
        s = jnp.where(mask[:, None, None, :] > 0, s, -1e30)
    if causal:
        T = q.shape[2]
        tri = np.tril(np.ones((T, T), bool))
        s = jnp.where(tri[None, None], s, -1e30)
    import jax
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def _rand(shape, seed):
    return np.random.default_rng(seed).standard_normal(shape).astype(
        np.float32)


@pytest.mark.parametrize("causal", [False, True], ids=["dense", "causal"])
@pytest.mark.parametrize("T", [128, 256])
def test_flash_matches_xla(T, causal):
    from incubator_mxnet_tpu.kernels import flash_attention
    q = _rand((2, 3, T, 64), 0)
    k = _rand((2, 3, T, 64), 1)
    v = _rand((2, 3, T, 64), 2)
    out = flash_attention(q, k, v, causal=causal)
    ref = _ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def test_flash_fallback_odd_seq():
    from incubator_mxnet_tpu.kernels import flash_attention
    q = _rand((1, 2, 100, 32), 3)   # 100 not divisible by the block
    out = flash_attention(q, q, q)
    ref = _ref(q, q, q)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def test_flash_gradients():
    """custom_vjp backward (XLA recompute) must match autodiff of the
    reference implementation."""
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu.kernels import flash_attention
    q = _rand((1, 2, 128, 32), 4)
    k = _rand((1, 2, 128, 32), 5)
    v = _rand((1, 2, 128, 32), 6)

    def loss_flash(q_, k_, v_):
        return jnp.sum(flash_attention(q_, k_, v_, causal=True) ** 2)

    def loss_ref(q_, k_, v_):
        return jnp.sum(_ref(q_, k_, v_, causal=True) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   rtol=1e-3, atol=1e-4)


@pytest.mark.parametrize("causal", [False, True], ids=["dense", "causal"])
def test_flash_masked_matches_xla(causal):
    """(B, Tk) key-validity mask (padded-batch valid_length shape) through
    the kernel's additive-bias path vs the XLA reference."""
    from incubator_mxnet_tpu.kernels import flash_attention
    T = 128
    q = _rand((3, 2, T, 64), 10)
    k = _rand((3, 2, T, 64), 11)
    v = _rand((3, 2, T, 64), 12)
    # ragged valid lengths incl. one full-length row
    mask = np.zeros((3, T), np.int32)
    for b, vl in enumerate([37, T, 90]):
        mask[b, :vl] = 1
    out = flash_attention(q, k, v, causal=causal, mask=mask)
    ref = _ref(q, k, v, causal=causal, mask=mask)
    # compare only valid query rows: padded rows attend to garbage by
    # construction in both impls but are masked out downstream
    out, ref = np.asarray(out), np.asarray(ref)
    for b, vl in enumerate([37, T, 90]):
        np.testing.assert_allclose(out[b, :, :vl], ref[b, :, :vl],
                                   rtol=2e-4, atol=2e-5)


def test_flash_masked_gradients():
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu.kernels import flash_attention
    T = 128
    q = _rand((2, 2, T, 32), 13)
    k = _rand((2, 2, T, 32), 14)
    v = _rand((2, 2, T, 32), 15)
    mask = np.zeros((2, T), np.int32)
    mask[0, :50] = 1
    mask[1, :] = 1
    # weight the loss by the valid-query mask so padded rows don't
    # contribute garbage gradients in either impl
    wq = mask[:, None, :, None].astype(np.float32)

    def loss_flash(q_, k_, v_):
        return jnp.sum((flash_attention(q_, k_, v_, mask=mask) * wq) ** 2)

    def loss_ref(q_, k_, v_):
        return jnp.sum((_ref(q_, k_, v_, mask=mask) * wq) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   rtol=1e-3, atol=1e-4)


def test_flash_masked_fallback_odd_seq():
    """Masked XLA fallback (odd T) matches the reference too."""
    from incubator_mxnet_tpu.kernels import flash_attention
    T = 100
    q = _rand((2, 2, T, 32), 16)
    mask = np.zeros((2, T), np.int32)
    mask[0, :60] = 1
    mask[1, :] = 1
    out = np.asarray(flash_attention(q, q, q, mask=mask))
    ref = np.asarray(_ref(q, q, q, mask=mask))
    for b, vl in enumerate([60, T]):
        np.testing.assert_allclose(out[b, :, :vl], ref[b, :, :vl],
                                   rtol=2e-4, atol=2e-5)


def test_sdpa_fusion_gate_masked(monkeypatch):
    """MXNET_USE_FUSION=1 routes the model-level SDPA (with a padded
    valid_length mask) through the Pallas kernel and matches the XLA
    path — the every-real-batch case VERDICT r03 flagged as falling back."""
    from incubator_mxnet_tpu.models.bert import _sdpa
    from incubator_mxnet_tpu.ndarray.ndarray import NDArray
    import jax.numpy as jnp
    B, T, C, H = 2, 128, 64, 2
    rng = np.random.default_rng(20)
    q = NDArray(jnp.asarray(rng.standard_normal((B, T, C)), jnp.float32))
    k = NDArray(jnp.asarray(rng.standard_normal((B, T, C)), jnp.float32))
    v = NDArray(jnp.asarray(rng.standard_normal((B, T, C)), jnp.float32))
    m = np.zeros((B, T), np.int32)
    m[0, :77] = 1
    m[1, :] = 1
    mask = NDArray(jnp.asarray(m))

    monkeypatch.delenv("MXNET_USE_FUSION", raising=False)
    base = _sdpa(q, k, v, H, mask=mask).asnumpy()
    monkeypatch.setenv("MXNET_USE_FUSION", "1")
    fused = _sdpa(q, k, v, H, mask=mask).asnumpy()
    np.testing.assert_allclose(fused[0, :77], base[0, :77],
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(fused[1], base[1], rtol=2e-4, atol=2e-5)


def test_flash_under_jit():
    import jax
    from incubator_mxnet_tpu.kernels import flash_attention
    q = _rand((1, 1, 128, 64), 7)
    f = jax.jit(lambda x: flash_attention(x, x, x, causal=True))
    out1 = f(q)
    out2 = f(q)   # cached executable
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2))
    ref = _ref(q, q, q, causal=True)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True], ids=["dense", "causal"])
@pytest.mark.parametrize("T", [256, 512])
def test_flash_pallas_backward_matches_xla_oracle(T, causal):
    """The FA2 Pallas backward (dQ/dK/dV kernels recomputing P from the
    saved logsumexp) vs the XLA-recompute oracle (MXNET_FLASH_BWD=xla)
    AND vs plain autodiff of the reference — masked and unmasked."""
    import os
    import jax
    from incubator_mxnet_tpu.kernels.flash_attention import \
        flash_attention as fa

    q = _rand((1, 2, T, 32), 10)
    k = _rand((1, 2, T, 32), 11)
    v = _rand((1, 2, T, 32), 12)
    for mask in (None,
                 np.concatenate([np.ones((1, T // 2), np.float32),
                                 np.zeros((1, T // 2), np.float32)], 1)):
        def loss(q_, k_, v_):
            return (fa(q_, k_, v_, causal=causal, mask=mask) ** 2).sum()

        os.environ["MXNET_FLASH_BWD"] = "pallas"
        try:
            gp = jax.grad(loss, (0, 1, 2))(q, k, v)
            os.environ["MXNET_FLASH_BWD"] = "xla"
            gx = jax.grad(loss, (0, 1, 2))(q, k, v)
        finally:
            os.environ.pop("MXNET_FLASH_BWD", None)

        def loss_ref(q_, k_, v_):
            return (_ref(q_, k_, v_, causal=causal, mask=mask) ** 2).sum()
        gr = jax.grad(loss_ref, (0, 1, 2))(q, k, v)
        for a, b in zip(gp, gx):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-3, atol=2e-4)
        for a, b in zip(gp, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-3, atol=2e-4)


# ---------------------------------------------------------------------------
# Blockwise ring attention (round 5): Pallas flash per ring step, exact
# logsumexp merge — vs the einsum ring oracle, forward AND gradients
# ---------------------------------------------------------------------------

def _ring_variant(use_flash, causal, mask, q, k, v):
    import jax
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from functools import partial
    from incubator_mxnet_tpu import parallel
    from incubator_mxnet_tpu.parallel.ring import _ring_body

    mesh = parallel.make_mesh({"seq": 4})
    spec = P(None, None, "seq", None)
    body = partial(_ring_body, axis_name="seq",
                   scale=q.shape[-1] ** -0.5, causal=causal,
                   use_flash=use_flash)
    if mask is not None:
        return shard_map(body, mesh=mesh,
                         in_specs=(spec, spec, spec, P(None, "seq")),
                         out_specs=spec, check_vma=False)(q, k, v, mask)
    return shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec, check_vma=False)(q, k, v)


# whole matrix rides the slow tier: multi-device ring emulation pays
# ~15s/mode in shard_map compiles on the 1-CPU tier-1 box; the ring
# path keeps cheap tier-1 coverage via test_parallel's ring tests
@pytest.mark.parametrize("mode", [
    pytest.param("dense", marks=pytest.mark.slow),
    pytest.param("causal", marks=pytest.mark.slow),
    pytest.param("masked", marks=pytest.mark.slow),
])
def test_blockwise_ring_matches_einsum_ring(mode):
    import jax
    import jax.numpy as jnp
    B, H, T, D = 2, 2, 32, 8
    rng = np.random.default_rng(5)
    q, k, v = (jnp.asarray(rng.normal(size=(B, H, T, D))
                           .astype(np.float32)) for _ in range(3))
    causal = mode == "causal"
    mask = None
    if mode == "masked":
        m = (rng.random((B, T)) > 0.25).astype(np.float32)
        m[:, :4] = 1.0            # >= 1 valid key per ring shard row
        m[:, 8:12] = 1.0
        m[:, 16:20] = 1.0
        m[:, 24:28] = 1.0
        mask = jnp.asarray(m)

    def loss(fn_flash):
        def f(q, k, v):
            o = _ring_variant(fn_flash, causal, mask, q, k, v)
            return (o.astype(jnp.float32) ** 2).sum()
        return f

    out_ein = _ring_variant(False, causal, mask, q, k, v)
    out_flash = _ring_variant(True, causal, mask, q, k, v)
    np.testing.assert_allclose(np.asarray(out_flash),
                               np.asarray(out_ein),
                               rtol=2e-4, atol=2e-4)

    ge = jax.grad(loss(False), argnums=(0, 1, 2))(q, k, v)
    gf = jax.grad(loss(True), argnums=(0, 1, 2))(q, k, v)
    for a, b, nm in zip(gf, ge, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=5e-3,
                                   err_msg=f"{mode} d{nm}")


@pytest.mark.parametrize("mode", ["dense", "causal", "masked"])
def test_flash_lse_pallas_grads_vs_xla(mode):
    """The Pallas lse-variant backward (g_lse folds into dd) vs the AD
    oracle, at the tile-aligned size where the kernel actually engages
    (small T routes to the XLA fallback via the shared dispatcher)."""
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu.kernels import flash_attention_lse
    from incubator_mxnet_tpu.kernels.flash_attention import (
        _xla_attention_lse)

    B, H, T, D = 1, 2, 128, 8
    rng = np.random.default_rng(9)
    q, k, v = (jnp.asarray(rng.normal(size=(B, H, T, D))
                           .astype(np.float32)) for _ in range(3))
    causal = mode == "causal"
    mask = None
    if mode == "masked":
        m = (rng.random((B, T)) > 0.3).astype(np.float32)
        m[:, 0] = 1.0
        mask = jnp.asarray(m)

    def f_pallas(q, k, v):
        o, lse = flash_attention_lse(q, k, v, causal=causal, mask=mask)
        return (o.astype(jnp.float32) ** 2).sum() + (1.3 * lse).sum()

    def f_xla(q, k, v):
        bb = None
        if mask is not None:
            bb = jnp.broadcast_to(
                jnp.where(mask > 0, 0.0, -1e30)[:, None, None, :],
                (B, H, 1, T)).reshape(B * H, 1, T)
        o, lse = _xla_attention_lse(
            q.reshape(B * H, T, D), k.reshape(B * H, T, D),
            v.reshape(B * H, T, D), D ** -0.5, causal, bias=bb)
        return (o.astype(jnp.float32) ** 2).sum() + (1.3 * lse).sum()

    va, ga = jax.value_and_grad(f_pallas, argnums=(0, 1, 2))(q, k, v)
    vb, gb = jax.value_and_grad(f_xla, argnums=(0, 1, 2))(q, k, v)
    assert abs(va - vb) < 1e-2 * max(1.0, abs(float(vb)))
    for a, b, nm in zip(ga, gb, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=5e-3,
                                   err_msg=f"{mode} d{nm}")


def test_blockwise_ring_tile_aligned_forward():
    """Pallas engages INSIDE the ring (T_local = 128 over 4 shards,
    interpret mode on CPU): forward parity with the einsum ring."""
    import jax.numpy as jnp
    B, H, T, D = 1, 1, 512, 8
    rng = np.random.default_rng(13)
    q, k, v = (jnp.asarray(rng.normal(size=(B, H, T, D))
                           .astype(np.float32)) for _ in range(3))
    out_ein = _ring_variant(False, True, None, q, k, v)
    out_flash = _ring_variant(True, True, None, q, k, v)
    np.testing.assert_allclose(np.asarray(out_flash),
                               np.asarray(out_ein),
                               rtol=2e-4, atol=2e-4)


# whole matrix rides the slow tier (~12s/mode of all-to-all shard_map
# compiles); the Ulysses path keeps tier-1 coverage via test_parallel's
# test_ulysses_matches_local / test_ulysses_causal_matches_ring
@pytest.mark.parametrize("mode", [
    pytest.param("dense", marks=pytest.mark.slow),
    pytest.param("causal", marks=pytest.mark.slow),
    pytest.param("masked", marks=pytest.mark.slow),
])
def test_ulysses_flash_matches_einsum(mode):
    """The Ulysses all-to-all path with the flash kernel on the gathered
    full-sequence block vs its einsum local attention — fwd + grads."""
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu import parallel
    from incubator_mxnet_tpu.parallel.ulysses import ulysses_attention

    B, H, T, D = 2, 4, 32, 8          # heads divisible by the axis
    rng = np.random.default_rng(23)
    q, k, v = (jnp.asarray(rng.normal(size=(B, H, T, D))
                           .astype(np.float32)) for _ in range(3))
    causal = mode == "causal"
    mask = None
    if mode == "masked":
        m = (rng.random((B, T)) > 0.3).astype(np.float32)
        m[:, 0] = 1.0
        mask = jnp.asarray(m)
    mesh = parallel.make_mesh({"seq": 4})

    def run(use_flash, q, k, v):
        return ulysses_attention(q, k, v, mesh=mesh, causal=causal,
                                 mask=mask, use_flash=use_flash)

    np.testing.assert_allclose(
        np.asarray(run(True, q, k, v)), np.asarray(run(False, q, k, v)),
        rtol=2e-4, atol=2e-4)

    def loss(use_flash):
        return lambda q, k, v: (run(use_flash, q, k, v)
                                .astype(jnp.float32) ** 2).sum()

    gf = jax.grad(loss(True), argnums=(0, 1, 2))(q, k, v)
    ge = jax.grad(loss(False), argnums=(0, 1, 2))(q, k, v)
    for a, b, nm in zip(gf, ge, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=5e-3,
                                   err_msg=f"{mode} d{nm}")
