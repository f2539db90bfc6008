"""kernels/gated_delta.py: the chunked prefill and the one-token step against
the recurrence token by token, and the Pallas recurrence (interpreted on the
CPU) against ``lax.scan``."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from incubator_mxnet_tpu.kernels import gated_delta as gd


def _inputs(T, H=3, Dk=16, Dv=8, seed=0, state=True):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    q = jax.random.normal(ks[0], (T, H, Dk))
    k = jax.random.normal(ks[1], (T, H, Dk))
    q, k = (x / jnp.linalg.norm(x, axis=-1, keepdims=True) for x in (q, k))
    v = jax.random.normal(ks[2], (T, H, Dv))
    g = -jnp.exp(jax.random.normal(ks[3], (T, H)) - 2.0)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (T, H)))
    s0 = jax.random.normal(ks[5], (H, Dk, Dv)) if state \
        else jnp.zeros((H, Dk, Dv))
    return q, k, v, g, beta, s0


def _close(a, b, tol=2e-5):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=tol,
                               atol=tol)


@pytest.mark.parametrize("T", [1, 63, 64, 65, 128, 200])
@pytest.mark.parametrize("state", [False, True])
def test_chunked_is_token_by_token(T, state):
    args = _inputs(T, state=state, seed=T)
    o_ref, last_ref = gd.gated_delta_scan(*args)
    o, snaps, last = gd.gated_delta_prefill(*args)
    assert snaps.shape[0] == 0
    _close(o, o_ref)
    _close(last, last_ref)


@pytest.mark.parametrize("T,n_live", [(128, 128), (128, 70), (200, 64),
                                      (192, 1)])
def test_live_mask_and_snapshots(T, n_live):
    """Positions that are not live leave the state as it is: the snapshots
    are the states after 64 and 128 LIVE-prefix positions, and the last
    state is the state after the last live one."""
    args = _inputs(T, seed=T + n_live)
    live = jnp.arange(T) < n_live
    o, snaps, last = gd.gated_delta_prefill(*args, live=live,
                                            snapshot_every=64)
    assert snaps.shape[0] == T // 64
    q, k, v, g, beta, s0 = args
    o_ref, last_ref = gd.gated_delta_scan(*(x[:n_live] for x in args[:5]),
                                          s0)
    _close(o[:n_live], o_ref)
    _close(last, last_ref)
    for i in range(T // 64):
        upto = min((i + 1) * 64, n_live)
        _, want = gd.gated_delta_scan(*(x[:upto] for x in args[:5]), s0)
        _close(snaps[i], want)
    # and the scan under the same mask agrees with itself
    _, masked = gd.gated_delta_scan(*args, live=live)
    np.testing.assert_array_equal(np.asarray(masked), np.asarray(last_ref))


def test_step_is_one_token_of_the_scan():
    q, k, v, g, beta, s0 = _inputs(5, seed=3)
    o_ref, last_ref = gd.gated_delta_scan(q, k, v, g, beta, s0)
    S = s0
    for t in range(5):
        o, S = gd.gated_delta_step(q[t], k[t], v[t], g[t], beta[t], S)
        _close(o, o_ref[t])
    _close(S, last_ref)
    # written out: S <- e^g S; u = beta (v - S^T k); S <- S + k u^T; o = S^T q
    S1 = np.exp(np.asarray(g[0]))[:, None, None] * np.asarray(s0)
    u = np.asarray(beta[0])[:, None] * (
        np.asarray(v[0]) - np.einsum("hkv,hk->hv", S1, np.asarray(k[0])))
    S1 = S1 + np.einsum("hk,hv->hkv", np.asarray(k[0]), u)
    o0, S0 = gd.gated_delta_step(q[0], k[0], v[0], g[0], beta[0], s0)
    _close(S0, S1)
    _close(o0, np.einsum("hkv,hk->hv", S1, np.asarray(q[0])))


def test_step_leaves_a_dead_row_bit_for_bit():
    q, k, v, g, beta, s0 = _inputs(4, seed=9)       # 4 rows of 3 heads
    S = jnp.stack([s0] * 4)
    live = jnp.asarray([True, False, True, False])
    _, S2 = gd.gated_delta_step(q, k, v, g, beta, S, live[:, None])
    S, S2 = np.asarray(S), np.asarray(S2)
    np.testing.assert_array_equal(S2[1], S[1])
    np.testing.assert_array_equal(S2[3], S[3])
    assert not np.array_equal(S2[0], S[0])


@pytest.mark.parametrize("T,every", [(128, 0), (192, 64), (320, 128)])
def test_pallas_forced_on_the_cpu_is_the_scan(monkeypatch, T, every):
    args = _inputs(T, H=2, Dk=128, Dv=128, seed=T)
    live = jnp.arange(T) < T - 37
    assert gd.gated_delta_impl(args[0]) == "lax_scan"
    want = gd.gated_delta_prefill(*args, live=live, snapshot_every=every)
    monkeypatch.setenv("MXNET_FA_DECODE_FORCE_PALLAS", "1")
    assert gd.gated_delta_impl(args[0]) == "pallas"
    got = gd.gated_delta_prefill(*args, live=live, snapshot_every=every)
    for a, b in zip(got, want):
        assert a.shape == b.shape
        _close(a, b, 1e-5)
