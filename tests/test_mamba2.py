"""The Mamba-2 recurrence's three forms (``kernels/mamba2.py``) against one
another at small sizes on the CPU: the chunked prefill and the one-token step
— as ``lax`` and as the Pallas kernel, interpreted — against the recurrence
token by token, which is the definition; positions that are not live."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from incubator_mxnet_tpu.kernels import mamba2 as m


def _inputs(T, H=4, P=8, N=16, seed=0, zero_state=False):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    dt = jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(0.5), (T, H))),
                     jnp.float32)
    g = -jnp.asarray(rng.uniform(1, 16, (H,)), jnp.float32) * dt
    s0 = jnp.zeros((N, H, P), jnp.float32) if zero_state else f(N, H, P)
    return f(T, H, P), dt, g, f(T, N), f(T, N), f(H), s0


#: (name, T, chunk, live positions or None, snapshot_every, from zeros)
PREFILL_CASES = [
    ("one_chunk", 8, 8, None, 0, True),
    ("three_chunks", 24, 8, None, 0, False),
    ("ragged_length", 29, 8, None, 0, False),
    ("ragged_live", 29, 8, 23, 0, False),
    ("live_ends_inside_a_chunk_with_snapshots", 40, 8, 27, 16, False),
    ("snapshots_every_chunk", 32, 8, None, 8, True),
    ("the_sources_chunk", 600, 256, 530, 256, False),
]


@pytest.mark.parametrize("case", PREFILL_CASES, ids=[c[0] for c in
                                                     PREFILL_CASES])
def test_prefill_is_the_scan(case, monkeypatch):
    """Outputs of the live positions, the last state and the state at every
    snapshot boundary, against the recurrence token by token from the same
    state.  Float32 on both sides, sums in another order."""
    _, T, chunk, n_live, every, zeros = case
    monkeypatch.setattr(m, "CHUNK", chunk)
    x, dt, g, B, C, D, s0 = _inputs(T, zero_state=zeros, seed=T)
    live = None if n_live is None else jnp.arange(T) < n_live
    n = T if n_live is None else n_live
    want, last = m.ssd_scan(x, dt, g, B, C, D, s0, live)
    got, snaps, end = m.ssd_prefill(x, dt, g, B, C, D, s0, live, every)
    np.testing.assert_allclose(got[:n], want[:n], atol=3e-5, rtol=1e-5)
    np.testing.assert_allclose(end, last, atol=3e-5, rtol=1e-5)
    assert snaps.shape == ((T // every if every else 0),) + s0.shape
    for j in range(snaps.shape[0]):
        b = (j + 1) * every
        _, at = m.ssd_scan(x[:b], dt[:b], g[:b], B[:b], C[:b], D, s0,
                           None if live is None else live[:b])
        np.testing.assert_allclose(snaps[j], at, atol=3e-5, rtol=1e-5)


def test_snapshot_spacing_is_whole_chunks():
    x, dt, g, B, C, D, s0 = _inputs(8)
    with pytest.raises(ValueError):
        m.ssd_prefill(x, dt, g, B, C, D, s0, snapshot_every=100)


@pytest.mark.parametrize("form", ["scan", "prefill"])
def test_positions_that_are_not_live_leave_the_state_bit_for_bit(
        form, monkeypatch):
    monkeypatch.setattr(m, "CHUNK", 8)
    x, dt, g, B, C, D, s0 = _inputs(19)
    dead = jnp.zeros(19, bool)
    if form == "scan":
        last = m.ssd_scan(x, dt, g, B, C, D, s0, dead)[1]
    else:
        _, snaps, last = m.ssd_prefill(x, dt, g, B, C, D, s0, dead, 8)
        assert (np.asarray(snaps) == np.asarray(s0)[None]).all()
    assert (np.asarray(last) == np.asarray(s0)).all()


def _rows(seed=1, R=5, n=3, rows=3, H=4, P=32, N=16):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    dt = jnp.asarray(rng.uniform(0.01, 0.5, (rows, H)), jnp.float32)
    return (f(R, n, N, H * P), f(rows, H, P), dt, -3.0 * dt, f(rows, N),
            f(rows, N), f(H))


#: (name, kernel forced, lanes a grid step of the kernel takes)
STEP_CASES = [("lax", False, None), ("pallas_one_block", True, None),
              ("pallas_two_blocks", True, 128)]
#: which of the three sequences' rows are live
LIVE_CASES = [("all", [True, True, True]), ("none", [False, False, False]),
              ("first", [True, False, False]),
              ("last", [False, False, True]),
              ("alternating", [False, True, False]),
              ("middle_dead", [True, False, True])]


@pytest.fixture
def step_form(request, monkeypatch):
    """One of :data:`STEP_CASES` set up (the Pallas kernel runs
    interpreted): the lanes a grid step takes, or None."""
    _, forced, lanes = request.param
    monkeypatch.setenv("MXNET_FA_DECODE_FORCE_PALLAS", "1" if forced else "0")
    if lanes:
        monkeypatch.setattr(m, "_STEP_LANES", lanes)
    m._step_pallas.clear_cache()
    yield request.param
    m._step_pallas.clear_cache()


@pytest.mark.parametrize("live", [c[1] for c in LIVE_CASES],
                         ids=[c[0] for c in LIVE_CASES])
@pytest.mark.parametrize("step_form", STEP_CASES, indirect=True,
                         ids=[c[0] for c in STEP_CASES])
def test_step_rows_is_the_step_in_place(step_form, live):
    """One token for rows 0..2 of layer 1 of a leaf of five rows and three
    layers: the live rows' outputs and new state are the step's, the
    outputs of the rows that are not live exactly 0, and every other number
    of the leaf — the rows that are not live, the rows past the sequences',
    the other layers — is as it came, bit for bit."""
    _, forced, lanes = step_form
    leaf, x, dt, g, B, C, D = _rows(P=64 if lanes else 32)
    rows, (N, H, P) = x.shape[0], (leaf.shape[2],) + x.shape[1:]
    on = np.asarray(live)
    assert m.ssd_impl(x) == ("pallas" if forced else "lax")
    y, out = jax.jit(m.ssd_step_rows)(leaf, jnp.int32(1), x, dt, g, B, C, D,
                                      jnp.asarray(on))
    want_y, want_S = m.ssd_step(x, dt, g, B, C, D,
                                leaf[:rows, 1].reshape(rows, N, H, P),
                                jnp.asarray(on))
    np.testing.assert_allclose(np.asarray(y)[on], np.asarray(want_y)[on],
                               atol=1e-5, rtol=1e-5)
    assert (np.asarray(y)[~on] == 0).all()
    np.testing.assert_allclose(out[:rows, 1],
                               want_S.reshape(rows, N, H * P), atol=1e-6,
                               rtol=1e-6)
    untouched = np.array(out)
    untouched[:rows, 1][on] = np.asarray(leaf)[:rows, 1][on]
    assert (untouched == np.asarray(leaf)).all()


@pytest.mark.parametrize("live", [
    [True] * 7, [False] * 7, [False, True, True, False, False, True, False],
    [True, False, False, False, False, False, True]],
    ids=["all", "none", "scattered", "ends"])
def test_the_work_list_names_the_live_rows_first_in_order(live):
    order, n_live = jax.jit(m.step_work_list)(jnp.asarray(live))
    on = np.asarray(live)
    assert int(n_live) == on.sum() and order.dtype == jnp.int32
    assert list(order[:int(n_live)]) == list(np.flatnonzero(on))
    assert (np.asarray(order[int(n_live):]) == 0).all()


def test_a_list_made_ahead_serves_as_the_one_made_inside(monkeypatch):
    """``work`` is what the step would have made of ``live`` itself."""
    monkeypatch.setenv("MXNET_FA_DECODE_FORCE_PALLAS", "1")
    leaf, x, dt, g, B, C, D = _rows()
    live = jnp.asarray([False, True, True])
    inside = m.ssd_step_rows(leaf, 2, x, dt, g, B, C, D, live)
    ahead = m.ssd_step_rows(leaf, 2, x, dt, g, B, C, D, live,
                            m.step_work_list(live))
    for a, b in zip(inside, ahead):
        assert (np.asarray(a) == np.asarray(b)).all()


def test_the_step_is_one_token_of_the_scan():
    x, dt, g, B, C, D, s0 = _inputs(5)
    S, ys = s0, []
    for t in range(5):
        y, S = m.ssd_step(x[t], dt[t], g[t], B[t], C[t], D, S)
        ys.append(y)
    want, last = m.ssd_scan(x, dt, g, B, C, D, s0)
    np.testing.assert_allclose(jnp.stack(ys), want, atol=1e-6, rtol=1e-6)
    np.testing.assert_allclose(S, last, atol=1e-6, rtol=1e-6)
