"""The cache's form alone (``serving/kvcache.py``: ``cache_forms`` and the
three kinds), no engine: what is written through a form reads back as a plain
numpy scatter has it, in every order a pool is stored in; the kinds and where
their pools lie are read off what a model states; and the engine's text holds
nothing of a pool's order or of what kind of thing a layer caches.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from incubator_mxnet_tpu.base import MXNetError
from incubator_mxnet_tpu.serving import kvcache
from incubator_mxnet_tpu.serving.kvcache import (GroupedKV, KVLayout,
                                                 StatedRows, StateLeaves,
                                                 cache_forms)

N, BS, NB = 9, 4, 5             # blocks, positions a block, table columns
TABLE = np.array([3, 7, 1, 5, 8], np.int32)


def _layout(**stated):
    base = dict(num_layers=1, kv_heads=2, head_dim=16, dtype="float32",
                windows=[None], max_length=64)
    base.update(stated)
    return KVLayout.of(base)


class Stored:
    """One kind of layer over one stored order: the form, its empty pools,
    and a pool's logical view ``[N, bs, *feat]`` (the padded lanes must
    still hold zeros)."""

    def __init__(self, name):
        self.name = name
        self.notes = []
        note = lambda *a: self.notes.append(a)          # noqa: E731
        if name == "rows":
            lay = _layout(rows=[((24, "float32"), (8, "float32"))],
                          selects=[3])
            self.feats = [(24,), (8,)]
            shapes = [(N, BS, 128), (N, BS, 128)]
            self.position_major = False
        else:
            D = 128 if name == "stated_lanes" else 16
            lay = _layout(head_dim=D)
            self.feats = [(2, D)] * 2
            self.position_major = name == "position_major"
            shapes = [(N, BS, 2, 128) if self.position_major
                      else (N, 2, BS, D)] * 2
        (self.form,), _ = cache_forms(lay, [None], BS, NB, note)
        self.pools = [jnp.zeros(s, jnp.float32) for s in shapes]

    def view(self, pool, feat):
        a = np.asarray(pool)
        if self.name in ("stated", "stated_lanes"):
            return a.transpose(0, 2, 1, 3)
        assert not a[..., feat[-1]:].any()      # the lanes past the features
        return a[..., :feat[-1]]

    def rows(self, rng, *lead):
        return [rng.standard_normal(lead + f).astype(np.float32)
                for f in self.feats]


ORDERS = ["stated", "stated_lanes", "position_major", "rows"]


@pytest.fixture(params=ORDERS)
def stored(request):
    return Stored(request.param)


def _scatter_prompt(ref, rows, j0):
    """Position ``t`` of a prompt goes to block ``TABLE[j0 + t // BS]`` at
    offset ``t % BS``; a column past the table is the null block 0."""
    for t, row in enumerate(rows):
        col = j0 + t // BS
        ref[TABLE[col] if col < NB else 0, t % BS] = row


@pytest.mark.parametrize("j0, traced", [(0, False), (2, True), (4, True)])
def test_a_prompt_written_through_the_form_is_the_numpy_scatter(
        stored, j0, traced):
    """The miss prefill's static column 0 and the hit prefill's traced
    ``ctx // bs``: 2 1/2 blocks of rows land where the table says, and from
    column 4 on two of the three strips pass the table and land in block
    0."""
    rng = np.random.default_rng(0)
    Tb = 2 * BS + 2
    rows = stored.rows(rng, Tb)
    caches = list(stored.pools)
    stored.form.write_prompt(caches, [jnp.asarray(r) for r in rows],
                             jnp.asarray(TABLE),
                             jnp.int32(j0) if traced else j0, traced,
                             stored.position_major)
    for pool, r, feat in zip(caches, rows, stored.feats):
        ref = np.zeros((N, BS) + feat, np.float32)
        _scatter_prompt(ref, r, j0)
        np.testing.assert_array_equal(stored.view(pool, feat), ref)
        if j0 == 4:             # columns 5 and 6 are past the table
            assert stored.view(pool, feat)[0].any()
            untouched = sorted(set(range(1, N)) - {int(TABLE[4])})
            assert not stored.view(pool, feat)[untouched].any()


def test_a_step_written_through_the_form_is_the_numpy_scatter(stored):
    rng = np.random.default_rng(1)
    blk = np.array([7, 1, 5], np.int32)
    off = np.array([3, 0, 2], np.int32)
    for pool, r, feat in zip(stored.pools, stored.rows(rng, 3),
                             stored.feats):
        got = stored.form.write_step(pool, jnp.asarray(blk),
                                     jnp.asarray(off), jnp.asarray(r),
                                     stored.position_major)
        ref = np.zeros((N, BS) + feat, np.float32)
        ref[blk, off] = r
        assert got.shape == pool.shape
        np.testing.assert_array_equal(stored.view(got, feat), ref)


def test_a_wide_step_write_is_its_columns_written_one_by_one(stored):
    """``(S, Q)`` blocks and offsets (the verify program) against Q writes of
    ``(S,)`` (the decode program)."""
    rng = np.random.default_rng(2)
    S, Q = 3, 3
    pos = np.array([2, 9, 14], np.int32)[:, None] + np.arange(Q)
    blk, off = TABLE[pos // BS], (pos % BS).astype(np.int32)
    for pool, r, feat in zip(stored.pools, stored.rows(rng, S, Q),
                             stored.feats):
        wide = stored.form.write_step(
            pool, jnp.asarray(blk), jnp.asarray(off), jnp.asarray(r),
            stored.position_major)
        one = pool
        for j in range(Q):
            one = stored.form.write_step(
                one, jnp.asarray(blk[:, j]), jnp.asarray(off[:, j]),
                jnp.asarray(r[:, j]), stored.position_major)
        np.testing.assert_array_equal(np.asarray(wide), np.asarray(one))
        ref = np.zeros((N, BS) + feat, np.float32)
        ref[blk, off] = r
        np.testing.assert_array_equal(stored.view(wide, feat), ref)


def _mixed():
    """Four layers of every kind (no model mixes them so; the rule does not
    care): K/V, a state of two leaves, an indexed latent's two rows, K/V."""
    return _layout(
        num_layers=4, windows=[None, None, 6, None],
        states=[None, (((4, 5), "float32"), ((3,), "int32")), None, None],
        rows=[None, None, ((24, "bfloat16"), (8, "float32")), None],
        selects=[None, None, 3, None])


class _Scaled:
    attn_scale = 0.25


def test_the_kinds_and_their_places_are_read_off_what_the_model_states():
    lay = _mixed()
    forms, n_pools = cache_forms(lay, [None, None, None, _Scaled()], BS, NB,
                                 None)
    assert [type(f) for f in forms] == [GroupedKV, StateLeaves, StatedRows,
                                        GroupedKV]
    # the layers' first rows in layer order, then their second rows; the
    # state's leaves after the last pool
    assert [f.ids for f in forms] == [(0, 3), (6, 7), (1, 4), (2, 5)]
    assert n_pools == 6
    assert [f.layer for f in forms] == [0, 1, 2, 3]
    assert [f.keeps_state for f in forms] == [False, True, False, False]
    assert [f.select for f in forms] == [None, None, 3, None]
    assert forms[2].window == 6 and forms[0].window is None
    assert (forms[0].scale, forms[3].scale) == (None, 0.25)
    assert forms[0].no_verify is None
    assert "latent" in forms[2].no_verify and "state" in forms[1].no_verify
    with pytest.raises(MXNetError, match="no speculation over a latent"):
        forms[2].verify_attend([], None, None, None, None, False)


def test_each_kind_allocates_what_it_states():
    lay = _mixed()
    forms, _ = cache_forms(lay, [None] * 4, BS, NB, None)
    cpu = jax.devices("cpu")[0]
    stored = (N, BS, 2, 128)
    got = [f.allocate(N, cpu, stored, 11) for f in forms]
    assert got[0] == got[3] == [(stored, "float32")] * 2
    assert got[1] == [((11, 4, 5), "float32"), ((11, 3), "int32")]
    assert got[2] == [((N, BS, 24), "bfloat16"), ((N, BS, 8), "float32")]
    # the K/V pools' shape is the layout's rule, asked only where a layer
    # keeps K and V
    assert kvcache.grouped_pool_shape(lay, forms, N, BS, cpu) \
        == ((N, 2, BS, 16), False)
    assert kvcache.grouped_pool_shape(lay, forms[1:3], N, BS, cpu) \
        == (None, False)
    assert kvcache.pool_layout(lay, N, BS, (N, 2, BS, 16), False) \
        == "default"
    assert kvcache.pool_layout(lay, N, BS, stored, True) == {
        "stored": "position_major", "shape": list(stored),
        "stated": [N, 2, BS, 16]}


@pytest.mark.parametrize("place", ["suffix", "step", "verify"])
def test_a_grouped_layer_attends_alike_over_either_stored_order(place):
    """Each of the three places a program reads the cache, the K/V pools as
    stated and position-major: the same attention, the same values cached,
    and the engine told what was picked (the gather, on the CPU)."""
    S, Hq, D, Q = 2, 2, 16, 3
    tables = np.stack([TABLE, TABLE[::-1]]).astype(np.int32)
    out, cached = {}, {}
    for name in ("stated", "position_major"):
        st = Stored(name)
        # something cached already: positions 0..7 of both slots' tables
        caches = list(st.pools)
        for s in range(S):
            st.form.write_prompt(
                caches, [jnp.asarray(r) for r in st.rows(
                    np.random.default_rng(10 + s), 2 * BS)],
                jnp.asarray(tables[s]), 0, False, st.position_major)
        r = np.random.default_rng(4)
        if place == "suffix":
            q, k, v = (jnp.asarray(r.standard_normal((1, BS, Hq, D)),
                                   jnp.float32) for _ in range(3))
            attend = st.form.suffix_attend(
                caches, jnp.asarray(tables[0]), jnp.int32(2 * BS),
                jnp.int32(2), BS, st.position_major)
        else:
            width = 1 if place == "step" else Q
            q, k, v = (jnp.asarray(r.standard_normal((S, width, Hq, D)),
                                   jnp.float32) for _ in range(3))
            positions = jnp.asarray([8, 9], jnp.int32)
            pos = np.array([8, 9])[:, None] + np.arange(width)
            blk = jnp.asarray(tables[np.arange(S)[:, None], pos // BS])
            off = jnp.asarray(pos % BS, jnp.int32)
            if place == "step":
                blk, off = blk[:, 0], off[:, 0]
            attend = getattr(st.form, place + "_attend")(
                caches, blk, off, jnp.asarray(tables), positions,
                st.position_major)
        out[name] = np.asarray(attend(q, k, v))
        cached[name] = [st.view(c, (2, D)) for c in caches]
        assert out[name].shape == q.shape
        assert st.notes == ([] if place == "suffix"
                            else [(0, "lax_gather", None)])
    np.testing.assert_allclose(out["stated"], out["position_major"],
                               rtol=1e-6, atol=1e-6)
    for a, b in zip(cached["stated"], cached["position_major"]):
        np.testing.assert_array_equal(a, b)


def test_the_engine_knows_neither_a_pools_order_nor_a_layers_kind():
    """The decision has one owner: the engine's text holds no test of a
    pool's rank, none of the layout's per-kind tuples, no latent attention
    and none of the paged entry points — it calls a layer's form."""
    import incubator_mxnet_tpu.serving.engine as engine
    src = open(os.path.splitext(engine.__file__)[0] + ".py").read()
    for gone in ("pool.ndim", "layout.rows", "layout.selects",
                 "layout.states", "latent_attention",
                 "paged_prefix_attention", "paged_decode_attention",
                 "paged_verify_decode_attention", "if self._position_major",
                 "if not self._position_major"):
        assert gone not in src, gone
