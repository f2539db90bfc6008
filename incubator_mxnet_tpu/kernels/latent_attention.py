"""Latent (multi-head latent, "MLA") attention over the paged cache, with
the learned index that picks the keys a query reads.

A latent layer keeps ONE row a cached position, ``[c | k_r]``: the
normalised key-value latent ``c`` (``r`` features) and the rotary key
``k_r`` (``d_r`` features) all its heads share.  A head's keys and values
are linear in it, ``k_n,h = c W_uk,h`` and ``v_h = c W_uv,h``, which gives
one layer two forms of the same attention:

* **unabsorbed** (a prompt: many queries, :func:`latent_prompt_attention`)
  — expand each cached row to its heads' keys and values once, then plain
  multi-head attention of width ``d_n + d_r`` over values of width ``d_v``;
* **absorbed** (a decode step: one query a slot,
  :func:`absorbed_attention`) — fold ``W_uk`` into the query,
  ``q~_h = q_n,h W_uk,h^T``, so that every head reads the SAME row:
  multi-query attention of ``H`` heads of width ``r + d_r`` whose values
  are the row's first ``r`` features (a page is fetched once and is key
  and value both), and ``W_uv`` is applied to the result.

An indexed layer keeps a second, narrow row a position, the index key
``k_I``.  A query scores every cached index key, ``I(t, s) = sum_j w_j(t)
relu(q_I,j(t) . k_I(s))``, and attends over exactly the ``k`` positions of
largest score (all of them while there are no more than ``k``).  The
choice is EXACT — :func:`choose_topk`'s set, lowest position first among
equals — never ``approx_max_k``; and it is a SET: nothing reads its
order, so no path sorts scores.  The ``k``-th largest is found by
counting passes over the scores' order keys (:func:`_kth_key`), ties at
it settled by position (:func:`_take_ties`); a prompt attends under the
mask (:func:`chosen_mask`), a decode step compacts it into the chosen
positions' pool rows (:func:`paged_index_select`).

The paged entry points (``paged_*``) read the block pool through a slot's
table as ``kernels/flash_attention.py``'s do; a pool of rows is stored
``[N, bs, Fp]`` (``serving.kvcache.KVLayout.row_pool_shape``).  On a TPU
the window-bounded decode read, the index scoring and the decode step's
choice are Pallas kernels (:func:`latent_decode_impl`,
:func:`index_select_impl` and :func:`prompt_index_impl` say which a call
takes); the read of the chosen rows is lax, and every kernel has a lax
form that is the CPU path and its reference.
"""
from __future__ import annotations

import functools
import importlib

import jax
import jax.numpy as jnp

# the module, not the function of that name the package exports: the one
# place the platform is read, so that one patch steers every kernel
_fa = importlib.import_module(__package__ + ".flash_attention")

__all__ = ["absorbed_attention", "latent_prompt_attention", "index_scores",
           "choose_topk", "chosen_mask", "paged_latent_decode",
           "paged_index_select", "paged_sparse_latent",
           "latent_decode_impl", "index_select_impl", "prompt_index_impl"]

#: bytes of float32 scores one tile of a prompt's queries may take
_TILE_BYTES = 2 ** 28


# ---------------------------------------------------------------------------
# the index: scores, and the exact choice of the k largest
# ---------------------------------------------------------------------------

def index_scores(q_i, w_i, k_i):
    """``q_i`` (..., Q, HI, dI), ``w_i`` (..., Q, HI) float32, ``k_i``
    (..., K, dI) -> float32 (..., Q, K): ``sum_j w_j relu(q_j . k)``.  The
    product takes its operands in the keys' type and accumulates in
    float32; ReLU, weights and the sum over index heads are float32."""
    s = jnp.einsum("...qhd,...kd->...qhk", q_i.astype(k_i.dtype), k_i,
                   preferred_element_type=jnp.float32)
    return jnp.sum(jax.nn.relu(s) * w_i.astype(jnp.float32)[..., None],
                   axis=-2)


def choose_topk(scores, k):
    """The ``k`` positions of largest ``scores`` (..., K) float32 — fewer
    where fewer are finite (``-inf`` marks a key the query may not read)
    — as ``(idx (..., k'), valid (..., k'))``, ``k' = min(k, K)``.
    Exact; among equal scores the lowest position first."""
    vals, idx = jax.lax.top_k(scores, min(int(k), scores.shape[-1]))
    return idx, vals > -jnp.inf


def _signed_key(x):
    """float32 -> int32 whose signed order is the floats' (``-inf``
    least)."""
    b = jax.lax.bitcast_convert_type(x, jnp.int32)
    return jnp.where(b < 0, b ^ jnp.int32(0x7FFFFFFF), b)


def _order_key(x):
    """float32 -> uint32 whose unsigned order is the floats' (``-inf``
    least)."""
    return jax.lax.bitcast_convert_type(_signed_key(x), jnp.uint32) \
        ^ jnp.uint32(0x80000000)


def _kth_key(count_ge, k, zero):
    """The largest order key ``t`` — the bits of a uint32, held in
    ``zero``'s integer type and shape — that ``k`` or more keys reach
    (``count_ge(t) >= k``): found bit by bit from the top, 32 counting
    passes.  The one threshold search of the prompt's mask and the decode
    step's choice; ``zero`` may be a tuple (searches side by side, one
    loop), which ``count_ge`` then takes and returns."""
    def bit(i, t):
        cand = jax.tree.map(lambda t: t | jax.lax.shift_left(
            jnp.ones_like(t), (31 - i).astype(t.dtype)), t)
        return jax.tree.map(
            lambda n, cand, t: jnp.where(n >= int(k), cand, t),
            count_ge(cand), cand, t)
    return jax.lax.fori_loop(0, 32, bit, zero)


def _take_ties(above, tied, rank, room):
    """The one tie rule: every key ``above`` the threshold, and of those
    ``tied`` at it the first ``room`` in position order (``rank``: a tied
    key's count among the tied up to and with itself)."""
    return above | (tied & (rank <= room))


def chosen_mask(scores, k):
    """:func:`choose_topk`'s set as a mask over the keys, (..., K) bool —
    what a prompt's dense attention runs under, and the lax form of a
    decode step's choice.  A mask needs the ``k``-th largest score, not
    the order of the others: it is found bit by bit (:func:`_kth_key`,
    where a sort of a prompt's ``(queries, keys)`` rows costs several
    times as much), and equal scores at the threshold are taken lowest
    position first."""
    K = scores.shape[-1]
    finite = scores > -jnp.inf
    if int(k) >= K:
        return finite
    u = _order_key(scores)
    thr = _kth_key(lambda c: jnp.sum(u >= c, axis=-1, keepdims=True), k,
                   jnp.zeros(scores.shape[:-1] + (1,), jnp.uint32))
    above = u > thr
    tied = (u == thr) & finite
    room = int(k) - jnp.sum(above, axis=-1, keepdims=True)
    return jax.lax.cond(
        jnp.any(jnp.sum(tied, axis=-1, keepdims=True) > room),
        lambda: _take_ties(above, tied, jnp.cumsum(tied, axis=-1), room),
        lambda: above | tied)


# ---------------------------------------------------------------------------
# the two forms
# ---------------------------------------------------------------------------

def _rows_attention(q_abs, rows, live, r_kv, scale):
    """Absorbed queries ``q_abs`` (S, H, F) over each slot's own ``rows``
    (S, K, Fp >= F) under ``live`` (S, K): float32 (S, H, r_kv).  Scores
    and softmax in float32, both products with the rows in their own
    type; the value is the row's first ``r_kv`` features."""
    F, Fp = q_abs.shape[-1], rows.shape[-1]
    q = q_abs.astype(rows.dtype)
    if Fp != F:                  # the rows' lanes past F hold zeros
        q = jnp.pad(q, ((0, 0), (0, 0), (0, Fp - F)))
    s = jnp.einsum("shf,skf->shk", q, rows,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(live[:, None, :], s, -1e30)
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    p = jnp.where(live[:, None, :], p, 0.0)
    p = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    return jnp.einsum("shk,skr->shr", p.astype(rows.dtype),
                      rows[..., :r_kv], preferred_element_type=jnp.float32)


def absorbed_attention(q_n, q_r, w_uk, w_uv, attend_rows):
    """The absorbed form around a read of the cache: ``q_n`` (S, H, d_n),
    ``q_r`` (S, H, d_r), ``w_uk`` (r, H, d_n), ``w_uv`` (r, H, d_v);
    ``attend_rows(q_abs (S, H, r + d_r)) -> (S, H, r)`` float32 is the
    multi-query attention over the cached rows.  Returns (S, H, d_v) in
    ``q_n``'s type."""
    dt = q_n.dtype
    q_c = jnp.einsum("shn,rhn->shr", q_n, w_uk,
                     preferred_element_type=jnp.float32).astype(dt)
    o = attend_rows(jnp.concatenate([q_c, q_r], axis=-1))
    return jnp.einsum("shr,rhv->shv", o.astype(dt), w_uv,
                      preferred_element_type=jnp.float32).astype(dt)


def _query_tile(T, K, heads):
    """Queries a tile: the largest power of two that divides ``T`` and
    keeps a tile's float32 scores — ``heads`` x tile x ``K`` — under
    :data:`_TILE_BYTES` (at least 8, or all of a short prompt)."""
    cap = max(8, _TILE_BYTES // (4 * int(K) * int(heads)))
    tile = 1
    while tile * 2 <= min(cap, T) and T % (tile * 2) == 0:
        tile *= 2
    return tile if tile >= 8 else T


def latent_prompt_attention(q_n, q_r, rows, q_pos, key_pos, w_uk, w_uv,
                            scale, window=None, select=None):
    """The unabsorbed form, for a prompt's queries over one sequence's
    rows: ``q_n`` (T, H, d_n), ``q_r`` (T, H, d_r), ``rows`` (K, Fp) the
    cached ``[c | k_r | zeros]`` of the keys at positions ``key_pos``
    (K,), ``q_pos`` (T,) the queries' positions, ``w_uk`` (r, H, d_n),
    ``w_uv`` (r, H, d_v).  Query ``t`` reads keys ``s <= t`` — with a
    ``window`` those with ``t - window < s`` only — and with ``select =
    (q_i (T, HI, dI), w_i (T, HI), k_i (K, dI), k)`` only the ``k`` of
    them its index scores highest (:func:`chosen_mask`).  Returns (T, H,
    d_v) in ``q_n``'s type.

    Every row is expanded to its heads' keys and values ONCE; queries are
    taken a tile at a time (:func:`_query_tile`), the index scores of a
    tile with its attention, so that no (T, K) array a head is ever
    whole."""
    dt = q_n.dtype
    T, H, _ = q_n.shape
    r, d_r = w_uk.shape[0], q_r.shape[-1]
    c = rows[:, :r].astype(dt)
    k_n = jnp.einsum("kr,rhn->khn", c, w_uk,
                     preferred_element_type=jnp.float32).astype(dt)
    v = jnp.einsum("kr,rhv->khv", c, w_uv,
                   preferred_element_type=jnp.float32).astype(dt)
    k_r = rows[:, r:r + d_r].astype(dt)
    K = rows.shape[0]

    def tile(args):
        qn, qr, qp, sel = args
        live = key_pos[None, :] <= qp[:, None]               # (tq, K)
        if window is not None:
            live = live & (key_pos[None, :] > qp[:, None] - int(window))
        if select is not None:
            with jax.named_scope("attn.index"):
                scores = sel[0] if len(sel) == 1 \
                    else index_scores(sel[0], sel[1], select[2])
                live = chosen_mask(jnp.where(live, scores, -jnp.inf),
                                   select[3])
        s = (jnp.einsum("qhn,khn->hqk", qn, k_n,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("qhd,kd->hqk", qr, k_r,
                          preferred_element_type=jnp.float32)) * scale
        s = jnp.where(live[None], s, -1e30)
        p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(live[None], p, 0.0)
        p = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
        return jnp.einsum("hqk,khv->qhv", p.astype(dt), v,
                          preferred_element_type=jnp.float32).astype(dt)

    sel, heads = None, H
    if select is not None and prompt_index_impl(select[0], select[2]) \
            == "pallas":
        with jax.named_scope("attn.index"):     # every query's, at once
            sel = (_index_scores_pallas(
                select[0], select[1].astype(jnp.float32), select[2],
                _fa._platform_of(select[0]) != "tpu"),)
    elif select is not None:        # a tile's scores with its attention
        sel, heads = (select[0], select[1]), max(H, select[0].shape[1])
    tq = _query_tile(T, K, heads)
    if tq == T:
        return tile((q_n, q_r, q_pos, sel))

    def cut(a):
        return a.reshape((T // tq, tq) + a.shape[1:])

    out = jax.lax.map(tile, jax.tree.map(cut, (q_n, q_r, q_pos, sel)))
    return out.reshape((T,) + out.shape[2:])


# ---------------------------------------------------------------------------
# the paged reads of a decode step: one query a slot
# ---------------------------------------------------------------------------

def _window_columns(tables, positions, bs, window):
    """The table columns a slot's window can touch, ``(cols (S, n),
    real (S, n))``: from the column of ``position - window + 1`` on, as
    many as a window spans wherever it starts; ``real`` is False for one
    past the table."""
    n_cols = tables.shape[1]
    n = min(n_cols, (int(window) + bs - 2) // bs + 1)
    first = jnp.maximum(positions - int(window) + 1, 0) // bs
    cols = first[:, None] + jnp.arange(n, dtype=jnp.int32)[None, :]
    return jnp.minimum(cols, n_cols - 1), cols < n_cols


def _xla_paged_latent_decode(q_abs, pool, tables, positions, r_kv, scale,
                             window):
    """The lax gather: each slot's blocks — those its window can touch,
    where it has one — as a dense strip of rows."""
    S = q_abs.shape[0]
    _, bs, Fp = pool.shape
    if window is None:
        cols = jnp.broadcast_to(
            jnp.arange(tables.shape[1], dtype=jnp.int32)[None],
            tables.shape)
        real = jnp.ones(tables.shape, bool)
    else:
        cols, real = _window_columns(tables, positions, bs, window)
    rows = pool[jnp.take_along_axis(tables, cols, axis=1)]  # (S, n, bs, Fp)
    key = (cols[:, :, None] * bs
           + jnp.arange(bs, dtype=jnp.int32)[None, None, :])
    live = real[:, :, None] & (key <= positions[:, None, None])
    if window is not None:
        live = live & (key > positions[:, None, None] - int(window))
    return _rows_attention(q_abs, rows.reshape(S, -1, Fp),
                           live.reshape(S, -1), r_kv, scale)


def latent_decode_impl(q, pool):
    """Which implementation :func:`paged_latent_decode` traces for a call
    with operand ``q`` (it names the platform) over the row ``pool`` (N,
    bs, Fp): ``"pallas"`` on a TPU — and wherever
    ``MXNET_FA_DECODE_FORCE_PALLAS=1`` asks, interpreted — for a bfloat16
    or float32 pool whose page is whole tiles (``Fp`` a multiple of 128,
    ``bs`` of 16 or 8), else ``"lax_gather"``."""
    from ..base import getenv_bool
    if _fa._platform_of(q) != "tpu" \
            and not getenv_bool("MXNET_FA_DECODE_FORCE_PALLAS"):
        return "lax_gather"
    _, bs, Fp = pool.shape
    whole = (pool.dtype == jnp.float32 and bs % 8 == 0) \
        or (pool.dtype == jnp.bfloat16 and bs % 16 == 0)
    return "pallas" if whole and Fp % 128 == 0 else "lax_gather"


def paged_latent_decode(q_abs, pool, tables, positions, r_kv, scale,
                        window=None):
    """A decode step's read of a latent pool: absorbed queries ``q_abs``
    (S, H, F) over the rows ``pool`` (N, bs, Fp) holds for each slot's
    positions ``<= positions[s]`` (and ``> positions[s] - window``),
    through ``tables`` (S, max_blocks).  Every page is read ONCE and is
    key and value both.  Returns float32 (S, H, r_kv).  ``positions`` may
    ride a scan's carry: every implementation masks by comparison."""
    positions = positions.astype(jnp.int32)
    if latent_decode_impl(q_abs, pool) == "pallas":
        return _paged_latent_pallas(
            q_abs, pool, tables.astype(jnp.int32), positions, int(r_kv),
            float(scale), None if window is None else int(window),
            _fa._platform_of(q_abs) != "tpu")
    return _xla_paged_latent_decode(q_abs, pool, tables, positions, r_kv,
                                    scale, window)


def index_select_impl(q, pool):
    """Which implementation :func:`paged_index_select` traces for a call
    with operand ``q`` over the index-key ``pool`` (N, bs, dIp):
    ``"select:kernel"`` on a TPU (and where
    ``MXNET_FA_DECODE_FORCE_PALLAS=1`` asks, interpreted) for a pool
    whose page is whole tiles of 16 to 128 positions — the pages
    scored in place (:func:`_paged_index_pallas`), then the threshold's
    counting passes and the compaction by rank over a slot's resident
    scores (:func:`_index_choose_pallas`); else ``"select:lax"`` — the
    slot's strip gathered, :func:`chosen_mask`, and one single-operand
    sort of the chosen positions' pool rows.  No form orders scores."""
    N, bs, _ = pool.shape
    # a 128-position group's table entries, three base-256 digits each,
    # must fit the choice kernel's small operand
    ok = latent_decode_impl(q, pool) == "pallas" and 128 % bs == 0 \
        and 3 * (128 // bs) <= _CHOOSE_AUX_ROWS - 2 and N < 2 ** 24
    return "select:kernel" if ok else "select:lax"


def _xla_paged_index_scores(q_i, w_i, pool, tables):
    S, n_cols = tables.shape
    _, bs, dIp = pool.shape
    keys = pool[tables].reshape(S, n_cols * bs, dIp)
    return index_scores(q_i[:, None], w_i[:, None], keys)[:, 0]


def _pool_rows(tables, bs):
    """(S, n_cols * bs): where each position of a slot's table lies in a
    pool taken as ``[N * bs, F]``."""
    return (tables[:, :, None] * bs + jnp.arange(
        bs, dtype=jnp.int32)[None, None, :]).reshape(tables.shape[0], -1)


def _xla_index_choose(scores, tables, positions, bs, k):
    """The lax form of the choice: :func:`chosen_mask` over the written
    positions, then the mask's pool rows to the front by ONE sort of one
    operand — the row is the key, nothing rides along and nothing needs
    stability (the mask settled the ties)."""
    S, K = scores.shape
    live = jnp.arange(K, dtype=jnp.int32)[None, :] <= positions[:, None]
    mask = chosen_mask(jnp.where(live, scores, -jnp.inf), k)
    rows = jax.lax.sort(
        jnp.where(mask, _pool_rows(tables, bs), jnp.iinfo(jnp.int32).max),
        dimension=1, is_stable=False)[:, :k]
    valid = jnp.arange(k, dtype=jnp.int32)[None, :] \
        < jnp.sum(mask, axis=-1, keepdims=True)
    return jnp.where(valid, rows, 0), valid


def paged_index_select(q_i, w_i, pool, tables, positions, k):
    """A decode step's choice: index queries ``q_i`` (S, HI, dI) and
    weights ``w_i`` (S, HI) against every index key ``pool`` (N, bs, dIp)
    holds for the slot's positions ``<= positions[s]``; the ``k`` of
    largest score as ``(rows (S, k'), valid (S, k'))``: where each chosen
    position's row lies in a pool taken as ``[N * bs, F]`` (``table[p //
    bs] * bs + p % bs``), which is what :func:`paged_sparse_latent`
    reads.  :func:`choose_topk`'s SET — exact, lowest position first
    among equals — in no stated order (attention over it is a sum): the
    ``k``-th largest score by counting passes, then a compaction of the
    mask (:func:`index_select_impl` says by which code); no score is ever
    sorted.  While a table holds no more than ``k`` positions every
    written one is chosen and nothing is counted."""
    S, n_cols = tables.shape
    _, bs, dIp = pool.shape
    dI = q_i.shape[-1]
    K = n_cols * bs
    positions = positions.astype(jnp.int32)
    tables = tables.astype(jnp.int32)
    if int(k) >= K:
        return _pool_rows(tables, bs), jnp.arange(
            K, dtype=jnp.int32)[None, :] <= positions[:, None]
    if dIp != dI:
        q_i = jnp.pad(q_i, ((0, 0), (0, 0), (0, dIp - dI)))
    if index_select_impl(q_i, pool) == "select:kernel":
        interpret = _fa._platform_of(q_i) != "tpu"
        scores = _paged_index_pallas(q_i, w_i.astype(jnp.float32), pool,
                                     tables, positions, interpret)
        return _index_choose_pallas(scores, tables, positions, int(bs),
                                    int(k), interpret)
    return _xla_index_choose(_xla_paged_index_scores(q_i, w_i, pool, tables),
                             tables, positions, bs, int(k))


def paged_sparse_latent(q_abs, pool, rows, valid, r_kv, scale):
    """A decode step's read of the CHOSEN rows: absorbed queries ``q_abs``
    (S, H, F) over the rows of ``pool`` (N, bs, Fp) that ``rows`` (S, k)
    names (:func:`paged_index_select`) where ``valid``; float32 (S, H,
    r_kv).  A row gather — ``k`` rows a slot, not the slot's strip."""
    N, bs, Fp = pool.shape
    chosen = pool.reshape(N * bs, Fp)[jnp.where(valid, rows, 0)]
    return _rows_attention(q_abs, chosen, valid, r_kv, scale)


# ---------------------------------------------------------------------------
# the Pallas kernel: window-bounded multi-query attention over ONE pool
# ---------------------------------------------------------------------------

def _page_fetch(slot_ref, group_ref, page_ref, run_ref, pos_ref, pool_hbm,
                buf, sem, n_pages, run_pages, n_cols, bs):
    """``fetch(t, op)`` of a kernel over ONE row pool ``pool_hbm`` (``[N *
    bs, Fp]``, left where it rests): start (``op`` "start") or await
    ("wait") the copies of work-list step ``t`` into half ``t % 2`` of
    ``buf`` (``[2, n_pages * bs, Fp]``) — ``kernels.flash_attention.
    _paged_gqa_kernel``'s, with one pool: where ``run_ref`` says a step's
    blocks lie in a row, ONE copy of ``n_pages`` blocks; where a group's
    do, one of ``run_pages``; else a copy a live column; nothing for a
    group past the write head."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    n_runs = n_pages // run_pages

    def fetch(t, op):
        half = jax.lax.rem(t, 2)

        def copy(src, dst, rows):
            getattr(pltpu.make_async_copy(
                pool_hbm.at[pl.ds(pl.multiple_of(src, bs), rows)],
                buf.at[half, pl.ds(dst, rows)], sem.at[half]), op)()

        @pl.when(run_ref[t * n_runs] == 2)
        def _step():
            copy(page_ref[t * n_pages] * bs, 0, n_pages * bs)

        @pl.when(run_ref[t * n_runs] != 2)
        def _groups():
            live = jnp.minimum(pos_ref[slot_ref[t]] // bs, n_cols - 1) \
                - group_ref[t] * n_pages + 1
            for u in range(n_runs):
                c, run = u * run_pages, run_ref[t * n_runs + u]

                @pl.when((run == 1) & (c < live))
                def _run(c=c):
                    copy(page_ref[t * n_pages + c] * bs, c * bs,
                         run_pages * bs)

                @pl.when(run == 0)
                def _blocks(c=c):
                    def block(j, _):
                        copy(page_ref[t * n_pages + j] * bs,
                             pl.multiple_of(j * bs, bs), bs)

                    jax.lax.fori_loop(c, jnp.minimum(c + run_pages, live),
                                      block, None)
    return fetch


def _paged_latent_kernel(slot_ref, group_ref, page_ref, run_ref, pos_ref,
                         steps_ref, q_ref, pool_hbm, o_ref, buf, sem,
                         acc_ref, m_ref, l_ref, *, scale, n_pages,
                         run_pages, n_cols, bs, r_kv, window):
    """``kernels.flash_attention._paged_gqa_kernel`` for a latent pool: one
    KV "head" whose page ``[bs, Fp]`` is fetched ONCE into ``buf`` and read
    twice from there — all of it as the keys, its first ``r_kv`` features
    as the values.  Work items, run flags, the hand-made double-buffered
    copies (:func:`_page_fetch`) and the online softmax are that
    kernel's."""
    from jax.experimental import pallas as pl
    i = pl.program_id(0)
    g = group_ref[i]
    pos = pos_ref[slot_ref[i]]
    R = q_ref.shape[1]
    T = n_pages * bs
    n_keys = n_cols * bs
    first = 0 if window is None \
        else jnp.maximum(pos - window + 1, 0) // T

    fetch = _page_fetch(slot_ref, group_ref, page_ref, run_ref, pos_ref,
                        pool_hbm, buf, sem, n_pages, run_pages, n_cols, bs)

    @pl.when(i == 0)
    def _zero():
        buf[...] = jnp.zeros_like(buf)

    jax.lax.fori_loop(jnp.where(i == 0, 0, i + 1),
                      jnp.minimum(i + 2, steps_ref[0]),
                      lambda t, _: fetch(t, "start"), None)
    fetch(i, "wait")

    @pl.when(g == first)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    idx = g * T + jax.lax.broadcasted_iota(jnp.int32, (R, T), 1)
    live = idx <= jnp.minimum(pos, n_keys - 1)
    if window is not None:
        live = live & (idx > pos - window)
    page = buf[jax.lax.rem(i, 2)]                               # (T, Fp)
    s = jax.lax.dot_general(
        q_ref[0].astype(page.dtype), page, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale             # (R, T)
    s = jnp.where(live, s, -1e30)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.where(live, jnp.exp(s - m_new), 0.0)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p.astype(page.dtype), page[:, :r_kv], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                     # (R, r_kv)
    m_ref[...] = m_new

    @pl.when((g + 1) * T > jnp.minimum(pos, n_keys - 1))
    def _fin():
        o_ref[0] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)


@functools.partial(jax.jit, static_argnames=("r_kv", "scale", "window",
                                             "interpret"))
def _paged_latent_pallas(q_abs, pool, tables, positions, r_kv, scale,
                         window, interpret):
    """``q_abs`` (S, H, F) over the row pool ``[N, bs, Fp]`` — taken as
    ``[N * bs, Fp]``, the same bytes, left where it rests.  The grouped
    kernel's work list with one KV head (:func:`_paged_work_list`: 512
    keys a step, ONE copy where the table names a step's blocks in a
    row), bounded from below by ``window``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    S, H, F = q_abs.shape
    N, bs, Fp = pool.shape
    n_cols = tables.shape[1]
    run_pages, n_runs = _fa._paged_gqa_step(bs, n_cols)
    n_pages = run_pages * n_runs
    n_steps, slot, group, page, run = _fa._paged_work_list(
        tables, positions, 1, bs, n_pages, window, runs=(run_pages, N))
    Rp = -(-H // 16) * 16               # whole tiles of either type
    rows = jnp.pad(q_abs.astype(jnp.float32),
                   ((0, 0), (0, Rp - H), (0, Fp - F)))
    spec_q = pl.BlockSpec((1, Rp, Fp), lambda i, slot, *_: (slot[i], 0, 0))
    spec_o = pl.BlockSpec((1, Rp, r_kv),
                          lambda i, slot, *_: (slot[i], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(n_steps,),
        in_specs=[spec_q, pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=spec_o,
        scratch_shapes=[
            pltpu.VMEM((2, n_pages * bs, Fp), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((Rp, r_kv), jnp.float32),
            pltpu.VMEM((Rp, 1), jnp.float32),
            pltpu.VMEM((Rp, 1), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _paged_latent_kernel, scale=scale, n_pages=n_pages,
        run_pages=run_pages, n_cols=n_cols, bs=bs, r_kv=r_kv,
        window=window)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, Rp, r_kv), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=32 * 2 ** 20),
        interpret=interpret,
    )(slot, group, page, run, positions, jnp.reshape(n_steps, (1,)), rows,
      pool.reshape(N * bs, Fp))
    return out[:, :H]


# ---------------------------------------------------------------------------
# the Pallas kernel of the index: scores of a slot's paged index keys
# ---------------------------------------------------------------------------

#: index keys one step of the scoring kernel takes, and the keys of one
#: of its runs (a copy each where the table names the blocks in a row): a
#: step costs about the same whatever it reads, and a slot has ~26 k keys
_INDEX_STEP_KEYS = 4096
_INDEX_RUN_KEYS = 1024


def _index_step(bs, n_cols):
    """``(pages a run, runs a step)`` of the scoring kernel's work list."""
    pages = min(max(1, _INDEX_RUN_KEYS // int(bs)), int(n_cols))
    return pages, min(_INDEX_STEP_KEYS // _INDEX_RUN_KEYS,
                      -(-int(n_cols) // pages))


def _paged_index_kernel(slot_ref, group_ref, page_ref, run_ref, pos_ref,
                        steps_ref, q_ref, w_ref, pool_hbm, o_ref, buf, sem,
                        *, n_pages, run_pages, n_cols, bs):
    """One step of :func:`_paged_index_pallas`'s work list: the ``HI``
    index queries of slot ``slot_ref[i]`` against its ``group_ref[i]``-th
    ``n_pages`` pages of index keys, fetched by :func:`_page_fetch`: ``q
    k^T`` on the MXU in the pool's type, then ReLU, the heads' weights and
    the sum over heads in float32 on the step's ``(HI, T)`` tile — the
    ``(slots, HI, keys)`` scores never reach memory.  A row past the write
    head scores whatever the buffer held: the caller masks by position."""
    from jax.experimental import pallas as pl
    i = pl.program_id(0)
    fetch = _page_fetch(slot_ref, group_ref, page_ref, run_ref, pos_ref,
                        pool_hbm, buf, sem, n_pages, run_pages, n_cols, bs)

    @pl.when(i == 0)
    def _zero():
        buf[...] = jnp.zeros_like(buf)

    jax.lax.fori_loop(jnp.where(i == 0, 0, i + 1),
                      jnp.minimum(i + 2, steps_ref[0]),
                      lambda t, _: fetch(t, "start"), None)
    fetch(i, "wait")
    keys = buf[jax.lax.rem(i, 2)]                               # (T, dIp)
    s = jax.lax.dot_general(
        q_ref[0].astype(keys.dtype), keys, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                     # (HI, T)
    o_ref[0] = jnp.sum(jnp.maximum(s, 0.0) * w_ref[0], axis=0,
                       keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _paged_index_pallas(q_i, w_i, pool, tables, positions, interpret):
    """Index scores float32 (S, max_blocks * bs) of ``q_i`` (S, HI, dIp),
    ``w_i`` (S, HI) over the index-key pool ``[N, bs, dIp]`` — taken as
    ``[N * bs, dIp]``, left where it rests — for the groups of each slot's
    table up to its write head; what lies past it is not written (the
    caller masks by position)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    S, HI, dIp = q_i.shape
    N, bs, _ = pool.shape
    n_cols = tables.shape[1]
    run_pages, n_runs = _index_step(bs, n_cols)
    n_pages = run_pages * n_runs
    n_groups = -(-n_cols // n_pages)
    T = n_pages * bs
    n_steps, slot, group, page, run = _fa._paged_work_list(
        tables, positions, 1, bs, n_pages, None, runs=(run_pages, N))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(n_steps,),
        in_specs=[
            pl.BlockSpec((1, HI, dIp), lambda i, slot, *_: (slot[i], 0, 0)),
            pl.BlockSpec((1, HI, 1), lambda i, slot, *_: (slot[i], 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(
            (1, 1, T), lambda i, slot, group, *_: (
                slot[i] * n_groups + group[i], 0, 0)),
        scratch_shapes=[pltpu.VMEM((2, T, dIp), pool.dtype),
                        pltpu.SemaphoreType.DMA((2,))],
    )
    out = pl.pallas_call(
        functools.partial(_paged_index_kernel, n_pages=n_pages,
                          run_pages=run_pages, n_cols=n_cols, bs=bs),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S * n_groups, 1, T), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=32 * 2 ** 20),
        interpret=interpret,
    )(slot, group, page, run, positions, jnp.reshape(n_steps, (1,)), q_i,
      w_i[..., None], pool.reshape(N * bs, dIp))
    return out.reshape(S, n_groups * T)[:, :n_cols * bs]


# ---------------------------------------------------------------------------
# the Pallas kernel of the choice: threshold and compaction, scores resident
# ---------------------------------------------------------------------------

#: slots one step of the choice kernel takes: their 32 counting passes are
#: independent chains that the scheduler interleaves
_CHOOSE_SLOTS = 8
#: rows of the choice kernel's small operand: base-256 digits of the table
#: entries of a 128-position group (3 x 128 / bs rows), then two rows the
#: kernel fills (the digits of a group's running count)
_CHOOSE_AUX_ROWS = 32

_NEG_INF_KEY = -2139095041          # _signed_key(-inf)
_INT32_MIN = -2 ** 31


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=jnp.float32)


def _group_prefix(m, tri, lower):
    """``m`` (G, 128) of 0 / 1 in bfloat16, position ``g * 128 + l``:
    ``(own, before)`` float32 (G, 128) — the count of ``m`` inside the
    group up to and with lane ``l``, and the count in the groups before
    ``g`` (every lane the same).  Two triangular products on the MXU,
    exact: a group holds at most 128, float32 sums them."""
    own = _dot(m, tri, ((1,), (0,)))
    each = jnp.broadcast_to(own[:, 127:128], own.shape).astype(jnp.bfloat16)
    return own, _dot(lower, each, ((1,), (0,)))


def _index_choose_kernel(pos_ref, s_ref, aux_ref, o_ref, key_ref, *, k, bs):
    """:func:`_index_choose_pallas` for ``_CHOOSE_SLOTS`` slots whose scores
    ``s_ref`` (slots, G, 128) rest in VMEM.  The threshold: the scores'
    order keys (:func:`_signed_key`; a position past the write head is
    ``-inf``) and :func:`_kth_key`'s 32 counting passes, the slots' chains
    side by side; :func:`_take_ties` at the threshold.  The compaction, a
    slot at a time, by RANK and with no sort, gather or scatter: output
    cell ``j`` belongs to the group whose running count spans it (a
    comparison, ``(G, k)``), a one-hot product on the MXU fetches that
    group's lane counts, its running count and its table entries (values
    a bfloat16 holds exactly, float32 sums), and a comparison down the
    lanes finds the cell's position in the group."""
    from jax.experimental import pallas as pl
    n, G, _ = s_ref.shape
    i = pl.program_id(0)
    bf = jnp.bfloat16
    idx = jax.lax.broadcasted_iota(jnp.int32, (G, 128), 0) * 128 \
        + jax.lax.broadcasted_iota(jnp.int32, (G, 128), 1)
    for s in range(n):
        key_ref[s] = _signed_key(
            jnp.where(idx <= pos_ref[i * n + s], s_ref[s], -jnp.inf))

    def count_ge(cands):
        return tuple(
            jnp.sum(jnp.sum((key_ref[s] >= (c ^ _INT32_MIN)).astype(
                jnp.int32), axis=0, keepdims=True), axis=1, keepdims=True)
            for s, c in enumerate(cands))

    thr = _kth_key(count_ge, k, (jnp.zeros((1, 1), jnp.int32),) * n)

    li = jax.lax.broadcasted_iota(jnp.int32, (128, 128), 0)
    lj = jax.lax.broadcasted_iota(jnp.int32, (128, 128), 1)
    tri = (li <= lj).astype(bf)           # [l', l]: l' <= l
    gi = jax.lax.broadcasted_iota(jnp.int32, (G, G), 0)
    gj = jax.lax.broadcasted_iota(jnp.int32, (G, G), 1)
    lower = (gj < gi).astype(bf)          # [g, g']: g' < g
    upper = (gi < gj).astype(bf)          # [g', g]: g' < g
    cells = o_ref.shape[1]                # k, in whole lane tiles
    cell_f = jax.lax.broadcasted_iota(jnp.int32, (1, cells), 1).astype(
        jnp.float32)
    aux_row = jax.lax.broadcasted_iota(jnp.int32, (_CHOOSE_AUX_ROWS, G), 0)
    nb = 128 // bs
    blk_row = jax.lax.broadcasted_iota(jnp.int32, (nb, cells), 0)

    for s in range(n):
        key = key_ref[s]
        t = thr[s] ^ _INT32_MIN
        above = key > t
        tied = (key == t) & (key > _NEG_INF_KEY)
        room = k - jnp.sum(jnp.sum(above.astype(jnp.int32), axis=0,
                                   keepdims=True), axis=1, keepdims=True)
        own, before = _group_prefix(tied.astype(bf), tri, lower)
        m = _take_ties(above, tied, own + before,
                       room.astype(jnp.float32)).astype(bf)
        # the mask's counts, groups down the sublanes (for the one-hot) ...
        own, before = _group_prefix(m, tri, lower)
        lo = before[:, :1]
        hi = lo + own[:, 127:128]
        total = hi[G - 1:G, :]                                  # (1, 1)
        onehot = ((lo <= cell_f) & (cell_f < hi)).astype(bf)    # (G, k)
        # ... and along the lanes (the product's left operand)
        lanes = _dot(tri, m, ((0,), (1,)))                      # (128, G)
        each = jnp.broadcast_to(lanes[127:128, :], (8, G)).astype(bf)
        run = _dot(each, upper, ((1,), (0,)))[:1]               # (1, G)
        run_hi = jnp.floor(run * (1.0 / 256))
        aux = jnp.where(aux_row == _CHOOSE_AUX_ROWS - 2, run_hi,
                        jnp.where(aux_row == _CHOOSE_AUX_ROWS - 1,
                                  run - 256 * run_hi, aux_ref[s]))
        got = _dot(lanes.astype(bf), onehot, ((1,), (0,)))      # (128, k)
        fetched = _dot(aux.astype(bf), onehot, ((1,), (0,)))    # (32, k)
        rank = cell_f - (256 * fetched[_CHOOSE_AUX_ROWS - 2:
                                       _CHOOSE_AUX_ROWS - 1]
                         + fetched[_CHOOSE_AUX_ROWS - 1:])
        lane = jnp.sum((got <= rank).astype(jnp.int32), axis=0,
                       keepdims=True)                           # (1, k)
        entry = fetched[:nb] + 256 * fetched[nb:2 * nb] \
            + 65536 * fetched[2 * nb:3 * nb]                    # (nb, k)
        block = jnp.sum(jnp.where(blk_row == lane // bs, entry, 0.0),
                        axis=0, keepdims=True).astype(jnp.int32)
        o_ref[s:s + 1, :] = jnp.where(cell_f < total,
                                      block * bs + lane % bs, -1)


@functools.partial(jax.jit, static_argnames=("bs", "k", "interpret"))
def _index_choose_pallas(scores, tables, positions, bs, k, interpret):
    """``(rows, valid)`` (S, k) of :func:`paged_index_select` from the
    ``scores`` float32 (S, K) of the positions ``tables`` (S, K / bs) name,
    ``K > k`` and ``128 % bs == 0``.  Positions are taken in groups of 128
    lanes, ``G`` of them (whole sublane tiles: the columns past ``K`` are
    past every write head); a slot's rows come out in position order."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    S, K = scores.shape
    n, nb = _CHOOSE_SLOTS, 128 // bs
    assert 3 * nb <= _CHOOSE_AUX_ROWS - 2 and k <= 2 ** 16, (bs, k)
    cells = -(-k // 128) * 128
    G = -(-K // 1024) * 8
    Sp = -(-S // n) * n
    scores = jnp.pad(scores, ((0, Sp - S), (0, G * 128 - K)))
    positions = jnp.pad(positions, (0, Sp - S))
    # the table entry of a group's b-th block, groups along the lanes
    entries = jnp.pad(tables, ((0, Sp - S), (0, G * nb - tables.shape[1])))
    entries = entries.reshape(Sp, G, nb).transpose(0, 2, 1)     # (Sp, nb, G)
    aux = jnp.concatenate(
        [(entries >> sh) & 255 for sh in (0, 8, 16)]
        + [jnp.zeros((Sp, _CHOOSE_AUX_ROWS - 3 * nb, G), jnp.int32)],
        axis=1).astype(jnp.float32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(Sp // n,),
        in_specs=[
            pl.BlockSpec((n, G, 128), lambda i, pos: (i, 0, 0)),
            pl.BlockSpec((n, _CHOOSE_AUX_ROWS, G), lambda i, pos: (i, 0, 0))],
        out_specs=pl.BlockSpec((n, cells), lambda i, pos: (i, 0)),
        scratch_shapes=[pltpu.VMEM((n, G, 128), jnp.int32)],
    )
    rows = pl.pallas_call(
        functools.partial(_index_choose_kernel, k=k, bs=bs),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Sp, cells), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 * 2 ** 20),
        interpret=interpret,
    )(positions, scores.reshape(Sp, G, 128), aux)[:S, :k]
    return jnp.maximum(rows, 0), rows >= 0


# ---------------------------------------------------------------------------
# the Pallas kernel of a prompt's index: dense scores, never a head's own
# ---------------------------------------------------------------------------

#: queries and keys one step of the prompt's scoring kernel takes: its
#: ``(queries x index heads, keys)`` float32 tile rests in VMEM (4 MB)
_PROMPT_INDEX_QUERIES = 8
_PROMPT_INDEX_KEYS = 2048


def prompt_index_impl(q_i, k_i):
    """Which implementation scores a prompt's queries ``q_i`` (T, HI, dI)
    against the sequence's index keys ``k_i`` (K, dI):
    ``"pallas"`` (:func:`_index_scores_pallas`) on a TPU — and where
    ``MXNET_FA_DECODE_FORCE_PALLAS=1`` asks, interpreted — for whole
    tiles (``dI`` a multiple of 128, ``HI`` of 8, ``T`` of 8), else
    ``"lax"`` (:func:`index_scores`, a tile of queries at a time)."""
    from ..base import getenv_bool
    if _fa._platform_of(q_i) != "tpu" \
            and not getenv_bool("MXNET_FA_DECODE_FORCE_PALLAS"):
        return "lax"
    T, HI, dI = q_i.shape
    ok = dI % 128 == 0 and HI % 8 == 0 and T % _PROMPT_INDEX_QUERIES == 0 \
        and k_i.shape[-1] == dI
    return "pallas" if ok else "lax"


def _index_scores_kernel(q_ref, w_ref, k_ref, o_ref, *, heads):
    """``_PROMPT_INDEX_QUERIES`` queries' ``heads`` index heads — rows
    ``(queries x heads, dI)`` — against a block of keys: ``q k^T`` on the
    MXU, then ReLU, the heads' weights and each query's sum over its heads
    on the float32 tile where it was made."""
    s = jax.lax.dot_general(
        q_ref[...].astype(k_ref.dtype), k_ref[...],
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    s = jnp.maximum(s, 0.0) * w_ref[...]
    for j in range(o_ref.shape[0]):
        o_ref[j:j + 1, :] = jnp.sum(s[j * heads:(j + 1) * heads], axis=0,
                                    keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _index_scores_pallas(q_i, w_i, k_i, interpret):
    """:func:`index_scores` for a prompt: ``q_i`` (T, HI, dI), ``w_i`` (T,
    HI) float32, ``k_i`` (K, dI) -> float32 (T, K).  The keys are taken a
    block at a time (outer axis: each is fetched once), the queries eight
    at a time; the ``(T, HI, K)`` scores never reach memory."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    T, HI, dI = q_i.shape
    K = k_i.shape[0]
    tq = _PROMPT_INDEX_QUERIES
    tk = min(_PROMPT_INDEX_KEYS, -(-K // 128) * 128)
    Kp = -(-K // tk) * tk
    keys = jnp.pad(k_i, ((0, Kp - K), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_index_scores_kernel, heads=HI),
        grid=(Kp // tk, T // tq),
        in_specs=[pl.BlockSpec((tq * HI, dI), lambda k, q: (q, 0)),
                  pl.BlockSpec((tq * HI, 1), lambda k, q: (q, 0)),
                  pl.BlockSpec((tk, dI), lambda k, q: (k, 0))],
        out_specs=pl.BlockSpec((tq, tk), lambda k, q: (q, k)),
        out_shape=jax.ShapeDtypeStruct((T, Kp), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=48 * 2 ** 20),
        interpret=interpret,
    )(q_i.reshape(T * HI, dI), w_i.reshape(T * HI, 1), keys)
    return out[:, :K]
